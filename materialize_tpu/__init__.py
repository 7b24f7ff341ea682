"""materialize_tpu: a TPU-native incremental view maintenance framework.

A brand-new framework with the capabilities of Materialize (reference:
/root/reference, imotai/materialize): ingest change streams, plan SQL into
dataflow plans, and incrementally maintain materialized views / indexes over
``(data, time, diff)`` update collections — but with the compute data plane
expressed as JAX/XLA kernels running SPMD over a TPU mesh instead of
timely/differential dataflow on CPU threads.

Layer map (mirrors SURVEY.md §1):

- ``repr``        — columnar data representation (Row/Datum analog: reference
                    ``src/repr/src/row.rs``, ``scalar.rs``)
- ``ops``         — device kernel substrate: sort, consolidate, segmented
                    reduction, lexicographic search, merge, compaction
- ``expr``        — MIR: relation + scalar expressions, MapFilterProject
                    (reference ``src/expr/src/{relation,scalar,linear}.rs``)
- ``transform``   — MIR→MIR optimizer (reference ``src/transform``)
- ``plan``        — LIR + MIR→LIR lowering (reference ``src/compute-types``)
- ``render``      — LIR → jitted step functions (reference ``src/compute/src/render.rs``)
- ``arrangement`` — multiversioned shared indexes in HBM (reference
                    differential arrangements + ``src/compute/src/arrangement``)
- ``parallel``    — device mesh, exchange (all_to_all), frontier lattice
                    (reference timely progress tracking + exchange pacts)
- ``storage``     — sources (load generators, upsert), persist-analog durability
- ``coord``       — catalog, timestamp oracle, coordinator (reference ``src/adapter``)
- ``sql``         — SQL frontend: parser → HIR → decorrelation → MIR
                    (reference ``src/sql-parser``, ``src/sql``)
"""

import os

import jax

# SQL semantics need exact 64-bit integer arithmetic (sums over SF>=100 TPCH
# overflow int32; reference uses i64 Diff + i128 accumulators,
# src/repr/src/diff.rs). Enable x64 before any array is created.
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: compile time for lax.sort grows
# superlinearly in array size, so steps at large capacity tiers are
# expensive to compile but cheap to run. Caching compiled executables
# across processes makes dataflow installation (the CREATE MATERIALIZED
# VIEW analog) pay that cost once per (plan, capacity signature).
#
# Placement: where JAX_COMPILATION_CACHE_DIR is set, JAX itself reads it
# and this package sets nothing. Otherwise the cache is one fixed
# directory inside the checkout (the path is part of nothing's identity,
# but a cache that moves never hits): environmentd, its replicas and the
# tests all share it. The installed JAX already keys CPU entries by the
# host's CPU feature list (the serialized CPU topology is hashed into
# the cache key), so no per-host subdirectory is needed.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

__version__ = "0.1.0"
