"""Compile the served path's device programs for a DESCRIBED TPU v5e.

No chip is attached and nothing runs: the TPU compiler installed here
lowers each program for device 0 of a ``v5e:2x2`` topology and raises
what the chip's compiler would raise (a kernel it refuses, a program
that does not fit). These guard every later PR at no chip time; a pass
is a compile, never a chip run.

The shapes are the tiers ``chip_smoke.py`` (the ``--chips 4``
rehearsal) reaches at its default scale factor (6,005 lineitem rows:
run tier 2^13). The benchmark's cells hydrate at 2^15-2^17 rows, where
one program compiles for minutes: those are compiled by the benchmark's
own cold run on the chip, not here. Everything that touches
the topology lives in the module-scoped fixtures below — never at
import, never ``autouse`` — and the compiles run in this process with
the persistent compilation cache off (an entry written for a described
device cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from materialize_tpu.expr import relation as mir
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.storage.generator.tpch import LINEITEM_SCHEMA

# The largest run tier the smoke's lineitem arrangement reaches.
RUN_TIER = 1 << 13


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile_for_chip(fn, *example_args)``: lower ``jax.jit(fn)``
    on the arguments' shapes, placed on the described chip, and
    compile. Returns the Compiled."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def abstract(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            )
        return x

    def run(fn, *args):
        return (
            jax.jit(fn)
            .lower(*jax.tree_util.tree_map(abstract, args))
            .compile()
        )

    yield run
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _lineitem_batch(capacity: int) -> Batch:
    n = 8
    cols = [
        np.arange(n).astype(c.dtype) for c in LINEITEM_SCHEMA.columns
    ]
    return Batch.from_numpy(
        LINEITEM_SCHEMA, cols, np.zeros(n, np.uint64),
        np.ones(n, np.int64), capacity=capacity,
    )


def _sort_lanes(batch: Batch):
    """Shape of the batch's stacked ``[cap, L]`` sort lanes for key
    l_orderkey (shapes only: nothing is computed)."""
    from materialize_tpu.arrangement.spine import arrange

    return jax.eval_shape(
        lambda b: arrange(b, (0,)).sort_lanes_2d(), batch
    )


def _no_interpreted_kernel(compiled) -> None:
    """Default dyncfgs put no hand-written kernel on the chip (the
    Pallas merge kernel the v5e compiler refused is gone)."""
    assert "tpu_custom_call" not in compiled.as_text()


def test_q1_step_core_compiles(compile_for_chip):
    """The flagship maintained dataflow's step (x64 and the u64 lanes
    included), as ``__graft_entry__.entry()`` hands it out."""
    import __graft_entry__ as graft

    fn, args = graft.entry()
    compiled = compile_for_chip(fn, *args)
    _no_interpreted_kernel(compiled)
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_cached_merge_compiles_at_run_tier(compile_for_chip):
    """``merge_sorted_cached`` with ``fused_merge`` at its default: two
    sorted lineitem runs at the smoke's largest tier."""
    from materialize_tpu.ops.merge import merge_sorted_cached

    a, b = _lineitem_batch(RUN_TIER), _lineitem_batch(256)

    def merge(ab, al, bb, bl):
        return merge_sorted_cached(ab, al, bb, bl, RUN_TIER)

    compiled = compile_for_chip(
        merge, a, _sort_lanes(a), b, _sort_lanes(b)
    )
    _no_interpreted_kernel(compiled)


def test_cached_consolidate_compiles_at_run_tier(compile_for_chip):
    from materialize_tpu.ops.consolidate import consolidate_sorted_cached

    a = _lineitem_batch(RUN_TIER)
    compile_for_chip(consolidate_sorted_cached, a, _sort_lanes(a))


def test_peek_lookup_gather_compiles(compile_for_chip):
    """The fast path's batched point-lookup gather over the lineitem
    index's spine (bound column l_orderkey, 32 probes)."""
    from materialize_tpu.coord.peek import (
        _make_lookup_core,
        _probe_arrays,
    )

    df = Dataflow(mir.Get("lineitem", LINEITEM_SCHEMA), name="idx")
    df._grow_for(("out", "base"), target=RUN_TIER)
    arrays, ok = _probe_arrays(
        LINEITEM_SCHEMA, (0,), [(k,) for k in range(32)], 32
    )
    compile_for_chip(
        _make_lookup_core((0,), 8),
        df.output,
        tuple(jnp.asarray(a) for a in arrays),
        jnp.asarray(ok),
    )


def test_index_churn_step_compiles_at_run_tier(compile_for_chip):
    """One churn tick into the lineitem index with its base run at the
    smoke's largest tier — the step the replica repeats."""
    df = Dataflow(mir.Get("lineitem", LINEITEM_SCHEMA), name="idx")
    df._grow_for(("out", "base"), target=RUN_TIER)
    args = (
        tuple(df.states), df.output, df.err_output,
        {"lineitem": _lineitem_batch(256)},
        jnp.asarray(0, dtype=jnp.uint64),
    )
    compile_for_chip(df._step_core, *args)
