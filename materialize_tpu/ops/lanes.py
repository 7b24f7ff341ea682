"""Order-preserving key-lane encoding.

Sorting, grouping, and range lookup all operate on *key lanes*: uint64 arrays
derived from data columns such that lexicographic comparison of lane tuples
matches SQL ordering of the underlying values. This is the TPU analog of the
reference's sortable Row byte encoding (src/repr/src/row.rs:120,
doc/developer/row-encoding.md) — but columnar, one lane per key column
(plus a null lane for nullable columns; NULLs sort first, grouped together,
matching reference Datum::Null ordering).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..repr.batch import Batch
from ..repr.schema import Column, ColumnType

# numpy scalars, not jnp: a module-level jnp constant would
# initialize the JAX backend (and so claim the chip) at import.
_SIGN64 = np.uint64(1 << 63)
_SIGN32 = np.uint32(1 << 31)


# Greedy power-of-two normalization rungs: sum must cover the full f64
# exponent span (down to 2^-1074 subnormals). With 512 twice and 1,1 at the
# tail, any finite positive double normalizes into [1, 2).
_F64_RUNGS = (512, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1, 1)


def _f64_lanes(arr: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Order-preserving (exponent, mantissa) uint64 lane pair for float64,
    computed with pure arithmetic — no 64-bit bitcasts, which TPU's x64
    rewrite cannot lower (verified on v5e), and exact over the ENTIRE f64
    range including values outside f32 range and subnormals.

    lane1 orders by class and exponent:
      -inf < negatives (by descending exponent) < ±0 < positives (by
      ascending exponent) < +inf < NaN.
    lane2 orders by the 52-bit mantissa within an exponent (bit-flipped for
    negatives). -0.0 and 0.0 share lanes (SQL equality).

    On TPU, f64 is double-double (~49-bit mantissa, f32 exponent range), so
    host values distinct only below device precision encode equal — equality
    follows device arithmetic, which is consistent. XLA also flushes
    subnormals to zero (FTZ), so they land in the zero bucket on every
    platform.
    """
    x = jnp.asarray(arr, dtype=jnp.float64)
    isnan = x != x
    pos_inf = x == jnp.inf
    neg_inf = x == -jnp.inf
    zero = x == 0.0
    neg = x < 0.0
    finite_nonzero = jnp.logical_not(isnan | pos_inf | neg_inf | zero)
    ax = jnp.where(finite_nonzero, jnp.abs(x), 1.0)

    # Greedy exponent extraction: bring ax into [1, 2), tracking e.
    e = jnp.zeros(x.shape, dtype=jnp.int64)
    for s in _F64_RUNGS:
        big = ax >= float(2.0**s)
        ax = jnp.where(big, ax * float(2.0**-s), ax)
        e = e + jnp.where(big, s, 0)
    for s in _F64_RUNGS:
        small = ax < float(2.0 ** (1 - s))
        ax = jnp.where(small, ax * float(2.0**s), ax)
        e = e - jnp.where(small, s, 0)

    mant = jnp.round((ax - 1.0) * float(1 << 52)).astype(jnp.int64)
    biased = e + 1075  # [1, 2099] for all finite nonzero doubles

    lane1 = jnp.where(
        isnan,
        jnp.uint64(5001),
        jnp.where(
            pos_inf,
            jnp.uint64(5000),
            jnp.where(
                neg_inf,
                jnp.uint64(0),
                jnp.where(
                    zero,
                    jnp.uint64(2201),
                    jnp.where(
                        neg,
                        (2201 - biased).astype(jnp.uint64),
                        (2201 + biased).astype(jnp.uint64),
                    ),
                ),
            ),
        ),
    )
    mant_key = jnp.where(neg, (1 << 52) - 1 - mant, mant)
    lane2 = jnp.where(finite_nonzero, mant_key, 0).astype(jnp.uint64)
    return lane1, lane2


def column_lanes(arr: jnp.ndarray, ctype: ColumnType) -> tuple[jnp.ndarray, ...]:
    """Encode one column as uint64 lane(s) with order-preserving
    lexicographic comparison. All types yield one lane except FLOAT64,
    which yields two (exponent, mantissa). Output is always a jnp array
    (numpy inputs + numpy sign constants would otherwise stay numpy and
    break traced indexing downstream)."""
    arr = jnp.asarray(arr)
    if ctype is ColumnType.BOOL:
        return (arr.astype(jnp.uint64),)
    if ctype in (
        ColumnType.INT32,
        ColumnType.INT64,
        ColumnType.DATE,
        ColumnType.TIMESTAMP,
        ColumnType.DECIMAL,
    ):
        # Two's-complement -> offset binary: flip the sign bit.
        return (arr.astype(jnp.int64).astype(jnp.uint64) ^ _SIGN64,)
    if ctype is ColumnType.STRING:
        # Dictionary codes: equality/grouping only (order is insertion order).
        return (arr.astype(jnp.int64).astype(jnp.uint64) ^ _SIGN64,)
    if ctype is ColumnType.FLOAT64:
        return _f64_lanes(arr)
    raise NotImplementedError(ctype)


def lane_count(ctype: ColumnType, nullable: bool) -> int:
    n = 2 if ctype is ColumnType.FLOAT64 else 1
    return n + (1 if nullable else 0)


def key_lanes(batch: Batch, key_indices) -> list[jnp.ndarray]:
    """Lanes for the given column indices. A nullable column (per SCHEMA,
    regardless of whether a runtime mask is present — lane arity must be a
    function of the schema alone so two batches of the same schema always
    compare lane-to-lane) contributes a leading null lane (0 = NULL,
    1 = non-NULL) so NULLs sort first and group together."""
    lanes = []
    for i in key_indices:
        col = batch.schema[i]
        arr = batch.cols[i]
        nulls = batch.nulls[i]
        val_lanes = column_lanes(arr, col.ctype)
        if col.nullable:
            if nulls is None:
                # No runtime mask: all rows non-NULL.
                lanes.append(jnp.ones(arr.shape, dtype=jnp.uint64))
                lanes.extend(val_lanes)
            else:
                lanes.append(
                    jnp.where(nulls, jnp.uint64(0), jnp.uint64(1))
                )
                lanes.extend(
                    jnp.where(nulls, jnp.uint64(0), vl) for vl in val_lanes
                )
        else:
            lanes.extend(val_lanes)
    if not lanes:
        # Empty key (global aggregate): every row is one group. A single
        # constant lane keeps the lane-tuple machinery uniform.
        lanes.append(jnp.zeros(batch.capacity, dtype=jnp.uint64))
    return lanes


def key_lane_width(schema, key_indices) -> int:
    """Static lane count key_lanes emits for these columns — the prefix
    width cached stacked sort lanes are sliced at for key-only
    searches. A function of the schema alone (key_lanes contract)."""
    w = sum(
        lane_count(schema[i].ctype, schema[i].nullable)
        for i in key_indices
    )
    return w if w else 1  # empty key: the single constant lane


def row_lanes(batch: Batch, include_time: bool = True) -> list[jnp.ndarray]:
    """Lanes over every column (plus optionally time) — full-row identity,
    used by consolidation."""
    lanes = key_lanes(batch, range(batch.schema.arity))
    if include_time:
        lanes.append(batch.time.astype(jnp.uint64))
    return lanes


def _mix_lane(h: jnp.ndarray, lane: jnp.ndarray) -> jnp.ndarray:
    """One sequential mixing stage of hash_lanes (shared by the unrolled
    and the scan-fused forms — values must match bit-for-bit)."""
    h = h ^ (
        lane
        + jnp.uint64(0x9E3779B97F4A7C15)
        + (h << jnp.uint64(6))
        + (h >> jnp.uint64(2))
    )
    h = h * jnp.uint64(0xBF58476D1CE4E5B9)
    return h ^ (h >> jnp.uint64(27))


def hash_lanes(lanes, seed: int = 0x9E3779B97F4A7C15) -> jnp.ndarray:
    """Mix lanes into a single uint64 hash (for exchange routing, not
    identity). Analog of the Exchange pact's key hash
    (timely columnar_exchange)."""
    h = jnp.full(lanes[0].shape, jnp.uint64(seed))
    for lane in lanes:
        h = _mix_lane(h, lane.astype(jnp.uint64))
    return h


def stack_lanes(lanes) -> jnp.ndarray:
    """Row-stack a lane tuple into one ``[cap, L]`` uint64 array — the
    fused form every data-dependent lane movement wants (PERF_NOTES
    design rule "move rows, not columns": a single row-gather fetches
    every lane of a row, instead of one gather per lane)."""
    return jnp.stack([l.astype(jnp.uint64) for l in lanes], axis=1)


def unstack_lanes(stacked: jnp.ndarray) -> list:
    """Inverse of stack_lanes (static unstack; slices fuse for free)."""
    return [stacked[:, j] for j in range(stacked.shape[1])]


# Second-stream seed for the hash-pair order (any odd constant distinct
# from hash_lanes' default works; fixed so host generators can replicate
# the order with numpy).
_HASH2_SEED = 0xC2B2AE3D27D4EB4F


def hash_pair(lanes) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two independent 64-bit hashes of a lane tuple — the HASH ORDER
    used by consolidation and big arrangements (round-5 redesign,
    PERF_NOTES.md): sorting/merging by (h1, h2) needs 2 sort operands
    and 2 search lanes instead of one per column, which is what makes
    sorts compile and searches execute at state scale. Equality remains
    EXACT everywhere: consumers compare full exact lanes on ADJACENT
    rows (cheap elementwise) — the hash pair only fixes a consistent
    total order, so a collision can at worst place two different rows
    next to each other, never merge them.

    Wide lane tuples run both mix chains as ONE lax.scan over the
    stacked lanes (round-6 kernel-budget work): the unrolled form
    emitted ~6 ops per lane per chain — ~300 eqns for a 25-lane row —
    which dominated the step program's op census. Bit-identical to the
    unrolled chains."""
    if len(lanes) >= 4:
        stacked = jnp.stack(
            [l.astype(jnp.uint64) for l in lanes]
        )  # [L, cap]
        h0 = jnp.stack(
            [
                jnp.full(lanes[0].shape, jnp.uint64(0x9E3779B97F4A7C15)),
                jnp.full(lanes[0].shape, jnp.uint64(_HASH2_SEED)),
            ]
        )

        def body(h, lane):
            return _mix_lane(h, lane[None, :]), None

        h, _ = jax.lax.scan(body, h0, stacked)
        return h[0], h[1]
    return hash_lanes(lanes), hash_lanes(lanes, seed=_HASH2_SEED)


def hash_pair_host(cols_u64: list) -> tuple:
    """Numpy replica of hash_pair over pre-encoded u64 lane arrays, so
    host-side producers (load generators) can emit batches PRE-SORTED
    in the device hash order (sorted ingest skips device sorts)."""
    import numpy as np

    def mix(seed):
        h = np.full(cols_u64[0].shape, np.uint64(seed))
        with np.errstate(over="ignore"):
            for lane in cols_u64:
                lane = lane.astype(np.uint64)
                h = h ^ (
                    lane
                    + np.uint64(0x9E3779B97F4A7C15)
                    + (h << np.uint64(6))
                    + (h >> np.uint64(2))
                )
                h = h * np.uint64(0xBF58476D1CE4E5B9)
                h = h ^ (h >> np.uint64(27))
        return h

    return mix(0x9E3779B97F4A7C15), mix(_HASH2_SEED)


def host_lane_encode(col, column: "Column", nulls=None):
    """Numpy replica of key_lanes' per-column encoding (FLOAT64
    unsupported — host presort callers are integer generators).
    Matches the device exactly, including the schema-driven null lane:
    a NULLABLE column always contributes a leading null lane (all-ones
    when no runtime mask is present), lane arity being a function of
    the schema alone. Returns list of u64 arrays."""
    import numpy as np

    ctype = column.ctype
    if ctype is ColumnType.FLOAT64:
        raise NotImplementedError("host lane encode: float64")
    if ctype is ColumnType.BOOL:
        v = col.astype(np.uint64)
    else:
        v = col.astype(np.int64).astype(np.uint64) ^ np.uint64(1 << 63)
    if not column.nullable:
        return [v]
    if nulls is None:
        return [np.ones(len(col), dtype=np.uint64), v]
    nl = nulls.astype(bool)
    return [
        np.where(nl, np.uint64(0), np.uint64(1)),
        np.where(nl, np.uint64(0), v),
    ]
