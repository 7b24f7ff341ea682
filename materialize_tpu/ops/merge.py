"""Merge of two lexicographically sorted batches — gather-based.

The device analog of a differential spine merge (reference: differential
spine maintenance behind MzArrange, compute/src/extensions/arrange.rs;
merge effort governed by arrangement_exert_proportionality,
cluster-client/src/client.rs:26-34).

TPU-native form (round-5 redesign, PERF_NOTES.md):
  1. positions of the SMALL side only, via one vectorized lexicographic
     binary search (pos_b = ib + searchsorted(a, b));
  2. a mark/cumsum inversion of those positions (one small-side scatter
     of 1s + one output-sized cumsum — no output-sized scatter);
  3. ONE row-gather per dtype family from concat(a, b) (gather cost is
     per-index, independent of row width — rows2d.py).
The old form scattered every field of both sides (30+ output-sized
scatters; 8.3s at 2M rows). This form costs ~0.15s at the same shape.

Round-6 fusion (`merge_sorted_cached`): lanes travel ROW-STACKED
(``[cap, L]`` uint64 — PERF_NOTES design rule "move rows, not
columns"), the binary search gathers one lane-row per iteration
instead of one gather per lane (ops/search.lex_searchsorted_2d,
behind the ``fused_merge`` dyncfg), and the merged run's lanes come
out of the SAME src gather
that moves the rows — so spine folds maintain their cached run lanes
without ever re-hashing columns (arrangement/spine.py lane cache).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..repr.batch import Batch
from ..utils.dyncfg import COMPUTE_CONFIGS, FUSED_MERGE
from .lanes import stack_lanes
from .rows2d import concat_groups, from_groups, gather_rows, to_groups
from .search import lex_searchsorted, lex_searchsorted_2d


def _normalize_nulls(a: Batch, b: Batch) -> tuple[Batch, Batch]:
    """Give both batches the same null-lane presence (union), so their
    row-group structures line up."""

    def widen(x: Batch, other: Batch) -> Batch:
        nulls = list(x.nulls)
        changed = False
        for i, (mine, theirs) in enumerate(zip(x.nulls, other.nulls)):
            if mine is None and theirs is not None:
                nulls[i] = jnp.zeros(x.capacity, dtype=jnp.bool_)
                changed = True
        return x.replace(nulls=tuple(nulls)) if changed else x

    return widen(a, b), widen(b, a)


def merge_insertion_points(
    a_lanes_2d: jnp.ndarray, a_count, b_lanes_2d: jnp.ndarray, b_count
) -> jnp.ndarray:
    """Right-side insertion point of every b row among a's valid prefix
    — the sorted-merge inner loop, implementation selected by the
    ``fused_merge`` dyncfg (all choices agree bit-for-bit):

      'lax' / 'auto' — fused binary search, one row-gather per
                       iteration, on every backend;
      'unfused'      — the legacy per-lane gather search (baseline).
    """
    mode = FUSED_MERGE(COMPUTE_CONFIGS)
    if mode == "unfused":
        from .lanes import unstack_lanes

        return lex_searchsorted(
            unstack_lanes(a_lanes_2d), a_count,
            unstack_lanes(b_lanes_2d), side="right",
        )
    return lex_searchsorted_2d(
        a_lanes_2d, a_count, b_lanes_2d, side="right"
    )


def merge_sorted_cached(
    a: Batch,
    a_lanes_2d: jnp.ndarray,
    b: Batch,
    b_lanes_2d: jnp.ndarray,
    out_capacity: int,
) -> tuple[Batch, jnp.ndarray, jnp.ndarray]:
    """Merge sorted `a` and `b` (same schema, each sorted by its stacked
    ``[cap, L]`` sort lanes) into one sorted batch of capacity
    `out_capacity`, CARRYING THE LANES: the returned ``[out_capacity,
    L]`` lane array is produced by the same src gather that moves the
    rows, so callers holding cached run lanes never re-derive them from
    columns. Stable: ties keep `a` rows first. Does NOT consolidate.

    Returns (batch, lanes_2d, overflowed): if a.count + b.count >
    out_capacity the tail is dropped, count is clamped, and
    `overflowed` is True — the host must retry at a larger capacity
    tier (SURVEY.md §7 hard part #1)."""
    # Positional type equality: column NAMES are documentation and may
    # legitimately differ across plan paths (e.g. a Let-bound reduce
    # named by HIR vs its MIR-lowered delta); operators are positional.
    assert tuple(c.dtype for c in a.schema.columns) == tuple(
        c.dtype for c in b.schema.columns
    ), (a.schema.names, b.schema.names)
    a, b = _normalize_nulls(a, b)
    cap_a, cap_b = a.capacity, b.capacity
    ib = jnp.arange(cap_b, dtype=jnp.int32)
    # Output position of each b row: its own rank + #{a rows before it}
    # (side='right': ties place a first — stable).
    pos_b = ib + merge_insertion_points(
        a_lanes_2d, a.count, b_lanes_2d, b.count
    )
    pos_b = jnp.where(ib < b.count, pos_b, out_capacity)  # drop padding

    # Invert: mark b positions (small-side scatter), cumsum to count b
    # rows at-or-before each output slot.
    mark = (
        jnp.zeros(out_capacity, dtype=jnp.int32)
        .at[pos_b]
        .set(1, mode="drop")
    )
    cum_b = jnp.cumsum(mark)
    take_b = mark == 1
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    src_b = cum_b - 1  # index into b at b-slots
    src_a = j - cum_b  # index into a at a-slots
    src = jnp.where(
        take_b,
        cap_a + jnp.clip(src_b, 0, cap_b - 1),
        jnp.clip(src_a, 0, cap_a - 1),
    )

    ga = to_groups(a)
    gb = to_groups(b)
    merged_groups = gather_rows(concat_groups(ga, gb), src)
    merged_lanes = jnp.concatenate([a_lanes_2d, b_lanes_2d])[src]

    total = (a.count + b.count).astype(jnp.int32)
    overflowed = total > out_capacity
    count = jnp.minimum(total, out_capacity)
    merged = from_groups(merged_groups, a, count)
    # Padding hygiene: the gather fills slots >= count with clamped
    # garbage rows; zero their diff/time (the old scatter form left
    # zeros there, and diff-based consumers rely on it). Lane padding
    # stays garbage — every lane consumer bounds itself by count.
    valid = j < count
    merged = merged.replace(
        diff=jnp.where(valid, merged.diff, 0),
        time=jnp.where(valid, merged.time, jnp.zeros_like(merged.time)),
    )
    return merged, merged_lanes, overflowed


def merge_sorted(
    a: Batch,
    a_lanes,
    b: Batch,
    b_lanes,
    out_capacity: int,
) -> tuple[Batch, jnp.ndarray]:
    """Lane-list compatibility wrapper over merge_sorted_cached (same
    semantics; stacks the lane tuples and drops the carried lanes)."""
    def as_2d(lanes):
        return (
            lanes
            if getattr(lanes, "ndim", None) == 2
            else stack_lanes(lanes)
        )

    merged, _, overflowed = merge_sorted_cached(
        a, as_2d(a_lanes), b, as_2d(b_lanes), out_capacity
    )
    return merged, overflowed
