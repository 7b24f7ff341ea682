"""Sorting and compaction kernels.

The workhorses of the update algebra on TPU: every consolidation, grouping,
and arrangement build starts with a lexicographic sort on key lanes.
XLA's variadic `lax.sort` sorts by the first `num_keys` operands
lexicographically — the device analog of the reference's batcher sort
(differential's `Batcher`, consumed via MzArrange,
compute/src/extensions/arrange.rs).

Invalid (padding) rows are kept at the tail by appending a validity lane
that sorts valid rows first.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp

from ..repr.batch import Batch


# The TPU compiler's own sort is cheap to compile up to 2^13 rows (16 s
# for three u64 keys and an index, described v5e) and costly beyond:
# 63 s at 2^14, 290 s at 2^15, 349 s at 2^17, growing with the number
# of key operands (1,309 s for nine keys at 2^17, batched or not): the
# compile-time cliff of ROADMAP A3. Above ``SORT_DIRECT_MAX`` rows
# ``sort_perm`` sorts blocks of ``SORT_BLOCK`` rows in a loop (one
# small sort compiled once) and merges them pairwise by binary search:
# 7.6 s at 2^17 rows. The merges gather row by row and are slow to RUN
# on the chip (Q3's ticks took 1.6 s each with three such sorts of
# 49,152 mostly empty rows), so a batch whose valid rows fit one block
# sorts that block alone. Programs whose sorts all fit
# ``SORT_DIRECT_MAX`` are what they were, and so is every program on
# another backend (XLA:CPU's sort compiles fast and runs faster than
# the merges: the suite's Q9 and Q21 took 8 minutes each through them).
SORT_DIRECT_MAX = 8192
SORT_BLOCK = 2048

# Whether the program being traced sorts large batches in blocks: a
# dataflow whose snapshot forced snapshot-size join arrangements
# (``presize_for_snapshot``) says so while it traces its step. The
# blocked sorts' code is large (Q15's hydration program grew by 241 MB
# and its warm set-up by 32 s with them, past ``setup_s``'s bound), so
# a program that compiles without them keeps the compiler's sort.
_in_blocks = False


@contextmanager
def sorting_in_blocks(on: bool):
    global _in_blocks
    was, _in_blocks = _in_blocks, on
    try:
        yield
    finally:
        _in_blocks = was


def sort_perm(lanes, count, capacity: int) -> jnp.ndarray:
    """Permutation sorting valid rows lexicographically by `lanes`,
    padding rows last. Stable."""
    idx = jnp.arange(capacity, dtype=jnp.int32)
    invalid = (idx >= count).astype(jnp.uint64)  # valid=0 sorts first
    keys = [invalid] + [l for l in lanes]
    # The cliff is the TPU compiler's; elsewhere its sort is the fast one
    if (
        capacity <= SORT_DIRECT_MAX
        or not _in_blocks
        or jax.default_backend() != "tpu"
    ):
        out = jax.lax.sort(keys + [idx], num_keys=len(keys), is_stable=True)
        return out[-1]
    return _large_sort_perm(keys, idx, count, capacity)


def _large_sort_perm(keys: list, idx, count, capacity: int) -> jnp.ndarray:
    """``sort_perm`` above ``SORT_DIRECT_MAX`` rows on a TPU."""

    def head():
        # the valid rows are a prefix: all of them lie in one block,
        # the rows after it are padding and stay where they are
        out = jax.lax.sort(
            [k[:SORT_BLOCK] for k in keys] + [idx[:SORT_BLOCK]],
            num_keys=len(keys), is_stable=True,
        )
        return jnp.concatenate([out[-1], idx[SORT_BLOCK:]])

    # A large batch with few valid rows is every tick's case (a join
    # site's output at its tier): it pays for the rows it holds.
    return jax.lax.cond(
        count <= SORT_BLOCK, head,
        lambda: _blocked_sort_perm(keys, capacity),
    )


def _merge_sorted_pair(xk, xi, yk, yi):
    """Two sorted runs of equal length (key lanes, row index) into
    one; on equal keys the rows of the first run come first."""
    from .search import lex_searchsorted

    n = xi.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    # a row's place: its own rank plus the other run's rows before it
    px = at + lex_searchsorted(yk, n, xk, "left")
    py = at + lex_searchsorted(xk, n, yk, "right")
    keys = tuple(
        jnp.zeros(2 * n, x.dtype).at[px].set(x).at[py].set(y)
        for x, y in zip(xk, yk)
    )
    return keys, jnp.zeros(2 * n, xi.dtype).at[px].set(xi).at[py].set(yi)


def _blocked_sort_perm(keys: list, capacity: int) -> jnp.ndarray:
    """``sort_perm`` for many rows: ``keys[0]`` is the validity lane.
    Rows are padded to a power-of-two number of blocks with rows that
    sort after every real one, each block is sorted by the compiler's
    sort, and sorted blocks are merged pairwise, level by level."""
    blocks = 1
    while blocks * SORT_BLOCK < capacity:
        blocks *= 2
    pad = blocks * SORT_BLOCK - capacity
    idx = jnp.arange(blocks * SORT_BLOCK, dtype=jnp.int32)
    keys = tuple(
        jnp.pad(k, (0, pad), constant_values=2 if j == 0 else 0).reshape(
            blocks, SORT_BLOCK
        )
        for j, k in enumerate(keys)
    )

    def sort_block(block):
        out = jax.lax.sort(block, num_keys=len(block) - 1, is_stable=True)
        return tuple(out[:-1]), out[-1]

    keys, idx = jax.lax.map(
        sort_block, keys + (idx.reshape(blocks, SORT_BLOCK),)
    )
    while idx.shape[0] > 1:
        keys, idx = jax.vmap(_merge_sorted_pair)(
            tuple(k[0::2] for k in keys), idx[0::2],
            tuple(k[1::2] for k in keys), idx[1::2],
        )
    return idx[0][:capacity]


def apply_perm(batch: Batch, perm: jnp.ndarray) -> Batch:
    """Reorder rows by `perm` — ONE row-gather per dtype family
    (gather cost is per-index, independent of row width; rows2d.py)."""
    from .rows2d import from_groups, gather_rows, to_groups

    groups = gather_rows(to_groups(batch), perm)
    return from_groups(groups, batch, batch.count)


def compact(batch: Batch, keep: jnp.ndarray) -> Batch:
    """Drop rows where `keep` is False, moving survivors to a contiguous
    prefix (stable). `keep` is anded with the validity mask.

    One row-scatter per dtype family: positions via exclusive cumsum,
    out-of-range drops (rows2d.py — the per-field form cost one
    output-sized scatter per field)."""
    from .rows2d import from_groups, scatter_rows, to_groups

    if keep.shape[0] == 0:  # capacity-0 batch: nothing to do
        return batch
    keep = jnp.logical_and(keep, batch.valid_mask())
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    new_count = (pos[-1] + 1).astype(jnp.int32)
    cap = batch.capacity
    dest = jnp.where(keep, pos, cap)  # cap is out of range -> dropped
    groups = scatter_rows(to_groups(batch), dest, cap)
    return from_groups(groups, batch, new_count)


def concat_batches(batches: list[Batch]) -> Batch:
    """Concatenate batches of the same schema (capacity = sum of caps).
    Valid rows are NOT contiguous across parts, so this compacts."""
    assert batches
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    cap = sum(b.capacity for b in batches)

    def cat(field):
        parts = [field(b) for b in batches]
        if any(p is None for p in parts):
            parts = [
                p
                if p is not None
                else jnp.zeros(b.capacity, dtype=bool)
                for p, b in zip(parts, batches)
            ]
        return jnp.concatenate(parts)

    keep = jnp.concatenate([b.valid_mask() for b in batches])
    out = Batch(
        cols=tuple(
            cat(lambda b, i=i: b.cols[i]) for i in range(schema.arity)
        ),
        nulls=tuple(
            (
                None
                if all(b.nulls[i] is None for b in batches)
                else cat(lambda b, i=i: b.nulls[i])
            )
            for i in range(schema.arity)
        ),
        time=cat(lambda b: b.time),
        diff=cat(lambda b: b.diff),
        count=jnp.asarray(cap, dtype=jnp.int32),
        schema=schema,
    )
    return compact(out, keep)


def shrink(batch: Batch, capacity: int):
    """Slice a batch down to a smaller capacity tier. Valid rows are
    always a contiguous prefix (every producer compacts), so this is a
    free static slice — no data movement. Returns (batch, overflow);
    on overflow (count > capacity) the tail was dropped and the host
    must retry at a larger tier.

    Used to decouple a consumer's compile-time capacity from a
    producer's: output deltas are few rows in large-capacity batches,
    and downstream sorts compile per capacity (superlinearly — see
    materialize_tpu/__init__.py)."""
    if capacity >= batch.capacity:
        return batch, jnp.asarray(False)

    def sl(a):
        return None if a is None else a[:capacity]

    out = Batch(
        cols=tuple(sl(c) for c in batch.cols),
        nulls=tuple(sl(n) for n in batch.nulls),
        time=sl(batch.time),
        diff=sl(batch.diff),
        count=jnp.minimum(batch.count, capacity),
        schema=batch.schema,
        # A prefix slice preserves every sortedness/uniqueness hint
        # (the consolidate -> shrink -> arrangement-insert chain relies
        # on the hint surviving to skip the insert-side re-sort).
        hints=batch.hints,
    )
    return out, batch.count > capacity


def segment_starts(lanes, count, capacity: int) -> jnp.ndarray:
    """Given rows already sorted by `lanes`, a bool mask marking the first
    row of each run of equal keys (padding rows excluded)."""
    idx = jnp.arange(capacity, dtype=jnp.int32)
    valid = idx < count
    first = idx == 0
    differs = jnp.zeros(capacity, dtype=bool)
    for lane in lanes:
        prev = jnp.concatenate([lane[:1], lane[:-1]])
        differs = jnp.logical_or(differs, lane != prev)
    return jnp.logical_and(valid, jnp.logical_or(first, differs))


def segment_ids(starts: jnp.ndarray) -> jnp.ndarray:
    """0-based segment id per row from a segment-start mask."""
    return jnp.cumsum(starts.astype(jnp.int32)) - 1
