"""Arrangements: sorted, consolidated columnar indexes resident in HBM.

Analog of differential arrangements/spines (reference:
doc/developer/arrangements.md; row-spine/src/lib.rs; shared via
TraceManager, compute/src/arrangement/manager.rs:33). Two forms:

- ``Arrangement``: a single fully-consolidated sorted run. Inserts
  merge-path + consolidate into a new run — O(state) per step. Used
  where operator state is output-sized (Reduce groups, distinct keys,
  TopK windows).

- ``Spine``: the amortized multi-run form for input-sized state (join
  arrangements, the output index): a geometric ladder of consolidated
  sorted runs plus, in append-slot ingest mode, a ring of per-step
  slot batches below run 0. Readers see the multiset sum of all runs
  and slots; a row may appear in several with cancelling diffs, which
  downstream consolidation resolves.

Order modes (round-5 redesign, PERF_NOTES.md): an arrangement is
sorted either in ``exact`` SQL-lane order (key columns then remaining
columns — required where readers exploit VALUE order inside a key
range: min/max, TopK) or in ``hash`` order (a 2-lane hash pair of the
key then of the full row). Hash order cuts sort operands and search
lanes from one-per-column to two, which is what lets sorts compile and
merges execute at state scale; EQUALITY remains exact everywhere
(consolidation compares adjacent rows exactly; a hash collision can
only make two different rows adjacent, never merge them).

Cached run lanes (round 6, ISSUE 5): a spine built with lane caching
carries each frozen run's ROW-STACKED sort lanes (``[cap, L]`` uint64)
in its state. Lanes are computed once when a run is (re)built at fold
time and from then on maintained by the merge's own row-gather
(ops/merge.merge_sorted_cached) and the consolidation's compaction
scatter (ops/consolidate.consolidate_sorted_cached) — the per-step
path never re-derives lanes from the columns of unchanged runs, which
was the bulk of the old per-step O(run0) work. Key-only searches slice
the static key-lane prefix of the same array.

Historical multiversion reads are deferred — with barrier-synchronous
micro-batch steps every reader sees the state exactly at the step
frontier, which matches the reference's behavior when logical compaction
keeps `since` at the frontier.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..ops.consolidate import (
    consolidate,
    consolidate_sorted,
    consolidate_sorted_cached,
)
from ..ops.lanes import hash_pair, key_lane_width, key_lanes, stack_lanes
from ..ops.merge import merge_sorted, merge_sorted_cached
from ..ops.search import lex_searchsorted_2d
from ..ops.sort import apply_perm, sort_perm
from ..repr.batch import Batch, capacity_tier
from ..repr.schema import Schema


def device_nbytes(tree) -> int:
    """Total bytes of the DEVICE-resident array leaves of a pytree
    (host numpy mirrors excluded): shape * itemsize from the aval —
    pure metadata, never a device read or sync."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                total += leaf.size * leaf.dtype.itemsize
            except (AttributeError, TypeError):
                pass
    return total


@jax.tree_util.register_pytree_node_class
@dataclass
class Arrangement:
    """A collection arranged (sorted) by a key-column prefix.

    batch: consolidated (no duplicate rows, nonzero diffs), sorted by
    the order mode's lanes. Times in the batch are all forwarded to
    the arrangement's logical `since` (full logical compaction), so
    `batch` is exactly the accumulated multiset.

    ``lanes2d`` is an ADVISORY cache of the batch's stacked sort lanes
    (``[cap, L]`` uint64): attached by Spine.runs() from the spine's
    lane cache, consumed by lookup_range, and deliberately NOT part of
    the pytree (an Arrangement crossing a jit boundary on its own
    simply drops the cache and recomputes)."""

    batch: Batch
    key: tuple  # static: key column indices
    order: str = "exact"  # static: "exact" | "hash"
    lanes2d: object = None  # advisory stacked sort-lane cache

    def tree_flatten(self):
        return (self.batch,), (self.key, self.order)

    @classmethod
    def tree_unflatten(cls, aux, children):
        key, order = aux
        return cls(children[0], key, order)

    @property
    def schema(self) -> Schema:
        return self.batch.schema

    @property
    def capacity(self) -> int:
        return self.batch.capacity

    def _rest(self) -> list:
        return [
            i for i in range(self.schema.arity) if i not in self.key
        ]

    def sort_lanes(self):
        """Lanes defining this arrangement's order.

        exact: key cols then all remaining cols (equal-key rows in
        deterministic SQL-lane order).
        hash: (key hash pair, full-row hash pair) — 4 lanes total."""
        rest = self._rest()
        if self.order == "hash":
            kh1, kh2 = hash_pair(key_lanes(self.batch, self.key))
            if not rest:
                # Full-column key: the row hash IS the key hash (same
                # lane sequence) — don't mix the chains twice.
                return [kh1, kh2, kh1, kh2]
            rh1, rh2 = hash_pair(
                key_lanes(self.batch, list(self.key) + rest)
            )
            return [kh1, kh2, rh1, rh2]
        return key_lanes(self.batch, list(self.key) + rest)

    def sort_lanes_2d(self) -> jnp.ndarray:
        """Stacked ``[cap, L]`` sort lanes — the cached array when this
        view carries one, else computed from the columns."""
        if self.lanes2d is not None:
            return self.lanes2d
        return stack_lanes(self.sort_lanes())

    def key_lane_prefix(self) -> int:
        """Static width of the key-only prefix of the sort lanes."""
        if self.order == "hash":
            return 2
        return key_lane_width(self.schema, self.key)

    def key_only_lanes(self):
        if self.order == "hash":
            kh1, kh2 = hash_pair(key_lanes(self.batch, self.key))
            return [kh1, kh2]
        return key_lanes(self.batch, list(self.key))

    def key_lanes_2d(self) -> jnp.ndarray:
        """Stacked key-only lanes: the prefix of the (possibly cached)
        sort lanes — except for the empty key, whose single constant
        lane is not a prefix of the full sort-lane sequence."""
        if not self.key:
            return jnp.zeros(
                (self.batch.capacity, 1), dtype=jnp.uint64
            )
        return self.sort_lanes_2d()[:, : self.key_lane_prefix()]

    def probe_lanes(self, batch: Batch, cols):
        """Lanes for probing THIS arrangement with `batch`'s `cols` —
        must match the arrangement's order mode."""
        if self.order == "hash":
            kh1, kh2 = hash_pair(key_lanes(batch, cols))
            return [kh1, kh2]
        return key_lanes(batch, cols)

    @staticmethod
    def empty(
        schema: Schema, key, capacity: int = 256, order: str = "exact"
    ) -> "Arrangement":
        return Arrangement(
            Batch.empty(schema, capacity), tuple(key), order
        )

    def map_batches(self, fn) -> "Arrangement":
        """Rebuild with ``fn`` applied to the contained batch (shared
        shape-management protocol with Spine: replication, count
        reshaping, growth). Drops the advisory lane cache."""
        return Arrangement(fn(self.batch), self.key, self.order)


def run_sort_lanes(batch: Batch, key, order: str) -> jnp.ndarray:
    """Stacked sort lanes of a run batch — the lane-cache (re)build,
    used at fold/grow time, never on the per-step path for frozen
    runs."""
    return stack_lanes(Arrangement(batch, tuple(key), order).sort_lanes())


def arrange(
    batch: Batch, key, capacity: int | None = None, order: str = "exact"
) -> Arrangement:
    """Sort+consolidate a batch into an Arrangement (build from scratch).

    An explicit ``capacity`` snaps to the pow2 quantization menu
    (ISSUE 16, plan/decisions.quantize_cap): spine run capacities are
    part of every step program's tier vector, so off-menu sizes would
    mint program-bank keys no other DDL can share. Growth never
    shrinks: the snap rounds up, and ``with_capacity`` forbids
    shrinking below the batch anyway."""
    if capacity is not None:
        from ..plan.decisions import quantize_cap

        capacity = quantize_cap(capacity, minimum=batch.capacity)
    key = tuple(key)
    cons = consolidate(batch, include_time=False)
    # consolidate's output is in full-row HASH order; a hash-mode
    # arrangement whose key is every column IN SCHEMA ORDER is
    # therefore already sorted (its key hash is computed over the same
    # lane sequence as consolidate's row hash) — the common
    # output-index case skips its re-sort entirely. A PERMUTED
    # full-column key hashes a different lane order and must re-sort.
    if order == "hash" and key == tuple(range(batch.schema.arity)):
        sorted_batch = cons
    else:
        arr = Arrangement(cons, key, order)
        perm = sort_perm(arr.sort_lanes(), cons.count, cons.capacity)
        sorted_batch = apply_perm(cons, perm)
    if capacity is not None and capacity != sorted_batch.capacity:
        sorted_batch = sorted_batch.with_capacity(capacity)
    return Arrangement(sorted_batch, key, order)


def insert(
    arr: Arrangement, delta: Batch, out_capacity: int
) -> tuple[Arrangement, jnp.ndarray]:
    """Merge a delta batch into the arrangement: the spine 'merge' step.

    Returns (new_arrangement, overflowed). The caller picks `out_capacity`
    (a tier >= expected survivors); on overflow retry with a larger tier —
    the exert-proportionality analog is that we always fully compact.
    """
    d = arrange(delta, arr.key, capacity=None, order=arr.order)
    merged, overflow = merge_sorted(
        arr.batch,
        arr.sort_lanes_2d(),
        d.batch,
        d.sort_lanes_2d(),
        out_capacity,
    )
    # Merged runs may contain the same row twice (once per side); both
    # sides share the arrangement's order, so equal rows are adjacent
    # in the merge and duplicate summation needs NO sort
    # (consolidate_sorted's exact adjacent comparison).
    cons = consolidate_sorted(merged)
    return Arrangement(cons, arr.key, arr.order), overflow


def lookup_range(arr: Arrangement, probe_lanes) -> tuple:
    """For each probe key, the [lo, hi) row range of matching keys.
    `probe_lanes` must come from Arrangement.probe_lanes (same order
    mode) — a lane list or an already-stacked ``[n, L]`` array.

    Fused form (round 6): both sides travel row-stacked, so each
    binary-search iteration is ONE row-gather — and when the
    arrangement carries cached lanes (a frozen spine run), the probed
    lanes are never re-derived from its columns."""
    lanes_2d = arr.key_lanes_2d()
    query_2d = (
        probe_lanes
        if getattr(probe_lanes, "ndim", None) == 2
        else stack_lanes(probe_lanes)
    )
    lo = lex_searchsorted_2d(
        lanes_2d, arr.batch.count, query_2d, side="left"
    )
    hi = lex_searchsorted_2d(
        lanes_2d, arr.batch.count, query_2d, side="right"
    )
    return lo, hi


@jax.tree_util.register_pytree_node_class
@dataclass
class Spine:
    """Amortized MULTI-RUN arrangement: a geometric ladder of
    consolidated sorted runs, smallest first (``runs_b[0]`` absorbs
    folded deltas; ``runs_b[-1]`` is the base). Logical content is
    the multiset sum of all runs; each run is individually sorted by
    the order mode's lanes and consolidated, but the SAME row may
    appear in several runs — readers combine (probe every run; sum
    diffs downstream).

    The point (differential's geometric spine merges, re-cast for
    fixed XLA shapes): per-step insert cost is O(delta) in append-slot
    mode (O(runs_b[0]) in merge mode); level l is folded into level
    l+1 every ``ratio^l`` compaction ticks, so a row is merged
    O(levels) times over its lifetime and the per-step amortized merge
    cost is O(levels * delta) — NOT O(state). Two levels reproduce the
    round-3/4 base+tail form; the big output index runs 3-4 levels.
    """

    runs_b: tuple  # Batches, smallest-first
    key: tuple  # static: key column indices
    order: str = "exact"  # static: "exact" | "hash"
    # Optional APPEND-SLOT ingest ring (round-5 perf design): S
    # independently sorted slot batches below runs_b[0]. With slots,
    # insert_tail costs O(delta) — the arranged delta BECOMES the next
    # slot (one switch + pad; no merge into a big run per step) — and
    # the level-0 fold tree-merges the slots into runs_b[0] every
    # compact_every steps. `cursor` (device scalar) picks the slot.
    slots: tuple = ()
    cursor: object = None  # int32 scalar when slots != ()
    # Cached run lanes (round 6): stacked [cap_i, L] uint64 sort lanes
    # per run (and per ingest slot), () when caching is off. Computed
    # at fold time, carried through merges by the merge's own gather —
    # see the module docstring for the invariants.
    lanes: tuple = ()
    slot_lanes: tuple = ()

    def tree_flatten(self):
        children = [self.runs_b]
        if self.lanes:
            children.append(self.lanes)
        if self.slots:
            children.append(self.slots)
            if self.lanes:
                children.append(self.slot_lanes)
            children.append(self.cursor)
        return tuple(children), (
            self.key, self.order, bool(self.slots), bool(self.lanes),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        key, order, has_slots, has_lanes = aux
        it = iter(children)
        runs_b = next(it)
        lanes = next(it) if has_lanes else ()
        slots, slot_lanes, cursor = (), (), None
        if has_slots:
            slots = next(it)
            if has_lanes:
                slot_lanes = next(it)
            cursor = next(it)
        return cls(
            runs_b, key, order, slots, cursor, lanes, slot_lanes
        )

    @property
    def levels(self) -> int:
        return len(self.runs_b)

    @property
    def base(self) -> Batch:
        return self.runs_b[-1]

    @property
    def tail(self) -> Batch:
        return self.runs_b[0]

    @property
    def schema(self) -> Schema:
        return self.base.schema

    @property
    def capacity(self) -> int:
        """Base-run capacity (the state-size tier)."""
        return self.base.capacity

    @property
    def tail_capacity(self) -> int:
        return self.tail.capacity

    def run_lanes_2d(self, i: int) -> jnp.ndarray:
        """Run i's stacked sort lanes: the cache when present, else
        derived from the run's columns (lane-cache-off compatibility)."""
        if self.lanes:
            return self.lanes[i]
        return run_sort_lanes(self.runs_b[i], self.key, self.order)

    def slot_lanes_2d(self, i: int) -> jnp.ndarray:
        if self.slot_lanes:
            return self.slot_lanes[i]
        return run_sort_lanes(self.slots[i], self.key, self.order)

    def with_run(
        self, i: int, batch: Batch, lanes: jnp.ndarray | None = None
    ) -> "Spine":
        """Replace run i. With lane caching on, ``lanes`` carries the
        new run's stacked sort lanes (folds pass the merge-carried
        array); None means the run's ROWS are unchanged in content
        (e.g. a count reset) and the cached array stays."""
        rs = list(self.runs_b)
        rs[i] = batch
        new_lanes = self.lanes
        if self.lanes:
            ls = list(self.lanes)
            if lanes is not None:
                ls[i] = lanes
            new_lanes = tuple(ls)
        return Spine(
            tuple(rs), self.key, self.order, self.slots, self.cursor,
            new_lanes, self.slot_lanes,
        )

    def with_cursor(self, cursor) -> "Spine":
        """Replace the slot cursor (shape management only — the SPMD
        layout carries it as a per-device ``[P]`` vector at the
        shard_map boundary and reshapes it to the per-worker scalar
        inside the step body; see ShardedDataflow)."""
        return Spine(
            self.runs_b, self.key, self.order, self.slots, cursor,
            self.lanes, self.slot_lanes,
        )

    def device_bytes(self) -> dict:
        """Device-resident bytes per spine component (ISSUE 12: the
        mz_arrangement_sizes byte columns): the run ladder, the
        append-slot ingest ring (+cursor), and the cached sort lanes.
        Pure metadata — shape*itemsize off the avals, no device read."""
        return {
            "runs": device_nbytes(self.runs_b),
            "slots": device_nbytes((self.slots, self.cursor)),
            "lanes": device_nbytes((self.lanes, self.slot_lanes)),
        }

    def runs(self) -> tuple:
        """Single-run Arrangement views for lookup/probe code (base
        first, then progressively smaller runs, then ingest slots),
        each carrying its cached lanes when the spine has them."""
        batches = tuple(reversed(self.runs_b)) + self.slots
        if self.lanes:
            lanes = tuple(reversed(self.lanes)) + self.slot_lanes
        else:
            lanes = (None,) * len(batches)
        return tuple(
            Arrangement(b, self.key, self.order, lanes2d=l)
            for b, l in zip(batches, lanes)
        )

    def map_batches(self, fn) -> "Spine":
        """Rebuild with ``fn`` applied to every run and slot batch. The
        lane cache survives shape-preserving maps (count reshapes, null
        canonicalization — lane values are a function of row content
        and schema only); a map that changes capacities (replication,
        growth) invalidates it, so the cache is dropped and the spine
        continues in lane-cache-off mode."""
        new_runs = tuple(fn(b) for b in self.runs_b)
        new_slots = tuple(fn(b) for b in self.slots)
        lanes, slot_lanes = self.lanes, self.slot_lanes
        if lanes and (
            any(
                nb.capacity != b.capacity
                for nb, b in zip(new_runs, self.runs_b)
            )
            or any(
                nb.capacity != b.capacity
                for nb, b in zip(new_slots, self.slots)
            )
        ):
            lanes, slot_lanes = (), ()
        return Spine(
            new_runs, self.key, self.order, new_slots, self.cursor,
            lanes, slot_lanes,
        )

    @staticmethod
    def empty(
        schema: Schema,
        key,
        capacity: int = 256,
        tail_capacity: int = 1024,
        order: str = "exact",
        levels: int = 2,
        ratio: int = 8,
        ingest_slots: int = 0,
        cache_lanes: bool | None = None,
    ) -> "Spine":
        """Capacities run geometrically from tail_capacity up, with the
        base pinned at ``capacity``. ``ingest_slots`` > 0 adds an
        append-slot ring of that many tail_capacity slots.
        ``cache_lanes`` None resolves the cached_run_lanes dyncfg."""
        from ..utils.dyncfg import CACHED_RUN_LANES, COMPUTE_CONFIGS

        if cache_lanes is None:
            cache_lanes = bool(CACHED_RUN_LANES(COMPUTE_CONFIGS))
        assert levels >= 2
        caps = [tail_capacity * (ratio**i) for i in range(levels - 1)]
        caps.append(capacity)  # base pinned exactly (callers may size
        # it below the mids deliberately to provoke overflow growth)
        key = tuple(key)
        runs = tuple(Batch.empty(schema, c) for c in caps)
        # Slots are null-canonicalized up front: they ride scan carries,
        # whose pytree structure must not change when an insert lands.
        slots = tuple(
            Batch.empty(schema, tail_capacity).canonicalize_nulls()
            for _ in range(ingest_slots)
        )
        cursor = (
            jnp.asarray(0, jnp.int32) if ingest_slots else None
        )
        lanes, slot_lanes = (), ()
        if cache_lanes:
            lanes = tuple(
                run_sort_lanes(b, key, order) for b in runs
            )
            slot_lanes = tuple(
                run_sort_lanes(s, key, order) for s in slots
            )
        return Spine(
            runs, key, order, slots, cursor, lanes, slot_lanes
        )


def _arrange_for_run(delta: Batch, key: tuple, order: str) -> Arrangement:
    """Arrange a delta for insertion into a SPINE RUN. Runs only need
    SORTEDNESS in the spine's order — a run may hold the same content
    at several times (the multiset-sum reader contract already allows
    a row in several runs; fold-time consolidate_sorted merges
    content-duplicates whenever runs combine). So a delta the step
    already content-hash-sorted ("hash_sorted": the step-level
    consolidate's output; "hash_consolidated": the presorted-producer
    guarantee) skips BOTH the sort and the content re-consolidation
    that the general arrange() pays — the second adjacent-compare
    chain per step in the old path (round-6 op census)."""
    if (
        order == "hash"
        and key == tuple(range(delta.schema.arity))
        and (
            "hash_sorted" in delta.hints
            or "hash_consolidated" in delta.hints
        )
    ):
        return Arrangement(delta, key, order)
    return arrange(delta, key, capacity=None, order=order)


def insert_tail(spine: Spine, delta: Batch) -> tuple[Spine, jnp.ndarray]:
    """Absorb a delta batch — the hot-path insert.

    With an append-slot ring: the arranged delta BECOMES slot
    ``cursor`` (O(delta): a pad + one lax.switch placement; no merge
    touches any run). Without slots: merge into the smallest run
    (O(runs_b[0] capacity)). Every other run passes through untouched
    (no copy: same buffers).

    Returns (new_spine, overflowed). On overflow the host grows the
    slot/tail tier (or compacts more often) and replays."""
    d = _arrange_for_run(delta, spine.key, spine.order)
    if spine.slots:
        slot_cap = spine.slots[0].capacity
        nb = d.batch
        overflow = nb.count > slot_cap
        if nb.capacity < slot_cap:
            nb = nb.with_capacity(slot_cap)
        elif nb.capacity > slot_cap:
            from ..ops.sort import shrink

            nb, sovf = shrink(nb, slot_cap)
            overflow = jnp.logical_or(overflow, sovf)
        # Uniform slot pytree structure: canonical null masks, no
        # producer hints (hints are aux metadata; a hinted batch would
        # differ structurally from the empty slots in switch branches
        # and scan carries).
        nb = nb.canonicalize_nulls().replace(hints=())
        caching = bool(spine.lanes)
        nb_lanes = (
            run_sort_lanes(nb, spine.key, spine.order)
            if caching
            else None
        )
        s = len(spine.slots)
        idx = spine.cursor % s

        def place(k):
            def f():
                out = list(
                    sl.canonicalize_nulls() for sl in spine.slots
                )
                out[k] = nb
                if not caching:
                    return tuple(out)
                ls = list(spine.slot_lanes)
                ls[k] = nb_lanes
                return tuple(out), tuple(ls)

            return f

        placed = jax.lax.switch(idx, [place(k) for k in range(s)])
        if caching:
            new_slots, new_slot_lanes = placed
        else:
            new_slots, new_slot_lanes = placed, ()
        new = Spine(
            spine.runs_b, spine.key, spine.order, new_slots,
            spine.cursor + 1, spine.lanes, new_slot_lanes,
        )
        return new, overflow
    tail = spine.tail
    merged, merged_lanes, overflow = merge_sorted_cached(
        tail,
        spine.run_lanes_2d(0),
        d.batch,
        d.sort_lanes_2d(),
        tail.capacity,
    )
    cons, cons_lanes = consolidate_sorted_cached(merged, merged_lanes)
    return spine.with_run(0, cons, cons_lanes), overflow


def _tree_merge_cached(parts: list, out_cap_final: int | None = None):
    """Pairwise merge a list of (sorted batch, stacked lanes) pairs
    into one (capacity = sum; never overflows). Lanes ride the merge
    gathers — no re-hashing at any level of the tree."""
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            (a, al), (b, bl) = parts[i], parts[i + 1]
            m, ml, _ = merge_sorted_cached(
                a, al, b, bl, a.capacity + b.capacity
            )
            nxt.append((m, ml))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def flush_slots(spine: Spine) -> tuple[Spine, jnp.ndarray]:
    """Fold the append-slot ring into runs_b[0]: tree-merge the slots,
    merge the result into run 0, clear the ring. Returns (new_spine,
    run-0 overflow)."""
    if not spine.slots:
        return spine, jnp.asarray(False)
    merged_slots, slot_merged_lanes = _tree_merge_cached(
        [
            (s, spine.slot_lanes_2d(i))
            for i, s in enumerate(spine.slots)
        ]
    )
    r0 = spine.runs_b[0]
    merged, merged_lanes, overflow = merge_sorted_cached(
        r0, spine.run_lanes_2d(0),
        merged_slots, slot_merged_lanes,
        r0.capacity,
    )
    cons, cons_lanes = consolidate_sorted_cached(merged, merged_lanes)
    cleared = tuple(
        s.replace(count=jnp.zeros_like(s.count)) for s in spine.slots
    )
    new_lanes = spine.lanes
    if new_lanes:
        new_lanes = (cons_lanes,) + tuple(spine.lanes[1:])
    return (
        Spine(
            (cons,) + spine.runs_b[1:], spine.key, spine.order,
            cleared, jnp.zeros_like(spine.cursor),
            new_lanes, spine.slot_lanes,
        ),
        overflow,
    )


def compact_depth(spine: Spine) -> int:
    """Number of fold levels this spine has (max compact_level index
    is compact_depth - 1). A slotted spine has one extra level: level
    0 is the slot flush; level l>0 folds run l-1 into run l."""
    return spine.levels - 1 + (1 if spine.slots else 0)


def compact_level(spine: Spine, level: int) -> tuple[Spine, jnp.ndarray]:
    """Fold one ladder level. Slotless: run `level` -> run `level+1`.
    Slotted: level 0 flushes the append-slot ring into run 0; level
    l>0 folds run l-1 into run l. Sort-free: runs share the spine's
    order, so the merge is a binary search + one row-gather per dtype
    family (lanes included — the target run's cached lanes come out of
    the same gather), and duplicate summation is the exact adjacent
    comparison. Returns (new_spine, overflowed) where the flag is the
    TARGET run's capacity overflow."""
    if spine.slots:
        if level == 0:
            return flush_slots(spine)
        lo_i, hi_i = level - 1, level
    else:
        lo_i, hi_i = level, level + 1
    lo, hi = spine.runs_b[lo_i], spine.runs_b[hi_i]
    merged, merged_lanes, overflow = merge_sorted_cached(
        hi,
        spine.run_lanes_2d(hi_i),
        lo,
        spine.run_lanes_2d(lo_i),
        hi.capacity,
    )
    cons, cons_lanes = consolidate_sorted_cached(merged, merged_lanes)
    out = spine.with_run(hi_i, cons, cons_lanes)
    out = out.with_run(
        lo_i, lo.replace(count=jnp.zeros_like(lo.count))
    )
    return out, overflow


_CLONE_JITS: dict = {}


def clone_state_tree(tree):
    """Deep-copy every device leaf of a state pytree (arrangements,
    spines, batches, scalars) to FRESH buffers in ONE fused program.

    Donation safety (the defer window's checkpoint contract): a step
    program compiled with ``donate_argnums`` hands its carry buffers
    to XLA — after dispatch they are dead and must
    never be read again. The rollback checkpoint therefore cannot hold
    references into the carry; it holds this clone instead. jit
    outputs never alias un-donated inputs, so every returned leaf is a
    fresh buffer."""
    from ..utils.compile_ledger import ledger_jit

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    jitfn = _CLONE_JITS.get(len(leaves))
    if jitfn is None:
        jitfn = ledger_jit(
            jax.jit(lambda *ls: tuple(jnp.copy(l) for l in ls)),
            "clone", "spine", f"clone:{len(leaves)}",
        )
        _CLONE_JITS[len(leaves)] = jitfn
    return jax.tree_util.tree_unflatten(treedef, jitfn(*leaves))


def compact_spine(spine: Spine):
    """Full cascade: fold every slot and run into the base (peeks and
    snapshots read the base as THE consolidated state). Cascades
    bottom-up so the base absorbs everything. Returns (new_spine,
    overflow flags [compact_depth], one per target run, smallest
    target first)."""
    flags = []
    for level in range(compact_depth(spine)):
        spine, ovf = compact_level(spine, level)
        flags.append(ovf)
    return spine, jnp.stack(flags)
