"""Round-6 O(delta) ingest: append-slot vs full-merge equivalence, the
per-step-work scaling gate, the fused search/merge parity checks, and
the cached-run-lane invariants (ISSUE 5).

The load-bearing claims pinned here:
- append-slot ingest + ladder folds produce a spine state row-for-row
  equal (after full compaction) to the every-tick merge path, across
  randomized batch sizes, duplicate keys, and retraction-heavy
  workloads;
- the step program's traced op count AND its intermediate-bytes
  footprint are flat across run0 capacities (16k/64k/256k) in
  append-slot mode — per-step work is O(delta), not O(run0) — while
  merge mode's bytes demonstrably grow;
- every fused_merge mode (auto, lax fused, legacy unfused) computes
  identical merges;
- cached run lanes always equal lanes recomputed from the run columns
  (over the valid prefix) after any sequence of inserts and folds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from materialize_tpu.arrangement.spine import (
    Spine,
    compact_depth,
    compact_level,
    compact_spine,
    insert_tail,
    run_sort_lanes,
)
from materialize_tpu.ops.consolidate import adjacent_equal, consolidate
from materialize_tpu.ops.lanes import stack_lanes
from materialize_tpu.ops.merge import merge_sorted
from materialize_tpu.ops.search import (
    lex_searchsorted,
    lex_searchsorted_2d,
)
from materialize_tpu.ops.sort import shrink
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema
from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

SCH = Schema(
    (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
)
NSCH = Schema(
    (
        Column("k", ColumnType.INT64),
        Column("v", ColumnType.INT64, nullable=True),
    )
)


def _batch(ks, vs, ds, t=0, cap=256, schema=SCH, vnulls=None):
    nulls = None
    if vnulls is not None:
        nulls = [None, np.asarray(vnulls, bool)]
    return Batch.from_numpy(
        schema,
        [np.asarray(ks, np.int64), np.asarray(vs, np.int64)],
        np.uint64(t),
        np.asarray(ds, np.int64),
        capacity=cap,
        nulls=nulls,
    )


def _base_rows(sp):
    return [r for r in sp.base.to_rows()]


def _content_rows(sp):
    """Base-run rows as (content..., diff) with NULLs rendered as None
    — to_rows() exposes raw column values, but the representative raw
    value UNDER a null mask is merge-order-dependent garbage (SQL
    equality is null-gated), so comparisons must mask it."""
    b = sp.base
    n = int(np.asarray(b.count))
    cols = [np.asarray(c)[:n] for c in b.cols]
    nulls = [
        None if x is None else np.asarray(x)[:n] for x in b.nulls
    ]
    diffs = np.asarray(b.diff)[:n]
    out = []
    for i in range(n):
        row = tuple(
            None
            if nulls[j] is not None and bool(nulls[j][i])
            else int(cols[j][i])
            for j in range(len(cols))
        )
        out.append(row + (int(diffs[i]),))
    return out


def _rand_batch(rng, t, schema=SCH, max_n=120, retract_heavy=False):
    n = int(rng.integers(1, max_n))
    ks = rng.integers(0, 40, n)  # small key range: duplicate-dense
    vs = rng.integers(0, 3, n)
    if retract_heavy:
        ds = rng.choice([-1, -1, 1, 2], n)
    else:
        ds = rng.choice([-1, 1, 1, 2], n)
    vnulls = (
        rng.random(n) < 0.2 if schema is NSCH else None
    )
    return _batch(
        ks, vs, ds, t=t, cap=256, schema=schema, vnulls=vnulls
    )


# --------------------------------------------------------------------------
# tentpole: append-slot path == full-merge path (property test)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("order", ["hash", "exact"])
@pytest.mark.parametrize("schema", [SCH, NSCH], ids=["plain", "nullable"])
def test_append_slot_matches_full_merge_property(order, schema):
    """Randomized churn (duplicate keys, retractions, varying batch
    sizes) through a slotted spine on the ladder fold cadence vs the
    every-tick merge spine: after full compaction the BASE RUNS must
    be row-for-row identical (both orders are deterministic given
    content, so list equality — not just multiset equality)."""
    ins = jax.jit(insert_tail)
    fold = jax.jit(compact_level, static_argnums=1)
    comp = jax.jit(compact_spine)
    for seed in (3, 11):
        rng = np.random.default_rng(seed)
        key = (0, 1)
        slotted = Spine.empty(
            schema, key, capacity=1 << 13, tail_capacity=512,
            order=order, levels=3, ratio=4, ingest_slots=4,
        )
        merged = Spine.empty(
            schema, key, capacity=1 << 13, tail_capacity=512,
            order=order, levels=3, ratio=4,
        )
        oracle: dict = {}
        for t in range(24):
            b = _rand_batch(rng, t, schema=schema)
            n = b._host_count
            for i in range(n):
                row = tuple(
                    None
                    if b.nulls[j] is not None
                    and bool(np.asarray(b.nulls[j])[i])
                    else int(np.asarray(b.cols[j])[i])
                    for j in range(schema.arity)
                )
                d = int(np.asarray(b.diff)[i])
                oracle[row] = oracle.get(row, 0) + d
            slotted, ovf_s = ins(slotted, b)
            merged, ovf_m = ins(merged, b)
            assert not bool(ovf_s) and not bool(ovf_m)
            if (t + 1) % 4 == 0:
                # Geometric cadence: level 0 every 4 ticks, level 1
                # every 16.
                deepest = 1 if (t + 1) % 16 == 0 else 0
                for lvl in range(deepest + 1):
                    slotted, o1 = fold(slotted, lvl)
                    merged, o2 = fold(merged, lvl)
                    assert not bool(o1) and not bool(o2)
        slotted, o1 = comp(slotted)
        merged, o2 = comp(merged)
        assert not np.asarray(o1).any() and not np.asarray(o2).any()
        # Row-for-row on (content..., diff): both orders are
        # deterministic given content, so the base runs must agree as
        # LISTS. Times are excluded — which input time survives a
        # content merge depends on fold order, and arrangement times
        # are all logically forwarded to `since` (spine.py docstring).
        # NULLs are masked to None: the raw value under a null mask is
        # representative garbage.
        rows_s = _content_rows(slotted)
        rows_m = _content_rows(merged)
        assert rows_s == rows_m, (seed, order)
        got = {}
        for r in rows_s:  # rows are (content..., diff)
            got[r[:-1]] = got.get(r[:-1], 0) + r[-1]
        assert {k: d for k, d in got.items() if d} == {
            k: d for k, d in oracle.items() if d
        }, (seed, order)


# --------------------------------------------------------------------------
# tentpole: per-step work is O(delta), independent of run0 capacity
# --------------------------------------------------------------------------


def _step_stats(out_slots: int, run0_cap: int):
    from materialize_tpu.analysis import (
        intermediate_bytes,
        kernel_count,
        trace_dataflow_step,
    )
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow

    df = Dataflow(
        mir.Get("L", SCH), state_cap=256, out_levels=3,
        out_slots=out_slots,
    )
    df._grow_for(("out", 0), target=run0_cap)
    closed = trace_dataflow_step(df, input_cap=256)
    return kernel_count(closed), intermediate_bytes(closed)


def test_per_step_work_flat_across_run0_capacity():
    """Acceptance gate (ISSUE 5): with append-slot ingest, the traced
    per-step op count AND the intermediate-bytes footprint must not
    grow with run0 capacity across {16k, 64k, 256k}. The merge-mode
    contrast below proves the metric bites."""
    caps = (1 << 14, 1 << 16, 1 << 18)
    slotted = [_step_stats(out_slots=4, run0_cap=c) for c in caps]
    ops = {s[0] for s in slotted}
    byts = {s[1] for s in slotted}
    assert len(ops) == 1, f"op count varies with run0 cap: {slotted}"
    assert len(byts) == 1, (
        f"per-step bytes scale with run0 cap: {slotted}"
    )
    # Contrast: merge-mode ingest touches run0 every step, so its
    # intermediate bytes DO grow with run0 capacity.
    unslotted = [_step_stats(out_slots=0, run0_cap=c) for c in caps]
    assert unslotted[-1][1] > unslotted[0][1], unslotted


# --------------------------------------------------------------------------
# fused search / merge parity
# --------------------------------------------------------------------------


def _sorted_lanes(rng, m, L, lo=0, hi=9):
    a = rng.integers(lo, hi, (m, L)).astype(np.uint64)
    return a[np.lexsort(a.T[::-1])]


def test_lex_searchsorted_2d_matches_legacy():
    rng = np.random.default_rng(5)
    for m, n, L in ((257, 63, 3), (64, 64, 1), (1024, 17, 4)):
        a = _sorted_lanes(rng, m, L)
        q = rng.integers(0, 9, (n, L)).astype(np.uint64)
        count = int(rng.integers(0, m + 1))
        al = [jnp.asarray(a[:, j]) for j in range(L)]
        ql = [jnp.asarray(q[:, j]) for j in range(L)]
        for side in ("left", "right"):
            legacy = np.asarray(
                lex_searchsorted(al, count, ql, side)
            )
            fused = np.asarray(
                lex_searchsorted_2d(
                    jnp.asarray(a), count, jnp.asarray(q), side
                )
            )
            assert (legacy == fused).all(), (m, n, L, side)


@pytest.mark.parametrize("mode", ["lax", "auto", "unfused"])
def test_fused_merge_modes_agree(mode):
    """Every fused_merge mode must produce the identical merged
    batch (the dyncfg contract); 'auto' is the lax search on every
    backend."""
    rng = np.random.default_rng(9)

    def mk(n_rows, t):
        ks = np.sort(rng.integers(0, 50, n_rows))
        vs = np.arange(n_rows)
        b = _batch(ks, vs, np.ones(n_rows, np.int64), t=t, cap=128)
        # Sort in exact order for a (k, v) key.
        from materialize_tpu.arrangement.spine import arrange

        return arrange(b, (0, 1), order="exact")

    a = mk(60, 0)
    b = mk(35, 1)

    def run():
        m, ovf = merge_sorted(
            a.batch, a.sort_lanes_2d(), b.batch, b.sort_lanes_2d(), 256
        )
        assert not bool(ovf)
        return m.to_rows()

    COMPUTE_CONFIGS.update({"fused_merge": "lax"})
    try:
        want = run()
        COMPUTE_CONFIGS.update({"fused_merge": mode})
        got = run()
    finally:
        COMPUTE_CONFIGS.update({"fused_merge": None})  # reset
    assert got == want, mode


# --------------------------------------------------------------------------
# cached run lanes: always equal a recompute over the valid prefix
# --------------------------------------------------------------------------


def _assert_lane_cache_exact(sp):
    for i in range(sp.levels):
        n = int(np.asarray(sp.runs_b[i].count))
        cached = np.asarray(sp.lanes[i])[:n]
        fresh = np.asarray(
            run_sort_lanes(sp.runs_b[i], sp.key, sp.order)
        )[:n]
        assert (cached == fresh).all(), f"run {i} lane cache diverged"
    for i in range(len(sp.slots)):
        n = int(np.asarray(sp.slots[i].count))
        cached = np.asarray(sp.slot_lanes[i])[:n]
        fresh = np.asarray(
            run_sort_lanes(sp.slots[i], sp.key, sp.order)
        )[:n]
        assert (cached == fresh).all(), f"slot {i} lane cache diverged"


@pytest.mark.parametrize("order", ["hash", "exact"])
def test_cached_lanes_match_recompute_through_folds(order):
    rng = np.random.default_rng(17)
    sp = Spine.empty(
        NSCH, (0, 1), capacity=1 << 12, tail_capacity=512,
        order=order, levels=3, ratio=4, ingest_slots=4,
        cache_lanes=True,
    )
    assert sp.lanes and sp.slot_lanes
    for t in range(12):
        b = _rand_batch(rng, t, schema=NSCH, max_n=80)
        sp, ovf = insert_tail(sp, b)
        assert not bool(ovf)
        _assert_lane_cache_exact(sp)
        if (t + 1) % 4 == 0:
            for lvl in range(compact_depth(sp)):
                sp, o = compact_level(sp, lvl)
                assert not bool(o)
                _assert_lane_cache_exact(sp)


def test_spine_without_lane_cache_still_correct():
    """cached_run_lanes=False keeps the legacy recompute path live
    (sharded spines and jit-boundary crossings rely on it)."""
    rng = np.random.default_rng(23)
    sp = Spine.empty(
        SCH, (0, 1), capacity=1 << 12, tail_capacity=512,
        order="hash", levels=3, ingest_slots=4, cache_lanes=False,
    )
    assert not sp.lanes and not sp.slot_lanes
    oracle: dict = {}
    for t in range(8):
        b = _rand_batch(rng, t, max_n=60)
        n = b._host_count
        for i in range(n):
            row = (
                int(np.asarray(b.cols[0])[i]),
                int(np.asarray(b.cols[1])[i]),
            )
            oracle[row] = oracle.get(row, 0) + int(
                np.asarray(b.diff)[i]
            )
        sp, ovf = insert_tail(sp, b)
        assert not bool(ovf)
        if (t + 1) % 4 == 0:
            sp, _ = compact_spine(sp)
    sp, _ = compact_spine(sp)
    got = {}
    for r in _base_rows(sp):
        got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
    assert {k: d for k, d in got.items() if d} == {
        k: d for k, d in oracle.items() if d
    }


# --------------------------------------------------------------------------
# consolidate hint chain + exact adjacent equality semantics
# --------------------------------------------------------------------------


def test_consolidate_hint_chain_skips_rework():
    rng = np.random.default_rng(2)
    ks = rng.integers(0, 10, 90)
    vs = rng.integers(0, 2, 90)
    ds = rng.choice([-1, 1, 2], 90)
    ts = rng.integers(0, 3, 90).astype(np.uint64)
    b = Batch.from_numpy(
        SCH, [ks.astype(np.int64), vs.astype(np.int64)], ts, ds,
        capacity=128,
    )
    c1 = consolidate(b, include_time=True)
    assert c1.hints == ("hash_sorted",)
    # shrink (the step's delta-tier slice) must preserve the hint —
    # the insert-side sort skip depends on it.
    s1, ovf = shrink(c1, 128)
    assert s1.hints == c1.hints and not bool(ovf)
    c2 = consolidate(c1, include_time=False)
    assert c2.hints == ("hash_consolidated",)
    direct = consolidate(b, include_time=False)

    def multiset(batch):
        acc: dict = {}
        for r in batch.to_rows():
            acc[r[:-2]] = acc.get(r[:-2], 0) + r[-1]
        return {k: d for k, d in acc.items() if d}

    assert multiset(c2) == multiset(direct) == multiset(b)
    # hash_consolidated input: consolidate is the identity object.
    assert consolidate(c2, include_time=False) is c2


def test_adjacent_equal_sql_semantics():
    """Raw-column adjacent equality must reproduce the lane encoding's
    equalities: NULL==NULL, NaN==NaN, -0.0==0.0, NULL!=value."""
    FSCH = Schema(
        (
            Column("f", ColumnType.FLOAT64),
            Column("v", ColumnType.INT64, nullable=True),
        )
    )
    f = np.array(
        [np.nan, np.nan, -0.0, 0.0, 1.5, 1.5, 1.5, 2.0],
        dtype=np.float64,
    )
    v = np.array([1, 1, 2, 2, 3, 3, 4, 9], dtype=np.int64)
    nulls = np.array([0, 0, 0, 0, 1, 1, 0, 0], dtype=bool)
    b = Batch.from_numpy(
        FSCH,
        [f, v],
        np.uint64(0),
        np.ones(8, np.int64),
        capacity=8,
        nulls=[None, nulls],
    )
    same = np.asarray(adjacent_equal(b, include_time=False))
    #           nan=nan  -0!=0? (-0.0==0.0 -> depends on v) ...
    # pairs: (0,1): nan==nan, v equal        -> True
    #        (1,2): nan vs -0.0              -> False
    #        (2,3): -0.0 == 0.0, v equal     -> True
    #        (3,4): value differs            -> False
    #        (4,5): 1.5==1.5, NULL==NULL     -> True
    #        (5,6): NULL vs 4                -> False
    #        (6,7): differs                  -> False
    assert same.tolist() == [
        True, False, True, False, True, False, False
    ]


# --------------------------------------------------------------------------
# slotted operator state end-to-end (the q9 shape: delta join at a
# state tier past the ingest_mode threshold)
# --------------------------------------------------------------------------


@pytest.mark.slow  # two cold compiles of a 3-input delta-join step
def test_slotted_delta_join_matches_merge_mode():
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.expr.scalar import ColumnRef
    from materialize_tpu.render.dataflow import Dataflow

    A = Schema((Column("a", ColumnType.INT64), Column("x", ColumnType.INT64)))
    B = Schema((Column("b", ColumnType.INT64), Column("y", ColumnType.INT64)))
    C = Schema((Column("c", ColumnType.INT64), Column("z", ColumnType.INT64)))
    expr = mir.Join(
        (mir.Get("A", A), mir.Get("B", B), mir.Get("C", C)),
        (
            (ColumnRef(0), ColumnRef(2)),
            (ColumnRef(2), ColumnRef(4)),
        ),
        implementation="delta",
    )

    def drive(state_cap):
        df = Dataflow(expr, state_cap=state_cap, out_slots=0)
        df._compact_every = 4
        rng = np.random.default_rng(13)
        for t in range(10):
            n = 50
            inp = {}
            for nm, sch in (("A", A), ("B", B), ("C", C)):
                ks = rng.integers(0, 12, n)
                vs = rng.integers(0, 5, n)
                ds = rng.choice([-1, 1, 1], n)
                inp[nm] = _batch(ks, vs, ds, t=t, cap=256, schema=sch)
            df.run_steps([inp])
        slotted = all(
            bool(s.slots)
            for parts in df.states
            for s in parts
            if isinstance(s, Spine)
        )
        acc: dict = {}
        for r in df.peek():
            acc[r[:-2]] = acc.get(r[:-2], 0) + r[-1]
        return {k: d for k, d in acc.items() if d}, slotted

    # Pin the baseline arm to merge explicitly (auto now resolves
    # big-state operator spines to the slot ring — ISSUE 7 satellite);
    # the dyncfg then flips the SAME dataflow's state spines to the
    # append-slot ring.
    COMPUTE_CONFIGS.update({"arrangement_ingest_mode": "merge"})
    try:
        want, was_slotted = drive(1 << 13)
    finally:
        COMPUTE_CONFIGS.update({"arrangement_ingest_mode": None})
    assert not was_slotted
    COMPUTE_CONFIGS.update({"arrangement_ingest_mode": "append_slot"})
    try:
        got, was_slotted = drive(1 << 13)
    finally:
        COMPUTE_CONFIGS.update({"arrangement_ingest_mode": None})
    assert was_slotted
    assert got == want


# --------------------------------------------------------------------------
# plan decision
# --------------------------------------------------------------------------


def test_ingest_mode_decision():
    from materialize_tpu.plan.decisions import (
        ingest_mode,
        state_ingest_mode,
    )

    assert ingest_mode(256) == "merge"
    assert ingest_mode(1 << 21) == "append_slot"
    assert ingest_mode(8 * 1024) == "append_slot"
    assert ingest_mode(8 * 1024 - 1) == "merge"
    # Operator-state spines now follow the same big-state auto rule
    # (the ISSUE 7 satellite paid off the round-6 deferral: tiers were
    # regenerated on this host with slotted state spines).
    assert state_ingest_mode(1 << 21) == "append_slot"
    assert state_ingest_mode(8 * 1024 - 1) == "merge"
    COMPUTE_CONFIGS.update({"arrangement_ingest_mode": "merge"})
    try:
        assert ingest_mode(1 << 21) == "merge"
    finally:
        COMPUTE_CONFIGS.update({"arrangement_ingest_mode": None})
    COMPUTE_CONFIGS.update(
        {"arrangement_ingest_mode": "append_slot"}
    )
    try:
        assert state_ingest_mode(256) == "append_slot"
    finally:
        COMPUTE_CONFIGS.update({"arrangement_ingest_mode": None})
