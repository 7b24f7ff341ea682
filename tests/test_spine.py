"""Two-run amortized spine: correctness against a multiset oracle.

The Spine is the big-state arrangement form (per-step insert cost must
not be linear in state size). These tests pin
its semantics: base ⊎ tail multiset sum, host-scheduled compaction,
overflow growth, and join/dataflow integration at state sizes well past
the tail tier.
"""

from __future__ import annotations

import numpy as np
import pytest

from materialize_tpu.arrangement.spine import (
    Spine,
    compact_spine,
    insert_tail,
)
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema

SCH = Schema((Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)))


def _batch(ks, vs, ds, t=0, cap=256):
    return Batch.from_numpy(
        SCH,
        [np.asarray(ks, np.int64), np.asarray(vs, np.int64)],
        np.uint64(t),
        np.asarray(ds, np.int64),
        capacity=cap,
    )


def _spine_rows(sp):
    """Host multiset view of base ⊎ tail."""
    acc: dict = {}
    for run in (sp.base, sp.tail):
        for r in run.to_rows():
            key = r[:-2]
            acc[key] = acc.get(key, 0) + r[-1]
    return {k: d for k, d in acc.items() if d != 0}


def test_spine_oracle_random_churn():
    import jax

    ins = jax.jit(insert_tail)
    comp = jax.jit(compact_spine)
    rng = np.random.default_rng(7)
    sp = Spine.empty(SCH, (0,), capacity=2048, tail_capacity=256)
    oracle: dict = {}
    for step in range(40):
        n = int(rng.integers(1, 30))
        ks = rng.integers(0, 60, n)
        vs = rng.integers(0, 4, n)
        ds = rng.choice([-1, 1, 2], n)
        for k, v, d in zip(ks, vs, ds):
            key = (int(k), int(v))
            oracle[key] = oracle.get(key, 0) + int(d)
            if oracle[key] == 0:
                del oracle[key]
        sp, ovf = ins(sp, _batch(ks, vs, ds, t=step, cap=64))
        assert not bool(ovf)
        # The combined view matches the oracle at EVERY step, compacted
        # or not (readers see base ⊎ tail).
        assert _spine_rows(sp) == oracle
        if step % 5 == 4:
            sp, bovf = comp(sp)
            assert not bool(bovf)
            assert int(sp.tail.count) == 0
            assert _spine_rows(sp) == oracle


def test_spine_tail_overflow_flagged():
    sp = Spine.empty(SCH, (0,), capacity=1024, tail_capacity=64)
    big = _batch(
        np.arange(100), np.zeros(100), np.ones(100), cap=128
    )
    sp2, ovf = insert_tail(sp, big)
    assert bool(ovf)


def test_spine_base_overflow_flagged():
    sp = Spine.empty(SCH, (0,), capacity=64, tail_capacity=256)
    sp, ovf = insert_tail(
        sp, _batch(np.arange(100), np.zeros(100), np.ones(100), cap=128)
    )
    assert not bool(ovf)
    sp, bovf = compact_spine(sp)
    assert bool(bovf)


def test_spine_cancellation_across_runs():
    """A row inserted (base) then retracted (tail) nets to zero for
    readers and vanishes at the next compaction."""
    sp = Spine.empty(SCH, (0,), capacity=256, tail_capacity=64)
    sp, _ = insert_tail(sp, _batch([1, 2], [0, 0], [1, 1], cap=64))
    sp, _ = compact_spine(sp)
    assert int(sp.base.count) == 2
    sp, _ = insert_tail(sp, _batch([1], [0], [-1], t=1, cap=64))
    assert _spine_rows(sp) == {(2, 0): 1}
    sp, _ = compact_spine(sp)
    assert int(sp.base.count) == 1
    assert _spine_rows(sp) == {(2, 0): 1}


def test_join_dataflow_large_state_amortized():
    """A join whose left arrangement grows to ~20k rows (≫ tail tier):
    results stay correct through scheduled compactions, tail growth, and
    base growth; and the hot step's insert capacity is the TAIL tier,
    not the state tier."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.expr.scalar import ColumnRef
    from materialize_tpu.render.dataflow import Dataflow

    left_sch = Schema(
        (Column("k", ColumnType.INT64), Column("a", ColumnType.INT64))
    )
    right_sch = Schema(
        (Column("k2", ColumnType.INT64), Column("b", ColumnType.INT64))
    )
    expr = mir.Join(
        (mir.Get("L", left_sch), mir.Get("R", right_sch)),
        ((ColumnRef(0), ColumnRef(2)),),
    )
    df = Dataflow(expr, state_cap=1 << 15)
    df._compact_every = 4

    rng = np.random.default_rng(3)
    n_per, steps = 1024, 20
    oracle_l: dict = {}
    right_rows = [(int(k), int(k) * 10) for k in range(50)]
    oracle_r = {r: 1 for r in right_rows}

    def right_batch(rows, t):
        if not rows:
            ks, bs, ds = [], [], []
        else:
            ks = [r[0] for r in rows]
            bs = [r[1] for r in rows]
            ds = [1] * len(rows)
        return Batch.from_numpy(
            right_sch,
            [np.asarray(ks, np.int64), np.asarray(bs, np.int64)],
            np.uint64(t),
            np.asarray(ds, np.int64),
            capacity=64,
        )

    for t in range(steps):
        ks = rng.integers(0, 50, n_per)
        vs = rng.integers(0, 1 << 30, n_per)
        for k, v in zip(ks, vs):
            oracle_l[(int(k), int(v))] = 1
        lb = Batch.from_numpy(
            left_sch,
            [ks.astype(np.int64), vs.astype(np.int64)],
            np.uint64(t),
            np.ones(n_per, np.int64),
            capacity=2048,
        )
        rb = right_batch(right_rows if t == 0 else [], t)
        df.run_steps([{"L": lb, "R": rb}])

    # Hot-path insert is ingest-tier-sized: the join state spine's
    # per-step insert target (the append-slot ring at this state tier
    # — plan/decisions.ingest_mode — else run 0) stayed ≪ the base
    # tier that holds the ~20k rows.
    spine_l = df.states[0][0]
    assert int(np.asarray(spine_l.base.count)) + int(
        np.asarray(spine_l.tail.count)
    ) >= len(oracle_l)
    ingest_cap = (
        spine_l.slots[0].capacity
        if spine_l.slots
        else spine_l.tail_capacity
    )
    assert ingest_cap < spine_l.capacity

    got = {}
    for r in df.peek():
        got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
        assert r[-1] != 0 or True
    expect = {}
    for (k, a), dl in oracle_l.items():
        for (k2, b), dr in oracle_r.items():
            if k == k2:
                expect[(k, a, k2, b)] = dl * dr
    got = {k: d for k, d in got.items() if d != 0}
    assert got == expect


def test_compaction_schedule_survives_overflow_replay():
    """Deferred spans that overflow replay the same compaction schedule
    (the counter is part of the rollback checkpoint)."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow

    expr = mir.Get("L", SCH)
    df = Dataflow(expr, state_cap=256)
    df._compact_every = 2
    rng = np.random.default_rng(1)
    oracle: dict = {}
    spans = []
    for t in range(6):
        n = 200  # out tail tier will overflow and grow mid-run
        ks = rng.integers(0, 500, n)
        vs = rng.integers(0, 3, n)
        for k, v in zip(ks, vs):
            key = (int(k), int(v))
            oracle[key] = oracle.get(key, 0) + 1
        spans.append(
            {"L": _batch(ks, vs, np.ones(n, np.int64), t=t, cap=256)}
        )
    df.run_steps(spans, defer_check=True)
    df.check_flags()
    got: dict = {}
    for r in df.peek():
        got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
    assert {k: d for k, d in got.items() if d} == oracle


def test_hash_spine_growth_preserves_order_mode():
    """Regression (round 5): growing a hash-ordered spine's base via the
    dataflow's _grow_spine must keep order='hash' — dropping it back to
    'exact' made every post-growth merge use exact lanes over
    hash-sorted runs (observed as wrong join results after the output
    index's first base overflow)."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow

    df = Dataflow(mir.Get("L", SCH), state_cap=256)
    assert df.output.order == "hash"
    grown = df._grow_spine(df.output, "base")
    assert grown.order == "hash"
    grown = df._grow_spine(df.output, "tail")
    assert grown.order == "hash"

    # End-to-end: churn far past the initial base capacity with
    # retractions; peeks (which force compactions and growth) must
    # stay oracle-exact.
    rng = np.random.default_rng(7)
    oracle: dict = {}
    for t in range(8):
        n = 150
        ks = rng.integers(0, 400, n)
        vs = rng.integers(0, 3, n)
        ds = rng.integers(-1, 2, n)
        ds[ds == 0] = 1
        for k, v, d in zip(ks, vs, ds):
            key = (int(k), int(v))
            oracle[key] = oracle.get(key, 0) + int(d)
        df.step({"L": _batch(ks, vs, ds, t=t, cap=256)})
        got: dict = {}
        for r in df.peek():
            got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
        assert {k: d for k, d in got.items() if d} == {
            k: d for k, d in oracle.items() if d
        }, f"diverged at step {t}"


def test_hash_spine_tail_larger_than_base():
    """Merging a tail whose CAPACITY exceeds the base/out capacity must
    stay exact (the real output spine runs with tail=out_delta_cap=4096
    over a small initial base)."""
    rng = np.random.default_rng(3)
    sp = Spine.empty(SCH, (0, 1), 512, 4096, order="hash")
    ms: dict = {}
    for t in range(6):
        n = 60
        ks = rng.integers(0, 30, n)
        vs = rng.integers(0, 3, n)
        ds = rng.integers(-1, 2, n)
        ds[ds == 0] = 1
        for k, v, d in zip(ks, vs, ds):
            key = (int(k), int(v))
            ms[key] = ms.get(key, 0) + int(d)
        sp, ovf = insert_tail(sp, _batch(ks, vs, ds, t=t, cap=256))
        assert not bool(ovf)
        sp, ovf = compact_spine(sp)
        assert not bool(ovf)
        got: dict = {}
        for r in sp.base.to_rows():
            got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
        assert {k: d for k, d in got.items() if d} == {
            k: d for k, d in ms.items() if d
        }


def test_multilevel_output_spine_oracle():
    """4-level geometric output spine under churn with retractions and
    growth: peeks (full cascade) stay oracle-exact through the span
    train's geometric fold cadence."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow

    rng = np.random.default_rng(23)
    spans = []
    oracle: dict = {}
    for t in range(32):
        n = 100
        ks = rng.integers(0, 800, n)
        vs = rng.integers(0, 3, n)
        ds = rng.integers(-1, 2, n)
        ds[ds == 0] = 1
        for k, v, d in zip(ks, vs, ds):
            key = (int(k), int(v))
            oracle[key] = oracle.get(key, 0) + int(d)
        spans.append({"L": _batch(ks, vs, ds, t=t, cap=256)})
    oracle = {k: d for k, d in oracle.items() if d}

    df = Dataflow(mir.Get("L", SCH), state_cap=256, out_levels=4)
    df._compact_every = 4
    df._compact_ratio = 2
    assert df.output.levels == 4
    df.run_steps(spans, defer_check=True)
    df.check_flags()
    got: dict = {}
    for r in df.peek():
        got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
    assert {k: d for k, d in got.items() if d} == oracle


def test_host_presort_matches_device_order():
    """Generator batches carrying the "hash_consolidated" hint must be
    in EXACTLY the device hash order (numpy replica of hash_pair), and
    a dataflow fed hinted batches must match one fed the same batches
    with the hint stripped (which re-sorts on device)."""
    import numpy as np

    from materialize_tpu.expr import relation as mir
    from materialize_tpu.ops.lanes import hash_pair, row_lanes
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.storage.generator.tpch import (
        LINEITEM_SCHEMA,
        TpchGenerator,
    )

    gen = TpchGenerator(sf=0.002, seed=5)
    batches = list(gen.snapshot_lineitem_batches(batch_orders=512))
    for t in range(6):
        batches.append(
            gen.churn_lineitem_batch(64, tick=t, time=1 + t)
        )
    for b in batches:
        assert b.hints == ("hash_consolidated",)
        n = b._host_count
        h1, h2 = hash_pair(row_lanes(b, include_time=False))
        h1, h2 = np.asarray(h1)[:n], np.asarray(h2)[:n]
        pairs = list(zip(h1.tolist(), h2.tolist()))
        assert pairs == sorted(pairs), "host order != device hash order"

    df_hint = Dataflow(mir.Get("lineitem", LINEITEM_SCHEMA))
    df_plain = Dataflow(mir.Get("lineitem", LINEITEM_SCHEMA))
    for i, b in enumerate(batches):
        df_hint.step({"lineitem": b})
        df_plain.step({"lineitem": b.replace(hints=())})
    assert sorted(
        r[:-2] + (r[-1],) for r in df_hint.peek()
    ) == sorted(r[:-2] + (r[-1],) for r in df_plain.peek())


def test_append_slot_spine_oracle():
    """Append-slot ingest ring: O(delta) per-step inserts into slot
    batches, flushed into run 0 at the level-0 fold. Oracle-exact
    under churn with retractions and growth."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow

    rng = np.random.default_rng(31)
    spans = []
    oracle: dict = {}
    for t in range(32):
        n = 100
        ks = rng.integers(0, 700, n)
        vs = rng.integers(0, 3, n)
        ds = rng.integers(-1, 2, n)
        ds[ds == 0] = 1
        for k, v, d in zip(ks, vs, ds):
            key = (int(k), int(v))
            oracle[key] = oracle.get(key, 0) + int(d)
        spans.append({"L": _batch(ks, vs, ds, t=t, cap=256)})
    oracle = {k: d for k, d in oracle.items() if d}

    df = Dataflow(
        mir.Get("L", SCH), state_cap=256, out_levels=3, out_slots=4,
    )
    df._compact_every = 4
    df._compact_ratio = 2
    assert df.output.slots and len(df.output.slots) == 4
    df.run_steps(spans, defer_check=True)
    df.check_flags()
    got: dict = {}
    for r in df.peek():
        got[r[:-2]] = got.get(r[:-2], 0) + r[-1]
    assert {k: d for k, d in got.items() if d} == oracle
