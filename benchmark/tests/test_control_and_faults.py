"""The comparison that decides ``correct``, shown to fail.

``test_control_*``: the lower-precision control (the reference with its
sums in float32) put in the program's place has to come out not correct,
at a size a test can hold. ``test_fault_*``: the rest of a run (the
comparison, fed what a window collects) with the timed path broken
underneath has to come out not correct, once for each fault a cell can
have: a step that leaves its state unchanged, half of a tick's batch
left out, an answer altered where it is produced, a stale answer, a
source row altered or lost on its way into the shard. No server and no
chip: the "program" here is the reference itself, broken.
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import compare  # noqa: E402

EPOCH_1992 = 8035  # 1992-01-01 as a day number


def lineitem(rng, n, time, diff=1):
    return {
        "l_suppkey": rng.integers(1, 11, n),
        "l_quantity": rng.integers(100, 5100, n),
        "l_extendedprice": rng.integers(90_000, 10_000_000, n),
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": np.array(list("ANR"))[rng.integers(0, 3, n)],
        "l_linestatus": np.array(list("FO"))[rng.integers(0, 2, n)],
        "l_shipdate": rng.integers(EPOCH_1992, EPOCH_1992 + 2526, n),
        "time": np.full(n, time, np.int64),
        "diff": np.full(n, diff, np.int64),
    }


def concat(parts):
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def world(seed, ticks=12, rows=3000, churn=64):
    """Sources as the window collects them: a base at time 9 and
    ``ticks`` ticks of churn (rows deleted, others inserted)."""
    rng = np.random.default_rng(seed)
    base = lineitem(rng, rows, 9)
    live = {k: v.copy() for k, v in base.items()}
    updates = []
    for t in range(10, 10 + ticks):
        idx = rng.choice(len(live["diff"]), churn, replace=False)
        gone = {k: v[idx].copy() for k, v in live.items()}
        gone["time"][:] = t
        gone["diff"][:] = -1
        new = lineitem(rng, churn, t)
        updates += [gone, new]
        keep = np.ones(len(live["diff"]), bool)
        keep[idx] = False
        live = concat([{k: v[keep] for k, v in live.items()}, new])
    supplier = {
        "s_suppkey": np.arange(1, 11),
        "s_name": np.array([f"Supplier#{k:09d}" for k in range(1, 11)]),
        "diff": np.ones(10, np.int64),
    }
    no_updates = {k: v[:0] for k, v in supplier.items()}
    no_updates["time"] = np.zeros(0, np.int64)
    return {
        "lineitem": compare.Timeline(base, concat(updates)),
        "supplier": compare.Timeline(supplier, no_updates),
    }


def view_history(ref, sources, first, end, precision="exact", skip=None):
    """What a sound program writes to the view's shard: the reference's
    answer at ``first - 1`` as the base, then its changes at every
    time. ``skip(t)`` true: the step at ``t`` leaves its state unchanged
    (nothing is written for that time)."""
    def at(t):
        return compare.multiset(ref.answer(
            {n: tl.at(t) for n, tl in sources.items()}, precision))

    def table(rows_diffs, time):
        cols = {c: [] for c in ref.COLUMNS}
        diffs, times = [], []
        for (row, d), t in zip(rows_diffs, time):
            for c, v in zip(ref.COLUMNS, row):
                cols[c].append(v)
            diffs.append(d)
            times.append(t)
        out = {c: np.array(v, dtype=object) for c, v in cols.items()}
        out["diff"] = np.array(diffs, np.int64)
        out["time"] = np.array(times, np.int64)
        return out

    held = at(first - 1)
    base = table(list(held.items()), [first - 1] * len(held))
    changes, times = [], []
    for t in range(first, end):
        if skip is not None and skip(t):
            continue
        now = at(t)
        for row in set(held) | set(now):
            d = now.get(row, 0) - held.get(row, 0)
            if d:
                changes.append((row, d))
                times.append(t)
        held = now
    return base, table(changes, times)


REFS = ["tpch_q15"]


@pytest.mark.parametrize("name", REFS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sound_run_is_correct(name, seed):
    ref = compare.load_reference(name)
    src = world(seed)
    base, upd = view_history(ref, src, 10, 22)
    reads = [(t, t + 1, ref.answer({n: tl.at(t) for n, tl in src.items()}))
             for t in range(10, 21)]
    v = compare.judge(ref, src, base, upd, (10, 22), reads)
    assert v["correct"], v
    assert v["view_times_checked"] == 12 and v["reads_checked"] == 11


@pytest.mark.parametrize("name", REFS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_float32_is_refused(name, seed):
    ref = compare.load_reference(name)
    src = world(seed)
    # the control in the program's place: it wrote the shard
    base, upd = view_history(ref, src, 10, 22, precision="float32")
    v = compare.judge(ref, src, base, upd, (10, 22), [])
    assert not v["correct"]
    assert v["checks"]["view_times_wrong"]["value"] >= 6
    # and the other way round, as run.py --control does it
    base, upd = view_history(ref, src, 10, 22)
    v = compare.judge(ref, src, base, upd, (10, 22), [], precision="float32")
    assert not v["correct"]


@pytest.mark.parametrize("name", REFS)
def test_fault_step_leaves_state_unchanged(name):
    ref = compare.load_reference(name)
    src = world(4)
    base, upd = view_history(ref, src, 10, 22, skip=lambda t: t == 15)
    v = compare.judge(ref, src, base, upd, (10, 22), [])
    # q15's answer does not change at every tick
    changed = view_history(ref, src, 15, 16)[1]["diff"]
    assert v["correct"] == (len(changed) == 0)
    base, upd = view_history(ref, src, 10, 22, skip=lambda t: t >= 12)
    assert not compare.judge(ref, src, base, upd, (10, 22), [])["correct"]


@pytest.mark.parametrize("name", REFS)
def test_fault_half_of_the_batch_left_out(name):
    ref = compare.load_reference(name)
    src = world(5, churn=600)
    # the program saw only every other update of tick 14 and later
    half = {}
    for n, tl in src.items():
        keep = np.ones(len(tl.times), bool)
        late = np.nonzero(tl.times >= 14)[0]
        keep[late[::2]] = False
        h = compare.Timeline.__new__(compare.Timeline)
        h.cols = {k: v[keep] for k, v in tl.cols.items()}
        h.times = tl.times[keep]
        half[n] = h
    base, upd = view_history(ref, half, 10, 22)
    v = compare.judge(ref, src, base, upd, (10, 22), [])
    assert not v["correct"] and v["first_wrong_time"]["time"] >= 14


@pytest.mark.parametrize("name", REFS)
def test_fault_answer_altered_or_stale(name):
    ref = compare.load_reference(name)
    src = world(6)
    base, upd = view_history(ref, src, 10, 22)

    def answer(t):
        return ref.answer({n: tl.at(t) for n, tl in src.items()})

    good = answer(16)
    bad = [tuple(good[0][:-1]) + (good[0][-1] + 1,)] + good[1:]
    for reads, key in (
        ([(16, 17, bad)], "reads_wrong"),      # a digit altered
        ([(0, -1, None)], "reads_unanswered"),  # never answered
    ):
        v = compare.judge(ref, src, base, upd, (10, 22), reads)
        assert not v["correct"] and v["checks"][key]["value"] == 1
    # a stale answer: exact at time 12, sent when 16 was complete
    if answer(12) != answer(16) and answer(12) != answer(17):
        v = compare.judge(ref, src, base, upd, (10, 22), [(16, 17, answer(12))])
        assert not v["correct"]
    # an empty window proves nothing
    v = compare.judge(ref, src, base, upd, (10, 10), [])
    assert not v["correct"]


def test_wire_rows_are_exact_decimals():
    ref = compare.load_reference("tpch_q15")
    rows = compare.wire_rows([("2", "Supplier#000000002", "853257.4800")], ref)
    assert rows == [(2, "Supplier#000000002", 8532574800)]


CONFIG = {"scale_factor": 0.001, "churn_orders": 2}


def shard_of(tables, time):
    """The regenerated tables as the source shards hold them at
    ``time``: one Timeline a relation, nothing after it."""
    out = {}
    for rel, table in tables.items():
        n = len(next(iter(table.values())))
        base = {**table, "diff": np.ones(n, np.int64)}
        none = {k: v[:0] for k, v in base.items()}
        none["time"] = np.zeros(0, np.int64)
        out[rel] = compare.Timeline(base, none)
    return out


@pytest.mark.parametrize("seed", [1, 2147483999, 2 ** 31 + 5])
def test_fault_source_row_altered_or_lost(seed):
    ref = compare.load_reference("tpch_q15")
    made = compare.load_reference("tpch_tables").tables_at(seed, CONFIG, 7)
    src = shard_of(made, 7)
    base, upd = view_history(ref, src, 7, 8)

    def verdict(sources):
        return compare.judge(
            ref, sources, base, upd, (7, 8), [], regenerated={7: made}
        )

    v = verdict(src)
    assert v["correct"] and v["checks"]["source_rows_wrong"]["value"] == 0
    # one price altered where the source is appended: a row lost, a row
    # that was never made
    broken = {k: {c: a.copy() for c, a in t.items()} for k, t in made.items()}
    broken["lineitem"]["l_extendedprice"][3] += 1
    v = verdict(shard_of(broken, 7))
    assert not v["correct"]
    assert v["checks"]["source_rows_wrong"]["value"] == 2
    assert v["first_wrong_source_row"]["relation"] == "lineitem"
    # a string decoded wrongly in every row
    broken = {k: dict(t) for k, t in made.items()}
    broken["supplier"]["s_name"] = np.char.add(made["supplier"]["s_name"], "x")
    v = verdict(shard_of(broken, 7))
    assert v["checks"]["source_rows_wrong"]["value"] == 20
    # half of the rows never appended
    broken = {k: {c: a[::2] for c, a in t.items()} for k, t in made.items()}
    assert not verdict(shard_of(broken, 7))["correct"]


def test_base_tables_copy_agrees_with_the_program():
    """The frozen copy against the generator it was copied from: fails
    when a later PR changes the data the benchmark runs on."""
    from materialize_tpu.repr.schema import GLOBAL_DICT
    from materialize_tpu.storage.generator.tpch import (
        LINEITEM_SCHEMA,
        TpchGenerator,
    )

    tables = compare.load_reference("tpch_tables")
    seed, config, ticks = 2147485555, {"scale_factor": 0.002,
                                       "churn_orders": 3}, 40
    gen = TpchGenerator(sf=config["scale_factor"], seed=seed)
    batches = list(gen.snapshot_lineitem_batches(time=0)) + [
        gen.churn_lineitem_batch(config["churn_orders"], t, t)
        for t in range(1, ticks + 1)
    ]
    names = [c.name for c in LINEITEM_SCHEMA.columns]
    cols = {n: [] for n in names + ["diff"]}
    for b in batches:
        arrays = b.to_columns()
        for n, c, a in zip(names, LINEITEM_SCHEMA.columns, arrays):
            a = np.asarray(a)
            if c.ctype.value == "string":
                a = np.array(GLOBAL_DICT.decode_many(a), dtype=str)
            cols[n].append(a)
        cols["diff"].append(np.asarray(arrays[-1]))
    program = compare.rows_of(
        {k: np.concatenate(v) for k, v in cols.items()}, tables.LINEITEM
    )
    made = tables.tables_at(seed, config, ticks)["lineitem"]
    copy = compare.rows_of(
        {**made, "diff": np.ones(len(made["l_orderkey"]), np.int64)},
        tables.LINEITEM,
    )
    assert program == copy and len(copy) > 11_000
