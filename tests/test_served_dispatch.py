"""The one way a step reaches the device on the served path:
``MaintainedView.step_span`` -> ``run_steps(defer_check=True)`` ->
``_dispatch_span``, through plans with a reduce (TPC-H Q1) and a join
under two reduces (Q15) on RF1/RF2-style churn, against the per-tick
``step`` over the same ticks: prefetch over a backlog, an overflow
replayed inside a span, the pipelined index path, and what every
committed span leaves behind (its flags readback, one freshness
sample, programs of three kinds)."""

import functools

import pytest

from materialize_tpu.coord.freshness import FRESHNESS
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.storage.generator.tpch import (
    LINEITEM_SCHEMA,
    SUPPLIER_SCHEMA,
    TpchGenerator,
)
from materialize_tpu.storage.persist import (
    MaintainedView,
    MemBlob,
    MemConsensus,
    PersistClient,
)
from materialize_tpu.storage.persist.operators import _host_updates
from materialize_tpu.utils.compile_ledger import LEDGER
from materialize_tpu.workloads.tpch import q1_mir, q15_mir

from .test_view_spans import _accum, _shard, span_records  # noqa: F401

# 60 orders: the smallest scale whose snapshot (228 lineitems) and
# ticks (12 orders retracted and inserted again, 80-110 updates) both
# fit the 256-row batch tier, so a view compiles one step program.
SF, SEED, CHURN_ORDERS, TICKS, SPAN = 4e-5, 5, 12, 20, 8
SCHEMAS = {"lineitem": LINEITEM_SCHEMA, "supplier": SUPPLIER_SCHEMA}
PLANS = {
    "q1": (q1_mir, ("lineitem",)),
    "q15": (q15_mir, ("lineitem", "supplier")),
}
# One tier of each plan, cut after render to half of what this feed
# needs (one regrow, one compile): Q1's output delta (4 groups
# retracted and inserted), Q15's join site.
UNDERSIZED = {
    "q1": lambda ctx: setattr(ctx, "out_delta_cap", 4),
    "q15": lambda ctx: ctx.join_caps.__setitem__(0, 4),
}
plans = pytest.mark.parametrize("plan", sorted(PLANS))


@pytest.fixture(scope="module")
def feed():
    """tick -> {source: (cols, nulls, time, diff)}: the snapshot at
    tick 0, then one refresh pair on ``lineitem`` a tick."""
    gen = TpchGenerator(sf=SF, seed=SEED)
    (snapshot,) = gen.snapshot_lineitem_batches(time=0)
    ticks = [
        {
            "lineitem": _host_updates(snapshot),
            "supplier": _host_updates(gen.table_batch("supplier")),
        }
    ]
    for t in range(1, TICKS + 1):
        churn = gen.churn_lineitem_batch(CHURN_ORDERS, t, time=t)
        assert 0 < int(churn.count) <= 256
        ticks.append({"lineitem": _host_updates(churn)})
    return ticks


@pytest.fixture(scope="module")
def served(feed):
    """A view of ``plan`` installed over empty source shards that then
    receive every tick: all of it backlog."""
    nothing = {
        s: _host_updates(Batch.empty(sch, 256))
        for s, sch in SCHEMAS.items()
    }

    def build(plan, name, sink=True, undersized=False):
        mk, sources = PLANS[plan]
        client = PersistClient(MemBlob(), MemConsensus())
        writers = {s: client.open_writer(s, SCHEMAS[s]) for s in sources}
        df = Dataflow(mk(), name=name)
        if undersized:
            UNDERSIZED[plan](df._ctx)
            df._remake_jit()
        view = MaintainedView(
            client, df, {s: (s, SCHEMAS[s]) for s in sources},
            "out" if sink else None,
        )
        for t, tick in enumerate(feed):
            for s, w in writers.items():
                w.compare_and_append(*tick.get(s, nothing[s]), t, t + 1)
        return client, view

    return build


@pytest.fixture(scope="module")
def per_tick(served):
    """plan -> (the shard, the final peek) of a sinked view that took
    every tick through ``step``; made once a plan."""

    @functools.cache
    def reference(plan):
        client, view = served(plan, f"{plan}_per_tick")
        for _ in range(TICKS + 1):
            assert view.step(timeout=0)
        shard = _shard(client, ordered=False)
        assert any(rows for _lo, _up, rows in shard[1:])
        return shard, _accum(view.peek())

    return reference


def _step_span(view):
    return view.step_span(max_ticks=SPAN, timeout=0)


def _step_spans(view):
    while view.upper < TICKS + 1:
        assert _step_span(view)
    assert not _step_span(view)


@plans
def test_sinked_spans_over_a_backlog_write_the_per_tick_shard(
    plan, served, per_tick, span_records
):
    name = f"{plan}_spans"
    client, view = served(plan, name)
    _step_spans(view)
    got = _shard(client, ordered=False)
    assert [(lo, up) for lo, up, _ in got] == [
        (t, t + 1) for t in range(TICKS + 1)
    ]
    assert got == per_tick(plan)[0]
    spans = span_records(name)
    assert [s["ticks"] for s in spans] == [8, 8, 5]
    assert [s["prefetched_ticks"] for s in spans] == [0, 8, 5]
    assert not any(s["replayed"] for s in spans)


@plans
def test_an_overflow_inside_a_span_replays_it_with_the_next_one_kept(
    plan, served, per_tick, span_records
):
    name = f"{plan}_undersized"
    client, view = served(plan, name, undersized=True)
    _step_spans(view)
    assert _shard(client, ordered=False) == per_tick(plan)[0]
    spans = span_records(name)
    replayed = [s for s in spans if s["replayed"]]
    assert replayed and replayed[0]["ticks"] == SPAN
    # the replay left what the span had gathered for the next one
    after = spans[spans.index(replayed[0]) + 1]
    assert after["prefetched_ticks"] == after["ticks"]


@plans
def test_an_index_view_peeks_the_per_tick_answer_after_pipelined_spans(
    plan, served, per_tick
):
    _client, view = served(plan, f"{plan}_index", sink=False)
    while view._dispatched < TICKS + 1:
        assert _step_span(view)
    assert view._inflight_span is not None
    assert view.upper == TICKS + 1 - 5  # the last span is in flight
    got = _accum(view.peek())
    assert view.upper == TICKS + 1
    assert got and got == per_tick(plan)[1]


# -- what a committed span leaves behind ----------------------------------

# stepping path -> (the view has a sink, one call of it)
PATHS = {
    "sync": (True, _step_span),
    "pipelined": (False, _step_span),
    "per_tick": (True, lambda view: view.step(timeout=0)),
}


def _drive(served, span_records, path, name):
    """Q1 through one stepping path: the view, its span records and
    the flag transfers it made."""
    sink, step = PATHS[path]
    _client, view = served("q1", name, sink=sink)
    readbacks = view.df._readbacks
    while view._dispatched < TICKS + 1:
        assert step(view)
    view.sync_spans()
    assert view.upper == TICKS + 1
    return view, span_records(name), view.df._readbacks - readbacks


@pytest.mark.parametrize("path", ["sync", "pipelined"])
def test_the_flag_readbacks_of_a_committed_span(
    path, served, span_records
):
    view, spans, readbacks = _drive(
        served, span_records, path, f"readbacks_{path}"
    )
    assert [s["ticks"] for s in spans] == [8, 8, 5]
    assert len(spans) == view.span_epoch
    # The pipelined commit fuses the step's and the fold's flags into
    # one transfer (read_flags_snapshot); check_flags reads the fold's
    # apart, in the spans that folded: the first two, at ticks 8, 16.
    assert readbacks == {"pipelined": 3, "sync": 3 + 2}[path]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_committed_span_feeds_the_freshness_recorder_once(
    path, served, span_records
):
    name = f"freshness_{path}"
    _view, spans, _ = _drive(served, span_records, path, name)
    assert len(spans) == (TICKS + 1 if path == "per_tick" else 3)
    recorded = [
        frontier
        for df, _r, frontier, lag_ms, _at in FRESHNESS.history_rows()
        if df == name and lag_ms >= 0.0
    ]
    assert recorded == [s["upper"] for s in spans]


def test_a_span_train_compiles_step_and_compact_programs_only(
    served, span_records
):
    mark = len(LEDGER.records())
    _drive(served, span_records, "sync", "program_kinds")
    kinds = {
        r.kind
        for r in LEDGER.records()[mark:]
        if r.name == "program_kinds"
    }
    assert {"step", "compact"} <= kinds
    assert kinds <= {"step", "step_donated", "compact"}
