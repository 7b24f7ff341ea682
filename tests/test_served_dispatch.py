"""The one way a step reaches the device on the served path:
``MaintainedView.step_span`` -> ``run_steps(defer_check=True)`` ->
``_dispatch_span``, through plans with a reduce (TPC-H Q1) and a join
under two reduces (Q15) on RF1/RF2-style churn, against the per-tick
``step`` over the same ticks: prefetch over a backlog, an overflow
replayed inside a span, the order in which a sinked span over a
backlog is written (beneath its successor's dispatch) and what that
order keeps, the pipelined index path, and what every committed span
leaves behind (its flags readback, one freshness sample, programs of
three kinds)."""

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
    assert [s["overlapped_commit_ticks"] for s in spans] == [8, 8, 0]
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


# -- the order in which a sinked span is written ----------------------------


def _calls(view):
    """Every ``run_steps``, ``_prefetch_ticks``, ``check_flags`` and
    sink ``compare_and_append`` of the view from now on, in order:
    "run", "gather", "check" or the chunk ``(lower, upper)``; a check
    that replayed is "replay"."""
    calls: list = []
    df, writer = view.df, view.writer
    run_steps, check_flags = df.run_steps, df.check_flags
    gather, caa = view._prefetch_ticks, writer.compare_and_append

    def spy_run(*a, **kw):
        calls.append("run")
        return run_steps(*a, **kw)

    def spy_check():
        i = len(calls)
        calls.append("check")
        if check_flags():
            calls[i] = "replay"
            return True
        return False

    def spy_gather(*a):
        calls.append("gather")
        return gather(*a)

    def spy_caa(cols, nulls, time, diff, lower, upper):
        # before the call: a chunk that raises was still attempted
        calls.append((lower, upper))
        return caa(cols, nulls, time, diff, lower, upper)

    df.run_steps, df.check_flags = spy_run, spy_check
    view._prefetch_ticks = spy_gather
    writer.compare_and_append = spy_caa
    return calls


def _chunks(lo, up):
    return [(t, t + 1) for t in range(lo, up)]


def _never_ahead_of_the_shard(client, view, name):
    """``upper`` and the freshness record trail the durable upper."""
    durable = client.machine("out").reload().upper
    assert view.upper <= durable
    recorded = [
        frontier
        for df, _r, frontier, _lag, _at in FRESHNESS.history_rows()
        if df == name
    ]
    assert all(f <= durable for f in recorded)
    return durable


@plans
def test_a_backlog_span_is_written_beneath_its_successors_dispatch(
    plan, served, per_tick
):
    name = f"{plan}_order"
    client, view = served(plan, name)
    calls = _calls(view)
    uppers = []
    while view._dispatched < TICKS + 1:
        assert _step_span(view)
        assert view.df._defer_ck is None  # validated before it returns
        uppers.append((view.upper, view._dispatched))
        assert _never_ahead_of_the_shard(client, view, name) == view.upper
    # the successor is on the device before the first append of the
    # span before it; its flags are read after the last; the last
    # span, with nothing gathered behind it, is written at once
    assert calls == (
        ["run", "gather", "check"]
        + ["run"] + _chunks(0, 8) + ["gather", "check"]
        + ["run"] + _chunks(8, 16) + ["gather", "check"]
        + _chunks(16, 21)
    )
    assert uppers == [(0, 8), (8, 16), (21, 21)]
    assert view._validated_span is None and view._kept == []
    assert not _step_span(view)
    assert _shard(client, ordered=False) == per_tick(plan)[0]


@plans
def test_an_overflow_in_the_successor_replays_the_successor_alone(
    plan, served, per_tick
):
    name = f"{plan}_late_overflow"
    client, view = served(plan, name)
    assert _step_span(view)  # 0-7 validated, 8-15 kept
    assert (view.upper, view._dispatched) == (0, 8)
    # the tier is cut with span 0 validated against the full one
    UNDERSIZED[plan](view.df._ctx)
    view.df._remake_jit()
    calls = _calls(view)
    _step_spans(view)
    replays = [i for i, c in enumerate(calls) if c == "replay"]
    assert replays, "no span was replayed"
    # span 0 was written, once, before the flags that replayed span 1
    # were read, and the replay made the 8 deltas of span 1 only
    assert calls[: replays[0] + 1] == (
        ["run"] + _chunks(0, 8) + ["gather", "replay"]
    )
    assert len(view.df.replayed_deltas) in (8, 5)
    written = [c for c in calls if isinstance(c, tuple)]
    assert written == _chunks(0, TICKS + 1)  # each chunk once, in order
    assert _shard(client, ordered=False) == per_tick(plan)[0]
    assert _accum(view.peek()) == per_tick(plan)[1]


@plans
def test_a_sink_conflict_under_the_successor_ends_in_an_exact_rebuild(
    plan, served, per_tick
):
    from materialize_tpu.storage.persist.operators import SinkConflict

    name = f"{plan}_conflict"
    client, view = served(plan, name)
    assert _step_span(view)
    calls = _calls(view)
    caa = view.writer.compare_and_append

    def conflict(cols, nulls, time, diff, lower, upper):
        if lower == 3:
            raise SinkConflict("a sibling's chunking won")
        return caa(cols, nulls, time, diff, lower, upper)

    view.writer.compare_and_append = conflict
    with pytest.raises(SinkConflict):
        _step_span(view)
    # raised with span 1 dispatched and unvalidated; 0-2 are durable
    assert calls == ["run"] + _chunks(0, 3)
    assert view.df._defer_ck is not None
    assert _never_ahead_of_the_shard(client, view, name) == 3
    # what the replica does with it (_rebuild_cascade): the view and
    # its dataflow go, span 1 with them; a fresh one resumes from the
    # durable upper
    view.expire()
    assert view._validated_span is None and view._kept == []
    mk, sources = PLANS[plan]
    fresh = MaintainedView(
        client, Dataflow(mk(), name=name),
        {s: (s, SCHEMAS[s]) for s in sources}, "out",
    )
    assert fresh.upper == 3
    _step_spans(fresh)
    got = _shard(client, ordered=False)
    assert got[0][:2] == (0, 1)
    assert got == per_tick(plan)[0]
    assert _accum(fresh.peek()) == per_tick(plan)[1]


@pytest.mark.parametrize("barrier", ["sync_spans", "peek", "step", "expire"])
def test_every_barrier_leaves_nothing_unwritten(
    barrier, served, per_tick, span_records
):
    name = f"barrier_{barrier}"
    client, view = served("q1", name)
    assert _step_span(view) and _step_span(view)
    assert (view.upper, view._dispatched) == (8, 16)
    assert view._validated_span is not None
    calls = _calls(view)
    if barrier == "step":
        assert view.step(timeout=0)  # writes 8-15, then tick 16
        assert calls[:8] == _chunks(8, 16)
        assert view.upper == view._dispatched == 17
    else:
        getattr(view, barrier)()
        # a copy-out and appends, never a replay; or, on expire, nothing
        # (a peek's check_flags finds no flags to read)
        assert [c for c in calls if c != "check"] == (
            [] if barrier == "expire" else _chunks(8, 16)
        )
        assert view.upper == view._dispatched == (
            8 if barrier == "expire" else 16
        )
    assert view._validated_span is None
    _never_ahead_of_the_shard(client, view, name)
    if barrier == "expire":
        return
    # flushed without a successor on the device: not overlapped
    assert [s["overlapped_commit_ticks"] for s in span_records(name)][
        :2
    ] == [8, 0]
    _step_spans(view)
    assert _shard(client, ordered=False) == per_tick("q1")[0]


def test_a_call_that_finds_no_tick_leaves_nothing_unwritten(served):
    client, view = served("q1", "no_tick")
    assert _step_span(view)
    assert view._validated_span is not None and view._kept
    # the kept ticks go (as on expire) but the validated span stays:
    # the next call finds nothing to dispatch and writes it
    kept, view._kept = view._kept, []
    for s_ in view.sources.values():
        s_.poll = lambda timeout=0.0: None
    wait = view._wait_for_inputs
    view._wait_for_inputs = lambda frontier, timeout: None
    assert not _step_span(view)
    assert view._validated_span is None
    assert view.upper == view._dispatched == 8
    assert client.machine("out").reload().upper == 8
    view._wait_for_inputs, view._kept = wait, kept
    _step_spans(view)


def test_a_view_that_keeps_up_is_written_in_the_call_that_ran_it(
    feed, span_records
):
    """One tick a call, the successor not yet there: the parent's
    order of statements, nothing crosses a call."""
    name = "keeps_up"
    client = PersistClient(MemBlob(), MemConsensus())
    w = client.open_writer("lineitem", LINEITEM_SCHEMA)
    view = MaintainedView(
        client, Dataflow(q1_mir(), name=name),
        {"lineitem": ("lineitem", LINEITEM_SCHEMA)}, "out",
    )
    calls = _calls(view)
    for t, tick in enumerate(feed[:6]):
        w.compare_and_append(*tick["lineitem"], t, t + 1)
        assert _step_span(view)
        assert view._validated_span is None
        assert view.upper == view._dispatched == t + 1
        assert _never_ahead_of_the_shard(client, view, name) == t + 1
        assert not _step_span(view)
    assert calls == [
        c for t in range(6) for c in ("run", "gather", "check", (t, t + 1))
    ]
    assert [s["overlapped_commit_ticks"] for s in span_records(name)] == (
        [0] * 6
    )


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
    # by identity, not by index: the ledger's two bounded deques are
    # full after a long run of other tests in this process
    before = LEDGER.records()
    old = {id(r) for r in before}
    _drive(served, span_records, "sync", "program_kinds")
    kinds = {
        r.kind
        for r in LEDGER.records()
        if id(r) not in old and r.name == "program_kinds"
    }
    assert {"step", "compact"} <= kinds
    assert kinds <= {"step", "step_donated", "compact"}
