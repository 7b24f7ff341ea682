"""A view's span train (``MaintainedView.step_span`` over
``run_steps(defer_check=True)``): the pipelined index-view path must be
row-for-row equal to serial stepping under duplicate/retraction churn
and mid-span peeks, a donated window must never read a donated buffer
after handoff (the checkpoint-clone contract), and a sinked span's
prefetch must write the per-tick shard."""

import numpy as np
import pytest

from materialize_tpu.expr import relation as mir
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema
from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

SCH = Schema(
    (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
)
K = 8  # ticks per span


def _mk(state_cap=1 << 14, slots=4, **kw):
    df = Dataflow(
        mir.Get("src", SCH), out_levels=3, out_slots=slots,
        state_cap=state_cap, **kw,
    )
    df._compact_every = 4
    df._compact_ratio = 4
    return df


def _accum(rows):
    acc: dict = {}
    for r in rows:
        acc[r[:-2]] = acc.get(r[:-2], 0) + r[-1]
    return {k: d for k, d in acc.items() if d}


def _churn_ticks(seed: int, n: int, n_rows=32, keyspace=64):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.integers(0, keyspace, n_rows).astype(np.int64),
            rng.integers(0, 8, n_rows).astype(np.int64),
            rng.choice(np.asarray([1, 1, -1]), n_rows).astype(np.int64),
        )
        for _ in range(n)
    ]


def _feed(w, t, tick):
    k, v, d = tick
    w.compare_and_append(
        [k, v], [None, None], np.full(len(d), t, np.uint64), d, t, t + 1
    )


@pytest.fixture
def donation(request):
    """``span_donation`` for one test: 'on' REQUESTS a donated carry
    (the cloned window checkpoint and the prover's verdict engage on
    any backend; the argnums follow the backend), 'off' never does."""
    before = COMPUTE_CONFIGS.current()["span_donation"]
    COMPUTE_CONFIGS.update({"span_donation": request.param})
    yield request.param == "on"
    COMPUTE_CONFIGS.update({"span_donation": before})


both_donations = pytest.mark.parametrize(
    "donation", ["on", "off"], indirect=True,
    ids=["donation_requested", "donation_not_requested"],
)
donation_requested = pytest.mark.parametrize(
    "donation", ["on"], indirect=True, ids=["donation_requested"]
)


def _sinked(ticks, name="mv", shard="out", df=None, **kw):
    """A sinked view (of ``df``, or a single-device one made from
    ``kw``) installed over an empty source shard, which then receives
    ``ticks``: nothing absorbed, all of it backlog."""
    from materialize_tpu.storage.persist import (
        MaintainedView,
        MemBlob,
        MemConsensus,
        PersistClient,
    )

    client = PersistClient(MemBlob(), MemConsensus())
    w = client.open_writer("src", SCH)
    view = MaintainedView(
        client, df or _mk(name=name, **kw), {"src": ("src", SCH)}, shard
    )
    assert view.upper == 0
    for t, tick in enumerate(ticks):
        _feed(w, t, tick)
    return client, w, view


def _index_view(ticks, **kw):
    """The same with no sink: an index view, which ``step_span``
    pipelines."""
    return _sinked(ticks, shard=None, **kw)[2]


def _serial(ticks, at=()):
    """The per-tick ``step`` over the same ticks: the final peek, and
    the peek at each upper in ``at``."""
    view = _index_view(ticks)
    seen = {}
    for _ in ticks:
        assert view.step(timeout=0)
        if view.upper in at:
            seen[view.upper] = _accum(view.peek())
    return _accum(view.peek()), seen


def _pipeline(view, n, on_span=None):
    """Dispatch spans of K until ``n`` ticks are in, one in flight."""
    while view._dispatched < n:
        assert view.step_span(max_ticks=K, timeout=0)
        if on_span is not None:
            on_span()


@both_donations
def test_pipelined_equals_serial_under_churn(donation):
    """Row-for-row equivalence: the same churn through the per-tick
    step and through pipelined spans, one flags readback a committed
    span."""
    ticks = _churn_ticks(7, 6 * K, n_rows=64, keyspace=512)
    view = _index_view(ticks)
    assert bool(view.donated_parts) == donation
    epoch, readbacks = view.span_epoch, view.df._readbacks
    _pipeline(view, len(ticks))
    assert view._inflight_span is not None  # the sixth, uncommitted
    view.sync_spans()
    assert view.upper == len(ticks)
    assert view.span_epoch - epoch == 6
    assert view.df._readbacks - readbacks == 6
    assert _accum(view.peek()) == _serial(ticks)[0]


@donation_requested
def test_mid_span_peeks_see_committed_boundaries(donation):
    """A peek admitted while a span is in flight sequences to a
    committed span boundary (the barrier commits it first) and matches
    the serial result at the same boundary, never a half-applied
    carry: through the view's own peek and through a read of the
    dataflow behind it (``span_barrier``)."""
    ticks = _churn_ticks(11, 4 * K, n_rows=64, keyspace=512)
    view = _index_view(ticks)
    peeked = {}

    def peek_every_other_span():
        if view._dispatched % (2 * K):
            return
        assert view._inflight_span is not None
        assert view.upper == view._dispatched - K
        read = view.peek if view._dispatched == 2 * K else view.df.peek
        peeked[view._dispatched] = _accum(read())
        assert view._inflight_span is None
        assert view.upper == view._dispatched

    _pipeline(view, len(ticks), peek_every_other_span)
    assert sorted(peeked) == [2 * K, 4 * K]
    assert peeked == _serial(ticks, at=peeked)[1]


def test_donation_checkpoint_is_cloned():
    """Donation safety: a window dispatched with donation requested
    rolls back to FRESH buffers (clones), never to references into
    the donated carry; reading a donated buffer after handoff would
    crash on TPU and silently alias on CPU. An un-donated window's
    checkpoint IS the carry it left behind."""
    import jax

    def carry(df):
        return jax.tree_util.tree_leaves(
            (tuple(df.states), df.output, df.err_output)
        )

    span = [
        {
            "src": Batch.from_numpy(
                SCH, [k, v], np.uint64(t), d, capacity=256
            )
        }
        for t, (k, v, d) in enumerate(_churn_ticks(3, K))
    ]
    shared = {}
    for donate in (True, False):
        df = _mk()
        live = {id(x) for x in carry(df)}
        df.run_steps(span, defer_check=True, donate=donate)
        ck = df._defer_ck
        assert ck is not None
        ck_leaves = jax.tree_util.tree_leaves(
            (tuple(ck[0]), ck[1], ck[2])
        )
        shared[donate] = sum(id(x) in live for x in ck_leaves)
        assert not df.check_flags()
    assert shared[True] == 0, "checkpoint references the donated carry"
    assert shared[False] > 0


@both_donations
def test_overflow_rolls_back_and_replays(donation):
    """An overflow mid-window (undersized tiers) must roll back to the
    window's checkpoint (the CLONE where donation is requested: it
    survives donation of the live carry), grow, replay, and still
    match serial."""
    ticks = _churn_ticks(23, 4 * K, n_rows=96, keyspace=512)
    # Deliberately tiny base run (merge-mode ingest, so it is folded
    # into every 16 ticks): the compaction cascade overflows it within
    # the window.
    view = _index_view(ticks, state_cap=256, slots=0)
    grown = []
    grow_for = view.df._grow_for
    view.df._grow_for = lambda key, *a: (
        grown.append(key), grow_for(key, *a)
    )[1]
    _pipeline(view, len(ticks))
    view.sync_spans()
    assert grown, "no tier overflowed: nothing was rolled back"
    assert view.upper == len(ticks)
    assert _accum(view.peek()) == _serial(ticks)[0]


def test_maintained_view_step_span_matches_step(tmp_path):
    """The replica-side pipelined path: MaintainedView.step_span
    (deferred commit, device-resident history) produces the same
    maintained result and serves the same AS OF rewinds as the
    per-tick step loop."""
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
        MaintainedView,
    )

    def build(tag):
        client = PersistClient(
            FileBlob(str(tmp_path / f"blob{tag}")),
            SqliteConsensus(str(tmp_path / f"c{tag}.db")),
        )
        w = client.open_writer("src", SCH)
        view = MaintainedView(
            client,
            Dataflow(mir.Get("src", SCH), out_slots=0),
            {"src": ("src", SCH)},
            None,
        )
        return client, w, view

    ticks = _churn_ticks(5, 24)

    _c1, w1, v_step = build("a")
    for t, tk in enumerate(ticks):
        _feed(w1, t, tk)
        assert v_step.step(timeout=5)

    _c2, w2, v_span = build("b")
    for t, tk in enumerate(ticks):
        _feed(w2, t, tk)
        if t % 6 == 5:  # span over the accumulated backlog
            while v_span._dispatched < t + 1:
                assert v_span.step_span(max_ticks=4, timeout=5)
    v_span.sync_spans()
    while v_span.upper < len(ticks):
        v_span.step_span(max_ticks=4, timeout=5)
        v_span.sync_spans()

    assert v_span.upper == v_step.upper == len(ticks)
    assert v_span.span_epoch > 0
    assert _accum(v_step.peek()) == _accum(v_span.peek())

    # AS OF rewinds through the (lazily host-converted) device history
    # agree at every commonly readable time.
    lo = max(v_step.since, v_span.since)
    for t in range(lo, len(ticks)):
        a = v_step.updates_as_of(t)
        b = v_span.updates_as_of(t)

        def acc(upd):
            cols, nulls, _tm, diff = upd
            out: dict = {}
            for i in range(len(diff)):
                key = tuple(int(c[i]) for c in cols)
                out[key] = out.get(key, 0) + int(diff[i])
            return {k: d for k, d in out.items() if d}

        assert acc(a) == acc(b), f"AS OF {t} diverged"


# -- sinked spans gather the next span's inputs while the device runs ----
#
# A sinked view (``writer`` set) over a backlog: what ``_step_span_sync``
# keeps for the span after it is the view's, wherever it steps next,
# and the sink shard is the one the per-tick path writes.


def _shard(client, shard="out", ordered=True):
    """The shard as written: [(lower, upper, rows)] a batch, the rows
    (k, v, time, diff) in the order they were appended."""
    st = client.machine(shard).reload()
    reader = client.open_reader(shard, "test-shard-dump")
    try:
        out = []
        for b in st.batches:
            _sch, cols, _nulls, time, diff = reader.fetch(b.lower, b.upper)
            rows = list(
                zip(*(c.tolist() for c in cols), time.tolist(), diff.tolist())
            )
            out.append(
                (b.lower, b.upper, rows if ordered else sorted(rows))
            )
    finally:
        reader.expire()
    return out


def _per_tick_shard(ticks, ordered=True):
    client, _w, view = _sinked(ticks)
    for _ in ticks:
        assert view.step(timeout=5)
    return _shard(client, ordered=ordered)


def _invariant(view):
    """The sources run ahead of what the view has dispatched by
    exactly what is kept, and that ahead of ``upper`` by at most one
    span: validated, unwritten, and only with its successor kept."""
    kept = [t for t, _inp, _at in view._kept]
    lo = view._dispatched
    assert kept == list(range(lo, lo + len(kept)))
    for s in view.sources.values():
        assert s.frontier == lo + len(kept)
    assert view.df._defer_ck is None  # nothing unvalidated crosses a call
    if view._validated_span is None:
        assert view.upper == lo
    else:
        assert kept
        unwritten = [t for t, _out in view._validated_span[1]]
        assert unwritten == list(range(view.upper, lo))


@pytest.fixture
def span_records():
    """The ``span`` records of the dataflow named, oldest first."""
    from materialize_tpu.utils.trace import TRACER

    saved = TRACER.level
    TRACER.set_level("info")
    TRACER.clear()
    yield lambda name: [
        r.attrs for r in TRACER.records()
        if r.name == "span" and r.attrs.get("dataflow") == name
    ]
    TRACER.set_level(saved)


def test_sinked_span_prefetch_writes_the_per_tick_shard(span_records):
    ticks = _churn_ticks(31, 26)
    client, _w, view = _sinked(ticks, name="prefetch_a")
    while view.upper < len(ticks):
        assert view.step_span(max_ticks=8, timeout=0)
        _invariant(view)
    assert view._kept == []
    assert not view.step_span(max_ticks=8, timeout=0)
    got = _shard(client)
    assert [(lo, up) for lo, up, _ in got] == [
        (t, t + 1) for t in range(len(ticks))
    ]
    assert got == _per_tick_shard(ticks)
    spans = span_records("prefetch_a")
    assert [s["ticks"] for s in spans] == [8, 8, 8, 2]
    assert spans[0]["prefetched_ticks"] == 0
    for s in spans[1:]:
        assert s["prefetched_ticks"] == s["ticks"]
    # every span but the last was written with its successor dispatched
    assert [s["overlapped_commit_ticks"] for s in spans] == [8, 8, 8, 0]


def test_sinked_span_prefetch_keeps_nothing_when_nothing_is_ready():
    ticks = _churn_ticks(32, 4)
    client, w, view = _sinked(ticks[:3])
    assert view.step_span(max_ticks=8, timeout=0)
    assert view.upper == 3 and view._kept == []
    _invariant(view)
    assert not view.step_span(max_ticks=8, timeout=0)
    _feed(w, 3, ticks[3])
    assert view.step_span(max_ticks=8, timeout=0)
    assert view.upper == 4 and view._kept == []
    assert not view.step_span(max_ticks=8, timeout=0)
    assert _shard(client) == _per_tick_shard(ticks)


def test_sinked_span_overflow_replay_leaves_kept_ticks_valid():
    ticks = _churn_ticks(33, 24, n_rows=96, keyspace=1 << 20)
    # runs this small overflow inside a span (merge-mode ingest, so
    # every step merges into them): check_flags replays the span
    # against grown tiers with the next one kept
    client, _w, view = _sinked(ticks, state_cap=256, slots=0)
    replays = []
    check_flags = view.df.check_flags

    def spy():
        kept = len(view._kept)
        if check_flags():
            replays.append(kept)
            return True
        return False

    view.df.check_flags = spy
    while view.upper < len(ticks):
        assert view.step_span(max_ticks=8, timeout=0)
        _invariant(view)
    assert any(replays), "no span was replayed with ticks kept"
    assert _shard(client, ordered=False) == _per_tick_shard(
        ticks, ordered=False
    )


def test_step_and_run_until_consume_kept_ticks_first(span_records):
    ticks = _churn_ticks(34, 20)
    client, _w, view = _sinked(ticks, name="prefetch_d")
    assert view.step_span(max_ticks=4, timeout=0)
    # 0-3 validated, 4-7 kept: written when the next entry needs them
    assert view.upper == 0 and view._dispatched == 4
    assert len(view._kept) == 4
    fetched = []
    fetch_to = view.sources["src"].fetch_to
    view.sources["src"].fetch_to = lambda target: (
        fetched.append(target), fetch_to(target)
    )[1]
    assert view.step(timeout=0)  # writes 0-3, then tick 4, kept
    assert view.upper == 5 and len(view._kept) == 3
    _invariant(view)
    view.run_until(9, timeout=0)  # 5-7 kept, 8 from the source
    assert view.upper == 9 and view._kept == []
    assert fetched == [9]
    # three kept and one more from the source make the next span
    assert view.step_span(max_ticks=3, timeout=0)  # 9-11, keeps 12-14
    assert view._dispatched == 12 and len(view._kept) == 3
    assert view.step_span(max_ticks=4, timeout=0)  # 12-14 kept, 15
    assert (view.upper, view._dispatched) == (12, 16)
    _invariant(view)
    while view.upper < len(ticks):
        assert view.step_span(max_ticks=4, timeout=0)
    assert sorted(fetched) == list(range(9, 21))  # no tick twice
    assert _shard(client) == _per_tick_shard(ticks)
    spans = span_records("prefetch_d")
    assert [(s["lower"], s["upper"]) for s in spans] == [
        (0, 4), (4, 5), (5, 6), (6, 7), (7, 8), (8, 9), (9, 12),
        (12, 16), (16, 20),
    ]
    assert [s["prefetched_ticks"] for s in spans] == [
        0, 1, 1, 1, 1, 0, 0, 3, 4,
    ]
    # written beneath the successor's dispatch: 9-12 and 12-16 alone
    # (0-4 was flushed by step(), 16-20 had no successor)
    assert [s["overlapped_commit_ticks"] for s in spans] == [
        0, 0, 0, 0, 0, 0, 3, 4, 0,
    ]


def test_kept_tick_lag_covers_the_time_it_was_kept():
    import time

    from materialize_tpu.coord.freshness import FRESHNESS

    _client, _w, view = _sinked(_churn_ticks(35, 8), name="prefetch_e")
    assert view.step_span(max_ticks=4, timeout=0)
    assert len(view._kept) == 4
    time.sleep(0.3)
    assert view.step_span(max_ticks=4, timeout=0)
    lag = {
        frontier: lag_ms
        for df, _r, frontier, lag_ms, _at in FRESHNESS.history_rows()
        if df == "prefetch_e"
    }
    # stamped when ITS fetch completed, not when its span began
    assert lag[8] >= 300.0


def test_expire_drops_kept_ticks_and_a_fresh_view_resumes():
    from materialize_tpu.storage.persist import MaintainedView

    ticks = _churn_ticks(36, 16)
    client, _w, view = _sinked(ticks)
    assert view.step_span(max_ticks=4, timeout=0)
    assert view.step_span(max_ticks=4, timeout=0)
    # 0-3 written, 4-7 validated and unwritten, 8-11 kept
    assert (view.upper, view._dispatched) == (4, 8)
    assert view._validated_span is not None and len(view._kept) == 4
    view.expire()
    assert view._kept == [] and view._validated_span is None
    assert view.upper == view._dispatched == 4
    assert client.machine("src").reload().reader_holds == ()
    assert client.machine("out").reload().upper == 4
    fresh = MaintainedView(client, _mk(), {"src": ("src", SCH)}, "out")
    assert fresh.upper == 4 and fresh._kept == []
    _invariant(fresh)
    while fresh.upper < len(ticks):
        assert fresh.step_span(max_ticks=4, timeout=0)
    fresh.expire()
    assert _shard(client, ordered=False) == _per_tick_shard(
        ticks, ordered=False
    )


# -- a sinked span is written beneath its successor's dispatch ---------------


@donation_requested
def test_a_validated_spans_deltas_outlive_the_donated_dispatch(donation):
    """Span K's deltas wait on the device while K+1 is dispatched with
    its carry donated: the prover sees them as roots and finds none in
    the carry, and the sanitizer, on, catches no read of a dead one."""
    from materialize_tpu.analysis.donation import LEDGER, view_verdict
    from materialize_tpu.analysis.provenance import (
        ProvenanceReport,
        scan_view,
    )

    before = COMPUTE_CONFIGS.current()["buffer_sanitizer"]
    COMPUTE_CONFIGS.update({"buffer_sanitizer": True})
    try:
        ticks = _churn_ticks(41, 20)
        client, _w, view = _sinked(ticks, name="donated_mv")
        assert view.step_span(max_ticks=K, timeout=0)
        assert view._validated_span is not None
        report = ProvenanceReport()
        scan_view(report, "donated_mv", view)
        roots = {
            root for rec in report.leaves.values()
            for root, _ in rec.holders
        }
        assert {f"donated_mv/validated[t={t}]" for t in range(K)} <= roots
        verdict = view_verdict("donated_mv", view, report=report)
        assert all(verdict.donatable.values()), verdict.reasons
        assert view.donated_parts  # the next dispatch asks for donation
        recorded = LEDGER.recorded
        while view.upper < len(ticks):
            assert view.step_span(max_ticks=K, timeout=0)
            _invariant(view)
        assert LEDGER.recorded > recorded  # carries were handed over
        assert LEDGER.caught == 0
        assert _shard(client) == _per_tick_shard(ticks)
    finally:
        COMPUTE_CONFIGS.update({"buffer_sanitizer": before})
        LEDGER.clear()


@pytest.mark.parametrize("shard", ["out", None], ids=["sinked", "index"])
def test_an_spmd_view_steps_to_the_same_answers(shard, span_records):
    """Four virtual devices: an SPMD view, sinked or not, takes the
    same span path (its commit gathers each delta to the host) and,
    over a backlog, the same order."""
    from materialize_tpu.parallel.mesh import make_mesh
    from materialize_tpu.render.dataflow import ShardedDataflow

    ticks = _churn_ticks(42, 20)
    name = f"spmd_{shard}"
    client, _w, view = _sinked(
        ticks, shard=shard,
        df=ShardedDataflow(mir.Get("src", SCH), make_mesh(4), name=name),
    )
    order = []
    run_steps, gather = view.df.run_steps, view.df.gather_delta
    view.df.run_steps = lambda *a, **kw: (
        order.append("run"), run_steps(*a, **kw)
    )[1]
    view.df.gather_delta = lambda out: (
        order.append("commit"), gather(out)
    )[1]
    uppers = []
    while view._dispatched < len(ticks):
        assert view.step_span(max_ticks=K, timeout=0)
        _invariant(view)
        uppers.append((view.upper, view._dispatched))
    assert uppers == [(0, 8), (8, 16), (20, 20)]
    assert order == (
        ["run", "run"] + ["commit"] * 8 + ["run"] + ["commit"] * 12
    )
    assert _accum(view.peek()) == _serial(ticks)[0]
    spans = span_records(name)
    assert [s["overlapped_commit_ticks"] for s in spans] == [8, 8, 0]
    if shard:
        # chunk for chunk the per-tick shard's rows (workers do not
        # consolidate across each other: compare the sums)
        def net(shard_):
            return [
                (lo, up, _accum([r[:-1] + (0, r[-1]) for r in rows]))
                for lo, up, rows in shard_
            ]

        assert net(_shard(client)) == net(_per_tick_shard(ticks))

