"""Pipelined span execution (ISSUE 7): the double-buffered, donated
span executor must be row-for-row equal to serial execution under
duplicate/retraction churn and mid-span peeks, and must never read a
donated buffer after handoff (the checkpoint-clone contract)."""

import numpy as np
import pytest

from materialize_tpu.expr import relation as mir
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.render.span_exec import SpanExecutor
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema

SCH = Schema(
    (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
)
K = 8  # ticks per span (multiple of _compact_every below)


def _mk(state_cap=1 << 14, slots=4, **kw):
    df = Dataflow(
        mir.Get("src", SCH), out_levels=3, out_slots=slots,
        state_cap=state_cap, **kw,
    )
    df._compact_every = 4
    df._compact_ratio = 4
    return df


def _churn_spans(seed: int, n_spans: int, n_rows=64, keyspace=512):
    """Deterministic duplicate/retraction churn: ~25% retractions,
    heavy key reuse (duplicates across and within ticks)."""
    rng = np.random.default_rng(seed)
    spans = []
    t = 0
    for _s in range(n_spans):
        sp = []
        for _i in range(K):
            k = rng.integers(0, keyspace, n_rows).astype(np.int64)
            v = rng.integers(0, 16, n_rows).astype(np.int64)
            d = rng.choice(
                np.asarray([1, 1, 1, -1]), n_rows
            ).astype(np.int64)
            sp.append(
                {
                    "src": Batch.from_numpy(
                        SCH, [k, v], np.uint64(t), d, capacity=256
                    )
                }
            )
            t += 1
        spans.append(sp)
    return spans


def _accum(rows):
    acc: dict = {}
    for r in rows:
        acc[r[:-2]] = acc.get(r[:-2], 0) + r[-1]
    return {k: d for k, d in acc.items() if d}


def test_pipelined_equals_serial_under_churn():
    """Row-for-row equivalence: the same churn through (a) serial
    synchronous run_steps and (b) the pipelined, donated executor."""
    spans_a = _churn_spans(7, 6)
    spans_b = _churn_spans(7, 6)

    df_ser = _mk()
    for sp in spans_a:
        df_ser.run_steps(sp)

    df_pip = _mk()
    ex = SpanExecutor(df_pip, donate=True)
    for sp in spans_b:
        ex.submit(sp)
    ex.close()

    assert _accum(df_ser.peek()) == _accum(df_pip.peek())
    st = ex.stats()
    assert st["readbacks_per_span"] == 1.0
    assert st["spans_committed"] == 6


def test_mid_span_peeks_see_committed_boundaries():
    """A peek admitted while a span is in flight sequences to a
    committed span boundary (the barrier syncs first) and matches the
    serial result at the same boundary — never a half-applied carry."""
    spans_a = _churn_spans(11, 4)
    spans_b = _churn_spans(11, 4)

    df_ser = _mk()
    serial_at = []
    for sp in spans_a:
        df_ser.run_steps(sp)
        serial_at.append(_accum(df_ser.peek()))

    df_pip = _mk()
    ex = SpanExecutor(df_pip, donate=True)
    pipelined_at = {}
    for i, sp in enumerate(spans_b):
        ex.submit(sp)
        if i % 2 == 1:
            # Mid-pipeline peek: span i is in flight; the barrier
            # must commit it before the read.
            pipelined_at[i] = _accum(df_pip.peek())
            assert df_pip.time == (i + 1) * K
    ex.close()
    for i, got in pipelined_at.items():
        assert got == serial_at[i], f"mismatch at boundary {i}"
    assert ex.boundary_syncs >= len(pipelined_at)


def test_donation_checkpoint_is_cloned():
    """Donation safety: with donation on, the rollback checkpoint's
    device leaves are FRESH buffers (clones), never references into
    the donated carry — reading a donated buffer after handoff would
    crash on TPU and silently alias on CPU."""
    import jax

    df = _mk()
    ex = SpanExecutor(df, donate=True)
    live_before = jax.tree_util.tree_leaves(
        (tuple(df.states), df.output, df.err_output)
    )
    live_ids = {id(x) for x in live_before}
    ex.submit(_churn_spans(3, 1)[0])
    ck = df._defer_ck
    assert ck is not None
    ck_leaves = jax.tree_util.tree_leaves((tuple(ck[0]), ck[1], ck[2]))
    overlap = [x for x in ck_leaves if id(x) in live_ids]
    assert not overlap, (
        "checkpoint references the donated carry: "
        f"{len(overlap)} shared buffers"
    )
    ex.close()


def test_overflow_rolls_back_and_replays_with_donation():
    """An overflow mid-window (undersized tiers) must roll back to the
    CLONED checkpoint, grow, replay, and still match serial — the
    checkpoint survives donation of the live carry."""
    spans_a = _churn_spans(23, 4, n_rows=96)
    spans_b = _churn_spans(23, 4, n_rows=96)

    df_ser = _mk(state_cap=1 << 14)
    for sp in spans_a:
        df_ser.run_steps(sp)

    # Deliberately tiny base run: the compaction cascade overflows it
    # within the window.
    df_pip = _mk(state_cap=256)
    ex = SpanExecutor(df_pip, donate=True)
    for sp in spans_b:
        ex.submit(sp)
    ex.close()
    assert _accum(df_ser.peek()) == _accum(df_pip.peek())


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


def _sinked(ticks, name="mv", **kw):
    """A sinked view installed over an empty source shard, which then
    receives ``ticks``: nothing absorbed, all of it backlog."""
    from materialize_tpu.storage.persist import (
        MaintainedView,
        MemBlob,
        MemConsensus,
        PersistClient,
    )

    client = PersistClient(MemBlob(), MemConsensus())
    w = client.open_writer("src", SCH)
    view = MaintainedView(
        client, _mk(name=name, **kw), {"src": ("src", SCH)}, "out"
    )
    assert view.upper == 0
    for t, tick in enumerate(ticks):
        _feed(w, t, tick)
    return client, w, view


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
    """The sources run ahead of the view by exactly what is kept."""
    kept = [t for t, _inp, _at in view._kept]
    assert kept == list(range(view.upper, view.upper + len(kept)))
    for s in view.sources.values():
        assert s.frontier == view.upper + len(kept)


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
    assert view.upper == 4 and len(view._kept) == 4
    fetched = []
    fetch_to = view.sources["src"].fetch_to
    view.sources["src"].fetch_to = lambda target: (
        fetched.append(target), fetch_to(target)
    )[1]
    assert view.step(timeout=0)  # tick 4, kept
    assert view.upper == 5 and len(view._kept) == 3
    _invariant(view)
    view.run_until(9, timeout=0)  # 5-7 kept, 8 from the source
    assert view.upper == 9 and view._kept == []
    assert fetched == [9]
    # three kept and one more from the source make the next span
    assert view.step_span(max_ticks=3, timeout=0)  # 9-11, keeps 12-14
    assert view.upper == 12 and len(view._kept) == 3
    assert view.step_span(max_ticks=4, timeout=0)  # 12-14 kept, 15
    assert view.upper == 16
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
    assert view.upper == 4 and len(view._kept) == 4
    view.expire()
    assert view._kept == []
    assert client.machine("src").reload().reader_holds == ()
    fresh = MaintainedView(client, _mk(), {"src": ("src", SCH)}, "out")
    assert fresh.upper == 4 and fresh._kept == []
    _invariant(fresh)
    while fresh.upper < len(ticks):
        assert fresh.step_span(max_ticks=4, timeout=0)
    fresh.expire()
    assert _shard(client, ordered=False) == _per_tick_shard(
        ticks, ordered=False
    )
