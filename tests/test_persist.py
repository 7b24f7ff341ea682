"""Durability-slice tests: the persist-analog storage engine.

Mirrors the reference's persist test strategy (SURVEY.md §4.1): codec
roundtrips, state-machine datadriven behavior (CaS, fencing, since/upper),
fault injection over an unreliable Blob (persist/src/unreliable.rs), and
the checkpoint/resume model — restart = re-render + re-hydrate from
shards at the output's upper (SURVEY.md §5)."""

import threading

import numpy as np
import pytest

from materialize_tpu.expr import relation as mir
from materialize_tpu.expr.relation import AggregateExpr, AggregateFunc
from materialize_tpu.expr.scalar import col
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.schema import (
    GLOBAL_DICT,
    Column,
    ColumnType,
    Schema,
)
from materialize_tpu.storage.persist import (
    Fenced,
    FileBlob,
    MaintainedView,
    MemBlob,
    MemConsensus,
    PersistClient,
    SqliteConsensus,
    UnreliableBlob,
    UpperMismatch,
    VersionedData,
    decode_part,
    encode_part,
    part_stats,
)
from materialize_tpu.storage.persist.codec import PartCorruptError

from .oracle import as_multiset

KV = Schema([Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)])


def _updates(pairs, t=0):
    """pairs: [(k, v, diff)] -> (cols, nulls, time, diff) host arrays."""
    k = np.array([p[0] for p in pairs], np.int64)
    v = np.array([p[1] for p in pairs], np.int64)
    d = np.array([p[2] for p in pairs], np.int64)
    return [k, v], [None, None], np.full(len(pairs), t, np.uint64), d


class TestCodec:
    def test_roundtrip_with_nulls_and_strings(self):
        schema = Schema(
            [
                Column("s", ColumnType.STRING),
                Column("x", ColumnType.INT64, nullable=True),
            ]
        )
        codes = GLOBAL_DICT.encode_many(["foo", "bar", "foo"])
        cols = [codes, np.array([1, 2, 3], np.int64)]
        nulls = [None, np.array([False, True, False])]
        time = np.array([0, 0, 1], np.uint64)
        diff = np.array([1, -1, 2], np.int64)
        data = encode_part(schema, cols, nulls, time, diff)
        sch2, c2, n2, t2, d2 = decode_part(data)
        assert [c.name for c in sch2.columns] == ["s", "x"]
        assert GLOBAL_DICT.decode_many(c2[0]) == ["foo", "bar", "foo"]
        np.testing.assert_array_equal(c2[1], cols[1])
        np.testing.assert_array_equal(n2[1], nulls[1])
        np.testing.assert_array_equal(t2, time)
        np.testing.assert_array_equal(d2, diff)

    def test_skipped_string_columns_stay_out_of_the_dictionary(
        self, monkeypatch
    ):
        """A reader that never looks at a string column gets code 0 for
        it, and its strings never enter this process's dictionary."""
        from materialize_tpu.repr.schema import StringDictionary
        from materialize_tpu.storage.persist import codec

        schema = Schema(
            [
                Column("name", ColumnType.STRING),
                Column("note", ColumnType.STRING),
            ]
        )
        notes = [f"skipped note {i}" for i in range(100)]
        writer = StringDictionary()  # another process's dictionary
        monkeypatch.setattr(codec, "GLOBAL_DICT", writer)
        data = encode_part(
            schema,
            [writer.encode_many(["a", "b"] * 50), writer.encode_many(notes)],
            [None, None],
            np.zeros(100, np.uint64),
            np.ones(100, np.int64),
        )
        monkeypatch.undo()
        before = len(GLOBAL_DICT)
        _sch, cols, _n, _t, _d = decode_part(data, frozenset({1}))
        assert GLOBAL_DICT.decode_many(cols[0][:2]) == ["a", "b"]
        assert (cols[1] == 0).all()
        assert len(GLOBAL_DICT) <= before + 2
        _sch, cols, _n, _t, _d = decode_part(data)
        assert GLOBAL_DICT.decode_many(cols[1]) == notes

    def test_unread_string_columns(self):
        from materialize_tpu.storage.persist.operators import (
            unread_string_columns,
        )

        sch = Schema(
            [
                Column("k", ColumnType.INT64),
                Column("name", ColumnType.STRING),
                Column("note", ColumnType.STRING),
            ]
        )
        get = mir.Get("t", sch)
        assert unread_string_columns(
            mir.Project(get, (0, 1)), "t", sch
        ) == {2}
        # under anything but a Project, every column reaches it
        assert unread_string_columns(
            mir.Project(mir.Filter(get, (col(0).gte(col(0)),)), (1,)),
            "t", sch,
        ) == set()
        # two reads of one input keep what either keeps
        assert unread_string_columns(
            mir.Union((mir.Project(get, (1,)), mir.Project(get, (2,)))),
            "t", sch,
        ) == set()
        # another input's reads are not this one's
        assert unread_string_columns(
            mir.Project(get, (0,)), "u", sch
        ) == {1, 2}

    def test_stats_and_corruption(self):
        cols, nulls, time, diff = _updates([(5, 50, 1), (9, 90, 1)])
        data = encode_part(KV, cols, nulls, time, diff)
        stats = part_stats(data)
        assert stats["k"] == [5, 9] and stats["v"] == [50, 90]
        with pytest.raises(PartCorruptError):
            decode_part(data[:-1] + bytes([data[-1] ^ 0xFF]))


class TestMachine:
    def _client(self):
        return PersistClient(MemBlob(), MemConsensus())

    def test_append_and_snapshot(self):
        c = self._client()
        w = c.open_writer("s1", KV)
        w.compare_and_append(*_updates([(1, 10, 1), (2, 20, 1)], t=0), 0, 1)
        w.compare_and_append(*_updates([(1, 10, -1)], t=1), 1, 2)
        r = c.open_reader("s1")
        _sch, cols, nulls, time, diff = r.snapshot(1)
        rows = list(zip(cols[0], cols[1], time, diff))
        assert as_multiset([(int(a), int(b), int(t), int(d)) for a, b, t, d in rows]) == {
            (2, 20): 1
        }

    def test_upper_mismatch_and_empty_advance(self):
        c = self._client()
        w = c.open_writer("s1", KV)
        w.compare_and_append(*_updates([(1, 1, 1)]), 0, 5)
        with pytest.raises(UpperMismatch):
            w.compare_and_append(*_updates([(2, 2, 1)], t=3), 3, 6)
        # Empty batch advances the upper (upper-only heartbeat).
        w.compare_and_append([np.zeros(0, np.int64)] * 2, [None, None],
                             np.zeros(0, np.uint64), np.zeros(0, np.int64),
                             5, 10)
        assert w.upper == 10

    def test_writer_fencing(self):
        c = self._client()
        w1 = c.open_writer("s1", KV)
        w2 = c.open_writer("s1", KV)  # newer epoch fences w1
        with pytest.raises(Fenced):
            w1.compare_and_append(*_updates([(1, 1, 1)]), 0, 1)
        w2.compare_and_append(*_updates([(1, 1, 1)]), 0, 1)

    def test_since_holds_and_compaction(self):
        c = self._client()
        w = c.open_writer("s1", KV)
        for t in range(12):
            # Insert k then retract at the next step: steady state is one row.
            ups = [(7, t, 1)] + ([(7, t - 1, -1)] if t else [])
            w.compare_and_append(*_updates(ups, t=t), t, t + 1)
        r = c.open_reader("s1", "rA")
        m = c.machine("s1")
        r.downgrade_since(10)
        assert m.reload().since == 10
        deleted = m.maybe_compact(max_batches=2)
        assert deleted > 0
        st = m.reload()
        assert len(st.batches) <= 2
        # Reads below since are rejected; at since they see the collapsed
        # history (times forwarded).
        with pytest.raises(ValueError):
            r.snapshot(9)
        _sch, cols, nulls, time, diff = r.snapshot(10)
        rows = [
            (int(cols[0][i]), int(cols[1][i]), int(time[i]), int(diff[i]))
            for i in range(len(diff))
        ]
        assert as_multiset(rows) == {(7, 10): 1}
        # Consensus truncation keeps the head readable.
        m.gc_consensus()
        c2 = PersistClient(c.blob, c.consensus)
        assert c2.machine("s1").state.upper == 12

    def test_concurrent_compaction_loses_cleanly(self):
        """Two machines compacting the same shard: exactly one swap wins,
        no appended data is lost (regression: stale-prefix swap)."""
        blob, cons = MemBlob(), MemConsensus()
        cA = PersistClient(blob, cons)
        cB = PersistClient(blob, cons)
        w = cA.open_writer("s1", KV)
        for t in range(10):
            self_ups = [(t % 3, t, 1)]
            w.compare_and_append(*_updates(self_ups, t=t), t, t + 1)
        mA, mB = cA.machine("s1"), cB.machine("s1")
        # B compacts a longer history than A merged: A must no-op.
        stA = mA.reload()
        merged_key, n, old_keys = mA._merge_parts(stA)
        mB.maybe_compact(max_batches=1)
        w.compare_and_append(*_updates([(9, 9, 1)], t=10), 10, 11)
        prefix = stA.batches

        def f(cur):
            if cur.batches[: len(prefix)] != prefix:
                return None, 0
            raise AssertionError("stale prefix should not match")

        assert mA._apply(f) == 0
        r = cA.open_reader("s1")
        _sch, cols, _nulls, _time, diff = r.snapshot(10)
        assert int(diff.sum()) == 11  # nothing lost

    def test_compaction_of_all_empty_batches(self):
        """Spine of upper-only (keyless) batches compacts without
        touching the blob (regression: blob.delete(''))."""
        c = PersistClient(MemBlob(), MemConsensus())
        w = c.open_writer("s1", KV)
        empty = (
            [np.zeros(0, np.int64)] * 2,
            [None, None],
            np.zeros(0, np.uint64),
            np.zeros(0, np.int64),
        )
        for t in range(10):
            w.compare_and_append(*empty, t, t + 1)
        m = c.machine("s1")
        m.maybe_compact(max_batches=2)
        assert len(m.reload().batches) <= 3 and m.reload().upper == 10

    def test_fileblob_rejects_escaping_keys(self, tmp_path):
        b = FileBlob(str(tmp_path / "blob"))
        with pytest.raises(ValueError):
            b.set("../escape", b"x")

    def test_multiple_reader_holds(self):
        c = self._client()
        w = c.open_writer("s1", KV)
        w.compare_and_append(*_updates([(1, 1, 1)]), 0, 5)
        rA = c.open_reader("s1", "rA")
        rB = c.open_reader("s1", "rB")
        rA.downgrade_since(4)
        assert c.machine("s1").reload().since == 0  # rB holds at 0
        rB.expire()
        rA.downgrade_since(4)
        assert c.machine("s1").reload().since == 4

    def test_concurrent_cas_total_order(self):
        cons = MemConsensus()
        oks = []

        def contend(i):
            ok = cons.compare_and_set(
                "k", None, VersionedData(0, f"w{i}".encode())
            )
            oks.append(ok)

        ts = [threading.Thread(target=contend, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sum(oks) == 1


class TestSqliteConsensus:
    def test_cas_across_connections(self, tmp_path):
        path = str(tmp_path / "consensus.db")
        c1 = SqliteConsensus(path)
        c2 = SqliteConsensus(path)
        assert c1.compare_and_set("k", None, VersionedData(0, b"a"))
        assert not c2.compare_and_set("k", None, VersionedData(0, b"b"))
        assert c2.compare_and_set("k", 0, VersionedData(1, b"c"))
        assert c1.head("k").data == b"c"
        assert [v.seqno for v in c1.scan("k", 0)] == [0, 1]
        c1.truncate("k", 1)
        assert [v.seqno for v in c2.scan("k", 0)] == [1]

    def test_file_blob_roundtrip(self, tmp_path):
        b = FileBlob(str(tmp_path / "blob"))
        b.set("shard/part-1", b"hello")
        b.set("shard/part-2", b"world")
        assert b.get("shard/part-1") == b"hello"
        assert b.list_keys("shard/") == ["shard/part-1", "shard/part-2"]
        b.delete("shard/part-1")
        assert b.get("shard/part-1") is None


class TestFaultInjection:
    def test_writer_retries_unreliable_blob(self):
        blob = UnreliableBlob(MemBlob(), fail_every=2)
        c = PersistClient(blob, MemConsensus())
        w = c.open_writer("s1", KV)
        for t in range(6):
            w.compare_and_append(*_updates([(t, t, 1)], t=t), t, t + 1)
        blob.fail_every = 0
        r = c.open_reader("s1")
        _sch, cols, _nulls, _time, diff = r.snapshot(5)
        assert int(diff.sum()) == 6

    @pytest.mark.chaos
    def test_acked_writes_survive_reload_under_faults(self):
        """Property (ISSUE 10 satellite): across fault rates, every
        ACKED compare_and_append — including retractions — is exactly
        visible after a restart (fresh client over the same durable
        state, faults off)."""
        for fail_every in (2, 3, 5):
            blob, cons = MemBlob(), MemConsensus()
            c = PersistClient(
                UnreliableBlob(blob, fail_every=fail_every), cons
            )
            w = c.open_writer("s1", KV)
            acked: dict = {}
            for t in range(20):
                ups = [(t % 4, t, 1)]
                if t >= 4:
                    ups.append((t % 4, t - 4, -1))  # retraction storm
                w.compare_and_append(*_updates(ups, t=t), t, t + 1)
                for k, v, d in ups:
                    acked[(k, v)] = acked.get((k, v), 0) + d
            acked = {k: n for k, n in acked.items() if n}
            c2 = PersistClient(blob, cons)  # "restart"
            assert c2.machine("s1").reload().upper == 20
            r = c2.open_reader("s1")
            _sch, cols, _n, _t, diff = r.snapshot(19)
            got: dict = {}
            for i in range(len(diff)):
                key = (int(cols[0][i]), int(cols[1][i]))
                got[key] = got.get(key, 0) + int(diff[i])
            got = {k: n for k, n in got.items() if n}
            assert got == acked, (fail_every, got, acked)

    @pytest.mark.chaos
    def test_failed_write_invisible_after_reload(self):
        """A write whose blob part can NEVER land must be fully
        invisible: the upper does not advance, no dangling part is
        referenced, a restart reads exactly the prior acked content,
        and the writer continues cleanly once the fault lifts."""
        from materialize_tpu.storage.persist import (
            ExternalDurabilityError,
        )

        blob, cons = MemBlob(), MemConsensus()
        ub = UnreliableBlob(blob, fail_every=0)
        c = PersistClient(ub, cons)
        w = c.open_writer("s1", KV)
        w.compare_and_append(*_updates([(1, 10, 1)], t=0), 0, 1)
        ub.fail_every = 1  # every blob op fails: retries must exhaust
        with pytest.raises(ExternalDurabilityError):
            w.compare_and_append(*_updates([(2, 20, 1)], t=1), 1, 2)
        ub.fail_every = 0
        c2 = PersistClient(blob, cons)
        st = c2.machine("s1").reload()
        assert st.upper == 1  # the failed write never acked
        for b in st.batches:  # no dangling part references
            for key in b.keys:
                assert blob.get(key) is not None
        r = c2.open_reader("s1")
        _sch, cols, _n, _t, diff = r.snapshot(0)
        rows = {
            (int(cols[0][i]), int(cols[1][i])): int(diff[i])
            for i in range(len(diff))
        }
        assert rows == {(1, 10): 1}
        w2 = c2.open_writer("s1", KV)  # continues after the fault
        w2.compare_and_append(*_updates([(2, 20, 1)], t=1), 1, 2)
        assert w2.upper == 2

    @pytest.mark.chaos
    def test_compaction_under_faults_preserves_content(self):
        """Compaction under injected blob faults (reads, the merged
        write, the best-effort deletes) must preserve the exact
        snapshot content — a leaked part is fine, lost data is not."""
        blob, cons = MemBlob(), MemConsensus()
        ub = UnreliableBlob(blob, fail_every=4)
        c = PersistClient(ub, cons)
        w = c.open_writer("s1", KV)
        for t in range(12):
            w.compare_and_append(
                *_updates([(t % 3, t, 1)], t=t), t, t + 1
            )
        m = c.machine("s1")
        c.open_reader("s1", "hold").downgrade_since(11)

        def content():
            r = c.open_reader("s1", "chk")
            _sch, cols, _n, _t, diff = r.snapshot(11)
            out: dict = {}
            for i in range(len(diff)):
                key = (int(cols[0][i]), int(cols[1][i]))
                out[key] = out.get(key, 0) + int(diff[i])
            return {k: n for k, n in out.items() if n}

        before = content()
        m.maybe_compact(max_batches=2)
        assert len(m.reload().batches) <= 2
        assert content() == before
        ub.fail_every = 0
        c2 = PersistClient(blob, cons)
        r2 = c2.open_reader("s1")
        _sch, cols, _n, _t, diff = r2.snapshot(11)
        after: dict = {}
        for i in range(len(diff)):
            key = (int(cols[0][i]), int(cols[1][i]))
            after[key] = after.get(key, 0) + int(diff[i])
        assert {k: n for k, n in after.items() if n} == before


def _q1ish_mir():
    """SUM(v) GROUP BY k over the kv source."""
    return mir.Get("kv", KV).reduce(
        (0,), (AggregateExpr(AggregateFunc.SUM_INT, col(1)),)
    )


class TestMaintainedView:
    def _feed(self, w, t, ups):
        w.compare_and_append(*_updates(ups, t=t), t, t + 1)

    def test_maintained_view_and_restart(self, tmp_path):
        blob = FileBlob(str(tmp_path / "blob"))
        cons = SqliteConsensus(str(tmp_path / "consensus.db"))
        c = PersistClient(blob, cons)
        w = c.open_writer("kv", KV)
        self._feed(w, 0, [(1, 10, 1), (2, 20, 1)])
        self._feed(w, 1, [(1, 5, 1)])

        mv = MaintainedView(
            c, Dataflow(_q1ish_mir()), {"kv": ("kv", KV)}, "mv_out"
        )
        self._feed(w, 2, [(2, 20, -1), (3, 7, 1)])
        mv.run_until(3)
        assert as_multiset(mv.peek()) == {(1, 15): 1, (3, 7): 1}

        # Output shard holds the same result durably.
        out_reader = c.open_reader("mv_out")
        _sch, cols, _nulls, time, diff = out_reader.snapshot(2)
        rows = [
            (int(cols[0][i]), int(cols[1][i]), int(time[i]), int(diff[i]))
            for i in range(len(diff))
        ]
        assert as_multiset(rows) == {(1, 15): 1, (3, 7): 1}

        # "Crash": drop the MaintainedView; new process = fresh client
        # over the same durable state; rehydrate and continue.
        del mv
        c2 = PersistClient(blob, SqliteConsensus(str(tmp_path / "consensus.db")))
        mv2 = MaintainedView(
            c2, Dataflow(_q1ish_mir()), {"kv": ("kv", KV)}, "mv_out"
        )
        assert as_multiset(mv2.peek()) == {(1, 15): 1, (3, 7): 1}
        w2 = c2.open_writer("kv", KV)  # fences w
        self._feed(w2, 3, [(1, 100, 1)])
        mv2.run_until(4)
        assert as_multiset(mv2.peek()) == {(1, 115): 1, (3, 7): 1}
        with pytest.raises(Fenced):
            self._feed(w, 4, [(9, 9, 1)])

    def test_hydration_from_nonzero_since(self):
        c = PersistClient(MemBlob(), MemConsensus())
        w = c.open_writer("kv", KV)
        for t in range(6):
            self._feed(w, t, [(1, 1, 1)])
        r = c.open_reader("kv", "holdr")
        r.downgrade_since(4)
        c.machine("kv").maybe_compact(max_batches=1)
        mv = MaintainedView(
            c, Dataflow(_q1ish_mir()), {"kv": ("kv", KV)}, "mv_out2"
        )
        # Hydrates at as_of=4 (the compacted since), then catches up.
        mv.run_until(6)
        assert as_multiset(mv.peek()) == {(1, 6): 1}


class TestDeviceResidentIndexSharing:
    """Round-3 item: same-process index imports stay ON DEVICE — the
    publisher's output spine is the snapshot and its step deltas are the
    pushed batches; no host round-trip on the sharing path (the
    TraceManager shares traces in memory, arrangement/manager.rs:33)."""

    def test_publisher_to_subscriber_zero_host_transfers(self):
        from materialize_tpu.storage.persist import IndexSource

        c = PersistClient(MemBlob(), MemConsensus())
        w = c.open_writer("kv", KV)
        for t, ups in enumerate(
            [[(1, 10, 1), (2, 20, 1)], [(1, 5, 1)], [(2, 20, -1)]]
        ):
            w.compare_and_append(*_updates(ups, t=t), t, t + 1)

        # Publisher: an INDEX (no output shard) on the summed view.
        pub = MaintainedView(
            c, Dataflow(_q1ish_mir()), {"kv": ("kv", KV)}, None
        )
        pub.run_until(3)
        assert as_multiset(pub.peek()) == {(1, 15): 1}

        # Subscriber imports the index: threshold-style downstream view.
        sub_schema = pub.df.out_schema
        isrc = IndexSource(pub, sub_schema)
        sub_expr = mir.Get("agg", sub_schema).filter(
            [col(1).gte(col(1))]  # identity-ish filter, keeps rows
        )
        sub = MaintainedView(
            c2 := c, Dataflow(sub_expr), {}, None,
            index_sources={"agg": isrc},
        )
        assert isrc._device, "same-process single-device import"
        assert isrc.host_transfers == 0
        assert as_multiset(sub.peek()) == as_multiset(pub.peek())

        # Deltas flow device->device: new input propagates through the
        # publisher step into the subscriber without host transfers.
        w.compare_and_append(*_updates([(3, 7, 1)], t=3), 3, 4)
        pub.run_until(4)
        sub.run_until(4)
        assert isrc.host_transfers == 0
        assert as_multiset(sub.peek()) == as_multiset(pub.peek())
        got = as_multiset(sub.peek())
        assert got[(3, 7)] == 1
