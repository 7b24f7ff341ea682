"""String-dictionary gap exhaustion and rebalance recovery.

Round-3 verdict weak #2/#7: a dense insertion order can exhaust a label
gap (observed in the wild: reverse() over a dictionary polluted with
catalog JSON), which used to brick the session — CreateDataflow failed
on the replica, was swallowed, and surfaced as "no such dataflow" at
peek time. Now encode raises DictExhausted, the replica rebalances the
label space, remaps installed plans, rebuilds all dataflows from durable
state, and retries the install (coord/replica.py
_recover_dict_exhaustion)."""

import numpy as np
import pytest

from materialize_tpu.repr.schema import (
    GLOBAL_DICT,
    DictExhausted,
)


def _squeeze_gap(a: str, b: str):
    """Force the labels of two (new) strings adjacent so any encode
    that lands between them exhausts the gap."""
    ca, cb = GLOBAL_DICT.encode(a), GLOBAL_DICT.encode(b)
    assert ca < cb
    with GLOBAL_DICT._lock:
        # Relabel b to ca+1 (order preserved: nothing else sits between
        # by construction — callers pick a/b lexicographically adjacent
        # in the current dictionary).
        del GLOBAL_DICT._by_code[cb]
        GLOBAL_DICT._codes[b] = ca + 1
        GLOBAL_DICT._by_code[ca + 1] = b
        GLOBAL_DICT.version += 1


class TestRebalance:
    def test_encode_raises_then_rebalance_recovers(self):
        a, b = "zzgapa", "zzgapb"
        mid = "zzgapaa"  # lands strictly between a and b
        _squeeze_gap(a, b)
        with pytest.raises(DictExhausted):
            GLOBAL_DICT.encode(mid)
        old_order = [
            s for _, s in GLOBAL_DICT.items_sorted()
        ]
        remap = GLOBAL_DICT.rebalance()
        # Order is preserved under the new labeling.
        new_order = [s for _, s in GLOBAL_DICT.items_sorted()]
        assert new_order == old_order
        codes = [c for c, _ in GLOBAL_DICT.items_sorted()]
        assert codes == sorted(codes)
        # Every old code is remapped and decodes to the same string.
        for old, new in remap.items():
            assert GLOBAL_DICT.decode(new) == GLOBAL_DICT._by_code[new]
        # The squeezed insert now succeeds.
        c = GLOBAL_DICT.encode(mid)
        assert (
            GLOBAL_DICT.encode(a) < c < GLOBAL_DICT.encode(b)
        )

    def test_remap_relation_rewrites_literals_and_constants(self):
        from materialize_tpu.expr import relation as mir
        from materialize_tpu.expr import scalar as ms
        from materialize_tpu.expr.remap import remap_relation
        from materialize_tpu.repr.schema import (
            Column,
            ColumnType,
            Schema,
        )

        code_x = GLOBAL_DICT.encode("remap_x")
        code_y = GLOBAL_DICT.encode("remap_y")
        sch = Schema(
            (
                Column("s", ColumnType.STRING),
                Column("n", ColumnType.INT64),
            )
        )
        expr = mir.Filter(
            mir.Union(
                (
                    mir.Get("t", sch),
                    mir.Constant((((code_y, 7), 1),), sch),
                )
            ),
            (
                ms.CallBinary(
                    ms.BinaryFunc.EQ,
                    ms.ColumnRef(0),
                    ms.Literal(code_x, ColumnType.STRING),
                ),
            ),
        )
        remap = {code_x: 111, code_y: 222}
        out = remap_relation(expr, remap)
        assert out.predicates[0].right.value == 111
        assert out.input.inputs[1].rows[0][0][0] == 222
        # Integer literals/values untouched.
        assert out.input.inputs[1].rows[0][0][1] == 7
        # No-op remap returns the same object (cheap fingerprinting).
        assert remap_relation(expr, {}) is expr


class TestReplicaRecovery:
    def test_exhaustion_during_query_recovers_end_to_end(
        self, tmp_path
    ):
        """Install a maintained view over strings, squeeze the gap the
        next query's env-table build must insert into, and check the
        query still answers (replica rebalanced + rebuilt)."""
        import socket
        import threading

        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )

        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "consensus.db")
        )
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever,
            args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        assert ready.wait(10)
        c = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        c.add_replica("r0", ("127.0.0.1", port))
        try:
            c.execute("CREATE TABLE rb (s text NOT NULL)")
            c.execute("INSERT INTO rb VALUES ('rbza'), ('rbzc')")
            # A maintained dataflow whose device state holds codes.
            c.execute(
                "CREATE MATERIALIZED VIEW rbv AS "
                "SELECT s FROM rb WHERE s <> 'rbzx'"
            )
            rows = c.execute("SELECT s FROM rbv").rows
            assert sorted(r[0] for r in rows) == ["rbza", "rbzc"]

            # Squeeze: upper('rbza') = 'RBZA' inserts between two
            # adjacent existing strings; make that gap width 1.
            lo = "RBZ"
            hi = "RBZB"
            _squeeze_gap(lo, hi)

            # This SELECT plans a transient dataflow whose env-table
            # build encodes 'RBZA' into the squeezed gap -> exhaustion
            # on the replica -> rebalance + rebuild + retry.
            rows = c.execute("SELECT upper(s) FROM rb").rows
            assert sorted(r[0] for r in rows) == ["RBZA", "RBZC"]

            # The maintained view survived the rebuild and still
            # answers correctly under the NEW labeling.
            rows = c.execute("SELECT s FROM rbv").rows
            assert sorted(r[0] for r in rows) == ["rbza", "rbzc"]

            # And it still maintains: new inserts flow.
            c.execute("INSERT INTO rb VALUES ('rbzb')")
            rows = c.execute("SELECT s FROM rbv").rows
            assert sorted(r[0] for r in rows) == [
                "rbza",
                "rbzb",
                "rbzc",
            ]
        finally:
            c.shutdown()


class TestStringsSltAfterLargeDict:
    def test_strings_slt_survives_polluted_dictionary(self, tmp_path):
        """The round-3 red test, distilled: pollute the dictionary with
        catalog-JSON-shaped strings (long common prefixes — the dense
        regime that exhausted a gap under reverse()'s table build), then
        run the full strings.slt. Recovery must make it pass."""
        import json as _json
        import os
        import socket
        import threading

        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )
        from materialize_tpu.testing.slt import run_slt_file

        # Dense pollution: JSON blobs differing late in the string, plus
        # their reverses (what reverse()'s table build would insert).
        for i in range(400):
            s = _json.dumps(
                {
                    "id": 1,
                    "name": f"tbl{i:03d}",
                    "sql": f"create table tbl{i:03d} (x bigint not null)",
                },
                sort_keys=True,
            )
            GLOBAL_DICT.encode(s)
            GLOBAL_DICT.encode(s[::-1])

        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "consensus.db")
        )
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever,
            args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        assert ready.wait(10)
        c = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        c.add_replica("r0", ("127.0.0.1", port))
        try:
            path = os.path.join(
                os.path.dirname(__file__), "slt", "strings.slt"
            )
            run_slt_file(path, c)
        finally:
            c.shutdown()


class TestSnapshotCoherence:
    """A rebalance concurrent with an in-flight multi-row read must not
    tear the labeling mid-operation (round-4 advisor finding): readers
    capture an epoch-coherent DictSnapshot at entry; rebalance REBINDS
    the internal maps, so the snapshot keeps decoding pre-rebalance
    codes while the live dictionary serves the new labeling."""

    def test_snapshot_survives_rebalance(self):
        code_a = GLOBAL_DICT.encode("snapcoh-a")
        snap = GLOBAL_DICT.snapshot()
        assert snap.decode(code_a) == "snapcoh-a"
        remap = GLOBAL_DICT.rebalance()
        new_a = remap[code_a]
        # Live dict: only the new labeling.
        assert GLOBAL_DICT.decode(new_a) == "snapcoh-a"
        # Old snapshot: still decodes the OLD code (a step that read
        # device arrays holding old codes finishes coherently).
        assert snap.decode(code_a) == "snapcoh-a"
        assert snap.epoch == GLOBAL_DICT.epoch - 1

    def test_same_epoch_inserts_visible_to_snapshot(self):
        snap = GLOBAL_DICT.snapshot()
        c = GLOBAL_DICT.encode("snapcoh-late-insert")
        # Same generation: the snapshot shares the live maps.
        assert snap.decode(c) == "snapcoh-late-insert"
        items = dict((s, k) for k, s in snap.items_sorted())
        assert items["snapcoh-late-insert"] == c

    def test_post_rebalance_inserts_invisible_to_snapshot(self):
        snap = GLOBAL_DICT.snapshot()
        GLOBAL_DICT.rebalance()
        c = GLOBAL_DICT.encode("snapcoh-after-rebalance")
        with pytest.raises(KeyError):
            snap.decode(c)
        # items_sorted on the old snapshot stays self-consistent (no
        # KeyError from post-rebalance insertions into _sorted).
        for k, s in snap.items_sorted():
            assert snap.decode(k) == s


def test_concurrent_inserts_keep_codes_in_string_order():
    """Threads inserting one at a time and in bulk into one dictionary
    (the replica decodes parts while env tables are built): every string
    gets one code, and codes ascend with the strings."""
    import random
    import sys
    import threading

    from materialize_tpu.repr.schema import StringDictionary

    d = StringDictionary()
    errors: list = []

    def work(k: int) -> None:
        rng = random.Random(k)
        try:
            for j in range(40):
                batch = [
                    f"t{k % 4} {rng.random():.12f}"
                    for _ in range(rng.choice((1, 5, 20)))
                ]
                if j % 2:
                    d.encode_bulk(batch)
                for s in batch:
                    d.encode(s)
                if j % 10 == 0:
                    d.items_sorted()
        except Exception as e:  # reported by the assertion below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=work, args=(k,)) for k in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    items = d.items_sorted()
    strings = [s for _, s in items]
    codes = [c for c, _ in items]
    assert strings == sorted(set(strings)) and len(strings) == len(d)
    assert all(a < b for a, b in zip(codes, codes[1:]))
    assert all(d.decode(c) == s for c, s in items)
