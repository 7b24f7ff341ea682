"""Happens-before race detector tests (ISSUE 17 tentpole b): the
vector-clock mechanics (fork/join and lock release/acquire edges),
finding quality (both stack chains), the suppression valve, pinned
reproductions of the racy access patterns this PR fixed in the
control plane, and a clean bill over the real serving + subscribe
paths with the detector on."""

import threading

import pytest

from materialize_tpu.analysis import racecheck
from materialize_tpu.utils import lockcheck

pytestmark = pytest.mark.analysis


@pytest.fixture
def detector():
    lockcheck.enable(reset=True)
    racecheck.enable(reset=True)
    yield racecheck
    # Leave the detector in whatever state the lane's dyncfg asks for
    # (the `pytest -m analysis` conftest enables it suite-wide).
    racecheck.disable()
    racecheck.maybe_enable_from_dyncfg(reset=True)


def _findings_for(name):
    return [f for f in racecheck.findings() if f.name == name]


class TestMechanics:
    def test_unlocked_concurrent_writes_detected(self, detector):
        racecheck.declare_shared("test.ww")
        wrote = threading.Event()

        def child():
            lockcheck.shared_write("test.ww")
            wrote.set()

        t = threading.Thread(target=child)
        t.start()
        assert wrote.wait(5)
        # Event hand-offs are deliberately NOT modeled: this write is
        # ordered in wall-clock time but not in the happens-before
        # relation — exactly the kind of "works on my machine" pair
        # the detector exists to flag.
        lockcheck.shared_write("test.ww")
        t.join()
        found = _findings_for("test.ww")
        assert [f.kind for f in found] == ["write-write"]

    def test_finding_carries_both_stack_chains(self, detector):
        racecheck.declare_shared("test.stacks")
        wrote = threading.Event()

        def child():
            lockcheck.shared_write("test.stacks")
            wrote.set()

        t = threading.Thread(target=child)
        t.start()
        assert wrote.wait(5)
        lockcheck.shared_write("test.stacks")
        t.join()
        (f,) = _findings_for("test.stacks")
        assert "test_racecheck.py" in f.a_where
        assert "test_racecheck.py" in f.b_where
        assert f.a_thread != f.b_thread
        assert "no happens-before edge" in str(f)

    def test_common_lock_orders_the_pair(self, detector):
        racecheck.declare_shared("test.locked")
        lk = lockcheck.tracked_lock("test.locked.lock")
        wrote = threading.Event()

        def child():
            with lk:
                lockcheck.shared_write("test.locked")
            wrote.set()

        t = threading.Thread(target=child)
        t.start()
        assert wrote.wait(5)
        with lk:  # acquire joins the clock the child's release left
            lockcheck.shared_write("test.locked")
        t.join()
        assert _findings_for("test.locked") == []

    def test_fork_and_join_edges(self, detector):
        racecheck.declare_shared("test.forkjoin")
        lockcheck.shared_write("test.forkjoin")  # before start: ordered

        def child():
            lockcheck.shared_read("test.forkjoin")
            lockcheck.shared_write("test.forkjoin")

        t = threading.Thread(target=child)
        t.start()
        t.join()
        lockcheck.shared_read("test.forkjoin")  # after join: ordered
        assert _findings_for("test.forkjoin") == []

    def test_suppress_is_a_valve(self, detector):
        racecheck.declare_shared("test.benign")
        racecheck.suppress("test.benign")
        try:
            wrote = threading.Event()

            def child():
                lockcheck.shared_write("test.benign")
                wrote.set()

            t = threading.Thread(target=child)
            t.start()
            assert wrote.wait(5)
            lockcheck.shared_write("test.benign")
            t.join()
            assert _findings_for("test.benign") == []
        finally:
            racecheck.unsuppress("test.benign")

    def test_declared_registry_covers_the_control_plane(self):
        reg = racecheck.registry()
        for name in (
            "controller.replicas",
            "controller.observed",
            "controller.peek_events",
            "controller.replica_stats",
            "subscribe.sessions",
            "freshness.lag_rings",
            "compile_ledger.seen",
            "dyncfg.values",
        ):
            assert name in reg, name


class TestFixedRaceReproductions:
    """Each pattern below is one this PR found live in the control
    plane and fixed; the reproduction pins the detector's ability to
    re-find it if the fix regresses."""

    def test_unlocked_snapshot_read_races_locked_write(self, detector):
        """controller.replicas pre-fix: _broadcast iterated
        self.replicas with NO lock while add_replica assigned under
        controller.state. The fix snapshots under the lock
        (coord/controller.py _broadcast)."""
        racecheck.declare_shared("repro.replicas")
        state = lockcheck.tracked_lock("repro.state")
        wrote = threading.Event()

        def adder():
            with state:
                lockcheck.shared_write("repro.replicas")
            wrote.set()

        t = threading.Thread(target=adder)
        t.start()
        assert wrote.wait(5)
        lockcheck.shared_read("repro.replicas")  # pre-fix: no lock
        t.join()
        assert [f.kind for f in _findings_for("repro.replicas")] == [
            "write-read"
        ]

    def test_wrong_lock_does_not_order(self, detector):
        """subscribe.session_count pre-fix: the hub's census read
        t.sessions under only the HUB lock while add/remove_session
        mutated under the TAIL lock — two locks, zero edges. The fix
        takes the tail lock per tail (coord/subscribe.py,
        hub -> tail nesting, the order close_session already uses)."""
        racecheck.declare_shared("repro.sessions")
        tail = lockcheck.tracked_lock("repro.tail")
        hub = lockcheck.tracked_lock("repro.hub")
        wrote = threading.Event()

        def session_add():
            with tail:
                lockcheck.shared_write("repro.sessions")
            wrote.set()

        t = threading.Thread(target=session_add)
        t.start()
        assert wrote.wait(5)
        with hub:  # pre-fix census: the WRONG lock
            lockcheck.shared_read("repro.sessions")
        t.join()
        assert [f.kind for f in _findings_for("repro.sessions")] == [
            "write-read"
        ]
        # and the fixed shape — hub THEN tail — is clean:
        racecheck.clear()
        t2 = threading.Thread(target=session_add)
        wrote.clear()
        t2.start()
        assert wrote.wait(5)
        with hub:
            with tail:
                lockcheck.shared_read("repro.sessions")
        t2.join()
        assert _findings_for("repro.sessions") == []


class TestServingPathClean:
    def test_serving_and_subscribe_paths_record_zero_findings(
        self, detector, tmp_path
    ):
        """The tier-1 control plane — DDL, ingest, fast/slow peeks,
        SUBSCRIBE delivery and teardown, introspection — produces no
        unsuppressed happens-before findings over the declared
        shared-state set (the same drive as the `race-free` gate of
        scripts/check_plans.py --bench, which tier-1 does not run)."""
        import socket
        import time

        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )

        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "c.db")
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
        coord = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        try:
            coord.add_replica("r0", ("127.0.0.1", port))
            coord.execute("CREATE TABLE t (a BIGINT, b BIGINT)")
            coord.execute("INSERT INTO t VALUES (1, 2), (3, 4)")
            coord.execute(
                "CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM t"
            )
            coord.execute("SELECT * FROM mv")
            sub = coord.execute(
                "SUBSCRIBE TO (SELECT a, b FROM t WHERE a >= 0)"
            ).subscription
            coord.execute("INSERT INTO t VALUES (5, 6)")
            final = coord._table_writers["t"].upper
            deadline = time.monotonic() + 60.0
            while sub.frontier < final and time.monotonic() < deadline:
                sub.pop_ready()
                time.sleep(0.01)
            sub.close()
            coord.execute("SELECT * FROM mz_donation")
            time.sleep(0.2)
        finally:
            coord.shutdown()
        assert [str(f) for f in racecheck.findings()] == []
