"""Control-plane tests: CTP-analog transport, replica workers, the
compute controller's history/rehydration, nonce fencing, active-active
peek dedup, and a real subprocess replica (the clusterd-test-driver /
test/cluster analog of SURVEY.md §4.3)."""

import os
import socket
import subprocess
import sys
import threading
import time as _time

import numpy as np
import pytest

from materialize_tpu.coord import protocol as ctp
from materialize_tpu.coord.controller import ComputeController
from materialize_tpu.coord.oracle import TimestampOracle
from materialize_tpu.coord.protocol import (
    DataflowDescription,
    PersistLocation,
)
from materialize_tpu.coord.replica import ReplicaWorker, serve_forever
from materialize_tpu.expr import relation as mir
from materialize_tpu.expr.relation import AggregateExpr, AggregateFunc
from materialize_tpu.expr.scalar import col
from materialize_tpu.repr.schema import Column, ColumnType, Schema
from materialize_tpu.storage.persist import (
    FileBlob,
    MemConsensus,
    PersistClient,
    SqliteConsensus,
)

from .oracle import as_multiset

KV = Schema([Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)])


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _feed(w, t, ups):
    k = np.array([p[0] for p in ups], np.int64)
    v = np.array([p[1] for p in ups], np.int64)
    d = np.array([p[2] for p in ups], np.int64)
    w.compare_and_append(
        [k, v], [None, None], np.full(len(ups), t, np.uint64), d, t, t + 1
    )


def _sum_by_k():
    return mir.Get("kv", KV).reduce(
        (0,), (AggregateExpr(AggregateFunc.SUM_INT, col(1)),)
    )


def _desc(name="mv1", sink=None):
    return DataflowDescription(
        name=name,
        expr=_sum_by_k(),
        source_imports={"kv": ("kv", KV)},
        sink_shard=sink,
    )


def _start_replica(tmp_path, rid="r0"):
    port = _free_port()
    loc = PersistLocation(
        str(tmp_path / "blob"), str(tmp_path / "consensus.db")
    )
    ready = threading.Event()
    t = threading.Thread(
        target=serve_forever, args=(port, loc, rid, ready), daemon=True
    )
    t.start()
    assert ready.wait(10)
    return port, loc


@pytest.fixture
def persist(tmp_path):
    return PersistClient(
        FileBlob(str(tmp_path / "blob")),
        SqliteConsensus(str(tmp_path / "consensus.db")),
    )


class TestTransport:
    def test_frame_roundtrip_and_crc(self):
        a, b = socket.socketpair()
        try:
            ctp.send_msg(a, {"kind": "Hello", "nonce": 7})
            assert ctp.recv_msg(b) == {"kind": "Hello", "nonce": 7}
            # Corrupt a payload byte: crc must catch it.
            payload = b"x" * 32
            import struct

            from materialize_tpu import native

            header = ctp.FRAME_MAGIC + struct.pack(
                "<II", len(payload), native.crc32c(payload)
            )
            a.sendall(header + b"y" + payload[1:])
            with pytest.raises(ctp.TransportError):
                ctp.recv_frame(b)
        finally:
            a.close()
            b.close()


class TestReplicaController:
    def test_end_to_end_peek(self, tmp_path, persist):
        port, _loc = _start_replica(tmp_path)
        w = persist.open_writer("kv", KV)
        ctl = ComputeController()
        ctl.add_replica("r0", ("127.0.0.1", port))
        ctl.create_dataflow(_desc())
        _feed(w, 0, [(1, 10, 1), (2, 20, 1)])
        _feed(w, 1, [(1, 5, 1), (2, 20, -1)])
        ctl.wait_frontier("mv1", 1)
        rows, served = ctl.peek("mv1", as_of=1)
        assert served >= 1
        assert as_multiset(rows) == {(1, 15): 1}
        ctl.shutdown()

    def test_active_active_dedup_and_failover(self, tmp_path, persist):
        portA, _ = _start_replica(tmp_path, "rA")
        portB, _ = _start_replica(tmp_path, "rB")
        w = persist.open_writer("kv", KV)
        ctl = ComputeController()
        ctl.add_replica("rA", ("127.0.0.1", portA))
        ctl.add_replica("rB", ("127.0.0.1", portB))
        ctl.create_dataflow(_desc())
        _feed(w, 0, [(7, 1, 1)])
        ctl.wait_frontier("mv1", 0)
        rows, _ = ctl.peek("mv1", as_of=0)
        assert as_multiset(rows) == {(7, 1): 1}
        # Drop one replica: the other keeps serving (active-active HA).
        ctl.drop_replica("rA")
        _feed(w, 1, [(7, 2, 1)])
        ctl.wait_frontier("mv1", 1)
        rows, _ = ctl.peek("mv1", as_of=1)
        assert as_multiset(rows) == {(7, 3): 1}
        ctl.shutdown()

    def test_active_active_shared_sink(self, tmp_path, persist):
        """Two replicas maintain the SAME sinked MV: their deterministic
        sink appends race benignly (loser observes the upper advanced
        and treats it as success); the shard stays consistent."""
        portA, _ = _start_replica(tmp_path, "rA")
        portB, _ = _start_replica(tmp_path, "rB")
        w = persist.open_writer("kv", KV)
        ctl = ComputeController()
        ctl.add_replica("rA", ("127.0.0.1", portA))
        ctl.add_replica("rB", ("127.0.0.1", portB))
        ctl.create_dataflow(_desc(sink="mv_shared"))
        for t in range(6):
            _feed(w, t, [(t % 2, t, 1)])
        # BOTH replicas must pass the frontier (min semantics).
        deadline = _time.monotonic() + 60
        while ctl.frontier("mv1") < 6:
            assert _time.monotonic() < deadline, ctl.frontiers
            _time.sleep(0.01)
        assert not ctl.statuses, ctl.statuses
        rows, _ = ctl.peek("mv1", as_of=5)
        assert as_multiset(rows) == {(0, 6): 1, (1, 9): 1}
        # Durable shard content matches too.
        r = persist.open_reader("mv_shared")
        _sch, cols, _n, time, diff = r.snapshot(5)
        shard_rows = [
            (int(cols[0][i]), int(cols[1][i]), int(time[i]), int(diff[i]))
            for i in range(len(diff))
        ]
        assert as_multiset(shard_rows) == {(0, 6): 1, (1, 9): 1}
        ctl.shutdown()

    def test_rehydration_after_replica_restart(self, tmp_path, persist):
        """Replica dies; a new one on the same address gets the compacted
        history replayed and serves again (rehydrate_failed_replicas)."""
        port, loc = _start_replica(tmp_path, "r0")
        w = persist.open_writer("kv", KV)
        ctl = ComputeController()
        ctl.add_replica("r0", ("127.0.0.1", port))
        ctl.create_dataflow(_desc(sink="mv1_out"))
        _feed(w, 0, [(3, 30, 1)])
        ctl.wait_frontier("mv1", 0)
        # Simulate crash: start a fresh worker process state on a new
        # port and repoint the controller (orchestrator reprovisioning).
        port2, _ = _start_replica(tmp_path, "r0v2")
        ctl.drop_replica("r0")
        ctl.add_replica("r0", ("127.0.0.1", port2))
        _feed(w, 1, [(3, 12, 1)])
        ctl.wait_frontier("mv1", 1)
        rows, _ = ctl.peek("mv1", as_of=1)
        assert as_multiset(rows) == {(3, 42): 1}
        ctl.shutdown()

    def test_reconciliation_keeps_unchanged_dataflows(self, tmp_path):
        """Reconnecting with an identical description must NOT rebuild
        the dataflow (server.rs:373 reconciliation)."""
        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "consensus.db")
        )
        worker = ReplicaWorker(location=loc)
        desc = _desc()
        worker._handle_command(None, ctp.create_dataflow(desc))
        inst = worker.dataflows["mv1"]
        worker._handle_command(None, ctp.create_dataflow(desc))
        assert worker.dataflows["mv1"] is inst  # same object: kept
        changed = DataflowDescription(
            name="mv1",
            expr=_sum_by_k(),
            source_imports={"kv": ("kv2", KV)},
            sink_shard=None,
        )
        worker._handle_command(None, ctp.create_dataflow(changed))
        assert worker.dataflows["mv1"] is not inst  # rebuilt

    def test_stale_controller_cannot_install_after_takeover(
        self, tmp_path
    ):
        """ISSUE 10 satellite: once a newer controller takes over, the
        fenced (stale-nonce) session must not be able to install
        dataflows — its link is torn down and commands on it go
        nowhere; a stale RECONNECT gets HelloReject carrying the
        fencing epoch (which the client uses to fast-forward)."""
        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "consensus.db")
        )
        worker = ReplicaWorker(location=loc)
        lsock = socket.socket()
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(4)
        port = lsock.getsockname()[1]
        threading.Thread(
            target=worker.serve, args=(lsock,), daemon=True
        ).start()
        s1 = socket.create_connection(("127.0.0.1", port))
        ctp.send_msg(s1, ctp.hello(5))
        assert ctp.recv_msg(s1)["kind"] == "HelloOk"
        s2 = socket.create_connection(("127.0.0.1", port))
        ctp.send_msg(s2, ctp.hello(9))  # takeover fences s1
        assert ctp.recv_msg(s2)["kind"] == "HelloOk"
        # The stale session is torn down.
        s1.settimeout(10.0)
        with pytest.raises((ctp.TransportError, OSError)):
            while True:
                ctp.recv_msg(s1)
        # A command shoved down the stale link must never install.
        try:
            ctp.send_msg(s1, ctp.create_dataflow(_desc("stale_mv")))
        except OSError:
            pass
        _time.sleep(0.5)
        assert "stale_mv" not in worker.dataflows
        # A stale reconnect is rejected WITH the fencing epoch.
        s3 = socket.create_connection(("127.0.0.1", port))
        ctp.send_msg(s3, ctp.hello(3))
        rej = ctp.recv_msg(s3)
        assert rej["kind"] == "HelloReject" and rej["epoch"] == 9
        # The live controller still installs fine.
        ctp.send_msg(s2, ctp.create_dataflow(_desc("live_mv")))
        deadline = _time.monotonic() + 60
        while "live_mv" not in worker.dataflows:
            assert _time.monotonic() < deadline
            _time.sleep(0.05)
        for s in (s1, s2, s3):
            s.close()
        worker.stop()

    def test_restarted_controller_refences_quickly(self, tmp_path):
        """A restarted controller's nonce counter resets to 1; the
        HelloReject fast-forward (ISSUE 10) must let it re-fence a
        surviving replica in one reject round instead of probing one
        nonce per backoff cycle."""
        port, _ = _start_replica(tmp_path)
        ctl1 = ComputeController()
        ctl1.add_replica("r0", ("127.0.0.1", port))
        assert ctl1.replicas["r0"].connected.wait(15)
        ctl1.shutdown()
        ctl2 = ComputeController()  # fresh process analog: nonce = 1
        ctl2.add_replica("r0", ("127.0.0.1", port))
        assert ctl2.replicas["r0"].connected.wait(15)
        assert ctl2.replicas["r0"].fenced >= 1
        snap = ctl2.recovery_snapshot()
        assert snap["replicas"]["r0"]["connected"]
        ctl2.shutdown()

    def test_nonce_fencing(self, tmp_path):
        """A controller with a stale nonce is rejected (split-brain
        prevention, protocol/command.rs:45-53)."""
        port, _ = _start_replica(tmp_path)
        s1 = socket.create_connection(("127.0.0.1", port))
        ctp.send_msg(s1, ctp.hello(5))
        assert ctp.recv_msg(s1)["kind"] == "HelloOk"
        s2 = socket.create_connection(("127.0.0.1", port))
        ctp.send_msg(s2, ctp.hello(3))  # stale
        assert ctp.recv_msg(s2)["kind"] == "HelloReject"
        # A HIGHER nonce preempts the live session (controller restart
        # taking over): s3 connects fine, s1 is fenced and dropped.
        s3 = socket.create_connection(("127.0.0.1", port))
        ctp.send_msg(s3, ctp.hello(9))
        assert ctp.recv_msg(s3)["kind"] == "HelloOk"
        s1.settimeout(5.0)
        with pytest.raises((ctp.TransportError, OSError)):
            while True:  # drain until the fenced session is torn down
                ctp.recv_msg(s1)
        s1.close()
        s2.close()
        s3.close()


class TestSpmdReplica:
    def test_multiworker_replica_end_to_end(self, tmp_path, persist):
        """A replica whose data plane runs SPMD over a 4-device mesh
        (shard_map + all_to_all exchange) serves the same results as a
        single-device one, through the full controller + persist path."""
        port = _free_port()
        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "consensus.db")
        )
        ready = threading.Event()
        threading.Thread(
            target=serve_forever,
            args=(port, loc, "spmd", ready),
            kwargs={"workers": 4},
            daemon=True,
        ).start()
        assert ready.wait(10)
        w = persist.open_writer("kv", KV)
        ctl = ComputeController()
        ctl.add_replica("spmd", ("127.0.0.1", port))
        ctl.create_dataflow(_desc(sink="mv_spmd"))
        _feed(w, 0, [(k, k * 10, 1) for k in range(8)])
        _feed(w, 1, [(3, 5, 1), (7, 70, -1)])
        ctl.wait_frontier("mv1", 1, timeout=180)
        rows, _ = ctl.peek("mv1", as_of=1, timeout=180)
        expect = {(k, k * 10): 1 for k in range(8) if k != 7}
        expect[(3, 35)] = expect.pop((3, 30))
        assert as_multiset(rows) == expect
        # The sink shard holds the gathered, consistent content too.
        r = persist.open_reader("mv_spmd")
        _sch, cols, _n, time, diff = r.snapshot(1)
        shard_rows = [
            (int(cols[0][i]), int(cols[1][i]), int(time[i]), int(diff[i]))
            for i in range(len(diff))
        ]
        assert as_multiset(shard_rows) == expect
        ctl.shutdown()


class TestSubprocessReplica:
    def test_real_process_replica(self, tmp_path):
        """Full process boundary: spawn the replica as a subprocess
        (clusterd), drive it over TCP, kill -9 it, respawn, verify
        rehydration — the mzcompose-style distributed test."""
        port = _free_port()
        blob = str(tmp_path / "blob")
        cons = str(tmp_path / "consensus.db")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)

        def spawn():
            return subprocess.Popen(
                [
                    sys.executable, "-m", "materialize_tpu.coord.replica",
                    "--port", str(port), "--blob", blob,
                    "--consensus", cons,
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                cwd=os.path.dirname(os.path.dirname(__file__)),
            )

        proc = spawn()
        try:
            # The replica says which device it computes on (PR 24):
            # spawned with JAX_PLATFORMS=cpu it names the cpu, on its
            # "listening" line and at HelloOk.
            line = ""
            while "listening" not in line:
                line = proc.stdout.readline().decode()
                assert line, "replica exited before listening"
            assert "platform=cpu" in line and "devices=1" in line, line
            persist = PersistClient(FileBlob(blob), SqliteConsensus(cons))
            w = persist.open_writer("kv", KV)
            ctl = ComputeController()
            ctl.add_replica("r0", ("127.0.0.1", port))
            ctl.create_dataflow(_desc(sink="mv_out"))
            _feed(w, 0, [(1, 1, 1), (2, 2, 1)])
            ctl.wait_frontier("mv1", 0, timeout=120)
            rows, _ = ctl.peek("mv1", as_of=0, timeout=120)
            assert as_multiset(rows) == {(1, 1): 1, (2, 2): 1}
            (state,) = ctl.replica_states()
            assert state["device"] == {
                "platform": "cpu", "kind": "cpu", "count": 1,
            }
            # Hard-kill and respawn on the same port: controller
            # reconnects and replays history; MV resumes from its shard.
            proc.kill()
            proc.wait()
            proc = spawn()
            _feed(w, 1, [(1, 41, 1)])
            ctl.wait_frontier("mv1", 1, timeout=120)
            rows, _ = ctl.peek("mv1", as_of=1, timeout=120)
            assert as_multiset(rows) == {(1, 42): 1, (2, 2): 1}
            ctl.shutdown()
        finally:
            proc.kill()
            proc.wait()


    def test_more_workers_than_devices_refuses_at_boot(self, tmp_path):
        """A device-count misconfiguration is permanent: the replica
        process exits non-zero at boot, naming it, instead of serving
        (environmentd turns that exit into its own)."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # one CPU device
        proc = subprocess.run(
            [
                sys.executable, "-m", "materialize_tpu.coord.replica",
                "--port", str(_free_port()),
                "--blob", str(tmp_path / "blob"),
                "--consensus", str(tmp_path / "consensus.db"),
                "--workers", "4",
            ],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        )
        assert proc.returncode != 0
        assert "--workers 4 exceeds available devices (1)" in proc.stderr


class TestOracle:
    def test_monotone_and_durable(self):
        cons = MemConsensus()
        o = TimestampOracle(cons)
        t1 = o.write_ts()
        t2 = o.write_ts()
        assert t2 > t1
        o.apply_write(t2)
        assert o.read_ts() == t2
        # A "restarted" oracle on the same consensus never regresses.
        o2 = TimestampOracle(cons)
        assert o2.write_ts() > t2
        assert o2.read_ts() == t2

    def test_concurrent_allocations_unique(self):
        cons = MemConsensus()
        o = TimestampOracle(cons)
        got = []
        lock = threading.Lock()

        def alloc():
            for _ in range(20):
                ts = o.write_ts()
                with lock:
                    got.append(ts)

        ts_threads = [threading.Thread(target=alloc) for _ in range(4)]
        for t in ts_threads:
            t.start()
        for t in ts_threads:
            t.join()
        assert len(set(got)) == len(got)
