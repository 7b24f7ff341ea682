"""The system under test, as a client sees it: ``environmentd`` as a
child process spoken to over HTTP SQL and pgwire, its durable shards
read with a persist client of this process's own, and the replica's
hook directory (``replica_hook/sitecustomize.py``).

Grown from a copy of ``chip_smoke.py``'s ``Server`` and ``Oracle``
(PR 24). This is the only module of the benchmark that imports the
program: the persist client that decodes the shards. Everything that
judges an answer (``references/``, ``compare.py``) works on the plain
numpy columns handed out from here.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class BenchFailure(Exception):
    pass


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Server:
    """environmentd with one one-device replica, as a child process."""

    def __init__(
        self, run_dir: str, tick_interval: float, replica_platform
    ):
        self.data_dir = os.path.join(run_dir, "data")
        self.hook_dir = os.path.join(run_dir, "hook")
        os.makedirs(self.hook_dir)
        self.log_path = os.path.join(run_dir, "environmentd.log")
        self.http_port = free_port()
        self.pg_port = free_port()
        env = dict(os.environ)
        if replica_platform is not None:
            # environmentd pins ITSELF to the CPU and hands this
            # environment to its replica unchanged.
            env["JAX_PLATFORMS"] = replica_platform
        env.pop("XLA_FLAGS", None)
        env["BENCH_HOOK_DIR"] = self.hook_dir
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(HERE, "replica_hook")]
            + [p for p in [env.get("PYTHONPATH")] if p]
        )
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m",
                "materialize_tpu.server.environmentd",
                "--data-dir", self.data_dir,
                "--pg-port", str(self.pg_port),
                "--http-port", str(self.http_port),
                "--replicas", "1", "--workers", "1",
                "--tick-interval", str(tick_interval),
            ],
            cwd=REPO, env=env, stdout=self.log,
            stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 40) -> str:
        self.log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise BenchFailure(
                f"environmentd exited with code {rc}:\n" + self.log_tail()
            )

    def wait_listening(self, timeout: float) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.check_alive()
            with open(self.log_path, errors="replace") as f:
                for line in f:
                    if line.startswith("materialize_tpu listening:"):
                        return line.strip()
            time.sleep(0.1)
        raise BenchFailure(
            "environmentd did not listen in time:\n" + self.log_tail()
        )

    def sql(self, query: str, timeout: float = 900.0) -> list:
        """POST /api/sql; the ``results`` list, one entry a statement.
        A server-side error is a failure."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http_port}/api/sql",
            data=json.dumps({"query": query}).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                body = json.loads(r.read())
        except urllib.error.HTTPError as e:
            raise BenchFailure(
                f"{query[:120]!r} -> HTTP {e.code}: "
                f"{e.read()[:2000].decode(errors='replace')}"
            )
        for res in body["results"]:
            if "error" in res:
                raise BenchFailure(f"{query[:120]!r} -> {res['error']}")
        return body["results"]

    def rows(self, query: str, timeout: float = 900.0) -> list:
        return self.sql(query, timeout)[-1]["rows"]

    def readyz(self) -> tuple[int, dict]:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.http_port}/api/readyz", timeout=30
            ) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    def wait_hydrated(self, names: list, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            self.check_alive()
            code, verdict = self.readyz()
            status = {
                r[0]: r[2]
                for r in self.rows(
                    "SELECT dataflow, replica, status "
                    "FROM mz_hydration_statuses"
                )
            }
            last = (code, verdict, status)
            if code == 200 and all(
                status.get(n) == "hydrated" for n in names
            ):
                return
            time.sleep(0.25)
        raise BenchFailure(f"hydration timed out: {last!r}")

    def replica_device(self) -> dict:
        """The device as the one connected replica reported it."""
        reps = self.rows(
            "SELECT name, connected, platform, device_kind, devices "
            "FROM mz_cluster_replicas"
        )
        if len(reps) != 1 or not reps[0][1]:
            raise BenchFailure(f"expected one connected replica: {reps}")
        return {
            "platform": reps[0][2], "kind": reps[0][3],
            "count": int(reps[0][4]),
        }

    def compile_log(self) -> list:
        """mz_compile_log rows of the replica: (kind, dataflow,
        seconds, cache, tier)."""
        return [
            (r[1], r[2], float(r[3]), r[4], r[5])
            for r in self.rows(
                "SELECT process, kind, dataflow, seconds, cache, tier "
                "FROM mz_compile_log"
            )
            if r[0] == "r0"  # the one replica; not the coordinator's plans
        ]

    def replica_metric(self, name: str, default=0.0) -> float:
        for k, v in self.rows("SELECT metric, value FROM mz_metrics"):
            if k.startswith(name + "{") and "replica=" in k:
                return float(v)
        return default

    def hook_send(self, command: str, payload: dict) -> None:
        """One command file for the replica's hook (written whole)."""
        err = os.path.join(self.hook_dir, "hook_error.json")
        if os.path.exists(err):
            os.unlink(err)
        tmp = os.path.join(self.hook_dir, command + ".tmp")
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, os.path.join(self.hook_dir, command))

    def hook_wait(self, answer: str, timeout: float) -> dict:
        """The hook's answer file, once it is there; it is consumed."""
        err = os.path.join(self.hook_dir, "hook_error.json")
        out = os.path.join(self.hook_dir, answer)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(out):
                with open(out) as f:
                    got = json.load(f)
                os.unlink(out)
                return got
            if os.path.exists(err):
                with open(err) as f:
                    raise BenchFailure(f"replica hook: {f.read()}")
            self.check_alive()
            time.sleep(0.05)
        raise BenchFailure(f"the replica's hook wrote no {answer}")

    def child_pids(self) -> list[int]:
        out = subprocess.run(
            ["pgrep", "-P", str(self.proc.pid)],
            capture_output=True, text=True,
        ).stdout.split()
        return [int(p) for p in out]

    def shutdown(self) -> dict:
        """SIGTERM environmentd (its graceful stop reaps the replica),
        wait for parent and replica, kill what is left."""
        pids = self.child_pids()
        rc = self.proc.poll()
        if rc is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                rc = self.proc.wait(timeout=10)
        deadline = time.monotonic() + 15
        left = pids
        while left and time.monotonic() < deadline:
            left = [p for p in left if os.path.exists(f"/proc/{p}")]
            time.sleep(0.1)
        for p in left:  # stop every process we started, whatever happened
            try:
                os.kill(p, signal.SIGKILL)
            except OSError:
                pass
        self.log.close()
        return {"environmentd_rc": rc, "replicas_killed": left}


class PgClient:
    """A minimal PostgreSQL v3 simple-query client (one connection)."""

    def __init__(self, port: int, timeout: float = 90.0):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        payload = struct.pack("!I", 196608) + b"user\x00bench\x00\x00"
        self.sock.sendall(struct.pack("!I", len(payload) + 4) + payload)
        self._until_ready()

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass

    def _recv_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        return bytes(buf)

    def _until_ready(self) -> list:
        msgs = []
        while True:
            tag = self._recv_exact(1)
            (length,) = struct.unpack("!I", self._recv_exact(4))
            msgs.append((tag, self._recv_exact(length - 4)))
            if tag == b"Z":
                return msgs

    def query(self, sql: str) -> list:
        """Rows of text fields (None for NULL); raises on an error
        response. Returns when the last row and ReadyForQuery are in."""
        payload = sql.encode() + b"\x00"
        self.sock.sendall(
            b"Q" + struct.pack("!I", len(payload) + 4) + payload
        )
        rows, error = [], None
        for tag, payload in self._until_ready():
            if tag == b"D":
                (n,) = struct.unpack("!H", payload[:2])
                off, row = 2, []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", payload[off:off + 4])
                    off += 4
                    if ln == -1:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif tag == b"E":
                for f in payload.split(b"\x00"):
                    if f[:1] == b"M":
                        error = f[1:].decode()
        if error is not None:
            raise BenchFailure(f"{sql[:80]!r} -> {error}")
        return rows


class Shards:
    """The deployment's durable shards, read by this process's own
    persist client: the inputs the views were computed from and what
    the replica wrote. Registering a reader holds a shard's ``since``,
    so every time from then on stays distinct and readable."""

    def __init__(self, data_dir: str):
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )

        self.blob_root = os.path.join(data_dir, "blob")
        self.client = PersistClient(
            FileBlob(self.blob_root),
            SqliteConsensus(os.path.join(data_dir, "consensus.db")),
        )
        self.readers: dict = {}

    def names(self) -> list:
        return sorted(
            d for d in os.listdir(self.blob_root)
            if os.path.isdir(os.path.join(self.blob_root, d))
        )

    def open(self, alias: str, suffix: str) -> None:
        """Open (and pin) the one shard whose directory name is
        ``suffix`` or ends with ``_<suffix>``."""
        found = [
            d for d in self.names()
            if d == suffix or d.endswith("_" + suffix)
        ]
        if len(found) != 1:
            raise BenchFailure(
                f"expected one shard for {suffix!r} under "
                f"{self.blob_root}: {self.names()}"
            )
        self.readers[alias] = self.client.open_reader(
            found[0], "benchmark-oracle"
        )

    def upper(self, alias: str) -> int:
        return int(self.readers[alias].upper)

    def close(self) -> None:
        for r in self.readers.values():
            try:
                r.expire()
            except Exception:
                pass

    @staticmethod
    def _plain(schema, cols, time_, diff, names=None) -> dict:
        """Program batches -> plain numpy: one array a column by name
        (dictionary codes decoded to strings), ``time``, ``diff``.
        ``names`` renames the columns by position (a view's shard keeps
        the plan's column names, not the view's)."""
        import numpy as np

        from materialize_tpu.repr.schema import GLOBAL_DICT

        out = {}
        if names is not None and len(names) != len(schema.columns):
            raise BenchFailure(
                f"shard has columns {schema.names}, expected {names}"
            )
        for i, (c, a) in enumerate(zip(schema.columns, cols)):
            a = np.asarray(a)
            if c.ctype.value == "string":
                uniq, inv = np.unique(a, return_inverse=True)
                strs = np.array(GLOBAL_DICT.decode_many(uniq), dtype=str)
                a = strs[inv.reshape(-1)] if len(a) else strs[:0]
            out[c.name if names is None else names[i]] = a
        out["time"] = np.asarray(time_).astype(np.int64)
        out["diff"] = np.asarray(diff).astype(np.int64)
        return out

    def snapshot(self, alias: str, as_of: int, names=None) -> dict:
        schema, cols, _n, t, diff = self.readers[alias].snapshot(as_of)
        return self._plain(schema, cols, t, diff, names)

    def updates(self, alias: str, lo: int, hi: int, names=None) -> dict:
        """Updates with lo <= time < hi."""
        schema, cols, _n, t, diff = self.readers[alias].fetch(lo, hi)
        return self._plain(schema, cols, t, diff, names)
