"""environmentd: the controller process.

Analog of the reference's ``environmentd`` (``Listeners::serve``,
``environmentd/src/lib.rs:361``): opens the durable catalog, boots the
coordinator + controllers, (optionally) spawns replica subprocesses, and
serves pgwire + HTTP. One command brings up a working deployment:

    python -m materialize_tpu.server.environmentd \
        --data-dir DIR [--pg-port P] [--http-port P] [--replicas N]
"""

from __future__ import annotations

import argparse
import atexit
import os
import signal
import socket
import subprocess
import sys
import time as _time

from ..coord.coordinator import Coordinator
from ..storage.persist import FileBlob, PersistClient, SqliteConsensus
from .http import HttpServer
from .pgwire import PgServer


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def bank_path(data_dir: str) -> str:
    """The deployment's program-bank directory (ISSUE 16): under the
    blob root, so the bank rides the same durable storage the shards
    do and ``--recover`` finds warm executables next to warm state."""
    return os.path.join(data_dir, "blob", "program_bank")


def spawn_replica(
    data_dir: str, port: int, rid: str, workers: int = 1
) -> subprocess.Popen:
    """One clusterd subprocess (orchestrator-process analog). It gets
    the environment this process was given, unchanged: the platform
    ``JAX_PLATFORMS`` names there is the REPLICA's (this process pins
    its own JAX to the CPU in ``main``)."""
    env = dict(os.environ)
    # Subprocess replicas share the deployment's program bank: the env
    # var is resolved once by compile.bank.get_bank() at first
    # ledger_jit dispatch — no flag threading through replica main.
    env.setdefault("MZ_PROGRAM_BANK", bank_path(data_dir))
    return subprocess.Popen(
        [
            sys.executable, "-m", "materialize_tpu.coord.replica",
            "--port", str(port),
            "--blob", os.path.join(data_dir, "blob"),
            "--consensus", os.path.join(data_dir, "consensus.db"),
            "--replica-id", rid,
            "--workers", str(workers),
        ],
        env=env,
    )


class Environment:
    """A running deployment: coordinator + replicas + listeners."""

    def __init__(
        self,
        data_dir: str,
        pg_port: int = 0,
        http_port: int = 0,
        n_replicas: int = 1,
        workers: int = 1,
        tick_interval: float | None = 0.05,
        in_process_replicas: bool = False,
    ):
        os.makedirs(data_dir, exist_ok=True)
        self.data_dir = data_dir
        # Every process in the deployment — this one (coordinator +
        # in-process replicas) and spawned subprocess replicas (via
        # MZ_PROGRAM_BANK in spawn_replica) — shares one bank under
        # the blob root. Recovery's re-renders become bank hits.
        from ..compile.bank import configure_bank
        from ..utils.lockcheck import tracked_lock

        configure_bank(bank_path(data_dir))
        self.procs: list[subprocess.Popen] = []
        self._in_process = in_process_replicas
        self._default_workers = workers
        # Replica registry (ISSUE 19): rid -> {port, proc|worker+thread,
        # workers}. add/drop/rolling-restart/autoscale actions all
        # serialize on the scale lock — the interleave model
        # `autoscale-vs-restart` pins why (an unserialized check-then-
        # spawn can bust the replica band or drop the last server).
        self.replica_records: dict[str, dict] = {}
        self._scale_lock = tracked_lock("environment.scale")
        self._replica_seq = n_replicas
        for i in range(n_replicas):
            self._spawn_record(f"r{i}", workers=workers)
        self.coord = Coordinator(
            PersistClient(
                FileBlob(os.path.join(data_dir, "blob")),
                SqliteConsensus(os.path.join(data_dir, "consensus.db")),
                # Production client (ISSUE 20): table/catalog appends
                # request leased background compaction off the serving
                # path per the compaction_mode dyncfg.
                auto_compaction=True,
            ),
            tick_interval=tick_interval,
        )
        for rid, rec in self.replica_records.items():
            self.coord.add_replica(rid, ("127.0.0.1", rec["port"]))
        self.pg = PgServer(self.coord, port=pg_port).start()
        self.http = HttpServer(self.coord, port=http_port).start()
        self._down = False
        # The SLO-driven autoscaler (coord/autoscaler.py): the policy
        # thread always runs; it acts only while the autoscale_policy
        # dyncfg is non-empty, so SET enables/disables it live.
        from ..coord.autoscaler import Autoscaler

        self.autoscaler = Autoscaler(
            self.coord.controller,
            lambda: self.add_replica(),
            lambda rid: self.drop_replica(rid, drain=True),
        ).start()

    # -- replica lifecycle (ISSUE 19) ---------------------------------------
    def _spawn_record(
        self, rid: str, workers: int | None = None
    ) -> dict:
        """Start one replica (subprocess or in-process thread, matching
        the deployment mode) and register it in the records map. Does
        NOT touch the coordinator — callers pair this with
        coord.add_replica under the scale lock."""
        port = _free_port()
        w = self._default_workers if workers is None else workers
        if self._in_process:
            import threading

            from ..coord.protocol import PersistLocation
            from ..coord.replica import serve_forever

            ready = threading.Event()
            handle: list = []
            t = threading.Thread(
                target=serve_forever,
                args=(
                    port,
                    PersistLocation(
                        os.path.join(self.data_dir, "blob"),
                        os.path.join(self.data_dir, "consensus.db"),
                    ),
                    rid,
                    ready,
                ),
                kwargs={"workers": w, "handle": handle},
                daemon=True,
            )
            t.start()
            ready.wait(10)
            rec = {
                "port": port,
                "proc": None,
                "worker": handle[0] if handle else None,
                "thread": t,
                "workers": w,
            }
        else:
            p = spawn_replica(self.data_dir, port, rid, w)
            self.procs.append(p)
            rec = {
                "port": port, "proc": p, "worker": None,
                "thread": None, "workers": w,
            }
        self.replica_records[rid] = rec
        return rec

    def _stop_record(self, rec: dict) -> None:
        p = rec.get("proc")
        if p is not None:
            from ..utils.retry import policy as _retry_policy

            budget = _retry_policy("shutdown").budget or 5.0
            p.terminate()
            try:
                p.wait(timeout=budget)
            except subprocess.TimeoutExpired:
                p.kill()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass
            if p in self.procs:
                self.procs.remove(p)
        w = rec.get("worker")
        if w is not None:
            w.stop()
            t = rec.get("thread")
            if t is not None:
                t.join(2)

    def add_replica(
        self, rid: str | None = None, workers: int | None = None
    ) -> str:
        """Runtime scale-up (`CREATE CLUSTER REPLICA` analog): spawn,
        register with the controller (the nonce Hello fences it like
        any boot-time replica), and return the name. It hydrates from
        the shared program bank, so join time is seconds — it becomes
        a routing candidate once the hydration board flips."""
        with self._scale_lock:
            if self._down:
                raise RuntimeError("environment is shut down")
            if rid is None:
                rid = f"r{self._replica_seq}"
                self._replica_seq += 1
            if rid in self.replica_records:
                raise ValueError(f"replica {rid!r} already exists")
            rec = self._spawn_record(rid, workers=workers)
            self.coord.add_replica(rid, ("127.0.0.1", rec["port"]))
        return rid

    def drop_replica(self, rid: str, drain: bool = True) -> dict:
        """Runtime scale-down (`DROP CLUSTER REPLICA` analog): drain
        (stop routing, move in-flight reads, then drop) or hard-drop,
        then stop the process/thread."""
        with self._scale_lock:
            return self._drop_replica_locked(rid, drain)

    def _drop_replica_locked(self, rid: str, drain: bool) -> dict:
        rec = self.replica_records.pop(rid, None)
        if rec is None:
            return {"dropped": False, "reason": "unknown replica"}
        ctl = self.coord.controller
        if drain:
            out = dict(ctl.drain_replica(rid))
        else:
            ctl.drop_replica(rid)
            out = {"drained": False}
        self._stop_record(rec)
        out["dropped"] = True
        return out

    def rolling_restart(
        self, hydrate_timeout: float = 60.0
    ) -> dict:
        """Restart every replica, one at a time, under live ingest +
        serving. Per replica: wait until every durable dataflow has at
        least one OTHER serving replica, drain it (in-flight reads
        move immediately), stop it, respawn the SAME rid (fenced
        Hello, warm program bank -> seconds-scale rehydration), and
        wait until it serves again before touching the next one.

        The "at least one hydrated replica serves every durable
        dataflow at every instant" invariant is CHECKED, not assumed:
        a monitor thread samples `controller.serving_replicas` for
        every durable dataflow throughout and the report carries every
        violation (none = the restart was continuously served).
        `rebuilds` counts the restarted replicas' reported dataflow
        rebuilds — 0 on unchanged fingerprints (reconciliation +
        program bank)."""
        import threading
        import time as _t

        ctl = self.coord.controller
        dataflows = sorted(set(self.coord.peekable.values()))
        monitor_stop = threading.Event()
        violations: list = []
        samples = [0]

        def monitor():
            while not monitor_stop.is_set():
                samples[0] += 1
                for df in dataflows:
                    if not ctl.serving_replicas(df):
                        violations.append((df, samples[0]))
                monitor_stop.wait(0.02)

        mt = threading.Thread(target=monitor, daemon=True)
        mt.start()
        report: dict = {"replicas": [], "aborted": None}
        try:
            for rid in list(self.replica_records):
                with self._scale_lock:
                    if rid not in self.replica_records:
                        continue  # dropped while we iterated
                    entry: dict = {"replica": rid}
                    t0 = _t.monotonic()
                    deadline = t0 + hydrate_timeout
                    # Precondition: losing `rid` must leave every
                    # durable dataflow served by someone else.
                    uncovered = dataflows
                    while _t.monotonic() < deadline:
                        uncovered = [
                            df
                            for df in dataflows
                            if not [
                                r
                                for r in ctl.serving_replicas(df)
                                if r != rid
                            ]
                        ]
                        if not uncovered:
                            break
                        _t.sleep(0.05)
                    if uncovered:
                        entry["error"] = (
                            "no other serving replica for "
                            f"{uncovered}; restart aborted"
                        )
                        report["replicas"].append(entry)
                        report["aborted"] = rid
                        break
                    workers = self.replica_records[rid]["workers"]
                    drained = self._drop_replica_locked(
                        rid, drain=True
                    )
                    entry["moved_reads"] = drained.get("moved", 0)
                    rec = self._spawn_record(rid, workers=workers)
                    self.coord.add_replica(
                        rid, ("127.0.0.1", rec["port"])
                    )
                    while _t.monotonic() < deadline:
                        if all(
                            rid in ctl.serving_replicas(df)
                            for df in dataflows
                        ):
                            break
                        _t.sleep(0.05)
                    entry["seconds"] = round(_t.monotonic() - t0, 3)
                    entry["rehydrated"] = all(
                        rid in ctl.serving_replicas(df)
                        for df in dataflows
                    )
                report["replicas"].append(entry)
        finally:
            monitor_stop.set()
            mt.join(2)
        # The restarted replicas' own rebuild counts (piggybacked on
        # their frontier reports): 0 on unchanged fingerprints.
        restarted = {e["replica"] for e in report["replicas"]}
        rebuilds = 0
        snap = ctl.recovery_snapshot()["dataflows"]
        for df, per in snap.items():
            for rep, counters in per.items():
                if rep in restarted:
                    rebuilds += int(counters.get("rebuilds", 0))
        report["rebuilds"] = rebuilds
        report["invariant"] = {
            "samples": samples[0],
            "violations": violations[:20],
            "continuously_served": not violations,
        }
        return report

    def await_replicas(self, timeout: float = 600.0) -> dict:
        """Block until every subprocess replica has said Hello, and
        return ``{rid: device}`` as each reported it. A replica that
        EXITS before connecting (its platform could not be had: no
        chip, or more replica processes than chips) raises with its
        exit code — its own stderr already carries JAX's error — so
        the caller ends the deployment instead of waiting on it or
        serving without it."""
        ctl = self.coord.controller
        deadline = _time.monotonic() + timeout
        while True:
            states = {s["name"]: s for s in ctl.replica_states()}
            pending = []
            for rid, rec in self.replica_records.items():
                s = states.get(rid)
                if s is not None and s["connected"] and s["device"]:
                    continue
                p = rec.get("proc")
                if p is not None and p.poll() is not None:
                    raise RuntimeError(
                        f"replica {rid} exited with code "
                        f"{p.returncode} before connecting"
                    )
                pending.append(rid)
            if not pending:
                return {
                    rid: states[rid]["device"]
                    for rid in self.replica_records
                }
            if _time.monotonic() > deadline:
                raise RuntimeError(
                    f"replicas {pending} did not connect within "
                    f"{timeout:.0f}s"
                )
            _time.sleep(0.05)

    # -- restart recovery (ISSUE 10) ----------------------------------------
    def recovery_report(self) -> dict:
        """What this boot recovered: the coordinator's catalog replay
        counts and the controller's replica/dataflow recovery view
        (the programmatic face of `mz_recovery`)."""
        report = {"coordinator": dict(self.coord.recovery)}
        report.update(self.coord.controller.recovery_snapshot())
        # Compile breakdown (ISSUE 16): how much of this boot's
        # compile wall the program bank absorbed. A warm-bank recover
        # of unchanged fingerprints shows bank_misses == 0 — ZERO
        # fresh XLA compiles — with the skipped wall in
        # compile_seconds_recovered.
        from ..compile.bank import get_bank
        from ..utils.compile_ledger import LEDGER

        s = LEDGER.summary()
        compiles = {
            "bank_hits": s["bank_hits"],
            "bank_misses": s["bank_misses"],
            "compile_seconds_recovered": s["bank_seconds_recovered"],
            "fresh_compiles": s["misses"],
        }
        bank = get_bank()
        if bank is not None:
            compiles["bank"] = bank.snapshot()
        report["compiles"] = compiles
        return report

    def await_recovery(self, timeout: float = 120.0) -> dict:
        """Block until every durable dataflow (MV/index) the replayed
        catalog re-registered is installed on some replica, then
        return the recovery report — the --recover boot path's proof
        obligation: the catalog came back AND the dataflows re-rendered
        and re-hydrated (from input-shard snapshots at the persisted
        as_of; storage/persist/operators.py)."""
        import time as _t

        deadline = _t.monotonic() + timeout
        for name in sorted(set(self.coord.peekable.values())):
            self.coord.controller.wait_installed(
                name, timeout=max(deadline - _t.monotonic(), 0.1)
            )
        # Install-acked is not compile-counted: hydration is the phase
        # that consults the program bank, and subprocess replicas ship
        # their compile records on the same Frontiers report that
        # flips the hydration board. Wait for the readiness verdict
        # (every durable dataflow hydrated somewhere), then let the
        # piggybacked ledger settle, so the report's `compiles` block
        # describes this boot instead of racing it.
        while _t.monotonic() < deadline:
            if self.coord.health()["ready"]:
                break
            _t.sleep(0.05)
        from ..utils.compile_ledger import LEDGER

        settle_until = min(deadline, _t.monotonic() + 5.0)
        prev = LEDGER.summary()
        while _t.monotonic() < settle_until:
            _t.sleep(0.1)
            cur = LEDGER.summary()
            if cur == prev:
                break
            prev = cur
        return self.recovery_report()

    def shutdown(self) -> dict:
        """Stop listeners, coordinator, and replicas. Replica exits
        escalate terminate -> kill when the graceful budget
        (retry_policy_shutdown) expires — a wedged replica must never
        hang shutdown forever — and the exit report says exactly what
        happened to each process (ISSUE 10 satellite)."""
        report: dict = {"replicas": [], "escalations": 0}
        if self._down:
            return report
        self._down = True
        # The flight recorder (doc/observability.md): where
        # MZ_TRACE_DUMP_DIR names a directory, the span rings (this
        # process's and what the replicas shipped) are written there
        # before anything is stopped. Unset: nothing.
        dump_dir = os.environ.get("MZ_TRACE_DUMP_DIR")
        if dump_dir and os.path.isdir(dump_dir):
            from ..utils.trace import TRACER

            TRACER.dump(os.path.join(dump_dir, "spans.jsonl"))
        self.autoscaler.stop()
        # In-process thread replicas stop via their worker handle (the
        # subprocess ones get the terminate -> kill loop below).
        for rec in self.replica_records.values():
            w = rec.get("worker")
            if w is not None:
                w.stop()
        # Un-configure the process-global bank: the deployment owns
        # its bank directory; a later Environment (or a bankless
        # caller in the same process, e.g. the test suite) must not
        # keep writing into this one.
        from ..compile.bank import configure_bank

        configure_bank(None)
        # Stop the process-global background compactor for the same
        # reason: its queue holds Machines rooted in THIS deployment's
        # blob/consensus; a later Environment starts a fresh one.
        from ..storage.persist import reset_compaction_service

        reset_compaction_service()
        self.pg.stop()
        self.http.stop()
        self.coord.shutdown()
        from ..utils.retry import policy as _retry_policy

        budget = _retry_policy("shutdown").budget or 5.0
        for p in self.procs:
            p.terminate()
        deadline = _time.monotonic() + budget
        for p in self.procs:
            entry = {"pid": p.pid, "escalated": False}
            try:
                entry["returncode"] = p.wait(
                    timeout=max(deadline - _time.monotonic(), 0.1)
                )
            except subprocess.TimeoutExpired:
                # Escalate: SIGKILL, then a short bounded reap. A
                # process that survives SIGKILL (unkillable D-state)
                # is reported, not waited on forever.
                entry["escalated"] = True
                report["escalations"] += 1
                p.kill()
                try:
                    entry["returncode"] = p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    entry["returncode"] = None
            report["replicas"].append(entry)
        return report


def main() -> None:
    # environmentd is a HOST process: the coordinator builds batches,
    # runs introspection dataflows and stamps bank entries through
    # JAX, and all of that belongs on the CPU. Pin this process before
    # any backend exists, whatever JAX_PLATFORMS says — the platform
    # named there is the replicas' (os.environ is left untouched and
    # spawn_replica hands it on). A chip belongs to one process.
    import jax

    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser(description="materialize_tpu environmentd")
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--pg-port", type=int, default=6875)
    ap.add_argument("--http-port", type=int, default=6876)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument(
        "--workers", type=int, default=1,
        help="devices per replica (SPMD mesh size)",
    )
    ap.add_argument(
        "--tick-interval", type=float, default=0.05,
        help="load-generator tick seconds",
    )
    ap.add_argument(
        "--recover", action="store_true",
        help="restart-recovery boot: replay the durable catalog, "
        "re-render every dataflow, wait for replicas to re-hydrate "
        "from persist, and print the recovery report before serving",
    )
    args = ap.parse_args()
    env = Environment(
        args.data_dir,
        pg_port=args.pg_port,
        http_port=args.http_port,
        n_replicas=args.replicas,
        workers=args.workers,
        tick_interval=args.tick_interval,
    )
    atexit.register(env.shutdown)
    # SIGTERM is a graceful stop: run the atexit shutdown (listeners,
    # coordinator, replicas reaped) instead of orphaning the replicas.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    try:
        devices = env.await_replicas()
    except RuntimeError as e:
        print(f"environmentd: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
    if args.recover:
        import json as _json

        report = env.await_recovery()
        print("recovery: " + _json.dumps(report, sort_keys=True),
              flush=True)
    print(
        f"materialize_tpu listening: pgwire=127.0.0.1:{env.pg.port} "
        f"http=127.0.0.1:{env.http.port} data={args.data_dir} "
        + "replicas="
        + ",".join(
            f"{rid}:{d['platform']}x{d['count']}"
            for rid, d in devices.items()
        ),
        flush=True,
    )
    try:
        signal.pause()
    except (KeyboardInterrupt, AttributeError):
        while True:
            _time.sleep(3600)


if __name__ == "__main__":
    main()
