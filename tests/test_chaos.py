"""Crash-consistency chaos lane (ISSUE 10): seeded fault injection
over the recovery spine, with EXACT oracles.

Run the lane with ``pytest -m chaos``; the full storms (subprocess
replica SIGKILLs, environmentd kill -9 + --recover) are additionally
marked ``slow`` so the tier-1 window only pays for the bounded
in-process storms. Every test asserts the three recovery invariants:

1. exact final results vs a host-side oracle (zero lost acknowledged
   writes AND zero double-applied deltas — only possible if neither
   happened);
2. rebuilds == 0 for fingerprint-unchanged dataflows (reconciliation
   as a counted invariant, via mz_recovery);
3. the durable state a future process would resume from matches too.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time as _time
import urllib.request

import pytest

from materialize_tpu.coord.coordinator import Coordinator
from materialize_tpu.coord.peek import PeekTimedOut, ServerBusy
from materialize_tpu.coord.protocol import PersistLocation
from materialize_tpu.coord.replica import serve_forever
from materialize_tpu.storage.persist import (
    FileBlob,
    PersistClient,
    SqliteConsensus,
)
from materialize_tpu.testing.chaos import (
    _free_port,
    run_chaos,
    subprocess_available,
)
from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS


def _start_replica(tmp_path, rid="r0"):
    port = _free_port()
    loc = PersistLocation(
        str(tmp_path / "blob"), str(tmp_path / "consensus.db")
    )
    ready = threading.Event()
    threading.Thread(
        target=serve_forever, args=(port, loc, rid, ready), daemon=True
    ).start()
    assert ready.wait(10)
    return port, loc


def _mk_coord(tmp_path) -> Coordinator:
    return Coordinator(
        PersistClient(
            FileBlob(str(tmp_path / "blob")),
            SqliteConsensus(str(tmp_path / "consensus.db")),
        ),
        tick_interval=None,
    )


@pytest.mark.chaos
class TestRetryPolicy:
    """The unified retry/timeout/backoff module (utils/retry.py):
    spec parsing, budget/attempt exhaustion, deterministic jitter,
    and the dyncfg surface resolution."""

    def test_parse_spec(self):
        from materialize_tpu.utils.retry import RetryPolicy

        p = RetryPolicy.parse(
            "base=10ms,max=1s,mult=3,jitter=0.5,attempts=4,budget=2s"
        )
        assert p.base == 0.01 and p.max == 1.0 and p.mult == 3.0
        assert p.jitter == 0.5 and p.attempts == 4 and p.budget == 2.0

    def test_attempts_exhaust_and_reraise(self):
        from materialize_tpu.utils.retry import RetryPolicy

        calls = []

        def f():
            calls.append(1)
            raise ValueError("nope")

        pol = RetryPolicy(base=0.0, max=0.0, attempts=3, jitter=0.0)
        with pytest.raises(ValueError):
            pol.retry(f, retryable=(ValueError,))
        assert len(calls) == 3

    def test_budget_deadline(self):
        from materialize_tpu.utils.retry import RetryPolicy

        pol = RetryPolicy(base=0.001, max=0.001, budget=0.05,
                          jitter=0.0)
        stream = pol.stream()
        t0 = _time.monotonic()
        while stream.sleep():
            pass
        assert _time.monotonic() - t0 < 1.0  # budget bounds the loop

    def test_seeded_jitter_deterministic(self):
        from materialize_tpu.utils.retry import RetryPolicy

        pol = RetryPolicy(base=0.05, max=2.0, jitter=0.3)
        a = pol.stream(seed=42)
        b = pol.stream(seed=42)
        for _ in range(6):
            assert a.next_sleep() == b.next_sleep()
            a.advance()
            b.advance()

    def test_surface_resolution_via_dyncfg(self):
        from materialize_tpu.utils.retry import policy

        try:
            COMPUTE_CONFIGS.update(
                {"retry_policy_reconnect": "base=1ms,max=2ms,mult=1"}
            )
            p = policy("reconnect")
            assert p.base == 0.001 and p.max == 0.002
        finally:
            COMPUTE_CONFIGS.update({"retry_policy_reconnect": None})
        assert policy("reconnect").base == 0.05  # default restored

    def test_parse_rejects_unknown_keys(self):
        from materialize_tpu.utils.retry import RetryPolicy

        with pytest.raises(ValueError):
            RetryPolicy.parse("base=10ms,atempts=3")  # typo'd key
        with pytest.raises(ValueError):
            RetryPolicy.parse("base=fast")  # unparseable duration

    def test_malformed_spec_falls_back_to_default(self):
        # A bad spec that somehow reached dyncfg (e.g. a durable
        # catalog written before SET-time validation) must degrade to
        # the surface default, never raise inside a reconnect daemon
        # thread.
        from materialize_tpu.utils.retry import policy

        try:
            COMPUTE_CONFIGS.update(
                {"retry_policy_reconnect": "base=fast"}
            )
            assert policy("reconnect").base == 0.05  # default
        finally:
            COMPUTE_CONFIGS.update({"retry_policy_reconnect": None})

    def test_unbounded_sleep_never_zero_after_budget(self):
        # The reconnect loop retries forever: once a configured budget
        # expires, next_sleep() clamps to 0.0 (correct for give-up
        # surfaces) but next_sleep_unbounded() must keep returning the
        # jittered backoff, or the loop busy-spins at full CPU.
        from materialize_tpu.utils.retry import RetryPolicy

        pol = RetryPolicy(base=0.05, max=0.2, budget=0.001, jitter=0.0)
        stream = pol.stream()
        _time.sleep(0.002)  # budget expired
        stream.advance()
        assert stream.next_sleep() == 0.0
        assert stream.next_sleep_unbounded() >= 0.05

    def test_set_rejects_malformed_spec_and_persists_nothing(
        self, tmp_path
    ):
        # SET-time validation: a malformed retry spec must fail the
        # statement and leave NOTHING in the durable catalog — a
        # persisted bad spec would degrade every future boot.
        coord = _mk_coord(tmp_path)
        try:
            with pytest.raises(Exception) as exc:
                coord.execute(
                    "SET retry_policy_reconnect = 'base=fast'"
                )
            assert "invalid value" in str(exc.value)
            assert not any(
                rec.get("set") == "retry_policy_reconnect"
                for rec in coord._catalog_live_records()
            )
        finally:
            coord.shutdown()

    def test_crash_between_set_writes_keeps_newest(self, tmp_path):
        # The SET path appends the NEW override record BEFORE
        # retracting the prior one, so a crash between the two durable
        # writes leaves two live records (never zero). Boot replays in
        # id order — newest wins — and self-heals by retracting the
        # orphaned older record.
        coord = _mk_coord(tmp_path)
        coord.execute("SET retry_policy_peek = 'budget=100s'")
        # Simulate the crash window: the second SET's append landed,
        # the retraction of the first record did not.
        coord._record_ddl(
            "SET retry_policy_peek = 'budget=110s'",
            {"set": "retry_policy_peek"},
        )
        coord.shutdown()
        try:
            coord2 = _mk_coord(tmp_path)
            try:
                assert coord2.execute(
                    "SHOW retry_policy_peek"
                ).rows == [("budget=110s",)]
                recs = [
                    rec for rec in coord2._catalog_live_records()
                    if rec.get("set") == "retry_policy_peek"
                ]
                assert len(recs) == 1  # orphan retracted at boot
                assert "budget=110s" in recs[0]["sql"]
            finally:
                coord2.shutdown()
        finally:
            COMPUTE_CONFIGS.update({"retry_policy_peek": None})

    def test_repeated_set_retracts_prior_record(self, tmp_path):
        # Later SETs retract the earlier override record (tracked
        # O(1) in _dyncfg_records), so boot replays exactly the
        # newest value per var.
        coord = _mk_coord(tmp_path)
        try:
            coord.execute("SET retry_policy_peek = 'budget=100s'")
            coord.execute("SET retry_policy_peek = 'budget=110s'")
            coord.execute("SET retry_policy_peek = 'budget=120s'")
            recs = [
                rec for rec in coord._catalog_live_records()
                if rec.get("set") == "retry_policy_peek"
            ]
            assert len(recs) == 1
            assert "budget=120s" in recs[0]["sql"]
        finally:
            coord.shutdown()
            COMPUTE_CONFIGS.update({"retry_policy_peek": None})


@pytest.mark.chaos
class TestChaosStorm:
    """Bounded in-process storms: UnreliableBlob + CTP connection
    kills + a partition, against the exact oracle."""

    def test_storm_blob_faults_and_conn_kills(self, tmp_path):
        rep = run_chaos(
            str(tmp_path / "storm"), seed=3, ticks=30,
            blob_fail_every=11,
        )
        assert rep.ok, rep.failures
        # The seeded plan injected real faults and the link recovered.
        assert rep.conn_kills >= 1 and rep.partitions >= 1
        assert rep.recovery["replicas"]["r0"]["reconnects"] >= 1
        # Counted reconciliation: the description never changed.
        v = rep.recovery["dataflows"]["mv_sums"]["r0"]
        assert v["rebuilds"] == 0
        assert v["reconciles"] >= 1

    def test_storm_frame_kills_different_seed(self, tmp_path):
        # Frame-level resets (mid-frame connection death exercises the
        # CRC / torn-frame path) on another seed.
        rep = run_chaos(
            str(tmp_path / "storm2"), seed=11, ticks=30,
            blob_fail_every=7, proxy_kill_every=20,
        )
        assert rep.ok, rep.failures
        assert rep.retractions > 0 and rep.late > 0  # real storm


@pytest.mark.chaos
class TestRestartRecovery:
    """Kill the control plane, keep the replica: a new coordinator
    over the same durable catalog must come back with every object,
    identical results, replayed dyncfg overrides, and ZERO rebuilds on
    the surviving replica."""

    def test_coordinator_restart_surviving_replica(self, tmp_path):
        port, _loc = _start_replica(tmp_path)
        coord = _mk_coord(tmp_path)
        coord.add_replica("r0", ("127.0.0.1", port))
        coord2 = None
        try:
            coord.execute(
                "CREATE TABLE kv (k bigint NOT NULL, v bigint NOT NULL)"
            )
            coord.execute(
                "INSERT INTO kv VALUES (1, 10), (2, 20), (1, 5)"
            )
            coord.execute(
                "CREATE MATERIALIZED VIEW sums AS "
                "SELECT k, sum(v) AS s FROM kv GROUP BY k"
            )
            # A durable dyncfg override: must replay on --recover boot.
            coord.execute("SET span_max_ticks = 4")
            # Retraction + late re-insert churn before the "crash".
            coord.execute("DELETE FROM kv WHERE k = 2")
            coord.execute("INSERT INTO kv VALUES (2, 7)")
            expect = coord.execute(
                "SELECT k, s FROM sums ORDER BY k"
            ).rows
            assert expect  # nontrivial oracle
            # "Crash" the control plane; the replica thread SURVIVES
            # with its arrangements intact.
            coord.shutdown()
            COMPUTE_CONFIGS.update({"span_max_ticks": None})
            coord2 = _mk_coord(tmp_path)
            # Catalog replay: every object returns, overrides replay.
            assert coord2.recovery["catalog_replayed"] >= 3
            assert coord2.recovery["dyncfg_replayed"] >= 1
            assert coord2.recovery["replay_failures"] == 0
            assert float(COMPUTE_CONFIGS.get("span_max_ticks")) == 4
            names = {it.name for it in coord2.catalog.items.values()}
            assert {"kv", "sums"} <= names
            coord2.add_replica("r0", ("127.0.0.1", port))
            got = coord2.execute(
                "SELECT k, s FROM sums ORDER BY k"
            ).rows
            assert got == expect
            # Counted reconciliation (the acceptance invariant): the
            # surviving replica KEPT the fingerprint-unchanged
            # dataflow — rebuilds == 0, reconciles incremented.
            deadline = _time.monotonic() + 30
            while True:
                snap = coord2.controller.recovery_snapshot()
                per = snap["dataflows"].get("sums", {}).get("r0")
                if per is not None and per["reconciles"] >= 1:
                    break
                assert _time.monotonic() < deadline, snap
                _time.sleep(0.01)
            assert per["rebuilds"] == 0, per
            # The restarted controller re-fenced the surviving replica
            # via nonce fast-forward (one reject, then straight in).
            assert snap["replicas"]["r0"]["fenced"] >= 1
            # And the relational surface serves the same invariant.
            res = coord2.execute(
                "SELECT object, value FROM mz_recovery "
                "WHERE scope = 'dataflow' AND metric = 'rebuilds'"
            )
            assert ("sums", 0.0) in res.rows
            # EXPLAIN ANALYSIS carries the recovery block.
            txt = coord2.execute(
                "EXPLAIN ANALYSIS FOR SELECT k FROM kv"
            ).text
            assert "recovery:" in txt and "catalog_replayed=" in txt
        finally:
            COMPUTE_CONFIGS.update({"span_max_ticks": None})
            if coord2 is not None:
                coord2.shutdown()
            else:
                coord.shutdown()


@pytest.mark.chaos
class TestPeekShed:
    """Peek-budget exhaustion is a RETRYABLE shed (ServerBusy: 53400
    at pgwire, 503 at HTTP), and a timed-out wait never leaves the
    sequencing lock poisoned."""

    def test_peek_timeout_retryable_and_lock_clean(self, tmp_path):
        coord = _mk_coord(tmp_path)  # deliberately NO replicas
        try:
            coord.execute(
                "CREATE TABLE t (a bigint NOT NULL)"
            )
            coord.execute("INSERT INTO t VALUES (1)")
            coord.execute(
                "CREATE MATERIALIZED VIEW m AS SELECT a FROM t"
            )
            coord.execute("SET retry_policy_peek = 'budget=300ms'")
            with pytest.raises(ServerBusy) as exc:
                coord.execute("SELECT a FROM m")
            assert "retry" in str(exc.value)
            # The front ends map it to the clean shed, not XX000.
            from materialize_tpu.server.pgwire import _error_code

            assert _error_code(exc.value) == "53400"
            # Sequencing lock not poisoned: later statements execute.
            assert coord.execute("SHOW retry_policy_peek").rows
            coord.execute("INSERT INTO t VALUES (2)")
            res = coord.execute(
                "SELECT name FROM mz_cluster_replicas"
            )
            assert res.rows == []
        finally:
            COMPUTE_CONFIGS.update({"retry_policy_peek": None})
            coord.shutdown()

    def test_batched_lookup_timeout_is_retryable(self):
        from materialize_tpu.coord.controller import ComputeController

        ctl = ComputeController()
        try:
            with pytest.raises(PeekTimedOut):
                ctl.peek_lookup(
                    "nope", (0,), False, (1,), 0, timeout=0.2
                )
            with pytest.raises(PeekTimedOut):
                ctl.peek("nope", as_of=0, timeout=0.2)
        finally:
            ctl.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
class TestReplicaKillStorm:
    """SIGKILL a subprocess replica mid-span (paced: the kill waits
    until the replica has caught up to the storm), respawn, and prove
    no acked write is lost and no delta double-applies."""

    def test_sigkill_midspan_storm(self, tmp_path):
        if not subprocess_available():
            pytest.skip("subprocess spawning unavailable")
        rep = run_chaos(
            str(tmp_path / "storm"), seed=7, ticks=30,
            blob_fail_every=9, proxy_kill_every=25,
            subprocess_replica=True, replica_kills=1,
            verify_timeout=480.0,
        )
        assert rep.ok, rep.failures
        assert rep.replica_kills == 1
        # The respawned replica re-hydrated from persist: a fresh
        # install, never a rebuild (rebuild = changed description).
        v = rep.recovery["dataflows"]["mv_sums"]["r0"]
        assert v["rebuilds"] == 0


@pytest.mark.chaos
class TestFailoverStorm:
    """Elastic-serving chaos (ISSUE 19): reads are ROUTED to one
    replica; killing that replica while a peek is parked in flight
    against it must resolve the peek through failover with exact
    rows, zero client-visible errors (≤1 retried statement), and a
    surviving routing target."""

    @pytest.mark.slow
    def test_smoke_two_replicas_in_process(self, tmp_path):
        # scripts/check_plans.py --bench (`failover-smoke`) runs this
        # same storm when someone runs that script; tier-1 runs
        # neither: this copy is in the slow/chaos lane.
        from materialize_tpu.testing.chaos import run_failover_smoke

        rep = run_failover_smoke(str(tmp_path / "fo"), seed=1)
        assert rep.ok, rep.failures
        assert rep.kills == 1
        assert rep.routed_before in rep.killed
        assert rep.routed_after not in rep.killed
        # The disconnect re-dispatched the in-flight peek — counted,
        # not inferred.
        assert rep.failovers >= 1
        assert rep.retried_statements <= 1
        assert rep.reader_queries >= 1

    @pytest.mark.slow
    def test_sigkill_routed_replica_mid_peek_n3(self, tmp_path):
        if not subprocess_available():
            pytest.skip("subprocess spawning unavailable")
        from materialize_tpu.testing.chaos import run_failover_storm

        rep = run_failover_storm(
            str(tmp_path / "fo3"), seed=7, ticks=16, replicas=3,
            subprocess_replicas=True, verify_timeout=480.0,
        )
        assert rep.ok, rep.failures
        assert rep.replicas == 3 and rep.kills == 1
        assert rep.routed_before in rep.killed
        assert rep.routed_after not in rep.killed
        assert rep.failovers >= 1
        assert rep.retried_statements <= 1
        # Push-plane attribution followed the failover: the SUBSCRIBE
        # tail's routed replica changed when the target died.
        assert rep.route_changes >= 1


def _http_sql(port: int, sql: str):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/sql",
        data=json.dumps({"query": sql}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=190) as r:
        out = json.loads(r.read())
    for res in out.get("results", []):
        if isinstance(res, dict) and res.get("error"):
            raise RuntimeError(res["error"])
    return out["results"][-1].get("rows", [])


def _read_until(proc, needle: str, timeout: float = 300.0) -> str:
    deadline = _time.monotonic() + timeout
    lines = []
    while _time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            rc = proc.poll()
            if rc is not None:
                raise AssertionError(
                    f"environmentd exited rc={rc} before {needle!r}: "
                    + "".join(lines[-20:])
                )
            _time.sleep(0.05)
            continue
        lines.append(line)
        if needle in line:
            return line
    raise AssertionError(
        f"timed out waiting for {needle!r}: " + "".join(lines[-20:])
    )


@pytest.mark.chaos
@pytest.mark.slow
class TestEnvironmentdCrash:
    """The acceptance scenario: kill -9 environmentd MID-INGEST,
    restart with --recover, and assert exactly — all catalog objects
    return, the maintained view matches the no-crash oracle over the
    acked writes, and zero acknowledged writes are lost."""

    def _spawn(self, data_dir: str, pg: int, hp: int, extra=()):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        return subprocess.Popen(
            [
                sys.executable, "-m",
                "materialize_tpu.server.environmentd",
                "--data-dir", data_dir,
                "--pg-port", str(pg), "--http-port", str(hp),
                "--replicas", "1", "--tick-interval", "0.5",
                *extra,
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )

    def test_kill9_mid_ingest_then_recover(self, tmp_path):
        if not subprocess_available():
            pytest.skip("subprocess spawning unavailable")
        data = str(tmp_path / "envd")
        pg1, hp1 = _free_port(), _free_port()
        p = self._spawn(data, pg1, hp1)
        p2 = None
        try:
            _read_until(p, "listening")
            _http_sql(
                hp1,
                "CREATE TABLE kv "
                "(k bigint NOT NULL, v bigint NOT NULL)",
            )
            _http_sql(
                hp1,
                "CREATE MATERIALIZED VIEW sums AS "
                "SELECT k, sum(v) AS s FROM kv GROUP BY k",
            )
            # Mid-ingest: a writer thread streams acked inserts (v is
            # unique per statement so ack bookkeeping is exact); the
            # kill lands while it runs, so at most ONE statement is
            # in flight unacked.
            acked: list = []
            inflight = [None]
            stop = threading.Event()

            def writer():
                i = 0
                while not stop.is_set():
                    i += 1
                    inflight[0] = i
                    try:
                        _http_sql(
                            hp1,
                            f"INSERT INTO kv VALUES ({i % 4}, {i})",
                        )
                    except Exception:
                        return
                    acked.append(i)
                    inflight[0] = None

            t = threading.Thread(target=writer, daemon=True)
            t.start()
            deadline = _time.monotonic() + 120
            while len(acked) < 10:
                assert _time.monotonic() < deadline, acked
                _time.sleep(0.05)
            os.kill(p.pid, signal.SIGKILL)
            p.wait()
            stop.set()
            t.join(30)
            maybe_inflight = inflight[0]
            acked_set = set(acked)
            assert len(acked_set) == len(acked)
            # Restart with --recover on the same data dir.
            pg2, hp2 = _free_port(), _free_port()
            p2 = self._spawn(data, pg2, hp2, extra=("--recover",))
            line = _read_until(p2, "recovery: ")
            report = json.loads(line.split("recovery: ", 1)[1])
            assert report["coordinator"]["catalog_replayed"] >= 2
            assert report["coordinator"]["replay_failures"] == 0
            _read_until(p2, "listening")
            # All catalog objects returned.
            objs = {r[0] for r in _http_sql(hp2, "SHOW OBJECTS")}
            assert {"kv", "sums"} <= objs
            # ZERO acked writes lost — asserted exactly: the table
            # holds every acked v, plus at most the one in-flight
            # statement the kill interrupted.
            rows = _http_sql(hp2, "SELECT k, v FROM kv")
            got = {int(r[1]) for r in rows}
            assert acked_set <= got, sorted(acked_set - got)
            extra = got - acked_set
            assert extra <= {maybe_inflight}, (extra, maybe_inflight)
            # The maintained view serves results identical to the
            # no-crash oracle over the recovered table contents.
            expect_sums: dict = {}
            for r in rows:
                k, v = int(r[0]), int(r[1])
                expect_sums[k] = expect_sums.get(k, 0) + v
            got_sums = {
                int(r[0]): int(r[1])
                for r in _http_sql(hp2, "SELECT k, s FROM sums")
            }
            assert got_sums == expect_sums
            # Writes keep flowing after recovery.
            _http_sql(hp2, "INSERT INTO kv VALUES (9, 999999)")
            rows2 = _http_sql(
                hp2, "SELECT s FROM sums WHERE k = 9"
            )
            assert any(int(r[0]) >= 999999 for r in rows2)
        finally:
            for proc in (p, p2):
                if proc is None:
                    continue
                try:
                    proc.kill()
                    proc.wait(timeout=30)
                except Exception:
                    pass


@pytest.mark.chaos
class TestCompactorStorm:
    """Leased background compaction under fire (ISSUE 20): the tick
    path only *requests* compaction; compactor A is crashed after its
    merge blob-write (lease held, orphan part — a SIGKILL's durable
    residue), compactor B takes over after lease expiry, a stale-epoch
    swap is fenced, and a reader pinned to a pre-swap batch list
    retries through CompactionRace. Every invariant is a counter."""

    def test_compactor_smoke(self, tmp_path):
        from materialize_tpu.testing.chaos import run_compactor_smoke

        rep = run_compactor_smoke(str(tmp_path / "cs"), seed=1)
        assert rep.ok, rep.failures
        # The SIGKILL residue: exactly one injected crash, and the
        # crashed compactor's lease was still held when we looked.
        assert rep.crashes == 1
        assert rep.crash_residue_holder == "chaos-compactor-a"
        # Expiry + handoff: B landed a merge with a bumped epoch.
        assert rep.handoffs == 1
        assert rep.handoff_epoch >= 2
        # The swap-in fence rejected a stale lease epoch.
        assert rep.fenced_swaps == 1
        # A reader racing the just-swapped parts observed the race and
        # the retrying snapshot healed to the exact oracle (rep.ok).
        assert rep.reader_races >= 1
        assert rep.reader_reads >= 1
        # Zero tick-path compaction work, by counter.
        assert rep.merges_inline == 0
        assert rep.blob_writes_inline == 0
        assert rep.merges_background >= 1
        assert rep.requests >= 1

    @pytest.mark.slow
    def test_compactor_storm_long(self, tmp_path):
        from materialize_tpu.testing.chaos import run_compactor_storm

        rep = run_compactor_storm(
            str(tmp_path / "cst"), seed=7, ticks=48, blob_fail_every=7
        )
        assert rep.ok, rep.failures
        assert rep.crashes == 1 and rep.handoffs == 1
        assert rep.merges_inline == 0 and rep.blob_writes_inline == 0
        assert rep.final_batches >= 0
