#!/usr/bin/env python3
"""chip_smoke.py: the served path on one TPU, end to end, checked.

    python chip_smoke.py             # the gate: one chip
    python chip_smoke.py --chips 4   # one replica over four chips: Q1/Q15 only
    JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.001   # rehearsal, no chip

Drives the entry points a user calls and nothing else: starts
``python -m materialize_tpu.server.environmentd`` (a host process, on
the CPU by its own choice) with ONE replica that owns the accelerator,
loads TPC-H through ``CREATE SOURCE ... LOAD GENERATOR tpch``, installs
an index on ``lineitem`` and materialized views for Q1 and Q15, lets the
generator churn, reads the views ``AS OF`` a closed timestamp plus a few
hundred fast-path point lookups, and compares every answer EXACTLY with
a numpy recomputation from the durable shards (read with a PersistClient
of this process's own). This process is client and oracle and pins its
own JAX to the CPU: exactly one process, the replica, holds the chip.

Every line printed before the last is a JSON object of set-up facts and
WALL seconds of one cold run (compiles included) — none is a rate. The
last line is the contract's: ``{"ok": true, "device": {...}}`` with the
device AS THE REPLICA REPORTED IT, printed only when every phase passed
and that device is a TPU. Any failure exits non-zero.

Without ``--sf`` the replica is started with ``JAX_PLATFORMS=tpu``:
where no chip can be had it fails at start with JAX's own error and so
does this script. ``--sf`` marks a rehearsal: the replica inherits
``JAX_PLATFORMS`` unchanged, so under ``JAX_PLATFORMS=cpu`` every phase
runs at the given scale factor on the CPU, the last line reports the
platform found with ``"ok": false``, and the exit code is 1.
"""

from __future__ import annotations

import argparse
import decimal
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# The scale factor of the gate. The `index` configuration's size (SF
# 0.25, 1,498,288 lineitem rows) is what the issue asked for; it does
# not fit a COLD run's 1200 s: on the served path every capacity tier
# is grown by doubling from 256 rows and each doubling recompiles the
# hydration step, whose compile for the v5e takes ~20 s at a 2^13-row
# input but ~300-450 s at 2^15 rows and beyond (described-topology
# compiles, PR 24; see CHANGES.md). SF 0.001 (6,005 lineitem rows, tier
# 2^13) is the largest whose whole ladder fits.
DEFAULT_SF = 0.001
MIN_CHURN_TICKS = 64
N_LOOKUPS = 256
# environmentd's load-generator tick. Reads are served at the sources'
# newest complete time, so a replica that steps slower than the
# generator ticks never answers (at the server's default 0.05 s the CPU
# rehearsal's replica fell behind without bound); the smoke offers a
# rate one replica is expected to hold and prints the lag it saw.
TICK_INTERVAL_S = 0.5
# Seconds any one wait (a DDL, hydration, churn) may take.
WAIT_BUDGET_S = 900.0


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- the server ---------------------------------------------------------------


class Server:
    """environmentd as a child process, spoken to over HTTP."""

    def __init__(self, out_dir: str, workers: int, replica_platform):
        self.data_dir = os.path.join(out_dir, "data")
        self.log_path = os.path.join(out_dir, "environmentd.log")
        self.http_port = free_port()
        env = dict(os.environ)
        if replica_platform is not None:
            # environmentd pins ITSELF to the CPU and hands this
            # environment to its replicas unchanged.
            env["JAX_PLATFORMS"] = replica_platform
        # A clean device view for the replica: a forced host device
        # count only makes sense for a multi-worker CPU rehearsal.
        env.pop("XLA_FLAGS", None)
        if workers > 1 and env.get("JAX_PLATFORMS", "") == "cpu":
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={workers}"
            )
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m",
                "materialize_tpu.server.environmentd",
                "--data-dir", self.data_dir,
                "--pg-port", str(free_port()),
                "--http-port", str(self.http_port),
                "--replicas", "1", "--workers", str(workers),
                "--tick-interval", str(TICK_INTERVAL_S),
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
            raise SmokeFailure(
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
        raise SmokeFailure(
            "environmentd did not listen in time:\n" + self.log_tail()
        )

    def sql(self, query: str, timeout: float = 600.0) -> list:
        """POST /api/sql; returns the ``results`` list (one entry per
        statement). A server-side error is a failure."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http_port}/api/sql",
            data=json.dumps({"query": query}).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                body = json.loads(r.read())
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{query[:120]!r} -> HTTP {e.code}: "
                f"{e.read()[:2000].decode(errors='replace')}"
            )
        for res in body["results"]:
            if "error" in res:
                raise SmokeFailure(f"{query[:120]!r} -> {res['error']}")
        return body["results"]

    def rows(self, query: str, timeout: float = 600.0) -> list:
        return self.sql(query, timeout)[-1]["rows"]

    def readyz(self) -> tuple[int, dict]:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.http_port}/api/readyz", timeout=30
            ) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    def replica_pids(self) -> list[int]:
        out = subprocess.run(
            ["pgrep", "-P", str(self.proc.pid)],
            capture_output=True, text=True,
        ).stdout.split()
        return [int(p) for p in out]

    def shutdown(self) -> dict:
        """SIGTERM environmentd (its graceful stop reaps the replica);
        report whether parent and replica are gone."""
        pids = self.replica_pids()
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
        return {"environmentd_rc": rc, "replicas_left": left}


# -- the SQL under test -------------------------------------------------------


def install_sql(sf: float, seed: int, with_index: bool) -> list[str]:
    from materialize_tpu.workloads.tpch import Q1_CUTOFF, Q15_HI, Q15_LO

    stmts = [
        "CREATE SOURCE t FROM LOAD GENERATOR tpch "
        f"(SCALE FACTOR {sf}, SEED {seed})",
    ]
    if with_index:
        stmts.append("CREATE INDEX lineitem_idx ON lineitem (l_orderkey)")
    stmts += [
        # TPC-H Q1 (dates are day numbers in this system; the cutoff is
        # date '1998-12-01' - 90 days).
        "CREATE MATERIALIZED VIEW q1 AS SELECT l_returnflag, "
        "l_linestatus, sum(l_quantity) AS sum_qty, "
        "sum(l_extendedprice) AS sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) "
        "AS sum_charge, count(*) AS count_order "
        f"FROM lineitem WHERE l_shipdate <= {Q1_CUTOFF} "
        "GROUP BY l_returnflag, l_linestatus",
        # TPC-H Q15: the revenue view, its scalar max, a join to
        # supplier (window [1996-01-01, 1996-04-01)).
        "CREATE VIEW revenue AS SELECT l_suppkey AS supplier_no, "
        "sum(l_extendedprice * (1 - l_discount)) AS total_revenue "
        f"FROM lineitem WHERE l_shipdate >= {Q15_LO} "
        f"AND l_shipdate < {Q15_HI} GROUP BY l_suppkey",
        "CREATE MATERIALIZED VIEW q15 AS SELECT s_suppkey, s_name, "
        "total_revenue FROM supplier, revenue "
        "WHERE s_suppkey = supplier_no AND total_revenue = "
        "(SELECT max(total_revenue) FROM revenue)",
    ]
    return stmts


# -- the oracle ---------------------------------------------------------------


class Oracle:
    """numpy recomputation from the durable shards, read with a
    PersistClient of this process's own on the server's data dir."""

    def __init__(self, data_dir: str):
        from materialize_tpu.storage.generator.tpch import (
            LINEITEM_SCHEMA,
            SUPPLIER_SCHEMA,
        )
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )

        # The subsource shards, found in the durable store itself (the
        # blob root holds one directory per shard, "<id>_<subsource>";
        # no served relation carries a subsource's shard id).
        blob_root = os.path.join(data_dir, "blob")
        shards = {}
        for sub in ("lineitem", "supplier"):
            found = [
                d for d in os.listdir(blob_root) if d.endswith("_" + sub)
            ]
            if len(found) != 1:
                raise SmokeFailure(
                    f"expected one {sub} shard under {blob_root}: {found}"
                )
            shards[sub] = found[0]
        self.client = PersistClient(
            FileBlob(blob_root),
            SqliteConsensus(os.path.join(data_dir, "consensus.db")),
        )
        self.li = LINEITEM_SCHEMA
        self.su = SUPPLIER_SCHEMA
        # Registering the readers holds the shards' since: every time
        # from here on stays readable for the oracle.
        self.li_reader = self.client.open_reader(
            shards["lineitem"], "chip-smoke-oracle"
        )
        self.su_reader = self.client.open_reader(
            shards["supplier"], "chip-smoke-oracle"
        )

    def lineitem_upper(self) -> int:
        return self.li_reader.upper

    def close(self) -> None:
        self.li_reader.expire()
        self.su_reader.expire()

    def _decode(self, codes):
        from materialize_tpu.repr.schema import GLOBAL_DICT

        import numpy as np

        codes = np.asarray(codes)
        uniq, inv = np.unique(codes, return_inverse=True)
        strs = np.array(GLOBAL_DICT.decode_many(uniq), dtype=object)
        return strs[inv]

    def lineitem_at(self, t: int):
        _s, cols, _n, _t, diff = self.li_reader.snapshot(t)
        return cols, diff

    def q1(self, cols, diff) -> list:
        import numpy as np

        from materialize_tpu.workloads.tpch import Q1_CUTOFF

        i = self.li.index_of
        m = cols[i("l_shipdate")] <= Q1_CUTOFF
        rf = cols[i("l_returnflag")][m]
        ls = cols[i("l_linestatus")][m]
        # Python ints (object dtype) where a product could pass 2^63.
        d = diff[m].astype(np.int64)
        qty = cols[i("l_quantity")][m].astype(np.int64)
        price = cols[i("l_extendedprice")][m].astype(np.int64)
        disc = cols[i("l_discount")][m].astype(np.int64)
        tax = cols[i("l_tax")][m].astype(np.int64)
        disc_price = price * (100 - disc)  # scale 4
        charge = disc_price * (100 + tax)  # scale 6
        pairs = np.stack([rf, ls], axis=1)
        uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        out = []
        for g in range(len(uniq)):
            sel = inv == g
            dg = d[sel]
            cnt = int(dg.sum())
            if cnt == 0:
                continue
            sums = [
                _exact_dot(x[sel], dg)
                for x in (qty, price, disc_price, charge)
            ]
            flag, status = self._decode(uniq[g])
            out.append(
                (
                    flag, status,
                    decimal.Decimal(sums[0]).scaleb(-2),
                    decimal.Decimal(sums[1]).scaleb(-2),
                    decimal.Decimal(sums[2]).scaleb(-4),
                    decimal.Decimal(sums[3]).scaleb(-6),
                    cnt,
                )
            )
        return sorted(out)

    def q15(self, cols, diff, t: int) -> list:
        import numpy as np

        from materialize_tpu.workloads.tpch import Q15_HI, Q15_LO

        i = self.li.index_of
        sd = cols[i("l_shipdate")]
        m = (sd >= Q15_LO) & (sd < Q15_HI)
        supp = cols[i("l_suppkey")][m].astype(np.int64)
        d = diff[m].astype(np.int64)
        rev = (
            cols[i("l_extendedprice")][m].astype(np.int64)
            * (100 - cols[i("l_discount")][m].astype(np.int64))
            * d
        )
        n_rows = np.zeros(int(supp.max()) + 1 if len(supp) else 1, np.int64)
        totals = np.zeros_like(n_rows)
        np.add.at(n_rows, supp, d)
        np.add.at(totals, supp, rev)
        live = np.nonzero(n_rows > 0)[0]
        if not len(live):
            return []
        best = int(totals[live].max())
        winners = {int(k) for k in live if int(totals[k]) == best}
        _s, scols, _n, _t, sdiff = self.su_reader.snapshot(t)
        k = self.su.index_of
        names = self._decode(scols[k("s_name")])
        out = []
        for key, name, dd in zip(scols[k("s_suppkey")], names, sdiff):
            if int(key) in winners and int(dd) > 0:
                out.extend(
                    [(int(key), name, decimal.Decimal(best).scaleb(-4))]
                    * int(dd)
                )
        return sorted(out)


def _exact_dot(a, d) -> int:
    """sum(a * d) as an exact Python int: int64 where the bound shows
    it cannot wrap, Python ints otherwise."""
    import numpy as np

    if not len(a):
        return 0
    bound = int(np.abs(a).max()) * int(np.abs(d).max()) * len(a)
    if bound < 1 << 62:
        return int((a * d).sum())
    return int((a.astype(object) * d.astype(object)).sum())


def normalise(rows: list, decimal_cols: tuple) -> list:
    """Server rows (JSON: decimals arrive as their exact text) ->
    comparable tuples."""
    out = []
    for r in rows:
        out.append(
            tuple(
                decimal.Decimal(v) if i in decimal_cols else v
                for i, v in enumerate(r)
            )
        )
    return sorted(out)


# -- phases -------------------------------------------------------------------


def wait_hydrated(server: Server, names: list, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        server.check_alive()
        code, verdict = server.readyz()
        status = {
            r[0]: r[2]
            for r in server.rows(
                "SELECT dataflow, replica, status "
                "FROM mz_hydration_statuses"
            )
        }
        last = (code, verdict, status)
        if code == 200 and all(
            status.get(n) == "hydrated" for n in names
        ):
            return
        time.sleep(0.5)
    raise SmokeFailure(f"hydration timed out: {last!r}")


def frontiers(server: Server) -> dict:
    return dict(
        server.rows("SELECT dataflow, upper FROM mz_dataflow_frontiers")
    )


def wait_churn(oracle: Oracle, start_upper: int, timeout: float) -> int:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        up = oracle.lineitem_upper()
        if up - start_upper >= MIN_CHURN_TICKS:
            return up
        time.sleep(0.2)
    raise SmokeFailure(
        f"fewer than {MIN_CHURN_TICKS} churn ticks committed in "
        f"{timeout:.0f}s (upper {oracle.lineitem_upper()}, "
        f"started at {start_upper})"
    )


def check_views(server: Server, oracle: Oracle) -> dict:
    """Read Q1 and Q15 AS OF one closed timestamp of the lineitem shard
    and compare with the oracle at that timestamp. A view keeps a
    bounded multiversion window behind its frontier, and the generator
    keeps ticking, so a timestamp can leave the window between being
    chosen and being read: that read is refused by the server, and a
    fresh timestamp is chosen (a refusal is never compared)."""
    attempts = 0
    while True:
        attempts += 1
        # The newest time BOTH views have reached (a closed time of the
        # shard: a view's frontier never passes its input's).
        t = min(frontiers(server)[n] for n in ("q1", "q15")) - 1
        try:
            res = server.sql(
                f"SELECT * FROM q1 AS OF {t}; SELECT * FROM q15 AS OF {t}"
            )
            break
        except SmokeFailure as e:
            if "not valid for all inputs" not in str(e) or attempts >= 50:
                raise
    got_q1 = normalise(res[0]["rows"], (2, 3, 4, 5))
    got_q15 = normalise(res[1]["rows"], (2,))
    cols, diff = oracle.lineitem_at(t)
    want_q1 = oracle.q1(cols, diff)
    want_q15 = oracle.q15(cols, diff, t)
    if got_q1 != want_q1:
        raise SmokeFailure(
            f"Q1 AS OF {t} differs from the oracle:\n got  {got_q1}\n"
            f" want {want_q1}"
        )
    if not want_q1:
        raise SmokeFailure("Q1 oracle is empty: nothing was checked")
    if got_q15 != want_q15:
        raise SmokeFailure(
            f"Q15 AS OF {t} differs from the oracle:\n got  {got_q15}\n"
            f" want {want_q15}"
        )
    if not want_q15:
        raise SmokeFailure("Q15 oracle is empty: nothing was checked")
    return {
        "as_of": t,
        "as_of_attempts": attempts,
        "lineitem_updates_at_as_of": int(len(diff)),
        "lineitem_rows_at_as_of": int(diff.sum()),
        "q1_groups": len(want_q1),
        "q15_rows": len(want_q15),
        "q1_equal": True,
        "q15_equal": True,
    }


def check_lookups(
    server: Server, oracle: Oracle, t: int, seed: int
) -> dict:
    """Point lookups on the lineitem index through the fast path. The
    fast path reads at the server's freshest complete time, which moves
    while the generator churns, so each batch of lookups is bracketed
    by the shard's upper before and after: an answer must equal the
    oracle's rows for that key at SOME closed time inside the bracket
    (for a key no tick touched in the bracket, that is one answer)."""
    import numpy as np

    li = oracle.li
    ok = li.index_of("l_orderkey")
    cols, diff = oracle.lineitem_at(t)
    keys_live = np.unique(cols[ok])
    rng = np.random.default_rng(seed)
    probe = rng.choice(keys_live, size=min(N_LOOKUPS, len(keys_live)),
                       replace=False)
    # Half the probes aim at keys the churn touched most recently.
    _s, ucols, _n, utime, udiff = oracle.li_reader.fetch(
        max(t - 8, 1), t + 1
    )
    if len(udiff):
        recent = np.unique(ucols[ok])
        take = min(len(recent), len(probe) // 2)
        probe[:take] = rng.choice(recent, size=take, replace=False)
    probe = [int(k) for k in probe]
    # Sort base rows by key once; per-key slices come from searchsorted.
    order = np.argsort(cols[ok], kind="stable")
    skeys = cols[ok][order]

    dec_cols = tuple(
        i for i, c in enumerate(li.columns) if c.scale and c.scale > 0
    )

    def wire(v, i):
        """A value as it compares after the JSON wire: decimals exact,
        everything else through the server's own text form."""
        if i in dec_cols:
            return decimal.Decimal(v)
        return json.loads(json.dumps(v, default=str))

    def key_rows(updates) -> dict:
        """Multiset {row: count} of (cols, diff, row indices) slices,
        decoded by the repo's own result decoder."""
        from materialize_tpu.repr.schema import decode_result_rows

        acc: dict = {}
        for pcols, pdiff, idx in updates:
            idx = np.asarray(idx, dtype=np.int64)
            rows = decode_result_rows(
                li, [c[idx] for c in pcols], [None] * len(pcols),
                np.zeros(len(idx), np.uint64), pdiff[idx],
            )
            for r in rows:
                row = tuple(wire(v, i) for i, v in enumerate(r[:-2]))
                acc[row] = acc.get(row, 0) + r[-1]
        return {r: n for r, n in acc.items() if n != 0}

    checked = 0
    moved = 0
    batch = 32
    t_batches = []
    for b0 in range(0, len(probe), batch):
        ks = probe[b0:b0 + batch]
        lo = oracle.lineitem_upper() - 1
        t0 = time.monotonic()
        results = server.sql(
            ";".join(
                f"SELECT * FROM lineitem WHERE l_orderkey = {k}"
                for k in ks
            )
        )
        t_batches.append(time.monotonic() - t0)
        hi = oracle.lineitem_upper() - 1
        lo = max(lo, t)
        hi = max(hi, lo)
        _s, ucols, _n, utime, udiff = oracle.li_reader.fetch(t + 1, hi + 1)
        for k, res in zip(ks, results):
            got: dict = {}
            for r in res["rows"]:
                row = tuple(wire(v, i) for i, v in enumerate(r))
                got[row] = got.get(row, 0) + 1
            a = np.searchsorted(skeys, k, "left")
            z = np.searchsorted(skeys, k, "right")
            base_idx = order[a:z]
            uidx = np.nonzero(ucols[ok] == k)[0] if len(udiff) else []
            candidates = []
            for tau in range(lo, hi + 1):
                sel = [j for j in uidx if int(utime[j]) <= tau]
                candidates.append(
                    key_rows(
                        [(cols, diff, base_idx), (ucols, udiff, sel)]
                    )
                )
            if len({tuple(sorted(c.items())) for c in candidates}) > 1:
                moved += 1
            if got not in candidates:
                raise SmokeFailure(
                    f"lookup l_orderkey={k} matches the oracle at no "
                    f"time in [{lo}, {hi}]:\n got  {got}\n want one of "
                    f"{candidates}"
                )
            if not any(candidates):
                raise SmokeFailure(f"probe key {k} has no rows")
            checked += 1
    return {
        "lookups": checked,
        "lookups_equal": True,
        "lookups_whose_key_moved_in_bracket": moved,
        "wall_seconds_per_batch_of_32_statements": [
            round(x, 4) for x in t_batches
        ],
    }


def server_facts(server: Server) -> dict:
    """What the SERVER says about the process that computed the
    answers: device, native kernels, program bank, compile ledger,
    overflow regrows."""
    reps = server.rows(
        "SELECT name, connected, platform, device_kind, devices "
        "FROM mz_cluster_replicas"
    )
    metrics = dict(
        server.rows("SELECT metric, value FROM mz_metrics")
    )

    def replica_metric(name: str, default=None):
        for k, v in metrics.items():
            if k.startswith(name + "{") and "replica=" in k:
                return v
        return default

    compiles = server.rows(
        "SELECT process, dataflow, kind, seconds, cache "
        "FROM mz_compile_log"
    )
    by_process: dict = {}
    for process, dataflow, kind, seconds, cache in compiles:
        p = by_process.setdefault(
            process,
            {"records": 0, "seconds": 0.0, "by_cache": {},
             "by_program": {}},
        )
        p["records"] += 1
        p["seconds"] += float(seconds)
        p["by_cache"][cache] = p["by_cache"].get(cache, 0) + 1
        # [count, wall seconds] per (dataflow, program kind): where a
        # cold run's compile time went.
        if process != "r0" and dataflow == "df":
            continue  # the coordinator's per-query introspection plans
        e = p["by_program"].setdefault(f"{dataflow}/{kind}", [0, 0.0])
        e[0] += 1
        e[1] = round(e[1] + float(seconds), 3)
    for p in by_process.values():
        p["seconds"] = round(p["seconds"], 3)
    bank_entries = server.rows(
        "SELECT count(*) FROM mz_program_bank WHERE state = 'stored'"
    )[0][0]
    return {
        "replicas": [
            {
                "name": r[0], "connected": r[1], "platform": r[2],
                "device_kind": r[3], "devices": r[4],
            }
            for r in reps
        ],
        "replica_native_kernels": replica_metric("mz_native_kernels"),
        "environmentd_native_kernels": metrics.get("mz_native_kernels"),
        "replica_program_bank": {
            k: replica_metric(f"mz_program_bank_{k}_total", 0.0)
            for k in ("hits", "misses", "stores", "errors")
        },
        "program_bank_entries_stored": bank_entries,
        "compile_ledger_by_process": by_process,
        "replica_overflow_regrows": replica_metric(
            "mz_overflow_regrows_total", 0.0
        ),
    }


def run(args) -> int:
    # This process is client and oracle: pin its JAX to the CPU before
    # any backend exists (the persist codec builds no device arrays,
    # but nothing here may ever reach for the chip).
    import faulthandler

    import jax

    jax.config.update("jax_platforms", "cpu")
    # `kill -USR1 <pid>` prints every thread's stack: where a run that
    # outlasts its time limit was waiting.
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    rehearsal = args.sf is not None
    sf = args.sf if rehearsal else DEFAULT_SF
    four = args.chips == 4
    out_dir = os.path.abspath(args.out)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    emit(
        {
            "phase": "config",
            "scale_factor": sf,
            "chips": args.chips,
            "rehearsal": rehearsal,
            "replica_JAX_PLATFORMS": (
                os.environ.get("JAX_PLATFORMS", "") if rehearsal else "tpu"
            ),
            "JAX_COMPILATION_CACHE_DIR": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR", ""
            ),
            "seed": args.seed,
            "tick_interval_seconds": TICK_INTERVAL_S,
        }
    )
    t_all = time.monotonic()
    server = Server(
        out_dir, workers=args.chips,
        replica_platform=None if rehearsal else "tpu",
    )
    oracle = None
    failure = None
    device = None
    try:
        t0 = time.monotonic()
        line = server.wait_listening(timeout=300)
        emit(
            {
                "phase": "start",
                "setup_wall_seconds": round(time.monotonic() - t0, 3),
                "listening": line,
            }
        )
        t0 = time.monotonic()
        for stmt in install_sql(sf, args.seed, with_index=not four):
            t1 = time.monotonic()
            server.sql(stmt, timeout=WAIT_BUDGET_S)
            emit(
                {
                    "phase": "ddl",
                    "statement": stmt[:60],
                    "setup_wall_seconds": round(
                        time.monotonic() - t1, 3
                    ),
                }
            )
        names = ["q1", "q15"] + ([] if four else ["lineitem_idx"])
        wait_hydrated(server, names, timeout=WAIT_BUDGET_S)
        emit(
            {
                "phase": "hydration",
                "setup_wall_seconds_load_install_hydrate": round(
                    time.monotonic() - t0, 3
                ),
                "dataflows": names,
            }
        )
        oracle = Oracle(server.data_dir)
        t0 = time.monotonic()
        start_upper = oracle.lineitem_upper()
        upper = wait_churn(oracle, start_upper, timeout=WAIT_BUDGET_S)
        emit(
            {
                "phase": "churn",
                "wall_seconds": round(time.monotonic() - t0, 3),
                "ticks_committed": upper - start_upper,
                "lineitem_upper": upper,
                "dataflow_frontiers": frontiers(server),
            }
        )
        t0 = time.monotonic()
        views = check_views(server, oracle)
        t = views["as_of"]
        views["phase"] = "views"
        views["wall_seconds_reads_and_oracle"] = round(
            time.monotonic() - t0, 3
        )
        emit(views)
        if not four:
            t0 = time.monotonic()
            lk = check_lookups(server, oracle, t, args.seed)
            lk["phase"] = "lookups"
            lk["wall_seconds_reads_and_oracle"] = round(
                time.monotonic() - t0, 3
            )
            emit(lk)
        server.check_alive()
        facts = server_facts(server)
        facts["phase"] = "server_facts"
        emit(facts)
        reps = facts["replicas"]
        if len(reps) != 1 or not reps[0]["connected"]:
            raise SmokeFailure(f"expected one connected replica: {reps}")
        if not server.replica_pids():
            raise SmokeFailure("the replica process has died")
        device = {
            "platform": reps[0]["platform"],
            "kind": reps[0]["device_kind"],
            "count": reps[0]["devices"],
        }
        if facts["replica_native_kernels"] != 1.0:
            raise SmokeFailure(
                "the replica runs the pure-Python fallbacks "
                f"(mz_native_kernels={facts['replica_native_kernels']})"
            )
        if device["count"] < args.chips:
            raise SmokeFailure(
                f"the replica reports {device['count']} device(s), "
                f"--chips {args.chips} was asked for"
            )
    except SmokeFailure as e:
        failure = str(e)
    except Exception as e:  # a bug in this script is a failure too
        failure = f"{type(e).__name__}: {e}"
    finally:
        if oracle is not None:
            try:
                oracle.close()
            except Exception:
                pass
        down = server.shutdown()
        # The data directory is scratch (hundreds of MB at SF 0.25);
        # the log and this output are what is kept.
        shutil.rmtree(server.data_dir, ignore_errors=True)
    down["phase"] = "shutdown"
    down["total_wall_seconds"] = round(time.monotonic() - t_all, 3)
    emit(down)
    if failure is None and (
        down["environmentd_rc"] != 0 or down["replicas_left"]
    ):
        failure = f"unclean shutdown: {down}"
    if failure is not None:
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr, flush=True)
        return 1
    ok = device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--sf", type=float, default=None,
        help="rehearsal scale factor (the replica then inherits "
        f"JAX_PLATFORMS); the gate runs SF {DEFAULT_SF} on JAX_PLATFORMS=tpu",
    )
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: one replica over four chips (--workers 4), Q1/Q15 and "
        "their oracle only",
    )
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument(
        "--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
        help="output directory (log; the data dir lives under it "
        "during the run)",
    )
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
