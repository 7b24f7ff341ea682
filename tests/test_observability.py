"""Observability plane (ISSUE 12): end-to-end statement traces across
CTP, the compile ledger, deployment-wide metrics, slow-statement log,
and exposition conformance.

The acceptance facts live here: ONE SELECT driven through pgwire shows
a single trace_id whose spans come from the pgwire front end, the
coordinator, the controller, AND the replica SUBPROCESS (context
propagated over CTP commands, completed spans piggybacked back on
Frontiers); a fresh DDL logs compile-ledger misses and a repeated
install of the identical definition logs hits."""

import json
import os
import socket
import sys
import threading
import time as _time

import pytest

from materialize_tpu.utils.compile_ledger import (
    CompileLedger,
    LEDGER,
    expr_fingerprint,
)
from materialize_tpu.utils.metrics import (
    MetricsRegistry,
    cluster_exposition,
)
from materialize_tpu.utils.trace import TRACER, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_coord(tmp_path, with_replica=True, subprocess_replica=False):
    from materialize_tpu.coord.coordinator import Coordinator
    from materialize_tpu.coord.protocol import PersistLocation
    from materialize_tpu.coord.replica import serve_forever
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )
    from materialize_tpu.testing.chaos import ReplicaProcess, _free_port

    loc = PersistLocation(
        str(tmp_path / "blob"), str(tmp_path / "consensus.db")
    )
    cleanup = []
    if with_replica:
        port = _free_port()
        if subprocess_replica:
            rp = ReplicaProcess(
                loc.blob_root, loc.consensus_path, port, rid="r0"
            )
            cleanup.append(rp.stop)
        else:
            ready = threading.Event()
            threading.Thread(
                target=serve_forever, args=(port, loc, "r0", ready),
                daemon=True,
            ).start()
            assert ready.wait(10)
    c = Coordinator(
        PersistClient(
            FileBlob(loc.blob_root), SqliteConsensus(loc.consensus_path)
        ),
        tick_interval=None,
    )
    if with_replica:
        c.add_replica("r0", ("127.0.0.1", port))
    return c, cleanup


# ---------------------------------------------------------------------------
# the tentpole acceptance: one statement, one tree, four layers
# ---------------------------------------------------------------------------


class TestTraceEndToEnd:
    def test_one_select_one_trace_across_processes(self, tmp_path):
        """A SELECT through pgwire produces ONE trace_id whose spans
        cover pgwire -> coordinator -> controller -> the replica
        subprocess, the replica half arriving over the Frontiers
        piggyback with the replica's process label."""
        from materialize_tpu.server.pgwire import PgServer
        from materialize_tpu.testing.chaos import subprocess_available
        from tests.test_server import MiniPg

        if not subprocess_available():
            pytest.skip("cannot spawn replica subprocesses here")
        coord, cleanup = _make_coord(
            tmp_path, subprocess_replica=True
        )
        pg = PgServer(coord).start()
        try:
            client = MiniPg(pg.port)
            _, _, err, _ = client.query(
                "CREATE TABLE ot (k BIGINT NOT NULL, v BIGINT)"
            )
            assert err is None, err
            client.query("INSERT INTO ot VALUES (1, 10), (2, 20)")
            _, _, err, _ = client.query(
                "CREATE MATERIALIZED VIEW omv AS SELECT k, v FROM ot"
            )
            assert err is None, err
            cols, rows, err, _ = client.query("SELECT * FROM omv")
            assert err is None, err
            assert sorted(tuple(r) for r in rows) == [
                ("1", "10"), ("2", "20")
            ]

            # The replica's spans arrive asynchronously on the next
            # Frontiers piggyback: poll mz_trace_spans until the
            # statement's tree is complete (or fail with what we saw).
            deadline = _time.monotonic() + 30.0
            tree = {}
            while _time.monotonic() < deadline:
                res = coord.execute(
                    "SELECT trace_id, span_id, parent_id, process, "
                    "name FROM mz_trace_spans"
                )
                spans = res.rows
                roots = [
                    r for r in spans
                    if r[4] == "pgwire.query"
                    and "SELECT * FROM omv" in self._root_sql(
                        coord, r[0]
                    )
                ]
                if roots:
                    tid = roots[-1][0]
                    tree = {
                        r[1]: r for r in spans if r[0] == tid
                    }
                    names = {r[4] for r in tree.values()}
                    if {"pgwire.query", "coord.execute",
                            "replica.peek"} <= names and any(
                        n.startswith("controller.") for n in names
                    ):
                        break
                _time.sleep(0.1)
            names = {r[4] for r in tree.values()}
            assert "pgwire.query" in names, names
            assert "coord.execute" in names, names
            assert any(
                n.startswith("controller.peek") for n in names
            ), names
            assert "replica.peek" in names, names
            # The replica span CROSSED processes: its process label is
            # the subprocess replica's, and its parent is a
            # coordinator-process controller span in the SAME tree.
            rep_spans = [
                r for r in tree.values() if r[4] == "replica.peek"
            ]
            assert rep_spans and all(
                r[3] == "replica:r0" for r in rep_spans
            ), rep_spans
            for r in rep_spans:
                parent = tree.get(r[2])
                assert parent is not None, (
                    "replica span's parent not in the tree", r, tree
                )
                assert parent[4].startswith("controller.peek")
            # Every non-root span links to a parent inside the tree.
            for r in tree.values():
                if r[4] == "pgwire.query":
                    assert r[2] == 0  # root
                else:
                    assert r[2] in tree, (r, sorted(names))
            # Same piggyback channel, metrics half (tentpole c): the
            # subprocess replica's /metrics samples arrive labeled
            # replica=r0 in mz_metrics AND in the merged exposition.
            deadline = _time.monotonic() + 30.0
            hit = []
            while _time.monotonic() < deadline and not hit:
                from materialize_tpu.coord.introspection import (
                    snapshot,
                )
                from materialize_tpu.repr.schema import GLOBAL_DICT

                hit = [
                    code for code, _v in snapshot(coord, "mz_metrics")
                    if "replica=r0" in GLOBAL_DICT.decode(code)
                ]
                if not hit:
                    client.query("INSERT INTO ot VALUES (3, 30)")
                    _time.sleep(0.3)
            assert hit, "no replica-labeled metrics arrived"
            from materialize_tpu.utils.metrics import (
                REGISTRY,
                cluster_exposition,
            )

            with coord.controller._lock:
                remote = dict(coord.controller.replica_metrics)
            text = cluster_exposition(REGISTRY, remote)
            assert 'replica="r0"' in text
            parse_exposition(text)  # conformant merged exposition
        finally:
            pg.stop()
            coord.shutdown()
            for fn in cleanup:
                fn()

    @staticmethod
    def _root_sql(coord, trace_id: int) -> str:
        for r in TRACER.records():
            if r.trace_id == trace_id and r.name == "pgwire.query":
                return str(r.attrs.get("sql", ""))
        return ""

    def test_trace_level_off_records_nothing(self, tmp_path):
        coord, cleanup = _make_coord(tmp_path)
        marker = "SELECT 8675309"
        try:
            coord.execute("SET trace_level = 'off'")
            coord.execute(marker)
            # Background threads of sibling tests may record spans
            # concurrently; the assertion is scoped to THIS statement.
            assert not any(
                str(r.attrs.get("sql", "")).startswith(marker)
                for r in TRACER.records()
            )
            coord.execute("SET trace_level = 'info'")
            coord.execute(marker)
            assert any(
                str(r.attrs.get("sql", "")).startswith(marker)
                for r in TRACER.records()
            )
        finally:
            coord.execute("SET trace_level = 'info'")
            coord.shutdown()
            for fn in cleanup:
                fn()

    def test_bad_trace_level_rejected(self, tmp_path):
        from materialize_tpu.sql.hir import PlanError

        coord, cleanup = _make_coord(tmp_path, with_replica=False)
        try:
            with pytest.raises(PlanError):
                coord.execute("SET trace_level = 'verbose'")
        finally:
            coord.shutdown()
            for fn in cleanup:
                fn()


# ---------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------


class TestCompileLedger:
    def test_hit_miss_classification(self):
        led = CompileLedger()
        r1 = led.record("step", "df1", "fp1", "tierA", 1.5)
        r2 = led.record("step", "df1", "fp1", "tierA", 0.3)
        r3 = led.record("step", "df1", "fp1", "tierB", 0.2)
        r4 = led.record("span", "df1", "fp1", "tierA", 0.1)
        assert r1.cache == "miss"
        assert r2.cache == "hit"  # same (kind, fp, tier) seen
        assert r3.cache == "miss"  # new tier
        assert r4.cache == "miss"  # new kind
        s = led.summary()
        assert s["compiles"] == 4
        assert s["hits"] == 1 and s["misses"] == 3
        assert s["hit_seconds"] == 0.3
        assert s["by_kind"]["step"]["compiles"] == 3

    def test_ledger_jit_detects_compiles(self):
        import jax
        import jax.numpy as jnp

        from materialize_tpu.utils.compile_ledger import ledger_jit

        led = CompileLedger()
        fn = ledger_jit(
            jax.jit(lambda x: x + 1), "step", "t", "fp", ledger=led
        )
        fn(jnp.ones(3))
        assert len(led.records()) == 1
        fn(jnp.ones(3))  # cached: no new record
        assert len(led.records()) == 1
        fn(jnp.ones(5))  # new signature: compile, new tier -> miss
        recs = led.records()
        assert len(recs) == 2
        assert all(r.cache == "miss" for r in recs)
        assert recs[0].tier != recs[1].tier
        # A FRESH jit of the same program family at a seen tier is the
        # program-bank hit.
        fn2 = ledger_jit(
            jax.jit(lambda x: x + 1), "step", "t", "fp", ledger=led
        )
        fn2(jnp.ones(3))
        assert led.records()[-1].cache == "hit"

    def test_fresh_ddl_misses_and_reinstall_hits(self, tmp_path):
        """Acceptance: a fresh DDL logs >=1 miss to mz_compile_log; a
        DROP + identical re-CREATE logs a hit (the wall a program bank
        keyed by (fingerprint, tier) would recover)."""
        coord, cleanup = _make_coord(tmp_path)
        try:
            coord.execute("CREATE TABLE clt (a INT, b INT)")
            coord.execute("INSERT INTO clt VALUES (1, 2)")
            coord.execute(
                "CREATE MATERIALIZED VIEW clmv AS "
                "SELECT a, b FROM clt"
            )
            coord.execute("SELECT * FROM clmv")
            res = coord.execute(
                "SELECT kind, cache FROM mz_compile_log "
                "WHERE dataflow = 'clmv'"
            )
            assert any(c == "miss" for _k, c in res.rows), res.rows
            # Identical re-install: same expr -> same fingerprint ->
            # the recompile ledgers as a HIT.
            coord.execute("DROP VIEW clmv")
            coord.execute(
                "CREATE MATERIALIZED VIEW clmv AS "
                "SELECT a, b FROM clt"
            )
            coord.execute("SELECT * FROM clmv")
            res = coord.execute(
                "SELECT kind, cache FROM mz_compile_log "
                "WHERE dataflow = 'clmv' AND cache = 'hit'"
            )
            assert res.rows, "re-install of an identical MV logged no hit"
            # EXPLAIN ANALYSIS prints the compiles: block with totals.
            txt = coord.execute(
                "EXPLAIN ANALYSIS SELECT * FROM clmv"
            ).text
            assert "compiles:" in txt
            assert "total: compiles=" in txt
            assert "seconds=" in txt
            assert "bankable_seconds=" in txt
        finally:
            coord.shutdown()
            for fn in cleanup:
                fn()

    def test_fingerprint_stable_across_objects(self):
        from materialize_tpu.expr import relation as mir
        from materialize_tpu.repr.schema import (
            Column,
            ColumnType,
            Schema,
        )

        sch = Schema((Column("k", ColumnType.INT64),))
        a = mir.Get("x", sch)
        b = mir.Get("x", sch)
        assert expr_fingerprint(a) == expr_fingerprint(b)
        assert expr_fingerprint(a) != expr_fingerprint(
            mir.Get("y", sch)
        )


# ---------------------------------------------------------------------------
# prometheus exposition conformance + quantile edges (satellite)
# ---------------------------------------------------------------------------


def parse_exposition(text: str) -> dict:
    """Strict mini-parser of the Prometheus text format: returns
    {family: {"type": kind, "samples": [(name, labels, value)]}};
    raises on malformed lines, duplicate TYPE headers, or samples
    outside their family."""
    import re

    families: dict = {}
    current = None
    line_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(?:\{([^}]*)\})?"
        r" (-?[0-9.eE+\-]+|[+-]Inf|NaN)$"
    )
    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith("# HELP "):
            continue
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ", 3)
            if name in families:
                raise ValueError(f"duplicate TYPE for {name}")
            if kind not in ("counter", "gauge", "histogram",
                            "summary", "untyped"):
                raise ValueError(f"bad kind {kind!r}")
            families[name] = {"type": kind, "samples": []}
            current = name
            continue
        if ln.startswith("#"):
            raise ValueError(f"unknown comment line {ln!r}")
        m = line_re.match(ln)
        if m is None:
            raise ValueError(f"malformed sample line {ln!r}")
        name, raw_labels, value = m.groups()
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in (
                families
            ):
                fam = name[: -len(suffix)]
        if fam != current:
            # samples must follow their family header contiguously
            if fam not in families:
                raise ValueError(f"sample {name!r} without TYPE")
        labels = {}
        if raw_labels:
            for part in raw_labels.split(","):
                k, v = part.split("=", 1)
                if not (v.startswith('"') and v.endswith('"')):
                    raise ValueError(f"unquoted label value in {ln!r}")
                labels[k] = v[1:-1]
        families[fam]["samples"].append((name, labels, float(value)))
    return families


class TestPrometheusConformance:
    def test_histogram_exposition_parses_and_is_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("obs_h_seconds", "latency",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        c = reg.counter("obs_c_total", "count with \n newline help")
        c.inc(3)
        fams = parse_exposition(reg.expose_text())
        assert fams["obs_h_seconds"]["type"] == "histogram"
        buckets = [
            (labels["le"], v)
            for name, labels, v in fams["obs_h_seconds"]["samples"]
            if name == "obs_h_seconds_bucket"
        ]
        # le labels include +Inf; counts are CUMULATIVE.
        assert [b[0] for b in buckets] == ["0.1", "1.0", "10.0", "+Inf"]
        assert [b[1] for b in buckets] == [1.0, 3.0, 4.0, 5.0]
        sums = {
            name: v
            for name, labels, v in fams["obs_h_seconds"]["samples"]
            if not name.endswith("_bucket")
        }
        assert sums["obs_h_seconds_count"] == 5.0
        assert abs(sums["obs_h_seconds_sum"] - 56.05) < 1e-9
        assert fams["obs_c_total"]["samples"][0][2] == 3.0

    def test_bucket_counts_render_as_integers(self):
        reg = MetricsRegistry()
        h = reg.histogram("obs_int_h", buckets=(1.0,))
        h.observe(0.5)
        text = reg.expose_text()
        assert 'obs_int_h_bucket{le="1.0"} 1\n' in text
        assert 'obs_int_h_count 1' in text

    def test_quantile_edge_cases(self):
        reg = MetricsRegistry()
        h = reg.histogram("obs_q", buckets=(0.1, 1.0, 10.0))
        assert h.quantile(0.5) == 0.0  # empty
        h.observe(0.5)  # single observation in bucket le=1.0
        assert h.quantile(0.0) == 1.0  # first NONEMPTY bucket
        assert h.quantile(0.5) == 1.0
        assert h.quantile(1.0) == 1.0
        h2 = reg.histogram("obs_q2", buckets=(0.1, 1.0))
        h2.observe(5.0)  # only the overflow bucket
        assert h2.quantile(0.5) == float("inf")
        assert h2.quantile(0.0) == float("inf")
        h3 = reg.histogram("obs_q3", buckets=(0.1, 1.0))
        h3.observe(0.05)
        h3.observe(5.0)
        assert h3.quantile(0.0) == 0.1
        assert h3.quantile(0.25) == 0.1
        assert h3.quantile(1.0) == float("inf")
        # q outside [0, 1] clamps instead of nonsense.
        assert h3.quantile(-1) == 0.1
        assert h3.quantile(2) == float("inf")

    def test_cluster_exposition_merges_with_replica_label(self):
        local = MetricsRegistry()
        local.counter("shared_total", "help").inc(1)
        remote_reg = MetricsRegistry()
        remote_reg.counter("shared_total", "help").inc(5)
        remote_reg.gauge("replica_only").set(7)
        text = cluster_exposition(
            local, {"r0": remote_reg.families()}
        )
        fams = parse_exposition(text)  # raises on duplicate TYPE
        samples = fams["shared_total"]["samples"]
        assert (
            "shared_total", {}, 1.0
        ) in samples
        assert ("shared_total", {"replica": "r0"}, 5.0) in samples
        assert fams["replica_only"]["samples"] == [
            ("replica_only", {"replica": "r0"}, 7.0)
        ]


# ---------------------------------------------------------------------------
# concurrency: consistent snapshots under writer storms (satellite)
# ---------------------------------------------------------------------------


class TestIntrospectionConcurrency:
    def test_mz_metrics_and_trace_spans_under_writers(self, tmp_path):
        """Reader snapshots of mz_metrics / mz_trace_spans stay
        well-formed while writer threads hammer the tracer and the
        registry — no torn reads, no dict-mutation races."""
        from materialize_tpu.utils.metrics import REGISTRY

        coord, cleanup = _make_coord(tmp_path, with_replica=False)
        stop = threading.Event()
        errors: list = []
        N_WRITERS = 4

        def span_writer(i):
            try:
                while not stop.is_set():
                    with TRACER.span(f"conc.w{i}", worker=i):
                        with TRACER.span("conc.inner"):
                            pass
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def metric_writer(i):
            try:
                name = f"conc_total_{i}_{os.getpid()}"
                m = REGISTRY.get(name) or REGISTRY.counter(name)
                h_name = f"conc_h_{i}_{os.getpid()}"
                h = REGISTRY.get(h_name) or REGISTRY.histogram(h_name)
                while not stop.is_set():
                    m.inc()
                    h.observe(0.01 * i)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [
            threading.Thread(target=span_writer, args=(i,),
                             daemon=True)
            for i in range(N_WRITERS)
        ] + [
            threading.Thread(target=metric_writer, args=(i,),
                             daemon=True)
            for i in range(N_WRITERS)
        ]
        for t in threads:
            t.start()
        try:
            from materialize_tpu.coord.introspection import snapshot
            from materialize_tpu.repr.schema import GLOBAL_DICT

            # Hammer the raw row constructors (where a torn read or
            # dict-mutation race would live) for the whole window.
            # Run until BOTH the time window and the iteration floor
            # are met: with 8 spinning writers on a loaded one-core
            # box the reader's GIL share is unpredictable, and a
            # fixed window alone flakes at 9/10 iterations.
            deadline = _time.monotonic() + 3.0
            reads = 0
            while _time.monotonic() < deadline or reads < 10:
                for vals in snapshot(coord, "mz_metrics"):
                    assert isinstance(vals[-1], float)
                for vals in snapshot(coord, "mz_trace_spans"):
                    assert vals[-2] >= 0  # duration_us (before attrs)
                reads += 1
            assert reads >= 10, reads
            # ...then one full SQL read through the renderer too.
            res = coord.execute(
                "SELECT metric, value FROM mz_metrics"
            )
            assert res.rows
            res = coord.execute(
                "SELECT name, duration_us FROM mz_trace_spans"
            )
            assert res.rows
            assert not errors, errors
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            coord.shutdown()
            for fn in cleanup:
                fn()


# ---------------------------------------------------------------------------
# slow-statement log + arrangement bytes + cluster relations
# ---------------------------------------------------------------------------


class TestSlowStatements:
    def test_threshold_gates_the_log(self, tmp_path):
        coord, cleanup = _make_coord(tmp_path, with_replica=False)
        try:
            coord.execute("CREATE TABLE slt_t (a INT)")
            # Disabled by default: nothing logged.
            assert coord.execute(
                "SELECT * FROM mz_slow_statements"
            ).rows == []
            coord.update_config({"slow_statement_ms": 0.0001})
            coord.execute("INSERT INTO slt_t VALUES (1)")
            res = coord.execute(
                "SELECT sql, ms FROM mz_slow_statements"
            )
            assert any(
                "INSERT INTO slt_t" in sql for sql, _ms in res.rows
            ), res.rows
            assert all(ms > 0 for _sql, ms in res.rows)
        finally:
            coord.update_config({"slow_statement_ms": None})
            coord.shutdown()
            for fn in cleanup:
                fn()


class TestArrangementBytes:
    def test_device_bytes_per_component(self, tmp_path):
        coord, cleanup = _make_coord(tmp_path)
        try:
            coord.execute("CREATE TABLE abt (a INT, b INT)")
            coord.execute("INSERT INTO abt VALUES (1, 2), (3, 4)")
            coord.execute(
                "CREATE MATERIALIZED VIEW abmv AS "
                "SELECT a, b FROM abt"
            )
            coord.execute("SELECT * FROM abmv")
            deadline = _time.monotonic() + 20.0
            rows = []
            while _time.monotonic() < deadline:
                rows = coord.execute(
                    "SELECT records, bytes, runs_bytes, slots_bytes, "
                    "lanes_bytes, history_bytes "
                    "FROM mz_arrangement_sizes "
                    "WHERE dataflow = 'abmv'"
                ).rows
                if rows and rows[0][1] > 0:
                    break
                _time.sleep(0.1)
            assert rows, "no mz_arrangement_sizes row for abmv"
            records, total, runs, slots, lanes, hist = rows[0]
            assert records == 2
            assert runs > 0
            assert total == runs + slots + lanes + hist
        finally:
            coord.shutdown()
            for fn in cleanup:
                fn()


# ---------------------------------------------------------------------------
# tracer unit behavior new in ISSUE 12
# ---------------------------------------------------------------------------


class TestTracerContexts:
    def test_statement_mints_distinct_trace_ids(self):
        tr = Tracer()
        with tr.statement("s1") as a:
            t1 = tr.current_trace()
            assert tr.context() == {"t": t1, "s": a}
        with tr.statement("s2"):
            t2 = tr.current_trace()
        assert t1 != t2
        recs = {r.name: r for r in tr.records()}
        assert recs["s1"].trace_id == t1
        assert recs["s2"].trace_id == t2
        assert recs["s1"].parent_id is None

    def test_adopt_links_remote_child(self):
        tr = Tracer()
        with tr.statement("root"):
            ctx = tr.context()
        remote = Tracer()
        with remote.adopt(ctx):
            with remote.span("child"):
                pass
        child = remote.records()[0]
        assert child.trace_id == ctx["t"]
        assert child.parent_id == ctx["s"]

    def test_ship_and_ingest_dedupe_by_pid(self):
        tr = Tracer()
        tr.enable_ship()
        with tr.span("shipped"):
            pass
        wire = tr.drain_shippable()
        assert len(wire) == 1
        assert tr.drain_shippable() == []
        # Same-pid ingest is dropped (in-process replica sharing).
        tr.ingest(wire, process="r0")
        assert len(tr.records()) == 1
        # A foreign pid lands, relabeled with the replica name.
        foreign = list(wire[0])
        foreign[-1] = wire[0][-1] + 1  # pid field
        tr2 = Tracer()
        tr2.ingest([tuple(foreign)], process="r9")
        recs = tr2.records()
        assert len(recs) == 1 and recs[0].process == "r9"

    def test_record_is_levelled(self):
        tr = Tracer()
        assert tr.record("dbg", 0.0, 0.1, level="debug") is None
        tr.set_level("debug")
        assert tr.record("dbg", 0.0, 0.1, level="debug") is not None

    def test_span_ids_embed_pid(self):
        tr = Tracer()
        with tr.span("x") as sid:
            pass
        assert sid >> 40 == os.getpid() & 0x3FFFFF


# ---------------------------------------------------------------------------
# chrome export of tracer records
# ---------------------------------------------------------------------------


class TestTraceExport:
    def test_spans_to_chrome_valid(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import trace_export

        tr = Tracer(process="unit")
        with tr.statement("stmt"):
            with tr.span("inner"):
                pass
        chrome = trace_export.tracer_records_to_chrome(tr.records())
        assert trace_export.validate_chrome_trace(chrome) == []
        names = {e["name"] for e in chrome["traceEvents"]}
        assert {"stmt", "inner"} <= names
        # json-serializable end to end
        json.dumps(chrome)


# ---------------------------------------------------------------------------
# phase spans of the maintenance path (ISSUE 27)
# ---------------------------------------------------------------------------

PHASES_SINKED = {
    "span.wait", "span.fetch", "span.upload", "span.dispatch",
    "span.readback", "span.append", "span.publish",
}


def _kv_schema():
    from materialize_tpu.repr.schema import Column, ColumnType, Schema

    return Schema(
        [Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)]
    )


def _kv_tick(t: int):
    import numpy as np

    return (
        [np.asarray([t], np.int64), np.asarray([10], np.int64)],
        [None, None],
        np.full(1, t, np.uint64),
        np.asarray([1], np.int64),
    )


def _kv_view(ticks: int, sink: str | None = "out", name: str = "mv"):
    """A view over one shard holding ``ticks`` un-compacted one-row
    batches, hydrated; its writer, to add more."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.storage.persist import (
        MaintainedView,
        MemBlob,
        MemConsensus,
        PersistClient,
    )

    kv = _kv_schema()
    c = PersistClient(MemBlob(), MemConsensus())
    w = c.open_writer("kv", kv)
    for t in range(ticks):
        w.compare_and_append(*_kv_tick(t), t, t + 1)
    view = MaintainedView(
        c, Dataflow(mir.Get("kv", kv), name=name), {"kv": ("kv", kv)}, sink
    )
    return view, w


def _append_ticks(w, lo: int, n: int) -> None:
    for t in range(lo, lo + n):
        w.compare_and_append(*_kv_tick(t), t, t + 1)


@pytest.fixture
def tracer():
    """The process tracer, emptied, at ``info``, with no annotate hook;
    restored afterwards (sibling tests share it)."""
    saved = (TRACER.level, TRACER.annotate)
    TRACER.set_level("info")
    TRACER.annotate = None
    TRACER.clear()
    yield TRACER
    TRACER.set_level(saved[0])
    TRACER.annotate = saved[1]


def _spans_of(dataflow: str) -> list:
    """(span record, {phase name: child record}) of one dataflow."""
    recs = TRACER.records()
    out = []
    for r in recs:
        if r.name == "span" and r.attrs.get("dataflow") == dataflow:
            kids = {c.name: c for c in recs if c.parent_id == r.span_id}
            out.append((r, kids))
    return out


class TestPhaseAccumulator:
    def test_one_record_a_phase_with_summed_counts(self):
        tr = Tracer()
        sp = tr.open("parent", lower=3)
        with tr.within(sp):
            for rows in (2, 5, 7):
                with tr.phase("parent.work", calls=1) as ph:
                    ph.add(rows=rows)
            with tr.phase("parent.other"):
                pass
        tr.close(sp, upper=4)
        recs = {r.name: r for r in tr.records()}
        assert set(recs) == {"parent", "parent.work", "parent.other"}
        assert recs["parent"].attrs == {"lower": 3, "upper": 4}
        work = recs["parent.work"]
        assert work.parent_id == recs["parent"].span_id
        assert work.attrs == {"calls": 3, "rows": 14, "n": 3}
        assert recs["parent.other"].attrs == {"n": 1}
        assert work.duration <= recs["parent"].duration
        assert work.start >= recs["parent"].start

    def test_a_span_never_closed_records_nothing(self):
        tr = Tracer()
        sp = tr.open("parent")
        with tr.within(sp):
            with tr.phase("parent.work"):
                pass
        assert tr.records() == []

    def test_a_phase_outside_a_span_is_nothing(self):
        tr = Tracer()
        calls = []
        tr.annotate = calls.append
        with tr.phase("lonely", rows=1) as ph:
            assert not ph
            ph.add(rows=2)
        with tr.adopt({"t": 1, "s": 2}):  # a context, not a span
            with tr.phase("lonely") as ph:
                assert not ph
        assert tr.records() == [] and calls == []

    def test_a_record_of_the_shipping_waits_for_company(self):
        tr = Tracer()
        tr.enable_ship()
        tr.record("report", 0.0, 0.0, ship_alone=False)
        assert tr.drain_shippable() == []  # no message of its own
        tr.record("news", 0.0, 0.0)
        assert [w[2] for w in tr.drain_shippable()] == ["report", "news"]
        assert tr.drain_shippable() == []

    def test_rings_keep_16384(self):
        from materialize_tpu.utils.trace import RING_CAPACITY

        assert RING_CAPACITY == 16384
        tr = Tracer()
        tr.enable_ship()
        assert tr._buf.maxlen == 16384
        assert tr._ingested.maxlen == 16384
        assert tr._ship.maxlen == 16384
        for i in range(16384 + 10):
            tr.record("r", 0.0, 0.0)
        assert len(tr.records()) == 16384


class TestMaintenancePhases:
    def test_sinked_span_has_every_phase_child(self, tracer):
        view, w = _kv_view(4)
        assert view.upper == 4
        _append_ticks(w, 4, 3)
        tracer.clear()
        assert view._step_span_sync(8, 1.0)
        spans = _spans_of("mv")
        assert len(spans) == 1  # one record a committed span
        span, kids = spans[0]
        assert span.level == "info"
        assert span.attrs["lower"] == 4
        assert span.attrs["upper"] == 7 == view.upper
        assert span.attrs["ticks"] == 3
        assert span.attrs["epoch"] == view.span_epoch
        assert span.attrs["replayed"] is False
        assert span.attrs["prefetched_ticks"] == 0
        assert span.attrs["overlapped_commit_ticks"] == 0
        assert set(kids) == PHASES_SINKED
        assert sum(k.duration for k in kids.values()) <= span.duration
        for k in kids.values():
            assert k.start >= span.start
            assert k.attrs["n"] >= 1
        assert kids["span.fetch"].attrs["rows"] == 3
        assert kids["span.append"].attrs["rows"] == 3
        assert kids["span.append"].attrs["cas_attempts"] == 3
        assert kids["span.append"].attrs["part_bytes"] > 0
        assert kids["span.dispatch"].attrs["programs"] >= 3
        assert kids["span.upload"].attrs["bytes"] > 0
        assert kids["span.readback"].attrs["bytes"] > 0
        # nothing ready: no span is committed, none is recorded
        tracer.clear()
        assert not view._step_span_sync(8, 0.0)
        assert _spans_of("mv") == []

    @pytest.mark.parametrize("path", ["sync", "pipelined", "tick"])
    def test_every_stepping_path_emits_the_same_names(self, tracer, path):
        sink = None if path == "pipelined" else "out"
        view, w = _kv_view(2, sink=sink, name=path)
        _append_ticks(w, 2, 2)
        tracer.clear()
        if path == "tick":
            assert view.step(1.0)
        else:
            assert view.step_span(timeout=1.0)
            view.sync_spans()
        names = {r.name for r in TRACER.records()}
        spans = _spans_of(path)
        assert len(spans) == 1
        span, kids = spans[0]
        assert span.attrs["upper"] == view.upper
        assert span.attrs["prefetched_ticks"] == 0
        # what the view reserves on the device (PR 30): the bytes of
        # its operator state and output spine, from their shapes, on
        # the span and on the gauge of /metrics
        from materialize_tpu.arrangement.spine import device_nbytes
        from materialize_tpu.utils.metrics import REGISTRY

        reserved = span.attrs["state_capacity_bytes"]
        assert reserved == device_nbytes((view.df.states, view.df.output))
        assert reserved > 0
        gauge = REGISTRY.get("mz_dataflow_state_capacity_bytes")
        assert gauge.value(path) == reserved
        assert (
            f'mz_dataflow_state_capacity_bytes{{dataflow="{path}"}} '
            in REGISTRY.expose_text()
        )
        # an index view appends nothing; every other phase is there
        want = PHASES_SINKED - ({"span.append"} if sink is None else set())
        assert set(kids) == want
        assert names == want | {"span"}

    def test_span_times_the_gather_for_the_span_after_it(self, tracer):
        view, w = _kv_view(2)
        _append_ticks(w, 2, 6)
        tracer.clear()
        assert view._step_span_sync(3, 1.0)
        assert view._step_span_sync(3, 1.0)
        (first, kids1), (second, kids2) = _spans_of("mv")
        assert (first.attrs["ticks"], second.attrs["ticks"]) == (3, 3)
        assert first.attrs["prefetched_ticks"] == 0
        assert second.attrs["prefetched_ticks"] == 3
        # the first span fetched and uploaded its own three one-row
        # ticks and, with its steps dispatched, the second's; the
        # second found nothing ready beyond its own
        assert kids1["span.fetch"].attrs["rows"] == 6
        assert kids1["span.fetch"].attrs["n"] == 6
        assert "span.fetch" not in kids2
        assert kids2["span.wait"].attrs["n"] == 1
        assert kids2["span.append"].attrs["rows"] == 3
        assert view.upper == 8 and view._kept == []
        # the first span was written in the second call, beneath the
        # second's dispatch, under its OWN record: that one closes
        # when its commit is durable, so the two records overlap and
        # each times its own copy-out, append and publish
        assert first.attrs["overlapped_commit_ticks"] == 3
        assert second.attrs["overlapped_commit_ticks"] == 0
        assert (first.attrs["upper"], second.attrs["upper"]) == (5, 8)
        assert kids1["span.append"].attrs["rows"] == 3
        assert kids1["span.append"].attrs["cas_attempts"] == 3
        assert first.start + first.duration > second.start
        assert (
            kids1["span.append"].start
            > kids2["span.dispatch"].start
        )

    def test_fetch_counts_grow_with_the_shards_age(self, tracer):
        view, w = _kv_view(4)

        def one_span(lo: int) -> dict:
            _append_ticks(w, lo, 2)
            tracer.clear()
            assert view._step_span_sync(8, 1.0)
            ((_span, kids),) = _spans_of("mv")
            return kids["span.fetch"].attrs

        young = one_span(4)
        assert young["reloads"] >= 2 and young["state_bytes"] > 0
        assert young["batches_listed"] >= 2 * 5
        # 200 ticks later, nothing having merged the source's batches
        _append_ticks(w, 6, 200)
        while view.upper < 206:
            assert view._step_span_sync(8, 1.0)
        old = one_span(206)
        assert old["reloads"] == young["reloads"]
        assert old["state_bytes"] > 10 * young["state_bytes"]
        assert old["batches_listed"] >= young["batches_listed"] + 2 * 200

    def test_annotate_hook_once_an_occurrence(self, tracer):
        view, w = _kv_view(2)
        _append_ticks(w, 2, 2)
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                return False

        tracer.annotate = Annotation
        tracer.clear()
        assert view._step_span_sync(8, 1.0)
        ((_span, kids),) = _spans_of("mv")
        assert all(n.startswith("mz:span.") for n in entered)
        for name, k in kids.items():
            assert entered.count("mz:" + name) == k.attrs["n"]
        assert len(entered) == sum(k.attrs["n"] for k in kids.values())
        # at 'off' nothing is annotated and nothing recorded
        _append_ticks(w, 4, 2)
        entered.clear()
        tracer.clear()
        tracer.set_level("off")
        assert view._step_span_sync(8, 1.0)
        assert entered == [] and TRACER.records() == []
        # and with no hook set, phases record without it
        tracer.set_level("info")
        tracer.annotate = None
        _append_ticks(w, 6, 1)
        assert view._step_span_sync(8, 1.0)
        assert entered == [] and len(_spans_of("mv")) == 1

    def test_persist_counters_by_shard_kind(self, tracer):
        from materialize_tpu.utils.metrics import REGISTRY

        def value(name, kind):
            m = REGISTRY.get(name)
            return 0.0 if m is None else m.value(kind)

        names = (
            "mz_persist_state_reloads_total",
            "mz_persist_state_decoded_bytes_total",
            "mz_persist_cas_attempts_total",
        )
        view, w = _kv_view(2)
        _append_ticks(w, 2, 2)
        before = {(n, k): value(n, k) for n in names
                  for k in ("source", "sink")}
        assert view._step_span_sync(8, 1.0)
        ((_span, kids),) = _spans_of("mv")
        src = kids["span.wait"].attrs["reloads"] + kids[
            "span.fetch"].attrs["reloads"]
        assert value(names[0], "source") - before[names[0], "source"] >= src
        assert value(names[0], "sink") - before[names[0], "sink"] >= 2
        assert value(names[1], "source") > before[names[1], "source"]
        assert value(names[2], "sink") - before[names[2], "sink"] >= 2
        text = REGISTRY.expose_text()
        assert 'mz_persist_state_reloads_total{shard="source"}' in text
        assert REGISTRY.get("mz_persist_parts_read_total") is not None or (
            kids["span.fetch"].attrs.get("parts_read", 0) == 0
        )

    def test_source_tick_one_record_a_tick(self, tracer):
        from materialize_tpu.coord.sources import GeneratorSource
        from materialize_tpu.storage.persist import (
            MemBlob,
            MemConsensus,
            PersistClient,
        )

        src = GeneratorSource(
            PersistClient(MemBlob(), MemConsensus()), "gen", "counter",
            {}, "gen", tick_interval=None,
        )
        first = src.t
        tracer.clear()
        for _ in range(3):
            src.tick_once()
        recs = [r for r in TRACER.records() if r.name == "source.tick"]
        assert [r.attrs["t"] for r in recs] == [first, first + 1, first + 2]
        for r in recs:
            a = r.attrs
            assert a["source"] == "gen"
            parts = (a["generate_ms"] + a["encode_ms"] + a["write_ms"]
                     + a["cas_ms"] + a["compact_ms"])
            assert parts == pytest.approx(a["work_ms"])
            assert a["work_ms"] == pytest.approx(r.duration * 1e3)
            assert min(a["generate_ms"], a["encode_ms"], a["write_ms"],
                       a["cas_ms"], a["compact_ms"]) >= 0
            assert a["reloads"] >= 1 and a["state_bytes"] > 0
            assert a["cas_attempts"] >= 1 and a["rows"] >= 1
            assert a["slept_ms"] == 0.0  # ticked by hand: no sleep
        # at 'off' a tick records nothing
        tracer.set_level("off")
        tracer.clear()
        src.tick_once()
        assert TRACER.records() == []

    def test_recorder_functions_are_host_sync_clean(self):
        from materialize_tpu.analysis.host_sync import (
            RECORDER_PATH,
            _resolve,
            lint_function,
        )

        new = {
            "Tracer.open", "Tracer.within", "Tracer.close",
            "Tracer.phase", "_Phase.__enter__", "_Phase.add",
            "_Phase.__exit__", "Tally.mark", "Tally.since",
            "persist_phase", "MaintainedView._open_span",
            "MaintainedView._close_span",
        }
        assert new <= {qn for _mod, qn in RECORDER_PATH}
        for mod, qn in RECORDER_PATH:
            if qn in new:
                assert lint_function(_resolve(mod, qn), where=qn) == []


class TestFlightRecorder:
    @staticmethod
    def _environment(tmp_path):
        from materialize_tpu.server.environmentd import Environment

        return Environment(
            str(tmp_path / "envd"), n_replicas=1, tick_interval=None,
            in_process_replicas=True,
        )

    def test_dump_written_on_the_stop_path(self, tmp_path, monkeypatch):
        dump_dir = tmp_path / "dump"
        dump_dir.mkdir()
        monkeypatch.setenv("MZ_TRACE_DUMP_DIR", str(dump_dir))
        env = self._environment(tmp_path)
        try:
            with TRACER.span("dump.marker", rows=7):
                pass
        finally:
            env.shutdown()
        path = dump_dir / "spans.jsonl"
        lines = [json.loads(ln) for ln in path.read_text().splitlines()]
        marker = [r for r in lines if r["name"] == "dump.marker"]
        assert marker and marker[-1]["attrs"] == {"rows": 7}
        assert set(marker[-1]) == {
            "trace_id", "span_id", "parent_id", "process", "name",
            "level", "start_us", "duration_us", "attrs",
        }
        # ... in the shape scripts/trace_export.py takes
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import trace_export

        out = tmp_path / "spans.chrome.json"
        assert trace_export.main([str(path), "-o", str(out)]) == 0
        chrome = json.loads(out.read_text())
        assert trace_export.validate_chrome_trace(chrome) == []
        events = [e for e in chrome["traceEvents"]
                  if e["name"] == "dump.marker"]
        assert events and events[-1]["args"]["rows"] == 7

    def test_no_dump_when_the_variable_is_unset(self, tmp_path, monkeypatch):
        monkeypatch.delenv("MZ_TRACE_DUMP_DIR", raising=False)
        monkeypatch.chdir(tmp_path)
        env = self._environment(tmp_path)
        env.shutdown()
        assert not any(
            f == "spans.jsonl" for _r, _d, fs in os.walk(tmp_path) for f in fs
        )

    def test_mz_trace_spans_serves_attrs(self, tmp_path, tracer):
        coord, cleanup = _make_coord(tmp_path, with_replica=False)
        try:
            sp = tracer.open("span", dataflow="shown", lower=1)
            with tracer.within(sp):
                with tracer.phase("span.fetch", reloads=2) as ph:
                    ph.add(state_bytes=4096)
            tracer.close(sp, upper=2)
            rows = coord.execute(
                "SELECT name, attrs FROM mz_trace_spans "
                "WHERE name = 'span.fetch' OR name = 'span'"
            ).rows
            got = {name: json.loads(attrs) for name, attrs in rows}
            assert got["span"] == {
                "dataflow": "shown", "lower": 1, "upper": 2,
            }
            assert got["span.fetch"] == {
                "n": 1, "reloads": 2, "state_bytes": 4096,
            }
            # a record without attributes serves the empty text
            tracer.record("bare", 0.0, 0.0)
            assert coord.execute(
                "SELECT attrs FROM mz_trace_spans WHERE name = 'bare'"
            ).rows == [("",)]
        finally:
            coord.shutdown()
            for fn in cleanup:
                fn()

    def test_report_frontiers_recorded_only_when_sent(self, tmp_path, tracer):
        coord, cleanup = _make_coord(tmp_path)
        try:
            coord.execute("CREATE TABLE rft (a INT)")
            coord.execute("INSERT INTO rft VALUES (1)")
            coord.execute(
                "CREATE MATERIALIZED VIEW rfmv AS SELECT a FROM rft"
            )
            assert coord.execute("SELECT * FROM rfmv").rows == [(1,)]
            sent = [
                r for r in TRACER.records()
                if r.name == "replica.report_frontiers"
            ]
            assert sent and all(r.attrs["bytes"] > 0 for r in sent)
            assert all("spans_shipped" in r.attrs for r in sent)
            # an idle replica's loop turns send nothing: no new record
            _time.sleep(0.3)
            n = len([r for r in TRACER.records()
                     if r.name == "replica.report_frontiers"])
            _time.sleep(0.3)
            assert n == len([r for r in TRACER.records()
                             if r.name == "replica.report_frontiers"])
        finally:
            coord.shutdown()
            for fn in cleanup:
                fn()
