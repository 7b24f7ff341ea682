"""Aux-subsystem tests: metrics registry, dyncfg, tracing spans, and
introspection relations queried through full SQL (SURVEY.md §5)."""

import socket
import threading

import pytest

from materialize_tpu.utils.dyncfg import (
    COMPUTE_CONFIGS,
    Config,
    ConfigSet,
)
from materialize_tpu.utils.metrics import MetricsRegistry
from materialize_tpu.utils.trace import Tracer


class TestMetrics:
    def test_counter_gauge_histogram_exposition(self):
        reg = MetricsRegistry()
        c = reg.counter("mt_requests_total", "requests")
        g = reg.gauge("mt_frontier", "frontier")
        h = reg.histogram("mt_latency_seconds", buckets=(0.1, 1.0))
        c.inc()
        c.inc(2)
        g.set(42)
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.expose_text()
        assert "mt_requests_total 3" in text
        assert "mt_frontier 42" in text
        assert 'mt_latency_seconds_bucket{le="0.1"} 1' in text
        assert 'mt_latency_seconds_bucket{le="+Inf"} 3' in text
        assert "mt_latency_seconds_count 3" in text
        assert h.quantile(0.5) == 1.0
        with pytest.raises(ValueError):
            reg.counter("mt_requests_total")

    def test_histogram_quantile_empty(self):
        reg = MetricsRegistry()
        assert reg.histogram("h").quantile(0.99) == 0.0


class TestDyncfg:
    def test_defaults_updates_and_coercion(self):
        cs = ConfigSet()
        flag = Config("my_flag", True, "a flag").register(cs)
        limit = Config("my_limit", 10).register(cs)
        assert flag(cs) is True
        assert limit(cs) == 10
        full = cs.update({"my_flag": "off", "my_limit": "32", "newer": 1})
        assert flag(cs) is False
        assert limit(cs) == 32
        assert full["newer"] == 1  # unknown keys carried through
        cur = cs.current()
        assert cur["my_flag"] is False

    def test_compute_configs_registered(self):
        assert COMPUTE_CONFIGS.get("delta_join_min_inputs") == 3


class TestTracer:
    def test_span_nesting_and_filtering(self):
        tr = Tracer()
        with tr.span("outer") as outer_id:
            with tr.span("inner"):
                pass
            with tr.span("debug_only", level="debug"):
                pass  # filtered out at info level
        recs = {r.name: r for r in tr.records()}
        assert set(recs) == {"outer", "inner"}
        assert recs["inner"].parent_id == outer_id
        tr.set_level("debug")
        with tr.span("d2", level="debug"):
            pass
        assert any(r.name == "d2" for r in tr.records())

    def test_adopt_propagates_the_shipped_context(self):
        tr = Tracer()
        with tr.span("client") as cid:
            shipped = tr.context()
        assert shipped["s"] == cid
        with tr.adopt(shipped):
            with tr.span("server"):
                pass
        with tr.adopt(None):  # nothing shipped: a pass-through
            with tr.span("orphan"):
                pass
        recs = {r.name: r for r in tr.records()}
        assert recs["server"].parent_id == cid
        assert recs["server"].trace_id == shipped["t"]
        assert recs["orphan"].parent_id is None


class TestIntrospectionSql:
    @pytest.fixture
    def coord(self, tmp_path):
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
            target=serve_forever, args=(port, loc, "r0", ready),
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
        yield c
        c.shutdown()

    def test_objects_and_frontiers(self, coord):
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT count(*) FROM counter"
        )
        res = coord.execute(
            "SELECT name, type FROM mz_objects WHERE type = 'source'"
        )
        names = [r[0] for r in res.rows]
        assert "c" in names and "counter" in names
        # Aggregation over introspection (full SQL surface).
        res = coord.execute(
            "SELECT type, count(*) AS n FROM mz_objects GROUP BY type"
        )
        kinds = dict(res.rows)
        assert kinds["introspection"] >= 5
        coord.sources["c"].tick_once()
        coord.execute("SELECT * FROM m")  # forces frontier waiting
        res = coord.execute(
            "SELECT dataflow, upper FROM mz_dataflow_frontiers "
            "WHERE dataflow = 'm'"
        )
        assert res.rows and res.rows[0][1] >= 1
        res = coord.execute(
            "SELECT dataflow, records FROM mz_arrangement_sizes "
            "WHERE dataflow = 'm'"
        )
        assert res.rows and res.rows[0][1] == 1
        res = coord.execute("SELECT name FROM mz_cluster_replicas")
        assert res.rows == [("r0",)]

    def test_mixing_rejected(self, coord):
        from materialize_tpu.sql.hir import PlanError

        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        with pytest.raises(PlanError):
            coord.execute(
                "SELECT * FROM mz_objects, counter"
            )
