"""Coordinator tests: SQL in, maintained results out — DDL sequencing,
durable catalog bootstrap, fast/slow-path peeks, timestamp selection,
EXPLAIN/SHOW, and restart recovery (the environmentd-level slice of
SURVEY.md §3.1/§3.2/§3.3)."""

import socket
import threading
import time

import pytest

from materialize_tpu.coord.coordinator import Coordinator
from materialize_tpu.coord.protocol import PersistLocation
from materialize_tpu.coord.replica import serve_forever
from materialize_tpu.sql.hir import PlanError
from materialize_tpu.storage.persist import (
    FileBlob,
    PersistClient,
    SqliteConsensus,
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def cluster(tmp_path):
    """One replica + a persist location + a coordinator factory."""
    loc = PersistLocation(
        str(tmp_path / "blob"), str(tmp_path / "consensus.db")
    )
    port = _free_port()
    ready = threading.Event()
    threading.Thread(
        target=serve_forever, args=(port, loc, "r0", ready), daemon=True
    ).start()
    assert ready.wait(10)

    coords = []

    def make_coord():
        c = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,  # manual ticks: deterministic tests
        )
        c.add_replica("r0", ("127.0.0.1", port))
        coords.append(c)
        return c

    yield make_coord
    for c in coords:
        c.shutdown()


class TestCoordinator:
    def test_mz_cluster_replicas_shows_the_device(self, cluster):
        """Which device serves a replica's answers is visible from the
        server (PR 24): the in-process replica of this CPU suite
        reports the forced 8-device cpu platform at HelloOk."""
        import jax

        coord = cluster()
        deadline = time.monotonic() + 30
        rows = []
        while time.monotonic() < deadline:
            rows = coord.execute(
                "SELECT name, platform, device_kind, devices "
                "FROM mz_cluster_replicas WHERE connected = 1"
            ).rows
            if rows and rows[0][1]:
                break
            time.sleep(0.05)
        assert rows == [("r0", "cpu", "cpu", len(jax.devices()))]

    def test_counter_mv_end_to_end(self, cluster):
        coord = cluster()
        assert coord.execute(
            "CREATE SOURCE c FROM LOAD GENERATOR counter"
        ).kind == "ok"
        coord.execute(
            "CREATE MATERIALIZED VIEW totals AS "
            "SELECT count(*) AS n, sum(counter) AS s FROM counter"
        )
        src = coord.sources["c"]
        for _ in range(4):
            src.tick_once()  # counter now holds 0,1,2,3,4
        res = coord.execute("SELECT * FROM totals")
        assert res.kind == "rows"
        assert res.rows == [(5, 10)]
        assert res.columns == ("n", "s")

    def test_slow_path_select_and_view_inlining(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE VIEW evens AS SELECT counter FROM counter "
            "WHERE counter % 2 = 0"
        )
        coord.sources["c"].tick_once()
        coord.sources["c"].tick_once()  # values 0,1,2
        res = coord.execute("SELECT counter FROM evens")
        assert res.rows == [(0,), (2,)]

    def test_index_makes_view_peekable(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE VIEW evens AS SELECT counter FROM counter "
            "WHERE counter % 2 = 0"
        )
        coord.execute("CREATE INDEX evens_idx ON evens")
        assert coord.peekable["evens"] == "evens_idx"
        coord.sources["c"].tick_once()
        coord.sources["c"].tick_once()
        res = coord.execute("SELECT counter FROM evens")
        assert res.rows == [(0,), (2,)]

    def test_select_after_tick_sees_data(self, cluster):
        """Timestamp selection: SELECT picks min(upper)-1 so it reads a
        complete time — data from completed ticks is always visible."""
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        res0 = coord.execute("SELECT counter FROM counter")
        assert res0.rows == [(0,)]
        coord.sources["c"].tick_once()
        res1 = coord.execute("SELECT counter FROM counter")
        assert res1.rows == [(0,), (1,)]

    def test_explain_and_show(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        res = coord.execute(
            "EXPLAIN OPTIMIZED PLAN FOR SELECT count(*) FROM counter"
        )
        assert "Reduce" in res.text
        res = coord.execute("SHOW objects")
        names = [r[0] for r in res.rows]
        assert "c" in names and "counter" in names

    def test_drop_and_errors(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT count(*) FROM counter"
        )
        coord.execute("DROP view m")
        with pytest.raises(PlanError):
            coord.execute("SELECT * FROM m")
        with pytest.raises(PlanError):
            coord.execute("DROP view m")
        assert coord.execute("DROP view IF EXISTS m").kind == "ok"

    def test_drop_kind_mismatch_and_dependency_protection(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT count(*) FROM counter"
        )
        # Wrong kind: a source is not a view.
        with pytest.raises(PlanError):
            coord.execute("DROP view c")
        # Dependency: the MV still reads the source's subsource.
        with pytest.raises(PlanError):
            coord.execute("DROP source c")
        coord.execute("DROP view m")
        coord.execute("DROP source c")  # now fine

    def test_failed_create_leaves_no_poison_record(self, cluster):
        """A CREATE that fails validation must not durably record DDL —
        a poison record would brick every future bootstrap."""
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute("CREATE VIEW v AS SELECT counter FROM counter")
        with pytest.raises(PlanError):
            coord.execute("CREATE VIEW v AS SELECT counter FROM counter")
        with pytest.raises(PlanError):
            coord.execute(
                "CREATE MATERIALIZED VIEW v AS SELECT count(*) FROM counter"
            )
        coord.shutdown()
        coord2 = cluster()  # must boot cleanly
        assert "v" in coord2.catalog.items

    def test_recreated_mv_does_not_resume_old_shard(self, cluster):
        """DROP + re-CREATE of an MV with the same name gets a FRESH
        shard (named by record id), not the old definition's data."""
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT count(*) AS n FROM counter"
        )
        coord.sources["c"].tick_once()
        assert coord.execute("SELECT * FROM m").rows == [(2,)]
        sh1 = coord.catalog.items["m"].definition["shard"]
        coord.execute("DROP view m")
        coord.execute(
            "CREATE MATERIALIZED VIEW m AS "
            "SELECT sum(counter) AS s FROM counter"
        )
        sh2 = coord.catalog.items["m"].definition["shard"]
        assert sh1 != sh2
        assert coord.execute("SELECT * FROM m").rows == [(1,)]  # 0+1

    def test_index_on_mv_visible_and_droppable(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        coord.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT count(*) FROM counter"
        )
        coord.execute("CREATE INDEX i ON m")
        names = [r[0] for r in coord.execute("SHOW objects").rows]
        assert "i" in names
        with pytest.raises(PlanError):
            coord.execute("DROP view m")  # index depends on it
        coord.execute("DROP index i")
        coord.execute("DROP view m")

    def test_restart_bootstrap(self, cluster, tmp_path):
        """Coordinator restart: catalog replays, sources resume ticking
        at their shard upper, MVs keep serving (0dt-ish recovery)."""
        coord = cluster()
        coord.execute(
            "CREATE SOURCE c FROM LOAD GENERATOR counter"
        )
        coord.execute(
            "CREATE MATERIALIZED VIEW totals AS "
            "SELECT count(*) AS n FROM counter"
        )
        coord.execute(
            "CREATE VIEW evens AS SELECT counter FROM counter "
            "WHERE counter % 2 = 0"
        )
        coord.sources["c"].tick_once()
        assert coord.execute("SELECT * FROM totals").rows == [(2,)]
        coord.shutdown()

        coord2 = cluster()  # fresh coordinator, same durable state
        assert sorted(coord2.sources) == ["c"]
        assert coord2.sources["c"].t == 2  # resumed at the shard upper
        coord2.sources["c"].tick_once()
        assert coord2.execute("SELECT * FROM totals").rows == [(3,)]
        assert coord2.execute("SELECT counter FROM evens").rows == [
            (0,), (2,),
        ]

    def test_tables_insert_select(self, cluster):
        coord = cluster()
        coord.execute(
            "CREATE TABLE people (id bigint NOT NULL, name text, "
            "age int)"
        )
        coord.execute(
            "INSERT INTO people VALUES (1, 'ada', 36), (2, 'grace', NULL)"
        )
        coord.execute("INSERT INTO people (id, name) VALUES (3, 'alan')")
        res = coord.execute("SELECT id, name, age FROM people")
        assert res.rows == [
            (1, "ada", 36), (2, "grace", None), (3, "alan", None),
        ]

    def test_table_group_commit_joined_read(self, cluster):
        """Two tables share the timeline: a read after writes to both
        sees a consistent joint snapshot (txn-wal en-masse uppers)."""
        coord = cluster()
        coord.execute("CREATE TABLE a (k bigint NOT NULL, v bigint)")
        coord.execute("CREATE TABLE b (k bigint NOT NULL, w bigint)")
        coord.execute("INSERT INTO a VALUES (1, 10)")
        coord.execute("INSERT INTO b VALUES (1, 20)")
        res = coord.execute(
            "SELECT a.k, v, w FROM a, b WHERE a.k = b.k"
        )
        assert res.rows == [(1, 10, 20)]
        coord.execute(
            "CREATE MATERIALIZED VIEW joined AS "
            "SELECT a.k AS k, v, w FROM a, b WHERE a.k = b.k"
        )
        coord.execute("INSERT INTO a VALUES (2, 11)")
        coord.execute("INSERT INTO b VALUES (2, 21)")
        res = coord.execute("SELECT * FROM joined")
        assert sorted(res.rows) == [(1, 10, 20), (2, 11, 21)]

    def test_tables_survive_restart(self, cluster):
        coord = cluster()
        coord.execute("CREATE TABLE t (x bigint NOT NULL)")
        coord.execute("INSERT INTO t VALUES (7)")
        coord.shutdown()
        coord2 = cluster()
        coord2.execute("INSERT INTO t VALUES (8)")
        assert coord2.execute("SELECT x FROM t").rows == [(7,), (8,)]

    def test_select_sorts_nulls_first(self, cluster):
        coord = cluster()
        coord.execute("CREATE TABLE t (x int, y text)")
        coord.execute(
            "INSERT INTO t VALUES (2, 'b'), (NULL, 'a'), (1, NULL)"
        )
        res = coord.execute("SELECT x, y FROM t")
        assert res.rows == [(None, "a"), (1, None), (2, "b")]

    def test_mv_survives_empty_group_commit_advances(self, cluster):
        """Writes to table a advance table b's upper with EMPTY chunks;
        an MV over b must step through them (regression: arity-0 batch
        from a parts-free fetch killed the dataflow)."""
        coord = cluster()
        coord.execute("CREATE TABLE a (x bigint NOT NULL)")
        coord.execute("CREATE TABLE b (y bigint NOT NULL)")
        coord.execute("INSERT INTO b VALUES (5)")
        coord.execute(
            "CREATE MATERIALIZED VIEW mb AS SELECT count(*) FROM b"
        )
        for i in range(4):
            coord.execute(f"INSERT INTO a VALUES ({i})")
        assert coord.execute("SELECT * FROM mb").rows == [(1,)]
        assert not coord.controller.statuses, list(
            coord.controller.statuses
        )

    def test_subscribe_not_stale_after_restart(self, cluster):
        """A new coordinator's first SUBSCRIBE must not tail a durable
        sink shard left by a previous run's subscription."""
        coord = cluster()
        coord.execute("CREATE TABLE t (x bigint NOT NULL)")
        coord.execute("INSERT INTO t VALUES (100)")
        sub = coord.execute("SUBSCRIBE t").subscription
        events, _ = sub.poll(timeout=30)
        assert [(e[0], e[-1]) for e in events] == [(100, 1)]
        coord.shutdown()
        coord2 = cluster()
        coord2.execute("CREATE TABLE u (y bigint NOT NULL)")
        coord2.execute("INSERT INTO u VALUES (999)")
        sub2 = coord2.execute("SUBSCRIBE u").subscription
        events2, _ = sub2.poll(timeout=30)
        assert [(e[0], e[-1]) for e in events2] == [(999, 1)]
        sub2.close()

    def test_subscribe_snapshot_then_deltas(self, cluster):
        coord = cluster()
        coord.execute("CREATE SOURCE c FROM LOAD GENERATOR counter")
        res = coord.execute(
            "SUBSCRIBE TO (SELECT count(*) AS n FROM counter)"
        )
        assert res.kind == "subscription"
        sub = res.subscription
        events, frontier = sub.poll(timeout=30)
        # Snapshot: count = 1 (value 0 at t=0).
        assert [(e[0], e[-1]) for e in events] == [(1, 1)]
        coord.sources["c"].tick_once()
        events2, _ = sub.poll(timeout=30)
        # Delta: retract 1, assert 2.
        assert sorted((e[0], e[-1]) for e in events2) == [(1, -1), (2, 1)]
        sub.close()

    def test_tpch_q1_through_sql(self, cluster):
        coord = cluster()
        coord.execute(
            "CREATE SOURCE t FROM LOAD GENERATOR tpch "
            "(SCALE FACTOR 0.003, CHURN ORDERS 4)"
        )
        coord.execute(
            "CREATE MATERIALIZED VIEW q1 AS "
            "SELECT l_returnflag, l_linestatus, "
            "sum(l_quantity) AS sum_qty, count(*) AS count_order "
            "FROM lineitem WHERE l_shipdate <= 10000 "
            "GROUP BY l_returnflag, l_linestatus"
        )
        src = coord.sources["t"]
        src.tick_once()
        src.tick_once()
        res = coord.execute("SELECT * FROM q1")
        assert res.kind == "rows" and len(res.rows) >= 1
        # Oracle check: recompute from the durable lineitem shard.
        import numpy as np

        sh = coord.catalog.items["lineitem"].definition["shard"]
        reader = coord.persist.open_reader(sh, "test-oracle")
        _s, cols, _n, _t, diff = reader.snapshot(
            coord.persist.machine(sh).reload().upper - 1
        )
        li = coord.catalog.items["lineitem"].schema
        rf = li.index_of("l_returnflag")
        ls = li.index_of("l_linestatus")
        qty = li.index_of("l_quantity")
        sd = li.index_of("l_shipdate")
        from materialize_tpu.repr.schema import GLOBAL_DICT

        acc: dict = {}
        for i in range(len(diff)):
            if int(cols[sd][i]) > 10000:
                continue
            key = (
                GLOBAL_DICT.decode(int(cols[rf][i])),
                GLOBAL_DICT.decode(int(cols[ls][i])),
            )
            n, s = acc.get(key, (0, 0))
            acc[key] = (
                n + int(diff[i]),
                s + int(diff[i]) * int(cols[qty][i]),
            )
        import decimal

        # l_quantity is DECIMAL(_, 2): results surface as exact decimals
        expect = sorted(
            (k[0], k[1], decimal.Decimal(s) / 100, n)
            for k, (n, s) in acc.items()
            if n
        )
        assert sorted(res.rows) == expect
