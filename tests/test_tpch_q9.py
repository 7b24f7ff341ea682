"""TPC-H Q9 as a maintained view on the normal path: its text as SQL
through ``Coordinator.execute`` over ``LOAD GENERATOR tpch`` with
``SPEC PARTS``, held to the benchmark's plain reference
(``benchmark/references/tpch_q9.py`` over the base tables that
``tpch_tables_q9.py`` makes again from the seed: nothing of the program)
at every tick, while a (nation, year) group vanishes and another
appears under the refresh pairs. Then the generator's option: with it,
TPC-H clause 4.2.3's part names and part-supplier rules and every
table's full row; without it, the tables the other configurations were
measured on.

One module fixture pays the view's compiles once (about two minutes on
the CPU), in a process of its own; every test of the view reads what it
recorded.
"""

import decimal
import importlib.util
import multiprocessing
import os
import socket
import threading
from concurrent.futures import ProcessPoolExecutor

import numpy as np
import pytest

from materialize_tpu.coord.coordinator import Coordinator
from materialize_tpu.coord.protocol import PersistLocation
from materialize_tpu.coord.replica import serve_forever
from materialize_tpu.repr.schema import StringDictionary
from materialize_tpu.storage.generator import tpch as gen_mod
from materialize_tpu.storage.persist import operators as persist_ops
from materialize_tpu.storage.persist import (
    FileBlob,
    PersistClient,
    SqliteConsensus,
)
from materialize_tpu.utils.compile_ledger import LEDGER
from materialize_tpu.utils.metrics import REGISTRY
from materialize_tpu.utils.trace import TRACER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Chosen by running the reference alone over seeds 1..300: tick 4
# takes the last green lineitem of one (nation, year) out, tick 6
# brings the first of another in.
SEED, TICKS = 125, 6
# The source is this old when the view is installed, so that the view
# hydrates from a snapshot (a view as old as its sources replays them).
AGED = 2
CONFIG = {"scale_factor": 0.002, "churn_orders": 4}
Q9 = (
    "CREATE MATERIALIZED VIEW q9 AS SELECT nation, o_year, "
    "sum(amount) AS sum_profit FROM (SELECT n_name AS nation, "
    "extract(year FROM o_orderdate) AS o_year, "
    "l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity "
    "AS amount FROM part, supplier, lineitem, partsupp, orders, nation "
    "WHERE s_suppkey = l_suppkey AND ps_suppkey = l_suppkey "
    "AND ps_partkey = l_partkey AND p_partkey = l_partkey "
    "AND o_orderkey = l_orderkey AND s_nationkey = n_nationkey "
    "AND p_name LIKE '%green%') AS profit GROUP BY nation, o_year "
    "ORDER BY nation, o_year DESC"
)


def _reference(name: str):
    path = os.path.join(REPO, "benchmark", "references", name + ".py")
    spec = importlib.util.spec_from_file_location("ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("tpch_q9")
TABLES = _reference("tpch_tables_q9")


def _plain(rows) -> list:
    """Rows as ``Coordinator.execute`` hands them out -> the
    reference's form (the sum unscaled at scale 4)."""
    return sorted(
        (
            nation, int(year),
            int(decimal.Decimal(profit).scaleb(
                REF.DECIMAL_SCALE["sum_profit"]
            )),
        )
        for nation, year, profit in rows
    )


def _expected(tick: int, precision: str = "exact") -> list:
    tables = TABLES.tables_at(SEED, CONFIG, tick)
    for t in tables.values():
        t["diff"] = np.ones(len(next(iter(t.values()))), np.int64)
    return REF.answer(tables, precision)


def _install_and_tick(tmp: str) -> dict:
    """A fresh install of the view on a replica of this process: its
    answer after every tick, the step programs and overflow regrows of
    its hydration and of its ticks, its ``span`` records' attributes, and
    the string columns of each input that its imports leave undecoded.
    The ``q9_run`` fixture runs it in a process of its own: every comment
    the generator makes stays in the dictionary of the process that made
    it, where each later string function's side-table would cover it."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    loc = PersistLocation(
        os.path.join(tmp, "blob"), os.path.join(tmp, "consensus.db")
    )
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ready = threading.Event()
    threading.Thread(
        target=serve_forever, args=(port, loc, "r0", ready), daemon=True,
    ).start()
    assert ready.wait(10)
    coord = Coordinator(
        PersistClient(
            FileBlob(loc.blob_root), SqliteConsensus(loc.consensus_path)
        ),
        tick_interval=None,  # manual ticks: deterministic
    )
    coord.add_replica("r0", ("127.0.0.1", port))
    regrows = REGISTRY.get_or_create(
        "counter", "mz_overflow_regrows_total"
    )

    def programs(before: list) -> list:
        # by identity, not by index: the ledger's two bounded deques
        # are full after a long run of other tests in this process
        old = {id(r) for r in before}
        return [
            r.tier for r in LEDGER.records()
            if id(r) not in old and r.name == "q9"
            and r.kind.startswith("step")
        ]

    skipped: dict = {}
    unread = persist_ops.unread_string_columns

    def spy(expr, name, schema):
        out = unread(expr, name, schema)
        skipped[name] = {schema.columns[i].name for i in out}
        return out

    saved = TRACER.level
    TRACER.set_level("info")
    TRACER.clear()  # what other tests of this process recorded
    persist_ops.unread_string_columns = spy
    try:
        coord.execute(
            "CREATE SOURCE t FROM LOAD GENERATOR tpch (SCALE FACTOR "
            f"{CONFIG['scale_factor']}, SEED {SEED}, CHURN ORDERS "
            f"{CONFIG['churn_orders']}, SPEC PARTS)"
        )
        for _ in range(AGED):
            coord.sources["t"].tick_once()
        mark, grown = LEDGER.records(), regrows.value
        coord.execute(Q9)
        answers = {AGED: _plain(coord.execute("SELECT * FROM q9").rows)}
        hydration = {
            "programs": programs(mark), "regrows": regrows.value - grown,
        }
        mark, grown = LEDGER.records(), regrows.value
        for tick in range(AGED + 1, TICKS + 1):
            coord.sources["t"].tick_once()
            # a read without AS OF is served at the newest complete
            # time of the sources: this tick's
            answers[tick] = _plain(coord.execute("SELECT * FROM q9").rows)
        ticks = {
            "programs": programs(mark), "regrows": regrows.value - grown,
        }
        records = TRACER.records()
    finally:
        persist_ops.unread_string_columns = unread
        TRACER.set_level(saved)
        coord.shutdown()
    return {
        "answers": answers, "hydration": hydration, "ticks": ticks,
        "skipped": skipped,
        "spans": [
            dict(r.attrs) for r in records
            if r.name == "span" and r.attrs["dataflow"] == "q9"
        ],
    }


@pytest.fixture(scope="module")
def q9_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("q9")
    spawn = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(1, mp_context=spawn) as pool:
        return pool.submit(_install_and_tick, str(tmp)).result(timeout=900)


@pytest.fixture(autouse=True)
def own_dictionary(monkeypatch):
    """The generator's strings go into a dictionary of the test's own,
    for the reason ``_install_and_tick`` gives."""
    monkeypatch.setattr(gen_mod, "GLOBAL_DICT", StringDictionary())
    return gen_mod.GLOBAL_DICT


def test_view_is_the_reference_at_every_tick(q9_run):
    assert sorted(q9_run["answers"]) == list(range(AGED, TICKS + 1))
    for tick, got in q9_run["answers"].items():
        want = _expected(tick)
        assert len(want) > 80  # never an empty reference
        assert got == want, f"tick {tick}"


def test_a_group_vanishes_and_another_appears(q9_run):
    groups = {
        t: {(r[0], r[1]) for r in rows}
        for t, rows in q9_run["answers"].items()
    }
    changed = {
        t: (groups[t] - groups[t - 1], groups[t - 1] - groups[t])
        for t in range(AGED + 1, TICKS + 1)
        if groups[t] != groups[t - 1]
    }
    assert sorted(changed) == [4, 6]
    came, went = changed[4]
    assert not came and len(went) == 1
    came, went = changed[6]
    assert len(came) == 1 and not went


def test_float32_control_differs(q9_run):
    differs = [
        t for t, got in q9_run["answers"].items()
        if _expected(t, "float32") != got
    ]
    assert differs, "the float32 control passes for the exact view"


def test_fresh_install_compiles_one_hydration_program(q9_run):
    """The nation pipeline of the hydration step matches every
    lineitem (nation -> supplier -> lineitem, each on a foreign key):
    the join site starts at lineitem's snapshot tier, which reaches the
    join unfiltered, so the one step overflows nothing. From
    ``out_delta_cap`` (4,096) the ladder compiled it three times here
    and six at the benchmark's scale."""
    assert len(q9_run["hydration"]["programs"]) == 1
    assert q9_run["hydration"]["regrows"] == 0
    assert len(q9_run["ticks"]["programs"]) == 1
    assert q9_run["ticks"]["regrows"] == 0


def test_span_records_count_the_delta_pipelines(q9_run):
    """Six inputs, six delta pipelines a step; only lineitem ticks, so
    one of them is fed a tick. This replica shares the process's
    dictionary with the generator, whose lineitem comments grow it every
    tick: each one-tick span builds the LIKE side-table again. A replica
    in a process of its own never decodes a comment (the next test) and
    builds none after hydration."""
    spans = q9_run["spans"]
    assert sum(s["ticks"] for s in spans) == TICKS - AGED
    for s in spans:
        assert s["delta_pipelines"] == 6 * s["ticks"]
        assert s["fed_delta_pipelines"] == s["ticks"]
        assert s["string_tables_built"] == s["ticks"], s


def test_imports_leave_the_text_the_view_never_reads(q9_run):
    """Projection pushdown leaves each input under a Project: what it
    drops is imported as code 0, so TPC-H's comments never reach the
    replica's dictionary, while the part names that the LIKE reads and
    the nation names that the view outputs do."""
    skipped = q9_run["skipped"]
    assert set(skipped) == {
        "lineitem", "orders", "part", "partsupp", "supplier", "nation",
    }
    assert {"l_comment", "l_shipinstruct", "l_shipmode"} <= skipped["lineitem"]
    assert {"o_comment", "o_clerk"} <= skipped["orders"]
    assert "p_name" not in skipped["part"]
    assert {"p_comment", "p_type", "p_container"} <= skipped["part"]
    assert "ps_comment" in skipped["partsupp"]
    assert {"s_comment", "s_address", "s_name"} <= skipped["supplier"]
    assert skipped["nation"] == {"n_comment"}


# -- the generator's SPEC PARTS ---------------------------------------------


def _generator(sf: float, spec: bool):
    return gen_mod.TpchGenerator(sf=sf, seed=7, spec_parts=spec)


def _lineitem_pairs(g):
    li = g.lineitems_for_orders(np.arange(1, g.n_orders + 1))
    return set(zip(li[1].tolist(), li[2].tolist()))


@pytest.mark.parametrize("sf", [0.002, 0.014])
def test_every_lineitem_matches_a_partsupp_row(sf):
    g = _generator(sf, True)
    ps = g.partsupp_table()
    pairs = set(zip(ps[0].tolist(), ps[1].tolist()))
    assert _lineitem_pairs(g) <= pairs
    # and churned versions too
    li = g.lineitems_for_orders(np.arange(1, 500), version=1007)
    assert set(zip(li[1].tolist(), li[2].tolist())) <= pairs
    # each i in 0..3 is drawn: a part's lineitems reach all four
    assert len(_lineitem_pairs(g)) > 3 * g.n_part


def test_each_part_has_four_distinct_suppliers():
    g = _generator(0.014, True)
    pk, sk = g.partsupp_table()[:2]
    assert (np.bincount(pk)[1:] == 4).all()
    assert len(set(zip(pk.tolist(), sk.tolist()))) == 4 * g.n_part
    assert sk.min() >= 1 and sk.max() <= g.n_supplier
    # clause 4.2.3's formula repeats a supplier at S = 20 (SF 0.002)
    # for the parts whose step S/4 + (p-1)/S is 10 (two suppliers
    # left) or 20 (one): twenty parts each of the 400
    pk, sk = _generator(0.002, True).partsupp_table()[:2]
    assert len(set(zip(pk.tolist(), sk.tolist()))) == 1600 - 20 * 2 - 20 * 3


def test_part_names_are_five_distinct_colour_words():
    g = _generator(0.014, True)
    names = gen_mod.GLOBAL_DICT.decode_many(g.part_table()[1])
    colours = set(gen_mod._COLORS)
    assert len(gen_mod._COLORS) == len(colours) == 92
    for name in names:
        words = name.split(" ")
        assert len(words) == len(set(words)) == 5 and set(words) <= colours
    green = sum("green" in n for n in names) / len(names)
    assert abs(green - 5 / 92) < 0.02
    # a pure function of (seed, p_partkey)
    assert gen_mod.GLOBAL_DICT.decode_many(
        _generator(0.014, True).part_table()[1]
    ) == names


def _lineitem_after(g, churn_orders: int, ticks: int) -> dict:
    """The lineitem source's rows after ``ticks`` ticks of churn, as
    ``{row: count}`` (codes for strings); no count ever goes below 0."""
    live: dict = {}
    batches = [(0, b) for b in g.snapshot_lineitem_batches()] + [
        (t, g.churn_lineitem_batch(churn_orders, t, t))
        for t in range(1, ticks + 1)
    ]
    for tick, b in batches:
        n = int(b.count)
        rows = zip(*[np.asarray(c)[:n].tolist() for c in b.cols])
        for row, d in zip(rows, np.asarray(b.diff)[:n].tolist()):
            live[row] = live.get(row, 0) + d
            assert live[row] >= 0, (tick, row)
    return live


# TPC-H clause 1.4.1's columns of each table, and clause 4.2.2.10's
# bounds of its text strings
_WIDTHS = {"lineitem": 16, "orders": 9, "part": 9, "partsupp": 5,
           "supplier": 7, "customer": 8, "nation": 4, "region": 3}
_TEXTS = {"l_comment": (10, 43), "o_comment": (19, 78),
          "p_comment": (5, 22), "ps_comment": (49, 198),
          "s_comment": (25, 100), "c_comment": (29, 116),
          "n_comment": (31, 114), "r_comment": (31, 115)}


def test_every_table_has_tpch_columns_and_text_lengths():
    g = _generator(0.002, True)
    made = {
        "lineitem": g.lineitems_for_orders(np.arange(1, g.n_orders + 1)),
        "orders": g.orders_rows(np.arange(1, g.n_orders + 1)),
        "part": g.part_table(), "partsupp": g.partsupp_table(),
        "supplier": g.supplier_table(), "customer": g.customer_table(),
        "nation": g.nation_table(), "region": g.region_table(),
    }
    for rel, cols in made.items():
        schema = gen_mod.SPEC_SCHEMAS[rel]
        assert schema.arity == len(cols) == _WIDTHS[rel], rel
        # the columns the other configurations read keep their places
        narrow = _generator(0.002, False).schema(rel)
        assert schema.names[:narrow.arity] == narrow.names
        for c, a in zip(schema.columns, cols):
            if c.name not in _TEXTS:
                continue
            lo, hi = _TEXTS[c.name]
            lengths = [len(t) for t in gen_mod.GLOBAL_DICT.decode_many(a)]
            assert lo <= min(lengths) and max(lengths) <= hi, c.name
            if len(a) > 1000:  # distinct in practice, as dbgen's are
                assert len(set(a.tolist())) > 0.99 * len(a), c.name
    phones = gen_mod.GLOBAL_DICT.decode_many(made["supplier"][4])
    for phone, nation in zip(phones, made["supplier"][1].tolist()):
        cc, a, b, c = phone.split("-")
        assert int(cc) == nation + 10 and len(a + b + c) == 10
    assert set(gen_mod.GLOBAL_DICT.decode_many(made["lineitem"][14])) <= {
        "REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"
    }
    words = set(" ".join(
        w for w in gen_mod.text_pool().split()
    ).replace(",", " ").split())
    assert "requests" in words and "special" in words


def test_churn_retracts_exactly_what_it_inserted():
    live = _lineitem_after(_generator(0.002, True), 4, 29)
    assert set(live.values()) <= {0, 1}


@pytest.mark.parametrize("sf", [0.002, 0.014])
def test_the_frozen_copy_agrees_with_the_generator(sf):
    """What the benchmark holds the source shards to is what the
    generator makes, column for column, churned lineitems included."""
    g = _generator(sf, True)
    frozen = TABLES.tables_at(7, {"scale_factor": sf, "churn_orders": 4}, 0)
    keys = np.arange(1, g.n_orders + 1)
    for rel, cols in (
        ("part", g.part_table()),
        ("partsupp", g.partsupp_table()),
        ("supplier", g.supplier_table()),
        ("nation", g.nation_table()),
        ("orders", g.orders_rows(keys)),
        ("lineitem", g.lineitems_for_orders(keys)),
    ):
        schema = gen_mod.SPEC_SCHEMAS[rel]
        assert tuple(frozen[rel]) == schema.names, rel
        for c, a in zip(schema.columns, cols):
            if c.ctype.value == "string":
                a = np.array(gen_mod.GLOBAL_DICT.decode_many(a))
            assert (np.asarray(frozen[rel][c.name]) == a).all(), (rel, c)
    # a churned version: l_suppkey is drawn from (seed, version, ...)
    li = g.lineitems_for_orders(np.arange(1, 300), version=1005)
    ref = TABLES.base.lineitems(7, TABLES.base.sizes(sf),
                                np.arange(1, 300), 1005)
    assert (
        np.array(gen_mod.GLOBAL_DICT.decode_many(li[15]))
        == TABLES.texts(
            10, 43, 15, np.uint64(7 * 1_000_003 + 1005),
            ref["l_orderkey"].astype(np.uint64),
            ref["l_linenumber"].astype(np.uint64),
        )
    ).all()
    i = TABLES.base._draw(
        0, 4, np.uint64(7 * 1_000_003 + 1005),
        ref["l_orderkey"].astype(np.uint64),
        ref["l_linenumber"].astype(np.uint64), 12,
    )
    assert (li[2] == TABLES.suppkey(ref["l_partkey"], i, g.n_supplier)).all()


@pytest.mark.parametrize("config", [
    {"scale_factor": 0.008, "churn_orders": 12},  # tpch_q15's
    {"scale_factor": 0.014, "churn_orders": 21},  # tpch_q3's
])
def test_without_the_option_the_tables_are_the_measured_ones(config):
    """Unset, the generator makes what ``tpch_tables.py`` and
    ``tpch_tables_q3.py`` (frozen before the option) hold, and its
    part names stay the 92 ``part k`` strings."""
    q3 = TABLES.q3
    g = gen_mod.TpchGenerator(sf=config["scale_factor"], seed=11)
    frozen = q3.tables_at(11, config, 3)
    frozen["supplier"] = TABLES.base.tables_at(11, config, 3)["supplier"]
    adapter_keys = np.arange(1, g.n_orders + 1)
    made = {
        "supplier": (gen_mod.SUPPLIER_SCHEMA, g.supplier_table()),
        "customer": (gen_mod.CUSTOMER_SCHEMA, g.customer_table()),
        "orders": (gen_mod.ORDERS_SCHEMA, g.orders_rows(adapter_keys)),
    }
    for rel, (schema, cols) in made.items():
        for c, a in zip(schema.columns, cols):
            if c.name not in frozen[rel]:
                continue
            if c.ctype.value == "string":
                a = np.array(gen_mod.GLOBAL_DICT.decode_many(a))
            assert (np.asarray(frozen[rel][c.name]) == a).all(), (rel, c)
    # lineitem through three ticks of churn, as the source holds it
    live = _lineitem_after(g, config["churn_orders"], 3)
    flag = {v: k for k, v in zip(
        ["R", "A", "N"], gen_mod.GLOBAL_DICT.encode_many(["R", "A", "N"])
    )}
    status = {v: k for k, v in zip(
        ["F", "O"], gen_mod.GLOBAL_DICT.encode_many(["F", "O"])
    )}
    got = sorted(
        row[:8] + (flag[row[8]], status[row[9]]) + row[10:]
        for row, d in live.items() if d
    )
    li = frozen["lineitem"]
    want = sorted(zip(*[np.asarray(li[c]).tolist() for c in li]))
    assert got == want
    names = set(gen_mod.GLOBAL_DICT.decode_many(g.part_table()[1]))
    assert names == {f"part {k}" for k in range(92)}
