"""North-star TPCH workloads as MIR (BASELINE.json gate configs).

These are the maintained-view definitions the driver benchmarks: Q1 (pure
accumulable Reduce), Q15 (join + SUM + MAX), Q9 (6-relation delta join).
Reference analogs: the TPCH load-generator source
(src/storage/src/source/generator/tpch.rs) feeding indexed materialized
views rendered by compute/src/render.rs.
"""

from __future__ import annotations

from ..expr import relation as mir
from ..expr.relation import AggregateExpr, AggregateFunc
from ..expr.scalar import CallUnary, UnaryFunc, col, lit
from ..repr.schema import ColumnType
from ..storage.generator.tpch import (
    LINEITEM_SCHEMA,
    NATION_SCHEMA,
    ORDERS_SCHEMA,
    PART_SCHEMA,
    PARTSUPP_SCHEMA,
    SUPPLIER_SCHEMA,
)

# date '1998-12-01' - 90 days, as a day number since 1970-01-01
Q1_CUTOFF = 8035 + 2526 - 90


def q1_mir() -> mir.RelationExpr:
    """TPCH Q1: GROUP BY returnflag, linestatus with 4 sums + count(*).

    Averages derive from sums/counts in result finishing, as in the
    reference (RowSetFinishing applies post-aggregation arithmetic).
    Exercises ReducePlan::Accumulable (render/reduce.rs:1357).
    """
    sch = LINEITEM_SCHEMA
    i = sch.index_of
    one = lit(100, ColumnType.DECIMAL, 2)  # 1.00 at scale 2
    disc_price = col(i("l_extendedprice")) * (one - col(i("l_discount")))
    charge_rhs = one + col(i("l_tax"))
    return (
        mir.Get("lineitem", sch)
        .filter([col(i("l_shipdate")).lte(lit(Q1_CUTOFF, ColumnType.DATE))])
        .map([disc_price])  # -> col 13, scale 4
        .map([col(13) * charge_rhs])  # -> col 14, scale 6
        .project([i("l_returnflag"), i("l_linestatus"),
                  i("l_quantity"), i("l_extendedprice"), 13, 14])
        .reduce(
            (0, 1),
            (
                AggregateExpr(AggregateFunc.SUM_INT, col(2)),  # sum_qty
                AggregateExpr(AggregateFunc.SUM_INT, col(3)),  # sum_base
                AggregateExpr(AggregateFunc.SUM_INT, col(4)),  # sum_disc
                AggregateExpr(AggregateFunc.SUM_INT, col(5)),  # sum_charge
                AggregateExpr(AggregateFunc.COUNT, lit(True)),  # count(*)
            ),
        )
    )


# Q15 revenue window: [1996-01-01, 1996-04-01) as day numbers.
Q15_LO = 9496
Q15_HI = 9587


def q15_mir() -> mir.RelationExpr:
    """TPCH Q15: top supplier(s) by quarterly revenue.

    revenue(supplier_no, total_revenue) = GROUP BY over a shipdate
    window; result joins supplier with revenue and the GLOBAL MAX of
    total_revenue. Exercises Let sharing, accumulable Reduce, the
    global-aggregate (empty group key) hierarchical MAX, and a 3-input
    linear join (the reference plans this with JoinPlan + ReducePlan
    Hierarchical; render/reduce.rs:850, linear_join.rs:204).

    Output: (s_suppkey, s_name, total_revenue).
    """
    li = LINEITEM_SCHEMA
    i = li.index_of
    one = lit(100, ColumnType.DECIMAL, 2)  # 1.00
    revenue = (
        mir.Get("lineitem", li)
        .filter([
            col(i("l_shipdate")).gte(lit(Q15_LO, ColumnType.DATE)),
            col(i("l_shipdate")).lt(lit(Q15_HI, ColumnType.DATE)),
        ])
        .map([col(i("l_extendedprice")) * (one - col(i("l_discount")))])
        .project([i("l_suppkey"), 13])
        .reduce(
            (0,), (AggregateExpr(AggregateFunc.SUM_INT, col(1)),)
        )
    )  # schema: [l_suppkey, total_revenue]
    rev_schema = revenue.schema()
    rev = mir.Get("__revenue__", rev_schema)
    maxrev = rev.reduce(
        (), (AggregateExpr(AggregateFunc.MAX, col(1)),)
    )  # schema: [max_revenue]
    # global columns: supplier [0..2], revenue [3..4], maxrev [5]
    joined = mir.Join(
        (mir.Get("supplier", SUPPLIER_SCHEMA), rev, maxrev),
        equivalences=((col(0), col(3)), (col(4), col(5))),
    ).project([0, 2, 4])  # s_suppkey, s_name, total_revenue
    return mir.Let("__revenue__", revenue, joined)


def q9_mir() -> mir.RelationExpr:
    """TPCH Q9 (product-type profit): 6-relation delta join + GROUP BY.

    Exercises JoinPlan::Delta — one update pipeline per input over shared
    arrangements (render/join/delta_join.rs:51; BASELINE.json config 3).
    The reference's ``p_name LIKE '%green%'`` filter is omitted
    (dictionary-coded strings have no device substring search yet); the
    join/aggregate plan shape is identical.

    Output: (n_name, o_year, sum_profit scale-4 decimal).
    """
    li, pt, sp = LINEITEM_SCHEMA, PART_SCHEMA, SUPPLIER_SCHEMA
    ps, od, na = PARTSUPP_SCHEMA, ORDERS_SCHEMA, NATION_SCHEMA
    i = li.index_of
    # Global column offsets, from the schemas' lengths (a column
    # appended to one relation shifts every relation after it).
    inputs = (("lineitem", li), ("part", pt), ("supplier", sp),
              ("partsupp", ps), ("orders", od), ("nation", na))
    base, at = {}, 0
    for name, sch in inputs:
        base[name] = at
        at += sch.arity

    def g(name: str, column: str):
        return col(base[name] + dict(inputs)[name].index_of(column))

    joined = mir.Join(
        tuple(mir.Get(name, sch) for name, sch in inputs),
        equivalences=(
            (col(i("l_suppkey")), g("supplier", "s_suppkey"),
             g("partsupp", "ps_suppkey")),
            (col(i("l_partkey")), g("part", "p_partkey"),
             g("partsupp", "ps_partkey")),
            (col(i("l_orderkey")), g("orders", "o_orderkey")),
            (g("supplier", "s_nationkey"), g("nation", "n_nationkey")),
        ),
    )
    one = lit(100, ColumnType.DECIMAL, 2)  # 1.00
    amount = col(i("l_extendedprice")) * (one - col(i("l_discount"))) - g(
        "partsupp", "ps_supplycost"
    ) * col(i("l_quantity"))  # scale 4
    o_year = CallUnary(UnaryFunc.EXTRACT_YEAR, g("orders", "o_orderdate"))
    n_name = base["nation"] + na.index_of("n_name")
    return (
        joined.map([amount, o_year])  # -> cols `at`, `at + 1`
        .project([n_name, at + 1, at])  # n_name, o_year, amount
        .reduce(
            (0, 1), (AggregateExpr(AggregateFunc.SUM_INT, col(2)),)
        )
    )
