"""Plain reference for the ``q3`` materialized view (TPC-H Q3, shipping
priority, clause 2.4.3 with the validation parameters of 2.4.3.3):
numpy and Python integers, nothing of the program.

    q3 = the ten rows, by (revenue DESC, o_orderdate), of
        SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount))
               AS revenue, o_orderdate, o_shippriority
        FROM customer, orders, lineitem
        WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
          AND l_orderkey = o_orderkey AND o_orderdate < 1995-03-15
          AND l_shipdate > 1995-03-15
        GROUP BY l_orderkey, o_orderdate, o_shippriority

``tables`` is the collection at ONE time: for each relation a dict of
numpy columns by name plus ``diff`` (the multiplicity of each row;
retractions are negative). Decimals are the stored unscaled integers
(scale 2 for price and discount), so ``revenue`` has scale 4 and is
exact in Python integers: the configuration's guarantee.

Ties. TPC-H leaves the order of rows that agree on (revenue,
o_orderdate) open; a tie at rank 10 changes which rows the view holds.
The program's top-k (``ops/topk.py``) keeps its input sorted by the
ORDER BY lanes and then by every column of its input in select-list
order, ascending, so among such rows the smaller ``l_orderkey`` ranks
first (it is unique a group, so nothing after it decides). The
reference does the same; the configuration states it under ``assumed``.

``precision="float32"`` is the CONTROL: the same query with revenue
accumulated in float32, the nearest precision below the exact decimals
the configuration states. The comparison has to refuse it.
"""

import datetime

import numpy as np

COLUMNS = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
DECIMAL_SCALE = {"revenue": 4}
SEGMENT = "BUILDING"
DATE = (datetime.date(1995, 3, 15) - datetime.date(1970, 1, 1)).days
LIMIT = 10


def answer(tables: dict, precision: str = "exact") -> list:
    """Sorted rows ``(l_orderkey, revenue_unscaled, o_orderdate,
    o_shippriority)``, one per copy (a multiset as a sorted list)."""
    if precision not in ("exact", "float32"):
        raise ValueError(f"unknown precision {precision!r}")
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    # customers of the segment, by key, with their multiplicity
    in_segment: dict = {}
    m = np.asarray(cu["c_mktsegment"]) == SEGMENT
    for key, d in zip(cu["c_custkey"][m].tolist(), cu["diff"][m].tolist()):
        in_segment[key] = in_segment.get(key, 0) + d
    # qualifying orders: (o_orderkey, o_orderdate, o_shippriority) with
    # the multiplicity of customer x orders
    orders: dict = {}
    m = np.asarray(od["o_orderdate"]) < DATE
    for key, cust, date, prio, d in zip(
        od["o_orderkey"][m].tolist(), od["o_custkey"][m].tolist(),
        od["o_orderdate"][m].tolist(), od["o_shippriority"][m].tolist(),
        od["diff"][m].tolist(),
    ):
        w = d * in_segment.get(cust, 0)
        if w:
            g = (key, date, prio)
            orders[g] = orders.get(g, 0) + w
    # lineitems shipped after the date, summed per order
    m = np.asarray(li["l_shipdate"]) > DATE
    okey = li["l_orderkey"][m].astype(np.int64)
    d = li["diff"][m].astype(np.int64)
    price = li["l_extendedprice"][m].astype(np.int64)
    disc = li["l_discount"][m].astype(np.int64)
    if not len(okey):
        return []
    size = int(okey.max()) + 1
    n_rows = np.zeros(size, np.int64)
    np.add.at(n_rows, okey, d)
    if precision == "exact":
        # scale 4; int64 holds it: checked, not assumed
        rev = price * (100 - disc) * d
        if float(np.abs(rev).astype(np.float64).sum()) >= 2.0 ** 62:
            raise OverflowError("q3 reference: revenue passes int64")
        totals = np.zeros(size, np.int64)
        np.add.at(totals, okey, rev)
    else:
        f = np.float32
        rev = price.astype(f) * (f(100) - disc.astype(f)) * d.astype(f)
        totals = np.zeros(size, f)
        np.add.at(totals, okey, rev)
        totals = np.rint(totals.astype(np.float64)).astype(np.int64)
    groups = []
    for (key, date, prio), w in orders.items():
        if key < size and w * int(n_rows[key]) > 0:
            groups.append((key, w * int(totals[key]), date, prio))
    # revenue DESC, o_orderdate, then the program's tie rule: the
    # remaining columns in select-list order, l_orderkey first
    groups.sort(key=lambda r: (-r[1], r[2], r[0], r[3]))
    return sorted(groups[:LIMIT])
