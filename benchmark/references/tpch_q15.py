"""Plain reference for the ``q15`` materialized view (TPC-H Q15, top
supplier): numpy and Python integers, nothing of the program.

    revenue(supplier_no, total_revenue) = sum(l_extendedprice * (1 -
        l_discount)) per l_suppkey over 1996-01-01 <= l_shipdate <
        1996-04-01
    q15 = supplier joined to revenue where total_revenue = max(...)

``tables`` is the collection at ONE time: for each relation a dict of
numpy columns by name plus ``diff`` (the multiplicity of each row;
retractions are negative). Decimals are the stored unscaled integers
(scale 2 for price and discount), so ``total_revenue`` has scale 4 and
is exact in Python integers: the configuration's guarantee.

``precision="float32"`` is the CONTROL: the same query with the sums
accumulated in float32, the nearest precision below the exact decimals
the configuration states. The comparison has to refuse it.
"""

import datetime

import numpy as np

COLUMNS = ("s_suppkey", "s_name", "total_revenue")
DECIMAL_SCALE = {"total_revenue": 4}
_EPOCH = datetime.date(1970, 1, 1)
LO = (datetime.date(1996, 1, 1) - _EPOCH).days
HI = (datetime.date(1996, 4, 1) - _EPOCH).days


def answer(tables: dict, precision: str = "exact") -> list:
    """Sorted rows ``(s_suppkey, s_name, total_revenue_unscaled)``,
    one per copy (a multiset as a sorted list)."""
    li = tables["lineitem"]
    m = (li["l_shipdate"] >= LO) & (li["l_shipdate"] < HI)
    supp = li["l_suppkey"][m].astype(np.int64)
    d = li["diff"][m].astype(np.int64)
    price = li["l_extendedprice"][m].astype(np.int64)
    disc = li["l_discount"][m].astype(np.int64)
    if not len(supp):
        return []
    n_rows = np.zeros(int(supp.max()) + 1, np.int64)
    np.add.at(n_rows, supp, d)
    if precision == "exact":
        # scale 4; int64 holds it: checked, not assumed
        rev = price * (100 - disc) * d
        if float(np.abs(rev).astype(np.float64).sum()) >= 2.0 ** 62:
            raise OverflowError("q15 reference: revenue passes int64")
        totals = np.zeros_like(n_rows)
        np.add.at(totals, supp, rev)
    elif precision == "float32":
        f = np.float32
        rev = price.astype(f) * (f(100) - disc.astype(f)) * d.astype(f)
        totals = np.zeros(len(n_rows), f)
        np.add.at(totals, supp, rev)
        totals = np.rint(totals.astype(np.float64)).astype(np.int64)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    live = np.nonzero(n_rows > 0)[0]
    if not len(live):
        return []
    best = int(totals[live].max())
    winners = {int(k) for k in live if int(totals[k]) == best}
    su = tables["supplier"]
    out = []
    for key, name, dd in zip(
        su["s_suppkey"].tolist(), su["s_name"].tolist(), su["diff"].tolist()
    ):
        if key in winners and dd > 0:
            out.extend([(int(key), name, best)] * int(dd))
    return sorted(out)
