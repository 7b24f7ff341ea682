"""Plain reference for the ``q9`` materialized view (TPC-H Q9, product
type profit measure, clause 2.4.9 with the validation parameter of
2.4.9.3, COLOR = green): numpy and Python integers, nothing of the
program.

    q9 = SELECT nation, o_year, sum(amount) AS sum_profit
         FROM (SELECT n_name AS nation,
                      extract(year FROM o_orderdate) AS o_year,
                      l_extendedprice * (1 - l_discount)
                        - ps_supplycost * l_quantity AS amount
               FROM part, supplier, lineitem, partsupp, orders, nation
               WHERE s_suppkey = l_suppkey AND ps_suppkey = l_suppkey
                 AND ps_partkey = l_partkey AND p_partkey = l_partkey
                 AND o_orderkey = l_orderkey
                 AND s_nationkey = n_nationkey
                 AND p_name LIKE '%green%') AS profit
         GROUP BY nation, o_year

(the query's ORDER BY nation, o_year DESC orders the output of a read;
a view holds no order).

``tables`` is the collection at ONE time: for each relation a dict of
numpy columns by name plus ``diff`` (the multiplicity of each row;
retractions are negative). Decimals are the stored unscaled integers
(scale 2 for price, discount, quantity and supply cost), so ``amount``
and ``sum_profit`` have scale 4 and are exact in Python integers: the
configuration's guarantee. Every relation is taken as a multiset: a
partsupp key held twice (TPC-H's formula repeats a supplier at a few
small scale factors) joins twice.

``precision="float32"`` is the CONTROL: the same query with the amounts
and their sums in float32, the nearest precision below the exact
decimals the configuration states. The comparison has to refuse it.
"""

import numpy as np

COLUMNS = ("nation", "o_year", "sum_profit")
DECIMAL_SCALE = {"sum_profit": 4}
COLOR = "green"


def _years(days) -> np.ndarray:
    return (
        np.asarray(days, np.int64).astype("datetime64[D]")
        .astype("datetime64[Y]").astype(np.int64) + 1970
    )


def answer(tables: dict, precision: str = "exact") -> list:
    """Sorted rows ``(nation, o_year, sum_profit_unscaled)``, one a
    group that holds rows."""
    if precision not in ("exact", "float32"):
        raise ValueError(f"unknown precision {precision!r}")
    li, od = tables["lineitem"], tables["orders"]
    pa, ps = tables["part"], tables["partsupp"]
    su, na = tables["supplier"], tables["nation"]
    pk = np.asarray(li["l_partkey"], np.int64)
    sk = np.asarray(li["l_suppkey"], np.int64)
    ok = np.asarray(li["l_orderkey"], np.int64)
    if not len(pk):
        return []
    n_part = int(max(pk.max(), np.max(pa["p_partkey"], initial=0))) + 1
    n_supp = int(max(sk.max(), np.max(su["s_suppkey"], initial=0),
                     np.max(ps["ps_suppkey"], initial=0))) + 1
    n_order = int(max(ok.max(), np.max(od["o_orderkey"], initial=0))) + 1
    # green parts: multiplicity by key
    green = np.zeros(n_part, np.int64)
    m = np.char.find(np.asarray(pa["p_name"]).astype(str), COLOR) >= 0
    np.add.at(green, np.asarray(pa["p_partkey"], np.int64)[m],
              np.asarray(pa["diff"], np.int64)[m])
    # partsupp by (partkey, suppkey): W = multiplicity, C = sum of
    # multiplicity x supplycost; a lineitem's amounts over its matches
    # sum to price x (1 - disc) x W - quantity x C
    key = (np.asarray(ps["ps_partkey"], np.int64) * n_supp
           + np.asarray(ps["ps_suppkey"], np.int64))
    d = np.asarray(ps["diff"], np.int64)
    w = np.zeros(n_part * n_supp, np.int64)
    c = np.zeros(n_part * n_supp, np.int64)
    np.add.at(w, key, d)
    np.add.at(c, key, d * np.asarray(ps["ps_supplycost"], np.int64))
    # supplier x nation: multiplicity by (suppkey, nation name)
    names = sorted(set(np.asarray(na["n_name"]).astype(str).tolist()))
    by_key: dict = {}
    for nk, name, dn in zip(np.asarray(na["n_nationkey"]).tolist(),
                            np.asarray(na["n_name"]).astype(str).tolist(),
                            np.asarray(na["diff"]).tolist()):
        by_key.setdefault(nk, []).append((names.index(name), dn))
    sn = np.zeros((n_supp, len(names)), np.int64)
    for s, nk, ds in zip(np.asarray(su["s_suppkey"]).tolist(),
                         np.asarray(su["s_nationkey"]).tolist(),
                         np.asarray(su["diff"]).tolist()):
        for j, dn in by_key.get(nk, ()):
            sn[s, j] += ds * dn
    # orders: multiplicity by (orderkey, year)
    oyear = _years(od["o_orderdate"])
    years = sorted(set(oyear.tolist()))
    oy = np.zeros((n_order, max(len(years), 1)), np.int64)
    np.add.at(oy, (np.asarray(od["o_orderkey"], np.int64),
                   np.searchsorted(years, oyear)),
              np.asarray(od["diff"], np.int64))
    # the lineitems that reach a group
    lkey = pk * n_supp + sk
    weight = np.asarray(li["diff"], np.int64) * green[pk]
    r = np.nonzero((weight != 0) & (w[lkey] != 0))[0]
    weight, lkey, sk, ok = weight[r], lkey[r], sk[r], ok[r]
    price = np.asarray(li["l_extendedprice"], np.int64)[r]
    disc = np.asarray(li["l_discount"], np.int64)[r]
    qty = np.asarray(li["l_quantity"], np.int64)[r]
    count = weight * w[lkey]
    if precision == "exact":
        # scale 4; int64 holds it: checked, not assumed
        amount = weight * (price * (100 - disc) * w[lkey] - qty * c[lkey])
        if float(np.abs(amount).astype(np.float64).sum()) * max(
            int(np.abs(sn).max(initial=0)), 1
        ) * max(int(np.abs(oy).max(initial=0)), 1) >= 2.0 ** 62:
            raise OverflowError("q9 reference: sum_profit passes int64")
        sums = np.einsum("r,rn,ry->ny", amount, sn[sk], oy[ok])
    else:
        f = np.float32
        amount = weight.astype(f) * (
            price.astype(f) * (f(100) - disc.astype(f)) * w[lkey].astype(f)
            - qty.astype(f) * c[lkey].astype(f)
        )
        sums = np.zeros((len(names), len(years)), f)
        for j in range(len(names)):
            for y in range(len(years)):
                # sequential float32 accumulation, as a float32 SUM would
                np.add.at(
                    sums[j], np.full(len(amount), y),
                    amount * sn[sk, j].astype(f) * oy[ok, y].astype(f),
                )
        sums = np.rint(sums.astype(np.float64)).astype(np.int64)
    counts = np.einsum("r,rn,ry->ny", count, sn[sk], oy[ok])
    return sorted(
        (names[j], years[y], int(sums[j, y]))
        for j, y in zip(*np.nonzero(counts > 0))
    )
