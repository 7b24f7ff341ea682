"""The base tables, made again from ``--seed`` with nothing of the
program: what the source shards must hold at a time.

The data of a run is made by the program's own load generator
(``materialize_tpu/storage/generator/tpch.py``) and reaches the
comparison through the program's source append, persist codec and
string dictionary. So that a fault in any of those does not pass on both
sides, this is a frozen copy of that generator's arithmetic as of PR 26
(numpy only): every field of every row is a pure function of (seed,
version, order, line), and a tick's orders are drawn from
``default_rng(seed * 31 + tick)``. The snapshot is written at time 0
with version 0; tick ``t`` (time ``t``, from 1) retracts the lineitems
of ``churn_orders`` orders and inserts them again at version
``1000 + t``.

A later PR that changes what the generator makes for the columns below
changes the benchmark's data and is refused by ``source_rows_wrong``;
columns it adds are not looked at.
"""

from __future__ import annotations

import numpy as np

EPOCH_1992 = 8035  # days from 1970-01-01 to 1992-01-01
DATE_RANGE = 2526  # 1992-01-01 .. 1998-12-01
TODAY = EPOCH_1992 + DATE_RANGE - 151
LINEITEM = (
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
    "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
)
SUPPLIER = ("s_suppkey", "s_nationkey", "s_name")
COLUMNS = {"lineitem": LINEITEM, "supplier": SUPPLIER}


def sizes(scale_factor: float) -> dict:
    return {
        "orders": max(int(1_500_000 * scale_factor), 16),
        "part": max(int(200_000 * scale_factor), 8),
        "supplier": max(int(10_000 * scale_factor), 4),
    }


def _mix64(*vals):
    with np.errstate(over="ignore"):
        h = np.uint64(0x9E3779B97F4A7C15)
        for v in vals:
            v = np.asarray(v, dtype=np.uint64)
            z = (h ^ v) + np.uint64(0x9E3779B97F4A7C15)
            z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            h = z ^ (z >> np.uint64(31))
    return h


def _draw(lo: int, hi: int, *keys):
    """Uniform integers in [lo, hi)."""
    return (_mix64(*keys) % np.uint64(hi - lo)).astype(np.int64) + lo


def lineitems(seed: int, n: dict, orderkeys, version: int) -> dict:
    """The lineitems of ``orderkeys`` at one churn version, by column."""
    orderkeys = np.asarray(orderkeys, np.int64)
    sd = np.uint64(seed * 1_000_003 + version)
    n_lines = _draw(1, 8, sd, orderkeys.astype(np.uint64), 11)
    okeys = np.repeat(orderkeys, n_lines)
    line = (
        np.arange(len(okeys))
        - np.repeat(np.cumsum(n_lines) - n_lines, n_lines)
    ).astype(np.int64) + 1
    u, li = okeys.astype(np.uint64), line.astype(np.uint64)
    partkey = _draw(1, n["part"] + 1, sd, u, li, 1)
    quantity = _draw(1, 51, sd, u, li, 3)
    retail = 90_000 + (partkey * 100) % 200_000 + (partkey % 1000) * 100
    orderdate = EPOCH_1992 + (okeys * 2654435761) % (DATE_RANGE - 151)
    shipdate = orderdate + _draw(1, 122, sd, u, li, 6)
    receiptdate = shipdate + _draw(1, 31, sd, u, li, 8)
    flags = np.array(["R", "A", "N"])
    return {
        "l_orderkey": okeys,
        "l_partkey": partkey,
        "l_suppkey": _draw(1, n["supplier"] + 1, sd, u, li, 2),
        "l_linenumber": line,
        "l_quantity": quantity * 100,  # scale 2
        "l_extendedprice": quantity * retail,
        "l_discount": _draw(0, 11, sd, u, li, 4),
        "l_tax": _draw(0, 9, sd, u, li, 5),
        "l_returnflag": np.where(
            receiptdate <= TODAY, flags[_draw(0, 2, sd, u, li, 9)], "N"
        ),
        "l_linestatus": np.where(shipdate > TODAY, "O", "F"),
        "l_shipdate": shipdate,
        "l_commitdate": orderdate + _draw(30, 91, sd, u, li, 7),
        "l_receiptdate": receiptdate,
    }


def supplier(seed: int, n: dict) -> dict:
    keys = np.arange(1, n["supplier"] + 1)
    rng = np.random.default_rng(seed + 7)
    return {
        "s_suppkey": keys,
        "s_nationkey": rng.integers(0, 25, size=len(keys)),
        "s_name": np.array([f"Supplier#{k:09d}" for k in keys]),
    }


def order_versions(seed: int, n: dict, churn_orders: int, time: int):
    """Each order's churn version once tick ``time`` has been applied."""
    version = np.zeros(n["orders"] + 1, np.int64)
    pool = np.arange(1, n["orders"] + 1)
    for tick in range(1, time + 1):
        drawn = np.random.default_rng(seed * 31 + tick).choice(
            pool, size=min(churn_orders, n["orders"]), replace=False
        )
        version[drawn] = 1000 + tick
    return version


def tables_at(seed: int, config: dict, time: int) -> dict:
    """``{relation: {column: array}}`` of the collections at ``time``
    under a configuration's ``scale_factor`` and ``churn_orders``, every
    row once."""
    n = sizes(config["scale_factor"])
    version = order_versions(seed, n, config["churn_orders"], time)
    parts = []
    for v in np.unique(version[1:]):
        keys = np.nonzero(version == v)[0]
        parts.append(lineitems(seed, n, keys[keys >= 1], int(v)))
    return {
        "lineitem": {
            c: np.concatenate([p[c] for p in parts]) for c in LINEITEM
        },
        "supplier": supplier(seed, n),
    }
