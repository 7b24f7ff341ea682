"""The base tables of the ``tpch_q3`` configuration, made again from
``--seed`` with nothing of the program: ``lineitem``, ``orders`` and
``customer`` as the source shards must hold them at a time.

``lineitem`` (the one relation that ticks) is ``tpch_tables.py``'s, with
its RF1/RF2 order versions. ``orders`` and ``customer`` never tick; they
are a frozen copy of the generator's arithmetic as of PR 29
(``materialize_tpu/storage/generator/tpch.py`` ``orders_rows`` and
``customer_table``), with the two columns that PR added for Q3:
``o_shippriority`` (TPC-H clause 4.2.3: 0) and ``c_mktsegment`` (clause
4.2.3: one of five segments, uniform; here a counter hash of (seed,
c_custkey)). A later PR that changes what the generator makes for these
columns changes the benchmark's data and is refused by
``source_rows_wrong``; columns it adds are not looked at.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "reference_tpch_tables",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "tpch_tables.py"),
)
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

STATUS = np.array(["F", "O"])
PRIORITIES = np.array(
    ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
)
SEGMENTS = np.array(
    ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
)


def n_customers(scale_factor: float) -> int:
    return max(int(150_000 * scale_factor), 8)


def orders(seed: int, n_orders: int, n_customer: int) -> dict:
    keys = np.arange(1, n_orders + 1)
    sd, u = np.uint64(seed * 1_000_003), keys.astype(np.uint64)
    return {
        "o_orderkey": keys,
        "o_custkey": base._draw(1, n_customer + 1, sd, u, 21),
        "o_orderstatus": STATUS[base._draw(0, 2, sd, u, 22)],
        "o_totalprice": base._draw(1_000_00, 500_000_00, sd, u, 23),
        "o_orderdate": base.EPOCH_1992
        + (keys * 2654435761) % (base.DATE_RANGE - 151),
        "o_orderpriority": PRIORITIES[base._draw(0, 5, sd, u, 24)],
        "o_shippriority": np.zeros(n_orders, np.int64),
    }


def customer(seed: int, n_customer: int) -> dict:
    keys = np.arange(1, n_customer + 1)
    rng = np.random.default_rng(seed + 13)
    return {
        "c_custkey": keys,
        "c_nationkey": rng.integers(0, 25, size=len(keys)),
        "c_name": np.array([f"Customer#{k:09d}" for k in keys]),
        "c_mktsegment": SEGMENTS[
            base._draw(
                0, len(SEGMENTS), np.uint64(seed * 1_000_003),
                keys.astype(np.uint64), 31,
            )
        ],
    }


def tables_at(seed: int, config: dict, time: int) -> dict:
    """``{relation: {column: array}}`` of the collections at ``time``
    under a configuration's ``scale_factor`` and ``churn_orders``, every
    row once."""
    n = base.sizes(config["scale_factor"])
    n_customer = n_customers(config["scale_factor"])
    return {
        "lineitem": base.tables_at(seed, config, time)["lineitem"],
        "orders": orders(seed, n["orders"], n_customer),
        "customer": customer(seed, n_customer),
    }
