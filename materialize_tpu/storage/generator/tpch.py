"""TPC-H load generator: deterministic, vectorized, host-side.

Analog of the reference's TPCH load-generator source
(src/storage/src/source/generator/tpch.rs): emits the TPC-H tables as an
initial snapshot of inserts, then (like the reference's tick mode) churns
orders — deleting and re-inserting order/lineitem groups — to produce a
sustained update stream. Distributions are the simplified deterministic
ones the reference uses, not the official dbgen text generator: uniform
keys/quantities/discounts, date ranges over 1992-1998.

All columns that the north-star workloads touch are generated with correct
types (DECIMAL as scaled int64, DATE as days-since-epoch, flags as
dictionary-coded strings); long text columns (comments) are omitted — they
are dead weight for every benchmark query.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ...ops.lanes import hash_pair_host, host_lane_encode
from ...repr.batch import Batch
from ...repr.schema import (
    GLOBAL_DICT,
    Column,
    ColumnType,
    Schema,
)


def presort_hash(schema: Schema, cols, diffs):
    """Host-side replica of the device hash order (ops/lanes.hash_pair
    over row lanes): returns (cols, diffs, n) sorted by (h1, h2) with
    duplicate-content rows merged (diffs summed, zeros dropped) — the
    batch satisfies the "hash_consolidated" hint, so ingest skips the
    device input sort entirely (the large-micro-batch cost ceiling:
    TPU sort execution is ~2us/row; numpy lexsort is ~20ns/row)."""
    lanes = []
    for col, c in zip(cols, schema.columns):
        lanes.extend(host_lane_encode(col, c, None))
    h1, h2 = hash_pair_host(lanes)
    order = np.lexsort((h2, h1))
    cols = [np.asarray(c)[order] for c in cols]
    diffs = np.asarray(diffs)[order]
    h1, h2 = h1[order], h2[order]
    n = len(diffs)
    if n:
        same = np.ones(n, dtype=bool)
        same[0] = False
        same[1:] &= (h1[1:] == h1[:-1]) & (h2[1:] == h2[:-1])
        for c in cols:
            same[1:] &= c[1:] == c[:-1]
        if same.any():
            # Rare duplicate content (e.g. a churn draw colliding with
            # the row it retracts): merge via segment sums.
            import numpy as _np

            seg = _np.cumsum(~same) - 1
            sums = _np.zeros(seg[-1] + 1, dtype=diffs.dtype)
            _np.add.at(sums, seg, diffs)
            leaders = ~same
            keep = leaders & (sums[seg] != 0)
            cols = [c[keep] for c in cols]
            diffs = sums[seg][keep]
    keep = diffs != 0
    if not keep.all():
        cols = [c[keep] for c in cols]
        diffs = diffs[keep]
    return cols, diffs, len(diffs)

_EPOCH_1992 = 8035  # days from 1970-01-01 to 1992-01-01
_DATE_RANGE = 2526  # days spanned by TPCH dates (1992-01-01..1998-12-01)

LINEITEM_SCHEMA = Schema(
    [
        Column("l_orderkey", ColumnType.INT64),
        Column("l_partkey", ColumnType.INT64),
        Column("l_suppkey", ColumnType.INT64),
        Column("l_linenumber", ColumnType.INT32),
        Column("l_quantity", ColumnType.DECIMAL, scale=2),
        Column("l_extendedprice", ColumnType.DECIMAL, scale=2),
        Column("l_discount", ColumnType.DECIMAL, scale=2),
        Column("l_tax", ColumnType.DECIMAL, scale=2),
        Column("l_returnflag", ColumnType.STRING),
        Column("l_linestatus", ColumnType.STRING),
        Column("l_shipdate", ColumnType.DATE),
        Column("l_commitdate", ColumnType.DATE),
        Column("l_receiptdate", ColumnType.DATE),
    ]
)

ORDERS_SCHEMA = Schema(
    [
        Column("o_orderkey", ColumnType.INT64),
        Column("o_custkey", ColumnType.INT64),
        Column("o_orderstatus", ColumnType.STRING),
        Column("o_totalprice", ColumnType.DECIMAL, scale=2),
        Column("o_orderdate", ColumnType.DATE),
        Column("o_orderpriority", ColumnType.STRING),
        Column("o_shippriority", ColumnType.INT32),
    ]
)

SUPPLIER_SCHEMA = Schema(
    [
        Column("s_suppkey", ColumnType.INT64),
        Column("s_nationkey", ColumnType.INT64),
        Column("s_name", ColumnType.STRING),
    ]
)

PART_SCHEMA = Schema(
    [
        Column("p_partkey", ColumnType.INT64),
        Column("p_name", ColumnType.STRING),
        Column("p_retailprice", ColumnType.DECIMAL, scale=2),
    ]
)

PARTSUPP_SCHEMA = Schema(
    [
        Column("ps_partkey", ColumnType.INT64),
        Column("ps_suppkey", ColumnType.INT64),
        Column("ps_supplycost", ColumnType.DECIMAL, scale=2),
    ]
)

CUSTOMER_SCHEMA = Schema(
    [
        Column("c_custkey", ColumnType.INT64),
        Column("c_nationkey", ColumnType.INT64),
        Column("c_name", ColumnType.STRING),
        Column("c_mktsegment", ColumnType.STRING),
    ]
)

NATION_SCHEMA = Schema(
    [
        Column("n_nationkey", ColumnType.INT64),
        Column("n_regionkey", ColumnType.INT64),
        Column("n_name", ColumnType.STRING),
    ]
)

REGION_SCHEMA = Schema(
    [
        Column("r_regionkey", ColumnType.INT64),
        Column("r_name", ColumnType.STRING),
    ]
)

_NATIONS = [
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
    "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
    "UNITED STATES",
]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# TPC-H clause 4.2.3: c_mktsegment is one of these five, uniform.
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
_NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1,
                  2, 3, 4, 2, 3, 3, 1]


@dataclass
class TpchGenerator:
    """Deterministic TPCH generator at a given scale factor.

    Row counts follow the spec: orders = 1.5M * sf, lineitem ~ 4 per
    order, part = 200k * sf, supplier = 10k * sf, customer = 150k * sf.
    """

    sf: float = 0.01
    seed: int = 1

    def __post_init__(self):
        self.n_orders = max(int(1_500_000 * self.sf), 16)
        self.n_part = max(int(200_000 * self.sf), 8)
        self.n_supplier = max(int(10_000 * self.sf), 4)
        self.n_customer = max(int(150_000 * self.sf), 8)
        self._flag_codes = GLOBAL_DICT.encode_many(["R", "A", "N"])
        self._status_codes = GLOBAL_DICT.encode_many(["F", "O"])
        self._prio_codes = GLOBAL_DICT.encode_many(
            ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
        )

    # -- per-order generation (deterministic in orderkey) -------------------
    #
    # Counter-based (splitmix64-style) hashing instead of a sequential
    # numpy Generator: every field of every row is a pure function of
    # (seed, version, orderkey, linenumber, field tag). A sequential rng
    # seeded per batch made a row's content depend on the BATCH it was
    # generated in, so a churn tick's "delete the old rows" did not match
    # the snapshot's rows — phantom +/- row pairs that cancel in sums but
    # break EXISTS/DISTINCT/Threshold semantics (negative multiplicities
    # are outside the differential contract; the reference's tpch.rs tick
    # loop deletes exactly the rows it inserted).
    def _mix64(self, *vals):
        with np.errstate(over="ignore"):
            h = np.uint64(0x9E3779B97F4A7C15)
            for v in vals:
                v = np.asarray(v, dtype=np.uint64)
                z = (h ^ v) + np.uint64(0x9E3779B97F4A7C15)
                z = (z ^ (z >> np.uint64(30))) * np.uint64(
                    0xBF58476D1CE4E5B9
                )
                z = (z ^ (z >> np.uint64(27))) * np.uint64(
                    0x94D049BB133111EB
                )
                h = z ^ (z >> np.uint64(31))
        return h

    def _draw(self, lo: int, hi: int, *keys) -> np.ndarray:
        """Uniform ints in [lo, hi), elementwise over broadcast keys."""
        span = np.uint64(hi - lo)
        return (self._mix64(*keys) % span).astype(np.int64) + lo

    def lineitems_for_orders(
        self, orderkeys: np.ndarray, version: int = 0
    ):
        """Generate lineitem rows for the given order keys.

        ``version`` selects the churn generation (0 = snapshot; churn
        tick t writes version 1000+t): rows are deterministic in
        (seed, version, orderkey) alone, never in batch composition.
        """
        sd = np.uint64(self.seed * 1_000_003 + version)
        ok_u = np.asarray(orderkeys, dtype=np.uint64)
        n_lines = self._draw(1, 8, sd, ok_u, 11)  # 1..7, avg 4 per spec
        okeys = np.repeat(orderkeys, n_lines)
        n = len(okeys)
        linenumber = (
            np.arange(n) - np.repeat(np.cumsum(n_lines) - n_lines, n_lines)
        ).astype(np.int32) + 1
        u = okeys.astype(np.uint64)
        li = linenumber.astype(np.uint64)
        partkey = self._draw(1, self.n_part + 1, sd, u, li, 1)
        suppkey = self._draw(1, self.n_supplier + 1, sd, u, li, 2)
        quantity = self._draw(1, 51, sd, u, li, 3) * 100  # 1..50, scale 2
        retail = 90_000 + (partkey * 100) % 200_000 + (partkey % 1000) * 100
        extendedprice = (quantity // 100) * retail
        discount = self._draw(0, 11, sd, u, li, 4)  # 0.00..0.10
        tax = self._draw(0, 9, sd, u, li, 5)  # 0.00..0.08
        orderdate = _EPOCH_1992 + (
            (okeys * 2654435761) % (_DATE_RANGE - 151)
        ).astype(np.int64)
        shipdate = orderdate + self._draw(1, 122, sd, u, li, 6)
        commitdate = orderdate + self._draw(30, 91, sd, u, li, 7)
        receiptdate = shipdate + self._draw(1, 31, sd, u, li, 8)
        today = _EPOCH_1992 + _DATE_RANGE - 151
        returnflag = np.where(
            receiptdate <= today,
            self._flag_codes[self._draw(0, 2, sd, u, li, 9)],
            self._flag_codes[2],
        ).astype(np.int64)
        linestatus = np.where(
            shipdate > today, self._status_codes[1], self._status_codes[0]
        ).astype(np.int64)
        cols = [
            okeys,
            partkey,
            suppkey,
            linenumber,
            quantity.astype(np.int64),
            extendedprice.astype(np.int64),
            (discount).astype(np.int64),
            (tax).astype(np.int64),
            returnflag,
            linestatus,
            shipdate.astype(np.int32),
            commitdate.astype(np.int32),
            receiptdate.astype(np.int32),
        ]
        return cols

    def orders_rows(self, orderkeys: np.ndarray):
        sd = np.uint64(self.seed * 1_000_003)
        u = np.asarray(orderkeys, dtype=np.uint64)
        custkey = self._draw(1, self.n_customer + 1, sd, u, 21)
        status = self._status_codes[
            self._draw(0, 2, sd, u, 22)
        ].astype(np.int64)
        totalprice = self._draw(1_000_00, 500_000_00, sd, u, 23)
        orderdate = _EPOCH_1992 + (
            (orderkeys * 2654435761) % (_DATE_RANGE - 151)
        ).astype(np.int64)
        prio = self._prio_codes[self._draw(0, 5, sd, u, 24)].astype(
            np.int64
        )
        return [
            orderkeys,
            custkey,
            status,
            totalprice.astype(np.int64),
            orderdate.astype(np.int32),
            prio,
            # TPC-H clause 4.2.3: o_shippriority is 0 on every order
            np.zeros(len(orderkeys), np.int32),
        ]

    # -- static dimension tables -------------------------------------------
    def supplier_table(self):
        rng = np.random.default_rng(self.seed + 7)
        keys = np.arange(1, self.n_supplier + 1)
        nation = rng.integers(0, 25, size=len(keys))
        names = GLOBAL_DICT.encode_many(
            [f"Supplier#{k:09d}" for k in keys]
        )
        return [keys, nation.astype(np.int64), names]

    def part_table(self):
        keys = np.arange(1, self.n_part + 1)
        names = GLOBAL_DICT.encode_many([f"part {k % 92}" for k in keys])
        retail = (
            90_000 + (keys * 100) % 200_000 + (keys % 1000) * 100
        ).astype(np.int64)
        return [keys, names, retail]

    def partsupp_table(self):
        rng = np.random.default_rng(self.seed + 11)
        pkeys = np.repeat(np.arange(1, self.n_part + 1), 4)
        skeys = (
            (pkeys + np.tile(np.arange(4), self.n_part) * (
                self.n_supplier // 4 + 1
            )) % self.n_supplier
        ) + 1
        cost = rng.integers(100, 1000_00, size=len(pkeys)).astype(np.int64)
        return [pkeys, skeys, cost]

    def customer_table(self):
        rng = np.random.default_rng(self.seed + 13)
        keys = np.arange(1, self.n_customer + 1)
        nation = rng.integers(0, 25, size=len(keys))
        names = GLOBAL_DICT.encode_many(
            [f"Customer#{k:09d}" for k in keys]
        )
        # a counter hash of the key, not a draw from `rng`: the columns
        # above stay what they were before this one was added
        segment = GLOBAL_DICT.encode_many(_SEGMENTS)[
            self._draw(
                0, len(_SEGMENTS), np.uint64(self.seed * 1_000_003),
                keys.astype(np.uint64), 31,
            )
        ].astype(np.int64)
        return [keys, nation.astype(np.int64), names, segment]

    def nation_table(self):
        names = GLOBAL_DICT.encode_many(_NATIONS)
        return [
            np.arange(25, dtype=np.int64),
            np.asarray(_NATION_REGION, dtype=np.int64),
            names,
        ]

    def region_table(self):
        names = GLOBAL_DICT.encode_many(_REGIONS)
        return [np.arange(5, dtype=np.int64), names]

    def table_batch(self, name: str, time: int = 0) -> Batch:
        """A static table as one insert batch (dimension-table snapshot)."""
        schema, cols = {
            "supplier": (SUPPLIER_SCHEMA, self.supplier_table),
            "part": (PART_SCHEMA, self.part_table),
            "partsupp": (PARTSUPP_SCHEMA, self.partsupp_table),
            "customer": (CUSTOMER_SCHEMA, self.customer_table),
            "nation": (NATION_SCHEMA, self.nation_table),
            "region": (REGION_SCHEMA, self.region_table),
        }[name]
        cols = cols()
        n = len(cols[0])
        return Batch.from_numpy(
            schema,
            cols,
            np.full(n, time, np.uint64),
            np.ones(n, np.int64),
        )

    # -- streaming interface ------------------------------------------------
    def snapshot_lineitem_batches(
        self, batch_orders: int = 4096, time: int = 0,
        capacity: int | None = None,
    ):
        """Yield Batch objects of lineitem inserts covering the
        snapshot — host-presorted in the device hash order (ingest
        skips the device sort; presort_hash)."""
        for start in range(1, self.n_orders + 1, batch_orders):
            keys = np.arange(
                start, min(start + batch_orders, self.n_orders + 1)
            )
            cols = self.lineitems_for_orders(keys)
            n = len(cols[0])
            cols, diffs, n = presort_hash(
                LINEITEM_SCHEMA, cols, np.ones(n, np.int64)
            )
            yield Batch.from_numpy(
                LINEITEM_SCHEMA,
                cols,
                np.full(n, time, np.uint64),
                diffs,
                capacity=capacity,
                hints=("hash_consolidated",),
            )

    def churn_lineitem_batch(
        self, n_orders: int, tick: int, time: int, capacity: int | None = None
    ) -> Batch:
        """One tick of order churn: delete + regenerate `n_orders` orders'
        lineitems (the reference's tick loop deletes and re-inserts an
        order per tick, tpch.rs). The generator tracks each order's
        current version so the deletion side matches EXACTLY the rows
        previously inserted for it, even when ticks overlap on orders."""
        rng = np.random.default_rng(self.seed * 31 + tick)
        keys = np.sort(
            rng.choice(
                np.arange(1, self.n_orders + 1), size=n_orders, replace=False
            )
        )
        if not hasattr(self, "_order_version"):
            self._order_version: dict = {}
        new_version = 1000 + tick
        by_version: dict = {}
        for k in keys:
            v = self._order_version.get(int(k), 0)
            by_version.setdefault(v, []).append(int(k))
        old_parts = [
            self.lineitems_for_orders(
                np.asarray(sorted(ks), dtype=keys.dtype), version=v
            )
            for v, ks in sorted(by_version.items())
        ]
        old = [np.concatenate(cols) for cols in zip(*old_parts)]
        new = self.lineitems_for_orders(keys, version=new_version)
        for k in keys:
            self._order_version[int(k)] = new_version
        cols = [np.concatenate([o, nw]) for o, nw in zip(old, new)]
        n_old, n_new = len(old[0]), len(new[0])
        diffs = np.concatenate(
            [np.full(n_old, -1, np.int64), np.ones(n_new, np.int64)]
        )
        cols, diffs, n = presort_hash(LINEITEM_SCHEMA, cols, diffs)
        times = np.full(n, time, np.uint64)
        return Batch.from_numpy(
            LINEITEM_SCHEMA, cols, times, diffs, capacity=capacity,
            hints=("hash_consolidated",),
        )
