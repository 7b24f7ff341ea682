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
dictionary-coded strings). By default the long text columns (comments,
addresses, phones) are omitted; ``SPEC PARTS`` makes every table's TPC-H
columns (``SPEC_SCHEMAS``), comments drawn from clause 4.2.2.10's text pool.
"""

from __future__ import annotations

import functools
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


def _widen(schema: Schema, *columns: Column) -> Schema:
    return Schema(list(schema.columns) + list(columns))


def _text(name: str) -> Column:
    return Column(name, ColumnType.STRING)


# Under ``SPEC PARTS`` every table has TPC-H's columns (clause 1.4.1):
# the ones above, then the ones they leave out, appended so that every
# column keeps its position.
SPEC_SCHEMAS = {
    "lineitem": _widen(
        LINEITEM_SCHEMA, _text("l_shipinstruct"), _text("l_shipmode"),
        _text("l_comment"),
    ),
    "orders": _widen(ORDERS_SCHEMA, _text("o_clerk"), _text("o_comment")),
    "supplier": _widen(
        SUPPLIER_SCHEMA, _text("s_address"), _text("s_phone"),
        Column("s_acctbal", ColumnType.DECIMAL, scale=2),
        _text("s_comment"),
    ),
    "part": _widen(
        PART_SCHEMA, _text("p_mfgr"), _text("p_brand"), _text("p_type"),
        Column("p_size", ColumnType.INT32), _text("p_container"),
        _text("p_comment"),
    ),
    "partsupp": _widen(
        PARTSUPP_SCHEMA, Column("ps_availqty", ColumnType.INT32),
        _text("ps_comment"),
    ),
    "customer": _widen(
        CUSTOMER_SCHEMA, _text("c_address"), _text("c_phone"),
        Column("c_acctbal", ColumnType.DECIMAL, scale=2),
        _text("c_comment"),
    ),
    "nation": _widen(NATION_SCHEMA, _text("n_comment")),
    "region": _widen(REGION_SCHEMA, _text("r_comment")),
}

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
# TPC-H clause 4.2.3 (dbgen's `colors`): P_NAME is five distinct words
# of these 92, joined by single spaces.
_COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue "
    "blush brown burlywood burnished chartreuse chiffon chocolate coral "
    "cornflower cornsilk cream cyan dark deep dim dodger drab firebrick "
    "floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen "
    "magenta maroon medium metallic midnight mint misty moccasin navajo "
    "navy olive orange orchid pale papaya peach peru pink plum powder "
    "puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise "
    "violet wheat white yellow"
).split()
# The words of TPC-H's text grammar (clauses 4.2.2.13-4.2.2.14; dbgen's
# `nouns`, `verbs`, ... lists), drawn uniformly here.
_NOUNS = (
    "foxes ideas theodolites pinto_beans instructions dependencies "
    "excuses platelets asymptotes courts dolphins multipliers sauternes "
    "warthogs frets dinos attainments somas Tiresias' patterns forges "
    "braids hockey_players frays warhorses dugouts notornis epitaphs "
    "pearls tithes waters orbits gifts sheaves depths sentiments decoys "
    "realms pains grouches escapades packages requests accounts deposits"
).split()
_NOUNS = [w.replace("_", " ") for w in _NOUNS]
_VERBS = (
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "maintain nod was lose sublate solve thrash promise engage hinder "
    "print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt"
).split()
_ADJECTIVES = (
    "furious sly careful blithe quick fluffy slow quiet ruthless thin "
    "close dogged daring brave stealthy permanent enticing idle busy "
    "regular final ironic even bold silent special pending unusual "
    "express"
).split()
_ADVERBS = (
    "sometimes always never furiously slyly carefully blithely quickly "
    "fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently"
).split()
_PREPOSITIONS = (
    "about|above|according to|across|after|against|along|alongside of|"
    "among|around|at|atop|before|behind|beneath|beside|besides|between|"
    "beyond|by|despite|during|except|for|from|in place of|inside|"
    "instead of|into|near|of|on|outside|over|past|since|through|"
    "throughout|to|toward|under|until|up|upon|without|with|within"
).split("|")
_AUXILIARIES = (
    "do|may|might|shall|will|would|can|could|should|ought to|must|"
    "will have to|shall have to|could have to|should have to|"
    "must have to|need to|try to"
).split("|")
_TERMINATORS = [".", ";", ":", "?", "!", "--"]
# dbgen's pool is 300 MB; 1 MiB keeps a comment's (offset, length)
# distinct in practice at the scale factors one host generates
_TEXT_POOL_BYTES = 1 << 20
_TEXT_POOL_SEED = 4_242_242
_INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                 "TAKE BACK RETURN"]
_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_TYPE_SYLLABLES = (
    ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"],
    ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"],
    ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"],
)
_CONTAINER_SYLLABLES = (
    ["SM", "LG", "MED", "JUMBO", "WRAP"],
    ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"],
)
# clause 4.2.2.7's v-string: at least 64 symbols
_VSTRING_CHARS = np.asarray(list(
    "0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ, "
))


def _sentence(r: list) -> str:
    """One sentence of clause 4.2.2.14's grammar from 20 draws ``r``:
    a noun phrase, then a verb phrase and what may follow it, or a
    prepositional phrase, a verb phrase and a noun or prepositional
    phrase; then a terminator."""

    def pick(words, x):
        return words[x % len(words)]

    def noun_phrase(a):
        form = r[a] % 4
        noun = pick(_NOUNS, r[a + 3])
        if form == 0:
            return [noun]
        if form == 1:
            return [pick(_ADJECTIVES, r[a + 2]), noun]
        if form == 2:
            return [pick(_ADJECTIVES, r[a + 1]) + ",",
                    pick(_ADJECTIVES, r[a + 2]), noun]
        return [pick(_ADVERBS, r[a + 1]), pick(_ADJECTIVES, r[a + 2]),
                noun]

    def verb_phrase(a):
        form = r[a] % 4
        verb = [pick(_VERBS, r[a + 2])]
        if form in (1, 3):
            verb = [pick(_AUXILIARIES, r[a + 1])] + verb
        if form in (2, 3):
            verb = verb + [pick(_ADVERBS, r[a + 3])]
        return verb

    def prep_phrase(a):
        return [pick(_PREPOSITIONS, r[a]), "the"] + noun_phrase(a + 1)

    form = r[0] % 5
    if form < 3:
        words = noun_phrase(1) + verb_phrase(5)
        if form == 1:
            words += prep_phrase(9)
        elif form == 2:
            words += noun_phrase(9)
    else:
        words = noun_phrase(1) + prep_phrase(5) + verb_phrase(10)
        words += noun_phrase(14) if form == 3 else prep_phrase(14)
    return " ".join(words) + pick(_TERMINATORS, r[19])


@functools.lru_cache(maxsize=1)
def text_pool() -> str:
    """Clause 4.2.2.10's text pool: sentences of the grammar joined by
    single spaces, ``_TEXT_POOL_BYTES`` long, the same in every process
    (dbgen's is not seeded by the data's seed either)."""
    out, size, j = [], 0, 0
    while size < _TEXT_POOL_BYTES:
        draws = TpchGenerator._mix64(
            np.uint64(_TEXT_POOL_SEED),
            np.arange(j, j + 4096, dtype=np.uint64)[:, None],
            np.arange(20, dtype=np.uint64)[None, :],
        ).tolist()
        for r in draws:
            s = _sentence(r)
            out.append(s)
            size += len(s) + 1
        j += 4096
    return " ".join(out)[:_TEXT_POOL_BYTES]


@dataclass
class TpchGenerator:
    """Deterministic TPCH generator at a given scale factor.

    Row counts follow the spec: orders = 1.5M * sf, lineitem ~ 4 per
    order, part = 200k * sf, supplier = 10k * sf, customer = 150k * sf.

    ``spec_parts`` (``SPEC PARTS``) takes TPC-H clause 4.2.3's rules for
    what ties parts to suppliers and names them: P_NAME is five distinct
    colour words, PS_SUPPKEY is the clause's formula for i = 0..3, and
    L_SUPPKEY the same formula with i drawn from 0..3, so that every
    lineitem matches a partsupp row. It also gives every table all of
    TPC-H's columns (``SPEC_SCHEMAS``): comments are clause 4.2.2.10's
    text strings, substrings of ``text_pool()``. Unset, every table and
    the string dictionary are what they were.
    """

    sf: float = 0.01
    seed: int = 1
    spec_parts: bool = False

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
        if self.spec_parts:
            self._instruction_codes = GLOBAL_DICT.encode_many(_INSTRUCTIONS)
            self._mode_codes = GLOBAL_DICT.encode_many(_MODES)

    def schema(self, table: str) -> Schema:
        """The table's schema: TPC-H's columns under ``spec_parts``."""
        if self.spec_parts:
            return SPEC_SCHEMAS[table]
        return {
            "lineitem": LINEITEM_SCHEMA, "orders": ORDERS_SCHEMA,
            "supplier": SUPPLIER_SCHEMA, "part": PART_SCHEMA,
            "partsupp": PARTSUPP_SCHEMA, "customer": CUSTOMER_SCHEMA,
            "nation": NATION_SCHEMA, "region": REGION_SCHEMA,
        }[table]

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
    @staticmethod
    def _mix64(*vals):
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

    def _texts(self, lo: int, hi: int, tag: int, *keys) -> list:
        """Clause 4.2.2.10's text string [lo, hi]: the substring of the
        text pool at a drawn offset, of a drawn length; draws ``tag``
        and ``tag + 1`` of ``keys``."""
        pool = text_pool()
        off = self._draw(0, len(pool) - hi, *keys, tag).tolist()
        n = self._draw(lo, hi + 1, *keys, tag + 1).tolist()
        return [pool[a:a + b] for a, b in zip(off, n)]

    @staticmethod
    def _encode(strings: list) -> np.ndarray:
        # new strings in bulk, spaced in their label gaps: one at a
        # time, random text runs a gap dry (StringDictionary.encode_bulk)
        GLOBAL_DICT.encode_bulk(strings)
        return GLOBAL_DICT.encode_many(strings)

    def _vstrings(self, lo: int, hi: int, tag: int, keys) -> list:
        """Clause 4.2.2.7's v-string [lo, hi] of each key: draws ``tag``
        (the length) and ``tag + 1`` (the characters)."""
        sd = np.uint64(self.seed * 1_000_003)
        k = np.asarray(keys, np.uint64)
        n = self._draw(lo, hi + 1, sd, k, tag).tolist()
        chars = _VSTRING_CHARS[self._draw(
            0, len(_VSTRING_CHARS), sd, k[:, None],
            np.arange(hi, dtype=np.uint64)[None, :], tag + 1,
        )]
        return ["".join(row[:m]) for row, m in zip(chars.tolist(), n)]

    def _phones(self, nation, tag: int, keys) -> list:
        """Clause 4.2.2.9: country code (nation key + 10), then three
        local numbers; draws ``tag`` to ``tag + 2``."""
        sd = np.uint64(self.seed * 1_000_003)
        k = np.asarray(keys, np.uint64)
        a = self._draw(100, 1000, sd, k, tag).tolist()
        b = self._draw(100, 1000, sd, k, tag + 1).tolist()
        c = self._draw(1000, 10000, sd, k, tag + 2).tolist()
        return [
            f"{n + 10}-{x}-{y}-{z}"
            for n, x, y, z in zip(np.asarray(nation).tolist(), a, b, c)
        ]

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
        if self.spec_parts:
            suppkey = self.spec_suppkey(
                partkey, self._draw(0, 4, sd, u, li, 12)
            )
        else:
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
        if self.spec_parts:
            cols += [
                self._instruction_codes[
                    self._draw(0, len(_INSTRUCTIONS), sd, u, li, 13)
                ].astype(np.int64),
                self._mode_codes[
                    self._draw(0, len(_MODES), sd, u, li, 14)
                ].astype(np.int64),
                self._encode(self._texts(10, 43, 15, sd, u, li)),
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
        cols = [
            orderkeys,
            custkey,
            status,
            totalprice.astype(np.int64),
            orderdate.astype(np.int32),
            prio,
            # TPC-H clause 4.2.3: o_shippriority is 0 on every order
            np.zeros(len(orderkeys), np.int32),
        ]
        if self.spec_parts:
            clerks = self._draw(
                1, max(int(self.sf * 1000), 1) + 1, sd, u, 25
            ).tolist()
            cols += [
                self._encode([f"Clerk#{c:09d}" for c in clerks]),
                self._encode(self._texts(19, 78, 26, sd, u)),
            ]
        return cols

    # -- static dimension tables -------------------------------------------
    def supplier_table(self):
        rng = np.random.default_rng(self.seed + 7)
        keys = np.arange(1, self.n_supplier + 1)
        nation = rng.integers(0, 25, size=len(keys))
        names = GLOBAL_DICT.encode_many(
            [f"Supplier#{k:09d}" for k in keys]
        )
        cols = [keys, nation.astype(np.int64), names]
        if self.spec_parts:
            sd, u = np.uint64(self.seed * 1_000_003), keys.astype(np.uint64)
            cols += [
                self._encode(self._vstrings(10, 40, 55, keys)),
                self._encode(self._phones(nation, 57, keys)),
                self._draw(-99_999, 1_000_000, sd, u, 60),
                self._encode(self._texts(25, 100, 61, sd, u)),
            ]
        return cols

    def spec_suppkey(self, partkey, i):
        """TPC-H clause 4.2.3: the i-th supplier of a part, i in 0..3,
        (partkey + i * (S/4 + (partkey - 1)/S)) mod S + 1, integer
        division. Where 2 or 3 times S/4 + (partkey - 1)/S is a multiple
        of S (some parts at S = 20, SF 0.002, among other small scale
        factors), it repeats a supplier for that part."""
        s = self.n_supplier
        partkey = np.asarray(partkey, np.int64)
        return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1

    def spec_part_names(self, keys) -> list:
        """Five distinct words of ``_COLORS`` a part, a pure function of
        (seed, p_partkey): the words of least counter hash."""
        sd = np.uint64(self.seed * 1_000_003)
        word = np.arange(len(_COLORS), dtype=np.uint64)
        words = np.asarray(_COLORS)
        names = []
        for lo in range(0, len(keys), 4096):
            k = np.asarray(keys[lo:lo + 4096], np.uint64)
            h = self._mix64(sd, k[:, None], word[None, :], 41)
            picked = words[np.argsort(h, axis=1, kind="stable")[:, :5]]
            names += [" ".join(row) for row in picked.tolist()]
        return names

    def part_table(self):
        keys = np.arange(1, self.n_part + 1)
        if not self.spec_parts:
            names = GLOBAL_DICT.encode_many(
                [f"part {k % 92}" for k in keys]
            )
        else:
            names = self._encode(self.spec_part_names(keys))
        retail = (
            90_000 + (keys * 100) % 200_000 + (keys % 1000) * 100
        ).astype(np.int64)
        cols = [keys, names, retail]
        if self.spec_parts:
            sd, u = np.uint64(self.seed * 1_000_003), keys.astype(np.uint64)
            mfgr = self._draw(1, 6, sd, u, 42)
            brand = mfgr * 10 + self._draw(1, 6, sd, u, 43)
            kinds = [
                np.asarray(words)[self._draw(0, len(words), sd, u, tag)]
                for words, tag in zip(_TYPE_SYLLABLES, (44, 45, 46))
            ]
            box = [
                np.asarray(words)[self._draw(0, len(words), sd, u, tag)]
                for words, tag in zip(_CONTAINER_SYLLABLES, (48, 49))
            ]
            cols += [
                self._encode([f"Manufacturer#{m}" for m in mfgr.tolist()]),
                self._encode([f"Brand#{b}" for b in brand.tolist()]),
                self._encode(
                    [" ".join(t) for t in zip(*(k.tolist() for k in kinds))]
                ),
                self._draw(1, 51, sd, u, 47).astype(np.int32),
                self._encode(
                    [" ".join(t) for t in zip(*(k.tolist() for k in box))]
                ),
                self._encode(self._texts(5, 22, 50, sd, u)),
            ]
        return cols

    def partsupp_table(self):
        rng = np.random.default_rng(self.seed + 11)
        pkeys = np.repeat(np.arange(1, self.n_part + 1), 4)
        if self.spec_parts:
            skeys = self.spec_suppkey(
                pkeys, np.tile(np.arange(4), self.n_part)
            )
        else:
            skeys = (
                (pkeys + np.tile(np.arange(4), self.n_part) * (
                    self.n_supplier // 4 + 1
                )) % self.n_supplier
            ) + 1
        cost = rng.integers(100, 1000_00, size=len(pkeys)).astype(np.int64)
        cols = [pkeys, skeys, cost]
        if self.spec_parts:
            sd = np.uint64(self.seed * 1_000_003)
            u = pkeys.astype(np.uint64)
            i = np.tile(np.arange(4, dtype=np.uint64), self.n_part)
            cols += [
                self._draw(1, 10_000, sd, u, i, 52).astype(np.int32),
                self._encode(self._texts(49, 198, 53, sd, u, i)),
            ]
        return cols

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
        cols = [keys, nation.astype(np.int64), names, segment]
        if self.spec_parts:
            sd, u = np.uint64(self.seed * 1_000_003), keys.astype(np.uint64)
            cols += [
                self._encode(self._vstrings(10, 40, 63, keys)),
                self._encode(self._phones(nation, 65, keys)),
                self._draw(-99_999, 1_000_000, sd, u, 68),
                self._encode(self._texts(29, 116, 69, sd, u)),
            ]
        return cols

    def nation_table(self):
        names = GLOBAL_DICT.encode_many(_NATIONS)
        cols = [
            np.arange(25, dtype=np.int64),
            np.asarray(_NATION_REGION, dtype=np.int64),
            names,
        ]
        if self.spec_parts:
            sd = np.uint64(self.seed * 1_000_003)
            cols.append(self._encode(self._texts(
                31, 114, 71, sd, np.arange(25, dtype=np.uint64)
            )))
        return cols

    def region_table(self):
        names = GLOBAL_DICT.encode_many(_REGIONS)
        cols = [np.arange(5, dtype=np.int64), names]
        if self.spec_parts:
            sd = np.uint64(self.seed * 1_000_003)
            cols.append(self._encode(self._texts(
                31, 115, 73, sd, np.arange(5, dtype=np.uint64)
            )))
        return cols

    def table_batch(self, name: str, time: int = 0) -> Batch:
        """A static table as one insert batch (dimension-table snapshot)."""
        cols = {
            "supplier": self.supplier_table,
            "part": self.part_table,
            "partsupp": self.partsupp_table,
            "customer": self.customer_table,
            "nation": self.nation_table,
            "region": self.region_table,
        }[name]()
        schema = self.schema(name)
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
            schema = self.schema("lineitem")
            cols, diffs, n = presort_hash(
                schema, cols, np.ones(n, np.int64)
            )
            yield Batch.from_numpy(
                schema,
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
        schema = self.schema("lineitem")
        cols, diffs, n = presort_hash(schema, cols, diffs)
        times = np.full(n, time, np.uint64)
        return Batch.from_numpy(
            schema, cols, times, diffs, capacity=capacity,
            hints=("hash_consolidated",),
        )
