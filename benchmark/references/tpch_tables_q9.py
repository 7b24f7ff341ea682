"""The base tables of the ``tpch_q9`` configuration, made again from
``--seed`` with nothing of the program: ``lineitem``, ``orders``,
``part``, ``partsupp``, ``supplier`` and ``nation`` as the source shards
must hold them at a time.

The configuration's source sets ``SPEC PARTS``, under which the
generator (``materialize_tpu/storage/generator/tpch.py``) takes TPC-H
clause 4.2.3's rules for three columns. This is a frozen copy of them
as of the PR that added the option:

* ``p_name``: five distinct words of the clause's 92 colour words,
  joined by single spaces: for each part the five of least counter hash
  of (seed, p_partkey, word);
* ``ps_suppkey``: (ps_partkey + i x (S/4 + (ps_partkey - 1)/S)) mod S
  + 1 for i = 0..3, integer division, S the number of suppliers;
* ``l_suppkey``: the same formula of l_partkey, with i a counter-hash
  draw of (seed, version, order, line) in 0..3.

The option also gives every table all of TPC-H's columns (clause
1.4.1), appended after the ones the other configurations read:

* text strings (``*_comment``, clause 4.2.2.10) are substrings of one
  text pool, at a drawn offset and of a drawn length; the pool is 1 MiB
  of sentences of clause 4.2.2.14's grammar over clause 4.2.2.13's words
  (dbgen's lists, drawn uniformly), the same for every seed;
* ``s_address``, ``c_address``: clause 4.2.2.7's v-strings;
  ``s_phone``: clause 4.2.2.9's numbers; ``s_acctbal`` in
  [-999.99, 9999.99];
* ``p_mfgr``, ``p_brand``, ``p_type``, ``p_size``, ``p_container``,
  ``ps_availqty``, ``o_clerk``, ``l_shipinstruct`` and ``l_shipmode``
  by clause 4.2.3's rules, every draw a counter hash.

Everything else is ``tpch_tables.py``'s (``lineitem`` with its RF1/RF2
order versions, ``supplier``) and ``tpch_tables_q3.py``'s (``orders``);
``ps_supplycost``, ``p_retailprice`` and ``nation`` are copied likewise
from the generator of that PR. A later PR that changes what the
generator makes for these columns changes the benchmark's data and is
refused by ``source_rows_wrong``; columns it adds are not looked at.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        "reference_" + name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _load("tpch_tables")
q3 = _load("tpch_tables_q3")

COLORS = np.array((
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
).split())
NATIONS = np.array([
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
    "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
    "UNITED STATES",
])
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1,
                 2, 3, 4, 2, 3, 3, 1]
NOUNS = [w.replace("_", " ") for w in (
    "foxes ideas theodolites pinto_beans instructions dependencies "
    "excuses platelets asymptotes courts dolphins multipliers sauternes "
    "warthogs frets dinos attainments somas Tiresias' patterns forges "
    "braids hockey_players frays warhorses dugouts notornis epitaphs "
    "pearls tithes waters orbits gifts sheaves depths sentiments decoys "
    "realms pains grouches escapades packages requests accounts deposits"
).split()]
VERBS = (
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "maintain nod was lose sublate solve thrash promise engage hinder "
    "print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt"
).split()
ADJECTIVES = (
    "furious sly careful blithe quick fluffy slow quiet ruthless thin "
    "close dogged daring brave stealthy permanent enticing idle busy "
    "regular final ironic even bold silent special pending unusual "
    "express"
).split()
ADVERBS = (
    "sometimes always never furiously slyly carefully blithely quickly "
    "fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently"
).split()
PREPOSITIONS = (
    "about|above|according to|across|after|against|along|alongside of|"
    "among|around|at|atop|before|behind|beneath|beside|besides|between|"
    "beyond|by|despite|during|except|for|from|in place of|inside|"
    "instead of|into|near|of|on|outside|over|past|since|through|"
    "throughout|to|toward|under|until|up|upon|without|with|within"
).split("|")
AUXILIARIES = (
    "do|may|might|shall|will|would|can|could|should|ought to|must|"
    "will have to|shall have to|could have to|should have to|"
    "must have to|need to|try to"
).split("|")
TERMINATORS = [".", ";", ":", "?", "!", "--"]
POOL_BYTES, POOL_SEED = 1 << 20, 4_242_242
INSTRUCTIONS = np.array(
    ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
)
MODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"])
TYPES = (
    ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"],
    ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"],
    ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"],
)
CONTAINERS = (
    ["SM", "LG", "MED", "JUMBO", "WRAP"],
    ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"],
)
VCHARS = np.array(list(
    "0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ, "
))


def sentence(r: list) -> str:
    """Clause 4.2.2.14's grammar from 20 draws: the template is r[0]
    mod 5, each phrase reads the draws at fixed offsets, r[19] picks the
    terminator."""
    def w(words, x):
        return words[x % len(words)]

    def noun_phrase(a):
        noun, form = w(NOUNS, r[a + 3]), r[a] % 4
        return [
            [noun],
            [w(ADJECTIVES, r[a + 2]), noun],
            [w(ADJECTIVES, r[a + 1]) + ",", w(ADJECTIVES, r[a + 2]), noun],
            [w(ADVERBS, r[a + 1]), w(ADJECTIVES, r[a + 2]), noun],
        ][form]

    def verb_phrase(a):
        verb, form = w(VERBS, r[a + 2]), r[a] % 4
        aux, adv = w(AUXILIARIES, r[a + 1]), w(ADVERBS, r[a + 3])
        return [[verb], [aux, verb], [verb, adv], [aux, verb, adv]][form]

    def prep_phrase(a):
        return [w(PREPOSITIONS, r[a]), "the"] + noun_phrase(a + 1)

    form = r[0] % 5
    if form == 0:
        words = noun_phrase(1) + verb_phrase(5)
    elif form == 1:
        words = noun_phrase(1) + verb_phrase(5) + prep_phrase(9)
    elif form == 2:
        words = noun_phrase(1) + verb_phrase(5) + noun_phrase(9)
    elif form == 3:
        words = (noun_phrase(1) + prep_phrase(5) + verb_phrase(10)
                 + noun_phrase(14))
    else:
        words = (noun_phrase(1) + prep_phrase(5) + verb_phrase(10)
                 + prep_phrase(14))
    return " ".join(words) + w(TERMINATORS, r[19])


@functools.lru_cache(maxsize=1)
def pool() -> str:
    text, j = "", 0
    while len(text) < POOL_BYTES:
        draws = base._mix64(
            np.uint64(POOL_SEED),
            np.arange(j, j + 4096, dtype=np.uint64)[:, None],
            np.arange(20, dtype=np.uint64)[None, :],
        ).tolist()
        more = " ".join(sentence(r) for r in draws)
        text = more if not text else text + " " + more
        j += 4096
    return text[:POOL_BYTES]


def texts(lo: int, hi: int, tag: int, *keys) -> np.ndarray:
    """Clause 4.2.2.10's text string [lo, hi] of each key."""
    p = pool()
    off = base._draw(0, len(p) - hi, *keys, tag).tolist()
    n = base._draw(lo, hi + 1, *keys, tag + 1).tolist()
    return np.array([p[a:a + b] for a, b in zip(off, n)])


def vstrings(seed: int, lo: int, hi: int, tag: int, keys) -> np.ndarray:
    sd, k = np.uint64(seed * 1_000_003), np.asarray(keys, np.uint64)
    n = base._draw(lo, hi + 1, sd, k, tag).tolist()
    chars = VCHARS[base._draw(
        0, len(VCHARS), sd, k[:, None],
        np.arange(hi, dtype=np.uint64)[None, :], tag + 1,
    )]
    return np.array(["".join(c[:m]) for c, m in zip(chars.tolist(), n)])


def phones(seed: int, nation, tag: int, keys) -> np.ndarray:
    sd, k = np.uint64(seed * 1_000_003), np.asarray(keys, np.uint64)
    a, b, c = (
        base._draw(lo, hi, sd, k, tag + j).tolist()
        for j, (lo, hi) in enumerate(((100, 1000), (100, 1000),
                                      (1000, 10000)))
    )
    return np.array([
        f"{n + 10}-{x}-{y}-{z}"
        for n, x, y, z in zip(np.asarray(nation).tolist(), a, b, c)
    ])


def suppkey(partkey, i, n_supplier: int):
    """TPC-H clause 4.2.3: the i-th of a part's four suppliers."""
    partkey = np.asarray(partkey, np.int64)
    return (
        partkey + i * (n_supplier // 4 + (partkey - 1) // n_supplier)
    ) % n_supplier + 1


def lineitem(seed: int, n: dict, churn_orders: int, time: int) -> dict:
    version = base.order_versions(seed, n, churn_orders, time)
    parts = []
    for v in np.unique(version[1:]):
        keys = np.nonzero(version == v)[0]
        li = base.lineitems(seed, n, keys[keys >= 1], int(v))
        sd = np.uint64(seed * 1_000_003 + int(v))
        i = base._draw(
            0, 4, sd, li["l_orderkey"].astype(np.uint64),
            li["l_linenumber"].astype(np.uint64), 12,
        )
        li["l_suppkey"] = suppkey(li["l_partkey"], i, n["supplier"])
        u = li["l_orderkey"].astype(np.uint64)
        line = li["l_linenumber"].astype(np.uint64)
        li["l_shipinstruct"] = INSTRUCTIONS[
            base._draw(0, len(INSTRUCTIONS), sd, u, line, 13)
        ]
        li["l_shipmode"] = MODES[base._draw(0, len(MODES), sd, u, line, 14)]
        li["l_comment"] = texts(10, 43, 15, sd, u, line)
        parts.append(li)
    return {c: np.concatenate([p[c] for p in parts]) for c in LINEITEM}


LINEITEM = base.LINEITEM + ("l_shipinstruct", "l_shipmode", "l_comment")


def orders(seed: int, n: dict, scale_factor: float) -> dict:
    o = q3.orders(seed, n["orders"], q3.n_customers(scale_factor))
    sd = np.uint64(seed * 1_000_003)
    u = o["o_orderkey"].astype(np.uint64)
    clerks = base._draw(1, max(int(scale_factor * 1000), 1) + 1, sd, u, 25)
    o["o_clerk"] = np.array([f"Clerk#{c:09d}" for c in clerks.tolist()])
    o["o_comment"] = texts(19, 78, 26, sd, u)
    return o


def part(seed: int, n: dict) -> dict:
    keys = np.arange(1, n["part"] + 1)
    sd = np.uint64(seed * 1_000_003)
    word = np.arange(len(COLORS), dtype=np.uint64)
    h = base._mix64(sd, keys.astype(np.uint64)[:, None], word[None, :], 41)
    picked = COLORS[np.argsort(h, axis=1, kind="stable")[:, :5]]
    u = keys.astype(np.uint64)
    mfgr = base._draw(1, 6, sd, u, 42)
    kind = [
        np.array(words)[base._draw(0, len(words), sd, u, tag)]
        for words, tag in zip(TYPES, (44, 45, 46))
    ]
    box = [
        np.array(words)[base._draw(0, len(words), sd, u, tag)]
        for words, tag in zip(CONTAINERS, (48, 49))
    ]
    return {
        "p_partkey": keys,
        "p_name": np.array([" ".join(r) for r in picked.tolist()]),
        "p_retailprice": 90_000 + (keys * 100) % 200_000
        + (keys % 1000) * 100,
        "p_mfgr": np.array([f"Manufacturer#{m}" for m in mfgr.tolist()]),
        "p_brand": np.array([
            f"Brand#{m}{b}" for m, b in zip(
                mfgr.tolist(), base._draw(1, 6, sd, u, 43).tolist()
            )
        ]),
        "p_type": np.char.add(
            np.char.add(np.char.add(kind[0], " "), kind[1]),
            np.char.add(" ", kind[2]),
        ),
        "p_size": base._draw(1, 51, sd, u, 47),
        "p_container": np.char.add(np.char.add(box[0], " "), box[1]),
        "p_comment": texts(5, 22, 50, sd, u),
    }


def partsupp(seed: int, n: dict) -> dict:
    pkeys = np.repeat(np.arange(1, n["part"] + 1), 4)
    rng = np.random.default_rng(seed + 11)
    sd, u = np.uint64(seed * 1_000_003), pkeys.astype(np.uint64)
    i = np.tile(np.arange(4, dtype=np.uint64), n["part"])
    return {
        "ps_partkey": pkeys,
        "ps_suppkey": suppkey(
            pkeys, np.tile(np.arange(4), n["part"]), n["supplier"]
        ),
        "ps_supplycost": rng.integers(100, 1000_00, size=len(pkeys)),
        "ps_availqty": base._draw(1, 10_000, sd, u, i, 52),
        "ps_comment": texts(49, 198, 53, sd, u, i),
    }


def supplier(seed: int, n: dict) -> dict:
    s = base.supplier(seed, n)
    keys = s["s_suppkey"]
    sd, u = np.uint64(seed * 1_000_003), keys.astype(np.uint64)
    s["s_address"] = vstrings(seed, 10, 40, 55, keys)
    s["s_phone"] = phones(seed, s["s_nationkey"], 57, keys)
    s["s_acctbal"] = base._draw(-99_999, 1_000_000, sd, u, 60)
    s["s_comment"] = texts(25, 100, 61, sd, u)
    return s


def nation(seed: int) -> dict:
    return {
        "n_nationkey": np.arange(25),
        "n_regionkey": np.asarray(NATION_REGION),
        "n_name": NATIONS,
        "n_comment": texts(
            31, 114, 71, np.uint64(seed * 1_000_003),
            np.arange(25, dtype=np.uint64),
        ),
    }


def tables_at(seed: int, config: dict, time: int) -> dict:
    """``{relation: {column: array}}`` of the collections at ``time``
    under a configuration's ``scale_factor`` and ``churn_orders``, every
    row once."""
    n = base.sizes(config["scale_factor"])
    return {
        "lineitem": lineitem(seed, n, config["churn_orders"], time),
        "orders": orders(seed, n, config["scale_factor"]),
        "part": part(seed, n),
        "partsupp": partsupp(seed, n),
        "supplier": supplier(seed, n),
        "nation": nation(seed),
    }
