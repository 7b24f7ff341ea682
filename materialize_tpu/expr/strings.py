"""String functions as dictionary side-tables.

Strings live host-side in the GLOBAL_DICT; device columns hold int32
codes (repr/schema.py). A string function over a column therefore
becomes a GATHER through a precomputed mapping array: for ``upper``,
``map[code] = encode(upper(decode(code)))`` — the function is applied
once per distinct string on the host, and the device does an O(n)
gather. This is the TPU-native analog of the reference's row-at-a-time
string function library (expr/src/scalar/func/impls/string.rs): the
dictionary IS the loop.

Mechanics: rendering collects the set of (func, params) keys used by a
dataflow's expressions; each step passes an ``env`` of mapping arrays
(one per key, padded to a power-of-two tier of the dictionary size) as
jit inputs, so arrays grow with the dictionary without retracing until
the tier changes. Inside the traced step, eval_expr reads the current
env through a trace-scope contextvar.

Ordering: dictionary codes are order-preserving labels
(repr/schema.py StringDictionary), so string comparisons, ORDER BY,
MIN/MAX, and TopK all operate on codes directly — no rank table.
"""

from __future__ import annotations

import contextlib
import contextvars
import re

import jax.numpy as jnp
import numpy as np

from ..repr.batch import capacity_tier
from ..repr.schema import GLOBAL_DICT

_TRACE_ENV: contextvars.ContextVar = contextvars.ContextVar(
    "mt_string_env", default=None
)


@contextlib.contextmanager
def trace_scope(env: dict):
    tok = _TRACE_ENV.set(env)
    try:
        yield
    finally:
        _TRACE_ENV.reset(tok)


def trace_env() -> dict:
    env = _TRACE_ENV.get()
    if env is None:
        raise RuntimeError(
            "string function evaluated outside a dataflow step with a "
            "string env (Dataflow passes it; direct eval_expr callers "
            "must wrap in strings.trace_scope(strings.build_env(keys)))"
        )
    return env


def env_key(func: str, *params) -> str:
    return "\x00".join([func] + [str(p) for p in params])


# -- host-side table computation ---------------------------------------------


def _like_regex(pattern: str, case_insensitive: bool) -> "re.Pattern":
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        elif ch == "\\" and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 1
        else:
            out.append(re.escape(ch))
        i += 1
    return re.compile(
        "(?s)" + "".join(out) + r"\Z",
        re.IGNORECASE if case_insensitive else 0,
    )


def _apply(func: str, params: tuple, s: str):
    """One string through one function (the host-side scalar kernel)."""
    if func == "upper":
        return s.upper()
    if func == "lower":
        return s.lower()
    if func == "trim":
        return s.strip(params[0]) if params else s.strip()
    if func == "ltrim":
        return s.lstrip(params[0]) if params else s.lstrip()
    if func == "rtrim":
        return s.rstrip(params[0]) if params else s.rstrip()
    if func == "initcap":
        return re.sub(
            r"[a-zA-Z0-9]+", lambda m: m.group(0).capitalize(), s
        )
    if func == "reverse":
        return s[::-1]
    if func == "length":
        return len(s)
    if func == "ascii":
        return ord(s[0]) if s else 0
    if func == "bit_length":
        return 8 * len(s.encode())
    if func == "octet_length":
        return len(s.encode())
    if func == "substr":
        start = int(params[0])
        # SQL substr is 1-based; start may be <= 0 (pg semantics)
        if len(params) > 1:
            n = int(params[1])
            end = start + n
            return s[max(start - 1, 0) : max(end - 1, 0)]
        return s[max(start - 1, 0) :]
    if func == "left":
        n = int(params[0])
        return s[:n] if n >= 0 else s[: len(s) + n]
    if func == "right":
        n = int(params[0])
        if n >= 0:
            return s[max(len(s) - n, 0) :] if n else ""
        return s[-n:]
    if func == "replace":
        return s.replace(params[0], params[1])
    if func == "concat_r":  # col || literal
        return s + params[0]
    if func == "concat_l":  # literal || col
        return params[0] + s
    if func == "lpad":
        n = int(params[0])
        fill = params[1] if len(params) > 1 else " "
        if len(s) >= n:
            return s[:n]
        pad = (fill * n)[: n - len(s)]
        return pad + s
    if func == "rpad":
        n = int(params[0])
        fill = params[1] if len(params) > 1 else " "
        if len(s) >= n:
            return s[:n]
        return s + (fill * n)[: n - len(s)]
    if func == "like":
        return bool(_like_regex(params[0], False).match(s))
    if func == "ilike":
        return bool(_like_regex(params[0], True).match(s))
    if func == "regex":
        return re.search(params[0], s) is not None
    if func == "position":
        return s.find(params[0]) + 1  # 0 when absent (pg)
    if func == "split_part":
        parts = s.split(params[0])
        i = int(params[1])
        return parts[i - 1] if 1 <= i <= len(parts) else ""
    raise NotImplementedError(func)


# result kind per function: code->code ("str"), ->int64, ->bool
RESULT_KINDS = {
    "upper": "str", "lower": "str", "trim": "str", "ltrim": "str",
    "rtrim": "str", "initcap": "str", "reverse": "str", "substr": "str",
    "left": "str", "right": "str", "replace": "str", "concat_r": "str",
    "concat_l": "str", "lpad": "str", "rpad": "str", "split_part": "str",
    "length": "int", "ascii": "int", "bit_length": "int",
    "octet_length": "int", "position": "int",
    "like": "bool", "ilike": "bool", "regex": "bool",
}


class _EnvCache:
    """Host cache: key -> (labels, values) np arrays, padded to a
    power-of-two tier of the dictionary size. Codes are SPARSE
    order-preserving labels (StringDictionary), so a table is a sorted
    label array + parallel values; the device lookup is
    searchsorted(labels, code) -> gather. Rebuilt when the dictionary
    version moves (growth only appends pairs, but label order is not
    insertion order, so the sorted arrays are rebuilt wholesale —
    dictionary sizes are host-trivial)."""

    def __init__(self):
        self._tables: dict[str, tuple] = {}
        self._version: dict[str, int] = {}
        # per-key computed results: label -> value. _apply (the Python
        # scalar kernel, possibly regex) runs ONCE per (key, string)
        # ever; dictionary growth only computes the NEW strings and
        # re-sorts arrays with numpy (streaming workloads stay
        # O(new strings) Python work per step, not O(dict)).
        self._done: dict[str, dict] = {}
        self._epoch = 0
        # tables computed (not served from the cache) since start: a
        # build after hydration means the dictionary moved
        self.built = 0

    def table(self, key: str) -> tuple:
        # Optimistic epoch validation: the build runs UNLOCKED (it can
        # hold O(dict) Python regex work — taking the dictionary lock
        # for its duration would stall every concurrent decode/encode),
        # then re-checks the epoch under the lock. A rebalance that
        # interleaved with the build (epoch moved) would have produced
        # tables mixing old and new labels against device arrays still
        # holding old codes (garbage gathers) — those are discarded and
        # the build retried under the new labeling.
        while True:
            built = self._table_once(key)
            with GLOBAL_DICT.lock():
                if self._epoch == GLOBAL_DICT.epoch:
                    return built
            # epoch moved mid-build: reset and retry
            self._tables.clear()
            self._version.clear()
            self._done.clear()
            self._epoch = GLOBAL_DICT.epoch

    def _table_once(self, key: str) -> tuple:
        # A rebalance relabeled every code: tables (label arrays) and
        # done maps (keyed by label, str-kind values are labels too)
        # are all garbage. Full reset.
        if self._epoch != GLOBAL_DICT.epoch:
            self._tables.clear()
            self._version.clear()
            self._done.clear()
            self._epoch = GLOBAL_DICT.epoch
        parts = key.split("\x00")
        func, params = parts[0], tuple(parts[1:])
        kind = RESULT_KINDS[func]
        dtype = {
            "str": np.int64, "int": np.int64, "bool": np.bool_
        }[kind]
        cached = self._tables.get(key)
        if cached is not None and self._version.get(key) == (
            GLOBAL_DICT.version
        ):
            return cached
        done = self._done.setdefault(key, {})
        # Version BEFORE the build: encoding 'str'-kind results below
        # grows the dictionary, and the table only covers the pre-build
        # snapshot — stamping the post-build version would make the next
        # build_env pass treat this stale table as current (self-nested
        # calls like upper(upper(x)) then gather garbage).
        pre_version = GLOBAL_DICT.version
        pairs = GLOBAL_DICT.items_sorted()  # snapshot
        todo = [(c, s) for c, s in pairs if c not in done]
        if kind == "str":
            # Two-phase: compute every result first, BULK-insert the
            # new strings (positional gap division — one-at-a-time
            # content interpolation packs long-common-prefix result
            # families into slivers and exhausts gaps; encode_bulk
            # divides each gap evenly by run length), then map.
            results = [
                (c, _apply(func, params, s)) for c, s in todo
            ]
            GLOBAL_DICT.encode_bulk([v for _, v in results])
            for c, v in results:
                done[c] = GLOBAL_DICT.encode(v)
        else:
            for c, s in todo:
                done[c] = _apply(func, params, s)
        n = len(pairs)
        tier = capacity_tier(max(n, 1))
        labels = np.full(tier, GLOBAL_DICT.MAX_LABEL, dtype=np.int64)
        labels[:n] = [c for c, _ in pairs]
        values = np.zeros(tier, dtype=dtype)
        values[:n] = [done[c] for c, _ in pairs]
        self._tables[key] = (labels, values)
        self._version[key] = pre_version
        self.built += 1
        return self._tables[key]


_CACHE = _EnvCache()


def tables_built() -> int:
    """Side-tables this process has computed (cache misses) so far."""
    return _CACHE.built


def build_env(keys, depth: int = 1) -> dict:
    """Mapping tables for the given keys at the current dictionary
    state (device-transferred by the caller as jit inputs): each env
    entry is a (sorted_labels, values) pair.

    ``depth`` is the maximum nesting depth of string calls in the
    dataflow's expressions (collect_keys reports it): a chained
    upper(trim(x)) needs the ``upper`` table to cover ``trim``'s RESULT
    strings, so tables are rebuilt depth times. A dictionary-size
    fixpoint would NOT terminate — generative functions (concat) grow
    the dictionary on every pass when applied to their own outputs."""
    fn_keys = sorted(set(keys))
    tables: dict = {}
    for _ in range(max(1, depth)):
        tables = {k: _CACHE.table(k) for k in fn_keys}
    return {
        k: (jnp.asarray(l), jnp.asarray(v))
        for k, (l, v) in tables.items()
    }


def lookup(table: tuple, codes):
    """Device-side table lookup: searchsorted over the sorted label
    array, then gather. Valid codes always hit exactly (tables cover
    the whole dictionary); padding rows gather garbage that downstream
    validity masks drop."""
    labels, values = table
    idx = jnp.searchsorted(labels, codes)
    return values[jnp.clip(idx, 0, values.shape[0] - 1)]


# -- render-time key collection ----------------------------------------------


def collect_keys(rel) -> tuple:
    """(keys, depth) for a MIR relation tree's expressions: the
    'str:*' function keys and
    the maximum string-call nesting depth (build_env pass count).
    Called by the render layer so each Dataflow's step only carries the
    tables it uses."""
    from ..repr.schema import ColumnType
    from . import relation as mir
    from . import scalar as ms

    keys: set = set()
    max_depth = [0]

    def str_depth(e) -> int:
        d = 0
        for f in getattr(e, "__dataclass_fields__", {}):
            v = getattr(e, f)
            if isinstance(v, ms.ScalarExpr):
                d = max(d, str_depth(v))
            elif isinstance(v, tuple):
                for x in v:
                    if isinstance(x, ms.ScalarExpr):
                        d = max(d, str_depth(x))
        if isinstance(e, ms.CallVariadic) and e.func.startswith(
            ms.STRING_FUNC_PREFIX
        ):
            d += 1
        return d

    def walk_scalar(e, schema):
        if isinstance(e, ms.CallVariadic) and e.func.startswith(
            ms.STRING_FUNC_PREFIX
        ):
            fn = e.func[len(ms.STRING_FUNC_PREFIX):]
            keys.add(ms._string_func_key(fn, e.exprs[1:]))
            max_depth[0] = max(max_depth[0], str_depth(e))
        for f in getattr(e, "__dataclass_fields__", {}):
            v = getattr(e, f)
            if isinstance(v, ms.ScalarExpr):
                walk_scalar(v, schema)
            elif isinstance(v, tuple):
                for x in v:
                    if isinstance(x, ms.ScalarExpr):
                        walk_scalar(x, schema)

    def walk(node):
        for ch in node.children():
            walk(ch)
        if isinstance(node, mir.Map):
            sch = node.input.schema()
            for e in node.scalars:
                walk_scalar(e, sch)
        elif isinstance(node, mir.Filter):
            sch = node.input.schema()
            for e in node.predicates:
                walk_scalar(e, sch)
        elif isinstance(node, mir.Join):
            sch = node.schema()
            for cls in node.equivalences:
                for e in cls:
                    walk_scalar(e, sch)
        elif isinstance(node, mir.Reduce):
            sch = node.input.schema()
            for a in node.aggregates:
                walk_scalar(a.expr, sch)
        elif isinstance(node, mir.FlatMap):
            sch = node.input.schema()
            for f in getattr(node, "__dataclass_fields__", {}):
                v = getattr(node, f)
                if isinstance(v, tuple):
                    for x in v:
                        if isinstance(x, ms.ScalarExpr):
                            walk_scalar(x, sch)

    walk(rel)
    return keys, max_depth[0]
