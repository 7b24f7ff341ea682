"""Relation schemas and column types.

Analog of the reference's ``RelationDesc`` / ``SqlScalarType``
(``src/repr/src/relation.rs``) and ``Datum`` (``src/repr/src/scalar.rs:85``),
re-cast columnar: a relation is a struct-of-arrays, each column a fixed-width
device array. Variable-width data (strings) is dictionary-encoded host-side
(int32 codes on device), matching SURVEY.md §7's design stance.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np


class ColumnType(enum.Enum):
    """Device-representable scalar types.

    Subset of the reference's 30 Datum variants (src/repr/src/scalar.rs:85)
    that covers the north-star workloads; exotic types (jsonb, ranges,
    arbitrary-precision numeric) are deferred to host-side fallback.
    """

    BOOL = "bool"
    INT32 = "int32"
    INT64 = "int64"
    FLOAT64 = "float64"
    # Days since the UNIX epoch, like reference Datum::Date.
    DATE = "date"
    # Milliseconds since the UNIX epoch, like mz Timestamp (repr/src/timestamp.rs:46).
    TIMESTAMP = "timestamp"
    # Fixed-point decimal stored as a scaled int64 (reference uses dec i128;
    # scale lives in the Column). Exact accumulation like Accum semigroup
    # (compute/src/render/reduce.rs:1357).
    DECIMAL = "decimal"
    # Dictionary code (int32) into a host-side StringDictionary.
    STRING = "string"

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(_DTYPES[self])

    @property
    def is_orderable_on_device(self) -> bool:
        # Every type, including STRING: dictionary codes are assigned by
        # order-preserving gap labeling (StringDictionary), so integer
        # code comparison == lexicographic string comparison.
        return True


_DTYPES = {
    ColumnType.BOOL: np.bool_,
    ColumnType.INT32: np.int32,
    ColumnType.INT64: np.int64,
    ColumnType.FLOAT64: np.float64,
    ColumnType.DATE: np.int32,
    ColumnType.TIMESTAMP: np.int64,
    ColumnType.DECIMAL: np.int64,
    ColumnType.STRING: np.int64,  # order-preserving dictionary labels
}

# Timestamps of the virtual time axis (not SQL timestamps): u64 ms since epoch,
# matching repr/src/timestamp.rs:46.
TIME_DTYPE = np.uint64
# Update multiplicities: i64, matching repr/src/diff.rs.
DIFF_DTYPE = np.int64


@dataclass(frozen=True)
class Column:
    name: str
    ctype: ColumnType
    nullable: bool = False
    # Decimal scale: value = unscaled / 10**scale.
    scale: int = 0

    @property
    def dtype(self) -> np.dtype:
        return self.ctype.dtype


@dataclass(frozen=True)
class Schema:
    """Column layout of a collection (RelationDesc analog)."""

    columns: tuple[Column, ...]

    def __init__(self, columns):
        object.__setattr__(self, "columns", tuple(columns))

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(c.name for c in self.columns)

    @property
    def arity(self) -> int:
        return len(self.columns)

    def __len__(self) -> int:
        return len(self.columns)

    def __getitem__(self, i: int) -> Column:
        return self.columns[i]

    def index_of(self, name: str) -> int:
        for i, c in enumerate(self.columns):
            if c.name == name:
                return i
        raise KeyError(name)

    def project(self, indices) -> "Schema":
        return Schema([self.columns[i] for i in indices])

    def concat(self, other: "Schema") -> "Schema":
        return Schema(self.columns + other.columns)

    def rename(self, names) -> "Schema":
        assert len(names) == len(self.columns)
        return Schema(
            [
                Column(n, c.ctype, c.nullable, c.scale)
                for n, c in zip(names, self.columns)
            ]
        )


class DictExhausted(RuntimeError):
    """A gap in the label space ran out between two dense neighbors.
    Recoverable: callers that own device-resident state catch this,
    call ``rebalance()``, remap host-side literals, and rebuild their
    dataflows (durable state is safe — persist parts store the actual
    strings, storage/persist/codec.py)."""


class _SortedStrings:
    """The dictionary's strings in lexicographic order: one long sorted
    list and a short sorted list of the newest, merged into it once the
    short one has ``RECENT`` strings or a reader asks for every string.
    An insert into one list of millions moves all of it, and a text
    column gains strings every tick."""

    RECENT = 8192

    def __init__(self, strings=()):
        import threading

        self._main: list[str] = list(strings)
        self._recent: list[str] = []
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._main) + len(self._recent)

    def neighbors(self, s: str) -> tuple:
        """The strings just below and just above ``s`` (None at an
        end)."""
        import bisect

        lo = hi = None
        with self._lock:
            for run in (self._main, self._recent):
                i = bisect.bisect_left(run, s)
                if i > 0 and (lo is None or run[i - 1] > lo):
                    lo = run[i - 1]
                if i < len(run) and (hi is None or run[i] < hi):
                    hi = run[i]
        return lo, hi

    def add(self, s: str) -> None:
        import bisect

        with self._lock:
            bisect.insort(self._recent, s)
            if len(self._recent) >= self.RECENT:
                self._merge()

    def add_many(self, strings: list) -> None:
        """Add sorted ``strings``: one merge, however many."""
        with self._lock:
            self._recent = sorted(self._recent + strings)
            if len(self._recent) >= self.RECENT:
                self._merge()

    def all(self) -> list[str]:
        """Every string, sorted; the caller must not change the list."""
        with self._lock:
            self._merge()
            return self._main

    def _merge(self) -> None:
        if self._recent:
            # two sorted runs: timsort merges them in linear time, and
            # rebinds, so a list handed out by all() never changes
            self._main = sorted(self._main + self._recent)
            self._recent = []


class DictSnapshot:
    """An immutable epoch-coherent read view of the dictionary (see
    StringDictionary.snapshot). Decodes codes of ITS generation even
    after a later rebalance has relabeled the live dictionary."""

    __slots__ = ("_codes", "_by_code", "_sorted", "epoch")

    def __init__(self, codes, by_code, sorted_, epoch):
        self._codes = codes
        self._by_code = by_code
        self._sorted = sorted_
        self.epoch = epoch

    def decode(self, code: int) -> str:
        return self._by_code[int(code)]

    def decode_many(self, codes) -> list[str]:
        return [self._by_code[int(c)] for c in np.asarray(codes)]

    def items_sorted(self) -> list[tuple[int, str]]:
        return [(self._codes[s], s) for s in self._sorted.all()]


class StringDictionary:
    """Host-side string dictionary: str <-> ORDER-PRESERVING int64 code.

    Codes are assigned by gap labeling in a 2^63-wide label space:
    a new string gets the midpoint of its lexicographic neighbors'
    labels (append/prepend get a fixed stride so sorted bulk loads do
    not bisect the space). Integer comparison of codes == lexicographic
    comparison of strings, FOREVER — codes never change once assigned,
    so device arrangements sorted by code lanes stay sorted as the
    dictionary grows (the property that unblocks ORDER BY / MIN / MAX /
    TopK over text on device; the reference gets it from sortable Row
    bytes, repr/src/row.rs + doc/developer/row-encoding.md).

    Labels are CONTENT-INTERPOLATED into the neighbor gap: the new
    string's fractional position between its neighbors (computed from
    the bytes after the neighbors' common prefix) picks the label, so
    monotone insertion runs spread proportionally through the gap
    instead of halving it per insert (plain midpoint labeling dies in
    ~60 nested inserts; interpolation handles the common sorted-bulk
    and generated-result patterns). A truly adversarial order can
    still exhaust a gap; that raises rather than silently relabeling,
    since relabeling would corrupt device-resident state.
    """

    MIN_LABEL = -(1 << 62)
    MAX_LABEL = 1 << 62

    def __init__(self):
        import threading

        self._sorted = _SortedStrings()
        self._codes: dict[str, int] = {}
        self._by_code: dict[int, str] = {}
        self.version = 0  # bumped on every insert (env-cache key)
        # Relabeling epoch: bumped by rebalance(). Every holder of codes
        # OUTSIDE this object (env caches, device arrangements, MIR
        # literals) must treat a changed epoch as total invalidation.
        self.epoch = 0
        self._lock = threading.RLock()
        # Process-wide recovery hooks: called (with the old->new remap)
        # inside rebalance() so in-process holders of codes (controller
        # command history, replica dataflows) can remap/rebuild.
        self._listeners: list = []

    def snapshot(self) -> "DictSnapshot":
        """An epoch-coherent read view. rebalance() REBINDS the internal
        maps (never mutates them in place), so a snapshot taken before a
        rebalance keeps decoding pre-rebalance codes correctly while the
        live dictionary already serves the new labeling — multi-row read
        operations (env-table builds, result decodes, persist part
        encodes) capture one snapshot at entry so a concurrent rebalance
        can never make them mix labelings mid-operation (torn reads were
        observed as KeyError on decode and garbage env tables)."""
        with self._lock:
            return DictSnapshot(
                self._codes, self._by_code, self._sorted, self.epoch
            )

    def lock(self):
        """The dictionary's reentrant lock: held by rebalance() for the
        whole relabel+listener cycle. Long read-modify cycles that must
        not interleave with a rebalance (the env-table build, which both
        reads items and encodes result strings) run under it."""
        return self._lock

    def add_rebalance_listener(self, fn) -> None:
        with self._lock:
            self._listeners.append(fn)

    def remove_rebalance_listener(self, fn) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    def rebalance(self) -> dict:
        """Reassign every code evenly across the label space (order
        preserved) after gap exhaustion. Returns the {old: new} remap.

        Device-resident arrangements and host caches keyed by code
        become garbage: registered listeners fire synchronously (under
        the lock) so the process's holders can remap host literals and
        schedule dataflow rebuilds. Cross-PROCESS holders (a remote
        replica's own dictionary) are not reached — the separate-process
        replica path keeps its own dictionary and must hit its own
        exhaustion independently (documented limitation, L7)."""
        with self._lock:
            strings = self._sorted.all()
            n = len(strings)
            span = self.MAX_LABEL - self.MIN_LABEL
            remap: dict[int, int] = {}
            new_codes: dict[str, int] = {}
            new_by_code: dict[int, str] = {}
            for i, s in enumerate(strings):
                new = self.MIN_LABEL + (i + 1) * span // (n + 1)
                remap[self._codes[s]] = new
                new_codes[s] = new
                new_by_code[new] = s
            # REBIND (never mutate) so pre-rebalance snapshots stay
            # coherent: their maps keep the old labeling; _sorted is
            # rebound too because encode() inserts into it in place.
            self._codes = new_codes
            self._by_code = new_by_code
            self._sorted = _SortedStrings(strings)
            self.version += 1
            self.epoch += 1
            for fn in list(self._listeners):
                fn(remap)
            return remap

    @staticmethod
    def _frac(lo_s: str | None, hi_s: str | None, s: str) -> float:
        """Approximate fractional position of ``s`` in (lo_s, hi_s),
        read from the 6 bytes after the neighbors' common prefix."""
        lb = lo_s.encode() if lo_s is not None else b""
        hb = hi_s.encode() if hi_s is not None else None
        sb = s.encode()
        i = 0
        if hb is not None:
            while i < len(lb) and i < len(hb) and lb[i] == hb[i]:
                i += 1

        def val(b) -> int:
            v = 0
            for k in range(6):
                v = (v << 8) | (b[i + k] if i + k < len(b) else 0)
            return v

        lv = val(lb)
        hv = val(hb) if hb is not None else 1 << 48
        sv = val(sb)
        if hv <= lv:
            return 0.5
        f = (sv - lv) / (hv - lv)
        return min(max(f, 1e-4), 1.0 - 1e-4)

    def encode(self, s: str) -> int:
        code = self._codes.get(s)
        if code is not None:
            return code
        with self._lock:
            code = self._codes.get(s)
            if code is not None:
                return code
            lo_s, hi_s = self._sorted.neighbors(s)
            lo = self._codes[lo_s] if lo_s is not None else self.MIN_LABEL
            hi = self._codes[hi_s] if hi_s is not None else self.MAX_LABEL
            gap = hi - lo
            if gap < 2:
                raise DictExhausted(
                    "string dictionary label space exhausted between "
                    f"{lo_s!r} and {hi_s!r}"
                )
            f = self._frac(lo_s, hi_s, s)
            code = lo + max(1, min(gap - 1, int(gap * f)))
            self._sorted.add(s)
            self._codes[s] = code
            self._by_code[code] = s
            self.version += 1
            return code

    def encode_many(self, strings) -> np.ndarray:
        return np.asarray([self.encode(s) for s in strings], dtype=np.int64)

    def encode_bulk(self, strings) -> None:
        """Insert a SET of new strings with positional gap division.

        Content interpolation (encode) fundamentally mislabels
        long-common-prefix families: the whole family maps to a tiny
        content interval, so one-at-a-time inserts pack its members into
        a sliver of the gap regardless of how many there are (observed:
        case-mapped catalog JSON families driving gaps to 1). A bulk
        insert knows every member up front, so each run of new strings
        falling between two existing neighbors divides that gap EVENLY
        by position — 10^6 strings in one gap get even spacing. Env
        table builds, parts of many strings and generated text columns
        use this: a string that falls alone into a gap takes its middle,
        which random text (clustered on common words, where content
        interpolation places it beside a neighbor) needs."""
        with self._lock:
            new = sorted(
                {s for s in strings if s not in self._codes}
            )
            if not new:
                return
            # Group the new strings into runs per existing-neighbor gap.
            runs: list[tuple[int, int, list[str]]] = []
            gap = None
            for s in new:
                lo_s, hi_s = self._sorted.neighbors(s)
                if (lo_s, hi_s) == gap:
                    runs[-1][2].append(s)
                    continue
                gap = (lo_s, hi_s)
                runs.append((
                    self._codes[lo_s] if lo_s is not None
                    else self.MIN_LABEL,
                    self._codes[hi_s] if hi_s is not None
                    else self.MAX_LABEL,
                    [s],
                ))
            # Validate EVERY run before mutating anything: a partial
            # insert would leave _sorted stale against _codes, and a
            # concurrent encode() could then hand out an already-taken
            # label (exception atomicity).
            # A run past either end of a non-empty dictionary is spaced
            # like the whole dictionary, not spread over the rest of the
            # label space: the next run there finds room again.
            density = (self.MAX_LABEL - self.MIN_LABEL) // (
                len(self._codes) + len(new) + 1
            )
            placed = []
            for lo, hi, run in runs:
                step = (hi - lo) // (len(run) + 1)
                if self._codes and (
                    lo == self.MIN_LABEL or hi == self.MAX_LABEL
                ):
                    step = min(step, density)
                if step < 1:
                    raise DictExhausted(
                        f"bulk insert of {len(run)} strings does not "
                        f"fit in gap {hi - lo} at {run[0]!r}"
                    )
                if lo == self.MIN_LABEL and hi != self.MAX_LABEL:
                    first = hi - len(run) * step
                else:
                    first = lo + step
                placed.append((first, step, run))
            for first, step, run in placed:
                for j, s in enumerate(run):
                    # distinct and inside (lo, hi): lo/hi exclusive
                    code = first + j * step
                    self._codes[s] = code
                    self._by_code[code] = s
            self._sorted.add_many(new)
            self.version += 1

    def decode(self, code: int) -> str:
        return self._by_code[int(code)]

    def decode_many(self, codes) -> list[str]:
        return [self._by_code[int(c)] for c in np.asarray(codes)]

    def items_sorted(self) -> list[tuple[int, str]]:
        """(code, string) pairs in lexicographic (== code) order."""
        return [(self._codes[s], s) for s in self._sorted.all()]

    def __len__(self) -> int:
        return len(self._sorted)


# A process-global dictionary registry keyed by (collection, column) is
# overkill for now: a single shared dictionary per process is correct (codes
# are only compared for equality) and keeps joins on string columns trivial.
GLOBAL_DICT = StringDictionary()


_EPOCH_DATE = None  # lazy datetime import


def days_to_date(days: int):
    import datetime as _dt

    return _dt.date(1970, 1, 1) + _dt.timedelta(days=int(days))


def date_to_days(d) -> int:
    import datetime as _dt

    return (d - _dt.date(1970, 1, 1)).days


def ms_to_ts(ms: int):
    import datetime as _dt

    return _dt.datetime(1970, 1, 1) + _dt.timedelta(
        milliseconds=int(ms)
    )


def ts_to_ms(ts) -> int:
    import datetime as _dt

    return int((ts - _dt.datetime(1970, 1, 1)).total_seconds() * 1000)


def decode_result_rows(schema: Schema, cols, nulls, time, diff) -> list:
    """Host update arrays -> result rows (vals..., time, diff) with
    STRING dictionary codes decoded to Python strings and NULLs as None.
    Codes are PROCESS-LOCAL, so every surface that hands rows across a
    process boundary (peek responses, SUBSCRIBE events) must decode
    through this one helper."""
    import decimal as _dec

    out = []
    pre_decoded = [
        getattr(c, "dtype", None) == np.dtype(object) for c in cols
    ]
    # One dictionary snapshot for the whole batch: a concurrent
    # rebalance must not relabel codes mid-decode.
    gdict = GLOBAL_DICT.snapshot()
    for i in range(len(diff)):
        vals = []
        for j, col in enumerate(schema.columns):
            if nulls[j] is not None and bool(nulls[j][i]):
                vals.append(None)
            elif pre_decoded[j]:
                # Edge-finalized basic-aggregate columns arrive as raw
                # Python strings (finalize_basic_columns) — they never
                # enter the dictionary.
                vals.append(cols[j][i])
            elif col.ctype is ColumnType.STRING:
                vals.append(gdict.decode(int(cols[j][i])))
            elif col.ctype is ColumnType.DECIMAL and col.scale:
                # scaled int -> exact decimal (the user-facing value;
                # _encode_internal re-scales on the way back in)
                vals.append(
                    _dec.Decimal(int(cols[j][i]))
                    / (10 ** col.scale)
                )
            elif col.ctype is ColumnType.DATE:
                vals.append(days_to_date(cols[j][i]))
            elif col.ctype is ColumnType.TIMESTAMP:
                vals.append(ms_to_ts(cols[j][i]))
            else:
                vals.append(cols[j][i].item())
        out.append(tuple(vals) + (int(time[i]), int(diff[i])))
    return out


def parse_text_value(raw: str, col: Column):
    """pg COPY text-format field -> python value for the column type."""
    import datetime as _dt
    import decimal as _dec

    t = col.ctype
    try:
        if t is ColumnType.BOOL:
            s = raw.strip().lower()
            if s in ("t", "true", "1", "yes", "on"):
                return True
            if s in ("f", "false", "0", "no", "off"):
                return False
            raise ValueError(raw)
        if t in (ColumnType.INT32, ColumnType.INT64):
            return int(raw)
        if t is ColumnType.FLOAT64:
            return float(raw)
        if t is ColumnType.DECIMAL:
            return _dec.Decimal(raw)
        if t is ColumnType.DATE:
            s = raw.strip()
            if s.lstrip("-").isdigit():
                return int(s)  # days-since-epoch shorthand
            return (
                _dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)
            ).days
        if t is ColumnType.TIMESTAMP:
            s = raw.strip()
            if s.lstrip("-").isdigit():
                return int(s)  # ms-since-epoch shorthand
            dt = _dt.datetime.fromisoformat(s.replace("T", " "))
            return int(
                (dt - _dt.datetime(1970, 1, 1)).total_seconds() * 1000
            )
        return raw
    except (ValueError, _dec.InvalidOperation) as exc:
        # ValueError here; callers in the SQL layer surface it as a
        # PlanError-compatible statement failure
        raise ValueError(
            f"invalid {t.value} value {raw!r} for column {col.name!r}"
        ) from exc


# The error-stream schema (one column: the error code; expr/errors.py
# maps codes to messages). Every dataflow maintains an arrangement of
# this shape next to its data output — the ok/err collection pair
# (compute/src/render.rs:12-101).
ERR_SCHEMA = Schema([Column("err_code", ColumnType.INT64)])
