"""Compile ledger: every XLA compile, anywhere, becomes a record.

ISSUE 12 tentpole (b), feeding ROADMAP item 4 (the ahead-of-time
program bank): cold XLA compiles are the worst real-hardware numbers
we have (~26s index step, 112s 4-operand sort, 227s q9 planning —
PERF_NOTES), yet nothing attributed wall-clock to them. This module
makes compilation a COUNTED surface: every jit program the system
builds (dataflow step/span/compact programs, donated variants, peek
gather programs) is wrapped with :func:`ledger_jit`, and each actual
XLA compile records ``(program kind, dataflow name, dataflow
fingerprint, tier vector, wall seconds, hit|miss)`` into a bounded
per-process ring.

Hit/miss semantics are the PROGRAM-BANK question, not jax's: a
``miss`` means this (kind, fingerprint, tier) was never compiled in
this process before; a ``hit`` means the same program was compiled
AGAIN (a re-install, a restart re-render, a fresh jit wrapper after
tier growth re-deriving an identical program). The total seconds spent
on hits is exactly the wall-clock a cross-process program bank keyed
by (fingerprint, tier) would recover.

Detection rides ``jax.jit``'s own per-signature cache
(``fn._cache_size()``): a call that grows the cache paid a trace +
compile, and only then does the wrapper touch the ledger — the
steady-state dispatch path pays two C attribute calls and a
perf_counter read, no tree flattening, no device sync (the wrapper is
registered with the host-sync linter).

Replica processes piggyback their records on Frontiers responses (the
span/verdict pattern); the controller ingests them, deduping by pid so
in-process replicas (which share this ledger) never double-report.
Surfaces: the ``mz_compile_log`` introspection relation, the
``mz_compile_*`` /metrics families, and EXPLAIN ANALYSIS's
``compiles:`` block.

With a program bank configured (ISSUE 16, compile/bank.py) every
``ledger_jit`` site becomes a bank lookup point. First sight of a
``(kind, fingerprint, tier)`` in this process consults the bank: a
usable entry deserializes in milliseconds and records ``bank_hit``
(attrs carry the compile seconds the hit recovered); a bank miss
compiles AHEAD-OF-TIME (``fn.lower(...).compile()`` — one trace, one
compile, and the executable in hand) and writes the entry back. The
resolved executable is routed directly on subsequent calls. Bank-off
dispatch is byte-identical to the pre-bank hot path.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time as _time
from collections import deque
from dataclasses import dataclass, field

from . import lockcheck


@dataclass
class CompileRecord:
    kind: str  # step | step_donated | span | compact | peek_* | ...
    name: str  # dataflow (or program owner) name
    fingerprint: str  # stable identity of the rendered program family
    tier: str  # tier vector: capacity/shape signature of this compile
    seconds: float
    # "miss" (first sight, compiled) | "hit" (recompiled a known key)
    # | "xla_hit" (the AOT compile was a load from the XLA persistent cache)
    # | "bank_hit" (served from the program bank — no XLA compile;
    # seconds is the deserialize wall, attrs["recovered_seconds"] the
    # compile wall it skipped)
    cache: str
    when: float = 0.0  # wall-clock stamp
    pid: int = 0
    process: str = ""
    attrs: dict = field(default_factory=dict)

    def to_wire(self) -> tuple:
        return (
            self.kind, self.name, self.fingerprint, self.tier,
            self.seconds, self.cache, self.when, self.pid,
            self.process, dict(self.attrs),
        )

    @classmethod
    def from_wire(cls, t: tuple) -> "CompileRecord":
        return cls(*t[:9], attrs=t[9])


class CompileLedger:
    # Hit/miss memory: one entry per distinct (kind, fingerprint,
    # tier) ever compiled, bounded so a long-lived deployment serving
    # endless distinct ad-hoc programs cannot leak (oldest keys evict
    # first; an evicted key's recompile re-classifies as "miss", which
    # only UNDERSTATES the bankable wall).
    SEEN_CAP = 32768

    def __init__(self, capacity: int = 4096):
        # Tracked (ISSUE 17): every ledger_jit site in any thread takes
        # this lock; the race detector also watches the _seen memory
        # through the lockcheck shared-state shims.
        from .lockcheck import tracked_lock

        self._lock = tracked_lock("compile.ledger")
        self._buf: deque[CompileRecord] = deque(maxlen=capacity)
        self._ingested: deque[CompileRecord] = deque(maxlen=capacity)
        self._seen: dict = {}  # insertion-ordered: FIFO eviction
        self._ship: deque | None = None
        self._pid = os.getpid()
        self._metrics = None

    def _metric_handles(self):
        if self._metrics is None:
            from .metrics import REGISTRY

            self._metrics = (
                REGISTRY.get_or_create(
                    "counter", "mz_compile_total",
                    "XLA program compiles observed by the ledger",
                ),
                REGISTRY.get_or_create(
                    "counter", "mz_compile_misses_total",
                    "compiles of a never-before-seen "
                    "(kind, fingerprint, tier) key",
                ),
                REGISTRY.get_or_create(
                    "counter", "mz_compile_hits_total",
                    "recompiles of an already-seen key — the wall "
                    "the program bank (ROADMAP 4) would recover",
                ),
                REGISTRY.get_or_create(
                    "histogram", "mz_compile_seconds",
                    "wall seconds per observed compile",
                    buckets=(
                        0.01, 0.05, 0.1, 0.5, 1, 5, 10, 30, 60, 120,
                        300,
                    ),
                ),
                REGISTRY.get_or_create(
                    "counter", "mz_compile_bank_hits_total",
                    "programs served from the persistent AOT bank "
                    "(deserialized, no XLA compile)",
                ),
                REGISTRY.get_or_create(
                    "counter", "mz_compile_bank_misses_total",
                    "compiles whose key was absent from the bank "
                    "(entry written back after the compile)",
                ),
            )
        return self._metrics

    # -- recording ------------------------------------------------------------
    def record(
        self,
        kind: str,
        name: str,
        fingerprint: str,
        tier: str,
        seconds: float,
        cache: str | None = None,
        **attrs,
    ) -> CompileRecord:
        key = (kind, fingerprint, tier)
        with self._lock:
            lockcheck.shared_write("compile_ledger.seen")
            if cache is None:
                if key in self._seen:
                    cache = "hit"
                else:
                    # Bounded-_seen misclassification fix (ISSUE 16
                    # satellite): an evicted key's recompile used to
                    # re-classify as "miss" — harmless while hit/miss
                    # was pure measurement, wrong once the bank serves
                    # the key. The bank's on-disk entry is the durable
                    # _seen: if it holds the key, this compile is a
                    # re-compile of a known program, never a cold miss.
                    cache = "hit" if self._bank_has(key) else "miss"
            self._seen[key] = True
            while len(self._seen) > self.SEEN_CAP:
                self._seen.pop(next(iter(self._seen)))
            rec = CompileRecord(
                kind, name, fingerprint, tier, seconds, cache,
                when=_time.time(), pid=os.getpid(),
                process=f"pid{os.getpid()}", attrs=attrs,
            )
            self._buf.append(rec)
            if self._ship is not None:
                self._ship.append(rec)
        handles = self._metric_handles()
        total, misses, hits, hist = handles[:4]
        bank_hits, bank_misses = handles[4:]
        if cache == "bank_hit":
            bank_hits.inc()
        else:
            total.inc()
            (misses if cache == "miss" else hits).inc()
            hist.observe(seconds)
            if attrs.get("bank") == "miss":
                bank_misses.inc()
        return rec

    @staticmethod
    def _bank_has(key: tuple) -> bool:
        """Durable seen-check against the program bank; never raises
        (called under the ledger lock on the compile path)."""
        try:
            from ..compile import bank as _bank

            b = _bank.get_bank()
            return b is not None and b.has(*key)
        except Exception:
            return False

    # -- cross-process shipping (Frontiers piggyback) ------------------------
    def enable_ship(self, capacity: int = 4096) -> None:
        with self._lock:
            if self._ship is None:
                self._ship = deque(maxlen=capacity)

    def drain_shippable(self) -> list[tuple]:
        if self._ship is None or not self._ship:
            return []
        with self._lock:
            out = [r.to_wire() for r in self._ship]
            self._ship.clear()
        return out

    def ingest(self, wire_records: list, process: str = "") -> None:
        me = os.getpid()
        with self._lock:
            for t in wire_records:
                rec = CompileRecord.from_wire(t)
                if rec.pid == me:
                    continue  # in-process replica: already in _buf
                if process:
                    rec.process = process
                self._ingested.append(rec)

    # -- introspection --------------------------------------------------------
    def records(self) -> list[CompileRecord]:
        with self._lock:
            return list(self._buf) + list(self._ingested)

    def summary(self, names: set | None = None) -> dict:
        """Totals (optionally scoped to dataflow ``names``): the
        EXPLAIN ANALYSIS surface. ``bank_hit`` records are
        NOT compiles — they count separately (``bank_hits``,
        ``bank_seconds_recovered`` = the compile wall they skipped),
        so ``compiles``/``misses``/``hits`` keep their pre-bank
        meaning."""
        recs = self.records()
        if names is not None:
            recs = [r for r in recs if r.name in names]
        banked = [r for r in recs if r.cache == "bank_hit"]
        recs = [r for r in recs if r.cache != "bank_hit"]
        out = {
            "compiles": len(recs),
            "misses": sum(1 for r in recs if r.cache == "miss"),
            "hits": sum(1 for r in recs if r.cache == "hit"),
            "seconds": round(sum(r.seconds for r in recs), 3),
            "hit_seconds": round(
                sum(r.seconds for r in recs if r.cache == "hit"), 3
            ),
            "bank_hits": len(banked),
            "bank_misses": sum(
                1 for r in recs if r.attrs.get("bank") == "miss"
            ),
            "bank_seconds_recovered": round(
                sum(
                    float(r.attrs.get("recovered_seconds", 0.0))
                    for r in banked
                ),
                3,
            ),
            "by_kind": {},
        }
        for r in recs:
            k = out["by_kind"].setdefault(
                r.kind, {"compiles": 0, "seconds": 0.0}
            )
            k["compiles"] += 1
            k["seconds"] = round(k["seconds"] + r.seconds, 3)
        return out

    def clear(self) -> None:
        with self._lock:
            lockcheck.shared_write("compile_ledger.seen")
            self._buf.clear()
            self._ingested.clear()
            self._seen.clear()
            if self._ship is not None:
                self._ship.clear()


LEDGER = CompileLedger()


def expr_fingerprint(obj) -> str:
    """Stable short fingerprint of a rendered expression (the PR 1
    fingerprint-stability work makes pickled MIR deterministic across
    processes and installs — the program-bank key's first half)."""
    import pickle

    try:
        payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    except Exception:
        payload = repr(obj).encode()
    return hashlib.blake2b(payload, digest_size=8).hexdigest()


def tier_vector(args: tuple, static: str = "") -> str:
    """Tier vector of one call signature: a digest of every array
    leaf's (shape, dtype) plus the total operand bytes — the program
    bank key's second half. ``static`` carries the capacity tiers a
    program bakes in at TRACE time (they show in no argument's shape):
    without it a program regrown after an overflow would share its
    key — and be served from the bank as — its smaller twin."""
    import jax

    h = hashlib.blake2b(digest_size=6)
    h.update(static.encode())
    total = 0
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            h.update(repr(leaf)[:32].encode())
            continue
        dt = getattr(leaf, "dtype", None)
        h.update(str((tuple(shape), str(dt))).encode())
        try:
            total += leaf.size * leaf.dtype.itemsize
        except (AttributeError, TypeError):
            pass
    return f"{h.hexdigest()}:{total}"


# Executables the XLA persistent compilation cache served to THIS thread
# (jax.monitoring fires the hit event synchronously in the compiling
# thread). `_resolve_route` reads the counter around its AOT compile.
_xla_cache_hits = threading.local()
_xla_cache_listener_installed = False


def _watch_xla_cache_hits() -> None:
    global _xla_cache_listener_installed
    if _xla_cache_listener_installed:
        return
    _xla_cache_listener_installed = True
    import jax.monitoring

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            _xla_cache_hits.n = getattr(_xla_cache_hits, "n", 0) + 1

    jax.monitoring.register_event_listener(on_event)


class LedgeredJit:
    """A ``jax.jit`` wrapper that records actual compiles. With no
    bank configured (the default) the hot path costs two C attribute
    reads and a perf_counter call; ledger work happens only on the
    (seconds-long) compile itself. With a bank, dispatch routes
    through per-tier resolved executables (one dict probe + a
    tier_vector digest — microseconds against the ~ms device step),
    and first sight of a tier goes bank-lookup-then-AOT-compile."""

    __slots__ = (
        "fn", "kind", "name", "fingerprint", "ledger", "static",
        "_routes",
    )

    def __init__(self, fn, kind, name, fingerprint, ledger=None,
                 static: str = ""):
        self.fn = fn
        self.kind = kind
        self.name = name
        self.fingerprint = fingerprint
        self.ledger = ledger if ledger is not None else LEDGER
        self.static = static
        self._routes = {}

    def __call__(self, *args, **kwargs):
        from ..compile import bank as _bank

        b = _bank.BANK if _bank._resolved else _bank.get_bank()
        if b is not None:
            return self._banked_call(b, args, kwargs)
        return self._plain_call(args, kwargs)

    def _plain_call(self, args, kwargs):
        fn = self.fn
        try:
            n0 = fn._cache_size()
        except (AttributeError, TypeError):  # jax without the API
            return fn(*args, **kwargs)
        t0 = _time.perf_counter()
        out = fn(*args, **kwargs)
        if fn._cache_size() > n0:
            self.ledger.record(
                self.kind,
                self.name,
                self.fingerprint,
                tier_vector(args, self.static),
                _time.perf_counter() - t0,
            )
        return out

    # -- program-bank dispatch (ISSUE 16) ---------------------------------
    def _banked_call(self, b, args, kwargs):
        tier = tier_vector(args, self.static)
        route = self._routes.get(tier)
        if route is None:
            route = self._resolve_route(b, tier, args, kwargs)
            self._routes[tier] = route
        if route is False:
            # Unbankable program (serializer/lowering limits): the
            # plain jit path, with normal ledger accounting.
            return self._plain_call(args, kwargs)
        try:
            return route(*args, **kwargs)
        except Exception:
            # A resolved executable the runtime won't accept (layout
            # or structure drift) must degrade to a recompile, never
            # to an error or a wrong result. Counted: the fallback
            # must be visible (mz_program_bank_errors_total).
            b.note_error()
            self._routes[tier] = False
            return self._plain_call(args, kwargs)

    def _resolve_route(self, b, tier, args, kwargs):
        key = (self.kind, self.fingerprint, tier)
        t0 = _time.perf_counter()
        loaded = b.lookup(*key)
        if loaded is not None:
            compiled, meta = loaded
            self.ledger.record(
                self.kind, self.name, self.fingerprint, tier,
                _time.perf_counter() - t0,
                cache="bank_hit",
                recovered_seconds=float(meta.get("seconds", 0.0)),
            )
            return compiled
        # Bank miss: compile ahead-of-time so the executable is in
        # hand for both dispatch and the write-back (calling the jit
        # would compile internally and keep the Compiled out of
        # reach).
        _watch_xla_cache_hits()
        hits0 = getattr(_xla_cache_hits, "n", 0)
        try:
            compiled = self.fn.lower(*args, **kwargs).compile()
        except Exception:
            # Unbankable (or uncompilable: the plain call that follows
            # raises the compiler's own error). Counted either way.
            b.note_error()
            return False
        secs = _time.perf_counter() - t0
        xla_hit = getattr(_xla_cache_hits, "n", 0) != hits0
        self.ledger.record(
            self.kind, self.name, self.fingerprint, tier, secs,
            # A load from the XLA persistent cache is not a cold
            # compile: the ledger says so ("xla_hit").
            cache="xla_hit" if xla_hit else None,
            bank="miss",
        )
        if not xla_hit:
            b.store(
                self.kind, self.fingerprint, tier, compiled,
                seconds=secs, name=self.name,
            )
        # else: the "compile" was a load from the XLA persistent cache.
        # Such an executable is NOT exported: serializing a rehydrated
        # executable damages it on the installed XLA:CPU (its kernels
        # go missing: "Function ... not found" at the next dispatch),
        # and the XLA cache already holds it for the next process.
        return compiled

    def lower(self, *args, **kwargs):
        return self.fn.lower(*args, **kwargs)

    def _cache_size(self):
        return self.fn._cache_size()


def ledger_jit(fn, kind: str, name: str, fingerprint: str,
               ledger=None, static: str = "") -> LedgeredJit:
    """Wrap an already-jitted callable so its compiles hit the ledger.
    ``static``: trace-time capacity tiers (see ``tier_vector``)."""
    return LedgeredJit(fn, kind, name, fingerprint, ledger, static)
