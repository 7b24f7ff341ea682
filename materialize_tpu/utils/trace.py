"""Tracing: per-statement trace trees across processes (ISSUE 12).

Analog of the reference's tracing stack (tracing + OpenTelemetry with
runtime-settable filters, SURVEY.md §5): spans record (trace_id,
span_id, parent_id, process, name, start, duration, attributes) into a
bounded per-process ring buffer queryable as the ``mz_trace_spans``
introspection relation; a dynamic level filter mirrors the
``log_filter`` system var (the ``trace_level`` dyncfg).

Cross-process propagation follows the reference's
OpenTelemetryContext-riding-commands pattern: the front end (pgwire /
HTTP) MINTS a trace_id per statement and opens the root span; the
coordinator and controller open child spans on the same thread
(thread-local context stack); CTP commands carry ``{"t": trace_id,
"s": span_id}`` so the replica can :meth:`Tracer.adopt` the remote
parent; and completed replica spans ship back PIGGYBACKED on Frontiers
responses (the PR 5/6 verdict pattern — shipped only when present, so
steady state with tracing off pays nothing). The controller ingests
shipped spans into this process's tracer, so one ``mz_trace_spans``
query shows ONE coherent tree per statement across every process.

Span ids embed the process id (``(pid << 40) | counter``) so ids from
different processes never collide in a merged tree; ingest drops
records whose pid equals ours (an in-process replica shares this
tracer — its spans are already in the ring).

Phases (ISSUE 27): the maintenance path does the same few things many
times a span (wait, fetch, upload, dispatch, readback, append,
publish), and the rings are bounded, so an occurrence is not a record.
``with TRACER.phase(name, **counts)`` inside an open span adds the
occurrence's duration and counts to that span, and when the span
closes it emits ONE child record a phase: start of the first
occurrence, summed duration, ``n`` occurrences, summed counts. A span
that outlives one call (the pipelined span is dispatched in one call
of the worker loop and committed in the next) is held by
``open``/``within``/``close``. Where ``annotate`` is set (the replica
sets ``jax.profiler.TraceAnnotation``; this module imports no jax)
every occurrence also opens ``annotate("mz:<name>")``, so a profiler
session shows the phases on the device trace's own clock.

The recorder is pure host bookkeeping — no device reads, no syncs —
and is registered with the host-sync linter (analysis/host_sync.py) so
a d2h sync can never sneak into the hot recording path.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time as _time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

LEVELS = {"off": 0, "error": 1, "info": 2, "debug": 3}
_INFO = LEVELS["info"]

# Ring capacity (records). The benchmark reads the rings at shutdown,
# about 130 s after the judged window's records were made (the
# profiler's stop alone takes 72-73 s): 46 phase and tick records a
# second in its busiest cell plus the harness's statement spans is
# about 8,000 records (PERF.md section 3). About 6 MB a ring.
RING_CAPACITY = 16384

# Span-id layout: the low 40 bits count, the bits above carry the pid.
_PID_SHIFT = 40


@dataclass
class SpanRecord:
    span_id: int
    parent_id: int | None
    name: str
    start: float
    duration: float
    level: str
    attrs: dict = field(default_factory=dict)
    trace_id: int = 0  # 0 = recorded outside any statement trace
    process: str = ""  # "" = this process (filled on ingest)
    pid: int = 0

    def to_wire(self) -> tuple:
        """Compact tuple for the Frontiers piggyback (attrs must be
        plain scalars/strings — enforced at record time by usage)."""
        return (
            self.span_id, self.parent_id, self.name, self.start,
            self.duration, self.level, dict(self.attrs), self.trace_id,
            self.process, self.pid,
        )

    def attrs_json(self) -> str:
        """The attributes as JSON text ("" when there are none): the
        ``attrs`` column of ``mz_trace_spans``."""
        if not self.attrs:
            return ""
        return json.dumps(self.attrs, sort_keys=True, default=_plain)

    def to_json(self) -> dict:
        """The ``mz_trace_spans`` row as an object, attributes nested:
        one line of the flight recorder's dump, and what
        ``scripts/trace_export.py`` takes."""
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id or 0,
            "process": self.process,
            "name": self.name,
            "level": self.level,
            "start_us": int(self.start * 1e6),
            "duration_us": int(self.duration * 1e6),
            "attrs": self.attrs,
        }

    @classmethod
    def from_wire(cls, t: tuple) -> "SpanRecord":
        (sid, parent, name, start, dur, level, attrs, trace_id,
         process, pid) = t
        return cls(
            sid, parent, name, start, dur, level, attrs, trace_id,
            process, pid,
        )


def _plain(value):
    """JSON fallback for an attribute that is no plain scalar (a numpy
    integer from a shape or a count)."""
    return value.item() if hasattr(value, "item") else str(value)


def _sum_into(total: dict, counts: dict) -> None:
    for k, v in counts.items():
        total[k] = total.get(k, 0) + v


class OpenSpan:
    """A span that is open: the entry of the thread-local context
    stack, and what ``Tracer.open`` hands out. ``phases`` maps a phase
    name to ``[first_start, summed_duration, occurrences, counts]``.
    ``name`` is None for an adopted remote context, which records
    nothing itself."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "level", "wall",
        "t0", "attrs", "phases",
    )

    def __init__(self, trace_id, span_id, parent_id=None, name=None,
                 level="info", attrs=None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.level = level
        self.attrs = attrs
        self.phases: dict = {}
        self.wall = _time.time()
        self.t0 = _time.perf_counter()


class _Phase:
    """One occurrence of a phase of an open span (``Tracer.phase``)."""

    __slots__ = ("_span", "_name", "_counts", "_ann", "_wall", "_t0")

    def __init__(self, span: OpenSpan, name: str, counts: dict, ann):
        self._span = span
        self._name = name
        self._counts = counts
        self._ann = ann

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._wall = _time.time()
        self._t0 = _time.perf_counter()
        return self

    def add(self, **counts) -> None:
        """Counts known only once the work is done (rows, bytes)."""
        _sum_into(self._counts, counts)

    def __exit__(self, *exc):
        dur = _time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        acc = self._span.phases.get(self._name)
        if acc is None:
            self._span.phases[self._name] = [
                self._wall, dur, 1, self._counts,
            ]
        else:
            acc[1] += dur
            acc[2] += 1
            _sum_into(acc[3], self._counts)
        return False


class _NoPhase:
    """What ``Tracer.phase`` hands out when nothing records: false, so
    a caller can skip the work of counting."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def add(self, **counts) -> None:
        pass


_NO_PHASE = _NoPhase()


class Tracer:
    """Per-process span recorder with cross-process context handoff."""

    def __init__(
        self, capacity: int = RING_CAPACITY, process: str = ""
    ):
        self.process = process or f"pid{os.getpid()}"
        self._pid = os.getpid()
        self._base = (self._pid & 0x3FFFFF) << _PID_SHIFT
        self._ids = itertools.count(1)
        self._level = LEVELS["info"]
        self._local = threading.local()
        self._lock = threading.Lock()
        self._buf: deque[SpanRecord] = deque(maxlen=capacity)
        # Ingested remote spans (piggybacked off Frontiers) live in
        # their own ring: clear() of local spans keeps remote history
        # and vice versa is not needed.
        self._ingested: deque[SpanRecord] = deque(maxlen=capacity)
        # Ship queue: records pending piggyback to a controller.
        # Bounded — an unreported replica must not grow without bound.
        self._ship: deque[SpanRecord] | None = None
        # Whether the ship queue holds anything worth a message of its
        # own: a record of the shipping itself is not (``record``'s
        # ``ship_alone``), or every report would cause the next.
        self._ship_news = False
        # Called with "mz:<phase>" at every occurrence of a phase; the
        # context manager it returns brackets the occurrence. The
        # replica sets jax.profiler.TraceAnnotation (coord/replica.py
        # main): a flag test while no profiler session is open.
        self.annotate = None

    # -- dynamic filter (log_filter / trace_level dyncfg analog) ------------
    def set_level(self, level: str) -> None:
        self._level = LEVELS[level]

    @property
    def level(self) -> str:
        for k, v in LEVELS.items():
            if v == self._level:
                return k
        return "info"

    def enabled(self, level: str = "info") -> bool:
        return LEVELS[level] <= self._level

    # -- id minting ----------------------------------------------------------
    def _next_id(self) -> int:
        if os.getpid() != self._pid:
            # Forked child (subprocess replicas exec fresh interpreters,
            # but be safe): re-base so ids stay collision-free.
            self._pid = os.getpid()
            self._base = (self._pid & 0x3FFFFF) << _PID_SHIFT
            self.process = f"pid{self._pid}"
        return self._base | next(self._ids)

    def new_trace(self) -> int:
        """Mint a fresh statement trace id."""
        return self._next_id()

    # -- thread-local context stack ------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    def current_span(self) -> int | None:
        """For protocol propagation: ship this with commands."""
        st = self._stack()
        return st[-1].span_id if st else None

    def current_trace(self) -> int:
        st = self._stack()
        return st[-1].trace_id if st else 0

    def context(self) -> dict | None:
        """The wire form of the current context (rides CTP commands),
        or None when no span is open on this thread."""
        st = self._stack()
        if not st:
            return None
        return {"t": st[-1].trace_id, "s": st[-1].span_id}

    # -- span API ------------------------------------------------------------
    def open(self, name: str, level: str = "info", root: bool = False,
             **attrs) -> OpenSpan | None:
        """Open a child span of the current thread context (or a fresh
        ROOT span minting a new trace_id when ``root=True``) without
        entering it: ``within`` makes it the context, ``close`` records
        it. One never closed records nothing. None when filtered by
        level."""
        if LEVELS[level] > self._level:
            return None
        st = self._stack()
        if root:
            trace_id, parent = self.new_trace(), None
        elif st:
            trace_id, parent = st[-1].trace_id, st[-1].span_id
        else:
            trace_id, parent = 0, None  # untraced orphan span
        return OpenSpan(
            trace_id, self._next_id(), parent, name, level, attrs
        )

    @contextmanager
    def within(self, span: OpenSpan | None):
        """Make an open span this thread's context: spans opened and
        phases timed inside are its children. ``None`` passes through."""
        if span is None:
            yield
            return
        st = self._stack()
        st.append(span)
        try:
            yield
        finally:
            st.pop()

    def close(self, span: OpenSpan | None, **attrs) -> None:
        """Record an open span, from its opening to now, and one child
        record for each phase timed within it."""
        if span is None:
            return
        dur = _time.perf_counter() - span.t0
        if attrs:
            span.attrs.update(attrs)
        recs = [
            SpanRecord(
                span.span_id, span.parent_id, span.name, span.wall,
                dur, span.level, span.attrs, span.trace_id,
                self.process, self._pid,
            )
        ]
        for name, (wall, total, n, counts) in span.phases.items():
            counts["n"] = n
            recs.append(
                SpanRecord(
                    self._next_id(), span.span_id, name, wall, total,
                    span.level, counts, span.trace_id, self.process,
                    self._pid,
                )
            )
        with self._lock:
            self._buf.extend(recs)
            if self._ship is not None:
                self._ship.extend(recs)
                self._ship_news = True

    @contextmanager
    def span(self, name: str, level: str = "info", root: bool = False,
             **attrs):
        """``open``, ``within`` and ``close`` in one. Yields the span
        id, or None when filtered by level."""
        sp = self.open(name, level, root, **attrs)
        if sp is None:
            yield None
            return
        st = self._stack()
        st.append(sp)
        try:
            yield sp.span_id
        finally:
            st.pop()
            self.close(sp)

    def phase(self, name: str, **counts):
        """Time one occurrence of a phase of the span open on this
        thread; ``counts`` (and those ``add``-ed to what the ``with``
        yields) sum over the occurrences. Outside a span, or below
        ``info``, nothing is timed, annotated or recorded."""
        if self._level < _INFO:
            return _NO_PHASE
        st = getattr(self._local, "stack", None)
        if not st or st[-1].name is None:
            return _NO_PHASE
        ann = self.annotate
        return _Phase(
            st[-1], name, counts,
            None if ann is None else ann("mz:" + name),
        )

    @contextmanager
    def statement(self, name: str, **attrs):
        """The front-end entry point: mint a trace and open its root
        span (one per SQL statement — pgwire/HTTP drive this)."""
        with self.span(name, root=True, **attrs) as sid:
            yield sid

    def record(
        self,
        name: str,
        start: float,
        duration: float,
        level: str = "info",
        parent: int | None = None,
        ship_alone: bool = True,
        **attrs,
    ) -> int | None:
        """Retroactive span record (its timings are known only once
        the work is done). Parent defaults to the current thread
        context. ``ship_alone=False``: the record waits in the ship
        queue for company instead of causing a message by itself.
        Pure host bookkeeping."""
        if LEVELS[level] > self._level:
            return None
        st = self._stack()
        trace_id = st[-1].trace_id if st else 0
        if parent is None and st:
            parent = st[-1].span_id
        span_id = self._next_id()
        self._append(
            SpanRecord(
                span_id, parent, name, start, duration, level, attrs,
                trace_id, self.process, self._pid,
            ),
            ship_alone,
        )
        return span_id

    def _append(self, rec: SpanRecord, news: bool = True) -> None:
        with self._lock:
            self._buf.append(rec)
            if self._ship is not None:
                self._ship.append(rec)
                self._ship_news |= news

    @contextmanager
    def adopt(self, ctx: dict | None):
        """Adopt a PROPAGATED remote context as this thread's parent
        (the replica side of command propagation). ``None`` is a
        no-op pass-through."""
        if not ctx:
            yield
            return
        st = self._stack()
        st.append(
            OpenSpan(int(ctx.get("t") or 0), int(ctx.get("s") or 0))
        )
        try:
            yield
        finally:
            st.pop()

    # -- cross-process shipping (Frontiers piggyback) ------------------------
    def enable_ship(self, capacity: int = RING_CAPACITY) -> None:
        """Start queueing completed spans for piggyback (replica side)."""
        with self._lock:
            if self._ship is None:
                self._ship = deque(maxlen=capacity)

    def drain_shippable(self) -> list[tuple]:
        """Completed spans pending piggyback, as wire tuples (empty
        when shipping is off or nothing happened — the common case)."""
        if not self._ship_news:
            return []
        with self._lock:
            out = [r.to_wire() for r in self._ship]
            self._ship.clear()
            self._ship_news = False
        return out

    def ingest(self, wire_records: list, process: str = "") -> None:
        """Absorb piggybacked spans from another process. Records from
        OUR pid are dropped (an in-process replica shares this tracer;
        its spans already sit in the local ring)."""
        me = os.getpid()
        with self._lock:
            for t in wire_records:
                rec = SpanRecord.from_wire(t)
                if rec.pid == me:
                    continue
                if process and (
                    not rec.process or rec.process.startswith("pid")
                ):
                    rec.process = process
                self._ingested.append(rec)

    # -- introspection --------------------------------------------------------
    def records(self, name_prefix: str = "") -> list[SpanRecord]:
        with self._lock:
            if not name_prefix:
                # list(deque) runs at C speed — keeps the critical
                # section short under writer pressure.
                return list(self._buf) + list(self._ingested)
            out = [
                r for r in self._buf if r.name.startswith(name_prefix)
            ]
            out.extend(
                r
                for r in self._ingested
                if r.name.startswith(name_prefix)
            )
        return out

    def trace_tree(self, trace_id: int) -> list[SpanRecord]:
        """All spans of one statement trace, roots first."""
        recs = [r for r in self.records() if r.trace_id == trace_id]
        recs.sort(key=lambda r: (r.parent_id is not None, r.start))
        return recs

    def dump(self, path: str) -> int:
        """The flight recorder: write every record of both rings, one
        JSON object a line (``SpanRecord.to_json``), oldest first.
        Returns the records written."""
        recs = self.records()
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r.to_json(), default=_plain))
                f.write("\n")
        return len(recs)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._ingested.clear()
            if self._ship is not None:
                self._ship.clear()
                self._ship_news = False


TRACER = Tracer()
