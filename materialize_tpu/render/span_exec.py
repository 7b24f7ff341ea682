"""Pipelined span executor: the double-buffered control plane.

ISSUE 7 / ROADMAP item 4: every dispatch+block round trip has a fixed
host cost (not re-measured on this machine, ROADMAP A3), and the
serial span protocol — dispatch span K, BLOCK on its readback, think,
dispatch span K+1 — leaves the device idle for the whole host-side
inter-span gap. The timely-dataflow discipline (Differential Dataflow,
PAPERS.md) is to keep the workers saturated and coordinate only at
frontier boundaries; this executor is that discipline for the render
layer's span programs:

    stage span K+1's inputs     (h2d upload — overlaps
                                 span K executing on device)
    dispatch span K+1           (queues behind K; device never drains)
    read span K's flags         (ONE tiny d2h readback per span: the
                                 OR-accumulated overflow flags; it
                                 blocks exactly until K finished while
                                 K+1 is already executing)
    commit span K               (frontier advance, trace record)

At most ONE span is in flight ahead of the committed boundary (double
buffering): the host is always preparing exactly the next span, and
every span's entire device→host traffic is the single flags readback
(``readbacks == 1`` in the timeline trace — the bench gate).

Buffer donation (``span_donation`` dyncfg): the span program's carry —
operator states, the output spine, the err arrangement, the device
time scalar — is donated to XLA (``donate_argnums``), so each span
writes its output state into the previous span's buffers instead of
allocating and copying state-sized arrays per dispatch. Donated
buffers are DEAD after dispatch; the rollback checkpoint is therefore
a fresh-buffer clone (``_clone_checkpoint``), and every read of
dataflow state sequences through :meth:`sync` (the span barrier wired
into ``output_batch``/``peek_errors``/``run_steps``) — no donated
buffer is ever read after handoff.

Overflow keeps the existing rollback/replay contract: flags accumulate
as a monotone on-device OR, so the span whose boundary readback first
reports an overflow triggers ``check_flags`` — roll back to the
window checkpoint, grow the flagged tiers, replay the window's logged
inputs — and the pipeline refills. Windows are bounded
(``span_window_spans``) so the defer log cannot grow without bound in
a long-running serving loop.
"""

from __future__ import annotations

import time as _time

import jax
import numpy as np


def resolve_donation(mode=None) -> bool:
    """Resolve the span-carry donation mode: explicit bool wins, then
    the ``span_donation`` dyncfg ('on'/'off'/'auto'); 'auto' donates
    only where the backend implements donation (TPU — CPU ignores it
    with a warning per buffer)."""
    if isinstance(mode, bool):
        return mode
    if mode is None:
        from ..utils.dyncfg import COMPUTE_CONFIGS, SPAN_DONATION

        mode = SPAN_DONATION(COMPUTE_CONFIGS)
    if mode in ("on", "true", True):
        return True
    if mode in ("off", "false", False):
        return False
    from .dataflow import _donation_supported

    return _donation_supported()


class SpanExecutor:
    """Double-buffered pipelined execution of a ``Dataflow``'s span
    program. One executor per dataflow; attaching sets the dataflow's
    span barrier so state reads sequence against span boundaries."""

    def __init__(self, df, donate=None, trace: bool = True):
        from .dataflow import _donation_supported

        self.df = df
        # `donate` is the REQUEST (dyncfg policy); `self.donate` is
        # what actually wires — run_span narrows to supporting
        # backends, and everything this executor reports (stats,
        # bench span_trace "donated") must reflect the effective
        # value, or an A/B comparison on an unsupported backend would
        # read two identical un-donated runs as donated-vs-not.
        self.donate_requested = resolve_donation(donate)
        self.donate = self.donate_requested and _donation_supported()
        # Reentrancy guard: the dataflow's span_barrier() must no-op
        # for reads issued by this executor's own dispatch/sync path.
        self.in_dispatch = False
        # (flags snapshot, trace rec, deltas, arrival monotonic stamp)
        self._inflight = None
        self.trace: list[dict] = [] if trace else None
        # Freshness identity: bench sets the label to the config name
        # so --measure/--trace lag summaries key per config; the
        # replica path records through MaintainedView instead.
        self.freshness_label = getattr(df, "name", "") or "span"
        self.freshness_replica = "local"
        self.spans_submitted = 0
        self.spans_committed = 0
        self.boundary_syncs = 0  # reads that forced a span boundary
        self.overflows = 0
        self._last_host_free: float | None = None
        df._span_exec = self

    # -- the pipeline -------------------------------------------------------
    def submit(self, inputs_list: list):
        """Stage + dispatch one span, then complete the PREVIOUS
        span's boundary (its one readback) — the readback waits for
        the previous span while this one is already queued on device.
        Returns the previous span's committed (validated) stacked
        deltas, or None when there was no previous span or its window
        was replayed."""
        from ..utils.dyncfg import COMPUTE_CONFIGS, SPAN_WINDOW_SPANS

        t0 = _time.perf_counter()
        arrived = _time.monotonic()  # freshness clock (lag_ms)
        gap_ms = (
            0.0
            if self._last_host_free is None
            else (t0 - self._last_host_free) * 1e3
        )
        prev_deltas = None
        self.in_dispatch = True
        try:
            window_sync_ms = 0.0
            if (
                len(self.df._defer_log)
                >= int(SPAN_WINDOW_SPANS(COMPUTE_CONFIGS))
            ):
                # Window boundary: validate + clear the defer log so
                # replay memory stays bounded. One extra sync point,
                # amortized over the window; the pipeline refills on
                # the next submit. Timed SEPARATELY — its blocking
                # readbacks are device wait, not upload/host work, and
                # must not inflate the overlap accounting.
                self._sync_locked()
                self.df.check_flags()
                window_sync_ms = (_time.perf_counter() - t0) * 1e3
            t_up = _time.perf_counter()
            staged = self._stage(inputs_list)
            t1 = _time.perf_counter()
            # Pass the REQUEST: run_span clones the rollback
            # checkpoint whenever donation is requested (cheap safety,
            # keeps the clone path covered on CPU) and narrows the
            # actual argnums to supporting backends itself.
            deltas = self.df.run_span(
                staged, donate=self.donate_requested
            )
            snap = self.df.flags_snapshot()
            t2 = _time.perf_counter()
            rec = {
                "span": self.spans_submitted,
                "ticks": len(inputs_list),
                "host_gap_ms": round(gap_ms, 3),
                "window_sync_ms": round(window_sync_ms, 3),
                "upload_ms": round((t1 - t_up) * 1e3, 3),
                "dispatch_ms": round((t2 - t1) * 1e3, 3),
                "readback_wait_ms": None,
                "readbacks": None,
                "overflow": False,
                # The EFFECTIVE per-span donation fact (narrowed to
                # supporting backends): bench --trace reports it per
                # span so an A/B trace can prove which mode ran.
                "donated": self.donate,
            }
            self.spans_submitted += 1
            # Arrival stamp for freshness: the span's inputs were in
            # hand when submit() was entered (t0 on the same clock).
            prev, self._inflight = (
                self._inflight,
                (snap, rec, deltas, arrived),
            )
            if prev is not None:
                prev_deltas = self._complete(prev)
        finally:
            self.in_dispatch = False
            self._last_host_free = _time.perf_counter()
        return prev_deltas

    def _stage(self, inputs_list: list) -> list:
        """h2d prefetch: upload every input batch's host leaves NOW so
        the transfer overlaps the in-flight span's device compute instead of
        happening lazily inside the next dispatch. The upload is
        input-sized (the delta), never state-sized. On CPU backends
        there is no transfer to hide — host and 'device' share cores —
        so staging passes through (same accelerator predicate as
        donation: a backend with a real h2d transfer)."""
        from .dataflow import _donation_supported

        if not _donation_supported():
            return inputs_list
        return [
            {
                name: jax.device_put(b)  # h2d: prefetch staging
                for name, b in inputs.items()
            }
            for inputs in inputs_list
        ]

    def _complete(self, handle):
        """The span boundary: ONE fused flags readback (blocks until
        the span's program finished), then commit — or, on overflow,
        roll back and replay the whole window through check_flags."""
        snap, rec, deltas, arrived = handle
        r0 = self.df._readbacks
        t0 = _time.perf_counter()
        overflow = self.df.read_flags_snapshot(snap)
        rec["readback_wait_ms"] = round(
            (_time.perf_counter() - t0) * 1e3, 3
        )
        rec["readbacks"] = self.df._readbacks - r0
        if overflow:
            # The flagged span (and everything after it, including the
            # span still in flight) replays against grown tiers; the
            # replay commits synchronously, so the in-flight handle is
            # already absorbed.
            rec["overflow"] = True
            self.overflows += 1
            self.df.check_flags()
            absorbed, self._inflight = self._inflight, None
            if absorbed is not None:
                arec = absorbed[1]
                arec["readbacks"] = 0
                arec["readback_wait_ms"] = 0.0
                arec["absorbed_by_replay"] = True
                if self.trace is not None:
                    self.trace.append(arec)
                self.spans_committed += 1
            deltas = None
        if self.trace is not None:
            self.trace.append(rec)
        self.spans_committed += 1
        # Span-boundary freshness: lag since the committed span's
        # inputs were submitted (pure host bookkeeping; this function
        # is RECORDER_PATH-linted, so a d2h sync here fails CI). The
        # frontier is the monotone committed-span counter — bench
        # dataflows have no tick timestamps of their own.
        from ..coord.freshness import FRESHNESS, lag_ms

        FRESHNESS.record(
            self.freshness_label,
            self.freshness_replica,
            self.spans_committed,
            lag_ms(arrived),
        )
        from ..utils.trace import TRACER

        if TRACER.enabled("debug"):
            # Ring-buffer record of the committed span (ISSUE 12):
            # DEBUG level so the default trace_level keeps the span
            # boundary recorder-free; attrs mirror the bench --trace
            # span schema so mz_trace_spans and the perfetto export
            # see the same stage/dispatch/readback-wait decomposition.
            TRACER.record(
                "span_exec.commit",
                _time.time(),  # host-sync: ok(pure host clock read)
                (rec["readback_wait_ms"] or 0.0) / 1e3,
                level="debug",
                span=rec["span"],
                ticks=rec["ticks"],
                upload_ms=rec["upload_ms"],
                dispatch_ms=rec["dispatch_ms"],
                host_gap_ms=rec["host_gap_ms"],
                donated=rec["donated"],
                overflow=rec["overflow"],
            )
        return deltas

    def sync(self):
        """Complete + commit the in-flight span — the read barrier
        every dataflow-state read sequences through. Peeks admitted
        while a span is in flight therefore always observe a committed
        span boundary, never a half-applied (or donated) carry."""
        if self._inflight is None:
            return
        self.boundary_syncs += 1
        self.in_dispatch = True
        try:
            self._sync_locked()
        finally:
            self.in_dispatch = False
            self._last_host_free = _time.perf_counter()

    def _sync_locked(self):
        if self._inflight is None:
            return
        handle, self._inflight = self._inflight, None
        self._complete(handle)

    def close(self):
        """Drain the pipeline, validate the window, and detach."""
        self.sync()
        self.df.check_flags()
        if self.df._span_exec is self:
            self.df._span_exec = None

    # -- observability ------------------------------------------------------
    def stats(self) -> dict:
        committed = [
            r for r in (self.trace or []) if r["readbacks"] is not None
        ]
        readbacks = [
            r["readbacks"]
            for r in committed
            if not r.get("absorbed_by_replay")
        ]
        return {
            "spans_submitted": self.spans_submitted,
            "spans_committed": self.spans_committed,
            "overflows": self.overflows,
            "boundary_syncs": self.boundary_syncs,
            "donated": self.donate,
            "readbacks_per_span": (
                float(np.mean(readbacks)) if readbacks else 0.0
            ),
        }
