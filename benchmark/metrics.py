"""The arithmetic of the end-to-end metrics: all the work, or all the
reads, of the whole window. Nothing is a median of chunks."""

from __future__ import annotations

import math

import numpy as np


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile over ALL values; a read that failed or
    went unanswered is passed as ``math.inf`` and so counts as missing
    any limit. ``q`` in (0, 100]."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def updates_per_s(
    update_times, view_upper_open: int, view_upper_close: int,
    window_seconds: float,
) -> float:
    """Source updates at the times the judged view's durable upper
    passed inside the window (``open <= time < close``), over the whole
    window's seconds."""
    t = np.asarray(update_times)
    n = np.count_nonzero((t >= view_upper_open) & (t < view_upper_close))
    return float(n) / window_seconds


def backlogged_stretch(
    commits, source_uppers, open_, close, span_ticks: int,
) -> dict:
    """The stretch of the window in which the replica had a full span
    waiting: from the open to the commit after which the source was
    fewer than ``span_ticks`` ticks past the view's frontier, or the
    whole window where that never happened.

    ``commits``: ``(frontier, at)`` of the view's committed spans (any
    order; those outside the window are ignored). ``source_uppers``:
    ``(at, upper)`` samples of the sources' newest complete upper,
    ascending in ``at``. ``open_`` / ``close``: ``(view frontier, at)``
    at the window's open and close. ``at`` is one wall clock
    throughout. The span after a commit was full-ready if the newest
    sample at or before that commit was at least ``span_ticks`` past
    its frontier. Where the view had caught up at the open, the
    stretch is the whole window. A stretch is never a failure: it
    reports what the window read. Its rate is ``updates_per_s`` from
    the open's frontier to ``view_upper`` over ``seconds``."""
    at = np.asarray([a for a, _ in source_uppers], dtype=float)
    up = [u for _, u in source_uppers]

    def upper_at(t: float) -> int:
        i = int(np.searchsorted(at, t, side="right")) - 1
        return up[max(i, 0)]

    def ready(point) -> bool:
        return upper_at(point[1]) >= point[0] + span_ticks

    inside = sorted(
        c for c in commits
        if open_[1] < c[1] <= close[1] and c[0] > open_[0]
    )
    ran_out = [c for c in inside if not ready(c)] if ready(open_) else []
    end = ran_out[0] if ran_out else close
    return {
        "seconds": end[1] - open_[1],
        "view_upper": end[0],
        "backlog_at_end": upper_at(end[1]) - end[0],
    }


def read_latencies_ms(reads: list) -> list:
    """``reads``: dicts with ``due`` and ``done`` (monotonic seconds;
    ``done`` None when the read failed or was never answered). Latency
    runs from the instant the read was DUE, so a stall is charged to
    every read it delayed."""
    return [
        math.inf if r["done"] is None else (r["done"] - r["due"]) * 1e3
        for r in reads
    ]
