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


def read_latencies_ms(reads: list) -> list:
    """``reads``: dicts with ``due`` and ``done`` (monotonic seconds;
    ``done`` None when the read failed or was never answered). Latency
    runs from the instant the read was DUE, so a stall is charged to
    every read it delayed."""
    return [
        math.inf if r["done"] is None else (r["done"] - r["due"]) * 1e3
        for r in reads
    ]
