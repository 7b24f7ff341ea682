"""Median of the replica's own maintenance lag (arrival of a span's
newest tick in the replica to that span's commit) over the spans
committed inside the window. It starts in the replica, not at the
client: a layer metric, not an end-to-end one."""

import statistics


def read(ctx: dict):
    lags = [r["lag_ms"] for r in ctx["lag_rows"]]
    if not lags:
        return None
    return statistics.median(lags)
