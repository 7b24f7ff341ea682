"""Ticks the judged view's upper stepped by, per committed span of the
replica's span loop, over the spans committed inside the window
(``mz_wallclock_lag_history`` has one row a committed span)."""


def read(ctx: dict):
    frontiers = sorted(r["frontier"] for r in ctx["lag_rows"])
    if len(frontiers) < 2:
        return None
    return (frontiers[-1] - frontiers[0]) / (len(frontiers) - 1)
