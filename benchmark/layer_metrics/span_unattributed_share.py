"""The instrument's own check: of the wall time from the start of the
first span committed inside the window to the end of the last, the
percentage that no phase record of the replica's spans and no
``replica.report_frontiers`` covers. High means a phase is missing."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got or not got["wall_us"]:
        return None
    return 100.0 * (1.0 - got["covered_us"] / got["wall_us"])
