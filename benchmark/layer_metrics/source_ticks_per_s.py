"""Ticks the coordinator's generator appended to the source shard in
the window, a second: the source shard's upper at open and at close."""


def read(ctx: dict):
    w = ctx["window"]
    lo, hi = w["source_upper"]
    return (hi - lo) / w["seconds"]
