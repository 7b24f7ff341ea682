"""Of the ticks the view absorbed, the percentage whose inputs the span
BEFORE theirs had already gathered (waited for, fetched, put on the
device) while the device ran it: summed ``prefetched_ticks`` over summed
``ticks`` of the ``span`` records committed inside the window. A program
whose spans do not carry the attribute reads None."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got or not got["ticks"]:
        return None
    kept = [s["attrs"].get("prefetched_ticks") for s in got["spans"]]
    if all(k is None for k in kept):
        return None
    return 100.0 * sum(k or 0 for k in kept) / got["ticks"]
