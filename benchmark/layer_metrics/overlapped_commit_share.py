"""Of the ticks the view absorbed, the percentage whose commit (the
output delta's copy to the host, the sink append, the publish) ran with
the span AFTER theirs already dispatched, so beside a busy device:
summed ``overlapped_commit_ticks`` over summed ``ticks`` of the ``span``
records committed inside the window. A program whose spans do not carry
the attribute reads None."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got or not got["ticks"]:
        return None
    over = [s["attrs"].get("overlapped_commit_ticks") for s in got["spans"]]
    if all(k is None for k in over):
        return None
    return 100.0 * sum(k or 0 for k in over) / got["ticks"]
