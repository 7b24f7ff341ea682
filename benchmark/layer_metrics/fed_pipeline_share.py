"""Of the delta-join pipelines the judged view's steps dispatched inside
the window, the percentage one of whose inputs had rows in that tick:
summed ``fed_delta_pipelines`` over summed ``delta_pipelines`` of the
``span`` records committed inside the window. The rest probed empty
deltas. A view without a delta join, or a program whose spans do not
carry the attributes, reads None."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got:
        return None
    attrs = [s["attrs"] for s in got["spans"]]
    dispatched = sum(a.get("delta_pipelines") or 0 for a in attrs)
    if not dispatched:
        return None
    fed = sum(a.get("fed_delta_pipelines") or 0 for a in attrs)
    return 100.0 * fed / dispatched
