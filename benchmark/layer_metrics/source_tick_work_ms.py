"""Mean milliseconds of work in one tick of the coordinator's
generator (generate, encode, blob write, state transition; its sleep is
not in it): ``work_ms`` of the ``source.tick`` records whose tick the
source shard's upper passed inside the window."""

from program_spans import load


def read(ctx: dict):
    got = load(ctx)
    if not got or not got["source_ticks"]:
        return None
    ticks = got["source_ticks"]
    return sum(r["attrs"]["work_ms"] for r in ticks) / len(ticks)
