"""Milliseconds of persist fetch a tick the view absorbed: the summed
``span.fetch`` phases (``ReadHandle.fetch`` under ``fetch_to``: state
reload, batch listing, part reads and decodes) of the spans committed
inside the window, over their ticks. ``span.wait``, time with nothing
to fetch, is not in it."""

from program_spans import load, phase_ms_per_tick


def read(ctx: dict):
    return phase_ms_per_tick(load(ctx), ("span.fetch",))
