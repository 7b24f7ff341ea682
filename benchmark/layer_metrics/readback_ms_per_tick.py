"""Milliseconds the replica's host waits for the device or copies
from it, a tick the view absorbed: the summed ``span.readback`` phases
(the flags read at the span boundary, the output delta's copy to the
host) of the spans committed inside the window, over their ticks."""

from program_spans import load, phase_ms_per_tick


def read(ctx: dict):
    return phase_ms_per_tick(load(ctx), ("span.readback",))
