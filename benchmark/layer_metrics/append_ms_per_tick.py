"""Milliseconds of persist sink append a tick the view absorbed: the
summed ``span.append`` phases (part encode, blob write, state reload and
compare-and-set; the device-to-host copy before it is ``span.readback``)
of the spans committed inside the window, over their ticks."""

from program_spans import load, phase_ms_per_tick


def read(ctx: dict):
    return phase_ms_per_tick(load(ctx), ("span.append",))
