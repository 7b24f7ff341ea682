"""Milliseconds the replica's host spends handing a tick to the
device: the summed ``span.upload`` (host arrays to device batches) and
``span.dispatch`` (enqueueing the step programs) phases of the spans
committed inside the window, over their ticks."""

from program_spans import load, phase_ms_per_tick


def read(ctx: dict):
    return phase_ms_per_tick(load(ctx), ("span.upload", "span.dispatch"))
