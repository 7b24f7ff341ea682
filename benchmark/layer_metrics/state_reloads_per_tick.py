"""Consensus head reads, each a decode of the whole ``ShardState``
(``Machine.reload``), a tick the view absorbed: the ``reloads`` counted
beneath ``span.wait``, ``span.fetch`` and ``span.append`` of the spans
committed inside the window, over their ticks."""

from program_spans import count_per_tick, load


def read(ctx: dict):
    return count_per_tick(
        load(ctx), ("span.wait", "span.fetch", "span.append"), "reloads"
    )
