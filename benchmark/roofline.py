"""Operations and bytes of one maintenance step, from shapes alone, and
the table of peaks. Kept with the benchmark so that no later change to
the program can move the yardstick."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of a device; an unknown device is an error,
    not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]


def step_bytes(live_rows: dict, delta_rows: dict, row_bytes: dict) -> float:
    """The least bytes one tick's step must move, whatever implements
    it: every live row of every relation the view maintains state over,
    read once, plus the tick's delta read and written.

    All three arguments are ``{relation: number}``; ``row_bytes`` is the
    stored width of one row (columns x item size, plus 8 bytes each for
    time and diff)."""
    total = 0.0
    for rel, width in row_bytes.items():
        total += live_rows.get(rel, 0) * width
        total += 2 * delta_rows.get(rel, 0) * width
    return total


def hbm_roofline_share(bytes_per_tick: float, busy_s_per_tick: float,
                       peak_bytes_per_s: float) -> float:
    """Percent: the least time to move the step's bytes at the peak
    over the device-busy time the step took."""
    return 100.0 * (bytes_per_tick / peak_bytes_per_s) / busy_s_per_tick
