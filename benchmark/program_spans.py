"""The program's own spans of the judged window, for the per-layer
readers that time the maintenance path from inside.

``environmentd`` writes its span rings (its own records and those its
replicas shipped) to ``$MZ_TRACE_DUMP_DIR/spans.jsonl`` on its graceful
stop (``doc/observability.md``: the flight recorder). The harness hands
a reader a fixed ``ctx`` that holds no spans and no path, so the
variable is the way in: importing this module sets it, to a fresh
directory removed at exit, unless the caller already has. Readers are
loaded before the harness copies ``os.environ`` for ``environmentd``,
which hands it to the replica unchanged. A program that does not know
the variable writes nothing, ``load`` returns None and the metrics are
left out.

Which records belong to the window is decided by exact joins, no clock:
a ``span`` record (one a committed span of a view) belongs if its
``upper`` is a ``frontier`` of ``ctx["lag_rows"]``, the judged view's
spans committed inside the window; a ``source.tick`` if its ``t`` lies in
``ctx["window"]["source_upper"]``.
"""

from __future__ import annotations

import atexit
import json
import os
import shutil
import tempfile

VARIABLE = "MZ_TRACE_DUMP_DIR"
DUMP = "spans.jsonl"

if VARIABLE not in os.environ:
    os.environ[VARIABLE] = tempfile.mkdtemp(prefix="mzspans-")
    # readers are loaded in runs that never call them, too
    atexit.register(shutil.rmtree, os.environ[VARIABLE], ignore_errors=True)

_memo: dict = {}


def load(ctx: dict, path: str | None = None):
    """The window's records, read once a ``ctx``; None where there is
    no dump or nothing of the window in it."""
    path = path or os.path.join(os.environ[VARIABLE], DUMP)
    key = (id(ctx), path)
    if key not in _memo:
        _memo.clear()
        _memo[key] = _load(ctx, path)
    return _memo[key]


def _load(ctx: dict, path: str):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        records = [json.loads(ln) for ln in f if ln.strip()]
    for r in records:
        r["end_us"] = r["start_us"] + r["duration_us"]
    lo, hi = ctx["window"]["source_upper"]
    source_ticks = [
        r for r in records
        if r["name"] == "source.tick" and lo <= r["attrs"].get("t", -1) < hi
    ]
    frontiers = {r["frontier"] for r in ctx["lag_rows"]}
    by_dataflow: dict = {}
    for r in records:
        if r["name"] == "span" and r["attrs"].get("upper") in frontiers:
            by_dataflow.setdefault(r["attrs"]["dataflow"], []).append(r)
    if not by_dataflow:
        return None
    # ctx does not name the judged view: it is the dataflow whose spans
    # match the most frontiers (every dataflow of a replica steps
    # through the same times)
    spans = max(by_dataflow.values(), key=len)
    spans.sort(key=lambda r: r["start_us"])
    children: dict = {}
    for r in records:
        if r["parent_id"]:
            children.setdefault(r["parent_id"], []).append(r)
    for s in spans:
        s["phases"] = {
            c["name"]: c for c in children.get(s["span_id"], [])
        }
    # everything the same process did between the first window span's
    # start and the last one's end: the phases of every span (another
    # dataflow's too) and the frontier reports sent
    w0, w1 = spans[0]["start_us"], max(s["end_us"] for s in spans)
    process = spans[0]["process"]
    inside = [
        r for r in records
        if r["process"] == process and w0 <= r["start_us"] < w1
    ]
    ids = {r["span_id"] for r in inside if r["name"] == "span"}
    covered_us = sum(
        r["duration_us"] for r in inside
        if r["parent_id"] in ids or r["name"] == "replica.report_frontiers"
    )
    return {
        "spans": spans,
        "ticks": sum(s["attrs"]["ticks"] for s in spans),
        "source_ticks": source_ticks,
        "wall_us": w1 - w0,
        "covered_us": covered_us,
    }


def phase_ms_per_tick(got, names: tuple):
    """Summed duration of the named phases over the window's spans, in
    milliseconds a tick absorbed."""
    if not got or not got["ticks"]:
        return None
    us = sum(
        s["phases"][n]["duration_us"]
        for s in got["spans"] for n in names if n in s["phases"]
    )
    return us / 1e3 / got["ticks"]


def count_per_tick(got, names: tuple, count: str):
    """One count summed over the named phases of the window's spans, a
    tick absorbed."""
    if not got or not got["ticks"]:
        return None
    total = sum(
        s["phases"][n]["attrs"].get(count, 0)
        for s in got["spans"] for n in names if n in s["phases"]
    )
    return total / got["ticks"]
