"""From the profiler's trace of the replica to numbers.

``read_xplane`` turns an ``.xplane.pb`` plus the hook's stack samples
(``replica_hook/sitecustomize.py``) into a plain document; ``reduce``
turns a document into busy seconds, the operations that took most time
and the idle gaps by what the host was doing. The split lets
``tests/test_harness.py`` check the reduction on a small recorded
document (``testdata/``) without the profiler's reader.

Clocks: device events carry nanoseconds on the trace's own clock; the
hook writes ``bench_sync_<time_ns>`` annotations into the trace, so the
offset to the host's ``time.time_ns()`` is read from the trace itself.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def read_xplane(path: str, hook: dict) -> dict:
    """The document: for each device plane its operations as
    ``[name, start_ns, duration_ns]`` on the HOST's clock, the program
    launches counted, and the hook's samples."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    offsets = []
    profile_start = None
    devices = []
    planes = []
    for plane in pd.planes:
        planes.append(plane.name)
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    profile_start = int(v)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench_sync_"):
                        offsets.append(
                            int(ev.name.rsplit("_", 1)[1]) - int(ev.start_ns)
                        )
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        ops, programs = [], 0
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [
                    [op_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns)]
                    for ev in line.events
                ]
            elif line.name == MODULES_LINE:
                programs = sum(1 for _ in line.events)
        devices.append(
            {"name": plane.name, "ops": ops, "programs": programs}
        )
    if offsets:
        offset, how = int(statistics.median(offsets)), "sync_annotations"
    elif profile_start is not None:
        offset, how = profile_start, "profile_start_time"
    else:
        offset, how = int(hook["started_ns"]), "hook_started_ns"
    for d in devices:
        for op in d["ops"]:
            op[1] += offset
    return {
        "planes": planes,
        "clock": {"offset_ns": offset, "from": how,
                  "syncs": len(offsets)},
        "window_ns": [int(hook["started_ns"]), int(hook["stop_call_ns"])],
        "devices": devices,
        "stacks": hook["stacks"],
        "samples": hook["samples"],
    }


def load_rules() -> dict:
    with open(os.path.join(HERE, "gap_rules.json")) as f:
        return json.load(f)


def classify(stack_ids: list, stacks: list, rules: dict) -> str:
    """What the worker-loop thread was doing in one sample."""
    table = dict(map(tuple, rules["rules"]))
    for sid in stack_ids:
        frames = stacks[sid]
        if rules["worker_thread_frame"] not in frames:
            continue
        if frames[0] == rules["worker_thread_frame"]:
            return rules["worker_loop_itself"]  # parked in its sleep
        for frame in frames:  # innermost first
            if frame in table:
                return table[frame]
    return rules["otherwise"]


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def op_name(hlo: str) -> str:
    """``%while.358 = (...) while(...), condition=...`` ->
    ``while.358_while``: the instruction's name and its opcode, in the
    characters a metric's name may have."""
    head, _, rest = hlo.partition(" = ")
    m = re.search(r"([a-z][\w-]*)\(", rest)
    short = head.strip().lstrip("%") + ("_" + m.group(1) if m else "")
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", short).strip("_")


def reduce(doc: dict, rules: dict, top: int = 10) -> dict:
    """Busy seconds (union of operation intervals inside the traced
    window, averaged over devices), the window's length, operations
    counted, the ``top`` operations by summed time, and the idle gaps
    summed by what the worker loop was doing (from the samples that fall
    inside each gap; a gap no sample fell in goes to the nearest one
    before it)."""
    w0, w1 = doc["window_ns"]
    window_s = (w1 - w0) / 1e9
    by_op: dict = {}
    busy_ns = []
    n_ops = n_programs = 0
    gaps = []
    for dev in doc["devices"]:
        clipped = []
        for name, start, dur in dev["ops"]:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            n_ops += 1
            clipped.append((a, b))
            by_op[name] = by_op.get(name, 0) + (b - a)
        n_programs += dev.get("programs", 0)
        merged = _union(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        edge = w0
        for a, b in merged:
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if edge < w1:
            gaps.append((edge, w1))
    n_dev = max(len(doc["devices"]), 1)
    samples = sorted(doc["samples"])
    times = [s[0] for s in samples]
    kinds = [classify(s[1], doc["stacks"], rules) for s in samples]
    # the worker loop's stacks no rule names, for the next rule
    unknown: dict = {}
    for s, kind in zip(samples, kinds):
        if kind != rules["otherwise"]:
            continue
        for sid in s[1]:
            if rules["worker_thread_frame"] in doc["stacks"][sid]:
                key = tuple(doc["stacks"][sid][:5])
                unknown[key] = unknown.get(key, 0) + 1

    idle: dict = {}
    for a, b in gaps:
        i, j = bisect.bisect_left(times, a), bisect.bisect_right(times, b)
        if j > i:
            share = (b - a) / (j - i)
            for k in range(i, j):
                idle[kinds[k]] = idle.get(kinds[k], 0) + share
        else:
            kind = kinds[i - 1] if i > 0 else rules["otherwise"]
            idle[kind] = idle.get(kind, 0) + (b - a)
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": window_s,
        "device_ops": n_ops,
        "device_programs": n_programs,
        "samples": len(samples),
        "unknown_stacks": [
            [list(k), n]
            for k, n in sorted(unknown.items(), key=lambda kv: -kv[1])[:5]
        ],
        "top_ops": [
            [n, ns / 1e9]
            for n, ns in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, ns / n_dev / 1e9]
            for k, ns in sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        ],
    }


def cut(doc: dict, n_ops: int) -> dict:
    """The first ``n_ops`` operations of the first device with the
    samples of that stretch: small enough to keep as test data."""
    dev = doc["devices"][0]
    ops = sorted(dev["ops"], key=lambda o: o[1])[:n_ops]
    w0 = min(o[1] for o in ops)
    w1 = max(o[1] + o[2] for o in ops)
    samples = [s for s in doc["samples"] if w0 <= s[0] <= w1]
    used = sorted({i for s in samples for i in s[1]})
    remap = {old: new for new, old in enumerate(used)}
    return {
        "planes": doc["planes"], "clock": doc["clock"],
        "window_ns": [w0, w1],
        "devices": [{"name": dev["name"], "ops": ops, "programs": 0}],
        "stacks": [doc["stacks"][i] for i in used],
        "samples": [[t, [remap[i] for i in ids]] for t, ids in samples],
    }
