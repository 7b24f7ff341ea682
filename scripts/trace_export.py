#!/usr/bin/env python
"""Export span records to Chrome trace-event JSON (load at perfetto.dev
or chrome://tracing).

  python scripts/trace_export.py SPANS.json [-o out.chrome.json]
      Convert a span-record dump (the ``mz_trace_spans`` shape: a
      JSON array of {trace_id, span_id, parent_id, process, name,
      start_us, duration_us, ...}, or one such object a line, as the
      flight recorder writes ``$MZ_TRACE_DUMP_DIR/spans.jsonl``) into
      one row per process.

The conversion functions are importable (tests schema-check
``validate_chrome_trace``).
"""

from __future__ import annotations

import argparse
import json
import sys

# Chrome trace-event format essentials: a JSON object with
# "traceEvents": [{name, ph, ts (µs), dur (µs), pid, tid, args}, ...].
REQUIRED_EVENT_KEYS = ("name", "ph", "ts", "pid", "tid")


def _event(name, ts_us, dur_us, pid, tid, **args) -> dict:
    return {
        "name": name,
        "ph": "X",
        "ts": round(float(ts_us), 3),
        "dur": round(max(float(dur_us), 0.0), 3),
        "pid": pid,
        "tid": tid,
        "cat": "materialize_tpu",
        "args": args,
    }


def _meta(pid, tid, what, label) -> dict:
    return {
        "name": what,
        "ph": "M",
        "ts": 0,
        "pid": pid,
        "tid": tid,
        "args": {"name": label},
    }


def spans_to_chrome(spans: list) -> dict:
    """mz_trace_spans-shaped records -> Chrome trace object: one pid
    per source process, spans as complete events at their wall-clock
    stamps (already µs), trace/span ids in args so a perfetto query
    can reassemble the statement tree."""
    events: list = []
    pids: dict = {}
    for r in spans:
        proc = str(r.get("process") or "unknown")
        pid = pids.setdefault(proc, len(pids))
        events.append(
            _event(
                str(r.get("name")),
                float(r.get("start_us", 0)),
                float(r.get("duration_us", 0)),
                pid,
                0,
                trace_id=r.get("trace_id"),
                span_id=r.get("span_id"),
                parent_id=r.get("parent_id"),
                level=r.get("level"),
                **(r.get("attrs") or {}),
            )
        )
    for proc, pid in pids.items():
        events.append(_meta(pid, 0, "process_name", proc))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def tracer_records_to_chrome(records) -> dict:
    """utils.trace.SpanRecord objects -> Chrome trace object."""
    return spans_to_chrome([r.to_json() for r in records])


def validate_chrome_trace(obj: dict) -> list[str]:
    """Schema check (tests + CI): returns violation strings, empty =
    valid Chrome trace-event JSON."""
    problems = []
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        for k in REQUIRED_EVENT_KEYS:
            if k not in ev:
                problems.append(f"event {i}: missing {k!r}")
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "M", "i", "C"):
            problems.append(f"event {i}: bad phase {ph!r}")
        if ph == "X" and "dur" not in ev:
            problems.append(f"event {i}: complete event missing dur")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i}: ts not numeric")
    return problems


def write_chrome_trace(path: str, obj: dict) -> str:
    with open(path, "w") as f:
        json.dump(obj, f)
    return path


def load_json_or_lines(path: str):
    """A JSON document, or one JSON object a line (the flight
    recorder's ``spans.jsonl``) as a list."""
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except ValueError:
        return [json.loads(ln) for ln in text.splitlines() if ln.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("input", help="a span-record JSON array, or one "
                    "record a line")
    ap.add_argument("-o", "--output", default=None)
    args = ap.parse_args(argv)
    data = load_json_or_lines(args.input)
    if isinstance(data, dict):
        data = [data]  # a dump of one line
    chrome = spans_to_chrome(data)
    problems = validate_chrome_trace(chrome)
    if problems:
        for p in problems:
            print(f"invalid: {p}", file=sys.stderr)
        return 1
    out = args.output or (
        args.input.rsplit(".json", 1)[0] + ".chrome.json"
    )
    write_chrome_trace(out, chrome)
    n = len(chrome["traceEvents"])
    print(f"wrote {out} ({n} events); load it at https://ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
