#!/usr/bin/env python3
"""Several runs of the benchmark in one call, one after another, so
that they share a machine and its compile cache.

    python benchmark/tools/series.py OUT_DIR ITEM [ITEM ...]

An ITEM is ``workload:seed:seconds:trace[:extra,args]``. Each run's
standard output and error go to ``OUT_DIR/<nn>_<workload>.out/.err``;
one summary line a run goes to ``OUT_DIR/summary.jsonl`` and to standard
output. Never touches JAX itself: each run is a process of its own.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    out_dir, items = sys.argv[1], sys.argv[2:]
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    for n, item in enumerate(items):
        workload, seed, seconds, trace, *extra = item.split(":")
        cmd = [
            sys.executable, os.path.join(HERE, "..", "run.py"),
            "--workload", workload, "--seed", seed,
            "--seconds", seconds, "--trace", trace,
        ] + [a for e in extra for a in e.split(",") if a]
        base = os.path.join(out_dir, f"{n:02d}_{workload}")
        t0 = time.monotonic()
        with open(base + ".out", "w") as o, open(base + ".err", "w") as e:
            rc = subprocess.run(cmd, stdout=o, stderr=e).returncode
        line = {"n": n, "item": item, "rc": rc,
                "wall_s": round(time.monotonic() - t0, 1)}
        with open(base + ".out") as f:
            lines = f.read().splitlines()
        try:
            last = json.loads(lines[-1]) if lines else {}
        except ValueError:
            last = {}
        if "metrics" in last:
            line["correct"] = last["correct"]
            line["metrics"] = {
                k: v["value"] for k, v in last["metrics"].items()
            }
            line["device"] = last["device"]
            f_ = last.get("facts", {})
            line["facts"] = {
                k: f_.get(k) for k in (
                    "view_upper", "source_upper", "backlog_ticks",
                    "updates_absorbed", "compiles_in_window",
                    "overflow_regrows_in_window", "warmup_s",
                    "reference_s", "reads_due", "reads_sent_late_p95_ms",
                    "read_p50_ms", "spans_committed")
            }
            if "control" in f_:
                line["control_correct"] = f_["control"]["correct"]
            line["checks"] = {
                k: v["value"] for k, v in last.get("checks", {}).items()
            }
        else:
            with open(base + ".err") as f:
                line["error"] = f.read()[-1500:]
        with open(os.path.join(out_dir, "summary.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
        worst = max(worst, rc)
    return worst


if __name__ == "__main__":
    sys.exit(main())
