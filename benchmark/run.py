#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the served path.

    python benchmark/run.py --workload q15_backlog --seed 7 \
        --seconds 45 --trace 0

Starts ``python -m materialize_tpu.server.environmentd`` (a host
process) with ONE replica on ``JAX_PLATFORMS=tpu``, installs the
configuration's SQL over HTTP, waits for hydration, warms up as the
traffic mix says, opens the window for ``--seconds``, closes it,
compares what the window produced with the plain reference, prints, and
shuts everything down. This process is client, load driver and oracle;
its own JAX is pinned to the CPU, so exactly one process, the replica,
holds the chip. Where the replica reports a device that is not a TPU
the run fails: there is no fall-back.

Everything about a cell is data found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``references/<reference>.py``, ``layer_metrics/<stem>.py`` (the reader of
every per-layer metric ``<stem>`` or ``<stem>.<suffix>``). A name that
cannot be found is an error, not a skip.

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, then ``facts`` (what the driver ignores) and last
``checks``: every number compared beside its limit, also printed as the
last lines of standard error.

``--rehearse`` runs the same cell with the replica on whatever
``JAX_PLATFORMS`` names (the CPU here): it proves the harness, never a
speed. Its device line names the platform it found and the exit code is
2, so it can never pass for a chip run. ``--control float32`` also runs
the comparison with the lower-precision control in the reference's
place and reports it under ``facts``; the control must fail.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, for setup_s

import argparse
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, REPO)

import compare  # noqa: E402
import metrics as arithmetic  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from served import BenchFailure, PgClient, Server, Shards  # noqa: E402

WAIT_S = 1100.0  # any one set-up wait; a cold first run compiles
TRACE_S = 3.0  # the traced stretch: stop_trace is slow on long ones
READ_GRACE_S = 60.0  # how long past the close an answer is waited for


def log(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def load_cell(workload: str) -> dict:
    """Everything the cell names, resolved: refuses what it cannot
    find."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise SystemExit(f"workload {workload!r}: no config {cell['config']!r}")
    with open(os.path.join(REPO, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    mix = traffic_mod.load_mix(cell["traffic"])
    ref = compare.load_reference(config["reference"])
    tables = compare.load_reference(config["base_tables"])
    e2e = [m for m in bench["end_to_end"] if m["name"] in mix["end_to_end"]]
    missing = set(mix["end_to_end"]) - {m["name"] for m in e2e}
    if missing:
        raise SystemExit(
            f"traffic {cell['traffic']!r} reports {sorted(missing)}, which "
            "BENCHMARK.json does not define"
        )
    layer = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if workload not in m["workloads"]:
                continue
        elif m["moves"] not in mix["end_to_end"]:
            continue
        # the reader is the module named by the metric's stem: the part
        # of its name before the first dot (`device_idle_share.updates`
        # is read by `layer_metrics/device_idle_share.py`)
        stem = m["name"].split(".", 1)[0]
        rpath = os.path.join(HERE, "layer_metrics", stem + ".py")
        if not os.path.exists(rpath):
            raise SystemExit(f"per-layer metric {m['name']!r}: no {rpath}")
        s = importlib.util.spec_from_file_location(
            "layer_" + stem, rpath
        )
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        layer.append((m, mod.read))
    return {
        "cell": cell, "config": config, "mix": mix, "ref": ref,
        "tables": tables,
        "end_to_end": e2e, "per_layer": layer,
    }


def wait_until(pred, timeout: float, what: str, server: Server,
               step: float = 0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        server.check_alive()
        time.sleep(step)
    raise BenchFailure(f"timed out after {timeout:.0f}s waiting for {what}")


def warm_up(server, shards, spec, mix, view_upper_hydrated):
    """Until every program the window will use has run once (and, where
    the mix says so, the view has caught up with its sources)."""
    w = mix["warmup"]
    statement = (mix.get("read_statement") or "").format(view=spec["view"])
    t0 = time.monotonic()
    wait_until(
        lambda: shards.upper("view") - view_upper_hydrated
        >= w["min_view_ticks"],
        WAIT_S, f"the view to advance {w['min_view_ticks']} ticks", server,
    )
    if w["catch_up"]:
        wait_until(
            lambda: shards.upper("update") - shards.upper("view") <= 1,
            WAIT_S, "the view to catch up with its sources", server, 0.05,
        )
    if w["warm_reads"]:
        client = PgClient(server.pg_port)
        try:
            for _ in range(w["warm_reads"]):
                client.query(statement)
        finally:
            client.close()
    # quiet: the last `quiet_ticks` the view absorbed compiled nothing
    while True:
        n0, u0 = len(server.compile_log()), shards.upper("view")
        wait_until(
            lambda: shards.upper("view") - u0 >= w["quiet_ticks"],
            WAIT_S, "the view to advance while compiles settle", server,
        )
        if len(server.compile_log()) == n0:
            break
    if w["catch_up"]:
        wait_until(
            lambda: shards.upper("update") - shards.upper("view") <= 1,
            WAIT_S, "the view to catch up with its sources", server, 0.02,
        )
    return time.monotonic() - t0


def lag_rows(server, view: str) -> list:
    """Every committed span of the view still in the ring (4,096)."""
    return [
        {"frontier": int(r[0]), "lag_ms": float(r[1]), "at": float(r[2])}
        for r in server.rows(
            "SELECT frontier, lag_ms, at FROM mz_wallclock_lag_history "
            f"WHERE dataflow = '{view}'"
        )
    ]


def run(args) -> int:
    import faulthandler
    import signal

    # Client and oracle only: this process never reaches for the chip.
    import jax

    jax.config.update("jax_platforms", "cpu")
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    got = load_cell(args.workload)
    cell, spec, mix, ref = (
        got["cell"], got["config"], got["mix"], got["ref"]
    )
    seconds = float(args.seconds)
    log({
        "phase": "config", "workload": cell["name"],
        "config": cell["config"], "traffic": cell["traffic"],
        "seed": args.seed, "seconds": seconds, "trace": args.trace,
        "rehearse": args.rehearse,
        "replica_JAX_PLATFORMS": (
            os.environ.get("JAX_PLATFORMS", "") if args.rehearse else "tpu"
        ),
        "JAX_COMPILATION_CACHE_DIR": os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", ""
        ),
    })
    # Data directory, hook directory and xplane live under a fresh
    # temporary directory outside the checkout, removed at shutdown.
    run_dir = tempfile.mkdtemp(prefix="mzbench-")
    server = Server(
        run_dir, tick_interval=mix["generator_sleep_s"],
        replica_platform=None if args.rehearse else "tpu",
    )
    shards = None
    readers = None
    failure = None
    result = None
    down = {}
    try:
        t0 = time.monotonic()
        line = server.wait_listening(timeout=300)
        device = server.replica_device()
        log({"phase": "start", "seconds": time.monotonic() - t0,
             "listening": line, "device": device})
        if device["platform"] != "tpu" and not args.rehearse:
            raise BenchFailure(
                f"the replica reports {device}: not a TPU, and no "
                "rehearsal was asked for"
            )
        if device["count"] < cell["chips"]:
            raise BenchFailure(
                f"the replica reports {device['count']} device(s); the "
                f"cell asks for {cell['chips']}"
            )
        peaks = None if args.rehearse else roofline.peaks_for(device["kind"])

        for stmt in spec.get("session", []):
            server.sql(stmt)
        t_ddl = time.monotonic()
        # a statement may name any plain number of the configuration
        knobs = {
            k: v for k, v in spec.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        for i, stmt in enumerate(spec["sql"]):
            t1 = time.monotonic()
            server.sql(stmt.format(seed=args.seed, **knobs), timeout=WAIT_S)
            log({"phase": "ddl", "statement": stmt[:60],
                 "seconds": time.monotonic() - t1})
            if i == spec["pin_sources_after_statement"]:
                # Registering the readers holds the source shards'
                # since: every later time stays distinct and readable.
                shards = Shards(server.data_dir)
                for alias, suffix in spec["sources"].items():
                    shards.open(alias, suffix)
                shards.readers["update"] = shards.readers[
                    spec["update_relation"]
                ]
                # ... and releases the history before `hydrate_at_tick`
                # for compaction, as a source's compaction window would:
                # the replica then hydrates at exactly that tick, from a
                # consolidated snapshot of the same size in every run.
                for alias in spec["sources"]:
                    shards.readers[alias].downgrade_since(
                        spec["hydrate_at_tick"]
                    )
        server.wait_hydrated(spec["hydrate"], timeout=WAIT_S)
        shards.open("view", spec["view_shard_suffix"])
        hydrated_upper = shards.upper("view")
        hydrated_at = int(
            shards.updates("view", 0, hydrated_upper)["time"].min()
        )
        log({"phase": "hydration",
             "seconds_ddl_to_hydrated": time.monotonic() - t_ddl,
             "source_upper": shards.upper("update"),
             "view_upper": hydrated_upper, "hydrated_at": hydrated_at})
        warm_s = warm_up(server, shards, spec, mix, hydrated_upper)
        compile_before = server.compile_log()
        regrows_before = server.replica_metric("mz_overflow_regrows_total")
        log({"phase": "warmup", "seconds": warm_s,
             "replica_compile_records": len(compile_before),
             "compile_log": [list(c) for c in compile_before],
             "source_upper": shards.upper("update"),
             "view_upper": shards.upper("view")})

        # -- the window ---------------------------------------------------
        import threading

        upper_lock = threading.Lock()

        def newest_complete() -> int:
            # the newest time complete in EVERY input of the view: what
            # a read without AS OF is served at
            with upper_lock:
                return min(shards.upper(a) for a in spec["sources"]) - 1

        schedule = traffic_mod.read_schedule(mix, args.seed, seconds)
        if schedule:
            readers = traffic_mod.Readers(
                lambda: PgClient(server.pg_port),
                mix["read_statement"].format(view=spec["view"]),
                schedule, newest_complete,
            )
        with upper_lock:
            view_open = shards.upper("view")
            source_open = shards.upper("update")
        t_open = time.monotonic()
        wall_open = time.time()
        setup_s = t_open - T_START
        if readers:
            readers.start(t_open)
        t_close = t_open + seconds
        hook_trace = None
        if args.trace:
            # the traced stretch is the window's last seconds, so the
            # profiler's slow stop falls after the close
            time.sleep(max(t_close - TRACE_S - 0.3 - time.monotonic(), 0))
            server.hook_send("trace.cmd", {
                "seconds": TRACE_S, "sample_ms": 4.0,
                "logdir": os.path.join(run_dir, "xplane"),
            })
        time.sleep(max(t_close - time.monotonic(), 0))
        with upper_lock:
            view_close = shards.upper("view")
            source_close = shards.upper("update")
        t_closed = time.monotonic()
        wall_close = time.time()
        window_s = t_closed - t_open
        reads = readers.join(t_closed + READ_GRACE_S) if readers else []
        server.check_alive()

        # -- after the close ---------------------------------------------
        compile_after = server.compile_log()
        regrows_after = server.replica_metric("mz_overflow_regrows_total")
        all_lags = lag_rows(server, spec["view"])
        lags = [r for r in all_lags if wall_open <= r["at"] <= wall_close]
        span_counts: dict = {}
        for (name,) in server.rows("SELECT name FROM mz_trace_spans"):
            span_counts[name] = span_counts.get(name, 0) + 1
        if args.trace:
            t1 = time.monotonic()
            hook_trace = server.hook_wait("trace_done.json", 280.0)
            log({"phase": "trace_stop",
                 "seconds_waited": time.monotonic() - t1,
                 "stop_trace_s": (hook_trace["stopped_ns"]
                                  - hook_trace["stop_call_ns"]) / 1e9})
        server.hook_send("memstats.cmd", {})
        mem = server.hook_wait("memstats.json", 30.0)
        peak_bytes = max(
            (p for p in mem["peak_bytes_in_use"] if p is not None),
            default=None,
        )
        if peak_bytes is None:
            if not args.rehearse:
                raise BenchFailure(f"no memory statistics: {mem}")
            peak_bytes = 0  # the CPU backend reports none

        # what the window produced, read back from durable storage
        base_t = view_open - 1
        read_hi = max([r["hi"] for r in reads if r["hi"] is not None]
                      + [view_close - 1])
        wait_until(
            lambda: shards.upper("update") > read_hi, 30.0,
            "the source shard to pass the last bracket", server,
        )
        src = {}
        for alias in spec["sources"]:
            src[alias] = compare.Timeline(
                shards.snapshot(alias, base_t),
                shards.updates(alias, base_t + 1, read_hi + 1),
            )
        view_base = shards.snapshot("view", base_t, ref.COLUMNS)
        view_updates = shards.updates(
            "view", base_t + 1, view_close, ref.COLUMNS
        )
        upd = src[spec["update_relation"]]
        absorbed = upd.count_between(view_open, view_close)
        generated = upd.count_between(source_open, source_close)
        shapes = {
            "live_rows": {
                a: int(tl.at(base_t)["diff"].sum()) for a, tl in src.items()
            },
            "delta_rows_per_tick": {
                spec["update_relation"]: absorbed
                / max(view_close - view_open, 1)
            },
            "row_bytes": {
                a: sum(
                    8 if v.dtype.kind in "OU" else v.dtype.itemsize
                    for v in tl.cols.values()
                ) + 8
                for a, tl in src.items()
            },
        }
        trace = None
        if hook_trace is not None:
            t1 = time.monotonic()
            xplanes = []
            for root, _d, files in os.walk(os.path.join(run_dir, "xplane")):
                xplanes += [
                    os.path.join(root, f) for f in files
                    if f.endswith(".xplane.pb")
                ]
            if len(xplanes) != 1:
                raise BenchFailure(f"expected one xplane: {xplanes}")
            doc = trace_reduce.read_xplane(xplanes[0], hook_trace)
            trace = trace_reduce.reduce(doc, trace_reduce.load_rules())
            w0, w1 = (x / 1e9 for x in doc["window_ns"])
            before = [r["frontier"] for r in all_lags if r["at"] <= w0]
            inside = [r["frontier"] for r in all_lags if r["at"] <= w1]
            trace["ticks"] = (
                max(inside) - max(before) if before and inside else 0
            )
            log({"phase": "trace", "seconds_reading": time.monotonic() - t1,
                 "xplane_bytes": os.path.getsize(xplanes[0]),
                 "planes": doc["planes"], "clock": doc["clock"],
                 **{k: trace[k] for k in (
                     "busy_s", "window_s", "device_ops", "device_programs",
                     "samples", "ticks", "unknown_stacks")}})
            if args.keep and any(d["ops"] for d in doc["devices"]):
                os.makedirs(args.keep, exist_ok=True)
                keep = os.path.join(
                    args.keep, f"{cell['name']}_{args.seed}_trace_cut.json"
                )
                with open(keep, "w") as f:
                    json.dump(trace_reduce.cut(doc, 2000), f)
        shards.close()
        shards = None
        down = server.shutdown()  # the program's state is freed

        # -- the comparison ----------------------------------------------
        t1 = time.monotonic()
        judged_reads = [
            (0, -1, None) if r["done"] is None
            else (r["lo"], r["hi"], compare.wire_rows(r["rows"], ref))
            for r in reads
        ]
        # the base tables made again from the seed, at the window's
        # first and last time, for the relations the view reads
        regenerated = {
            t: {
                rel: table
                for rel, table in got["tables"].tables_at(
                    args.seed, spec, t).items()
                if rel in src
            }
            for t in sorted({view_open, max(view_close - 1, view_open)})
        }
        verdict = compare.judge(
            ref, src, view_base, view_updates, (view_open, view_close),
            judged_reads, seed=args.seed, regenerated=regenerated,
        )
        control = None
        if args.control:
            control = compare.judge(
                ref, src, view_base, view_updates, (view_open, view_close),
                judged_reads, precision=args.control, seed=args.seed,
                regenerated=regenerated,
            )
        reference_s = time.monotonic() - t1

        # -- the metrics -------------------------------------------------
        lat = arithmetic.read_latencies_ms(reads)
        late = [
            (r["sent"] - r["due"]) * 1e3 for r in reads
            if r["sent"] is not None
        ]
        values = {
            "setup_s": setup_s,
            "updates_per_s": arithmetic.updates_per_s(
                upd.times, view_open, view_close, window_s
            ),
        }
        if lat:
            values["read_p95_ms"] = arithmetic.percentile(lat, 95.0)
        ctx = {
            "window": {
                "seconds": window_s,
                "source_upper": [source_open, source_close],
                "view_upper": [view_open, view_close],
            },
            "lag_rows": lags, "trace": trace, "shapes": shapes,
            "compile_before": compile_before, "peaks": peaks,
            "device": device, "reads": reads, "latencies_ms": lat,
        }
        out_metrics = {}
        if args.trace:
            for m, read in got["per_layer"]:
                v = read(ctx)
                if v is not None:  # nothing to read: left out, never 0
                    out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in got["end_to_end"]:
                out_metrics[m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"],
                }
        new_compiles = compile_after[len(compile_before):]
        facts = {
            "window_s": window_s, "setup_s": setup_s, "warmup_s": warm_s,
            "reference_s": reference_s, "hydrated_at_tick": hydrated_at,
            "view_upper": [view_open, view_close],
            "source_upper": [source_open, source_close],
            "backlog_ticks": [source_open - view_open,
                              source_close - view_close],
            "updates_absorbed": absorbed, "updates_generated": generated,
            "compiles_in_window": len(new_compiles),
            "compiled_in_window": [list(c) for c in new_compiles[:8]],
            "overflow_regrows_in_window": regrows_after - regrows_before,
            "spans_committed": len(lags),
            "trace_spans_by_name": span_counts,
            "reads_due": len(reads),
            "reads_sent_late_p95_ms": (
                arithmetic.percentile(late, 95.0) if late else None
            ),
            "reads_sent_late_max_ms": max(late) if late else None,
            "read_p50_ms": (
                statistics.median(lat) if lat else None
            ),
            "view_times_checked": verdict["view_times_checked"],
            "reads_checked": verdict["reads_checked"],
            "first_wrong_time": verdict["first_wrong_time"],
            "first_wrong_read": verdict["first_wrong_read"],
            "first_wrong_source_row": verdict["first_wrong_source_row"],
            "shapes": shapes, "rehearsal": args.rehearse,
        }
        if control is not None:
            facts["control"] = {
                "precision": args.control, "correct": control["correct"],
                "checks": control["checks"],
            }
        dev = {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"], "memory_peak_bytes": peak_bytes,
        }
        result = {
            "correct": verdict["correct"],
            "attempted": (view_close - view_open) + len(reads),
            "failed": sum(1 for r in reads if r["done"] is None)
            + verdict["checks"]["view_times_wrong"]["value"],
            "metrics": out_metrics,
            "device": dev,
        }
        if trace is not None:
            dev["busy_s"] = trace["busy_s"]
            dev["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": trace["top_ops"],
                "idle_gaps": trace["idle_gaps"],
            }
        result["facts"] = facts
        result["checks"] = verdict["checks"]
    except BenchFailure as e:
        failure = str(e)
    except Exception as e:  # a bug in the harness is a failure too
        import traceback

        failure = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"
    finally:
        if shards is not None:
            shards.close()
        if not down:
            down = server.shutdown()
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(server.log_path, os.path.join(
                args.keep, f"{args.workload}_{args.seed}_environmentd.log"))
        shutil.rmtree(run_dir, ignore_errors=True)
    log({"phase": "shutdown", **down,
         "total_seconds": time.monotonic() - T_START})
    if failure is None and (
        down.get("environmentd_rc") != 0 or down.get("replicas_killed")
    ):
        failure = f"unclean shutdown: {down}"
    if failure is not None:
        print(f"benchmark: FAILED: {failure}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    # A rehearsal's line names the device it found and its exit code is
    # not 0: it can never pass for a chip run.
    return 2 if args.rehearse else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="the replica inherits JAX_PLATFORMS (the CPU here); proves "
        "the harness, exits 2",
    )
    ap.add_argument(
        "--control", choices=("float32",), default=None,
        help="also judge the lower-precision control; it must fail",
    )
    ap.add_argument(
        "--keep", default=None,
        help="an already ignored directory for a cut of the reduced "
        "trace (and the server's log after a failure)",
    )
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
