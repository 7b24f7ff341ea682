"""The benchmark's window into the replica, without editing the program.

Only the process that holds the chip can trace it or read its memory
statistics, and ``environmentd`` starts the replica with its own
environment unchanged. ``benchmark/run.py`` puts this directory on
``PYTHONPATH``, so every Python child imports this module at start-up;
it does nothing unless ``BENCH_HOOK_DIR`` is set AND the process is
``python -m materialize_tpu.coord.replica``.

In the replica it starts one daemon thread that watches
``$BENCH_HOOK_DIR`` for command files written by the runner:

``memstats.cmd``
    write ``memstats.json``: ``peak_bytes_in_use`` of every local device.
``trace.cmd`` (JSON ``{"seconds": s, "logdir": d, "sample_ms": m}``)
    ``jax.profiler.start_trace(d)``, sample every thread's Python stack
    each ``m`` ms for ``s`` seconds (what the host was doing, for the
    idle gaps), ``stop_trace``, write ``trace_done.json`` with the
    samples and the host clock at each boundary. Clock alignment: three
    ``bench_sync_<time_ns>`` annotations are written into the trace.

Nothing is imported from jax until a command arrives, by which time the
replica has long since initialised its backend.

In the replica and in ``environmentd`` it also appends every garbage
collection of the oldest generation, and any other that took 20 ms or
more, to ``$BENCH_HOOK_DIR/gc_<role>.jsonl`` (wall start, ms,
generation): a pause of either process holds the view's rate.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _role() -> str | None:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            cmd = f.read()
    except OSError:
        return None
    for role, module in (
        ("replica", b"materialize_tpu.coord.replica"),
        ("environmentd", b"materialize_tpu.server.environmentd"),
    ):
        if module in cmd:
            return role
    return None


def _record_gc(hook_dir: str, role: str) -> None:
    import gc

    path = os.path.join(hook_dir, f"gc_{role}.jsonl")
    began = [0.0, 0.0]

    def note(phase, info):
        if phase == "start":
            began[0], began[1] = time.perf_counter(), time.time()
            return
        ms = (time.perf_counter() - began[0]) * 1e3
        if info["generation"] == 2 or ms >= 20.0:
            with open(path, "a") as f:
                f.write(json.dumps(
                    [began[1], ms, info["generation"], info["collected"]]
                ) + "\n")

    gc.callbacks.append(note)


def _write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)  # the runner never sees half a file


def _memstats() -> dict:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    return {"peak_bytes_in_use": peaks, "time_ns": time.time_ns()}


def _stacks(own: int, depth: int = 64) -> list:
    out = []
    for ident, frame in sys._current_frames().items():
        if ident == own:
            continue
        names = []
        while frame is not None and len(names) < depth:
            code = frame.f_code
            names.append(
                os.path.basename(code.co_filename) + ":" + code.co_name
            )
            frame = frame.f_back
        out.append(names)
    return out


def _trace(cmd: dict) -> dict:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the sampler below replaces it
    opts.host_tracer_level = 1  # TraceAnnotation: the clock syncs
    own = threading.get_ident()
    period = float(cmd.get("sample_ms", 4.0)) / 1e3
    t_call = time.time_ns()
    jax.profiler.start_trace(cmd["logdir"], profiler_options=opts)
    t_started = time.time_ns()

    def sync():
        n = time.time_ns()
        with jax.profiler.TraceAnnotation(f"bench_sync_{n}"):
            pass

    sync()
    table: dict = {}
    samples = []
    deadline = time.monotonic() + float(cmd["seconds"])
    while time.monotonic() < deadline:
        now = time.time_ns()
        ids = []
        for s in _stacks(own):
            key = tuple(s)
            ids.append(table.setdefault(key, len(table)))
        samples.append([now, ids])
        time.sleep(period)
    sync()
    sync()
    t_stop_call = time.time_ns()
    jax.profiler.stop_trace()
    t_stopped = time.time_ns()
    return {
        "start_call_ns": t_call,
        "started_ns": t_started,
        "stop_call_ns": t_stop_call,
        "stopped_ns": t_stopped,
        "stacks": [list(k) for k in table],  # id = position
        "samples": samples,
    }


def _watch(hook_dir: str) -> None:
    mem_cmd = os.path.join(hook_dir, "memstats.cmd")
    trace_cmd = os.path.join(hook_dir, "trace.cmd")
    while True:
        try:
            if os.path.exists(mem_cmd):
                os.unlink(mem_cmd)
                _write_json(
                    os.path.join(hook_dir, "memstats.json"), _memstats()
                )
            if os.path.exists(trace_cmd):
                with open(trace_cmd) as f:
                    cmd = json.load(f)
                os.unlink(trace_cmd)
                _write_json(
                    os.path.join(hook_dir, "trace_done.json"), _trace(cmd)
                )
        except Exception as e:  # report, never take the replica down
            _write_json(
                os.path.join(hook_dir, "hook_error.json"),
                {"error": f"{type(e).__name__}: {e}"},
            )
        time.sleep(0.02)


def _install() -> None:
    hook_dir = os.environ.get("BENCH_HOOK_DIR")
    role = _role() if hook_dir else None
    if role is None:
        return
    _record_gc(hook_dir, role)
    if role != "replica":
        return
    threading.Thread(
        target=_watch, args=(hook_dir,), daemon=True,
        name="bench-replica-hook",
    ).start()


_install()
