"""The harness checked by hand on the CPU: ``pytest benchmark/tests``.

No test here starts a server or touches libtpu. Tier-1 collects
``tests/`` only, so its count is untouched.
"""

import json
import math
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import metrics  # noqa: E402
import roofline  # noqa: E402
import run as runner  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_names_and_units(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock"
        )
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert len(c["source"]) <= 200 and c["file"].startswith("benchmark/")
        for key in c["reduced"]:
            assert NAME.match(key)
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_resolves(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        got = runner.load_cell(w["name"])
        assert got["ref"].COLUMNS and callable(got["ref"].answer)
        reported = {m["name"] for m in got["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert got["per_layer"], w["name"]
        for m, read in got["per_layer"]:
            assert callable(read)
            # a per-layer metric moves a metric its cells report
            assert m["moves"] in reported, (w["name"], m["name"])
        for name in reported:
            cells = e2e[name].get("workloads")
            assert cells is None or w["name"] in cells


def test_unknown_names_are_refused(bench, tmp_path, monkeypatch):
    with pytest.raises(SystemExit):
        runner.load_cell("no_such_cell")
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no_such_mix")
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v99")
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_read_schedule_same_arrivals_every_seed():
    mix = traffic.load_mix("steady_fresh")
    a = traffic.read_schedule(mix, 1, 45.0)
    b = traffic.read_schedule(mix, 2147483999, 45.0)
    assert len(a) == mix["readers"] == len(b)
    n = sum(len(c) for c in a)
    assert n == sum(len(c) for c in b) == int(45 * mix["reads_per_s"])
    period = mix["readers"] / mix["reads_per_s"]
    for sched in (a, b):
        firsts = sorted(c[0] for c in sched)
        gaps = {round(y - x, 9) for x, y in zip(firsts, firsts[1:])}
        assert gaps == {round(period / mix["readers"], 9)}


def test_recorded_trace_reduces_as_expected():
    with open(os.path.join(BENCH, "testdata",
                           "q15_backlog_trace_cut.json")) as f:
        doc = json.load(f)
    r = trace_reduce.reduce(doc, trace_reduce.load_rules())
    assert r["device_ops"] == 1993  # 7 of the 2,000 end past the stretch
    assert r["window_s"] == pytest.approx(0.005895456)
    assert r["busy_s"] == pytest.approx(0.00588407)
    assert 100 * r["busy_s"] / r["window_s"] == pytest.approx(99.807, abs=1e-3)
    assert [n for n, _ in r["top_ops"][:3]] == [
        "while.366_while", "fusion.617_fusion", "fusion.653_fusion"
    ]
    assert r["top_ops"][0][1] == pytest.approx(0.000956739)
    # the one sample in the stretch has the worker loop in check_flags
    assert dict(r["idle_gaps"])["readback"] == pytest.approx(4.695e-06)
    assert all(" " not in k for k, _ in r["idle_gaps"])


def test_gap_attribution_on_a_made_up_timeline():
    ms = 1_000_000
    worker = ["replica.py:_worker_loop", "replica.py:_serve_session"]
    stacks = [
        ["operators.py:_append"] + worker,
        ["client.py:wait_for_upper", "operators.py:_gather_ready_ticks"]
        + worker,
        ["socket.py:accept", "replica.py:acceptor"],  # another thread
        ["somewhere.py:else"] + worker,
        worker,  # parked in the loop's own sleep
    ]
    doc = {
        "window_ns": [0, 100 * ms],
        "devices": [{"name": "/device:TPU:0", "programs": 2, "ops": [
            ["a_fusion", 0, 20 * ms], ["b_while", 10 * ms, 20 * ms],
            ["a_fusion", 60 * ms, 10 * ms],
        ]}],
        "stacks": stacks,
        "samples": [[35 * ms, [2, 0]], [45 * ms, [0]], [55 * ms, [1, 2]],
                    [75 * ms, [3]], [85 * ms, [4]]],
    }
    r = trace_reduce.reduce(doc, trace_reduce.load_rules())
    assert r["busy_s"] == pytest.approx(0.040)  # union: 0-30 and 60-70
    gaps = dict(r["idle_gaps"])
    # 30-60 ms: two samples appending, one waiting; 70-100 ms: one
    # sample no rule names, one parked in the worker loop
    assert gaps["persist_append"] == pytest.approx(0.020)
    assert gaps["waiting_for_a_tick"] == pytest.approx(0.010)
    assert gaps["unknown"] == pytest.approx(0.015)
    assert gaps["waiting_in_the_worker_loop"] == pytest.approx(0.015)
    assert r["top_ops"][0] == ["a_fusion", pytest.approx(0.030)]
    assert r["unknown_stacks"][0][1] == 1
    assert trace_reduce.op_name(
        "%while.358 = (u32[]{:T(128)}) while((u32[]) %t), condition=%c"
    ) == "while.358_while"


def test_a_stall_moves_both_end_to_end_metrics():
    # 20 reads a second for 10 s, each answered in 50 ms ...
    due = [i * 0.05 for i in range(200)]
    smooth = [{"due": d, "done": d + 0.05} for d in due]
    # ... and the same with the server stalled from 4.0 s to 5.0 s: every
    # read due in the stall is answered when it ends
    stalled = [
        {"due": d, "done": max(d + 0.05, 5.0) if 4.0 <= d < 5.0 else d + 0.05}
        for d in due
    ]
    p_smooth = metrics.percentile(metrics.read_latencies_ms(smooth), 95)
    p_stall = metrics.percentile(metrics.read_latencies_ms(stalled), 95)
    assert p_smooth == pytest.approx(50.0)
    assert p_stall > 400.0  # 20 of 200 reads waited, up to a second
    # an unanswered read misses any limit
    lost = smooth[:-11] + [{"due": 9.9, "done": None}] * 11
    assert metrics.percentile(metrics.read_latencies_ms(lost), 95) == math.inf
    # updates: 128 a tick, 20 ticks a second; the stalled view's upper
    # ends the window 20 ticks short
    times = [t for t in range(100, 300) for _ in range(128)]
    full = metrics.updates_per_s(times, 100, 300, 10.0)
    short = metrics.updates_per_s(times, 100, 280, 10.0)
    assert full == pytest.approx(2560.0)
    assert short == pytest.approx(2304.0)
    with pytest.raises(ValueError):
        metrics.percentile([], 95)


def test_roofline_bytes_from_shapes():
    b = roofline.step_bytes(
        {"lineitem": 6000, "supplier": 10}, {"lineitem": 128},
        {"lineitem": 112, "supplier": 40},
    )
    assert b == 6000 * 112 + 10 * 40 + 2 * 128 * 112
    share = roofline.hbm_roofline_share(b, 0.030, 819e9)
    assert 0 < share < 100 and share == pytest.approx(
        100 * (b / 819e9) / 0.030
    )
