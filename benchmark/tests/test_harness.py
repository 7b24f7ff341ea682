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


def backlog_timeline(behind_at_open, made=20.0, span_s=0.3, stall=None,
                     slow=None, seconds=45.0):
    """A made-up window: a generator making ``made`` ticks a second of
    96 updates each (``stall``: (from, to) seconds in which it makes
    none), a replica committing spans of up to 8 ready ticks every
    ``span_s`` seconds (``slow``: (from, to, factor) seconds in which a
    span takes ``factor`` times as long), the view 1,000 at the open,
    sampled every 10 ms. Returns the rule's arguments."""
    t0, f0 = 100.0, 1000

    def made_by(t):
        e = t - t0
        if stall:
            e -= min(max(e - stall[0], 0.0), stall[1] - stall[0])
        return f0 + behind_at_open + int(made * e)

    commits, f, a = [(f0, t0 - 0.1)], f0, t0 - 0.1
    while a < t0 + seconds:
        k = min(8, made_by(a) - f)
        if k <= 0:
            a += 0.01
            continue
        factor = slow[2] if slow and slow[0] <= a - t0 < slow[1] else 1.0
        f, a = f + k, a + span_s * factor
        commits.append((f, a))
    close_at = t0 + seconds
    close = (max(c for c, at in commits if at <= close_at), close_at)
    uppers = [(t0 + i * 0.01, made_by(t0 + i * 0.01))
              for i in range(int(seconds * 100) + 1)]
    times = [t for t in range(f0, close[0] + 8) for _ in range(96)]
    return times, commits, uppers, (f0, t0), close


def stretch_and_window(behind_at_open, **kw):
    """The rule's stretch, its rate and the whole window's rate."""
    times, commits, uppers, open_, close = backlog_timeline(
        behind_at_open, **kw
    )
    got = metrics.backlogged_stretch(
        commits, uppers, open_, close, span_ticks=8
    )
    rate = metrics.updates_per_s(
        times, open_[0], got["view_upper"], got["seconds"]
    )
    window = metrics.updates_per_s(
        times, open_[0], close[0], close[1] - open_[1]
    )
    return got, rate, window


def test_the_backlogged_stretch_on_a_made_up_timeline():
    replica = 8 / 0.3 * 96  # 2,560 updates/s while a full span waits
    # 208 ticks behind, 26.7 absorbed against 20 made: a full span is
    # waiting until 30 s
    got, rate, window = stretch_and_window(208)
    assert got["seconds"] == pytest.approx(30.0, abs=0.6)
    assert got["backlog_at_end"] < 8
    assert rate == pytest.approx(replica, rel=0.01)
    assert window < 0.93 * replica  # the window reads the generator too
    # 1,000 behind: a full span waits all through; the rule is the
    # window's, to within one span
    got, rate, window = stretch_and_window(1000)
    assert got["seconds"] == pytest.approx(45.0)
    assert rate == window
    assert got["backlog_at_end"] == pytest.approx(1000 - 45 * 6.67, abs=20)
    assert window == pytest.approx(replica, rel=0.007)
    # caught up at the open (3 behind, under a span): the whole window
    got, rate, window = stretch_and_window(3)
    assert got["seconds"] == pytest.approx(45.0)
    assert rate == window == pytest.approx(20 * 96, rel=0.02)
    # the generator stalls 10 s while the spans are still full, and the
    # replica's spans take twice as long in the same 10 s: the stall stays
    # in the stretch and the replica is not flattered
    got, rate, window = stretch_and_window(
        1000, stall=(10.0, 20.0), slow=(10.0, 20.0, 2.0)
    )
    assert got["seconds"] == pytest.approx(45.0) and rate == window
    assert window == pytest.approx(replica * 40 / 45, rel=0.01)
    # ... and a stall that empties the backlog ends the stretch for good,
    # though the generator comes back faster than the replica
    got, _, _ = stretch_and_window(100, made=40.0, stall=(2.0, 12.0))
    assert got["seconds"] < 12.0


def test_the_recorded_spans_replay_through_the_stretch_rule():
    with open(os.path.join(BENCH, "testdata",
                           "q15_backlog_spans_cut.jsonl")) as f:
        records = [json.loads(ln) for ln in f]
    with open(os.path.join(BENCH, "testdata",
                           "q15_backlog_spans_cut.expected.json")) as f:
        expected = json.load(f)
    end = {id(r): (r["start_us"] + r["duration_us"]) / 1e6 for r in records}
    commits = [(r["attrs"]["upper"], end[id(r)])
               for r in records if r["name"] == "span"]
    ticks = sorted((end[id(r)], r["attrs"]["t"] + 1)
                   for r in records if r["name"] == "source.tick")
    lo, hi = expected["source_upper"]
    # the window opened after tick lo - 1 was appended: the sources at lo
    opened = next(at for at, u in ticks if u == lo)
    open_ = (expected["before"], opened)
    close = (expected["frontiers"][-1], commits[-1][1])
    uppers = [t for t in ticks if t[0] >= opened] + [(close[1], hi)]
    got = metrics.backlogged_stretch(commits, uppers, open_, close, 8)
    # never ran out: the whole window, up to the last commit
    assert got["seconds"] == pytest.approx(close[1] - opened)
    assert got["view_upper"] == close[0] == 649
    assert got["backlog_at_end"] == hi - 649 == 572
    # the same spans with the sources 8 ticks short at the second
    # commit: the stretch ends at it
    short = [(at, min(u, commits[1][0] + 7)) for at, u in uppers]
    got = metrics.backlogged_stretch(commits, short, open_, close, 8)
    assert got["view_upper"] == 633
    assert got["seconds"] == pytest.approx(commits[1][1] - opened)


def test_the_window_stalls_name_the_slowest_tick_and_span(tmp_path):
    import program_spans

    path = os.path.join(BENCH, "testdata", "q15_backlog_spans_cut.jsonl")
    with open(path) as f:
        records = [json.loads(ln) for ln in f]
    lo = min(r["start_us"] for r in records) / 1e6
    hi = max(r["start_us"] + r["duration_us"] for r in records) / 1e6
    got = program_spans.window_stalls(lo, hi, path)
    # tick 1218 ended 23.448 ms after tick 1217, the widest of four gaps
    assert got["tick"]["gap_ms"] == pytest.approx(23.448)
    assert got["tick"]["work_ms"] == pytest.approx(17.877789)
    assert got["tick"]["encode_ms"] == pytest.approx(1.128229)
    # the first of four spans ran longest to the next one's start
    assert got["span"]["wall_ms"] == pytest.approx(427.987)
    assert got["span"]["median_ms"] == pytest.approx(420.569)
    assert got["span"]["at_s"] == pytest.approx(
        (1790917779563977 - lo * 1e6) / 1e6)
    assert got["span"]["excess_s"] == 0
    assert set(got["span"]["phases_ms"]) >= {
        "span.upload", "span.dispatch", "span.readback", "span.append"}
    # a span 1.2 s late: its excess over twice the median is charged
    late = [dict(r) for r in records]
    for r in late:
        if r["name"] == "span" and r["attrs"]["upper"] >= 641:
            r["start_us"] += 1_200_000
    moved = str(tmp_path / "late_spans.jsonl")
    with open(moved, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in late)
    got = program_spans.window_stalls(lo, hi + 2.0, moved)
    assert got["span"]["wall_ms"] == pytest.approx(1620.569)
    assert got["span"]["excess_s"] == pytest.approx(1.620569 - 2 * 0.427987)
    assert program_spans.window_stalls(lo, hi, path + ".missing") is None


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
