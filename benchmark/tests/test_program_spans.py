"""The readers of the program's own spans, checked by hand on the CPU
against a cut of a chip run's flight-recorder dump
(``testdata/q15_backlog_spans_cut.jsonl``: one span committed before
the window and three inside it with their phase records, the frontier
reports sent between them and one after, five source ticks, one
statement span; ``q15_backlog``, seed 2147486211, TPU v5 lite).
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import program_spans  # noqa: E402

CUT = os.path.join(BENCH, "testdata", "q15_backlog_spans_cut.jsonl")
EXPECTED = os.path.join(BENCH, "testdata", "q15_backlog_spans_cut.expected.json")


def reader(stem: str):
    spec = importlib.util.spec_from_file_location(
        "layer_" + stem, os.path.join(BENCH, "layer_metrics", stem + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def expected():
    with open(EXPECTED) as f:
        return json.load(f)


@pytest.fixture
def ctx(expected, monkeypatch, tmp_path):
    """What the harness hands a reader, and the dump where the readers
    look for it."""
    dump_dir = tmp_path / "dump"
    dump_dir.mkdir()
    with open(CUT) as f:
        (dump_dir / program_spans.DUMP).write_text(f.read())
    monkeypatch.setenv(program_spans.VARIABLE, str(dump_dir))
    return {
        "window": {"seconds": 45.0, "source_upper": expected["source_upper"],
                   "view_upper": [0, 0]},
        "lag_rows": [{"frontier": f, "lag_ms": 0.0, "at": 0.0}
                     for f in expected["frontiers"]],
    }


def records():
    with open(CUT) as f:
        return [json.loads(ln) for ln in f]


def test_the_joins_are_exact(ctx, expected):
    got = program_spans.load(ctx)
    # the span committed before the window has no frontier among the
    # lag rows: it is not in, whatever its clock says
    assert [s["attrs"]["upper"] for s in got["spans"]] == expected["frontiers"]
    assert expected["before"] not in expected["frontiers"]
    assert got["ticks"] == expected["ticks"] == 8 * len(expected["frontiers"])
    lo, hi = expected["source_upper"]
    assert [r["attrs"]["t"] for r in got["source_ticks"]] == list(range(lo, hi))
    # ... and one tick of the cut lies before the window's first
    assert any(r["name"] == "source.tick" and r["attrs"]["t"] == lo - 1
               for r in records())
    for s in got["spans"]:
        assert set(s["phases"]) == {
            "span.wait", "span.fetch", "span.upload", "span.dispatch",
            "span.readback", "span.append", "span.publish",
        }


@pytest.mark.parametrize("stem,phases", [
    ("fetch_ms_per_tick", ["span.fetch"]),
    ("append_ms_per_tick", ["span.append"]),
    ("dispatch_ms_per_tick", ["span.upload", "span.dispatch"]),
    ("readback_ms_per_tick", ["span.readback"]),
])
def test_phase_milliseconds_a_tick(ctx, expected, stem, phases):
    # by hand: the phases' summed microseconds over the three window
    # spans (printed when the cut was made), over their 24 ticks
    us = sum(expected["phase_us"][p] for p in phases)
    assert reader(stem)(ctx) == pytest.approx(us / 1e3 / expected["ticks"])
    # and again from the file, without the module under test
    recs = records()
    ids = {r["span_id"] for r in recs if r["name"] == "span"
           and r["attrs"]["upper"] in expected["frontiers"]}
    again = sum(r["duration_us"] for r in recs
                if r["parent_id"] in ids and r["name"] in phases)
    assert again == us


def test_state_reloads_a_tick(ctx, expected):
    total = sum(expected["phase_reloads"][p]
                for p in ("span.wait", "span.fetch", "span.append"))
    assert reader("state_reloads_per_tick")(ctx) == pytest.approx(
        total / expected["ticks"]
    )
    assert expected["phase_reloads"]["span.dispatch"] == 0


def test_source_tick_work(ctx, expected):
    work = expected["work_ms"]
    assert len(work) == 4
    assert reader("source_tick_work_ms")(ctx) == pytest.approx(
        sum(work) / len(work)
    )


def test_unattributed_share(ctx, expected):
    want = 100.0 * (1.0 - expected["covered_us"] / expected["wall_us"])
    assert reader("span_unattributed_share")(ctx) == pytest.approx(want)
    assert 0.0 <= want < 5.0
    # the report sent after the last window span is not counted: by hand,
    # the phases of the three spans plus the reports between them
    recs = records()
    got = program_spans.load(ctx)
    w0 = got["spans"][0]["start_us"]
    w1 = max(s["end_us"] for s in got["spans"])
    reports = [r for r in recs if r["name"] == "replica.report_frontiers"]
    assert any(r["start_us"] >= w1 for r in reports)
    inside = sum(r["duration_us"] for r in reports if w0 <= r["start_us"] < w1)
    phases = sum(sum(p["duration_us"] for p in s["phases"].values())
                 for s in got["spans"])
    assert inside + phases == expected["covered_us"] == got["covered_us"]


STEMS = [
    "fetch_ms_per_tick", "append_ms_per_tick", "dispatch_ms_per_tick",
    "readback_ms_per_tick", "state_reloads_per_tick",
    "source_tick_work_ms", "span_unattributed_share",
]


@pytest.mark.parametrize("stem", STEMS)
def test_no_dump_reads_none(ctx, monkeypatch, tmp_path, stem):
    empty = tmp_path / "empty"
    empty.mkdir()
    monkeypatch.setenv(program_spans.VARIABLE, str(empty))
    assert reader(stem)(ctx) is None  # a parent program writes no dump


@pytest.mark.parametrize("stem", STEMS)
def test_an_empty_window_reads_none(ctx, stem):
    ctx["lag_rows"] = []  # no span committed inside the window
    ctx["window"]["source_upper"] = [5, 5]  # and no tick made in it
    assert reader(stem)(ctx) is None


def test_the_variable_is_set_at_import():
    # importing the module (above) set it, unless the caller had:
    # environmentd, started later, inherits it
    assert os.path.isdir(os.environ[program_spans.VARIABLE])
