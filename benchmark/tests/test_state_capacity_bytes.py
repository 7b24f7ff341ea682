"""``state_capacity_bytes``, checked by hand on the CPU against a cut of
a chip run's flight-recorder dump
(``testdata/q3_backlog_spans_cut.jsonl``: four consecutive spans of the
judged view ``q3`` with their phase records and the frontier reports
sent between them; ``q3_backlog``, seed 2147490302, TPU v5 lite, PR 30,
from inside the run's window). The fourth span is outside the window
the test hands the reader.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import program_spans  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
CUT = os.path.join(DATA, "q3_backlog_spans_cut.jsonl")
EXPECTED = os.path.join(DATA, "q3_backlog_spans_cut.expected.json")
# a dump of the program before its spans said what the view holds
# (PR 28's cut of a q15_backlog run)
OLD_CUT = os.path.join(DATA, "q15_backlog_prefetch_spans_cut.jsonl")
OLD_EXPECTED = os.path.join(
    DATA, "q15_backlog_prefetch_spans_cut.expected.json"
)


def read(ctx: dict):
    spec = importlib.util.spec_from_file_location(
        "layer_state_capacity_bytes",
        os.path.join(BENCH, "layer_metrics", "state_capacity_bytes.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def ctx_of(cut: str, expected: str, monkeypatch, tmp_path) -> dict:
    """What the harness hands a reader, and the dump where it looks."""
    with open(expected) as f:
        want = json.load(f)
    with open(cut) as f:
        (tmp_path / program_spans.DUMP).write_text(f.read())
    monkeypatch.setenv(program_spans.VARIABLE, str(tmp_path))
    return {
        "window": {"seconds": 45.0, "source_upper": want["source_upper"],
                   "view_upper": [0, 0]},
        "lag_rows": [{"frontier": f, "lag_ms": 0.0, "at": 0.0}
                     for f in want["frontiers"]],
    }


def test_reads_the_last_window_spans_attribute(monkeypatch, tmp_path):
    with open(EXPECTED) as f:
        want = json.load(f)
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    got = read(ctx)
    assert isinstance(got, float)
    assert got == want["state_capacity_bytes"] > 0
    # and again from the file, without the modules under test
    with open(CUT) as f:
        spans = [r for r in map(json.loads, f) if r["name"] == "span"]
    inside = [s for s in spans if s["attrs"]["upper"] in want["frontiers"]]
    assert len(inside) == 3 and len(spans) == 4
    last = max(inside, key=lambda s: s["attrs"]["upper"])
    assert last["attrs"]["state_capacity_bytes"] == got
    # sized before the first step and never regrown: every span of
    # the cut carries the one value
    assert {s["attrs"]["state_capacity_bytes"] for s in spans} == {got}
    # the cut's fourth span is after the window: joined out by its upper
    assert want["after"] in [s["attrs"]["upper"] for s in spans]
    assert want["after"] not in want["frontiers"]


def test_a_program_without_the_attribute_reads_none(monkeypatch, tmp_path):
    ctx = ctx_of(OLD_CUT, OLD_EXPECTED, monkeypatch, tmp_path)
    assert program_spans.load(ctx)["ticks"] == 24  # the spans are there
    assert read(ctx) is None  # ... without the attribute: left out


def test_no_dump_reads_none(monkeypatch, tmp_path):
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    os.remove(tmp_path / program_spans.DUMP)
    assert read(ctx) is None


def test_an_empty_window_reads_none(monkeypatch, tmp_path):
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    ctx["lag_rows"] = []  # no span committed inside the window
    assert read(ctx) is None
