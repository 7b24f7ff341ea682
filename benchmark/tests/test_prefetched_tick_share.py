"""``prefetched_tick_share``, checked by hand on the CPU against a cut of
a chip run's flight-recorder dump
(``testdata/q15_backlog_prefetch_spans_cut.jsonl``: the judged view's
first four spans after its hydration with their phase records and the
frontier reports sent between them; ``q15_backlog``, seed 2147486311,
TPU v5 lite, PR 28). The first span found nothing kept, the others were
gathered whole by the span before them; the fourth is outside the
window the test hands the reader.
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

DATA = os.path.join(BENCH, "testdata")
CUT = os.path.join(DATA, "q15_backlog_prefetch_spans_cut.jsonl")
EXPECTED = os.path.join(DATA, "q15_backlog_prefetch_spans_cut.expected.json")
# a dump of the program before it kept ticks (PR 27's cut)
OLD_CUT = os.path.join(DATA, "q15_backlog_spans_cut.jsonl")
OLD_EXPECTED = os.path.join(DATA, "q15_backlog_spans_cut.expected.json")


def read(ctx: dict):
    spec = importlib.util.spec_from_file_location(
        "layer_prefetched_tick_share",
        os.path.join(BENCH, "layer_metrics", "prefetched_tick_share.py"),
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


def test_share_of_ticks_the_span_before_gathered(monkeypatch, tmp_path):
    with open(EXPECTED) as f:
        want = json.load(f)
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    # by hand (printed when the cut was made): 0 of 8, 8 of 8, 8 of 8
    assert want["prefetched_ticks"] == [0, 8, 8] and want["ticks"] == 24
    assert read(ctx) == pytest.approx(100.0 * 16 / 24)
    # and again from the file, without the modules under test
    with open(CUT) as f:
        spans = [r for r in map(json.loads, f) if r["name"] == "span"
                 and r["attrs"]["upper"] in want["frontiers"]]
    assert [s["attrs"]["prefetched_ticks"] for s in spans] == [0, 8, 8]
    assert sum(s["attrs"]["ticks"] for s in spans) == 24
    # the cut's fourth span is after the window: joined out by its upper
    with open(CUT) as f:
        uppers = [r["attrs"]["upper"] for r in map(json.loads, f)
                  if r["name"] == "span"]
    assert want["after"] in uppers and want["after"] not in want["frontiers"]


def test_a_program_that_keeps_no_ticks_reads_none(monkeypatch, tmp_path):
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
