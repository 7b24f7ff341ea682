"""``fed_pipeline_share`` and ``string_table_builds``, checked by hand on
the CPU against a cut of a chip run's flight-recorder dump
(``testdata/q9_backlog_spans_cut.jsonl``: four consecutive spans of the
judged view ``q9`` from inside the window, with their phase records and
the frontier reports sent between them; ``q9_backlog``, seed
2147484002, TPU v5 lite). Q9 joins six relations in one delta join, so
a step dispatches six pipelines; only ``lineitem`` ticks, so one of them
is fed. The fourth span is outside the window the test hands the
reader.
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

from test_prefetched_tick_share import ctx_of  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
CUT = os.path.join(DATA, "q9_backlog_spans_cut.jsonl")
EXPECTED = os.path.join(DATA, "q9_backlog_spans_cut.expected.json")
# a dump of the program before its spans carried the attributes (the
# recorded cut of q3_backlog, whose view has a delta join of three)
OLD = "q3_backlog_spans_cut"


def reader(stem: str):
    spec = importlib.util.spec_from_file_location(
        "layer_" + stem, os.path.join(BENCH, "layer_metrics", stem + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _inside(want: dict) -> list:
    with open(CUT) as f:
        spans = [r for r in map(json.loads, f) if r["name"] == "span"]
    return [s for s in spans if s["attrs"]["upper"] in want["frontiers"]]


def test_fed_share_is_one_pipeline_in_six(monkeypatch, tmp_path):
    with open(EXPECTED) as f:
        want = json.load(f)
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    # by hand (printed when the cut was made): 8 ticks a span, six
    # pipelines a tick, one of them fed
    assert want["ticks"] == 24
    assert want["delta_pipelines"] == [48, 48, 48]
    assert want["fed_delta_pipelines"] == [8, 8, 8]
    assert reader("fed_pipeline_share")(ctx) == pytest.approx(100.0 / 6)
    # and again from the file, without the modules under test
    inside = _inside(want)
    assert len(inside) == 3
    assert sum(s["attrs"]["fed_delta_pipelines"] for s in inside) == 24
    assert sum(s["attrs"]["delta_pipelines"] for s in inside) == 144


def test_no_string_table_is_built_in_the_window(monkeypatch, tmp_path):
    with open(EXPECTED) as f:
        want = json.load(f)
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    assert want["string_tables_built"] == [0, 0, 0]
    got = reader("string_table_builds")(ctx)
    assert isinstance(got, float) and got == 0.0
    assert [s["attrs"]["string_tables_built"] for s in _inside(want)] == [
        0, 0, 0
    ]


def test_builds_are_summed_over_the_window(monkeypatch, tmp_path):
    """A dictionary that moved: two builds on one span of the window,
    three on the span after it, which is not counted."""
    with open(EXPECTED) as f:
        want = json.load(f)
    records = []
    with open(CUT) as f:
        for r in map(json.loads, f):
            if r["name"] == "span":
                if r["attrs"]["upper"] == want["frontiers"][1]:
                    r["attrs"]["string_tables_built"] = 2
                if r["attrs"]["upper"] == want["after"]:
                    r["attrs"]["string_tables_built"] = 3
            records.append(r)
    cut = tmp_path / "moved.jsonl"
    cut.write_text("".join(json.dumps(r) + "\n" for r in records))
    ctx = ctx_of(str(cut), EXPECTED, monkeypatch, tmp_path)
    assert reader("string_table_builds")(ctx) == 2.0


@pytest.mark.parametrize("stem", ["fed_pipeline_share", "string_table_builds"])
def test_a_program_without_the_attributes_reads_none(
    stem, monkeypatch, tmp_path
):
    ctx = ctx_of(
        os.path.join(DATA, OLD + ".jsonl"),
        os.path.join(DATA, OLD + ".expected.json"), monkeypatch, tmp_path,
    )
    assert program_spans.load(ctx)["ticks"] == 24  # the spans are there
    assert reader(stem)(ctx) is None  # ... without the attributes


@pytest.mark.parametrize("stem", ["fed_pipeline_share", "string_table_builds"])
def test_no_dump_reads_none(stem, monkeypatch, tmp_path):
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    os.remove(tmp_path / program_spans.DUMP)
    assert reader(stem)(ctx) is None
