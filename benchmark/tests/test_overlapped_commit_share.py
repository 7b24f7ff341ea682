"""``overlapped_commit_share``, checked by hand on the CPU against a cut
of a chip run's flight-recorder dump
(``testdata/q15_backlog_overlap_spans_cut.jsonl``: four consecutive
spans of the judged view from inside the window, with their phase
records and the frontier reports sent between them; ``q15_backlog``,
seed 2147486603, TPU v5 lite, PR 33). Over the backlog every span was
written with its successor dispatched; the fourth is outside the window
the test hands the reader. A record is closed when its span's last
append is durable, one call after the one that ran it, so consecutive
records overlap in time.
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
CUT = os.path.join(DATA, "q15_backlog_overlap_spans_cut.jsonl")
EXPECTED = os.path.join(DATA, "q15_backlog_overlap_spans_cut.expected.json")
# dumps of the program before it wrote a span beneath its successor
# (PR 28's cut, PR 30's of the other cell)
OLD = ["q15_backlog_prefetch_spans_cut", "q3_backlog_spans_cut"]


def read(ctx: dict):
    spec = importlib.util.spec_from_file_location(
        "layer_overlapped_commit_share",
        os.path.join(BENCH, "layer_metrics", "overlapped_commit_share.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_share_of_ticks_written_beneath_the_successor(monkeypatch, tmp_path):
    with open(EXPECTED) as f:
        want = json.load(f)
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    # by hand (printed when the cut was made): 8 of 8, three times
    assert want["overlapped_commit_ticks"] == [8, 8, 8]
    assert want["ticks"] == 24
    assert read(ctx) == pytest.approx(100.0)
    # and again from the file, without the modules under test
    with open(CUT) as f:
        records = [json.loads(ln) for ln in f]
    spans = [r for r in records if r["name"] == "span"]
    inside = [s for s in spans if s["attrs"]["upper"] in want["frontiers"]]
    assert [s["attrs"]["overlapped_commit_ticks"] for s in inside] == [8, 8, 8]
    assert sum(s["attrs"]["ticks"] for s in inside) == 24
    # the cut's fourth span is after the window: joined out by its upper
    assert want["after"] in [s["attrs"]["upper"] for s in spans]
    assert want["after"] not in want["frontiers"]
    # a record closes when its commit is durable, beneath its
    # successor's dispatch: it ends after the next one has started, and
    # its append starts after the next one's dispatch
    by_id = {s["span_id"]: s for s in spans}
    phases = {
        (r["parent_id"], r["name"]): r
        for r in records if r["parent_id"] in by_id
    }
    spans.sort(key=lambda s: s["start_us"])
    for a, b in zip(spans, spans[1:]):
        assert a["attrs"]["upper"] == b["attrs"]["lower"]
        assert a["start_us"] + a["duration_us"] > b["start_us"]
        assert (
            phases[(a["span_id"], "span.append")]["start_us"]
            > phases[(b["span_id"], "span.dispatch")]["start_us"]
        )


def test_a_span_partly_overlapped_counts_its_ticks(monkeypatch, tmp_path):
    """One span of the three flushed by a barrier: 16 of 24."""
    with open(CUT) as f:
        records = [json.loads(ln) for ln in f]
    with open(EXPECTED) as f:
        want = json.load(f)
    flushed = next(
        r for r in records
        if r["name"] == "span" and r["attrs"]["upper"] == want["frontiers"][1]
    )
    flushed["attrs"]["overlapped_commit_ticks"] = 0
    edited = tmp_path / "edited.jsonl"
    edited.write_text("".join(json.dumps(r) + "\n" for r in records))
    ctx = ctx_of(str(edited), EXPECTED, monkeypatch, tmp_path)
    assert read(ctx) == pytest.approx(100.0 * 16 / 24)


@pytest.mark.parametrize("stem", OLD)
def test_a_program_without_the_counter_reads_none(
    stem, monkeypatch, tmp_path
):
    ctx = ctx_of(
        os.path.join(DATA, stem + ".jsonl"),
        os.path.join(DATA, stem + ".expected.json"),
        monkeypatch, tmp_path,
    )
    assert program_spans.load(ctx)["ticks"] == 24  # the spans are there
    assert read(ctx) is None  # ... without the attribute: left out


def test_no_dump_and_an_empty_window_read_none(monkeypatch, tmp_path):
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    ctx["lag_rows"] = []  # no span committed inside the window
    assert read(ctx) is None
    ctx = ctx_of(CUT, EXPECTED, monkeypatch, tmp_path)
    os.remove(tmp_path / program_spans.DUMP)
    assert read(ctx) is None
