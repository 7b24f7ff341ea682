"""Run the SLT corpus (tests/slt/*.slt) against a live deployment —
the sqllogictest tier of SURVEY.md §4.2."""

import glob
import os

import pytest

SLT_DIR = os.path.join(os.path.dirname(__file__), "slt")
SLT_FILES = sorted(glob.glob(os.path.join(SLT_DIR, "*.slt")))

# The corpus runs from THREE test files (this one, test_slt_b.py,
# test_slt_c.py), a third of it each: the driver runs the suite with
# `--dist loadfile`, which pins a file to one worker, and the whole
# corpus in one file was the suite's critical path (1204 s of a 1220 s
# run, PR 24). Every .slt file is still exactly one test case.
SHARDS = 3


def slt_params(shard: int) -> dict:
    """``pytest.mark.parametrize`` keywords for this shard's files."""
    # Blocks of four files round-robin: on the PR 24 timings this
    # balances the shards (461/450/293 s) better than striding by one.
    files = [
        p for i, p in enumerate(SLT_FILES) if (i // 4) % SHARDS == shard
    ]
    return {
        "argnames": "path",
        "argvalues": files,
        "ids": [os.path.basename(p) for p in files],
    }


def check_slt_file(path, coord):
    from materialize_tpu.testing.slt import run_slt_file

    n = run_slt_file(path, coord)
    assert n > 0


@pytest.fixture
def coord(tmp_path):
    import socket
    import threading

    from materialize_tpu.coord.coordinator import Coordinator
    from materialize_tpu.coord.protocol import PersistLocation
    from materialize_tpu.coord.replica import serve_forever
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )

    loc = PersistLocation(
        str(tmp_path / "blob"), str(tmp_path / "consensus.db")
    )
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ready = threading.Event()
    threading.Thread(
        target=serve_forever, args=(port, loc, "r0", ready), daemon=True
    ).start()
    assert ready.wait(10)
    c = Coordinator(
        PersistClient(
            FileBlob(loc.blob_root), SqliteConsensus(loc.consensus_path)
        ),
        tick_interval=None,
    )
    c.add_replica("r0", ("127.0.0.1", port))
    yield c
    c.shutdown()


def test_corpus_present():
    assert len(SLT_FILES) >= 3


@pytest.mark.parametrize(**slt_params(0))
def test_slt_file(path, coord):
    check_slt_file(path, coord)


class TestRunnerItself:
    def test_mismatch_reported_with_location(self, coord):
        from materialize_tpu.testing.slt import SltError, run_slt

        text = (
            "statement ok\n"
            "CREATE TABLE zz (x bigint NOT NULL)\n"
            "\n"
            "query I\n"
            "SELECT count(*) FROM zz\n"
            "----\n"
            "99\n"
        )
        with pytest.raises(SltError) as e:
            run_slt(text, coord, name="inline")
        assert "inline:4" in str(e.value) and "99" in str(e.value)
