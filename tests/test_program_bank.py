"""Persistent AOT program bank (ISSUE 16): bank-served executables are
row-for-row equal to fresh compiles under duplicate/retraction churn,
corruption and version skew degrade to clean compiles (never crash,
never wrong results), tier quantization makes rung-mates share bank
keys, `environmentd --recover` serves recompiles from the bank (ZERO
fresh XLA compiles for unchanged fingerprints), and async compile
serves a fresh DDL in generic merge mode until the specialized program
hot-swaps in at a span boundary.

CPU caveat pinned here too: jaxlib's CPU PJRT cannot re-serialize a
module whose compile was not the first in-process instance (the
payload later fails deserialization with "Symbols not found").
``ProgramBank.store`` load-verifies every payload before export, so
such entries never reach the bank — and the tests that assert bank
HITS export from a fresh subprocess (``_EXPORT_SCRIPT``) where every
compile is the first of its module.
"""

import os
import pickle
import time as _time

import numpy as np
import pytest

from materialize_tpu.compile.bank import (
    ProgramBank,
    configure_bank,
    get_bank,
)
from materialize_tpu.expr import relation as mir
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema
from materialize_tpu.utils.compile_ledger import LEDGER, CompileLedger

from .oracle import net_rows

SCH = Schema(
    (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
)


@pytest.fixture(autouse=True)
def _bank_off_after():
    """Every test leaves the process-global bank unconfigured."""
    yield
    configure_bank(None)


def _churn(df: Dataflow, seed: int = 7, steps: int = 6, n: int = 32):
    """Deterministic duplicate/retraction churn into ``df``."""
    rng = np.random.default_rng(seed)
    t0 = df.time
    for i in range(steps):
        k = rng.integers(0, 64, n).astype(np.int64)
        v = rng.integers(0, 8, n).astype(np.int64)
        d = rng.choice(np.asarray([1, 1, -1]), n).astype(np.int64)
        df.run_steps([{"src": Batch.from_numpy(
            SCH, [k, v], np.uint64(t0 + i), d, capacity=64
        )}])
    assert not df.check_flags()
    return net_rows(df.peek())


def _mk() -> Dataflow:
    return Dataflow(mir.Get("src", SCH), name="bank-prop")


# The export leg of the bank tests runs in a FRESH subprocess with a
# COLD JAX persistent compilation cache: this runtime cannot reliably
# re-serialize an executable that was itself rehydrated from the XLA
# persistent cache (or JIT-compiled earlier in the same process), and
# store verification (ProgramBank.store) rejects those payloads —
# which would leave nothing to serve when the checkout's .jax_cache
# is warm from earlier runs.
_EXPORT_SCRIPT = """\
import json, sys

from materialize_tpu.compile.bank import configure_bank, get_bank
from tests.test_program_bank import _churn, _mk

configure_bank(sys.argv[1])
rows = _churn(_mk())
b = get_bank()
print(json.dumps({
    "rows": [[int(x) for x in r] for r in rows],
    "stores": b.stats["stores"],
    "errors": b.stats["errors"],
}))
"""


@pytest.fixture(scope="module")
def exported_bank(tmp_path_factory):
    """(bank_dir, report) from one fresh-subprocess churn of `_mk()`.
    The directory is shared across tests — copy it before mutating."""
    import json
    import subprocess
    import sys

    bank_dir = str(tmp_path_factory.mktemp("bank-export") / "bank")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("xla-cache")
    )
    proc = subprocess.run(
        [sys.executable, "-c", _EXPORT_SCRIPT, bank_dir],
        cwd=repo, capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["stores"] > 0, report
    return bank_dir, report


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(placed, tmp_path):
    """Where the package puts the XLA persistent cache (PR 24): with
    JAX_COMPILATION_CACHE_DIR set it sets NO directory in code (JAX
    reads the variable itself); without it the directory is
    <checkout>/.jax_cache — never the home directory, a temporary name
    or anything that moves between runs. A child interpreter that only
    imports the package reports its config."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOME"] = str(tmp_path / "home")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    out = subprocess.run(
        [
            sys.executable, "-c",
            "import jax, materialize_tpu; "
            "print(jax.config.jax_compilation_cache_dir)",
        ],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    want = (
        str(tmp_path / "xla") if placed
        else os.path.join(repo, ".jax_cache")
    )
    assert out.stdout.strip().splitlines()[-1] == want
    assert not (tmp_path / "home").exists()  # nothing under HOME


def _copy_bank(src: str, tmp_path) -> str:
    import shutil

    dst = str(tmp_path / "bank")
    shutil.copytree(src, dst)
    return dst


def _canon(rows):
    return [[int(x) for x in r] for r in rows]


class TestBankEquivalence:
    def test_banked_equals_fresh_under_churn(
        self, tmp_path, exported_bank
    ):
        """The oracle property: the SAME churn through (a) a fresh
        in-process compile, (b) a bank-exporting run in a fresh
        subprocess, (c) an in-process bank-SERVED run (new jit
        wrappers, executables deserialized from disk) nets identical
        rows — and (c) actually hit the bank."""
        src, exported = exported_bank
        bank_dir = _copy_bank(src, tmp_path)
        configure_bank(None)
        want = _churn(_mk())
        configure_bank(bank_dir)
        bank = get_bank()
        hits_before = bank.stats["hits"]
        served = _churn(_mk())
        assert bank.stats["hits"] > hits_before, bank.stats
        assert _canon(served) == _canon(want) == exported["rows"]
        # And the ledger classified the serves as bank_hit, with the
        # stored compile wall carried as recovered seconds.
        s = LEDGER.summary()
        assert s["bank_hits"] > 0

    def test_corrupt_entry_recompiles_cleanly(
        self, tmp_path, exported_bank
    ):
        """A truncated entry is a miss, not a crash: the damaged file
        is unlinked, the program recompiles fresh, and the results
        stay row-for-row correct."""
        src, _ = exported_bank
        bank_dir = _copy_bank(src, tmp_path)
        configure_bank(None)
        want = _churn(_mk())
        configure_bank(bank_dir)
        bank = get_bank()
        ents = bank.entries()
        assert ents, "export produced no bank entries"
        for e in ents:
            path = bank.path_for(e["kind"], e["fingerprint"], e["tier"])
            with open(path, "r+b") as f:
                f.truncate(64)
        errors_before = bank.stats["errors"]
        got = _churn(_mk())
        assert got == want
        assert bank.stats["errors"] > errors_before
        # Damaged entries never survive: each truncated file was
        # unlinked, and at most replaced by a verified re-store.
        for e in ents:
            path = bank.path_for(e["kind"], e["fingerprint"], e["tier"])
            assert (
                not os.path.exists(path)
                or os.path.getsize(path) != 64
            ), "truncated entry survived the serve"

    def test_version_skew_entry_skipped_not_unlinked(
        self, tmp_path, exported_bank
    ):
        """A stale-jaxlib entry is skipped (miss + error) but NOT
        deleted — another deployment at that version may still own
        it."""
        src, _ = exported_bank
        bank_dir = _copy_bank(src, tmp_path)
        bank = ProgramBank(bank_dir)
        e = bank.entries()[0]
        path = bank.path_for(e["kind"], e["fingerprint"], e["tier"])
        with open(path, "rb") as f:
            entry = pickle.load(f)
        entry["meta"]["jaxlib"] = "0.0.0-stale"
        with open(path, "wb") as f:
            pickle.dump(entry, f)
        fresh = ProgramBank(bank_dir)
        assert fresh.lookup(
            e["kind"], e["fingerprint"], e["tier"]
        ) is None
        assert os.path.exists(path), "skewed entry must not be unlinked"
        assert fresh.stats["errors"] == 1
        assert fresh.stats["misses"] == 1

    def test_missing_entry_is_plain_miss(self, tmp_path):
        bank = ProgramBank(str(tmp_path / "bank"))
        assert bank.lookup("step", "cafebabe", "t0_0") is None
        assert bank.stats["misses"] == 1
        assert bank.stats["errors"] == 0


class TestLedgerBankClassification:
    def test_bank_presence_prevents_cold_miss_classification(
        self, tmp_path
    ):
        """Satellite 1: `_seen` eviction (or a fresh process) must not
        misclassify a bank-held program as a cold miss — existence in
        the bank proves the key compiled SOMEWHERE."""
        b = configure_bank(str(tmp_path / "bank"))
        open(b.path_for("step", "cafe", "t1_8"), "wb").close()
        led = CompileLedger()
        led.record("step", "df", "cafe", "t1_8", 0.1)
        led.record("span", "df", "beef", "t2_8", 0.1)
        by_kind = {r.kind: r.cache for r in led.records()}
        assert by_kind["step"] == "hit"
        assert by_kind["span"] == "miss"

    def test_bank_hit_records_kept_out_of_compile_totals(self):
        """bank_hit serves are NOT compiles: summary() keeps the
        pre-bank meaning of compiles/misses/hits and counts the bank
        separately, with the recovered wall."""
        led = CompileLedger()
        led.record("step", "df", "aa", "t", 1.0, cache="miss",
                   bank="miss")
        led.record("step", "df", "aa", "t", 0.01, cache="bank_hit",
                   recovered_seconds=1.0)
        s = led.summary()
        assert s["compiles"] == 1
        assert s["misses"] == 1
        assert s["bank_hits"] == 1
        assert s["bank_misses"] == 1
        assert s["bank_seconds_recovered"] == 1.0


class TestTierQuantization:
    def test_quantize_cap_menu(self):
        from materialize_tpu.plan.decisions import (
            QUANT_MENU_FLOOR,
            quantization_menu,
            quantize_cap,
        )

        assert quantize_cap(1) == QUANT_MENU_FLOOR
        assert quantize_cap(256) == 256
        assert quantize_cap(257) == 512
        assert quantize_cap(300) == quantize_cap(400) == 512
        assert quantize_cap(512) == 512
        assert quantize_cap(513) == 1024
        menu = quantization_menu(256, 4096)
        assert list(menu) == [256, 512, 1024, 2048, 4096]

    def test_rung_mates_share_state_shapes(self):
        """Two DDLs whose capacities differ only within one pow2 rung
        render identical state shapes — the precondition for shared
        bank keys (the end-to-end key-sharing proof is the
        `tier-quantization` gate of scripts/check_plans.py --bench,
        which is run by hand, not by tier-1)."""
        import jax

        a = Dataflow(mir.Get("src", SCH), name="qa", state_cap=300)
        b = Dataflow(mir.Get("src", SCH), name="qb", state_cap=400)
        sa = jax.tree_util.tree_map(lambda x: x.shape, a.states)
        sb = jax.tree_util.tree_map(lambda x: x.shape, b.states)
        assert sa == sb

    def test_spine_growth_quantizes_but_never_shrinks(self):
        from materialize_tpu.plan.decisions import quantize_cap

        df = Dataflow(mir.Get("src", SCH), name="qg")
        before = df.output.runs_b[1].capacity
        target = before + 300  # off-menu, above the current rung
        df._grow_for(("out", 1), target=target)
        grown = df.output.runs_b[1].capacity
        # the grown run's capacity landed on the pow2 menu
        assert grown == quantize_cap(target)
        assert grown > before
        # a smaller target never shrinks the run
        df._grow_for(("out", 1), target=before)
        assert df.output.runs_b[1].capacity == grown


def _poll(fn, timeout: float = 90.0, every: float = 0.2):
    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        _time.sleep(every)
    raise AssertionError(f"condition never became true: {fn}")


class TestRecoverFromBank:
    def test_recover_serves_programs_from_bank(self, tmp_path):
        """The restart proof: boot, install a projection MV, shut
        down; a second boot over the same data dir re-renders every
        dataflow with ZERO fresh XLA compiles — every program a bank
        hit, the skipped wall printed in the recovery report."""
        import jax

        from materialize_tpu.server.environmentd import Environment

        # Cold XLA persistent cache for the test's duration: an
        # executable rehydrated from a warm host cache cannot be
        # re-serialized (see module docstring), so boot1's stores
        # must come from true fresh compiles to be deterministic
        # across repeated suite runs on one host.
        old_cache = jax.config.jax_compilation_cache_dir
        jax.config.update(
            "jax_compilation_cache_dir", str(tmp_path / "xla-cache")
        )
        data = str(tmp_path / "envd")
        env1 = Environment(
            data, n_replicas=1, tick_interval=None,
            in_process_replicas=True,
        )
        try:
            # Three columns + arithmetic projection: a module shape
            # nothing else in the suite compiles, so boot1's stores
            # are first-in-process compiles (see module docstring —
            # re-serialized modules fail store verification).
            env1.coord.execute(
                "CREATE TABLE rp (k BIGINT NOT NULL, "
                "v BIGINT NOT NULL, w BIGINT NOT NULL)"
            )
            env1.coord.execute(
                "INSERT INTO rp VALUES (1, 10, 100), (2, 20, 200), "
                "(1, 5, 50)"
            )
            env1.coord.execute(
                "CREATE MATERIALIZED VIEW rpmv AS "
                "SELECT k, v + w FROM rp WHERE k >= 1"
            )
            rows1 = sorted(
                env1.coord.execute("SELECT * FROM rpmv").rows
            )
            r1 = env1.recovery_report()["compiles"]
            assert r1["bank"]["stores"] > 0, r1
        finally:
            env1.shutdown()
        # The ledger is process-global: clear it so boot2's breakdown
        # counts only the recovery's own compiles.
        LEDGER.clear()
        env2 = Environment(
            data, n_replicas=1, tick_interval=None,
            in_process_replicas=True,
        )
        try:
            rep = env2.await_recovery()
            c = rep["compiles"]
            assert c["bank_hits"] > 0, c
            assert c["bank_misses"] == 0, c
            assert c["fresh_compiles"] == 0, c
            assert c["compile_seconds_recovered"] > 0, c
            rows2 = sorted(
                env2.coord.execute("SELECT * FROM rpmv").rows
            )
            assert rows2 == rows1
            # The relational + EXPLAIN surfaces agree.
            res = env2.coord.execute(
                "SELECT metric, value FROM mz_recovery "
                "WHERE scope = 'compile'"
            )
            got = dict(res.rows)
            assert got["bank_hits"] >= 1
            assert got["bank_misses"] == 0
            res = env2.coord.execute(
                "SELECT kind FROM mz_program_bank "
                "WHERE state = 'stored'"
            )
            assert res.rows, "mz_program_bank served no entries"
        finally:
            env2.shutdown()
            jax.config.update("jax_compilation_cache_dir", old_cache)


class TestAsyncCompileHotSwap:
    def test_fresh_ddl_serves_generic_then_swaps(self, tmp_path):
        """Async compile (tentpole c): with the dyncfg on and a bank
        configured, a fresh MV serves correct results IMMEDIATELY on
        the generic merge-mode program, then hot-swaps to the
        specialized program at a span boundary; results stay correct
        across the swap and the swap is visible in mz_program_bank."""
        import threading

        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )
        from materialize_tpu.testing.chaos import _free_port
        from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

        configure_bank(str(tmp_path / "bank"))
        COMPUTE_CONFIGS.update({"enable_async_compile": True})
        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "consensus.db")
        )
        port = _free_port()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever, args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        assert ready.wait(10)
        coord = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        coord.add_replica("r0", ("127.0.0.1", port))
        try:
            coord.execute(
                "CREATE TABLE swt (k BIGINT NOT NULL, "
                "v BIGINT NOT NULL)"
            )
            coord.execute(
                "INSERT INTO swt VALUES (1, 10), (2, 20)"
            )
            coord.execute(
                "CREATE MATERIALIZED VIEW swmv AS "
                "SELECT k, sum(v) FROM swt GROUP BY k"
            )
            # Correct BEFORE the swap lands (the generic merge-mode
            # program is serving).
            assert sorted(
                coord.execute("SELECT * FROM swmv").rows
            ) == [(1, 10), (2, 20)]

            def swap_state():
                per = coord.controller.swap_states.get("swmv", {})
                return per.get("r0", {}).get("state") in (
                    "swapped", "swap-failed"
                ) and per.get("r0", {}).get("state")

            state = _poll(swap_state)
            assert state == "swapped", (
                coord.controller.swap_states.get("swmv")
            )
            # Correct AFTER the swap: new writes flow through the
            # specialized program.
            coord.execute("INSERT INTO swt VALUES (1, 5), (3, 7)")
            assert sorted(
                coord.execute("SELECT * FROM swmv").rows
            ) == [(1, 15), (2, 20), (3, 7)]
            res = coord.execute(
                "SELECT dataflow, state FROM mz_program_bank "
                "WHERE kind = 'swap'"
            )
            assert ("swmv", "swapped") in res.rows
        finally:
            coord.shutdown()
            COMPUTE_CONFIGS.update({"enable_async_compile": None})
