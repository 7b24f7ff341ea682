"""Test configuration: force an 8-virtual-device CPU platform BEFORE the jax
backend initializes, so multi-chip sharding paths are exercised without TPU
hardware (the analog of the reference's multi-process tests without a real
cluster: clusterd-test-driver / mzcompose)."""

import os

from materialize_tpu.parallel.compat import force_host_devices

force_host_devices()

# The suite is dominated by XLA:CPU compiles of tiny per-tier programs
# whose run time is nothing: compile them at the backend's lowest
# optimization level (about a quarter off the SLT corpus's wall time,
# PR 24; the suite sits at its time limit). Results are unchanged —
# the data plane is integer/decimal arithmetic compared exactly. Test-
# spawned subprocess replicas drop XLA_FLAGS and keep the default.
os.environ["XLA_FLAGS"] += (
    " --xla_backend_optimization_level=0"
    " --xla_llvm_disable_expensive_passes=true"
)

# The suite is a CPU suite whatever JAX_PLATFORMS the shell carries: pin
# the platform before any backend exists.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# No persistent-cache threshold for the suite: it is dominated by many
# sub-second CPU compiles of per-capacity-tier dataflow steps that
# recur across tests and workers (the cache itself is configured
# process-wide in materialize_tpu/__init__.py).
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# -- process-exit hygiene ----------------------------------------------------
# Full-suite runs intermittently die AFTER "N passed" with
# `terminate called after throwing an instance of ''` /
# `FATAL: exception not rethrown` — a native (XLA/plugin) thread hitting a
# C++ teardown race in static destructors at interpreter exit. Python-side
# threads are all daemonized and servers close in fixtures; the crash is
# below us. Standard workaround: once pytest has finished reporting,
# hard-exit with the real status so native teardown never runs (the OS
# reclaims everything). atexit is LIFO and this registers after jax's
# import-time hooks, so it runs first and skips them as well.
import atexit  # noqa: E402
import sys  # noqa: E402

_exit_status: dict = {"code": None}


def pytest_sessionfinish(session, exitstatus):
    _exit_status["code"] = int(exitstatus)


def _hard_exit():
    if _exit_status["code"] is not None:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(_exit_status["code"])


atexit.register(_hard_exit)


# -- optimizer typecheck safety net ------------------------------------------
# The MIR typechecker (materialize_tpu/analysis/typecheck.py) runs between
# every optimizer transform for the whole suite, so a transform that
# corrupts schemas or binding discipline fails loudly AT that transform
# (transform/src/typecheck.rs discipline) instead of surfacing as a wrong
# SLT result three layers later. Production default is off (dyncfg
# optimizer_typecheck); tests pay the small planning overhead gladly.
from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS  # noqa: E402

COMPUTE_CONFIGS.update({"optimizer_typecheck": True})


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "analysis: static-analysis lane (typechecker, monotonicity, "
        "jaxpr linter, donation prover/sanitizer) — run fast with "
        "`pytest -m analysis`",
    )
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 lane (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "chaos: crash-consistency / fault-injection lane (ISSUE 10) — "
        "seeded deterministic faults, exact oracles; run with "
        "`pytest -m chaos` (full storms are additionally marked slow)",
    )
    # The use-after-donate sanitizer is DEFAULT ON in the analysis
    # lane (ISSUE 8): donated dispatches record their killed carry
    # leaves and every guarded read site validates against the ledger.
    # The full suite keeps the production default (off) — individual
    # donation tests flip it explicitly. Matches the `analysis` marker
    # being SELECTED (compound expressions like
    # `-m "analysis and not slow"` included), not an exact string.
    import re

    markexpr = (getattr(config.option, "markexpr", "") or "").strip()
    if re.search(r"(?<!not )\banalysis\b", markexpr):
        COMPUTE_CONFIGS.update({"buffer_sanitizer": True})
        # The happens-before race detector rides the same lane (ISSUE
        # 17): declared shared state across the whole suite is checked
        # for unsynchronized access pairs; tests read
        # racecheck.findings() to assert clean (or reproduce a fixed
        # race). Production default off — one None check per access.
        COMPUTE_CONFIGS.update({"race_detector": True})
        from materialize_tpu.analysis import racecheck
        from materialize_tpu.utils import lockcheck

        lockcheck.enable()
        racecheck.maybe_enable_from_dyncfg(reset=True)


# -- replica-worker leak control ---------------------------------------------
# Many tests spawn in-process ReplicaWorkers via serve_forever threads and
# never stop them; a leaked replica keeps STEPPING its installed dataflows
# for the remainder of the suite. The accumulation starves later tests
# (observed: the suite slowing from ~12 to ~35 minutes) and has triggered
# segfaults in concurrent XLA compile-cache loads. Track every worker
# created during a test and stop it at teardown.
import pytest  # noqa: E402


# -- the forced-multi-device analysis lane (ISSUE 9) -------------------------
# The shard-spec prover tests (`pytest -m analysis`) run against a real
# 8-worker mesh on the forced CPU platform above. The fixture skips
# where the platform could not actually be forced to 8 devices (an
# operator's own xla_force_host_platform_device_count wins).


@pytest.fixture
def eight_worker_mesh():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip(
            f"need 8 forced devices, have {len(jax.devices())}"
        )
    from materialize_tpu.parallel.mesh import make_mesh

    return make_mesh(8)


@pytest.fixture(autouse=True)
def _stop_leaked_replica_workers(monkeypatch):
    from materialize_tpu.coord import replica as _replica_mod

    created: list = []
    orig_init = _replica_mod.ReplicaWorker.__init__

    def tracking_init(self, *a, **k):
        orig_init(self, *a, **k)
        created.append(self)

    monkeypatch.setattr(
        _replica_mod.ReplicaWorker, "__init__", tracking_init
    )
    yield
    for w in created:
        try:
            w.stop()
        except Exception:
            pass
