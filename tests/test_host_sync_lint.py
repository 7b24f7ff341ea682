"""Host-sync hazard linter (ISSUE 7 satellite): the per-span hot path
must be statically free of accidental device→host sync points, and the
index / q1 step programs must carry no host callbacks — the pipelined
control plane's one-readback-per-span invariant, enforced before any
hardware run."""

import os
import textwrap

import pytest

pytestmark = pytest.mark.analysis


def test_hot_path_has_zero_findings():
    """The registered per-span hot-path functions (dispatch, the
    sinked span's prefetch, pipelined commit bookkeeping) lint clean.
    ``scripts/check_plans.py --bench`` runs the same lint when someone
    runs it; tier-1 runs it here."""
    from materialize_tpu.analysis import lint_hot_path

    findings = lint_hot_path()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_index_and_q1_step_programs_clean():
    """The acceptance gate: zero host-sync findings on the index and
    q1 step programs (jaxpr half of the rule — a host callback inside
    the step is a per-step d2h round trip)."""
    from materialize_tpu.analysis import host_sync_findings_dataflow
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.storage.generator.tpch import LINEITEM_SCHEMA
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.tpch import q1_mir

    index = Dataflow(
        mir.Get("lineitem", LINEITEM_SCHEMA), name="index",
        out_levels=4, out_slots=4,
    )
    assert host_sync_findings_dataflow(index) == []
    q1 = Dataflow(optimize(q1_mir()), name="q1")
    assert host_sync_findings_dataflow(q1) == []


_BAD_FIXTURE = textwrap.dedent(
    """
    import numpy as np
    import jax

    def bad_hot_fn(x):
        h = np.asarray(x)
        n = x.count.item()
        jax.block_until_ready(x)
        y = jax.device_put(h)
        return n

    def sanctioned_fn(x):
        import jax
        ok = np.asarray(x)  # host-sync: ok(test boundary)
        up = jax.device_put(x)  # h2d: staging upload
        return ok, up
    """
)


def test_seeded_hazards_are_flagged(tmp_path):
    """Each hazard class fires exactly once on a seeded-bad function;
    the pragmas sanction intentional boundaries."""
    import importlib.util

    p = tmp_path / "hs_fixture.py"
    p.write_text(_BAD_FIXTURE)
    spec = importlib.util.spec_from_file_location("hs_fixture", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    from materialize_tpu.analysis import HOST_SYNC, lint_function

    bad = lint_function(mod.bad_hot_fn)
    assert len(bad) == 4
    assert all(f.lint_id == HOST_SYNC for f in bad)
    msgs = "\n".join(f.message for f in bad)
    assert "np.asarray" in msgs
    assert ".item()" in msgs
    assert "block_until_ready" in msgs
    assert "device_put" in msgs
    assert lint_function(mod.sanctioned_fn) == []


def test_check_plans_bench_gates_host_sync():
    """``scripts/check_plans.py --bench`` includes the host-sync gate:
    a source-level check that the wiring exists. Nothing in tier-1
    runs that script (it traces TPCH programs and drives storms); it
    is run by hand (ROADMAP C18)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "check_plans.py",
    )
    with open(path) as f:
        src = f.read()
    assert "lint_hot_path" in src
    assert "host-sync-hot-path" in src
