#!/usr/bin/env python
"""Static plan checking over the SLT corpus + jaxpr-lint of the bench
dataflows.

Two modes:

  python scripts/check_plans.py [slt files...]
      Parse every statement in tests/slt/*.slt (default) or the given
      files, maintain a planning catalog, and for every planned
      relation expression run the full static pipeline:
      parse -> plan -> typecheck(raw) -> optimize (with the
      per-transform typechecker on) -> typecheck_lir -> monotonicity.
      Exit non-zero on any violation, naming file:line and the failing
      stage. No dataflow is rendered and nothing compiles — this is
      the fast CI lane for "every plan the corpus can produce survives
      the analysis subsystem".

  python scripts/check_plans.py --bench
      Render the standard bench dataflows (TPCH Q1/Q15, the
      BASELINE.json gate configs that run on every accelerator) and
      walk their step programs' jaxprs with the TPU-hazard linter
      (analysis/jaxpr_lint.py). Exit non-zero on any finding.

Both modes are pure host work and run on CPU (`JAX_PLATFORMS=cpu`).
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Both modes are host work: pin this process AND the gate children it
# starts to the CPU, whatever was inherited, so a run on a machine with
# a chip never has two processes wanting it.
os.environ["JAX_PLATFORMS"] = "cpu"
# The sharding gates (--bench, ISSUE 9) render the bench configs SPMD
# over an 8-virtual-device CPU mesh; force the device count before the
# jax backend initializes.
from materialize_tpu.parallel.compat import force_host_devices  # noqa: E402

force_host_devices()


def _iter_plan_exprs(plan):
    """(kind, expr) pairs carried by one statement Plan."""
    from materialize_tpu.sql.plan import (
        CreateViewPlan,
        DeletePlan,
        SelectPlan,
        SubscribePlan,
        UpdatePlan,
    )

    if isinstance(plan, SelectPlan):
        yield "select", plan.expr
    elif isinstance(plan, CreateViewPlan):
        yield "view", plan.expr
    elif isinstance(plan, SubscribePlan):
        yield "subscribe", plan.expr
    elif isinstance(plan, DeletePlan):
        yield "delete", plan.expr
    elif isinstance(plan, UpdatePlan):
        for name in ("expr", "selection", "read"):
            e = getattr(plan, name, None)
            if e is not None:
                yield "update", e
                break


def _apply_catalog(plan, catalog) -> None:
    """Mirror the coordinator's catalog bookkeeping for the statement
    kinds the SLT corpus uses (tables, views, indexes, drops)."""
    from materialize_tpu.sql.catalog import CatalogItem
    from materialize_tpu.sql.plan import (
        CreateIndexPlan,
        CreateTablePlan,
        CreateViewPlan,
        DropPlan,
    )

    if isinstance(plan, CreateTablePlan):
        catalog.create(
            CatalogItem(plan.name, "table", plan.schema),
            or_replace=True,
        )
    elif isinstance(plan, CreateViewPlan):
        schema = plan.expr.schema()
        if plan.column_names and len(plan.column_names) == schema.arity:
            schema = schema.rename(plan.column_names)
        catalog.create(
            CatalogItem(
                plan.name,
                "materialized-view" if plan.materialized else "view",
                schema,
                definition=plan.expr,
                column_names=plan.column_names,
            ),
            or_replace=True,
        )
    elif isinstance(plan, DropPlan):
        catalog.drop(plan.name, if_exists=True)
    elif isinstance(plan, CreateIndexPlan):
        pass  # indexes add no schema


def check_slt_file(path: str, verbose: bool = False) -> list[str]:
    """Run the static pipeline over one SLT file; returns violation
    descriptions (empty = clean)."""
    from materialize_tpu.analysis import analyze, typecheck, typecheck_lir
    from materialize_tpu.sql.catalog import Catalog
    from materialize_tpu.sql.hir import PlanError
    from materialize_tpu.sql.parser import ParseError
    from materialize_tpu.sql.plan import plan_statement
    from materialize_tpu.testing.slt import parse_slt
    from materialize_tpu.transform.optimizer import optimize

    with open(path) as f:
        records = parse_slt(f.read())

    catalog = Catalog()
    violations: list[str] = []
    n_checked = 0
    for rec in records:
        if rec.kind == "statement_error":
            continue  # meant to fail; nothing to check
        where = f"{path}:{rec.line}"
        try:
            plan = plan_statement(rec.sql, catalog)
        except (PlanError, ParseError):
            # The live harness (tests/test_slt.py) is the authority on
            # whether statements execute; here only plannable relation
            # expressions are in scope.
            continue
        for kind, expr in _iter_plan_exprs(plan):
            n_checked += 1
            stage = "typecheck(raw)"
            try:
                typecheck(expr)
                stage = "optimize+typecheck"
                opt = optimize(expr)
                stage = "typecheck(optimized)"
                typecheck(opt)
                stage = "typecheck_lir"
                typecheck_lir(opt)
                stage = "monotonicity"
                analyze(opt)
            except Exception as e:  # noqa: BLE001 — report, don't die
                violations.append(
                    f"{where} [{kind}] failed at {stage}: {e}\n"
                    f"    {rec.sql.strip().splitlines()[0]}"
                )
        _apply_catalog(plan, catalog)
    if verbose:
        print(
            f"  {os.path.basename(path)}: {n_checked} plan(s) checked,"
            f" {len(violations)} violation(s)"
        )
    return violations


def run_slt_mode(paths: list[str], verbose: bool) -> int:
    from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

    # Per-transform blame attribution for the whole sweep.
    COMPUTE_CONFIGS.update({"optimizer_typecheck": True})
    all_violations: list[str] = []
    for path in paths:
        all_violations.extend(check_slt_file(path, verbose))
    if all_violations:
        print(f"{len(all_violations)} violation(s):")
        for v in all_violations:
            print(f"  {v}")
        return 1
    print(f"OK: {len(paths)} SLT file(s) clean")
    return 0


BUDGET_PATH = os.path.join(REPO, "tests", "kernel_budget.json")


def bench_dataflows() -> dict:
    """name -> Dataflow factory for the budget-gated bench configs —
    pure renders, no generators (CI must not pay TPCH data
    generation). The index entry has a deep output spine (4-level
    ladder + 4-slot append ring); op
    census is capacity-independent, so the init-tier capacities are
    fine."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.storage.generator.tpch import LINEITEM_SCHEMA
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.tpch import q1_mir, q15_mir

    return {
        "index": lambda: Dataflow(
            mir.Get("lineitem", LINEITEM_SCHEMA), name="index",
            out_levels=4, out_slots=4,
        ),
        "q1": lambda: Dataflow(optimize(q1_mir()), name="q1"),
        "q15": lambda: Dataflow(optimize(q15_mir()), name="q15"),
    }


def run_bench_mode(verbose: bool) -> int:
    """Jaxpr-lint the standard bench dataflows AND gate their step
    programs' op census against the checked-in kernel budgets
    (tests/kernel_budget.json) — a launch-count regression fails CI
    statically, before any hardware run (abstract tracing only;
    nothing compiles)."""
    import json

    from materialize_tpu.analysis import (
        kernel_count,
        lint_jaxpr,
        trace_dataflow_step,
    )
    from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

    COMPUTE_CONFIGS.update({"optimizer_typecheck": True})
    budgets = {}
    if os.path.exists(BUDGET_PATH):
        with open(BUDGET_PATH) as f:
            budgets = json.load(f)
    rc = 0
    from materialize_tpu.analysis.jaxpr_lint import _carry_finding

    def gate(name: str, closed, findings, n_ops) -> None:
        nonlocal rc
        budget = budgets.get(name)
        over = (
            budget is not None
            and n_ops is not None
            and n_ops > budget
        )
        if findings or over:
            rc = 1
            ops_desc = (
                f"{n_ops} ops"
                if n_ops is not None
                else "trace failed, census unavailable"
            )
            print(
                f"{name}: {len(findings)} finding(s), "
                f"{ops_desc} (budget {budget})"
            )
            for f in findings:
                print(f"  {f}")
            if over:
                print(
                    f"  [kernel-budget] {name} program has {n_ops} "
                    f"ops, budget is {budget} "
                    "(tests/kernel_budget.json): a change re-grew the "
                    "launch count. Either fuse the regression away or "
                    "consciously raise the budget in the same PR."
                )
        else:
            print(
                f"{name}: clean, {n_ops} ops"
                + (f" (budget {budget})" if budget is not None else "")
            )

    for name, mk in bench_dataflows().items():
        df = mk()
        # One abstract trace feeds both the linter and the census
        # (tracing a TPCH step program costs seconds per config). A
        # trace-time carry mismatch must still surface as the curated
        # CARRY_VARY finding, not a crash that skips later configs.
        try:
            closed = trace_dataflow_step(df)
        except TypeError as e:
            findings = _carry_finding(e)
            if findings is None:
                raise
            closed, n_ops = None, None
        else:
            findings = lint_jaxpr(closed)
            n_ops = kernel_count(closed)
        gate(name, closed, findings, n_ops)
        if name == "index":
            # The serving plane (round 7, ISSUE 6): the batched-gather
            # peek programs are budgeted exactly like the step program
            # — a launch-count regression in the read path fails CI
            # statically too.
            from materialize_tpu.coord.peek import trace_peek_programs

            for pname, pclosed in trace_peek_programs(df).items():
                gate(
                    pname,
                    pclosed,
                    lint_jaxpr(pclosed),
                    kernel_count(pclosed),
                )
    # The pipelined control plane's host-sync gate (ISSUE 7): an
    # accidental d2h sync point (np.asarray / .item() /
    # block_until_ready / un-donated device_put) on the per-span hot
    # path fails statically — it would serialize the span pipeline
    # and reintroduce the per-span RTT tax.
    from materialize_tpu.analysis import lint_hot_path

    hs = lint_hot_path()
    gate("host-sync-hot-path", None, hs, 0)
    rc |= run_donation_gates(gate)
    rc |= run_sharding_gates(gate, budgets)
    rc |= run_lockcheck_smoke(gate)
    rc |= run_chaos_smoke(gate)
    rc |= run_failover_smoke_gate(gate)
    rc |= run_compactor_smoke_gate(gate)
    rc |= run_subscribe_smoke(gate, budgets)
    rc |= run_mz_relations_gate(gate)
    rc |= run_bank_roundtrip_gate(gate)
    rc |= run_tier_quantization_gate(gate)
    rc |= run_race_free_gate(gate)
    rc |= run_interleave_smoke_gate(gate)
    return rc


# One deterministic churn workload, shared by the program-bank gates:
# duplicate/retraction churn over a bare-Get index, net rows compared
# across processes (the same content-equivalence discipline as
# tests/oracle.net_rows).
_BANK_GATE_SCRIPT = r"""
import json, sys
import numpy as np
from materialize_tpu.compile.bank import configure_bank, get_bank
from materialize_tpu.expr import relation as mir
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema
from materialize_tpu.utils.compile_ledger import LEDGER

configure_bank(sys.argv[1])
sch = Schema(
    (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
)
df = Dataflow(mir.Get("src", sch), name="bank-smoke")
rng = np.random.default_rng(7)
t0 = df.time
for i in range(6):
    n = 32
    k = rng.integers(0, 64, n).astype(np.int64)
    v = rng.integers(0, 8, n).astype(np.int64)
    d = rng.choice(np.asarray([1, 1, -1]), n).astype(np.int64)
    df.run_steps([{"src": Batch.from_numpy(
        sch, [k, v], np.uint64(t0 + i), d, capacity=64
    )}])
df._compact_now()
assert not df.check_flags(), "overflow in bank gate workload"
from collections import defaultdict
acc = defaultdict(int)
for r in df.peek():
    acc[tuple(int(c) for c in r[:-2])] += int(r[-1])
rows = sorted([*k, n] for k, n in acc.items() if n != 0)
s = LEDGER.summary()
print(json.dumps({
    "rows": rows,
    "bank_hits": s["bank_hits"],
    "bank_misses": s["bank_misses"],
    "fresh_compiles": s["misses"],
    "caches": sorted({r.cache for r in LEDGER.records()}),
    "bank": get_bank().snapshot(),
}))
"""


def _run_gate_child(script: str, bank_dir: str, xla_cache_dir: str):
    """Run a program-bank gate script in a fresh interpreter and parse
    the JSON object on its last stdout line."""
    import json
    import subprocess
    import sys

    # A cold, gate-private XLA persistent cache: executables
    # rehydrated from a warm host cache cannot be re-serialized (the
    # payload fails deserialization), so a warm host cache would make
    # the cold run's stores fail verification and the gate flake.
    # JAX reads the variable itself; the package then sets no cache
    # directory in code.
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = xla_cache_dir
    out = subprocess.run(
        [sys.executable, "-c", script, bank_dir],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if out.returncode != 0:
        tail = out.stderr.strip().splitlines()
        raise RuntimeError(
            f"bank gate subprocess rc={out.returncode}: "
            + (tail[-1] if tail else "no stderr")
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_bank_roundtrip_gate(gate) -> int:
    """Program-bank round-trip gate (ISSUE 16 satellite): run the
    same deterministic churn workload in TWO fresh subprocesses
    sharing one bank directory. The first (cold) run compiles and
    exports every program; the second runs with EVERY in-process
    cache gone (new interpreter) and must (a) produce byte-identical
    net rows, (b) record bank_hit serves, and (c) pay ZERO fresh
    XLA compiles — the restart-proof invariant, checked in CI on CPU
    before any hardware run."""
    import shutil
    import tempfile

    from materialize_tpu.analysis import LintFinding

    findings = []
    bank_dir = tempfile.mkdtemp(prefix="bank-gate-")
    xla_cache = tempfile.mkdtemp(prefix="bank-gate-xla-")
    try:
        cold = _run_gate_child(_BANK_GATE_SCRIPT, bank_dir, xla_cache)
        warm = _run_gate_child(_BANK_GATE_SCRIPT, bank_dir, xla_cache)
        if cold["bank"]["stores"] == 0:
            findings.append(LintFinding(
                "bank-roundtrip", "export",
                "cold run stored no bank entries: ledger_jit sites "
                "no longer write back to the program bank",
            ))
        if warm["rows"] != cold["rows"]:
            findings.append(LintFinding(
                "bank-roundtrip", "equivalence",
                "bank-served run produced different net rows than "
                f"the fresh-compile run: {warm['rows'][:5]!r} vs "
                f"{cold['rows'][:5]!r}",
            ))
        if warm["bank_hits"] == 0 or "bank_hit" not in warm["caches"]:
            findings.append(LintFinding(
                "bank-roundtrip", "reimport",
                "warm run recorded no bank_hit: the bank lookup path "
                f"never served (caches={warm['caches']!r})",
            ))
        if warm["fresh_compiles"] != 0:
            findings.append(LintFinding(
                "bank-roundtrip", "compile-wall",
                f"warm run still paid {warm['fresh_compiles']} fresh "
                "XLA compile(s) with every fingerprint banked — the "
                "restart proof requires ZERO",
            ))
    except OSError as e:
        print(f"bank-roundtrip: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings = [LintFinding(
            "bank-roundtrip", "driver",
            f"bank roundtrip gate failed to run: {e!r}",
        )]
    finally:
        shutil.rmtree(bank_dir, ignore_errors=True)
        shutil.rmtree(xla_cache, ignore_errors=True)
    gate("bank-roundtrip", None, findings, 0)
    return 1 if findings else 0


# Two rung-mate DDLs (state_cap 300 vs 400, both snapping to 512)
# through one bank, in a fresh interpreter (see _run_gate_child).
_QUANT_GATE_SCRIPT = r"""
import json, sys
from collections import defaultdict
import numpy as np
from materialize_tpu.compile.bank import configure_bank, get_bank
from materialize_tpu.expr import relation as mir
from materialize_tpu.plan.decisions import quantize_cap
from materialize_tpu.render.dataflow import Dataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema

configure_bank(sys.argv[1])
sch = Schema(
    (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
)


def run_once(cap):
    rng = np.random.default_rng(11)
    df = Dataflow(mir.Get("src", sch), name=f"quant-{cap}", state_cap=cap)
    t0 = df.time
    for i in range(3):
        n = 16
        k = rng.integers(0, 32, n).astype(np.int64)
        v = rng.integers(0, 8, n).astype(np.int64)
        d = rng.choice(np.asarray([1, 1, -1]), n).astype(np.int64)
        df.run_steps([{"src": Batch.from_numpy(
            sch, [k, v], np.uint64(t0 + i), d, capacity=64
        )}])
    acc = defaultdict(int)
    for r in df.peek():
        acc[tuple(int(c) for c in r[:-2])] += int(r[-1])
    return sorted([*key, n] for key, n in acc.items() if n != 0)


rows_a = run_once(300)
entries_after_a = get_bank().snapshot()["entries"]
hits_before = get_bank().stats["hits"]
rows_b = run_once(400)
snap = get_bank().snapshot()
print(json.dumps({
    "rungs": [quantize_cap(300), quantize_cap(400)],
    "rows_a": rows_a,
    "rows_b": rows_b,
    "entries_after_a": entries_after_a,
    "entries": snap["entries"],
    "hits_before": hits_before,
    "hits": snap["hits"],
}))
"""


def run_tier_quantization_gate(gate) -> int:
    """Tier-quantization gate (ISSUE 16 satellite): two DDLs whose
    requested capacities differ only WITHIN one pow2 rung (state_cap
    300 vs 400, both snapping to 512) must share every bank key — the
    second dataflow adds ZERO new bank entries and serves its step
    programs as bank hits. A capacity leaking un-quantized into tier
    vectors (or a menu regression) fails here."""
    import shutil
    import tempfile

    from materialize_tpu.analysis import LintFinding

    findings = []
    bank_dir = tempfile.mkdtemp(prefix="quant-gate-")
    xla_cache = tempfile.mkdtemp(prefix="quant-gate-xla-")
    try:
        r = _run_gate_child(_QUANT_GATE_SCRIPT, bank_dir, xla_cache)
        if r["rungs"][0] != r["rungs"][1]:
            findings.append(LintFinding(
                "tier-quantization", "menu",
                f"300 and 400 landed on different rungs "
                f"({r['rungs'][0]} vs {r['rungs'][1]}): the "
                "pow2 menu no longer coalesces size-only DDL "
                "differences",
            ))
        if r["rows_a"] != r["rows_b"]:
            findings.append(LintFinding(
                "tier-quantization", "equivalence",
                "same churn through the two rung-mates produced "
                "different net rows",
            ))
        if r["entries"] != r["entries_after_a"]:
            findings.append(LintFinding(
                "tier-quantization", "key-sharing",
                f"the second DDL grew the bank from "
                f"{r['entries_after_a']} to {r['entries']} entries: "
                "capacities within one pow2 rung no longer share "
                "bank keys",
            ))
        if r["hits"] == r["hits_before"]:
            findings.append(LintFinding(
                "tier-quantization", "reuse",
                "the second DDL served no bank hits despite "
                "rung-identical capacities",
            ))
    except OSError as e:
        print(f"tier-quantization: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings = [LintFinding(
            "tier-quantization", "driver",
            f"tier quantization gate failed to run: {e!r}",
        )]
    finally:
        shutil.rmtree(bank_dir, ignore_errors=True)
        shutil.rmtree(xla_cache, ignore_errors=True)
    gate("tier-quantization", None, findings, 0)
    return 1 if findings else 0


def run_mz_relations_gate(gate) -> int:
    """Introspection coverage gate (ISSUE 12 satellite): EVERY
    registered introspection relation must serve `SELECT * FROM
    <rel>` without error against a live coordinator+replica — a
    schema/snapshot drift (column count mismatch, a snapshot reading
    a renamed field) fails here instead of in production dashboards."""
    import tempfile
    import threading

    from materialize_tpu.analysis import LintFinding
    from materialize_tpu.coord.coordinator import Coordinator
    from materialize_tpu.coord.introspection import (
        INTROSPECTION_SCHEMAS,
    )
    from materialize_tpu.coord.protocol import PersistLocation
    from materialize_tpu.coord.replica import serve_forever
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )

    import shutil

    findings = []
    coord = None
    tmp = None
    try:
        tmp = tempfile.mkdtemp(prefix="mzrel-gate-")
        loc = PersistLocation(
            os.path.join(tmp, "blob"), os.path.join(tmp, "c.db")
        )
        from materialize_tpu.testing.chaos import _free_port

        port = _free_port()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever, args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        ready.wait(10)
        coord = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        coord.add_replica("r0", ("127.0.0.1", port))
        # Populate: a table + MV + index + a statement, so relations
        # with rows actually exercise their row constructors.
        coord.execute("CREATE TABLE mzrel_t (a INT, b INT)")
        coord.execute("INSERT INTO mzrel_t VALUES (1, 2)")
        coord.execute(
            "CREATE MATERIALIZED VIEW mzrel_mv AS "
            "SELECT a, b FROM mzrel_t"
        )
        coord.execute("SELECT * FROM mzrel_mv")
        # Freshness-plane coverage (ISSUE 15): these relations are the
        # data-plane health surface — dropping one from the registry
        # must fail the gate, not silently shrink the loop below.
        required = {
            "mz_wallclock_lag_history",
            "mz_hydration_statuses",
            "mz_source_statuses",
            "mz_sink_statuses",
            # Elastic-serving plane (ISSUE 19): replica lifecycle and
            # the autoscaler's decision ledger are operator-facing
            # surfaces — dropping either breaks the scale-out
            # dashboards the same way a freshness relation would.
            "mz_cluster_replicas",
            "mz_autoscale_events",
        }
        for rel in sorted(required - set(INTROSPECTION_SCHEMAS)):
            findings.append(
                LintFinding(
                    "mz-relations", rel,
                    "required introspection relation is not "
                    "registered in INTROSPECTION_SCHEMAS",
                )
            )
        for rel, schema in sorted(INTROSPECTION_SCHEMAS.items()):
            try:
                res = coord.execute(f"SELECT * FROM {rel}")
                if len(res.columns) != schema.arity:
                    findings.append(
                        LintFinding(
                            "mz-relations", rel,
                            f"served {len(res.columns)} columns, "
                            f"schema declares {schema.arity}",
                        )
                    )
            except Exception as e:
                findings.append(
                    LintFinding(
                        "mz-relations", rel,
                        f"SELECT * FROM {rel} failed: {e!r}",
                    )
                )
    except OSError as e:
        print(f"mz-relations: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings.append(
            LintFinding(
                "mz-relations", "driver",
                f"mz-relations gate failed to run: {e!r}",
            )
        )
    finally:
        if coord is not None:
            coord.shutdown()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    gate("mz-relations", None, findings, 0)
    return 1 if findings else 0


def run_subscribe_smoke(gate, budgets: dict) -> int:
    """Push-plane smoke gate (ISSUE 11 satellite): a small hub run —
    >= 8 concurrent same-query SUBSCRIBE sessions over one table
    under churn — asserting the two structural invariants:

      * readbacks-per-span == 1.0 (each committed span window is
        fetched from the sink shard ONCE for ALL sessions; a
        per-session tail regression makes this N);
      * exactly ONE dataflow install shared by every session;

    plus the zero-device-programs fact: the fan-out hub is pure host
    code, so tests/kernel_budget.json must carry NO subscribe-plane
    program budgets (a key appearing there means someone put device
    work on the push path — that is a cost-model change this gate
    makes deliberate, not accidental)."""
    import shutil
    import tempfile
    import threading

    from materialize_tpu.analysis import LintFinding

    findings = []
    stray = [
        k for k in budgets
        if k.startswith("subscribe") or k.startswith("sub_")
    ]
    if stray:
        findings.append(
            LintFinding(
                "subscribe-smoke", "kernel-budget",
                f"kernel_budget.json has subscribe-plane entries "
                f"{stray}: the push plane is host-side by design "
                "(one shard readback per span, zero device "
                "programs); adding device work to it changes the "
                "cost model in doc/perf.md",
            )
        )
    storm_dir = tempfile.mkdtemp(prefix="subscribe-gate-")
    try:
        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )

        loc = PersistLocation(
            os.path.join(storm_dir, "blob"),
            os.path.join(storm_dir, "consensus.db"),
        )
        from materialize_tpu.testing.chaos import _free_port

        port = _free_port()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever, args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        ready.wait(10)
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
                "CREATE TABLE skv (k BIGINT NOT NULL, "
                "v BIGINT NOT NULL)"
            )
            coord.execute("INSERT INTO skv VALUES (0, 0)")
            sql = "SUBSCRIBE TO (SELECT k, v FROM skv WHERE k >= 0)"
            subs = [
                coord.execute(sql).subscription for _ in range(8)
            ]
            for i in range(4):
                coord.execute(
                    f"INSERT INTO skv VALUES ({i + 1}, {i})"
                )
            final = coord._table_writers["skv"].upper
            import time as _t

            deadline = _t.monotonic() + 120.0
            while any(s.frontier < final for s in subs):
                if _t.monotonic() > deadline:
                    findings.append(
                        LintFinding(
                            "subscribe-smoke", "delivery",
                            "sessions never reached the final "
                            f"frontier {final}: "
                            f"{[s.frontier for s in subs]}",
                        )
                    )
                    break
                for s in subs:
                    s.pop_ready()
                _t.sleep(0.01)
            snap = coord.subscribe_hub.snapshot()
            if snap["installs"] != 1:
                findings.append(
                    LintFinding(
                        "subscribe-smoke", "sharing",
                        f"{snap['installs']} dataflow installs for 8 "
                        "same-query sessions (expected exactly 1: "
                        "the hub's expr-fingerprint sharing broke)",
                    )
                )
            if (
                not snap["spans"]
                or snap["readbacks"] != snap["spans"]
            ):
                findings.append(
                    LintFinding(
                        "subscribe-smoke", "invariant",
                        f"readbacks {snap['readbacks']} != spans "
                        f"{snap['spans']} across 8 sessions: the "
                        "one-readback-per-span invariant broke "
                        "(per-session tails?)",
                    )
                )
            for s in subs:
                s.close()
        finally:
            coord.shutdown()
    except OSError as e:
        print(f"subscribe-smoke: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings.append(
            LintFinding(
                "subscribe-smoke", "driver",
                f"subscribe smoke failed to run: {e!r}",
            )
        )
    finally:
        shutil.rmtree(storm_dir, ignore_errors=True)
    gate("subscribe-smoke", None, findings, 0)
    return 1 if findings else 0


def run_chaos_smoke(gate) -> int:
    """Chaos-lane smoke gate (ISSUE 10 satellite): ONE bounded,
    seeded storm from the chaos harness (testing/chaos.py) — blob
    faults + CTP connection kills + a partition against an in-process
    replica, ~30 ticks — checking the exact-result, zero-lost-ack,
    and zero-rebuild invariants. The full storms (subprocess replica
    SIGKILLs, environmentd kill -9) stay in `pytest -m "chaos and
    slow"`; this gate is the cheap always-on slice of the same
    machinery. Skips cleanly where sockets/threads are unavailable."""
    import shutil
    import tempfile

    from materialize_tpu.analysis import LintFinding
    from materialize_tpu.testing.chaos import run_chaos

    storm_dir = tempfile.mkdtemp(prefix="chaos-gate-")
    try:
        rep = run_chaos(
            storm_dir,
            seed=1,
            ticks=25,
            blob_fail_every=11,
            proxy_kill_every=30,
        )
        findings = [
            LintFinding("chaos-smoke", "invariant", f)
            for f in rep.failures
        ]
    except OSError as e:
        print(f"chaos-smoke: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings = [
            LintFinding(
                "chaos-smoke", "driver",
                f"chaos smoke failed to run: {e!r}",
            )
        ]
    finally:
        shutil.rmtree(storm_dir, ignore_errors=True)
    gate("chaos-smoke", None, findings, 0)
    return 1 if findings else 0


def run_failover_smoke_gate(gate) -> int:
    """Elastic-serving smoke gate (ISSUE 19 satellite): one bounded
    seeded failover storm — two in-process replicas, routed reads, a
    pinned in-flight peek, SIGKILL-equivalent stop of the routed-to
    replica mid-span — asserting exact oracle results, at least one
    observed failover, and that the post-storm routing target is a
    survivor. The N=3 subprocess storm stays in `pytest -m "chaos and
    slow"`; this is the always-on slice of the same machinery."""
    import shutil
    import tempfile

    from materialize_tpu.analysis import LintFinding
    from materialize_tpu.testing.chaos import run_failover_smoke

    storm_dir = tempfile.mkdtemp(prefix="failover-gate-")
    try:
        rep = run_failover_smoke(storm_dir, seed=3)
        findings = [
            LintFinding("failover-smoke", "invariant", f)
            for f in rep.failures
        ]
        if not rep.failures:
            if rep.kills != 1:
                findings.append(
                    LintFinding(
                        "failover-smoke", "invariant",
                        f"expected exactly one mid-peek kill, saw "
                        f"{rep.kills} — the storm no longer exercises "
                        "the failover path it exists to gate",
                    )
                )
            if rep.failovers < 1:
                findings.append(
                    LintFinding(
                        "failover-smoke", "invariant",
                        "routed-to replica was killed mid-peek but "
                        "the controller recorded zero failovers",
                    )
                )
    except OSError as e:
        print(f"failover-smoke: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings = [
            LintFinding(
                "failover-smoke", "driver",
                f"failover smoke failed to run: {e!r}",
            )
        ]
    finally:
        shutil.rmtree(storm_dir, ignore_errors=True)
    gate("failover-smoke", None, findings, 0)
    return 1 if findings else 0


def run_compactor_smoke_gate(gate) -> int:
    """Off-path compaction smoke gate (ISSUE 20): one bounded churn
    storm under UnreliableBlob with the production tick path
    (auto_compaction, compaction_mode=background) plus the full lease
    choreography — compactor crashed after its merge blob-write,
    lease-expiry handoff to a second compactor, stale-epoch swap
    fence, reader racing a just-swapped part. The gate's acceptance
    invariants are COUNTERS, not inspection: zero tick-path merges
    and zero tick-path compaction blob writes, >=1 background merge,
    and a bounded uncompacted-run count — plus exact oracle multisets
    on every read (rep.failures). The long storm stays in
    `pytest -m "chaos and slow"`."""
    import shutil
    import tempfile

    from materialize_tpu.analysis import LintFinding
    from materialize_tpu.testing.chaos import run_compactor_smoke

    storm_dir = tempfile.mkdtemp(prefix="compactor-gate-")
    try:
        rep = run_compactor_smoke(storm_dir, seed=1)
        findings = [
            LintFinding("compactor-smoke", "invariant", f)
            for f in rep.failures
        ]
        if not rep.failures:
            for check, msg in (
                (
                    rep.crashes == 1,
                    f"expected exactly one injected compactor crash, "
                    f"saw {rep.crashes}",
                ),
                (
                    rep.handoffs >= 1,
                    "no lease-expiry handoff to the second compactor",
                ),
                (
                    rep.fenced_swaps >= 1,
                    "stale-epoch swap was never fenced",
                ),
                (
                    rep.reader_races >= 1,
                    "no reader ever raced a just-swapped part",
                ),
            ):
                if not check:
                    findings.append(
                        LintFinding("compactor-smoke", "invariant", msg)
                    )
    except OSError as e:
        print(f"compactor-smoke: skipped (environment: {e!r})")
        return 0
    except Exception as e:
        findings = [
            LintFinding(
                "compactor-smoke", "driver",
                f"compactor smoke failed to run: {e!r}",
            )
        ]
    finally:
        shutil.rmtree(storm_dir, ignore_errors=True)
    gate("compactor-smoke", None, findings, 0)
    return 1 if findings else 0


def sharded_bench_dataflows(mesh) -> dict:
    """name -> ShardedDataflow factory for the SPMD sharding gates:
    the same three budget-gated configs as bench_dataflows, rendered
    over the worker mesh (pure renders + abstract traces, nothing
    compiles)."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import ShardedDataflow
    from materialize_tpu.storage.generator.tpch import LINEITEM_SCHEMA
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.tpch import q1_mir, q15_mir

    return {
        "index": lambda: ShardedDataflow(
            mir.Get("lineitem", LINEITEM_SCHEMA), mesh, name="index",
            out_levels=4, out_slots=4,
        ),
        "q1": lambda: ShardedDataflow(
            optimize(q1_mir()), mesh, name="q1"
        ),
        "q15": lambda: ShardedDataflow(
            optimize(q15_mir()), mesh, name="q15"
        ),
    }


def run_sharding_gates(gate, budgets: dict) -> int:
    """The shard-spec prover gates (ISSUE 9), over the sharded renders
    of index/q1/q15:

    - ``spmd-safety``: every slot-ring cursor must be PROVEN
      shard-local (the verdict that gates append-slot ingest under
      SPMD), and the index config must actually resolve to the slot
      ring — a regression that silently falls back to merge-mode
      O(run0) ingest fails here, statically;
    - ``comm-budget``: the step program's communication census
      (collective count, per-kind counts, per-device byte volume) must
      stay within the checked-in budgets
      (tests/kernel_budget.json ``<config>_comm``). A kind absent from
      the budget allows ZERO sites — a collective sneaking into a
      shard-local stage (the index ingest path budgets nothing but the
      packed-flags psum) is a static CI failure, before any multi-chip
      run."""
    import jax

    from materialize_tpu.analysis import LintFinding

    if len(jax.devices()) < 8:
        print(
            "sharding gates: skipped "
            f"(need 8 devices, have {len(jax.devices())})"
        )
        return 0
    from materialize_tpu.parallel.mesh import make_mesh

    rc = 0
    mesh = make_mesh(8)
    for name, mk in sharded_bench_dataflows(mesh).items():
        sdf = mk()
        rep = sdf.sharding_report()
        sf = []
        if not rep["safe"]:
            blames = "; ".join(
                b
                for cur in rep.get("cursors", ())
                for b in cur.get("blame", ())
            ) or str(rep.get("error"))
            sf.append(
                LintFinding(
                    "spmd-safety",
                    name,
                    "slot-ring cursor not provably shard-local "
                    f"({blames}) — SPMD falls back to O(run0) merge "
                    "ingest",
                )
            )
        if name == "index" and rep["ingest_mode"] != "append_slot":
            sf.append(
                LintFinding(
                    "spmd-safety",
                    name,
                    "index config no longer resolves to prover-gated "
                    "append-slot ingest under SPMD (got "
                    f"{rep['ingest_mode']!r}): multi-chip ingest "
                    "regressed to O(run0) per step",
                )
            )
        gate(f"{name}-spmd-safety", None, sf, 0)
        budget = budgets.get(f"{name}_comm")
        census = rep["census"]
        cf = []
        if budget is not None:
            if census["collectives"] > budget["collectives"]:
                cf.append(
                    LintFinding(
                        "comm-budget",
                        name,
                        f"{census['collectives']} collective site(s), "
                        f"budget {budget['collectives']} "
                        "(tests/kernel_budget.json): a change added "
                        "communication to the step program. Remove it "
                        "or consciously raise the budget in this PR.",
                    )
                )
            if census["bytes"] > budget["bytes"]:
                cf.append(
                    LintFinding(
                        "comm-budget",
                        name,
                        f"{census['bytes']} B per-device collective "
                        f"volume, budget {budget['bytes']} B",
                    )
                )
            allowed = budget.get("kinds", {})
            for kind, n in sorted(census["kinds"].items()):
                if n > allowed.get(kind, 0):
                    cf.append(
                        LintFinding(
                            "comm-budget",
                            name,
                            f"unexpected collective {kind!r} x{n} "
                            f"(budget {allowed.get(kind, 0)}): a "
                            "collective entered a stage budgeted "
                            "shard-local",
                        )
                    )
        gate(f"{name}-comm-budget", None, cf, 0)
        rc |= 1 if (sf or cf) else 0
    return rc


def run_donation_gates(gate) -> int:
    """Buffer-provenance / donation-safety gates (ISSUE 8):

    - every standard bench dataflow, freshly rendered (no
      subscribers), must PROVE fully donatable — zero
      unsound-donation findings is the acceptance gate for the
      replica's donated run_steps span train;
    - the donated step program's lowering must carry
      input_output_aliases on carry parameters only (a signature
      refactor that drifts donate_argnums off the carry fails here,
      statically);
    - the donated-leaf-reuse AST rule: no registered dispatch
      function reads a carry attribute between a dispatch and its
      re-assignment."""
    from materialize_tpu.analysis import (
        UNSOUND_DONATION,
        LintFinding,
        dataflow_verdict,
        donation_lowering_findings,
        lint_donated_reuse,
    )

    rc = 0
    for name, mk in bench_dataflows().items():
        df = mk()
        v = dataflow_verdict(name, df, requested=True)
        vf = list(v.findings)
        if not v.safe:
            vf.append(
                LintFinding(
                    UNSOUND_DONATION,
                    name,
                    "freshly rendered dataflow is not provably "
                    "donatable: " + "; ".join(v.reasons),
                )
            )
        gate(f"{name}-donation", None, vf, 0)
        rc |= 1 if vf else 0
    low = donation_lowering_findings()
    gate("donation-lowering", None, low, 0)
    dr = lint_donated_reuse()
    gate("donated-reuse", None, dr, 0)
    return 1 if (low or dr) else 0


def run_lockcheck_smoke(gate) -> int:
    """Lock-order sanitizer smoke (ISSUE 8 satellite): drive the
    ordinary coordinator/replica serving path — DDL, ingest, fast- and
    slow-path peeks, introspection — with utils/lockcheck recording
    every lock acquisition, and gate on zero findings (no order
    cycles, no device dispatch under the sequencing lock)."""
    import socket
    import tempfile
    import threading
    import time as _t

    from materialize_tpu.analysis import LintFinding
    from materialize_tpu.coord.coordinator import Coordinator
    from materialize_tpu.coord.protocol import PersistLocation
    from materialize_tpu.coord.replica import serve_forever
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )
    from materialize_tpu.utils import lockcheck

    lockcheck.enable()
    coord = None
    try:
        tmp = tempfile.mkdtemp(prefix="lockcheck-smoke-")
        loc = PersistLocation(
            os.path.join(tmp, "blob"), os.path.join(tmp, "c.db")
        )
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever,
            args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        ready.wait(10)
        coord = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        coord.add_replica("r0", ("127.0.0.1", port))
        coord.execute("CREATE TABLE t (a INT, b INT)")
        coord.execute("INSERT INTO t VALUES (1, 2), (3, 4)")
        coord.execute(
            "CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM t"
        )
        coord.execute("CREATE INDEX i ON mv (a)")
        coord.execute("SELECT * FROM mv")
        coord.execute("SELECT * FROM mv WHERE a = 1")
        coord.execute("SELECT * FROM mz_donation")
        _t.sleep(0.2)  # let the replica loop run a few parked passes
    finally:
        if coord is not None:
            coord.shutdown()
        lockcheck.disable()
    findings = [
        LintFinding("lockcheck", f.kind, f.message)
        for f in lockcheck.findings()
    ]
    gate("lockcheck-smoke", None, findings, 0)
    return 1 if findings else 0


def run_race_free_gate(gate) -> int:
    """Happens-before race gate (ISSUE 17): drive the ordinary
    serving path AND the subscribe push plane with the vector-clock
    detector on (dyncfg ``race_detector``, analysis/racecheck.py) and
    gate on ZERO unsuppressed findings over the declared shared-state
    set — the controller maps, the hub session tables, the freshness
    rings, the compile ledger, the dyncfg store. A finding here is an
    access pair with no happens-before edge: a real (if maybe narrow)
    race, reported with both stack chains."""
    import shutil
    import tempfile
    import threading
    import time as _t

    from materialize_tpu.analysis import LintFinding, racecheck
    from materialize_tpu.utils import lockcheck
    from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

    COMPUTE_CONFIGS.update({"race_detector": True})
    lockcheck.enable()
    racecheck.maybe_enable_from_dyncfg(reset=True)
    coord = None
    tmp = tempfile.mkdtemp(prefix="race-free-gate-")
    try:
        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )
        from materialize_tpu.testing.chaos import _free_port

        loc = PersistLocation(
            os.path.join(tmp, "blob"), os.path.join(tmp, "c.db")
        )
        port = _free_port()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever,
            args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        ready.wait(10)
        coord = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        coord.add_replica("r0", ("127.0.0.1", port))
        coord.execute("CREATE TABLE rt (a BIGINT, b BIGINT)")
        coord.execute("INSERT INTO rt VALUES (1, 2), (3, 4)")
        coord.execute(
            "CREATE MATERIALIZED VIEW rmv AS SELECT a, b FROM rt"
        )
        coord.execute("SELECT * FROM rmv")
        coord.execute("SELECT * FROM rmv WHERE a = 1")
        sub = coord.execute(
            "SUBSCRIBE TO (SELECT a, b FROM rt WHERE a >= 0)"
        ).subscription
        coord.execute("INSERT INTO rt VALUES (5, 6)")
        final = coord._table_writers["rt"].upper
        deadline = _t.monotonic() + 60.0
        while sub.frontier < final and _t.monotonic() < deadline:
            sub.pop_ready()
            _t.sleep(0.01)
        sub.close()
        coord.execute("SELECT * FROM mz_donation")
        _t.sleep(0.2)  # let absorber/tail threads run a few passes
    except OSError as e:
        print(f"race-free: skipped (environment: {e!r})")
        return 0
    finally:
        if coord is not None:
            coord.shutdown()
        racecheck.disable()
        lockcheck.disable()
        COMPUTE_CONFIGS.update({"race_detector": False})
        shutil.rmtree(tmp, ignore_errors=True)
    findings = [
        LintFinding("racecheck", f.kind, str(f))
        for f in racecheck.findings()
    ]
    gate("race-free", None, findings, 0)
    return 1 if findings else 0


def run_interleave_smoke_gate(gate) -> int:
    """Interleaving-explorer gate (ISSUE 17): exhaustively check the
    two protocol models whose state spaces are small enough for CI —
    the epoch-fencing handshake (real ``_NonceSource``) and the
    catalog SET append-then-retract crash window (every crash point in
    every surviving schedule). Fails on any violation, wedge, or
    truncation; the explored-state counts are printed so a model edit
    that silently collapses coverage is visible in the gate output."""
    from materialize_tpu.analysis import LintFinding
    from materialize_tpu.analysis.interleave import MODELS, explore

    findings = []
    for name in ("fencing", "set-crash-window"):
        res = explore(MODELS[name], crash=True)
        print(
            f"interleave-smoke: {name}: {res.schedules} schedules, "
            f"{res.crash_branches} crash branches, {res.steps} steps"
        )
        if res.truncated:
            findings.append(
                LintFinding(
                    "interleave", "truncated",
                    f"{name}: state space truncated at "
                    f"{res.schedules} schedules — the model grew past "
                    "the exhaustive budget; shrink it or raise "
                    "max_schedules deliberately",
                )
            )
        for v in res.violations:
            findings.append(
                LintFinding("interleave", v.kind, v.format())
            )
    gate("interleave-smoke", None, findings, 0)
    return 1 if findings else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "paths", nargs="*",
        help="SLT files to check (default: tests/slt/*.slt)",
    )
    ap.add_argument(
        "--bench", action="store_true",
        help="jaxpr-lint the standard bench dataflows instead",
    )
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.bench:
        return run_bench_mode(args.verbose)
    paths = args.paths or sorted(
        glob.glob(os.path.join(REPO, "tests", "slt", "*.slt"))
    )
    if not paths:
        print("no SLT files found", file=sys.stderr)
        return 2
    return run_slt_mode(paths, args.verbose)


if __name__ == "__main__":
    sys.exit(main())
