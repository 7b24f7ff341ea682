"""Static analysis over MIR/LIR plans and rendered jaxprs.

Analog of the reference's ``transform/src/typecheck.rs`` (the typecheck
pass run between optimizer transforms) plus the physical-monotonicity
interpreter (``compute-types/src/plan/interpret``), extended with a
TPU-specific layer the reference has no analog for: a linter over the
jitted step function's ClosedJaxpr that flags device hazards (float64
leaks, host callbacks on the hot path, recompile hazards) before they
cost a device crash or a silent 100x slowdown.

Three passes:

- ``typecheck``: bottom-up MIR validation (schema flow, column-ref
  bounds, binding discipline, plan-decision consistency). Wired between
  optimizer transforms behind the ``optimizer_typecheck`` dyncfg so a
  transform bug is blamed on the transform that introduced it.
- ``monotonic``: an abstract-interpretation lattice over MIR answering
  "can this collection carry negative diffs" (nonneg) and "is it
  append-only" — consumed by threshold elision and reduce/topk planning.
- ``jaxpr_lint``: walks a rendered step function's jaxpr for TPU
  hazards; surfaced via scripts/check_plans.py and the test suite.
- ``host_sync``: AST lint of the per-span HOT PATH's Python source for
  accidental host sync points (np.asarray / .item() /
  block_until_ready / un-donated device_put) — the pipelined control
  plane's one-readback-per-span invariant, enforced statically.
- ``provenance`` / ``donation``: buffer-provenance scan over the
  render-layer state trees (per device leaf: span-carry-owned /
  shared-across-dataflows / host-retained / cache-retained + the
  sharing graph), the donation-safety prover gating the replica's
  donated ``run_steps`` span train, the runtime use-after-donate
  sanitizer (dyncfg ``buffer_sanitizer``), and the static
  cross-checks (lowered input_output_aliases, donated-leaf-reuse AST
  rule).
- ``shard_prop``: a shard-spec abstract interpreter over rendered
  step-program jaxprs (PartitionSpec-style lattice: replicated ⊑
  shard-local ⊑ cross-worker) emitting a collective-communication
  census (the comm analog of ``op_census``) and the SPMD-safety
  verdict gating per-device slot-ring ingest under ``shard_map``
  (ISSUE 9).
- ``racecheck`` / ``interleave``: the concurrency pair (ISSUE 17) —
  a vector-clock happens-before race detector over the control
  plane's declared shared state (dyncfg ``race_detector``), and a
  DPOR interleaving explorer that model-checks the coordination
  protocols (fencing, reconciliation, the SET crash window, peek
  batching, subscribe teardown) exhaustively. ``racecheck`` is
  re-exported here; ``interleave`` is imported directly (its model
  factories lazily import coord modules).

See doc/analysis.md for the catalogue of invariants and lints.
"""

from .donation import (  # noqa: F401
    LEDGER,
    UNSOUND_DONATION,
    USE_AFTER_DONATE,
    DonationVerdict,
    UseAfterDonateError,
    dataflow_verdict,
    donation_lowering_findings,
    guard_read,
    lint_donated_reuse,
    view_verdict,
)
from .provenance import (  # noqa: F401
    PROV_CACHE,
    PROV_CARRY,
    PROV_HOST,
    PROV_SHARED,
    ProvenanceReport,
    scan_dataflow,
    scan_replica,
    scan_view,
)
from .jaxpr_lint import (  # noqa: F401
    LintFinding,
    intermediate_bytes,
    kernel_count,
    lint_dataflow,
    lint_jaxpr,
    lint_step_fn,
    op_census,
    trace_dataflow_step,
)
from .host_sync import (  # noqa: F401
    HOST_SYNC,
    host_sync_findings_dataflow,
    lint_function,
    lint_hot_path,
)
from .shard_prop import (  # noqa: F401
    CROSS_WORKER,
    REPLICATED,
    SHARD_LOCAL,
    CollectiveSite,
    CommCensus,
    ShardSafetyVerdict,
    comm_census,
    dataflow_sharding_report,
    shard_map_analyses,
    sharded_step_report,
    sharding_display,
    single_device_report,
    spmd_safety,
    trace_sharded_step,
)
from .monotonic import (  # noqa: F401
    BOTTOM,
    SOURCE_DEFAULT,
    TOP,
    Facts,
    analyze,
)
from .typecheck import (  # noqa: F401
    TransformTypecheckError,
    TypecheckError,
    typecheck,
    typecheck_lir,
)
from . import racecheck  # noqa: F401
from .racecheck import RaceFinding  # noqa: F401


def report(expr, source_monotonic=frozenset()) -> str:
    """Text summary of every analysis over one MIR plan (the EXPLAIN
    ANALYSIS payload): typecheck verdict, monotonicity facts of the
    output collection, and LIR plan-decision consistency."""
    lines = []
    try:
        sch = typecheck(expr)
        lines.append(
            "typecheck: ok "
            f"(arity={sch.arity}, "
            f"types=[{', '.join(c.ctype.value for c in sch.columns)}])"
        )
    except TypecheckError as e:
        # A plan that fails typecheck is exactly what this surface
        # exists to diagnose — but the downstream passes assume a
        # well-typed tree (analyze/typecheck_lir call schema() and
        # index into children unguarded), so running them would trade
        # the verdict for an arbitrary IndexError/KeyError.
        lines.append(f"typecheck: FAILED: {e}")
        lines.append("monotonicity: skipped (plan does not typecheck)")
        lines.append("lir: skipped (plan does not typecheck)")
        return "\n".join(lines)
    facts = analyze(
        expr,
        source_facts={
            n: TOP for n in source_monotonic
        },
    )
    lines.append(
        f"monotonicity: nonneg={str(facts.nonneg).lower()} "
        f"append_only={str(facts.append_only).lower()}"
    )
    try:
        typecheck_lir(expr)
        lines.append("lir: ok")
    except TypecheckError as e:
        lines.append(f"lir: FAILED: {e}")
    return "\n".join(lines)
