"""Host-sync hazard linter over the per-span hot path (ISSUE 7).

The pipelined control plane's contract is ONE device→host readback per
span: overflow flags accumulate on-device and are read once at the
span boundary while the next span executes. A single accidental sync
point on the dispatch path — an ``np.asarray`` of a device value, an
``.item()``, a ``block_until_ready`` — serializes the pipeline and
silently reintroduces the per-span blocking round trip that this
whole refactor removes; an un-donated state-sized
``device_put`` reintroduces the per-span state copy donation exists to
avoid. These are HOST Python constructs, invisible to the jaxpr
linter, so this pass lints the *source* of the registered hot-path
functions (AST walk) and pairs it with the jaxpr-level callback scan
for the step programs themselves.

Sanctioned sync points carry a pragma on the offending line:

    ``# host-sync: ok(<why>)`` — an intentional boundary readback
    (the span-commit flags read IS the protocol's one readback);
    ``# h2d: <why>``          — an intentional staging upload (the
    prefetch ``device_put`` that overlaps the in-flight span).

Wired into ``scripts/check_plans.py --bench`` and the ``-m analysis``
pytest lane: a new sync point on the hot path fails CI statically,
before any hardware run.
"""

from __future__ import annotations

import ast
import inspect
import textwrap

from .jaxpr_lint import HOST_CALLBACK, LintFinding, lint_jaxpr

HOST_SYNC = "host-sync"

# Host-sync hazards: calls that force (or can force) a device->host
# transfer / synchronization when applied to device values.
_SYNC_ATTR_CALLS = frozenset({"item", "block_until_ready", "tolist"})
_SYNC_FUNC_CALLS = frozenset({"asarray", "array"})  # np.asarray/np.array
_H2D_CALLS = frozenset({"device_put"})
_NUMPY_NAMES = frozenset({"np", "numpy", "_np"})

# The per-span hot path: everything between two span boundaries. The
# boundary readback itself (read_flags_snapshot / _read_flags) is the
# protocol's sanctioned sync point and is pragma'd at its np.asarray.
DEFAULT_HOT_PATH = (
    ("materialize_tpu.render.dataflow", "_DataflowBase._dispatch_span"),
    ("materialize_tpu.render.dataflow", "_DataflowBase._dispatch_compact"),
    ("materialize_tpu.render.dataflow", "_DataflowBase._pack_flags"),
    ("materialize_tpu.render.dataflow", "_DataflowBase.flags_snapshot"),
    (
        "materialize_tpu.render.dataflow",
        "_DataflowBase.read_flags_snapshot",
    ),
    ("materialize_tpu.render.dataflow", "_DataflowBase._or_acc"),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._step_span_pipelined",
    ),
    # The sinked span's gather for the span after it runs between the
    # dispatch and the flags readback: a d2h read there would wait for
    # the device and put the gather back in series with it.
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._prefetch_ticks",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._gather_ready_ticks",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._wait_for_inputs",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "ShardSource.fetch_to",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "updates_to_batch",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._record_history",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._publish",
    ),
)

# The observability recorder path (ISSUE 12): trace-span recording,
# the compile-ledger wrapper around every jitted dispatch, and the
# span-commit cadence records all sit ON the per-span hot path — they
# must be pure host bookkeeping (no d2h reads, no blocking). Linted by
# the same host-sync gate as the dispatch path itself.
RECORDER_PATH = (
    ("materialize_tpu.utils.trace", "Tracer.record"),
    ("materialize_tpu.utils.trace", "Tracer._append"),
    ("materialize_tpu.utils.trace", "Tracer.span"),
    # Phase spans of the maintenance path (ISSUE 27): opened, timed
    # and closed once or many times a committed span. The phases wrap
    # syncs on purpose (span.readback); the recorder never syncs.
    ("materialize_tpu.utils.trace", "Tracer.open"),
    ("materialize_tpu.utils.trace", "Tracer.within"),
    ("materialize_tpu.utils.trace", "Tracer.close"),
    ("materialize_tpu.utils.trace", "Tracer.phase"),
    ("materialize_tpu.utils.trace", "_Phase.__enter__"),
    ("materialize_tpu.utils.trace", "_Phase.add"),
    ("materialize_tpu.utils.trace", "_Phase.__exit__"),
    ("materialize_tpu.storage.persist.machine", "Tally.mark"),
    ("materialize_tpu.storage.persist.machine", "Tally.since"),
    ("materialize_tpu.storage.persist.operators", "persist_phase"),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._open_span",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._close_span",
    ),
    ("materialize_tpu.utils.compile_ledger", "LedgeredJit.__call__"),
    ("materialize_tpu.utils.compile_ledger", "CompileLedger.record"),
    ("materialize_tpu.utils.compile_ledger", "tier_vector"),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._commit_span",
    ),
    # The freshness plane (ISSUE 15): wallclock-lag recording at every
    # committed span boundary must be pure host bookkeeping — deque
    # appends, a histogram bucket walk, and the SLO comparison.
    ("materialize_tpu.coord.freshness", "lag_ms"),
    ("materialize_tpu.coord.freshness", "FreshnessRecorder.record"),
    (
        "materialize_tpu.coord.freshness",
        "FreshnessRecorder._check_slo",
    ),
    (
        "materialize_tpu.storage.persist.operators",
        "MaintainedView._record_freshness",
    ),
)

DEFAULT_HOT_PATH = DEFAULT_HOT_PATH + RECORDER_PATH


def _resolve(module_path: str, qualname: str):
    import importlib

    mod = importlib.import_module(module_path)
    obj = mod
    for part in qualname.split("."):
        obj = getattr(obj, part)
    return obj


def _line_pragma(src_lines: list[str], lineno: int) -> str:
    """The comment tail of a source line (1-indexed within the
    function's own source)."""
    if 1 <= lineno <= len(src_lines):
        line = src_lines[lineno - 1]
        if "#" in line:
            return line.split("#", 1)[1].strip()
    return ""


def _is_numpy_value(node: ast.expr) -> bool:
    return (
        isinstance(node, ast.Name) and node.id in _NUMPY_NAMES
    ) or (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id in _NUMPY_NAMES
    )


def lint_function(fn, where: str | None = None) -> list[LintFinding]:
    """AST-lint one hot-path function's source for host-sync hazards.
    Returns findings; lines carrying a ``host-sync: ok`` / ``h2d:``
    pragma are sanctioned and skipped."""
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return []
    src_lines = src.splitlines()
    tree = ast.parse(src)
    name = where or getattr(fn, "__qualname__", str(fn))
    findings: list[LintFinding] = []

    def sanctioned(lineno: int) -> bool:
        pragma = _line_pragma(src_lines, lineno)
        return pragma.startswith("host-sync: ok") or pragma.startswith(
            "h2d:"
        )

    def flag(node: ast.AST, what: str, why: str) -> None:
        if sanctioned(node.lineno):
            return
        findings.append(
            LintFinding(
                HOST_SYNC,
                f"{name}:{node.lineno}",
                f"{what} on the per-span hot path: {why}. Move it to "
                "a span boundary (read_flags_snapshot is the one "
                "sanctioned readback per span) or mark an intentional "
                "boundary with `# host-sync: ok(<why>)`.",
            )
        )

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in _SYNC_ATTR_CALLS and not _is_numpy_value(
                f.value
            ):
                flag(
                    node,
                    f"`.{f.attr}()`",
                    "it blocks until the in-flight span finishes and "
                    "transfers device data to the host",
                )
            elif f.attr in _SYNC_FUNC_CALLS and _is_numpy_value(
                f.value
            ):
                flag(
                    node,
                    f"`np.{f.attr}` of a (potentially device) value",
                    "a d2h transfer here serializes the pipeline — "
                    "every span would block on a device round trip",
                )
            elif f.attr in _H2D_CALLS:
                flag(
                    node,
                    "`device_put`",
                    "an un-donated state-sized upload copies state "
                    "every span; "
                    "prefetch staging of INPUT batches is sanctioned "
                    "with a `# h2d: <why>` pragma, state must ride "
                    "the donated carry",
                )
        elif isinstance(f, ast.Name):
            if f.id in ("block_until_ready", "device_put"):
                flag(
                    node,
                    f"`{f.id}`",
                    "host synchronization on the dispatch path",
                )
    return findings


def lint_hot_path(extra=()) -> list[LintFinding]:
    """Lint every registered per-span hot-path function (plus
    ``extra`` (module, qualname) pairs). Zero findings is the CI gate
    (scripts/check_plans.py --bench)."""
    findings: list[LintFinding] = []
    for module_path, qualname in tuple(DEFAULT_HOT_PATH) + tuple(extra):
        fn = _resolve(module_path, qualname)
        findings.extend(lint_function(fn, where=qualname))
    findings.sort(key=lambda f: (f.where, f.message))
    return findings


def host_sync_findings_dataflow(df, input_cap: int = 256):
    """Host-sync verdict for one rendered dataflow's STEP PROGRAM: the
    jaxpr-level half of the rule (a host callback primitive inside the
    step is a per-step d2h round trip — the same hazard expressed in
    the program instead of the driver). Returns only callback
    findings; the AST half is global (lint_hot_path)."""
    from .jaxpr_lint import trace_dataflow_step

    closed = trace_dataflow_step(df, input_cap)
    return [
        f for f in lint_jaxpr(closed) if f.lint_id == HOST_CALLBACK
    ]
