"""Donation-safety prover + use-after-donate sanitizer.

Three layers, one contract: a buffer handed to XLA via
``donate_argnums`` is DEAD after dispatch — nothing on the host may
read it, re-dispatch it as an operand, or pickle it into history.

1. **The prover** (:func:`view_verdict`): turns the buffer-provenance
   scan (analysis/provenance.py) into a per-entry-point verdict — a
   span-carry argnum (states / output / err_output / time_dev) is
   provably donatable iff no device leaf reachable from it is also
   reachable from any root outside that carry (an ``IndexSource``
   base snapshot, a multiversion-history entry, a plain-reference
   rollback checkpoint, another dataflow). The replica's ``run_steps``
   span train donates exactly the parts the verdict allows.

2. **The sanitizer** (:class:`DonationLedger`, dyncfg
   ``buffer_sanitizer``): every donated dispatch records the
   just-killed carry leaves (weakrefs — the ledger never extends a
   buffer's lifetime) together with the provenance chain that owned
   them. Guarded read sites (``guard_read``: IndexSource snapshots,
   multiversion rewinds, step operand packing) raise
   :class:`UseAfterDonateError` naming *who still held the alias* the
   moment a dead buffer is touched. Because the donation CONTRACT is
   backend-independent (render/dataflow._donation_supported narrows
   only the argnums), the sanitizer enforces it on CPU too — the test
   suite catches use-after-donate bugs on hosts where real donation
   would not even be wired.

3. **The static cross-checks**: :func:`donation_lowering_findings`
   lowers a donated step program and verifies the argnums actually
   became ``input_output_aliases`` on carry parameters (and never on
   input operands); :func:`lint_donated_reuse` extends the
   host_sync AST walk with a donated-leaf rule — between a donated
   dispatch call and the re-assignment of each carry attribute, any
   Python read of that attribute is a use-after-donate, flagged
   before any hardware run.
"""

from __future__ import annotations

import ast
import inspect
import textwrap
import threading
import weakref
from dataclasses import dataclass, field

from .jaxpr_lint import LintFinding
from .provenance import (
    CARRY_PARTS,
    ProvenanceReport,
    scan_view,
)

USE_AFTER_DONATE = "use-after-donate"
UNSOUND_DONATION = "unsound-donation"

# Argnum of each carry part in the step program signature
# (states, output, err_output, inputs, time[, env]).
STEP_ARGNUM = {
    "states": 0,
    "output": 1,
    "err_output": 2,
    "time_dev": 4,
}


class UseAfterDonateError(RuntimeError):
    """A buffer donated to a step program was read (or re-dispatched)
    after the dispatch that killed it."""


def sanitizer_enabled() -> bool:
    from ..utils.dyncfg import BUFFER_SANITIZER, COMPUTE_CONFIGS

    return bool(BUFFER_SANITIZER(COMPUTE_CONFIGS))


# ---------------------------------------------------------------------------
# the runtime ledger
# ---------------------------------------------------------------------------


class DonationLedger:
    """Registry of dead (donated) device buffers, keyed by Python
    object identity with weakref validation — an id() reused by a new
    array after the donated one was collected can never false-positive.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # id -> (weakref to the array, provenance chain, span counter)
        self._entries: dict = {}
        self.recorded = 0
        self.caught = 0

    def record(self, tree, chain: str) -> int:
        """Mark every device leaf of ``tree`` as donated (dead).
        ``chain`` is the provenance string explaining which dispatch
        killed it. Returns the number of leaves recorded."""
        import jax

        n = 0
        with self._lock:
            if len(self._entries) > 65536:
                self._entries = {
                    k: v
                    for k, v in self._entries.items()
                    if v[0]() is not None
                }
            for leaf in jax.tree_util.tree_leaves(tree):
                if not isinstance(leaf, jax.Array):
                    continue
                try:
                    ref = weakref.ref(leaf)
                except TypeError:
                    continue
                self._entries[id(leaf)] = (ref, chain)
                n += 1
            self.recorded += n
        return n

    def check(self, tree, who: str) -> None:
        """Raise UseAfterDonateError if any device leaf of ``tree`` was
        donated. ``who`` names the reader (the alias holder)."""
        import jax

        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        with self._lock:
            for path, leaf in leaves:
                entry = self._entries.get(id(leaf))
                if entry is None or entry[0]() is not leaf:
                    continue
                self.caught += 1
                from .provenance import _path_str

                raise UseAfterDonateError(
                    f"use-after-donate: {who}{_path_str(path)} reads a "
                    f"buffer that was donated by {entry[1]} — the "
                    "reader still held an alias into the donated carry "
                    "(resolve by cloning at the sharing boundary, or "
                    "exclude the argnum from donation)"
                )

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


LEDGER = DonationLedger()


def guard_read(tree, who: str) -> None:
    """Read-site guard: validates ``tree`` against the donated-buffer
    ledger when the sanitizer is on. Wired at the access points the
    provenance analysis names as alias-capable: IndexSource base
    snapshots / pending fetches, multiversion-history rewinds, and
    span operand packing."""
    if sanitizer_enabled():
        LEDGER.check(tree, who)


# ---------------------------------------------------------------------------
# the prover
# ---------------------------------------------------------------------------


@dataclass
class DonationVerdict:
    """Per-entry-point donation safety for one dataflow's span carry."""

    name: str
    requested: bool
    donatable: dict = field(default_factory=dict)  # part -> bool
    reasons: list = field(default_factory=list)
    provenance: dict = field(default_factory=dict)  # class -> leaf count
    findings: list = field(default_factory=list)  # LintFindings (unsound)

    @property
    def safe(self) -> bool:
        return all(self.donatable.get(p, False) for p in CARRY_PARTS)

    def donate_parts(self) -> tuple:
        """The provably-safe subset of the carry to donate (empty
        tuple = do not donate)."""
        return tuple(p for p in CARRY_PARTS if self.donatable.get(p))

    def describe(self) -> str:
        parts = ",".join(self.donate_parts()) or "none"
        prov = " ".join(
            f"{k}={v}" for k, v in sorted(self.provenance.items())
        )
        head = (
            f"donation: safe={str(self.safe).lower()} "
            f"donatable=[{parts}] provenance({prov})"
        )
        if self.reasons:
            head += "\n  " + "\n  ".join(self.reasons)
        return head

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "safe": self.safe,
            "requested": self.requested,
            "donatable": dict(self.donatable),
            "reasons": list(self.reasons),
            "provenance": {
                k: int(v) for k, v in self.provenance.items()
            },
        }


def verdict_display(v: dict) -> tuple:
    """(donated, provenance) display strings for one REPORTED verdict
    dict — the single formatter behind EXPLAIN ANALYSIS's donation
    block and the mz_donation introspection rows, so the two surfaces
    can never disagree about the same verdict."""
    donated = ",".join(v.get("donated", [])) or "none"
    prov = " ".join(
        f"{k}={n}"
        for k, n in sorted((v.get("provenance") or {}).items())
    )
    return donated, prov


def view_verdict(
    name: str,
    view,
    requested: bool = True,
    report: ProvenanceReport | None = None,
) -> DonationVerdict:
    """Prove (or refute) donation safety for one MaintainedView's
    ``run_steps`` span train. Scans the view's full device-state roots
    and rules each carry argnum donatable iff nothing outside the
    carry aliases it. An aliasing *cloned* checkpoint is additionally
    reported as an UNSOUND finding — the clone contract guarantees
    fresh buffers, so an alias there is a bug, not a policy choice."""
    if report is None:
        report = ProvenanceReport()
        scan_view(report, name, view)
    v = DonationVerdict(
        name=name,
        requested=bool(requested),
        provenance=report.class_census(),
    )
    donated_window = getattr(view.df, "_defer_donated", ())
    for part in CARRY_PARTS:
        shared = report.shared_leaves(name, part)
        v.donatable[part] = not shared
        for rec in shared:
            reason = (
                f"{part}: leaf {rec.dtype}{list(rec.shape)} aliased by "
                f"{rec.chain()}"
            )
            v.reasons.append(reason)
            if part in donated_window:
                # This part is donated in the CURRENT deferred window
                # yet something still aliases it: the prover's gate
                # was bypassed or the clone contract broke.
                v.findings.append(
                    LintFinding(
                        UNSOUND_DONATION,
                        f"{name}/{part}",
                        f"donated carry part is aliased: {reason}",
                    )
                )
    return v


def dataflow_verdict(name: str, df, requested: bool = True):
    """Verdict for a bare rendered Dataflow (no view-level retentions):
    the shape check_plans.py --bench gates — a freshly rendered,
    subscriber-less dataflow must always prove fully donatable."""
    from .provenance import scan_dataflow

    report = ProvenanceReport()
    scan_dataflow(report, name, df)
    view = _BareDataflowView(df)
    return view_verdict(name, view, requested, report=report)


class _BareDataflowView:
    """Adapter giving a bare Dataflow the view surface view_verdict
    touches (no history, no subscribers)."""

    def __init__(self, df):
        self.df = df
        self._history = ()
        self._subscribers = ()


# ---------------------------------------------------------------------------
# static cross-check 1: donated argnums really become IO aliases
# ---------------------------------------------------------------------------


def donation_lowering_findings() -> list:
    """Lower a donated step program for a tiny synthetic dataflow and
    verify the donation wiring at the HLO boundary: every
    ``tf.aliasing_output`` parameter annotation must sit on a carry
    leaf (never on an input operand), and at least the bulk of the
    carry must alias. Catches a refactor that silently reorders the
    step signature out from under ``donate_argnums`` — the failure
    mode donation bugs are made of. Pure lowering: nothing compiles
    for a backend, nothing executes."""
    import re

    import jax
    import numpy as np

    from ..expr import relation as mir
    from ..render.dataflow import Dataflow
    from ..repr.batch import Batch
    from ..repr.schema import Column, ColumnType, Schema

    sch = Schema(
        (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
    )
    df = Dataflow(mir.Get("src", sch), name="donation-xcheck")
    jitfn = df._donated_step_program(CARRY_PARTS)
    inp = {
        "src": Batch.from_numpy(
            sch,
            [np.zeros(0, np.int64), np.zeros(0, np.int64)],
            np.zeros(0, np.uint64),
            np.zeros(0, np.int64),
            capacity=256,
        )
    }
    import jax.numpy as jnp

    carry = (
        tuple(df.states),
        df.output,
        df.err_output,
    )
    time_dev = jnp.asarray(0, dtype=jnp.uint64)
    n_carry_pre = len(jax.tree_util.tree_leaves(carry))
    n_inputs = len(jax.tree_util.tree_leaves(inp))
    lowered = jitfn.lower(*carry, inp, time_dev)
    txt = lowered.as_text()
    findings: list = []
    main = next(
        (
            l
            for l in txt.splitlines()
            if "func.func public @main" in l
        ),
        "",
    )
    aliased = [
        int(m.group(1))
        for m in re.finditer(
            r"%arg(\d+)[^%]*?tf\.aliasing_output", main
        )
    ]
    # Flattened parameter order follows the call: carry-before-inputs
    # (states, output, err), then the input batches, then time.
    input_lo, input_hi = n_carry_pre, n_carry_pre + n_inputs
    for i in aliased:
        if input_lo <= i < input_hi:
            findings.append(
                LintFinding(
                    UNSOUND_DONATION,
                    f"step-lowering/arg{i}",
                    "an INPUT operand carries tf.aliasing_output: the "
                    "donate_argnums wiring drifted off the carry "
                    "arguments (inputs must never be donated — the "
                    "defer log replays them on overflow)",
                )
            )
    if not aliased:
        findings.append(
            LintFinding(
                UNSOUND_DONATION,
                "step-lowering",
                "donate_argnums produced ZERO input_output_aliases: "
                "the donated step program would silently copy its "
                "whole carry every dispatch",
            )
        )
    return findings


# ---------------------------------------------------------------------------
# static cross-check 2: donated leaves never re-read after dispatch
# ---------------------------------------------------------------------------

# The host attributes that hold the donated carry between dispatches.
CARRY_ATTRS = ("states", "output", "err_output", "_time_dev")

# Names a dispatch call's function must end in to count as a (possibly
# donated) span/step dispatch. NOTE: `_donated_step_program` is the
# jit BUILDER, not a dispatch — it must not open a reuse window.
_DISPATCH_NAMES = ("jitfn", "step_fn", "_step_jit")

# (module, qualname) of every function that performs donated dispatches.
DONATED_DISPATCH_SITES = (
    ("materialize_tpu.render.dataflow", "_DataflowBase._dispatch_span"),
)


def _is_dispatch_call(node: ast.Call) -> bool:
    f = node.func
    name = None
    if isinstance(f, ast.Name):
        name = f.id
    elif isinstance(f, ast.Attribute):
        name = f.attr
    if name is None:
        return False
    return any(name.endswith(d) or d in name for d in _DISPATCH_NAMES)


def lint_donated_reuse_function(fn, where: str | None = None) -> list:
    """AST rule: after a span/step dispatch call, a Python READ of a
    carry attribute (``self.states`` / ``self.output`` /
    ``self.err_output`` / ``self._time_dev``) before that attribute is
    re-assigned is a use-after-donate — under donation those buffers
    died at the dispatch. Lines carrying ``# donated: ok(<why>)`` are
    sanctioned. Lexical (lineno) ordering: loop back-edges re-enter
    through the re-assignments, so the window between dispatch and
    store is exactly the dangerous region."""
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return []
    src_lines = src.splitlines()
    tree = ast.parse(src)
    name = where or getattr(fn, "__qualname__", str(fn))
    findings: list = []

    dispatch_lines = [
        node.lineno
        for node in ast.walk(tree)
        if isinstance(node, ast.Call) and _is_dispatch_call(node)
    ]
    if not dispatch_lines:
        return []

    def sanctioned(lineno: int) -> bool:
        if 1 <= lineno <= len(src_lines):
            line = src_lines[lineno - 1]
            if "#" in line:
                return (
                    line.split("#", 1)[1].strip().startswith("donated: ok")
                )
        return False

    for attr in CARRY_ATTRS:
        loads, stores = [], []
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Attribute)
                and node.attr == attr
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            ):
                (
                    stores
                    if isinstance(node.ctx, (ast.Store, ast.Del))
                    else loads
                ).append(node.lineno)
        for d in dispatch_lines:
            # The dangerous window: (dispatch line, first store after].
            later_stores = [s for s in stores if s > d]
            window_end = min(later_stores) if later_stores else 10**9
            for l in loads:
                if d < l <= window_end and not sanctioned(l):
                    findings.append(
                        LintFinding(
                            USE_AFTER_DONATE,
                            f"{name}:{l}",
                            f"`self.{attr}` read after the dispatch at "
                            f"line {d} and before its re-assignment: "
                            "under donation that buffer is dead the "
                            "moment the dispatch returns. Re-assign "
                            "the carry first, or mark an intentional "
                            "pre-donation read with `# donated: "
                            "ok(<why>)`.",
                        )
                    )
    findings.sort(key=lambda f: (f.where, f.message))
    return findings


def lint_donated_reuse(extra=()) -> list:
    """Lint every registered donated-dispatch function (plus ``extra``
    (module, qualname) pairs). Zero findings is the CI gate."""
    from .host_sync import _resolve

    findings: list = []
    for module_path, qualname in (
        tuple(DONATED_DISPATCH_SITES) + tuple(extra)
    ):
        fn = _resolve(module_path, qualname)
        findings.extend(
            lint_donated_reuse_function(fn, where=qualname)
        )
    findings.sort(key=lambda f: (f.where, f.message))
    return findings
