"""TPU-hazard linter over rendered step functions' jaxprs.

The render layer compiles each dataflow to one jitted step program
(render/dataflow.py). A class of bugs is invisible at the MIR/LIR level
but expensive on TPU hardware:

- ``f64-leak``: float64 arrays in the program. TPU has no native f64 —
  XLA emulates it as double-double at a large multiple of the f32 cost
  (and some generations refuse outright). An f64 usually sneaks in via
  an untyped Python float literal under ``jax_enable_x64``.
- ``host-callback``: ``pure_callback``/``io_callback``/``debug_print``
  primitives inside the step. Each one forces a device->host round trip
  per step, which serializes the pipeline: the step rate is then
  bounded by that round trip and not by the device.
- ``dyn-shape``: dynamically-shaped values. XLA recompiles per shape
  signature; a data-dependent shape in the hot loop means a compile
  per step.
- ``carry-vary``: a ``lax.scan``/``while_loop`` carry whose
  shape/dtype/structure varies between iterations. JAX refuses these at
  trace time; the linter converts the refusal into a structured finding
  with the fix (pad the carry to a static capacity tier — exactly the
  guard the r5 ingest-ring span program maintains by hand, see
  render/dataflow.py ``_build_letrec``'s loop-carry invariant).
- ``big-const``: large constants baked into the jaxpr. Baked constants
  are re-shipped per compile and defeat the compile cache across
  processes; device-resident state must flow through arguments.

Run it via ``scripts/check_plans.py --bench``, the ``-m analysis``
pytest lane (tests/test_jaxpr_lint.py), or directly::

    from materialize_tpu.analysis import lint_dataflow
    findings = lint_dataflow(df)
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

F64_LEAK = "f64-leak"
HOST_CALLBACK = "host-callback"
DYN_SHAPE = "dyn-shape"
CARRY_VARY = "carry-vary"
BIG_CONST = "big-const"

# Default threshold for big-const: anything >= 1 MiB baked into the
# graph is a real compile-cache/ship cost.
DEFAULT_MAX_CONST_BYTES = 1 << 20

_CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "host_callback_call",
        "outside_call",
    }
)


@dataclass(frozen=True)
class LintFinding:
    lint_id: str
    where: str  # jaxpr path, e.g. "scan/while/body"
    message: str

    def __str__(self):
        return f"[{self.lint_id}] at {self.where or '<top>'}: {self.message}"


def _subjaxprs_of_eqn(eqn):
    """(name, Jaxpr) pairs for every sub-jaxpr in an eqn's params."""
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for i, x in enumerate(vals):
            inner = getattr(x, "jaxpr", x)  # ClosedJaxpr -> Jaxpr
            if hasattr(inner, "eqns") and hasattr(inner, "invars"):
                tag = k if len(vals) == 1 else f"{k}[{i}]"
                consts = getattr(x, "consts", ())
                out.append((tag, inner, consts))
    return out


def _aval_findings(aval, where: str, seen: dict) -> None:
    dt = getattr(aval, "dtype", None)
    if dt is not None and dt == np.dtype("float64"):
        seen.setdefault((F64_LEAK, where), 0)
        seen[(F64_LEAK, where)] += 1
    shape = getattr(aval, "shape", ())
    for d in shape:
        if not isinstance(d, int):
            seen.setdefault((DYN_SHAPE, where), 0)
            seen[(DYN_SHAPE, where)] += 1
            break


def _check_consts(consts, where: str, max_const_bytes: int, findings):
    for c in consts:
        nbytes = getattr(c, "nbytes", 0)
        if nbytes and nbytes >= max_const_bytes:
            findings.append(
                LintFinding(
                    BIG_CONST,
                    where,
                    f"constant of {nbytes} bytes "
                    f"(shape {getattr(c, 'shape', '?')}, dtype "
                    f"{getattr(c, 'dtype', '?')}) baked into the "
                    "graph; pass device state through arguments so "
                    "the compile cache stays shape-keyed and the "
                    "value is not re-shipped per compile",
                )
            )


def lint_jaxpr(
    closed_jaxpr,
    max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
) -> list[LintFinding]:
    """Walk a ClosedJaxpr (recursing into scan/while/cond/pjit bodies)
    and return all TPU-hazard findings, deterministically ordered."""
    findings: list[LintFinding] = []
    # (lint_id, path) -> occurrence count, for the per-value lints that
    # would otherwise fire thousands of times in one program.
    seen: dict = {}

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _check_consts(
        getattr(closed_jaxpr, "consts", ()), "", max_const_bytes,
        findings,
    )

    def walk(jx, path: str):
        for v in list(jx.invars) + list(jx.constvars):
            _aval_findings(v.aval, path, seen)
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            here = f"{path}/{prim}" if path else prim
            if prim in _CALLBACK_PRIMS or "callback" in prim:
                findings.append(
                    LintFinding(
                        HOST_CALLBACK,
                        here,
                        f"host callback primitive {prim!r} on the hot "
                        "path: every step pays a device->host round "
                        "trip that serializes the pipeline; "
                        "move the computation on-device or to the "
                        "serving edge",
                    )
                )
            for v in eqn.outvars:
                _aval_findings(v.aval, here, seen)
            for tag, sub, consts in _subjaxprs_of_eqn(eqn):
                sub_path = f"{here}:{tag}"
                _check_consts(
                    consts, sub_path, max_const_bytes, findings
                )
                walk(sub, sub_path)

    walk(jaxpr, "")
    for (lint_id, where), n in seen.items():
        if lint_id == F64_LEAK:
            findings.append(
                LintFinding(
                    F64_LEAK,
                    where,
                    f"{n} float64 value(s): TPU emulates f64 in "
                    "software at a large multiple of the f32 cost. "
                    "Check for untyped Python float literals "
                    "(jax_enable_x64 promotes them to f64) or a "
                    "FLOAT64 column on a hot path that a DECIMAL "
                    "(scaled int64) column would serve exactly",
                )
            )
        else:
            findings.append(
                LintFinding(
                    DYN_SHAPE,
                    where,
                    f"{n} dynamically-shaped value(s): XLA compiles "
                    "per shape signature, so a data-dependent shape "
                    "in the step means a recompile per step; use a "
                    "static capacity tier with an overflow flag "
                    "(render/dataflow.py's tier scheme)",
                )
            )
    findings.sort(key=lambda f: (f.lint_id, f.where, f.message))
    return findings


def op_census(closed_jaxpr) -> dict:
    """Primitive census of a (Closed)Jaxpr: primitive name ->
    occurrence count, recursing into scan/while/cond/pjit bodies (each
    body counted ONCE — the census approximates the program's kernel
    count, i.e. how many distinct ops XLA must schedule, which is what
    a launch-bound step program pays per dispatch; PERF_NOTES round
    5)."""
    from collections import Counter

    counts: Counter = Counter()
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def walk(jx):
        for eqn in jx.eqns:
            counts[eqn.primitive.name] += 1
            for _tag, sub, _consts in _subjaxprs_of_eqn(eqn):
                walk(sub)

    walk(jaxpr)
    return dict(counts)


def kernel_count(closed_jaxpr) -> int:
    """Total op count of the census — the number the kernel budget
    gate (scripts/check_plans.py --bench, tests/kernel_budget.json)
    compares against."""
    return sum(op_census(closed_jaxpr).values())


def intermediate_bytes(closed_jaxpr) -> int:
    """Sum of every eqn OUTPUT's aval size (recursive). The honest
    per-dispatch WORK proxy: op count stays flat as capacities grow
    (shapes change, the program doesn't), but a step that touches a
    run0-sized array produces run0-sized outputs — so this number is
    what the O(delta) scaling test pins flat across run0 capacities."""
    total = 0
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def walk(jx):
        nonlocal total
        for eqn in jx.eqns:
            for v in eqn.outvars:
                aval = v.aval
                size = getattr(aval, "size", 0)
                dt = getattr(aval, "dtype", None)
                if dt is not None:
                    total += int(size) * np.dtype(dt).itemsize
            for _tag, sub, _consts in _subjaxprs_of_eqn(eqn):
                walk(sub)

    walk(jaxpr)
    return total


_CARRY_ERROR_MARKERS = (
    "carry",
    "body_fun",
    "body function",
    "same type structure",
    "differs from the carry",
)


def _carry_finding(e: TypeError) -> list[LintFinding] | None:
    """Convert a trace-time carry-mismatch TypeError into the
    carry-vary finding (None if the error is something else)."""
    msg = str(e)
    if not any(m in msg.lower() for m in _CARRY_ERROR_MARKERS):
        return None
    return [
        LintFinding(
            CARRY_VARY,
            "<trace>",
            "scan/while carry changes shape, dtype, or "
            "structure between iterations — a recompile/trace "
            "hazard on the hot path. Make every carried value "
            "chunk-invariant: pad to a static capacity tier "
            "and carry a row count, as the render layer does "
            "for LetRec binding deltas and the ingest ring "
            f"(render/dataflow.py). Trace error: {msg}",
        )
    ]


def lint_step_fn(
    fn, *args, max_const_bytes: int = DEFAULT_MAX_CONST_BYTES
) -> list[LintFinding]:
    """Trace ``fn(*args)`` to a jaxpr and lint it. A trace-time carry
    mismatch (scan/while carries must be iteration-invariant; JAX
    refuses otherwise) is converted into a ``carry-vary`` finding
    instead of an opaque TypeError."""
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except TypeError as e:
        findings = _carry_finding(e)
        if findings is not None:
            return findings
        raise
    return lint_jaxpr(closed, max_const_bytes)


def _unbound_gets(expr, env=None) -> dict:
    """name -> Schema for every Get not bound by a Let/LetRec — the
    dataflow's source inputs."""
    from ..expr import relation as mir

    env = env or set()
    out: dict = {}

    def go(e, env):
        if isinstance(e, mir.Get):
            if e.name not in env:
                out.setdefault(e.name, e._schema)
            return
        if isinstance(e, mir.Let):
            go(e.value, env)
            go(e.body, env | {e.name})
            return
        if isinstance(e, mir.LetRec):
            env2 = env | set(e.names)
            for v in e.values:
                go(v, env2)
            go(e.body, env2)
            return
        for c in e.children():
            go(c, env)

    go(expr, set(env))
    return out


def trace_dataflow_step(df, input_cap: int = 256, hints: tuple = ()):
    """Trace a rendered ``Dataflow``'s step program to a ClosedJaxpr
    (abstract tracing only — nothing compiles or runs): empty input
    batches at the dataflow's current state capacities. ``hints``
    attaches producer hints to the traced inputs — pass
    ``("hash_consolidated",)`` to trace the program the presorted
    bench ingest actually runs (hints are trace-time facts, so the
    hinted and unhinted step programs genuinely differ)."""
    import jax
    import jax.numpy as jnp

    from ..repr.batch import Batch

    inputs = {
        name: Batch.empty(sch, input_cap).replace(hints=hints)
        for name, sch in _unbound_gets(df.expr).items()
    }
    time = jnp.asarray(df.time, dtype=jnp.uint64)
    env = df._build_env()
    args = (
        tuple(df.states), df.output, df.err_output, inputs, time,
    )
    if env is not None:
        args = args + (env,)
    return jax.make_jaxpr(lambda *a: df._step_core(*a))(*args)


def lint_dataflow(
    df,
    input_cap: int = 256,
    max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
) -> list[LintFinding]:
    """Lint a rendered ``Dataflow``'s step program: traces
    ``_step_core`` with empty input batches at the dataflow's current
    state capacities (abstract tracing only — nothing compiles or
    runs) and walks the resulting jaxpr."""
    try:
        closed = trace_dataflow_step(df, input_cap)
    except TypeError as e:
        findings = _carry_finding(e)
        if findings is not None:
            return findings
        raise
    return lint_jaxpr(closed, max_const_bytes)
