"""Shard-spec abstract interpreter + collective-communication census.

ROADMAP item 2's prover (ISSUE 9): under SPMD the planner used to
hard-force ingest back to merge mode because the append-slot cursor was
a replicated scalar the ``shard_map`` boundary specs could not carry —
so multi-chip runs paid exactly the O(run0) cost the append-slot ring
eliminated. The fix carries the cursor as a SHARDED ``[devices]``
vector (one per-device slot cursor), which is sound iff the cursor's
dataflow stays SHARD-LOCAL across the whole step program: worker p's
output cursor may depend only on worker p's inputs (plus replicated
values) — never on data that crossed the worker axis through a
collective. This module *proves* that property statically, the same
prover→gated-enablement pattern as the PR 1 typechecker and the PR 5
donation prover.

The analysis is an abstract interpretation over the rendered step
program's jaxpr with a PartitionSpec-style sharding lattice:

    REPLICATED  ⊑  SHARD_LOCAL  ⊑  CROSS_WORKER

- ``REPLICATED``: the value is identical on every worker (a ``P()``
  boundary input, a constant, or an axis-reduction like ``psum`` whose
  output is uniform by construction).
- ``SHARD_LOCAL``: the value may differ per worker, but worker p's
  value is a pure function of worker p's shard inputs and replicated
  values (a ``P(axis)`` boundary input, ``axis_index``, or any
  composition of the two). Carrying such a leaf as a sharded
  ``[devices]`` vector is exactly equivalent to each worker owning a
  private scalar — the slot-cursor soundness condition.
- ``CROSS_WORKER`` (top): the value incorporates other workers' data
  via a data-moving collective (``all_to_all``, ``all_gather``,
  ``ppermute``, ...). A carry leaf in this class cannot ride a
  per-device spec without changing semantics; the verdict blames the
  offending eqn.

Seeds come from the ``shard_map`` eqn's boundary specs (``in_names``:
a spec naming the worker axis seeds SHARD_LOCAL, an empty spec seeds
REPLICATED), and the interpreter propagates classes through every eqn,
recursing into scan/while/cond/pjit bodies (loop carries run to a
fixpoint on the 3-point lattice).

Alongside the verdict the walk emits a **communication census** — the
comm analog of PR 2's ``op_census``: every collective site's kind,
mesh axes, and per-device operand byte volume. ``check_plans.py
--bench`` gates the standard bench configs against checked-in comm
budgets (``tests/kernel_budget.json``): a collective sneaking into a
shard-local stage fails CI statically, before any multi-chip run.

Surfaces: ``ShardedDataflow.sharding_report()`` (the render-layer
gate), ``EXPLAIN ANALYSIS``'s ``sharding:`` block, the ``mz_sharding``
introspection relation, and the ``comm-budget`` / ``spmd-safety``
gates in ``scripts/check_plans.py --bench``. See doc/analysis.md §6.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field

import numpy as np

try:  # pragma: no cover - version compatibility
    from jax.extend.core import Literal as _Literal
except Exception:  # noqa: BLE001
    from jax.core import Literal as _Literal

from .jaxpr_lint import _subjaxprs_of_eqn

# -- the sharding lattice ----------------------------------------------------

REPLICATED = "replicated"
SHARD_LOCAL = "shard-local"
CROSS_WORKER = "cross-worker"

_ORDER = {REPLICATED: 0, SHARD_LOCAL: 1, CROSS_WORKER: 2}

#: Abstract value: (lattice class, frozenset of blame strings — the
#: collective sites whose cross-worker data reaches this value).
_BOTTOM = (REPLICATED, frozenset())


def join_class(a: str, b: str) -> str:
    """Lattice join of two sharding classes."""
    return a if _ORDER[a] >= _ORDER[b] else b


def _join(a, b):
    return (join_class(a[0], b[0]), a[1] | b[1])


def _join_all(vals):
    out = _BOTTOM
    for v in vals:
        out = _join(out, v)
    return out


# Collective primitives and the lattice class of their OUTPUT.
# Axis reductions produce the same value on every worker (REPLICATED);
# data-moving collectives hand each worker other workers' rows
# (CROSS_WORKER). ``axis_index`` moves nothing (SHARD_LOCAL, handled
# separately — it is not a communication site).
_COLLECTIVE_RESULT = {
    "psum": REPLICATED,
    "psum2": REPLICATED,
    "pmax": REPLICATED,
    "pmin": REPLICATED,
    "pand": REPLICATED,
    "por": REPLICATED,
    "all_gather": CROSS_WORKER,
    "all_to_all": CROSS_WORKER,
    "ppermute": CROSS_WORKER,
    "pshuffle": CROSS_WORKER,
    "reduce_scatter": CROSS_WORKER,
    "pgather": CROSS_WORKER,
    "pdot": CROSS_WORKER,
}


def _aval_bytes(x) -> int:
    aval = getattr(x, "aval", None)
    size = getattr(aval, "size", 0)
    dt = getattr(aval, "dtype", None)
    if dt is None or not size:
        return 0
    return int(size) * np.dtype(dt).itemsize


def _eqn_axes(eqn) -> tuple:
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


@dataclass(frozen=True)
class CollectiveSite:
    """One collective-communication eqn in a step program."""

    path: str  # jaxpr path, e.g. "shard_map/scan:jaxpr/psum"
    primitive: str
    axes: tuple
    bytes_moved: int  # per-device operand bytes entering the collective
    result_class: str

    def __str__(self):
        return (
            f"{self.primitive}@{self.path or '<top>'} "
            f"axes={list(self.axes)} bytes={self.bytes_moved}"
        )


@dataclass
class CommCensus:
    """The communication census of one step program (the comm analog
    of PR 2's op_census): every collective site, with aggregates the
    budget gate compares against."""

    sites: list = field(default_factory=list)

    def add(self, site: CollectiveSite) -> None:
        self.sites.append(site)

    def extend(self, other: "CommCensus") -> None:
        self.sites.extend(other.sites)

    @property
    def collectives(self) -> int:
        return len(self.sites)

    @property
    def total_bytes(self) -> int:
        return sum(s.bytes_moved for s in self.sites)

    def kinds(self) -> dict:
        return dict(Counter(s.primitive for s in self.sites))

    def to_budget(self) -> dict:
        """The checked-in budget shape (tests/kernel_budget.json):
        collective count, per-device byte volume, and the per-kind
        breakdown — what check_plans.py --bench enforces."""
        return {
            "collectives": self.collectives,
            "bytes": self.total_bytes,
            "kinds": self.kinds(),
        }


@dataclass(frozen=True)
class ShardSafetyVerdict:
    """SPMD-safety verdict for one carry leaf (a slot-ring cursor):
    whether it stays shard-local across the whole step program, with
    the offending collective site(s) named when it does not."""

    leaf: str  # carry path, e.g. "output.cursor"
    cls: str  # lattice class of the leaf's output value
    safe: bool
    blame: tuple = ()  # collective sites whose data reaches the leaf
    reason: str = ""

    def describe(self) -> str:
        if self.safe:
            return f"{self.leaf}: {self.cls} (safe)"
        why = self.reason or "cross-worker data reaches the carry"
        blames = "; ".join(self.blame) if self.blame else "<unmapped>"
        return f"{self.leaf}: {self.cls} UNSAFE — {why} [{blames}]"


# -- the abstract interpreter ------------------------------------------------


def _eval_jaxpr(jaxpr, in_vals, census: CommCensus, path: str = ""):
    """Propagate abstract sharding values through one (Closed)Jaxpr.
    ``in_vals`` seeds the invars; constvars/consts seed REPLICATED
    (baked constants are identical on every worker). Returns the
    abstract values of the outvars; collective sites are appended to
    ``census``."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    env: dict = {}

    def read(a):
        if isinstance(a, _Literal):
            return _BOTTOM
        return env.get(a, _BOTTOM)

    for v, val in zip(inner.invars, in_vals):
        env[v] = val
    for v in inner.constvars:
        env[v] = _BOTTOM

    for eqn in inner.eqns:
        prim = eqn.primitive.name
        here = f"{path}/{prim}" if path else prim
        invals = [read(a) for a in eqn.invars]

        if prim in _COLLECTIVE_RESULT:
            rescls = _COLLECTIVE_RESULT[prim]
            site = CollectiveSite(
                here,
                prim,
                _eqn_axes(eqn),
                sum(_aval_bytes(a) for a in eqn.invars),
                rescls,
            )
            census.add(site)
            blame = (
                frozenset({str(site)})
                if rescls == CROSS_WORKER
                else frozenset()
            )
            for v in eqn.outvars:
                env[v] = (rescls, blame)
            continue

        if prim == "axis_index":
            # The worker's own coordinate: varies per worker, moves no
            # data, and is a pure function of worker identity.
            for v in eqn.outvars:
                env[v] = (SHARD_LOCAL, frozenset())
            continue

        if prim == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = eqn.params["jaxpr"]
            consts = invals[:nc]
            carry = invals[nc : nc + ncar]
            xs = invals[nc + ncar :]
            for _ in range(2 * max(ncar, 1) + 2):
                outs = _eval_jaxpr(
                    body, consts + carry + xs, CommCensus(), here
                )
                new_carry = [
                    _join(c, o) for c, o in zip(carry, outs[:ncar])
                ]
                if new_carry == carry:
                    break
                carry = new_carry
            outs = _eval_jaxpr(body, consts + carry + xs, census, here)
            outvals = [
                _join(c, o) for c, o in zip(carry, outs[:ncar])
            ] + outs[ncar:]
            for v, o in zip(eqn.outvars, outvals):
                env[v] = o
            continue

        if prim == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            cond = eqn.params["cond_jaxpr"]
            body = eqn.params["body_jaxpr"]
            cc = invals[:cn]
            bc = invals[cn : cn + bn]
            carry = invals[cn + bn :]
            pred = _BOTTOM
            for _ in range(2 * max(len(carry), 1) + 2):
                pred = _join_all(
                    _eval_jaxpr(cond, cc + carry, CommCensus(), here)
                )
                outs = _eval_jaxpr(body, bc + carry, CommCensus(), here)
                # Trip count depends on the predicate: its class taints
                # every carried value.
                new_carry = [
                    _join(_join(c, o), pred)
                    for c, o in zip(carry, outs)
                ]
                if new_carry == carry:
                    break
                carry = new_carry
            _eval_jaxpr(cond, cc + carry, census, f"{here}:cond")
            outs = _eval_jaxpr(body, bc + carry, census, f"{here}:body")
            outvals = [
                _join(_join(c, o), pred) for c, o in zip(carry, outs)
            ]
            for v, o in zip(eqn.outvars, outvals):
                env[v] = o
            continue

        if prim == "cond":
            branches = eqn.params["branches"]
            predv = invals[0]
            ops = invals[1:]
            outvals = None
            for bi, br in enumerate(branches):
                outs = _eval_jaxpr(
                    br, ops, census, f"{here}:branches[{bi}]"
                )
                if outvals is None:
                    outvals = outs
                else:
                    outvals = [
                        _join(a, b) for a, b in zip(outvals, outs)
                    ]
            # Branch selection depends on the predicate: its class
            # taints every output.
            outvals = [_join(o, predv) for o in (outvals or [])]
            for v, o in zip(eqn.outvars, outvals):
                env[v] = o
            continue

        subs = _subjaxprs_of_eqn(eqn)
        if subs:
            if len(subs) == 1 and len(subs[0][1].invars) == len(
                eqn.invars
            ):
                # pjit / closed_call / custom_* : invars map 1:1.
                tag, sub, _consts = subs[0]
                outs = _eval_jaxpr(
                    sub, invals, census, f"{here}:{tag}"
                )
                if len(outs) == len(eqn.outvars):
                    for v, o in zip(eqn.outvars, outs):
                        env[v] = o
                    continue
            # Unknown higher-order primitive: conservative — seed every
            # sub-jaxpr with the join of the operands, join everything.
            joined = _join_all(invals)
            for tag, sub, _consts in subs:
                outs = _eval_jaxpr(
                    sub,
                    [joined] * len(sub.invars),
                    census,
                    f"{here}:{tag}",
                )
                for o in outs:
                    joined = _join(joined, o)
            for v in eqn.outvars:
                env[v] = joined
            continue

        # Shard-local first-order op: per-worker elementwise semantics
        # — the output's class is the join of the operands'.
        out = _join_all(invals)
        for v in eqn.outvars:
            env[v] = out

    return [read(v) for v in inner.outvars]


# -- shard_map boundary handling ---------------------------------------------


def _spec_is_sharded(names) -> bool:
    """Whether one flat invar's boundary spec names a mesh axis.
    ``shard_map`` stores specs as ``in_names`` dicts ({array dim ->
    axis names}); newer APIs may carry PartitionSpec tuples — handle
    both."""
    if names is None:
        return True  # unknown spec: assume per-worker (conservative)
    if isinstance(names, dict):
        return bool(names)
    try:
        return any(x is not None for x in tuple(names))
    except TypeError:
        return bool(names)


@dataclass
class ShardMapAnalysis:
    """The abstract interpretation of ONE shard_map region."""

    eqn: object
    axis_names: tuple
    in_classes: tuple  # seed class per flat invar
    out_classes: tuple  # (class, blame frozenset) per flat outvar
    census: CommCensus


def shard_map_analyses(closed_jaxpr) -> list:
    """Find every ``shard_map`` eqn in a traced program (recursing
    through pjit wrappers) and abstractly interpret its body: seeds
    from the boundary in-specs, classes propagated through every eqn,
    collective census collected."""
    out: list = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def walk(jx, path):
        for eqn in jx.eqns:
            if eqn.primitive.name == "shard_map":
                out.append(_analyze_shard_map_eqn(eqn, path))
                continue
            for tag, sub, _consts in _subjaxprs_of_eqn(eqn):
                walk(sub, f"{path}/{eqn.primitive.name}:{tag}")

    walk(jaxpr, "")
    return out


def _analyze_shard_map_eqn(eqn, path: str) -> ShardMapAnalysis:
    body = eqn.params["jaxpr"]
    in_names = eqn.params.get("in_names")
    if in_names is None:
        in_names = eqn.params.get("in_specs")
    n_in = len(getattr(body, "jaxpr", body).invars)
    if in_names is None:
        in_names = (None,) * n_in
    seeds = [
        (
            (SHARD_LOCAL, frozenset())
            if _spec_is_sharded(names)
            else _BOTTOM
        )
        for names in in_names
    ]
    mesh = eqn.params.get("mesh")
    axis_names = tuple(
        str(a) for a in getattr(mesh, "axis_names", ())
    )
    census = CommCensus()
    here = f"{path}/shard_map" if path else "shard_map"
    outs = _eval_jaxpr(body, seeds, census, here)
    return ShardMapAnalysis(
        eqn=eqn,
        axis_names=axis_names,
        in_classes=tuple(s[0] for s in seeds),
        out_classes=tuple(outs),
        census=census,
    )


def comm_census(closed_jaxpr) -> CommCensus:
    """The merged communication census of every shard_map region in a
    traced step program (a program with no shard_map region — a
    single-device render — has an empty census by construction)."""
    census = CommCensus()
    for an in shard_map_analyses(closed_jaxpr):
        census.extend(an.census)
    return census


# -- carry-leaf identification ----------------------------------------------


def cursor_leaves(out_shape) -> list:
    """(flat output index, label) of every slot-ring cursor leaf in a
    step program's output pytree (the ``return_shape=True`` tree of
    ``trace_sharded_step``). The cursor is the LAST leaf of a slotted
    Spine's flattened children — a registered-pytree fact pinned by
    tests/test_shard_prop.py."""
    import jax

    from ..arrangement.spine import Spine

    found: list = []
    acc = {"idx": 0}

    def nleaves(x) -> int:
        return len(jax.tree_util.tree_leaves(x))

    def walk(x, label):
        if isinstance(x, Spine):
            n = nleaves(x)
            if x.slots and x.cursor is not None:
                found.append((acc["idx"] + n - 1, f"{label}.cursor"))
            acc["idx"] += n
            return
        if isinstance(x, (tuple, list)):
            for i, c in enumerate(x):
                walk(c, f"{label}[{i}]")
            return
        if isinstance(x, dict):
            # tree_flatten orders dict children by sorted key.
            for k in sorted(x):
                walk(x[k], f"{label}[{k}]")
            return
        acc["idx"] += nleaves(x)

    labels = ("delta", "states", "output", "err_output", "time", "flags")
    for part, lab in zip(out_shape, labels):
        walk(part, lab)
    return found


def _out_class_at(closed_jaxpr, analyses, flat_index: int):
    """The abstract value of top-level output ``flat_index``, mapped
    through the shard_map boundary (the body outvar that produced it).
    None when the leaf cannot be mapped (then the caller must assume
    unsafe)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    v = jaxpr.outvars[flat_index]
    if isinstance(v, _Literal):
        return _BOTTOM  # a literal output is trivially replicated
    for an in analyses:
        for j, ov in enumerate(an.eqn.outvars):
            if ov is v:
                return an.out_classes[j]
    return None


def spmd_safety(closed_jaxpr, out_shape) -> tuple:
    """(census, verdicts): the communication census plus one
    ShardSafetyVerdict per slot-ring cursor leaf in the step program's
    carry. A program with no cursors returns an empty verdict list —
    vacuously safe (merge-mode ingest has no cursor obligation)."""
    analyses = shard_map_analyses(closed_jaxpr)
    census = CommCensus()
    for an in analyses:
        census.extend(an.census)
    verdicts = []
    for idx, label in cursor_leaves(out_shape):
        oc = _out_class_at(closed_jaxpr, analyses, idx)
        if oc is None:
            verdicts.append(
                ShardSafetyVerdict(
                    label,
                    CROSS_WORKER,
                    False,
                    (),
                    "cursor leaf could not be mapped through the "
                    "shard_map boundary (assumed unsafe)",
                )
            )
            continue
        cls, blame = oc
        verdicts.append(
            ShardSafetyVerdict(
                label,
                cls,
                cls != CROSS_WORKER,
                tuple(sorted(blame)),
                ""
                if cls != CROSS_WORKER
                else "cross-worker data reaches the cursor carry",
            )
        )
    return census, verdicts


# -- render-layer entry points ----------------------------------------------


def trace_sharded_step(sdf, input_cap: int = 256):
    """Abstract-trace a ``ShardedDataflow``'s shard_map step program
    (nothing compiles or runs): empty per-worker-packed input batches
    at the dataflow's current state capacities. Returns
    (ClosedJaxpr, output shape pytree)."""
    import jax
    import jax.numpy as jnp

    from ..repr.batch import Batch
    from .jaxpr_lint import _unbound_gets

    inputs = {
        name: Batch.empty(sch, input_cap)
        for name, sch in _unbound_gets(sdf.expr).items()
    }
    packed = sdf._pack_inputs(inputs)
    time = jnp.asarray(sdf.time, dtype=jnp.uint64)
    env = sdf._build_env()
    args = (
        tuple(sdf.states), sdf.output, sdf.err_output, packed, time,
    )
    if env is not None:
        args = args + (env,)
    return jax.make_jaxpr(sdf._step_fn, return_shape=True)(*args)


def sharded_step_report(sdf, input_cap: int = 256) -> dict:
    """Run the prover over a ShardedDataflow's step program and return
    the report dict every surface consumes (``mz_sharding`` rows,
    EXPLAIN ANALYSIS's ``sharding:`` block, the check_plans gates).
    ``safe`` is the conjunction over cursor verdicts (vacuously true
    in merge mode); a trace/analysis failure
    reports unsafe with the error recorded — the render layer then
    falls back to merge ingest, never to an unproven slot ring."""
    try:
        closed, out_shape = trace_sharded_step(sdf, input_cap)
        census, verdicts = spmd_safety(closed, out_shape)
    except Exception as e:  # noqa: BLE001 — prover failure = unproven
        return {
            "spmd": True,
            "workers": sdf.num_shards,
            "axis": sdf.axis_name,
            "ingest_mode": "merge",
            "safe": False,
            "cursors": [],
            "census": {"collectives": 0, "bytes": 0, "kinds": {}},
            "error": f"shard-prop trace failed: {e!r}",
        }
    return {
        "spmd": True,
        "workers": sdf.num_shards,
        "axis": sdf.axis_name,
        "ingest_mode": (
            "append_slot" if _has_slot_cursors(sdf) else "merge"
        ),
        "safe": all(v.safe for v in verdicts),
        "cursors": [
            {
                "leaf": v.leaf,
                "class": v.cls,
                "safe": v.safe,
                "blame": list(v.blame),
                "reason": v.reason,
            }
            for v in verdicts
        ],
        "census": census.to_budget(),
        "error": None,
    }


def _has_slot_cursors(df) -> bool:
    """Whether any spine in the dataflow's carry runs append-slot
    ingest (i.e. carries a slot-ring cursor)."""
    from ..arrangement.spine import Spine

    if df.output.slots:
        return True
    return any(
        isinstance(s, Spine) and s.slots
        for parts in df.states
        for s in parts
    )


def single_device_report(df) -> dict:
    """The trivial sharding report of a single-device dataflow — the
    surfaces cover EVERY installed dataflow, SPMD or not, so a
    missing row never reads as an unproven one."""
    return {
        "spmd": False,
        "workers": 1,
        "axis": None,
        "ingest_mode": (
            "append_slot" if _has_slot_cursors(df) else "merge"
        ),
        "safe": True,
        "cursors": [],
        "census": {"collectives": 0, "bytes": 0, "kinds": {}},
        "error": None,
    }


def dataflow_sharding_report(df) -> dict:
    """The sharding report of ANY rendered dataflow: the cached prover
    report for SPMD dataflows, the trivial report otherwise."""
    rep = getattr(df, "sharding_report", None)
    if callable(rep):
        return rep()
    return single_device_report(df)


def sharding_display(report: dict) -> tuple:
    """(census string, blame string) for one report — the single
    formatter behind EXPLAIN ANALYSIS's sharding block and the
    mz_sharding introspection rows, so the two surfaces can never
    disagree."""
    c = report.get("census") or {}
    kinds = c.get("kinds") or {}
    census = (
        f"{c.get('collectives', 0)} collective(s), "
        f"{c.get('bytes', 0)} B"
    )
    if kinds:
        census += (
            " ["
            + ", ".join(
                f"{k}={n}" for k, n in sorted(kinds.items())
            )
            + "]"
        )
    blames = [
        b
        for cur in report.get("cursors", ())
        for b in cur.get("blame", ())
    ]
    if report.get("error"):
        blames.append(report["error"])
    return census, "; ".join(blames)
