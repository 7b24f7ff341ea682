"""Render: MIR relation expressions -> one jitted XLA step function.

Analog of the reference's render layer (compute/src/render.rs:202
``build_compute_dataflow``, :1155 ``render_plan_expr``), re-cast for TPU:
instead of building a graph of timely operators that run cooperatively,
rendering builds ONE pure function

    step(states, inputs, time) -> (output_delta, new_states, overflows)

that XLA compiles once per capacity signature and the host calls per
micro-batch (barrier-synchronous execution, SURVEY.md §7 design stance).
Stateful operators (Reduce, Join, TopK, Threshold) own slots in the
`states` tuple (Arrangements). Capacity overflow is detected on device
and resolved host-side by growing the overflowed tier and retrying the
step — the compile-cache-per-capacity-tier scheme.

Two execution modes share the same render walk:

- ``Dataflow``: single device, no exchange (the one-worker replica).
- ``ShardedDataflow``: SPMD over a worker mesh via ``shard_map``; every
  stateful operator's input is routed to the key's owning worker with an
  all_to_all exchange first (timely's Exchange pact, SURVEY.md §2.4) —
  so each worker maintains a disjoint shard of every arrangement.

The wrappers own the host side: frontier/time advancement, jit caching,
overflow retries, and the output arrangement serving peeks (the
TraceManager + handle_peek analog, compute/src/compute_state.rs:744).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..arrangement.spine import (
    Arrangement,
    Spine,
    arrange,
    compact_level,
    compact_spine,
    device_nbytes,
    insert,
    insert_tail,
)
from ..expr import relation as mir
from ..expr.errors import N_CODES as _N_ERR_CODES
from ..expr.linear import MapFilterProject, apply_mfp
from ..ops.consolidate import consolidate
from ..ops.delta_join import DeltaJoinOp
from ..ops.flat_map import flat_map
from ..ops.join import JoinOp
from ..ops.reduce import ReduceOp
from ..ops.temporal import TemporalFilterOp, canonicalize_temporal
from ..ops.threshold import ThresholdOp
from ..ops.topk import TopKOp
from ..ops.sort import concat_batches, shrink
from ..parallel.compat import shard_map
from ..parallel.exchange import exchange
from ..parallel.mesh import WORKER_AXIS, worker_sharding
from ..repr.batch import Batch, capacity_tier
from ..repr.schema import DIFF_DTYPE, TIME_DTYPE, Schema
from ..utils.trace import TRACER


def _donation_supported() -> bool:
    """Whether the backend honors ``donate_argnums``. CPU ignores
    donation (warning per buffer), so the argnums are wired only
    where they do something. The
    donation-SAFETY contract (cloned rollback checkpoint, span-
    boundary read barrier) stays backend-independent: callers request
    donation, the clone always happens, the argnums follow the
    backend."""
    return jax.default_backend() == "tpu"


def resolve_donation(mode=None) -> bool:
    """Resolve the span-carry donation mode: explicit bool wins, then
    the ``span_donation`` dyncfg ('on'/'off'/'auto'); 'auto' donates
    only where the backend implements donation (TPU — CPU ignores it
    with a warning per buffer)."""
    if isinstance(mode, bool):
        return mode
    if mode is None:
        from ..utils.dyncfg import COMPUTE_CONFIGS, SPAN_DONATION

        mode = SPAN_DONATION(COMPUTE_CONFIGS)
    if mode in ("on", "true", True):
        return True
    if mode in ("off", "false", False):
        return False
    return _donation_supported()


@dataclass
class _StateSlot:
    index: int
    init: Arrangement


class _RenderContext:
    """Collects state slots while walking the MIR tree (one walk at trace
    time per compilation). In sharded mode it also carries the mesh-axis
    facts every exchange site needs."""

    def __init__(self, source_schemas: dict, num_shards: int = 1,
                 axis_name: str = WORKER_AXIS, slot_cap: int = 256,
                 join_cap: int = 1024, state_cap: int = 256,
                 spmd_safe=None, force_merge_ingest: bool = False):
        self.source_schemas = source_schemas
        # Initial capacity tier for every stateful operator's
        # arrangements. Overflow growth doubles tiers as needed; callers
        # that know their steady-state size pass a larger tier up front
        # to skip the overflow->grow->recompile ladder (each rung is a
        # fresh XLA compile of the step program). Caps snap to the pow2
        # quantization menu (ISSUE 16): size-only DDL differences must
        # not mint new program-bank keys.
        from ..plan.decisions import quantize_cap

        state_cap = quantize_cap(state_cap)
        slot_cap = quantize_cap(slot_cap)
        join_cap = quantize_cap(join_cap)
        self.state_cap = state_cap
        # Ingest-mode decision for operator-state spines
        # (plan/decisions.py state_ingest_mode, the EXPLAIN-visible
        # source of truth): the number of append slots spine states
        # are built with, 0 = merge ingest. Under SPMD the slot cursor
        # rides the shard_map boundary as a per-device [P] vector,
        # gated on the shard-spec prover's verdict (ISSUE 9):
        # ``spmd_safe`` is True only for a render whose cursor the
        # prover has verdicted (or is about to verdict — the trial
        # render) shard-local; None/False resolve to merge.
        from ..plan.decisions import INGEST_RING_SLOTS, state_ingest_mode

        self.spmd_safe = spmd_safe
        # force_merge_ingest (ISSUE 16 async compile): the GENERIC
        # program family — merge ingest regardless of the dyncfg/auto
        # decision, so a fresh DDL's immediately-installed dataflow is
        # the cheapest-to-have-banked program while the specialized
        # one compiles in the background.
        self.ingest_slots = (
            INGEST_RING_SLOTS
            if not force_merge_ingest
            and state_ingest_mode(
                state_cap, spmd=num_shards > 1, spmd_safe=spmd_safe
            )
            == "append_slot"
            else 0
        )
        self.slots: list[_StateSlot] = []
        self.operators: list = []  # parallel to slots: op configs
        # (slot, part) -> (name, join site): the ONE Get that feeds a
        # join arrangement through stateless operators only, whose
        # rows bound the arrangement's, and the site its rows are
        # probed at (presize_for_snapshot).
        self.source_fed: dict = {}
        # The (slot, part)s of source_fed whose input reaches the join
        # through no filter: all of its snapshot can meet the site.
        self.unfiltered_fed: set = set()
        # One entry per delta-join pipeline: the inputs (Gets, with
        # let bindings resolved through ``let_inputs``) whose rows reach
        # the pipeline's own delta; the span records count the
        # pipelines whose inputs had rows (doc/observability.md).
        self.delta_pipelines: list[frozenset] = []
        self.let_inputs: dict = {}
        # Set once presize_for_snapshot has grown an arrangement: the
        # step's large sorts are then traced in blocks (ops/sort.py).
        self.sort_in_blocks = False
        self.num_shards = num_shards
        self.axis_name = axis_name
        # Per-destination send-slot capacity for exchanges; grown on
        # overflow (mutated by the host wrapper, read at trace time).
        self.slot_cap = slot_cap
        self.n_exchanges = 0
        # Per-join-site output capacity tier (match fan-out is
        # data-dependent); grown on overflow, read at trace time.
        self.join_caps: list[int] = []
        self.default_join_cap = join_cap
        # Per-LetRec-site binding-delta capacity tier.
        self.letrec_caps: list[int] = []
        self.default_letrec_cap = 2048
        # Output deltas are shrunk to this tier before the output
        # arrangement insert, so the insert's sorts compile at a small
        # capacity regardless of input batch size.
        self.out_delta_cap = 4096
        # The dataflow's first processed timestamp (its as_of): set by
        # the host wrapper before the first step, read at trace time.
        # Constants emit exactly once, AT this time (render.rs:1170
        # "rows advanced to as_of") — not at literal time 0, which a
        # hydrated dataflow never processes.
        self.first_time = 0
        # Reduce sites with basic (collection) aggregates: (mir node id,
        # state slot, ReduceOp). The dataflow resolves these against its
        # top-level expression to build edge finalizers (ops/reduce.py
        # basic tier — render/reduce.rs:369 analog).
        self.basic_sites: list = []

    @property
    def sharded(self) -> bool:
        return self.num_shards > 1

    def new_slot(self, op, init: Arrangement) -> int:
        idx = len(self.slots)
        self.slots.append(_StateSlot(idx, init))
        self.operators.append(op)
        return idx

    def new_exchange_site(self) -> int:
        idx = self.n_exchanges
        self.n_exchanges += 1
        return idx

    def new_join_site(self) -> int:
        self.join_caps.append(self.default_join_cap)
        return len(self.join_caps) - 1

    def maybe_exchange(self, batch: Batch, key, site: int, ovf: dict,
                       null_aware: bool = True):
        """Route `batch` by `key` to owning workers (no-op single-shard)."""
        if not self.sharded:
            return batch, ovf
        routed, overflow = exchange(
            batch, key, self.axis_name, self.num_shards, self.slot_cap,
            null_aware,
        )
        ovf = dict(ovf)
        ovf[("x", site)] = overflow
        return routed, ovf


def _sole_get(expr: mir.RelationExpr) -> str | None:
    """The name of the one Get under Filter/Map/Project only: what
    reaches the consumer is at most that collection's rows."""
    while isinstance(expr, (mir.Filter, mir.Map, mir.Project)):
        expr = expr.input
    return expr.name if isinstance(expr, mir.Get) else None


def _note_source_fed(ctx, expr, slot: int, part: int, jsite: int) -> None:
    """Record (``source_fed``) a join arrangement that one input feeds
    through stateless operators, and whether through no Filter."""
    fed_by = _sole_get(expr)
    if fed_by is None:
        return
    ctx.source_fed[(slot, part)] = (fed_by, jsite)
    while isinstance(expr, (mir.Map, mir.Project)):
        expr = expr.input
    if isinstance(expr, mir.Get):
        ctx.unfiltered_fed.add((slot, part))


def _inputs_under(
    expr: mir.RelationExpr, ctx: _RenderContext
) -> frozenset:
    """The dataflow inputs whose rows can reach ``expr``'s delta: its
    Gets, a let binding's resolved to what its value reads."""
    if isinstance(expr, mir.Get):
        return ctx.let_inputs.get(expr.name, frozenset((expr.name,)))
    out: frozenset = frozenset()
    for ch in expr.children():
        out |= _inputs_under(ch, ctx)
    return out


def _build(expr: mir.RelationExpr, ctx: _RenderContext):
    """Returns a closure (states, inputs, time) -> (delta_batch,
    state_updates: dict slot->new_state, overflow_flags: dict key->flag).

    Overflow keys: ("state", slot) for arrangement tiers, ("x", site)
    for exchange slot tiers.
    """

    if isinstance(expr, mir.Get):
        name = expr.name

        def run(states, inputs, time):
            return inputs[name], {}, {}

        return run

    if isinstance(expr, mir.Constant):
        schema = expr._schema
        rows = expr.rows

        def run(states, inputs, time):
            # Emit the constant collection exactly once: at the
            # dataflow's as_of, nothing afterwards (render.rs:1170-1212).
            n = len(rows)
            cap = capacity_tier(max(n, 1))
            cols = []
            for j, c in enumerate(schema.columns):
                vals = np.asarray(
                    [r[0][j] for r in rows], dtype=c.dtype
                ) if n else np.zeros(0, dtype=c.dtype)
                pad = np.zeros(cap, dtype=c.dtype)
                pad[:n] = vals
                cols.append(jnp.asarray(pad))
            diffs = np.zeros(cap, dtype=DIFF_DTYPE)
            diffs[:n] = [r[1] for r in rows]
            first = (time == ctx.first_time).astype(jnp.int32)
            if ctx.sharded:
                # Exactly one worker emits the constant; the exchange in
                # front of any stateful consumer routes rows to owners.
                first = first * (
                    jax.lax.axis_index(ctx.axis_name) == 0
                ).astype(jnp.int32)
            return (
                Batch(
                    cols=tuple(cols),
                    nulls=tuple(None for _ in schema.columns),
                    time=jnp.full(cap, time, dtype=TIME_DTYPE),
                    diff=jnp.asarray(diffs),
                    count=first * n,
                    schema=schema,
                ),
                {},
                {},
            )

        return run

    if isinstance(expr, mir.Project):
        inner = _build(expr.input, ctx)
        mfp = MapFilterProject(
            expr.input.schema().arity, projection=expr.outputs
        )

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            return apply_mfp(mfp, b, time), upd, ovf

        return run

    if isinstance(expr, mir.Map):
        inner = _build(expr.input, ctx)
        mfp = MapFilterProject(
            expr.input.schema().arity, expressions=expr.scalars
        )
        out_schema = expr.schema()  # MIR's naming (c{i}) is authoritative

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            return (
                apply_mfp(mfp, b, time).replace(schema=out_schema),
                upd,
                ovf,
            )

        return run

    if isinstance(expr, mir.Filter):
        from ..expr.scalar import contains_mz_now

        temporal = [p for p in expr.predicates if contains_mz_now(p)]
        plain = [p for p in expr.predicates if not contains_mz_now(p)]
        inner = _build(expr.input, ctx)
        mfp = MapFilterProject(
            expr.input.schema().arity, predicates=plain
        )
        if not temporal:

            def run(states, inputs, time):
                b, upd, ovf = inner(states, inputs, time)
                return apply_mfp(mfp, b, time), upd, ovf

            return run

        # Temporal predicates: plain filter first, then the scheduled
        # window operator (expr/src/linear.rs:1724 MfpPlan). No exchange:
        # each worker schedules its own rows' futures.
        from ..utils.dyncfg import (
            COMPUTE_CONFIGS,
            ENABLE_TEMPORAL_FILTERS,
        )

        if not ENABLE_TEMPORAL_FILTERS(COMPUTE_CONFIGS):
            raise NotImplementedError(
                "temporal filters disabled by dyncfg "
                "enable_temporal_filters"
            )
        lo_exprs, hi_exprs = canonicalize_temporal(temporal)
        op = TemporalFilterOp(
            expr.input.schema(), tuple(lo_exprs), tuple(hi_exprs)
        )
        slot = ctx.new_slot(op, op.init_state(ctx.state_cap))
        osite = ctx.new_join_site()  # output-capacity tier

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            b = apply_mfp(mfp, b, time)
            new_state, out, overflow, out_ovf = op.step(
                states[slot], b, time, ctx.join_caps[osite]
            )
            upd = dict(upd)
            upd[slot] = new_state
            ovf = dict(ovf)
            for part, flag in overflow.items():
                ovf[("state", slot, part)] = flag
            ovf[("join", osite)] = out_ovf
            return out, upd, ovf

        return run

    if isinstance(expr, mir.Negate):
        inner = _build(expr.input, ctx)

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            return b.replace(diff=-b.diff), upd, ovf

        return run

    if isinstance(expr, mir.Union):
        inners = [_build(i, ctx) for i in expr.inputs]

        def run(states, inputs, time):
            parts, upd, ovf = [], {}, {}
            for f in inners:
                b, u, o = f(states, inputs, time)
                parts.append(b)
                upd.update(u)
                ovf.update(o)
            return concat_batches(parts), upd, ovf

        return run

    if isinstance(expr, mir.Reduce):
        op = ReduceOp(
            expr.input.schema(), expr.group_key, expr.aggregates
        )
        slot = ctx.new_slot(op, op.init_state(ctx.state_cap))
        if op.basic_aggs:
            ctx.basic_sites.append((id(expr), slot, op))
        site = ctx.new_exchange_site()
        inner = _build(expr.input, ctx)
        group_key = expr.group_key

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            b, ovf = ctx.maybe_exchange(b, group_key, site, ovf)
            new_state, out, overflow = op.step(states[slot], b, time)
            upd = dict(upd)
            upd[slot] = new_state
            ovf = dict(ovf)
            for part, flag in overflow.items():
                ovf[("state", slot, part)] = flag
            return out, upd, ovf

        return run

    if isinstance(expr, mir.Let):
        val = _build(expr.value, ctx)
        ctx.let_inputs[expr.name] = _inputs_under(expr.value, ctx)
        body = _build(expr.body, ctx)
        name = expr.name

        def run(states, inputs, time):
            vb, upd, ovf = val(states, inputs, time)
            # The binding's delta is computed ONCE and shared by every
            # Get (arrangement sharing analog: NormalizeLets + the
            # TraceManager let bindings, render_plan.rs bind stages).
            inner_inputs = dict(inputs)
            inner_inputs[name] = vb
            ob, u2, o2 = body(states, inner_inputs, time)
            return ob, {**upd, **u2}, {**ovf, **o2}

        return run

    if isinstance(expr, mir.Join):
        return _build_join(expr, ctx)

    if isinstance(expr, mir.LetRec):
        return _build_letrec(expr, ctx)

    if isinstance(expr, mir.Threshold):
        op = ThresholdOp(expr.input.schema())
        slot = ctx.new_slot(op, op.init_state(ctx.state_cap))
        site = ctx.new_exchange_site()
        inner = _build(expr.input, ctx)
        all_cols = tuple(range(expr.input.schema().arity))

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            b, ovf = ctx.maybe_exchange(b, all_cols, site, ovf)
            new_state, out, overflow = op.step(states[slot], b, time)
            upd = dict(upd)
            upd[slot] = new_state
            ovf = dict(ovf)
            for part, flag in overflow.items():
                ovf[("state", slot, part)] = flag
            return out, upd, ovf

        return run

    if isinstance(expr, mir.TopK):
        op = TopKOp(
            expr.input.schema(), expr.group_key, expr.order_by,
            expr.limit, expr.offset,
        )
        slot = ctx.new_slot(op, op.init_state(ctx.state_cap))
        site = ctx.new_exchange_site()
        inner = _build(expr.input, ctx)
        group_key = expr.group_key

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            b, ovf = ctx.maybe_exchange(b, group_key, site, ovf)
            new_state, out, overflow = op.step(states[slot], b, time)
            upd = dict(upd)
            upd[slot] = new_state
            ovf = dict(ovf)
            for part, flag in overflow.items():
                ovf[("state", slot, part)] = flag
            return out, upd, ovf

        return run

    if isinstance(expr, mir.FlatMap):
        inner = _build(expr.input, ctx)
        fsite = ctx.new_join_site()  # fan-out capacity tier, like a join
        out_schema = expr.schema()
        func, exprs = expr.func, expr.exprs

        def run(states, inputs, time):
            b, upd, ovf = inner(states, inputs, time)
            out, overflow = flat_map(
                b, func, exprs, out_schema, time, ctx.join_caps[fsite]
            )
            ovf = dict(ovf)
            ovf[("join", fsite)] = overflow
            return out, upd, ovf

        return run

    if isinstance(expr, mir.ArrangeBy):
        # Arrangement sharing across operators is implicit (Let bindings
        # compute each delta once); ArrangeBy is a planner hint here.
        return _build(expr.input, ctx)

    raise NotImplementedError(
        f"render: {type(expr).__name__} not supported in operator set v0"
    )


def _build_join(expr: mir.Join, ctx: _RenderContext):
    # The linear-vs-delta decision and stage keys come from the plan
    # layer (materialize_tpu/plan/decisions.py) so EXPLAIN PHYSICAL PLAN
    # prints exactly what renders.
    from ..plan import join_implementation

    if join_implementation(expr) == "delta":
        return _build_join_delta(expr, ctx)
    return _build_join_linear(expr, ctx)


def _build_join_delta(expr: mir.Join, ctx: _RenderContext):
    """Delta join plan: per-input update pipelines over shared arrangements
    (JoinPlan::Delta, compute-types/src/plan/join.rs; delta_join.rs:51).
    In SPMD mode every arrangement insert and every probe is preceded by
    an all_to_all on the relevant key (the half_join exchange)."""
    schemas = [i.schema() for i in expr.inputs]
    op = DeltaJoinOp(tuple(schemas), expr.equivalences)
    slot = ctx.new_slot(
        op,
        op.init_state(ctx.state_cap, ingest_slots=ctx.ingest_slots),
    )
    jsite = ctx.new_join_site()
    inners = [_build(i, ctx) for i in expr.inputs]
    ctx.delta_pipelines += [_inputs_under(i, ctx) for i in expr.inputs]
    for p, (j, _key) in enumerate(op.arr_specs):
        _note_source_fed(ctx, expr.inputs[j], slot, p, jsite)
    ex_sites = {}
    for p in range(len(op.arr_specs)):
        ex_sites[("ins", p)] = ctx.new_exchange_site()
    for i, (steps, _) in enumerate(op.pipelines):
        for j, acc_key, j_key, ap in steps:
            ex_sites[("probe", i, ap)] = ctx.new_exchange_site()

    def run(states, inputs, time):
        deltas, upd, ovf = [], {}, {}
        for f in inners:
            b, u, o = f(states, inputs, time)
            deltas.append(b)
            upd.update(u)
            ovf.update(o)

        ovf_box = {"d": dict(ovf)}

        def exchange_fn(b, key, tag):
            b2, ovf_box["d"] = ctx.maybe_exchange(
                b, key, ex_sites[tag], ovf_box["d"], null_aware=False
            )
            return b2

        new_state, out, st_ovf, j_ovf = op.step(
            states[slot],
            deltas,
            time,
            ctx.join_caps[jsite],
            exchange_fn if ctx.sharded else None,
        )
        upd = dict(upd)
        upd[slot] = new_state
        ovf = dict(ovf_box["d"])
        for part, flag in st_ovf.items():
            ovf[("state", slot, part)] = flag
        ovf[("join", jsite)] = j_ovf
        return out, upd, ovf

    return run


def _build_join_linear(expr: mir.Join, ctx: _RenderContext):
    """Linear join plan: left-fold binary JoinOp stages, each with both
    sides exchanged on the stage key (JoinPlan::Linear,
    compute-types/src/plan/join.rs:46; rendering linear_join.rs:204)."""
    schemas = [i.schema() for i in expr.inputs]
    offsets = [0]
    for s in schemas:
        offsets.append(offsets[-1] + s.arity)
    inners = [_build(i, ctx) for i in expr.inputs]

    stages = []
    acc_schema = schemas[0]
    all_consumed: set = set()
    for i in range(1, len(expr.inputs)):
        from ..plan import join_stage_keys

        left_key, right_key, consumed = join_stage_keys(expr, offsets, i)
        all_consumed.update(consumed)
        op = JoinOp(acc_schema, schemas[i], left_key, right_key)
        slot = ctx.new_slot(
            op,
            op.init_state(
                ctx.state_cap, ingest_slots=ctx.ingest_slots
            ),
        )
        jsite = ctx.new_join_site()
        lsite = ctx.new_exchange_site()
        rsite = ctx.new_exchange_site()
        stages.append((op, slot, jsite, lsite, rsite, left_key, right_key))
        # part 0 arranges the accumulated left side (an input only at
        # the first stage), part 1 the input this stage brings in
        for p, j in ((0, 0), (1, i)) if i == 1 else ((1, i),):
            _note_source_fed(ctx, expr.inputs[j], slot, p, jsite)
        acc_schema = op.out_schema
    if len(all_consumed) != len(expr.equivalences):
        # An intra-input equality (all members in one input) would be
        # silently unenforced — the optimizer should have rewritten it
        # into a Filter; refuse rather than emit wrong rows.
        raise NotImplementedError(
            "equivalence class not consumable as a join key "
            "(intra-input equality: rewrite as Filter)"
        )

    def run(states, inputs, time):
        deltas, upd, ovf = [], {}, {}
        for f in inners:
            b, u, o = f(states, inputs, time)
            deltas.append(b)
            upd.update(u)
            ovf.update(o)
        acc = deltas[0]
        for (op, slot, jsite, lsite, rsite, lkey, rkey), d_right in zip(
            stages, deltas[1:]
        ):
            acc, ovf = ctx.maybe_exchange(
                acc, lkey, lsite, ovf, null_aware=False
            )
            d_right, ovf = ctx.maybe_exchange(
                d_right, rkey, rsite, ovf, null_aware=False
            )
            new_state, out, st_ovf, j_ovf = op.step(
                states[slot], acc, d_right, time, ctx.join_caps[jsite]
            )
            upd = dict(upd)
            upd[slot] = new_state
            ovf = dict(ovf)
            for part, flag in st_ovf.items():
                ovf[("state", slot, part)] = flag
            ovf[("join", jsite)] = j_ovf
            acc = out
        return acc, upd, ovf

    return run


def _build_letrec(expr: mir.LetRec, ctx: _RenderContext):
    """WITH MUTUALLY RECURSIVE: device-resident fixpoint iteration.

    Analog of the reference's iterative scopes (compute/src/render.rs:887
    ``render_recursive_plan``; differential ``Variable`` + PointStamp
    timestamps). The TPU re-cast is a ``jax.lax.while_loop`` of semi-naive
    (Jacobi) iterations — compiled once, running entirely on device:

      iter 0: binding values see the step's real source deltas and empty
              binding deltas;
      iter k: values see empty source deltas and iteration k-1's binding
              deltas; stateful operators inside the values carry their
              arrangements through the loop (the converged state at outer
              time t is the correct starting state for t+1, exactly the
              effect of differential's full logical compaction).

    Convergence = every binding's consolidated delta is empty (psum'd
    across workers in SPMD mode, so the loop condition is mesh-uniform);
    ``max_iters`` caps divergent or float-asymptotic recursions
    (LetRecLimit / RETURN AT RECURSION LIMIT analog). The body sees the
    per-step total (telescoped) binding deltas.

    Known limitation (documented, as in SURVEY.md §7 hard part re:
    determinism/recursion): retraction propagation uses derivation
    counting, which matches the reference's semantics for monotone and
    acyclic-derivation recursions; cyclic derivations with retractions
    would need iteration-indexed state (differential's nested timestamps).
    """
    names = expr.names
    schemas = expr.value_schemas
    value_fns = [_build(v, ctx) for v in expr.values]
    body_fn = _build(expr.body, ctx)
    site = len(ctx.letrec_caps)
    ctx.letrec_caps.append(ctx.default_letrec_cap)
    max_iters = expr.max_iters if expr.max_iters is not None else 100_000

    def run(states, inputs, time):
        cap = ctx.letrec_caps[site]

        def canon_states(states_l):
            """Null-mask presence must be loop-invariant (pytree aux of
            the while_loop carry): canonicalize every arrangement (or
            spine-run) batch."""
            out = []
            for s in states_l:
                if isinstance(s, tuple):
                    out.append(
                        tuple(
                            a.map_batches(
                                lambda b: b.canonicalize_nulls()
                            )
                            for a in s
                        )
                    )
                else:
                    out.append(s)
            return out

        def run_values(states_l, it_inputs):
            """One iteration: returns (new_states_list, deltas, ovf
            dict, err-count vector [N_ERR_CODES]).

            Error-stream batches raised INSIDE the fixpoint cannot ride
            the outer step's Python-list err sink (values created in
            the while_loop trace would escape the loop as leaked
            tracers). Instead they fold into a fixed-shape per-code
            count vector that RIDES THE LOOP CARRY; the outer run()
            converts the final counts into err update rows
            (render.rs:12-101 — LetRec-internal errors reach the err
            collection, and retract: a deletion re-evaluates the site
            with diff=-1)."""
            from ..expr import errors as _errors

            with _errors.step_scope() as sink:
                sts, deltas_i, ovf_i = _run_values_inner(
                    states_l, it_inputs
                )
            errs = jnp.zeros((_N_ERR_CODES,), jnp.int64)
            for eb in sink:
                errs = errs.at[eb.cols[0]].add(eb.diff)
            return sts, deltas_i, ovf_i, errs

        def _run_values_inner(states_l, it_inputs):
            states_l = list(states_l)
            ovf = {}
            deltas = []
            for i, fn in enumerate(value_fns):
                d, upd, o = fn(states_l, it_inputs, time)
                for k, v in upd.items():
                    states_l[k] = v
                ovf.update(o)
                d = consolidate(d, include_time=False)
                d, so = shrink(d, cap)
                if d.capacity != cap:
                    # Loop-carry invariant: binding deltas/accums must
                    # sit at EXACTLY the site cap — a value expr whose
                    # output tier is below cap would otherwise make
                    # iteration-0 accums smaller than the body's
                    # concat+shrink output (while_loop type mismatch).
                    d = d.with_capacity(cap)
                ovf[("lr", site, i)] = so
                # Rebrand to the DECLARED binding schema (value exprs may
                # produce equivalent columns under different names).
                deltas.append(
                    d.replace(schema=schemas[i]).canonicalize_nulls()
                )
            return canon_states(states_l), deltas, ovf

        # Iteration 0: real inputs, empty binding deltas.
        it0_inputs = dict(inputs)
        for nm, sch in zip(names, schemas):
            it0_inputs[nm] = Batch.empty(sch, cap)
        states_l, deltas, ovf, errs0 = run_values(
            list(states), it0_inputs
        )
        accums = list(deltas)

        ovf_keys = sorted(ovf.keys())

        def pack(o):
            if not ovf_keys:
                return jnp.zeros((0,), jnp.bool_)
            return jnp.stack(
                [jnp.asarray(o[k]).astype(jnp.bool_).reshape(()) for k in ovf_keys]
            )

        empty_inputs = {
            k: b.replace(count=jnp.zeros_like(b.count))
            for k, b in inputs.items()
        }

        def cond(carry):
            _, deltas_c, _, it, _, _ = carry
            pending = jnp.asarray(0, jnp.int32)
            for d in deltas_c:
                pending = pending + d.count.reshape(()).astype(jnp.int32)
            if ctx.sharded:
                pending = jax.lax.psum(pending, ctx.axis_name)
            return jnp.logical_and(it < max_iters, pending > 0)

        def body(carry):
            states_c, deltas_c, accums_c, it, ovf_c, errs_c = carry
            it_inputs = dict(empty_inputs)
            for nm, d in zip(names, deltas_c):
                it_inputs[nm] = d
            states_n, new_deltas, o, errs_n = run_values(
                list(states_c), it_inputs
            )
            new_accums = []
            for i, (a, d) in enumerate(zip(accums_c, new_deltas)):
                m = consolidate(
                    concat_batches([a, d]), include_time=False
                )
                m, so = shrink(m, cap)
                o[("lr", site, i)] = jnp.logical_or(o[("lr", site, i)], so)
                new_accums.append(m.canonicalize_nulls())
            assert sorted(o.keys()) == ovf_keys, "ovf keys drifted"
            return (
                tuple(states_n),
                tuple(new_deltas),
                tuple(new_accums),
                it + 1,
                jnp.logical_or(ovf_c, pack(o)),
                errs_c + errs_n,
            )

        carry0 = (
            tuple(states_l),
            tuple(deltas),
            tuple(accums),
            jnp.asarray(1, jnp.int32),
            pack(ovf),
            errs0,
        )
        states_f, _, accums_f, _, ovf_f, errs_f = jax.lax.while_loop(
            cond, body, carry0
        )
        # Surface the fixpoint's accumulated per-code error counts into
        # the OUTER step's err collection (zero-diff rows consolidate
        # away downstream).
        from ..expr import errors as _errors
        from ..repr.schema import ERR_SCHEMA

        if _errors.step_active():
            _errors.push_step(
                Batch(
                    cols=(
                        jnp.arange(_N_ERR_CODES, dtype=jnp.int64),
                    ),
                    nulls=(None,),
                    time=jnp.full(
                        _N_ERR_CODES, time, dtype=jnp.uint64
                    ),
                    diff=errs_f,
                    count=jnp.asarray(_N_ERR_CODES, jnp.int32),
                    schema=ERR_SCHEMA,
                )
            )

        # Body consumes real inputs + the per-step total binding deltas.
        body_inputs = dict(inputs)
        for nm, a in zip(names, accums_f):
            body_inputs[nm] = a
        states_l = list(states_f)
        out, upd_b, ovf_b = body_fn(states_l, body_inputs, time)

        upd = {i: s for i, s in enumerate(states_l)}
        upd.update(upd_b)
        ovf_out = {k: ovf_f[i] for i, k in enumerate(ovf_keys)}
        ovf_out.update(ovf_b)
        return out, upd, ovf_out

    return run



def _scalar_col_refs(e, out: set) -> None:
    from ..expr import scalar as ms

    if isinstance(e, ms.ColumnRef):
        out.add(e.index)
        return
    for f in getattr(e, "__dataclass_fields__", {}):
        v = getattr(e, f)
        if isinstance(v, ms.ScalarExpr):
            _scalar_col_refs(v, out)
        elif isinstance(v, tuple):
            for x in v:
                if isinstance(x, ms.ScalarExpr):
                    _scalar_col_refs(x, out)


def _resolve_basic_sites(expr: mir.RelationExpr, ctx) -> list:
    """Resolve basic-aggregate Reduce sites against the dataflow's
    top-level expression.

    A basic aggregate's device column is an opaque digest; its real
    (variable-width) value exists only at the serving edge. The digest
    may flow to the output through Project/Map/Filter layers that do
    not COMPUTE on it; anything else would leak digests into real
    operators, so it raises. Returns
    [(output col, state slot, state part, AggregateExpr, value Column,
      key_out_cols)] where key_out_cols maps each group-key column to
    its position in the OUTPUT schema (None if any key column was
    projected away — finalization then falls back to digest-only
    lookup).
    """
    if not ctx.basic_sites:
        return []
    chain = []
    node = expr
    while isinstance(node, (mir.Project, mir.Map, mir.Filter)):
        chain.append(node)
        node = node.input
    sites = {nid: (slot, op) for (nid, slot, op) in ctx.basic_sites}
    finalizers: list = []
    if id(node) in sites:
        slot, op = sites.pop(id(node))
        pos: dict = {}
        # Track the group-key columns through the chain too: when they
        # all survive to the output, finalization keys its lookup by
        # group key (digest demoted to a consistency check) — a 64-bit
        # digest collision between two groups then raises instead of
        # silently serving one group's result for the other.
        keypos: dict = {k: k for k in range(op.n_key)}
        for b, (j, agg) in enumerate(op.basic_aggs):
            part = 1 + len(op.hier_aggs) + b
            vcol = agg.expr.typ(op.input_schema)
            pos[op.n_key + j] = (slot, part, agg, vcol)
        for layer in reversed(chain):
            if isinstance(layer, (mir.Map, mir.Filter)):
                exprs = (
                    layer.scalars
                    if isinstance(layer, mir.Map)
                    else layer.predicates
                )
                refs: set = set()
                for e in exprs:
                    _scalar_col_refs(e, refs)
                if refs & set(pos):
                    raise NotImplementedError(
                        "string_agg/array_agg/list_agg results cannot "
                        "feed scalar expressions or filters: the "
                        "maintained device column is a digest, "
                        "finalized only at the serving edge"
                    )
            else:  # Project
                pos = {
                    o: pos[srcidx]
                    for o, srcidx in enumerate(layer.outputs)
                    if srcidx in pos
                }
                inv = {}
                for o, srcidx in enumerate(layer.outputs):
                    for k, p in keypos.items():
                        if p == srcidx and k not in inv:
                            inv[k] = o
                keypos = inv
        key_out = (
            tuple(keypos[k] for k in range(op.n_key))
            if len(keypos) == op.n_key
            else None
        )
        finalizers = [(o, *v, key_out) for o, v in pos.items()]
    if sites:
        raise NotImplementedError(
            "string_agg/array_agg/list_agg must sit at the dataflow "
            "output (optionally under Project/Map/Filter); composing "
            "them into joins, further reduces, or other operators is "
            "not supported"
        )
    return finalizers


def _finalize_basic_value(agg, vcol, values, vnulls, mults, gdict) -> str:
    """Materialize one group's basic-aggregate result from its sorted
    multiset (host side). ``vnulls`` marks NULL elements (array_agg/
    list_agg preserve them; rendered as pg's array NULL literal).
    ``gdict`` is the caller's epoch-coherent dictionary snapshot."""
    from ..expr.relation import AggregateFunc
    from ..repr.schema import ColumnType

    def render(v) -> str:
        if vcol.ctype is ColumnType.STRING:
            return gdict.decode(int(v))
        if vcol.ctype is ColumnType.BOOL:
            return "t" if v else "f"
        if vcol.ctype is ColumnType.DECIMAL and vcol.scale:
            q = 10 ** vcol.scale
            sign = "-" if v < 0 else ""
            v = abs(int(v))
            return f"{sign}{v // q}.{v % q:0{vcol.scale}d}"
        if vcol.ctype is ColumnType.DATE:
            from ..repr.schema import days_to_date

            return str(days_to_date(v))
        if vcol.ctype is ColumnType.TIMESTAMP:
            from ..repr.schema import ms_to_ts

            return str(ms_to_ts(v))
        return str(int(v))

    parts: list = []
    for i, (v, m) in enumerate(zip(values, mults)):
        s = (
            "NULL"
            if vnulls is not None and bool(vnulls[i])
            else render(v)
        )
        parts.extend([s] * int(m))
    if agg.func is AggregateFunc.STRING_AGG:
        sep = agg.params[0] if agg.params else ""
        return sep.join(parts)
    return "{" + ",".join(parts) + "}"



class _DataflowBase:
    """Shared host-side machinery: pipelined stepping, overflow-driven
    capacity growth with rollback/replay, peeks.

    The output arrangement (the index export: TraceManager arrangement,
    render.rs:357) lives ON DEVICE as part of the step state; per-step
    host traffic is one packed overflow-flag readback, checked once per
    pipelined run (a device->host readback blocks on everything queued
    before it, so the hot loop never reads data back)."""

    def _init_output(
        self, capacity: int = 256, levels: int = 2, slots: int = 0
    ):
        from ..repr.schema import ERR_SCHEMA

        out_key = tuple(range(self.out_schema.arity))
        # The output index is a two-run Spine: per-step inserts touch
        # only the tail; scheduled compactions fold the tail into the
        # base (so an index over a 2^20-row collection costs O(tail)
        # per step, not O(state)). HASH order: the output index serves
        # consolidation and full scans, never in-range value order, so
        # it rides the 2-lane hash order that keeps state-scale merges
        # sort-free and search-cheap (spine.py order modes).
        self.output = Spine.empty(
            self.out_schema, out_key, capacity,
            tail_capacity=self._ctx.out_delta_cap,
            order="hash",
            levels=levels,
            ingest_slots=slots,
        )
        # The err collection: scalar-evaluation errors maintained next
        # to the data output (ok/err pair, render.rs:12-101). Reads
        # consult it first; deleting the offending row retracts the
        # error.
        self.err_output = Arrangement.empty(ERR_SCHEMA, (0,), 256)
        self._ovf_keys: list = []
        # Device-resident logical time: created once, then carried as a
        # step output -> next step input. Feeding time from the host
        # would cost one h2d transfer per step (the dominant per-step
        # cost in round 1 on an installation that is gone; not
        # measured here).
        self._time_dev = None
        # Deferred-overflow-check bookkeeping (see run_steps/check_flags).
        # Flags accumulate as a running ON-DEVICE logical_or — one tiny
        # array regardless of how many steps are deferred. (Keeping a
        # per-step list and stacking at check time built a program with
        # one operand PER DEFERRED STEP; at ~500 steps that program took
        # tens of minutes to build and run.)
        self._defer_ck = None
        self._defer_log: list = []
        self._defer_flags = None
        self._defer_cflags = None
        # Donation bookkeeping for the CURRENT defer window (ISSUE 8):
        # which carry parts ride donated dispatches (the provenance
        # prover's unsound-donation check reads this), and whether the
        # window checkpoint is a fresh-buffer clone (a donated window
        # with a plain reference checkpoint would resurrect dead
        # buffers on rollback).
        self._defer_donated: tuple = ()
        self._defer_ck_cloned = False
        # Spine-compaction schedule (differential's geometric spine-
        # merge budget): every `_compact_every` steps, fold level 0 of
        # every spine into level 1; every `_compact_every *
        # _compact_ratio^l` steps, also fold level l. Deterministic —
        # driven by a host tick counter that is part of the rollback
        # checkpoint, so overflow replays reproduce the same schedule.
        self._compact_every = 8
        self._compact_ratio = 8
        self._compact_tick = 0
        self._compact_jits: dict = {}
        self._covf_keys = self._compact_keys()
        # d2h readback census: every flags transfer increments it (a
        # committed span costs one). And the barrier of the
        # MaintainedView stepping this dataflow, if any: reads of
        # dataflow state commit that view's in-flight span first.
        self._readbacks = 0
        self._span_barrier = None
        # What presize_for_snapshot grew, until release_snapshot_tiers:
        # (slot, part) -> the ingest tier's capacity before the hop,
        # join site -> its tier before
        self._presized: dict = {}
        self._presized_sites: dict = {}

    # Back-compat shim for callers that poked the old counter directly.
    @property
    def _steps_since_compact(self) -> int:
        return self._compact_tick % self._compact_every

    def _compact_keys(self) -> list:
        """Overflow-flag keys of the compact program (per-target-run
        growth across every spine level), in the deterministic order
        every compact variant packs them (variants that do not touch a
        level pack False for it — flag shape is uniform). A slotted
        spine's level-0 flush targets run 0, so its keys start at run
        index 0; slotless spines' first target is run 1."""
        from ..arrangement.spine import compact_depth

        keys = []
        for slot, parts in enumerate(self.states):
            for p, s in enumerate(parts):
                if isinstance(s, Spine):
                    first = 0 if s.slots else 1
                    for lvl in range(compact_depth(s)):
                        keys.append(("state", slot, (p, first + lvl)))
        first = 0 if self.output.slots else 1
        for lvl in range(compact_depth(self.output)):
            keys.append(("out", first + lvl))
        return keys

    def _due_levels(self, tick: int) -> int:
        """Highest spine level due for folding at compaction tick
        `tick` (tick counts steps; called when tick %
        _compact_every == 0). Level l folds every
        _compact_every * _compact_ratio^l steps."""
        lvl = 0
        period = self._compact_every * self._compact_ratio
        while tick % period == 0:
            lvl += 1
            period *= self._compact_ratio
        return lvl

    def _pack_flags(self, ovf: dict) -> jnp.ndarray:
        """Deterministically order overflow flags into one tiny array.
        Captures the key order at trace time (the dict's keys are a
        static property of the rendered plan)."""
        keys = sorted(ovf.keys())
        self._ovf_keys = keys
        if not keys:
            return jnp.zeros((0,), jnp.bool_)
        return jnp.stack(
            [jnp.asarray(ovf[k]).astype(jnp.bool_).reshape(()) for k in keys]
        )

    def delta_pipelines_fed(self, ticks) -> tuple:
        """``(dispatched, fed)`` over one input dict a tick: the
        delta-join pipelines each step dispatches, and of those the
        ones one of whose inputs had rows in that tick. Row counts are
        the host's (``Batch._host_count``); a batch without one counts
        as holding rows. No device read."""
        pipes = self._ctx.delta_pipelines
        fed = 0
        for inputs in ticks:
            had = {
                name for name, b in inputs.items()
                if getattr(b, "_host_count", 1)
            }
            fed += sum(1 for p in pipes if p & had)
        return len(pipes) * len(ticks), fed

    # state_capacity_bytes()'s value, until a tier is regrown
    _reserved_bytes: int | None = None

    def state_capacity_bytes(self) -> int:
        """Bytes of device memory the operator state and the output
        spine RESERVE: capacities x stored row widths (columns, time,
        diff, cached sort lanes), whatever the live row counts. Shapes
        off the avals, never a device read (safe at span close,
        analysis/host_sync.py); known at render and kept until
        ``_grow_for`` regrows a tier."""
        if self._reserved_bytes is None:
            self._reserved_bytes = device_nbytes((self.states, self.output))
        return self._reserved_bytes

    def presize_for_snapshot(self, rows: dict) -> int:
        """Before the one step that hydrates a snapshot: every join
        arrangement that ONE input feeds through stateless operators
        (``source_fed``) goes, in one hop, to the tier that holds all
        ``rows[input]`` rows: the ingest tier the step's batch passes
        through and every run it is folded into (ROADMAP A2). The
        doubling ladder would get there one overflow and one compile
        of the hydration-size step program at a time; this bound is at
        most the rows the filters in between remove too large, and
        depends on the snapshot's size alone, so every run of a
        deployment compiles the same programs. A tier that holds its
        input already is left as it is.

        What such an arrangement's rows match at their join site has
        no bound in the inputs' sizes. The site's tier starts where
        every snapshot-size delta is cut before it meets a sort
        (``out_delta_cap``), if the input is that large, and keeps the
        ladder from there; as do the states that a join or a reduce
        feeds. An input that reaches the join through no filter starts
        the site at its whole snapshot's tier instead: a probe that
        matches each of its rows on a foreign key (TPC-H Q9's nation ->
        supplier -> lineitem) puts all of them there in the hydration
        step, five doublings and five compiles up a ladder from
        ``out_delta_cap``.

        How many arrangements were grown."""
        from ..plan.decisions import quantize_cap

        grown = 0
        for (slot, part), (name, jsite) in sorted(
            self._ctx.source_fed.items()
        ):
            if name not in rows:
                continue
            target = quantize_cap(rows[name])
            spine = self.states[slot][part]
            # the ingest tier (the slot ring, else run 0), then every
            # run a fold targets
            runs = list(enumerate(spine.runs_b))
            tiers = [("tail", (spine.slots or spine.runs_b)[0])] + (
                runs if spine.slots else runs[1:]
            )
            small = [w for w, b in tiers if b.capacity < target]
            if "tail" in small:
                # release_snapshot_tiers gives the ingest tier back
                self._presized[(slot, part)] = tiers[0][1].capacity
            for which in small:
                self._grow_for(("state", slot, (part, which)), target)
            grown += bool(small)
            self._ctx.sort_in_blocks |= bool(small)
            probe = (
                target if (slot, part) in self._ctx.unfiltered_fed
                else min(target, self._ctx.out_delta_cap)
            )
            if self._ctx.join_caps[jsite] < probe:
                self._presized_sites.setdefault(
                    jsite, self._ctx.join_caps[jsite]
                )
                self._grow_for(("join", jsite), probe)
        if grown:
            self._remake_jit()  # the programs' key says how they sort
        return grown

    def release_snapshot_tiers(self) -> dict:
        """After the one hydration step has committed and
        ``_compact_now`` has folded every run into its base: what
        ``presize_for_snapshot`` grew for the snapshot's sake goes
        back to what a tick needs, and the programs are remade for it.

        Each ingest tier it grew is EMPTY reserved capacity at the
        snapshot's tier now, and a merge-ingest tick would search,
        gather and rewrite all of it to absorb one batch. The runs a
        fold targets keep the snapshot tier: they hold the rows. No
        row moves. The size is derived: a ring slot holds one tick's
        batch, run 0 every batch between two folds
        (``_compact_every``), a batch being the smallest tier a source
        hands a tick; never below the tier the spine was rendered
        with, never above what presizing gave it. A tier that still
        holds rows is left as it is.

        Each join site it grew pads every probe's output, and all
        that the outputs feed, to the snapshot's matches: it goes back
        to the tier it was rendered with.

        A source whose ticks outgrow either overflows it and the
        ladder doubles it, as it would have from the render's tiers.

        What was released: arrangements, their rows and bytes, join
        sites."""
        from ..plan.decisions import quantize_cap

        before = self.state_capacity_bytes()
        shards = getattr(self, "num_shards", 1)  # capacities are global
        spines = {
            (slot, part): self.states[slot][part]
            for slot, part in sorted(self._presized)
        }
        # one small read: hydration is off the hot path and the
        # caller's result_batch() has synchronised already
        counts = jax.device_get(
            {
                at: [b.count for b in sp.slots or sp.runs_b[:1]]
                for at, sp in spines.items()
            }
        )
        arrangements = rows = 0
        for (slot, part), spine in spines.items():
            ticks = 1 if spine.slots else self._compact_every
            have = (spine.slots or spine.runs_b)[0].capacity
            cap = min(
                max(
                    quantize_cap(ticks * capacity_tier(1)) * shards,
                    self._presized[slot, part],
                ),
                have,
            )
            if cap == have or any(
                np.any(c) for c in counts[slot, part]
            ):
                continue
            parts = list(self.states[slot])
            parts[part] = self._release_spine(spine, cap)
            self.states[slot] = tuple(parts)
            arrangements += 1
            rows += (have - cap) * max(len(spine.slots), 1)
        for jsite, rendered in self._presized_sites.items():
            self._ctx.join_caps[jsite] = rendered
        sites = len(self._presized_sites)
        self._presized, self._presized_sites = {}, {}
        if arrangements or sites:
            self._reserved_bytes = None
            self._remake_jit()  # the tick's programs, for its tiers
        return {
            "arrangements": arrangements,
            "rows_released": rows,
            "bytes_released": before - self.state_capacity_bytes(),
            "join_sites": sites,
        }

    def _static_tiers(self) -> str:
        """The capacity tiers this dataflow's programs bake in at trace
        time (grown by ``_grow_for`` + ``_remake_jit``, visible in no
        argument shape): part of every program's ledger/bank key."""
        c = self._ctx
        return repr(
            (c.join_caps, c.slot_cap, c.letrec_caps, c.out_delta_cap)
            + (("sort_in_blocks",) if c.sort_in_blocks else ())
        )

    def _grow_for(self, key, target: int | None = None) -> None:
        """Grow the capacity tier behind an overflowed key — one
        doubling by default, or straight to ``target`` in a single pad
        (presize_for_snapshot, which knows the size, skips the
        doubling ladder, whose every rung costs a compile and a
        dispatch). Explicit targets snap to
        the pow2 quantization menu (ISSUE 16) so presized tiers
        land on bankable program keys; doubling from a quantized base
        stays on the menu by construction."""
        if target is not None:
            from ..plan.decisions import quantize_cap

            target = quantize_cap(target)
        else:
            # An overflow flag came back from the device: the step or
            # span is rolled back and replayed against the doubled
            # tier (a new compile). Counted per regrown tier.
            from ..utils.metrics import REGISTRY

            REGISTRY.get_or_create(
                "counter", "mz_overflow_regrows_total",
                "capacity tiers doubled after a device overflow flag "
                "(each rolls back and replays the step or span)",
            ).inc()
        self._reserved_bytes = None
        if key[0] == "state":
            _, slot, part = key
            parts = list(self.states[slot])
            if isinstance(part, tuple):  # spine sub-run: (part, which)
                p, which = part
                parts[p] = self._grow_spine(parts[p], which, target)
            else:
                parts[part] = self._grow_arrangement(parts[part], target)
            self.states[slot] = tuple(parts)
        elif key[0] == "out":
            self.output = self._grow_spine(self.output, key[1], target)
        elif key[0] == "join":
            self._ctx.join_caps[key[1]] = (
                target or self._ctx.join_caps[key[1]] * 2
            )
            self._remake_jit()
        elif key[0] == "x":
            self._ctx.slot_cap *= 2
            self._remake_jit()
        elif key[0] == "lr":
            self._ctx.letrec_caps[key[1]] *= 2
            self._remake_jit()
        elif key[0] == "outd":
            self._ctx.out_delta_cap *= 2
            self._remake_jit()
        elif key[0] == "errout":
            self.err_output = self._grow_arrangement(
                self.err_output, target
            )
        else:
            raise AssertionError(f"unknown overflow key {key}")

    def _grow_arrangement(
        self, arr: Arrangement, target: int | None = None
    ) -> Arrangement:
        return arr.map_batches(lambda b: self._grow_batch(b, target))

    @staticmethod
    def _pad_lanes(lanes, new_cap: int):
        """Zero-pad a cached ``[cap, L]`` lane array to a grown run
        capacity. Pad rows' lanes are garbage either way (every lane
        consumer bounds itself by the run's count), so no recompute."""
        if lanes.shape[0] >= new_cap:
            return lanes
        return (
            jnp.zeros((new_cap, lanes.shape[1]), lanes.dtype)
            .at[: lanes.shape[0]]
            .set(lanes)
        )

    def _grow_spine(
        self, spine: Spine, which, target: int | None = None
    ) -> Spine:
        """Grow one run of a spine. `which` is a run index, or the
        aliases "base" (largest run) / "tail" (the ingest tier: the
        slot ring when present, else run 0). Cached lanes are padded
        alongside their run."""
        if which == "tail" and spine.slots:
            new_slots = tuple(
                self._grow_batch(s, target) for s in spine.slots
            )
            slot_lanes = spine.slot_lanes
            if slot_lanes:
                slot_lanes = tuple(
                    self._pad_lanes(l, nb.capacity)
                    for l, nb in zip(slot_lanes, new_slots)
                )
            return Spine(
                spine.runs_b,
                spine.key,
                spine.order,
                new_slots,
                spine.cursor,
                spine.lanes,
                slot_lanes,
            )
        if which == "base":
            which = spine.levels - 1
        elif which == "tail":
            which = 0
        grown = self._grow_batch(spine.runs_b[which], target)
        lanes = None
        if spine.lanes:
            lanes = self._pad_lanes(spine.lanes[which], grown.capacity)
        return spine.with_run(which, grown, lanes)

    def _release_spine(self, spine: Spine, cap: int) -> Spine:
        """``_grow_spine(spine, "tail", ...)`` undone: the ingest tier
        (the slot ring when present, else run 0) as its own first
        ``cap`` rows, cached lanes alongside. The tier must be EMPTY
        (release_snapshot_tiers reads the counts): structure, dtypes
        and null-mask layout stay the run's own."""
        if spine.slots:
            return Spine(
                spine.runs_b,
                spine.key,
                spine.order,
                tuple(self._release_batch(s, cap) for s in spine.slots),
                spine.cursor,
                spine.lanes,
                tuple(self._first_rows(l, cap) for l in spine.slot_lanes),
            )
        lanes = None
        if spine.lanes:
            lanes = self._first_rows(spine.lanes[0], cap)
        return spine.with_run(
            0, self._release_batch(spine.runs_b[0], cap), lanes
        )

    def _release_batch(self, b: Batch, cap: int) -> Batch:
        """An EMPTY batch cut to capacity ``cap``. Not
        ``Batch.with_capacity``, which rightly refuses to shrink a
        batch whose count is traced: here the caller has read the
        count on the host, and it is zero."""
        return b.replace(
            cols=tuple(self._first_rows(c, cap) for c in b.cols),
            nulls=tuple(
                None if n is None else self._first_rows(n, cap)
                for n in b.nulls
            ),
            time=self._first_rows(b.time, cap),
            diff=self._first_rows(b.diff, cap),
        )

    def _check_slot_ring(self) -> None:
        """The append-slot ring must hold every insert between level-0
        flushes: a ring smaller than _compact_every would silently
        overwrite unflushed slots (the cursor wraps; no overflow flag
        can catch it)."""
        for sp in [self.output] + [
            s
            for parts in self.states
            for s in parts
            if isinstance(s, Spine)
        ]:
            if sp.slots and len(sp.slots) < self._compact_every:
                raise ValueError(
                    f"ingest slot ring ({len(sp.slots)}) smaller than "
                    f"compact_every ({self._compact_every}): inserts "
                    "would overwrite unflushed slots"
                )

    def step(self, inputs: dict) -> Batch:
        """Feed one micro-batch of updates per source; returns the output
        delta (device-resident) and advances the frontier."""
        return self.run_steps([inputs])[-1]

    def gather_delta(self, out: Batch) -> Batch:
        """Host view of a step's output delta. Single-device dataflows
        are already host-readable; ShardedDataflow overrides this to
        gather per-worker shards. Callers (MaintainedView) use this
        uniformly instead of duck-typing on the dataflow class."""
        return out

    @property
    def time(self) -> int:
        """Host mirror of the dataflow frontier (all steps < time are
        complete)."""
        return self._time

    @time.setter
    def time(self, v: int) -> None:
        # External time assignment (e.g. MaintainedView aligning the
        # dataflow to a shard as_of) must invalidate the device-resident
        # time carry, or steps would run at a stale timestamp. The hot
        # loop (_dispatch_span) advances self._time directly so the
        # carry survives normal stepping.
        self._time = v
        if getattr(self, "_time_dev", None) is not None:
            self._time_dev = None

    def _apply_err_delta(self, err_output, err_parts, ovf: dict):
        """Fold a step's collected error batches into the err
        arrangement (shared by single-device and sharded step bodies).
        Returns the new err arrangement; mutates ovf and records the
        trace-time fact of whether this dataflow CAN produce errors
        (peek_errors shortcuts when it can't)."""
        self._has_errors = bool(err_parts)
        if not err_parts:
            return err_output
        errs = consolidate(
            concat_batches(err_parts), include_time=False
        )
        errs, err_shrink = shrink(errs, 2048)
        new_err, err_ovf = insert(
            err_output, errs, out_capacity=err_output.capacity
        )
        ovf[("errout",)] = jnp.logical_or(err_shrink, err_ovf)
        return new_err

    def _accumulate_errors(self, rows) -> list[tuple]:
        acc: dict = {}
        for r in rows:
            acc[r[0]] = acc.get(r[0], 0) + r[-1]
        return sorted((c, n) for c, n in acc.items() if n != 0)

    # -- basic-aggregate edge finalization ---------------------------------
    # Shared by single-device and sharded dataflows (sharded overrides
    # _basic_multiset_host with a per-worker gather — the reduce input
    # exchange keys groups to one worker, so shards concatenate into a
    # group-contiguous multiset). render/reduce.rs:369 analog.

    def _basic_multiset_host(self, arr) -> dict:
        """Host view of one basic-aggregate multiset arrangement."""
        b = arr.batch
        n = int(b.count)
        return {
            "n": n,
            "cols": [np.asarray(c)[:n] for c in b.cols],
            "nulls": [
                None if x is None else np.asarray(x)[:n]
                for x in b.nulls
            ],
            "diff": np.asarray(b.diff)[:n],
        }

    def capture_basic_multisets(self) -> dict:
        """Pre-step host snapshot of every basic multiset part: the
        persist-sink delta path finalizes RETRACTION rows against the
        state their digests describe (the post-step multiset no longer
        holds it)."""
        out: dict = {}
        for fi, (_oc, slot, part, *_rest) in enumerate(
            self._basic_finalizers
        ):
            out[fi] = self._basic_multiset_host(
                self.states[slot][part]
            )
        return out

    def _basic_group_maps(self, multisets: dict | None = None) -> list:
        """Per-finalizer (by_digest, by_key) result-lookup maps built
        from the multiset state (or from pre-captured host views)."""
        from ..ops.reduce import _NULL_DIGEST, _mix64_host
        from ..repr.schema import GLOBAL_DICT

        gdict = GLOBAL_DICT.snapshot()
        maps: list = []
        for fi, (
            out_col, slot, part, agg, vcol, key_out
        ) in enumerate(self._basic_finalizers):
            arr = self.states[slot][part]
            b = (
                multisets[fi]
                if multisets is not None
                else self._basic_multiset_host(arr)
            )
            bcols, bnulls, diffs = b["cols"], b["nulls"], b["diff"]
            keep = diffs != 0
            n_key = len(arr.key)
            vals = bcols[n_key][keep].astype(np.int64)
            vnl = bnulls[n_key]
            vnl = vnl[keep] if vnl is not None else None
            mult = diffs[keep]
            by_digest: dict = {}
            by_key: dict = {}
            if len(vals):
                # Masked key columns, computed ONCE (the per-group loop
                # below only indexes them — re-masking per group made
                # finalization O(groups * rows)).
                kcols = [bcols[ki][keep] for ki in range(n_key)]
                knulls = [
                    None if bnulls[ki] is None else bnulls[ki][keep]
                    for ki in range(n_key)
                ]
                # Group boundaries: multiset rows sort by (key, value)
                # with NULL keys canonicalized first, so groups are
                # contiguous; compare raw values gated on null flags.
                change = np.zeros(len(vals), dtype=bool)
                change[0] = True
                for kc, nl in zip(kcols, knulls):
                    if nl is None:
                        change[1:] |= kc[1:] != kc[:-1]
                    else:
                        both = ~nl[1:] & ~nl[:-1]
                        change[1:] |= (nl[1:] != nl[:-1]) | (
                            both & (kc[1:] != kc[:-1])
                        )
                starts = np.flatnonzero(change)
                ends = np.append(starts[1:], len(vals))
                m = _mix64_host(vals).astype(np.uint64)
                if vnl is not None:
                    m = np.where(
                        vnl,
                        np.uint64(np.int64(_NULL_DIGEST)),
                        m,
                    )
                m = m * mult.astype(np.uint64)
                for s0, e0 in zip(starts, ends):
                    dig = int(
                        m[s0:e0].sum(dtype=np.uint64).astype(np.int64)
                    )
                    res = _finalize_basic_value(
                        agg, vcol, vals[s0:e0],
                        vnl[s0:e0] if vnl is not None else None,
                        mult[s0:e0], gdict,
                    )
                    by_digest[dig] = res
                    if key_out is not None:
                        kt = tuple(
                            None
                            if knulls[ki] is not None
                            and bool(knulls[ki][s0])
                            else kcols[ki][s0].item()
                            for ki in range(n_key)
                        )
                        by_key[kt] = (dig, res)
            maps.append((by_digest, by_key))
        return maps

    def finalize_basic_columns(
        self, cols, nulls, diffs=None, old_multisets=None
    ) -> list:
        """Edge finalization of basic aggregates: replace each digest
        value in the host output columns with the group's materialized
        result STRING (object-dtype column; decode_result_rows passes
        pre-decoded columns through — results never round-trip the
        global dictionary, which peeks under churn would otherwise grow
        without bound), computed from the maintained (key, value)
        multiset state.

        When every group-key column survives to the output, the lookup
        is keyed by group key with the digest as a consistency check (a
        64-bit digest collision between groups raises instead of
        serving the wrong group's result); digest-only lookup is the
        fallback for outputs that project keys away.

        With ``diffs`` + ``old_multisets`` (the persist-sink delta
        path), RETRACTION rows (diff < 0) resolve against the pre-step
        maps — their digests describe group states the current multiset
        no longer holds."""
        if not self._basic_finalizers:
            return list(cols)
        new_maps = self._basic_group_maps()
        old_maps = (
            self._basic_group_maps(old_multisets)
            if old_multisets is not None
            else None
        )
        cols = list(cols)
        for fi, (
            out_col, slot, part, agg, vcol, key_out
        ) in enumerate(self._basic_finalizers):
            src = np.asarray(cols[out_col])
            out = np.empty(len(src), dtype=object)
            nl = nulls[out_col] if nulls else None
            key_src = (
                [np.asarray(cols[ko]) for ko in key_out]
                if key_out is not None
                else None
            )
            for i in range(len(src)):
                if nl is not None and nl[i]:
                    out[i] = None
                    continue
                retract = (
                    diffs is not None
                    and old_maps is not None
                    and diffs[i] < 0
                )
                by_digest, by_key = (
                    old_maps[fi] if retract else new_maps[fi]
                )
                d = int(src[i])
                if key_out is not None:
                    kt = tuple(
                        None
                        if nulls[ko] is not None and bool(nulls[ko][i])
                        else key_src[kk][i].item()
                        for kk, ko in enumerate(key_out)
                    )
                    hit = by_key.get(kt)
                    if hit is None:
                        raise RuntimeError(
                            "basic-aggregate group has no multiset "
                            "entry (state divergence)"
                        )
                    dig, res = hit
                    if dig != d:
                        raise RuntimeError(
                            "basic-aggregate digest mismatch for group "
                            f"{kt!r} (digest/multiset divergence)"
                        )
                    out[i] = res
                else:
                    if d not in by_digest:
                        raise RuntimeError(
                            "basic-aggregate digest has no multiset "
                            "group (digest/multiset divergence)"
                        )
                    out[i] = by_digest[d]
            cols[out_col] = out
        return cols

    def _build_env(self):
        if getattr(self, "_str_keys", None):
            # dictionary side-tables for string functions: built once
            # per span (inputs are already encoded, so the dictionary
            # is stable across the span's steps)
            from ..expr import strings

            return strings.build_env(
                self._str_keys, getattr(self, "_str_depth", 1)
            )
        return None

    def _checkpoint(self):
        return (
            list(self.states),
            self.output,
            self.err_output,
            self.time,
            self._time_dev,
            self._compact_tick,
        )

    def _restore(self, ck):
        (
            self.states,
            self.output,
            self.err_output,
            self.time,
            self._time_dev,
            self._compact_tick,
        ) = ck

    def _dispatch_compact(self, max_level: int = 10**9):
        """Dispatch one spine-compaction program folding levels
        [0, max_level] of every spine (clamped per spine; the default
        is a full cascade). Async like steps; returns its packed
        per-target-run overflow flags (key order: self._covf_keys —
        uniform across variants; untouched levels pack False)."""
        from ..utils.lockcheck import device_dispatch

        device_dispatch("_dispatch_compact")
        jitfn = self._compact_jits.get(max_level)
        if jitfn is None:
            jitfn = self._make_compact_jit(max_level)
            self._compact_jits[max_level] = jitfn
        new_states, new_output, cfl = jitfn(
            tuple(self.states), self.output
        )
        self.states = list(new_states)
        self.output = new_output
        return cfl

    def _compact_core_single(self, states, output, max_level: int = 10**9):
        """Trace body of the compact program (single-device layout).
        Walks the static state layout; only Spine parts are touched —
        levels [0, max_level] of each (clamped to the spine's depth)."""
        from ..arrangement.spine import compact_depth

        flags = {}
        new_states = []
        for slot, parts in enumerate(states):
            ps = list(parts)
            for p, s in enumerate(ps):
                if isinstance(s, Spine):
                    sp = s
                    first = 0 if sp.slots else 1
                    for lvl in range(
                        min(max_level + 1, compact_depth(sp))
                    ):
                        sp, ovf = compact_level(sp, lvl)
                        flags[("state", slot, (p, first + lvl))] = ovf
                    ps[p] = sp
            new_states.append(tuple(ps))
        new_out = output
        first = 0 if output.slots else 1
        for lvl in range(min(max_level + 1, compact_depth(output))):
            new_out, ovf = compact_level(new_out, lvl)
            flags[("out", first + lvl)] = ovf
        packed = jnp.stack(
            [
                jnp.asarray(
                    flags.get(k, jnp.asarray(False))
                ).astype(jnp.bool_).reshape(())
                for k in self._covf_keys
            ]
        )
        return tuple(new_states), new_out, packed

    @staticmethod
    def _or_acc(acc, fl):
        """Fold one packed flag array into the running on-device OR."""
        if acc is None:
            return fl
        return jnp.logical_or(acc, fl)

    def _dispatch_span(self, packed: list, env, donate: tuple = ()):
        """Asynchronously dispatch one step per packed input, plus the
        scheduled spine compactions. ZERO host transfers: time rides as
        a device scalar (created once per dataflow), overflow flags
        accumulate as a running on-device logical_or for the caller to
        check. Returns (deltas, step-flag OR, compaction-flag OR).

        ``donate`` names the carry parts handed to the step program's
        ``donate_argnums`` (the prover-approved subset): each step then
        writes its output carry into the previous step's buffers
        instead of allocating state-sized arrays per dispatch. The
        killed leaves are recorded in the sanitizer ledger — dead the
        moment the dispatch returns."""
        from ..utils.lockcheck import device_dispatch

        device_dispatch("_dispatch_span")
        if self._time_dev is None:
            self._time_dev = jnp.asarray(self.time, dtype=jnp.uint64)
        step_fn = self._step_jit
        record = None
        if donate:
            from ..analysis.donation import (
                LEDGER,
                STEP_ARGNUM as part_arg,
                sanitizer_enabled,
            )

            if _donation_supported():
                step_fn = self._donated_step_program(tuple(donate))
            # Resolve the sanitizer ONCE per dispatch train: with it
            # off (the production default) the hot loop must not pay
            # per-tick ledger-argument construction or a dyncfg-lock
            # read. The contract still holds on any backend when on.
            record = LEDGER.record if sanitizer_enabled() else None
        deltas, flags_or, cflags_or = [], None, None
        programs = 0
        with TRACER.phase("span.dispatch") as ph:
            for p in packed:
                args = (
                    tuple(self.states),
                    self.output,
                    self.err_output,
                    p,
                    self._time_dev,
                )
                if env is not None:
                    out, new_states, new_output, new_err, new_t, fl = (
                        step_fn(*args, env)
                    )
                else:
                    out, new_states, new_output, new_err, new_t, fl = (
                        step_fn(*args)
                    )
                self.states = list(new_states)
                self.output = new_output
                self.err_output = new_err
                self._time_dev = new_t
                self._time += 1  # direct: keep the device carry live
                if record is not None:
                    record(
                        tuple(args[part_arg[part]] for part in donate),
                        f"{self.name}.run_steps step "
                        f"t={self._time - 1} "
                        f"(donated {','.join(donate)})",
                    )
                deltas.append(out)
                flags_or = self._or_acc(flags_or, fl)
                self._compact_tick += 1
                programs += 1
                if self._compact_tick % self._compact_every == 0:
                    programs += 1
                    cflags_or = self._or_acc(
                        cflags_or,
                        self._dispatch_compact(
                            min(
                                self._due_levels(self._compact_tick),
                                self._max_compact_level(),
                            )
                        ),
                    )
            ph.add(programs=programs)
        return deltas, flags_or, cflags_or

    def _read_flags(self, flags_or, keys: list) -> np.ndarray:
        """One tiny d2h readback of the OR-accumulated overflow flags.
        A readback blocks until every step dispatched before it has
        finished, so it serializes the pipeline: latency-critical paths
        defer it via run_steps(defer_check=True) + check_flags()."""
        if flags_or is not None and keys:
            self._readbacks += 1
            fh = np.asarray(flags_or)  # [nkeys] or [nkeys, P]
            return fh.reshape(len(keys), -1).any(axis=1)
        return np.zeros(len(keys) if keys else 0, dtype=bool)

    def _overflowed_keys(self, flags_or, cflags_or) -> list:
        """Read both flag groups (steps + compactions); returns the list
        of overflowed tier keys."""
        out = []
        for i in np.nonzero(self._read_flags(flags_or, self._ovf_keys))[0]:
            out.append(self._ovf_keys[i])
        for i in np.nonzero(
            self._read_flags(cflags_or, self._covf_keys)
        )[0]:
            out.append(self._covf_keys[i])
        return out

    def _compact_now(self) -> None:
        """Synchronously compact every spine (full cascade into the
        base): peeks and snapshots read the base run as THE
        consolidated state. Grows run tiers on overflow and retries."""
        while True:
            ck = self._checkpoint()
            cfl = self._dispatch_compact()
            self._compact_tick = 0
            over = self._read_flags(cfl, self._covf_keys)
            if not over.any():
                return
            self._restore(ck)
            for i in np.nonzero(over)[0]:
                self._grow_for(self._covf_keys[i])

    def output_batch(self) -> Batch:
        """Consolidated single-run view of the maintained output index
        (device-resident). Forces a spine compaction first — peeks are
        off the hot path (compute_state.rs:744 handle_peek reads a
        trace cursor; here the compacted base run IS the cursor)."""
        self.span_barrier()
        self.check_flags()
        self._compact_now()
        return self.output.base

    def output_records(self) -> int:
        """Approximate maintained row count (sum over all runs and
        ingest slots; may overcount rows whose diffs cancel across
        runs until the next compaction). Introspection only — one
        small d2h read. Deliberately NOT span-barriered: the replica
        reports records alongside every frontier change, and syncing
        there would serialize the span double-buffer once per loop;
        counts may include rows an in-flight span is still inserting
        (the refs are that span's OUTPUT buffers — always valid, even
        under donation)."""
        return int(
            sum(
                np.asarray(b.count).sum()
                for b in self.output.runs_b + self.output.slots
            )
        )

    def run_steps(
        self,
        inputs_list: list,
        defer_check: bool = False,
        donate=False,
    ) -> list:
        """Feed several micro-batches with deferred overflow handling:
        all steps are submitted asynchronously and the packed overflow
        flags are read once at the end of the span; on overflow the
        whole span is rolled back (states are immutable device values),
        tiers grown, and the span replayed — steps are pure, so the
        replay is idempotent. This keeps the hot loop free of per-step
        syncs.

        With ``defer_check=True`` even the end-of-span readback is
        skipped: flags are stashed on device and only read when the
        caller invokes :meth:`check_flags` (or a later synchronous
        ``run_steps``). Until then the span's inputs stay referenced so
        an overflow discovered later can still roll back and replay.

        ``donate`` hands carry parts to the step program's
        ``donate_argnums`` — True for the whole carry, or a tuple of
        part names from ``analysis.provenance.CARRY_PARTS`` (the
        prover's per-argnum verdict). The donation CONTRACT (cloned
        window checkpoint, ledger record of the killed leaves) engages
        whenever donation is REQUESTED; the argnums themselves narrow
        to backends that honor donation (_donation_supported — the one
        shared predicate). Callers must decide donation at a fresh
        defer window: a window that started with a plain-reference
        checkpoint keeps its spans un-donated (rollback would
        resurrect dead buffers otherwise).

        CAVEAT: deltas returned from a deferred span are PROVISIONAL —
        if a tier overflowed they were computed against truncated
        arrangements. Do not feed them to a sink until
        :meth:`check_flags` returns False; when it returns True, the
        corrected per-step deltas of the replay are available on
        ``self.replayed_deltas`` (in dispatch order)."""
        from ..analysis.provenance import CARRY_PARTS

        self.span_barrier()
        if getattr(self, "_first_time", None) is None:
            # The dataflow's as_of: the first processed timestamp
            # (constants fire exactly here; baked at trace time).
            self._first_time = int(self.time)
            self._ctx.first_time = self._first_time
        self._check_slot_ring()
        parts = (
            tuple(CARRY_PARTS)
            if donate is True
            else tuple(donate or ())
        )
        with TRACER.phase("span.upload") as ph:
            packed = [self._pack_inputs(i) for i in inputs_list]
            env = self._build_env()
            if ph:
                # what packing itself put on the device (a sharded
                # dataflow deals rows across workers here; a batch
                # passed through was counted where it was built)
                ph.add(
                    bytes=device_nbytes(
                        [
                            b
                            for p, i in zip(packed, inputs_list)
                            for k, b in p.items()
                            if b is not i.get(k)
                        ]
                    )
                )
        if parts:
            from ..analysis.donation import guard_read

            # Re-dispatching a donated buffer as an operand is itself
            # a use-after-donate (sanitizer-gated, no-op when off).
            guard_read(packed, f"{self.name}.run_steps operands")
        if defer_check:
            if self._defer_ck is None:
                self._defer_ck = (
                    self._clone_checkpoint()
                    if parts
                    else self._checkpoint()
                )
                self._defer_ck_cloned = bool(parts)
            elif parts and not self._defer_ck_cloned:
                # Mid-window donation flip with a plain reference
                # checkpoint: donating now could resurrect dead
                # buffers on rollback. Stay un-donated until the
                # window turns over (the view re-decides there).
                parts = ()
            if parts:
                self._defer_donated = tuple(
                    sorted(set(self._defer_donated) | set(parts))
                )
            deltas, flags_or, cflags_or = self._dispatch_span(
                packed, env, donate=parts
            )
            self._defer_log.append((packed, env))
            if flags_or is not None:
                self._defer_flags = self._or_acc(
                    self._defer_flags, flags_or
                )
            if cflags_or is not None:
                self._defer_cflags = self._or_acc(
                    self._defer_cflags, cflags_or
                )
            return deltas
        self.check_flags()
        while True:
            ck = (
                self._clone_checkpoint() if parts else self._checkpoint()
            )
            deltas, flags, cflags = self._dispatch_span(
                packed, env, donate=parts
            )
            with TRACER.phase("span.readback"):
                over = self._overflowed_keys(flags, cflags)
            if over:
                self._restore(ck)
                for k in over:
                    self._grow_for(k)
                continue
            return deltas

    def _max_compact_level(self) -> int:
        """Deepest fold index any spine in this dataflow can take."""
        from ..arrangement.spine import compact_depth

        deepest = compact_depth(self.output) - 1
        for parts in self.states:
            for s in parts:
                if isinstance(s, Spine):
                    deepest = max(deepest, compact_depth(s) - 1)
        return deepest

    def check_flags(self) -> bool:
        """Resolve deferred overflow checks: one flags readback covering
        every span dispatched with ``defer_check=True``. On overflow,
        rolls back to the pre-defer checkpoint, grows the flagged tiers,
        and replays the logged spans synchronously. Returns whether any
        overflow occurred (callers timing the deferred spans use this to
        invalidate their measurement)."""
        if self._defer_flags is None and self._defer_cflags is None:
            self._defer_ck = None
            self._defer_log = []
            self._defer_donated = ()
            self._defer_ck_cloned = False
            return False
        over = self._overflowed_keys(self._defer_flags, self._defer_cflags)
        log = self._defer_log
        ck = self._defer_ck
        self._defer_log = []
        self._defer_flags, self._defer_cflags = None, None
        self._defer_ck = None
        self._defer_donated = ()
        self._defer_ck_cloned = False
        if not over:
            return False
        self._restore(ck)
        for k in over:
            self._grow_for(k)
        # The deltas handed out during the deferred window were computed
        # against truncated state; the replay's corrected deltas are
        # published for callers that forward deltas to sinks.
        self.replayed_deltas = []
        for packed, env in log:
            while True:
                ck2 = self._checkpoint()
                deltas, flags, cflags = self._dispatch_span(packed, env)
                ovf = self._overflowed_keys(flags, cflags)
                if not ovf:
                    self.replayed_deltas.extend(deltas)
                    break
                self._restore(ck2)
                for k in ovf:
                    self._grow_for(k)
        return True

    # -- pipelined span boundaries (ISSUE 7) --------------------------------
    #
    # The double-buffered protocol of an index view's span train
    # (MaintainedView._step_span_pipelined): dispatch span K+1, THEN
    # read span K's accumulated overflow flags — the readback blocks
    # exactly until span K's program finished (all of a dispatch's
    # outputs become ready together), while span K+1 is already queued
    # behind it on device. One snapshot readback per span is the
    # span's entire d2h traffic.

    def flags_snapshot(self):
        """Reference the OR-accumulated deferred overflow flags AS OF
        NOW. Flags accumulate monotonically (logical_or), so a
        snapshot taken after dispatching span K covers every span
        <= K and nothing after — reading it is the span-boundary
        commit check."""
        return (self._defer_flags, self._defer_cflags)

    def read_flags_snapshot(self, snap) -> bool:
        """ONE fused d2h readback of a flags snapshot; True if any
        overflow occurred up to the snapshot point (the caller then
        runs :meth:`check_flags` for the rollback+replay). Blocks
        until the snapshot's producing span has finished executing —
        this is the pipelined span train's per-span sync point."""
        f, c = snap
        parts = []
        if f is not None:
            parts.append(jnp.ravel(jnp.asarray(f)).astype(jnp.uint8))
        if c is not None:
            parts.append(jnp.ravel(jnp.asarray(c)).astype(jnp.uint8))
        if not parts:
            return False
        fused = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        self._readbacks += 1
        return bool(
            np.asarray(fused).any()  # host-sync: ok(the ONE boundary readback per span)
        )

    def span_barrier(self) -> None:
        """Sequence a state read against span boundaries: when a
        MaintainedView steps this dataflow, its in-flight span's carry
        may hold donated (dead) buffers and a provisional frontier —
        complete and commit it before reading dataflow state. No-op
        without a view or from the view's own dispatch."""
        barrier = self._span_barrier
        if barrier is not None and not barrier.in_dispatch:
            barrier.sync()

    def _clone_checkpoint(self):
        """A rollback checkpoint whose device leaves are FRESH buffer
        copies — required before the first DONATED span dispatch of a
        defer window: donation hands the live carry's buffers to XLA,
        so a plain reference checkpoint would resurrect dead buffers
        on rollback."""
        from ..arrangement.spine import clone_state_tree

        st, out, err, tdev = clone_state_tree(
            (
                tuple(self.states),
                self.output,
                self.err_output,
                self._time_dev,
            )
        )
        return (list(st), out, err, self.time, tdev, self._compact_tick)


class Dataflow(_DataflowBase):
    """A maintained dataflow on one device: install once, feed update
    batches, peek.

    The host-side analog of an installed DataflowDescription with an
    index export (compute-types/src/dataflows.rs:32).
    """

    def __init__(self, expr: mir.RelationExpr, name: str = "df",
                 state_cap: int = 256, out_levels: int = 2,
                 out_slots: int | None = None,
                 force_merge_ingest: bool = False):
        from ..expr import strings

        self.expr = expr
        self.name = name
        self.out_schema = expr.schema()
        # Stable render identity for the compile ledger (ISSUE 12):
        # pickled-MIR fingerprints are deterministic across installs
        # and processes (PR 1), so a re-CREATE of the same definition
        # ledgers its compiles as HITS — the program-bank opportunity.
        from ..utils.compile_ledger import expr_fingerprint

        self._fingerprint = expr_fingerprint(expr)
        self._str_keys, self._str_depth = strings.collect_keys(expr)
        # Tier quantization (ISSUE 16): a requested state_cap snaps to
        # its pow2 menu rung so two DDLs differing only in size render
        # byte-identical programs and share one program-bank key.
        from ..plan.decisions import quantize_cap

        state_cap = quantize_cap(state_cap)
        ctx = _RenderContext(
            {}, state_cap=state_cap,
            force_merge_ingest=force_merge_ingest,
        )
        if force_merge_ingest:
            out_slots = 0
        if out_slots is None:
            # Ingest-mode decision for the output index (plan layer —
            # same source of truth EXPLAIN prints): append-slot ring
            # for big-state outputs, every-step run-0 merge otherwise.
            from ..plan.decisions import INGEST_RING_SLOTS, ingest_mode

            out_slots = (
                INGEST_RING_SLOTS
                if ingest_mode(state_cap, ctx.out_delta_cap)
                == "append_slot"
                else 0
            )
        self._run = _build(expr, ctx)
        self._ctx = ctx
        self._basic_finalizers = _resolve_basic_sites(expr, ctx)
        self.states = [s.init for s in ctx.slots]
        # Big output indexes run a deeper geometric run ladder
        # (out_levels=3-4) so base-scale merges amortize to every
        # ratio^(levels-1) steps, plus an append-slot ingest ring
        # (out_slots=compact_every) for O(delta) per-step inserts
        # (spine.py).
        self._init_output(levels=out_levels, slots=out_slots)
        self.time = 0  # frontier: all steps < time are complete
        self._remake_jit()

    def _remake_jit(self):
        # A fresh jit wrapper so trace-time reads of mutable ctx tiers
        # (join_caps, slot_cap) take effect after growth. Dataflows
        # whose expressions use string functions carry the dictionary
        # side-tables as an extra jit input (expr/strings.py); others
        # keep the 4-argument signature (and their compile-cache
        # entries).
        from ..utils.compile_ledger import ledger_jit

        fp = getattr(self, "_fingerprint", self.name)
        self._donated_step_jits = {}
        if self._str_keys:
            self._step_jit = ledger_jit(
                jax.jit(
                    lambda s, o, eo, i, t, env: self._step_core(
                        s, o, eo, i, t, env
                    )
                ),
                "step", self.name, fp, static=self._static_tiers(),
            )
        else:
            self._step_jit = ledger_jit(
                jax.jit(
                    lambda s, o, eo, i, t: self._step_core(
                        s, o, eo, i, t
                    )
                ),
                "step", self.name, fp, static=self._static_tiers(),
            )

    def _donated_step_program(self, parts: tuple):
        """The step jit with ``donate_argnums`` on the prover-approved
        carry parts (the replica's donated ``run_steps`` span train,
        ISSUE 8): each step's output carry reuses the previous step's
        buffers instead of allocating state-sized arrays per tick.
        Cached per part subset; inputs (argnum 3) are never donated —
        the defer log replays them on overflow."""
        from ..analysis.donation import STEP_ARGNUM

        parts = tuple(sorted(parts))
        jitfn = self._donated_step_jits.get(parts)
        if jitfn is None:
            from ..utils.compile_ledger import ledger_jit

            argnums = tuple(
                sorted(STEP_ARGNUM[p] for p in parts)
            )
            if self._str_keys:
                jitfn = jax.jit(
                    lambda s, o, eo, i, t, env: self._step_core(
                        s, o, eo, i, t, env
                    ),
                    donate_argnums=argnums,
                )
            else:
                jitfn = jax.jit(
                    lambda s, o, eo, i, t: self._step_core(
                        s, o, eo, i, t
                    ),
                    donate_argnums=argnums,
                )
            jitfn = ledger_jit(
                jitfn, "step_donated", self.name,
                getattr(self, "_fingerprint", self.name),
                static=f"{self._static_tiers()}|{parts}",
            )
            self._donated_step_jits[parts] = jitfn
        return jitfn

    def _grow_batch(self, b: Batch, target: int | None = None) -> Batch:
        cap = target if target is not None else b.capacity * 2
        return b.with_capacity(cap) if cap > b.capacity else b

    def _first_rows(self, a, cap: int):
        return a[:cap]

    def _make_compact_jit(self, max_level: int = 10**9):
        from ..utils.compile_ledger import ledger_jit

        return ledger_jit(
            jax.jit(
                lambda s, o: self._compact_core_single(s, o, max_level)
            ),
            "compact", self.name,
            getattr(self, "_fingerprint", self.name),
            static=f"{self._static_tiers()}|{max_level}",
        )

    def _pack_inputs(self, inputs: dict) -> dict:
        return inputs

    # pure, jitted once per capacity signature
    def _step_core(self, states, output, err_output, inputs, time,
                   env=None):
        from ..expr import strings

        with strings.trace_scope(env if env is not None else {}):
            return self._step_core_inner(
                states, output, err_output, inputs, time
            )

    def _step_core_inner(self, states, output, err_output, inputs, time):
        from ..expr import errors as _errors
        from ..ops.sort import sorting_in_blocks

        with sorting_in_blocks(self._ctx.sort_in_blocks):
            with _errors.step_scope() as err_parts:
                out, upd, ovf = self._run(states, inputs, time)
            # The delta is what sinks/subscribers see: consolidate so
            # union-produced +/- pairs at the same time cancel.
            out = consolidate(out, include_time=True)
            out, shrink_ovf = shrink(out, self._ctx.out_delta_cap)
            new_output, out_ovf = insert_tail(output, out)
        new_states = list(states)
        for k, v in upd.items():
            new_states[k] = v
        ovf = dict(ovf)
        ovf[("outd",)] = shrink_ovf
        ovf[("out", "tail")] = out_ovf
        # The err collection delta (scalar-eval errors published by
        # apply_mfp sites during the _run trace above).
        new_err = self._apply_err_delta(err_output, err_parts, ovf)
        # time+1 rides back to the host loop as a device scalar so the
        # next step needs no h2d transfer (see _dispatch_span).
        return (
            out,
            tuple(new_states),
            new_output,
            new_err,
            time + jnp.asarray(1, dtype=time.dtype),
            self._pack_flags(ovf),
        )

    def peek(self) -> list[tuple]:
        """Read the full maintained result (SELECT * FROM mv)."""
        b = self.output_batch()
        if not self._basic_finalizers:
            return b.to_rows()
        n = int(b.count)
        cols = [np.asarray(c)[:n] for c in b.cols]
        nulls = [
            None if x is None else np.asarray(x)[:n] for x in b.nulls
        ]
        cols = self.finalize_basic_columns(cols, nulls)
        cols = cols + [
            np.asarray(b.time)[:n], np.asarray(b.diff)[:n]
        ]
        return [
            tuple(
                x.item() if isinstance(x, np.generic) else x
                for x in row
            )
            for row in zip(*cols)
        ]

    def peek_errors(self) -> list[tuple]:
        """The maintained err collection: [(err_code, count)] with
        count != 0. Nonempty means reads of this dataflow must raise
        (the reference picks an arbitrary error; render.rs:12-101).
        Dataflows whose step program has no error-emitting sites (a
        trace-time fact) skip the device readback entirely."""
        if not getattr(self, "_has_errors", False):
            return []
        self.span_barrier()
        self.check_flags()
        return self._accumulate_errors(self.err_output.batch.to_rows())


def _shard_rows(arrays, n: int, num_shards: int, shard_cap: int):
    """Deal host rows round-robin across shards; returns per-field
    [num_shards * shard_cap] arrays + [num_shards] counts. Ingestion
    balance only — exchange re-routes by key inside the step."""
    base, extra = divmod(n, num_shards)
    counts = np.full(num_shards, base, dtype=np.int32)
    counts[:extra] += 1

    def pack(a):
        if a is None:
            return None
        out = np.zeros(num_shards * shard_cap, dtype=a.dtype)
        for s in range(num_shards):
            rows = a[s::num_shards]
            out[s * shard_cap : s * shard_cap + len(rows)] = rows
        return out

    return [pack(a) for a in arrays], counts


class ShardedDataflow(_DataflowBase):
    """A maintained dataflow SPMD over a worker mesh.

    Worker = device; every stateful operator's state is sharded by key
    hash; inputs are dealt across workers and exchanged on key inside the
    step (the timely model, SURVEY.md §2.4 row 1). One ``shard_map``-ped
    jitted step per capacity signature. Each worker also maintains its
    own shard of the output arrangement; peeks gather + combine.

    Append-slot ingest under SPMD (ISSUE 9): the slot-ring cursor is
    carried as a PER-DEVICE ``[P]`` int32 vector riding the shard_map
    boundary specs like every other state leaf (reshaped to the
    per-worker scalar inside the step body), which is sound iff the
    cursor's dataflow is shard-local — worker p's cursor depends only
    on worker p's inputs. The shard-spec abstract interpreter
    (analysis/shard_prop.py) PROVES that property over the rendered
    step program; a refuted (or unprovable) cursor re-renders in
    merge-ingest mode, with the blame surfaced via
    ``sharding_report()`` / ``mz_sharding`` / EXPLAIN ANALYSIS.
    """

    def __init__(self, expr: mir.RelationExpr, mesh, name: str = "df",
                 slot_cap: int = 256, input_shard_cap: int = 1024,
                 output_cap: int = 256, state_cap: int = 256,
                 out_levels: int = 2, out_slots: int | None = None):
        from ..expr import strings

        self.expr = expr
        self.mesh = mesh
        self.name = name
        from ..utils.compile_ledger import expr_fingerprint

        self._fingerprint = expr_fingerprint(expr)
        self._str_keys, self._str_depth = strings.collect_keys(expr)
        if len(mesh.axis_names) != 1:
            raise ValueError(
                "ShardedDataflow wants a 1-D worker mesh (make_mesh); "
                f"got axes {mesh.axis_names}"
            )
        self.axis_name = mesh.axis_names[0]
        self.num_shards = int(mesh.shape[self.axis_name])
        self.out_schema = expr.schema()
        # Quantize every requested capacity to the pow2 menu
        # (ISSUE 16): size-only differences must share bank keys.
        from ..plan.decisions import quantize_cap

        self.input_shard_cap = quantize_cap(input_shard_cap)
        self._sharding = worker_sharding(mesh, self.axis_name)
        self._slot_cap0 = quantize_cap(slot_cap)
        self._output_cap = quantize_cap(output_cap)
        self._state_cap = quantize_cap(state_cap)
        self._out_levels = out_levels
        self._requested_out_slots = out_slots
        self._shard_prop_report: dict | None = None
        # TRIAL render, prover gate, fallback (ISSUE 9): render as if
        # the cursor proof will succeed; when any spine actually took
        # a slot ring, run the shard-spec prover over the rendered
        # step program and keep the ring only on a SAFE verdict —
        # otherwise re-render in merge mode. Dataflows whose ingest
        # decision is merge anyway (the common small-state case) never
        # pay the abstract trace.
        self._render(spmd_safe=True)
        from ..analysis.shard_prop import _has_slot_cursors

        if _has_slot_cursors(self):
            from ..analysis.shard_prop import sharded_step_report

            report = sharded_step_report(self)
            self._shard_prop_report = report
            if not report["safe"]:
                self._render(spmd_safe=False)
                self._shard_prop_report = dict(
                    report, ingest_mode="merge"
                )

    def _render(self, spmd_safe) -> None:
        """One full render at the given prover assumption (the ingest
        decisions consult ``spmd_safe`` through
        plan/decisions.state_ingest_mode — the EXPLAIN-visible source
        of truth)."""
        ctx = _RenderContext(
            {}, num_shards=self.num_shards, axis_name=self.axis_name,
            slot_cap=self._slot_cap0, state_cap=self._state_cap,
            spmd_safe=spmd_safe,
        )
        self._run = _build(self.expr, ctx)
        # Basic aggregates work sharded: the reduce input exchange keys
        # every group to exactly one worker, so the per-worker multiset
        # shards are group-disjoint and _basic_multiset_host's gather
        # yields a group-contiguous multiset for edge finalization.
        self._basic_finalizers = _resolve_basic_sites(self.expr, ctx)
        self._ctx = ctx
        out_slots = self._requested_out_slots
        if out_slots is None:
            from ..plan.decisions import INGEST_RING_SLOTS, ingest_mode

            out_slots = (
                INGEST_RING_SLOTS
                if ingest_mode(
                    self._state_cap,
                    ctx.out_delta_cap,
                    spmd=True,
                    spmd_safe=spmd_safe,
                )
                == "append_slot"
                else 0
            )
        elif out_slots and spmd_safe is not True:
            # An explicitly requested ring is still prover-gated under
            # SPMD: a refuted cursor falls back to merge (correctness
            # beats the request; sharding_report carries the blame).
            out_slots = 0
        # Per-shard states, stored as global arrays [P * cap] / counts [P].
        self.states = [
            self._replicate_empty(s.init) for s in ctx.slots
        ]
        self._init_output(
            self._output_cap, levels=self._out_levels, slots=out_slots
        )
        self.output = self._replicate_empty_one(self.output)
        self.err_output = self._replicate_empty_one(self.err_output)
        self.time = 0
        self._remake_jit()

    def sharding_report(self) -> dict:
        """The shard-spec prover's report over this dataflow's step
        program (ISSUE 9): communication census, per-cursor
        SPMD-safety verdicts, resolved ingest mode. Computed eagerly
        when a slot ring was requested (it gates the enablement),
        lazily for merge-mode dataflows; cached — surfaces
        (mz_sharding, EXPLAIN ANALYSIS) read it
        for free after the first call."""
        if self._shard_prop_report is None:
            from ..analysis.shard_prop import sharded_step_report

            self._shard_prop_report = sharded_step_report(self)
        return self._shard_prop_report

    # -- sharded state layout ----------------------------------------------
    def _replicate_empty(self, parts: tuple) -> tuple:
        """Each worker starts with empty shards of every state part."""
        return tuple(self._replicate_empty_one(a) for a in parts)

    def _replicate_empty_one(self, obj):
        """Each worker starts with an empty shard of this arrangement
        (or of each run of a spine). A slot-ring cursor becomes a
        PER-DEVICE [P] vector (each worker owns a private ring cursor;
        the shard-spec prover guarantees it stays shard-local)."""
        out = obj.map_batches(self._rep_batch)
        if isinstance(out, Spine) and out.cursor is not None:
            out = out.with_cursor(
                jax.device_put(
                    np.zeros(self.num_shards, np.int32),
                    self._sharding,
                )
            )
        return out

    def _rep_batch(self, b: Batch) -> Batch:
        P_ = self.num_shards

        def rep(a):
            if a is None:
                return None
            return jax.device_put(
                np.zeros(P_ * a.shape[0], dtype=a.dtype), self._sharding
            )

        return Batch(
            cols=tuple(rep(c) for c in b.cols),
            nulls=tuple(rep(n) for n in b.nulls),
            time=rep(b.time),
            diff=rep(b.diff),
            count=jax.device_put(
                np.zeros(P_, dtype=np.int32), self._sharding
            ),
            schema=b.schema,
        )

    def _grow_batch(self, b: Batch, target: int | None = None) -> Batch:
        """Grow every shard's capacity ([P, cap] -> [P, new_cap]):
        doubled by default, or straight to a GLOBAL ``target`` capacity
        (same units as b.capacity, i.e. P * per-shard)."""
        P_ = self.num_shards
        cap = b.capacity // P_
        new_cap = (
            -(-target // P_) if target is not None else cap * 2
        )
        if new_cap <= cap:
            return b

        def grow(a):
            if a is None:
                return None
            h = np.asarray(a).reshape(P_, cap)
            out = np.zeros((P_, new_cap), dtype=h.dtype)
            out[:, :cap] = h
            return jax.device_put(
                out.reshape(P_ * new_cap), self._sharding
            )

        return Batch(
            cols=tuple(grow(c) for c in b.cols),
            nulls=tuple(grow(n) for n in b.nulls),
            time=grow(b.time),
            diff=grow(b.diff),
            count=b.count,
            schema=b.schema,
        )

    def _first_rows(self, a, cap: int):
        """Every shard's first rows ([P, have] -> [P, cap / P]; ``cap``
        is GLOBAL, as in ``_grow_batch``)."""
        P_ = self.num_shards
        h = np.asarray(a)
        h = h.reshape((P_, h.shape[0] // P_) + h.shape[1:])
        return jax.device_put(
            np.ascontiguousarray(h[:, : cap // P_]).reshape(
                (cap,) + h.shape[2:]
            ),
            self._sharding,
        )

    # -- the SPMD step ------------------------------------------------------
    # Boundary rank adjustment: counts (and the slot cursor) cross the
    # shard_map boundary rank-1 ([1] per worker from the global [P])
    # and run the step body as scalars.
    @staticmethod
    def _scalar_counts(s: tuple) -> tuple:
        def fix(o):
            o = o.map_batches(
                lambda b: b.replace(count=b.count.reshape(()))
            )
            if isinstance(o, Spine) and o.cursor is not None:
                o = o.with_cursor(o.cursor.reshape(()))
            return o

        return tuple(fix(o) for o in s)

    @staticmethod
    def _vec_counts(s: tuple) -> tuple:
        def fix(o):
            o = o.map_batches(
                lambda b: b.replace(count=b.count.reshape((1,)))
            )
            if isinstance(o, Spine) and o.cursor is not None:
                o = o.with_cursor(o.cursor.reshape((1,)))
            return o

        return tuple(fix(o) for o in s)

    def _remake_jit(self):
        axis = self.axis_name
        scalar_counts = self._scalar_counts
        vec_counts = self._vec_counts

        def body(states, output, err_output, inputs, time):
            from ..expr import errors as _errors

            with _errors.step_scope() as err_parts:
                out, upd, ovf = self._run(states, inputs, time)
            new_states = list(states)
            for k, v in upd.items():
                new_states[k] = v
            out = consolidate(out, include_time=True)
            out, shrink_ovf = shrink(out, self._ctx.out_delta_cap)
            new_output, out_ovf = insert_tail(output, out)
            ovf = dict(ovf)
            ovf[("outd",)] = shrink_ovf
            ovf[("out", "tail")] = out_ovf
            # Each worker maintains its own err shard (errors stay
            # where computed; peek_errors gathers).
            new_err = self._apply_err_delta(err_output, err_parts, ovf)
            # Overflow anywhere aborts the span on every worker.
            flags = self._pack_flags(ovf)
            flags = (
                jax.lax.psum(flags.astype(jnp.int32), axis) > 0
            ).reshape(-1, 1)
            # Rank-1 counts for the shard_map boundary.
            out = out.replace(count=out.count.reshape((1,)))
            new_states = tuple(vec_counts(s) for s in new_states)
            (new_output,) = vec_counts((new_output,))
            (new_err,) = vec_counts((new_err,))
            new_time = time + jnp.asarray(1, dtype=time.dtype)
            return out, new_states, new_output, new_err, new_time, flags

        def per_worker(states, output, err_output, inputs, time, env=None):
            from ..expr import strings

            # Leaves arrive rank-preserved: counts are [1]; make scalar.
            states = [scalar_counts(s) for s in states]
            (output,) = scalar_counts((output,))
            (err_output,) = scalar_counts((err_output,))
            inputs = {
                k: b.replace(count=b.count.reshape(()))
                for k, b in inputs.items()
            }
            with strings.trace_scope(env if env is not None else {}):
                return body(states, output, err_output, inputs, time)

        if self._str_keys:
            # env (the string side-tables) rides along REPLICATED: every
            # worker gathers through identical dictionaries
            def step(states, output, err_output, inputs, time, env):
                return shard_map(
                    per_worker,
                    mesh=self.mesh,
                    in_specs=(P(self.axis_name), P(self.axis_name),
                              P(self.axis_name), P(self.axis_name),
                              P(), P()),
                    out_specs=(P(self.axis_name), P(self.axis_name),
                               P(self.axis_name), P(self.axis_name),
                               P(), P(None, self.axis_name)),
                    check_vma=False,
                )(states, output, err_output, inputs, time, env)
        else:
            def step(states, output, err_output, inputs, time):
                return shard_map(
                    lambda s, o, eo, i, t: per_worker(s, o, eo, i, t),
                    mesh=self.mesh,
                    in_specs=(P(self.axis_name), P(self.axis_name),
                              P(self.axis_name), P(self.axis_name),
                              P()),
                    out_specs=(P(self.axis_name), P(self.axis_name),
                               P(self.axis_name), P(self.axis_name),
                               P(), P(None, self.axis_name)),
                    check_vma=False,
                )(states, output, err_output, inputs, time)

        # The raw (un-jitted) step: the shard-spec abstract
        # interpreter traces it to reach the shard_map eqn's boundary
        # specs (analysis/shard_prop.trace_sharded_step).
        from ..utils.compile_ledger import ledger_jit

        self._step_fn = step
        self._step_jit = ledger_jit(
            jax.jit(step), "step_spmd", self.name,
            getattr(self, "_fingerprint", self.name),
            static=self._static_tiers(),
        )

    def _donated_step_program(self, parts: tuple):
        raise NotImplementedError(
            "SPMD dataflows do not donate their carry: the per-worker "
            "shard layout rides shard_map boundary specs that "
            "donate_argnums cannot alias through — the view layer "
            "routes SPMD views to the un-donated per-tick path. (The "
            "old second blocker — SPMD forcing merge ingest — is "
            "gone: the shard-spec prover now gates a per-device "
            "slot ring, ISSUE 9.)"
        )

    def _make_compact_jit(self, max_level: int = 10**9):
        axis = self.axis_name
        scalar_counts = self._scalar_counts
        vec_counts = self._vec_counts

        def per_worker(states, output):
            states = [scalar_counts(s) for s in states]
            (output,) = scalar_counts((output,))
            new_states, new_out, fl = self._compact_core_single(
                states, output, max_level
            )
            new_states = tuple(vec_counts(s) for s in new_states)
            (new_out,) = vec_counts((new_out,))
            fl = (jax.lax.psum(fl.astype(jnp.int32), axis) > 0).reshape(
                -1, 1
            )
            return new_states, new_out, fl

        def compact(states, output):
            return shard_map(
                per_worker,
                mesh=self.mesh,
                in_specs=(P(self.axis_name), P(self.axis_name)),
                out_specs=(
                    P(self.axis_name),
                    P(self.axis_name),
                    P(None, self.axis_name),
                ),
                check_vma=False,
            )(states, output)

        from ..utils.compile_ledger import ledger_jit

        return ledger_jit(
            jax.jit(compact), "compact_spmd", self.name,
            getattr(self, "_fingerprint", self.name),
            static=f"{self._static_tiers()}|{max_level}",
        )

    def _pack_inputs(self, inputs: dict) -> dict:
        packed = {}
        for name, b in inputs.items():
            if isinstance(b, Batch) and b.count.ndim == 0:
                # Host-global batch: deal rows across workers.
                n = int(b.count)
                cols = [np.asarray(c)[:n] for c in b.cols]
                nulls = [
                    None if nl is None else np.asarray(nl)[:n]
                    for nl in b.nulls
                ]
                time = np.asarray(b.time)[:n]
                diff = np.asarray(b.diff)[:n]
                cap = self.input_shard_cap
                while cap * self.num_shards < n or capacity_tier(
                    max((n + self.num_shards - 1) // self.num_shards, 1)
                ) > cap:
                    cap *= 2
                fields, counts = _shard_rows(
                    cols + nulls + [time, diff], n, self.num_shards, cap
                )
                k = len(cols)
                put = lambda a: (
                    None
                    if a is None
                    else jax.device_put(a, self._sharding)
                )
                packed[name] = Batch(
                    cols=tuple(put(a) for a in fields[:k]),
                    nulls=tuple(put(a) for a in fields[k : 2 * k]),
                    time=put(fields[2 * k]),
                    diff=put(fields[2 * k + 1]),
                    count=jax.device_put(counts, self._sharding),
                    schema=b.schema,
                )
            else:
                packed[name] = b
        return packed

    def _gather_batch(self, out: Batch) -> Batch:
        """Concatenate every worker's shard rows into one host batch."""
        P_ = self.num_shards
        counts = np.asarray(out.count)
        cap = out.diff.shape[0] // P_
        sel = np.concatenate(
            [
                np.arange(p * cap, p * cap + counts[p])
                for p in range(P_)
            ]
        ).astype(np.int64) if counts.sum() else np.zeros(0, dtype=np.int64)
        cols = [np.asarray(c)[sel] for c in out.cols]
        nulls = [
            None if nl is None else np.asarray(nl)[sel] for nl in out.nulls
        ]
        return Batch.from_numpy(
            out.schema,
            cols,
            np.asarray(out.time)[sel],
            np.asarray(out.diff)[sel],
            nulls=nulls,
        )

    def gather_delta(self, out: Batch) -> Batch:
        """Host view of a per-worker output delta from step()."""
        return self._gather_batch(out)

    def _basic_multiset_host(self, arr) -> dict:
        """Host view of a SHARDED basic multiset: concatenate each
        worker's valid rows. Groups are worker-disjoint (reduce's keyed
        exchange), so the concatenation is group-contiguous — exactly
        what the group-boundary scan in _basic_group_maps needs."""
        b = self._gather_batch(arr.batch)
        n = int(b.count)
        return {
            "n": n,
            "cols": [np.asarray(c)[:n] for c in b.cols],
            "nulls": [
                None if x is None else np.asarray(x)[:n]
                for x in b.nulls
            ],
            "diff": np.asarray(b.diff)[:n],
        }

    def peek_errors(self) -> list[tuple]:
        """Gather every worker's err shard: [(err_code, count)]."""
        if not getattr(self, "_has_errors", False):
            return []
        self.span_barrier()
        self.check_flags()
        return self._accumulate_errors(
            self._gather_batch(self.err_output.batch).to_rows()
        )

    def peek(self) -> list[tuple]:
        """Gather and combine every worker's output-arrangement shard.
        Different workers may hold the same row value (outputs stay where
        they were computed), so diffs are summed host-side."""
        b = self._gather_batch(self.output_batch())
        if self._basic_finalizers:
            n = int(b.count)
            cols = [np.asarray(c)[:n] for c in b.cols]
            nulls = [
                None if x is None else np.asarray(x)[:n]
                for x in b.nulls
            ]
            cols = self.finalize_basic_columns(cols, nulls)
            cols = cols + [
                np.asarray(b.time)[:n], np.asarray(b.diff)[:n]
            ]
            rows = [
                tuple(
                    x.item() if isinstance(x, np.generic) else x
                    for x in row
                )
                for row in zip(*cols)
            ]
        else:
            rows = b.to_rows()
        acc: dict = {}
        for r in rows:
            key = r[:-2]  # value columns only: shards may hold the same
            acc[key] = acc.get(key, 0) + r[-1]  # row at different times
        return [k + (0, d) for k, d in acc.items() if d != 0]
