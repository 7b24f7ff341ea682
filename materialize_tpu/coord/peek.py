"""The O(result) peek serving plane (ISSUE 6 / ROADMAP item 3).

Analog of the reference's adapter-layer peek fast path
(``adapter/src/coord/peek.rs`` fast-path detection + ``compute``'s
``handle_peek`` reading an arranged trace directly): a SELECT that is a
key-equality lookup or a full scan over a maintained index is served by
ROW-GATHERING from the index dataflow's output spine — no transient
dataflow, no render, no per-query compile. The plan-side recognizer
lives in ``plan/decisions.peek_fast_path`` (EXPLAIN-visible); this
module owns the replica-side device gather programs and the host glue.

Three gather programs, each jitted once per (index shape, key-arity,
batch tier) and reused for every peek of that shape:

- **scan**: concatenate every spine run's (and ingest slot's) valid
  rows — the result IS the maintained multiset, read without the
  compaction cascade ``output_batch()`` pays. O(result) host transfer.
- **point** (every column bound): the probe rows' 2-lane hash pair is
  binary-searched against each run's CACHED key lanes
  (``Spine.lanes`` + ``ops/search.lex_searchsorted_2d`` — the PR 2
  machinery), candidate rows in the match range are gathered and
  raw-verified (hash collisions can only make rows adjacent, never
  equal), and the net multiplicity comes back per probe. O(B log n)
  device work, O(B) transfer.
- **lookup** (a column subset bound): per probe, a masked compaction
  over the concatenated runs — equality mask, cumsum, and a
  searchsorted over the running count picks the first S match
  positions with NO output-sized scatter (PERF_NOTES design rule);
  matching rows are gathered into a [B, S] result. O(B·state)
  elementwise device work, O(result) transfer.

Batches of probes arrive stacked from the controller's peek batcher
(coord/controller.py): N concurrent sessions' lookups against the same
index pad to a pow2 batch lane and share ONE dispatch, so the
dispatch + readback round trip is amortized across every waiting
reader instead of paid per peek.
"""

from __future__ import annotations

import numpy as np


class ServerBusy(RuntimeError):
    """Admission control shed a read: the peek queue is full or too
    many gather batches are in flight. Surfaced as SQLSTATE 53400 at
    pgwire and HTTP 503 — a clean, retryable overload signal instead of
    an unbounded backlog. The flush-vs-shed hand-off (every submitted
    peek either resolves or sheds with THIS error, never silently
    drops) is model-checked over all interleavings by
    ``analysis/interleave.BatcherModel``."""


class PeekTimedOut(ServerBusy):
    """A peek (or batched gather) wait exhausted its budget
    (``retry_policy_peek``). A ServerBusy subclass on purpose: the
    client should RETRY, so the front ends shed it exactly like an
    admission-control rejection (SQLSTATE 53400 / HTTP 503), never a
    generic internal error — and the sequencing lock is released
    around every such wait, so a timed-out statement can never poison
    later ones (ISSUE 10 satellite)."""


# Span tiers for match ranges: the gather program reserves S candidate
# slots per probe and retries at the next tier when a probe matches
# more (duplicates / wide groups).
_MIN_SPAN = 8
_MIN_BATCH = 8


def _pow2(n: int, minimum: int) -> int:
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


def _peek_jits(df) -> dict:
    return df.__dict__.setdefault("_peek_jit_cache", {})


def _peek_jit(df, kind: str, fn, static: tuple = ()):
    """Ledger-wrapped peek program (ISSUE 12): gather-program compiles
    join mz_compile_log like every step/span program. ``static`` is
    what ``fn`` closes over (bound columns, reserved span): it shows
    in no argument shape, so it must be in the program's key."""
    from ..utils.compile_ledger import ledger_jit

    import jax

    return ledger_jit(
        jax.jit(fn), kind, getattr(df, "name", "peek"),
        getattr(df, "_fingerprint", getattr(df, "name", "peek")),
        static=repr(static),
    )


# ---------------------------------------------------------------------------
# device cores (traced per spine shape; shared with the census tooling)
# ---------------------------------------------------------------------------


def _concat_spine(spine):
    """Concatenate every run's and ingest slot's columns into one
    virtual array set + a validity mask. Readers see the multiset sum
    of all runs (spine.py contract); consolidation of duplicate rows
    across runs happens host-side in the coordinator's _finish."""
    import jax.numpy as jnp

    batches = list(spine.runs_b) + list(spine.slots)
    arity = len(batches[0].cols)
    cols, nulls = [], []
    for j in range(arity):
        cols.append(jnp.concatenate([b.cols[j] for b in batches]))
        if any(b.nulls[j] is not None for b in batches):
            nulls.append(
                jnp.concatenate(
                    [
                        b.nulls[j]
                        if b.nulls[j] is not None
                        else jnp.zeros(b.capacity, bool)
                        for b in batches
                    ]
                )
            )
        else:
            nulls.append(None)
    time = jnp.concatenate([b.time for b in batches])
    diff = jnp.concatenate([b.diff for b in batches])
    valid = jnp.concatenate(
        [
            jnp.logical_and(
                jnp.arange(b.capacity, dtype=jnp.int32) < b.count,
                b.diff != 0,
            )
            for b in batches
        ]
    )
    return cols, nulls, time, diff, valid


def _scan_core(spine):
    """The peek-scan program: one dispatch, O(result) readback."""
    return _concat_spine(spine)


def _make_lookup_core(bound_cols: tuple, span: int):
    """Masked-compaction gather for a PARTIAL column binding: per
    probe, an equality mask over the concatenated runs, a cumsum, and
    ``searchsorted(cumsum, 1..S)`` to pick the first S match positions
    (no output-sized scatter — PERF_NOTES round-5 design rules), then
    one gather per column at those positions."""
    import jax
    import jax.numpy as jnp

    def core(spine, probes, ok):
        cols, nulls, time, diff, valid = _concat_spine(spine)
        total = valid.shape[0]

        def one(pvals, okb):
            m = jnp.logical_and(valid, okb)
            for k, j in enumerate(bound_cols):
                mj = cols[j] == pvals[k]
                if nulls[j] is not None:
                    mj = jnp.logical_and(mj, jnp.logical_not(nulls[j]))
                m = jnp.logical_and(m, mj)
            csum = jnp.cumsum(m.astype(jnp.int32))
            cnt = csum[-1]
            tgt = jnp.searchsorted(
                csum, jnp.arange(1, span + 1, dtype=jnp.int32)
            )
            tgt = jnp.clip(tgt, 0, total - 1)
            out_cols = tuple(c[tgt] for c in cols)
            out_nulls = tuple(
                None if nl is None else nl[tgt] for nl in nulls
            )
            return (out_cols, out_nulls, time[tgt], diff[tgt], cnt)

        # vmap, not lax.map: probes evaluate as ONE vectorized pass
        # ([B, total] masks — the batch lane rides the same elementwise
        # kernels), not B sequential sweeps. Peak mask memory is
        # B × total bools; the controller's PEEK_MAX_BATCH bounds B.
        return jax.vmap(one)(tuple(probes), ok)

    return core


def _make_point_core(schema, span: int):
    """Hash-lane point lookup for a FULL column binding: probe hash
    pairs binary-search each run's cached key lanes (one [B, L]
    row-gather per iteration — lex_searchsorted_2d), the candidate
    range is gathered and raw-verified, and the probe's net
    multiplicity comes back. The only possible matching row IS the
    probe tuple, so the result is O(B) scalars."""
    import jax.numpy as jnp

    from ..arrangement.spine import lookup_range
    from ..ops.lanes import stack_lanes
    from ..repr.batch import Batch
    from ..repr.schema import DIFF_DTYPE, TIME_DTYPE

    arity = schema.arity

    def core(spine, probes, ok):
        B = probes[0].shape[0]
        pb = Batch(
            cols=tuple(probes),
            nulls=tuple(None for _ in range(arity)),
            time=jnp.zeros(B, dtype=TIME_DTYPE),
            diff=jnp.ones(B, dtype=DIFF_DTYPE),
            count=jnp.asarray(B, jnp.int32),
            schema=schema,
        )
        runs = spine.runs()
        q2d = stack_lanes(
            runs[0].probe_lanes(pb, list(range(arity)))
        )
        net = jnp.zeros(B, jnp.int64)
        need = jnp.zeros(B, jnp.int32)
        for arr in runs:
            lo, hi = lookup_range(arr, q2d)
            cap = arr.batch.capacity
            pos = (
                lo[:, None]
                + jnp.arange(span, dtype=jnp.int32)[None, :]
            )
            in_range = pos < hi[:, None]
            posc = jnp.clip(pos, 0, cap - 1)
            eq = in_range
            for j in range(arity):
                g = arr.batch.cols[j][posc]
                mj = g == probes[j][:, None]
                if arr.batch.nulls[j] is not None:
                    mj = jnp.logical_and(
                        mj, jnp.logical_not(arr.batch.nulls[j][posc])
                    )
                eq = jnp.logical_and(eq, mj)
            d = arr.batch.diff[posc]
            net = net + jnp.sum(
                jnp.where(eq, d, jnp.zeros_like(d)), axis=1
            )
            need = jnp.maximum(need, (hi - lo).astype(jnp.int32))
        net = jnp.where(ok, net, jnp.zeros_like(net))
        # Mask padding probes out of the span-escalation signal too: a
        # zero-filled pad tuple is a legitimate key, and a wide group
        # of zero rows would otherwise drive every batch to a huge
        # span tier.
        need = jnp.where(ok, need, jnp.zeros_like(need))
        return net, need

    return core


# ---------------------------------------------------------------------------
# host glue (replica side)
# ---------------------------------------------------------------------------


def _probe_arrays(schema, bound_cols, probes, batch: int):
    """Stack probe tuples into per-column device-dtype arrays padded to
    the pow2 batch lane, plus the validity mask."""
    n = len(probes)
    ok = np.zeros(batch, dtype=bool)
    ok[:n] = True
    by_col = list(zip(*probes)) if probes else [
        () for _ in bound_cols
    ]
    arrays = []
    for k, j in enumerate(bound_cols):
        dt = schema.columns[j].dtype
        a = np.zeros(batch, dtype=dt)
        if n:
            a[:n] = np.asarray(by_col[k], dtype=dt)
        arrays.append(a)
    return tuple(arrays), ok


def _decode(schema, cols, nulls, time, diff) -> list:
    from ..repr.schema import decode_result_rows

    return decode_result_rows(schema, cols, nulls, time, diff)


def _scan_rows(df) -> list:
    import jax

    jits = _peek_jits(df)
    fn = jits.get("scan")
    if fn is None:
        fn = _peek_jit(df, "peek_scan", _scan_core)
        jits["scan"] = fn
    cols, nulls, time, diff, valid = fn(df.output)
    mask = np.asarray(valid)
    h_cols = [np.asarray(c)[mask] for c in cols]
    h_nulls = [
        None if nl is None else np.asarray(nl)[mask] for nl in nulls
    ]
    return _decode(
        df.out_schema,
        h_cols,
        h_nulls,
        np.asarray(time)[mask],
        np.asarray(diff)[mask],
    )


def _span_hints(df) -> dict:
    """Last sufficient span tier per program signature: starting every
    call at the minimum tier would re-run the too-small program (and
    pay its dispatch) on every peek of a group wider than _MIN_SPAN."""
    return df.__dict__.setdefault("_peek_span_hints", {})


def _lookup_groups(df, bound_cols: tuple, probes: list) -> list:
    import jax

    schema = df.out_schema
    B = _pow2(max(len(probes), 1), _MIN_BATCH)
    arrays, ok = _probe_arrays(schema, bound_cols, probes, B)
    jits = _peek_jits(df)
    span = _span_hints(df).get(("lookup", bound_cols), _MIN_SPAN)
    while True:
        key = ("lookup", bound_cols, B, span)
        fn = jits.get(key)
        if fn is None:
            fn = _peek_jit(
                df, "peek_lookup", _make_lookup_core(bound_cols, span),
                static=(bound_cols, span),
            )
            jits[key] = fn
        cols, nulls, time, diff, cnt = fn(df.output, arrays, ok)
        cnt = np.asarray(cnt)
        mx = int(cnt.max()) if len(probes) else 0
        if mx <= span:
            break
        # A probe matched more rows than the reserved span: retry at
        # the covering tier (compile-cache-per-tier, like capacities).
        span = _pow2(mx, _MIN_SPAN)
    _span_hints(df)[("lookup", bound_cols)] = span
    # ONE decode over every probe's matches, split by counts after —
    # a per-probe decode paid a dictionary snapshot + call overhead per
    # group, which dominated small point-lookup batches.
    h_cols = [np.asarray(c) for c in cols]
    h_nulls = [None if nl is None else np.asarray(nl) for nl in nulls]
    h_time, h_diff = np.asarray(time), np.asarray(diff)
    npr = len(probes)
    counts = [int(cnt[i]) for i in range(npr)]
    sel_rows = [i for i in range(npr) for _ in range(counts[i])]
    sel_slots = [s for i in range(npr) for s in range(counts[i])]
    flat = _decode(
        schema,
        [c[sel_rows, sel_slots] for c in h_cols],
        [
            None if nl is None else nl[sel_rows, sel_slots]
            for nl in h_nulls
        ],
        h_time[sel_rows, sel_slots],
        h_diff[sel_rows, sel_slots],
    )
    groups, pos = [], 0
    for n in counts:
        groups.append(flat[pos : pos + n])
        pos += n
    return groups


def _point_groups(df, bound_cols: tuple, probes: list, served_t: int):
    import jax

    schema = df.out_schema
    arity = schema.arity
    # Reorder each probe tuple into schema column order (bound_cols is
    # column-sorted by the planner, but be explicit).
    order = {j: k for k, j in enumerate(bound_cols)}
    full = [
        tuple(p[order[j]] for j in range(arity)) for p in probes
    ]
    B = _pow2(max(len(full), 1), _MIN_BATCH)
    arrays, ok = _probe_arrays(
        schema, tuple(range(arity)), full, B
    )
    jits = _peek_jits(df)
    span = _span_hints(df).get(("point",), _MIN_SPAN)
    while True:
        key = ("point", B, span)
        fn = jits.get(key)
        if fn is None:
            fn = _peek_jit(
                df, "peek_point", _make_point_core(schema, span),
                static=(span,),
            )
            jits[key] = fn
        net, need = fn(df.output, arrays, ok)
        need = np.asarray(need)
        mx = int(need.max()) if len(full) else 0
        if mx <= span:
            break
        span = _pow2(mx, _MIN_SPAN)
    _span_hints(df)[("point",)] = span
    net = np.asarray(net)
    # One decode over the hit probes (the rows ARE the probe tuples).
    hits = [i for i in range(len(full)) if int(net[i]) != 0]
    rows = []
    if hits:
        cols = [
            np.asarray([full[i][j] for i in hits], dtype=c.dtype)
            for j, c in enumerate(schema.columns)
        ]
        rows = _decode(
            schema,
            cols,
            [None] * arity,
            np.full(len(hits), served_t, np.uint64),
            net[hits].astype(np.int64),
        )
    groups = [[] for _ in full]
    for pos, i in enumerate(hits):
        groups[i] = [rows[pos]]
    return groups


def _host_filter_groups(view, bound_cols: tuple, probes: list,
                        scan: bool) -> list:
    """Fallback for dataflows without the single-device gather path
    (SPMD output shards, basic-aggregate finalizers): read the gathered
    result batch once and filter host-side. Still no transient
    dataflow, still one read amortized over the whole batch."""
    from ..storage.persist.operators import _host_updates

    schema = view.df.out_schema
    cols, nulls, time, diff = _host_updates(view.result_batch())
    if scan:
        return [_decode(schema, cols, nulls, time, diff)]
    groups = []
    for p in probes:
        mask = np.ones(len(diff), dtype=bool)
        for k, j in enumerate(bound_cols):
            v = np.asarray(p[k]).astype(schema.columns[j].dtype)
            mask &= np.asarray(cols[j]) == v
            if nulls[j] is not None:
                mask &= ~np.asarray(nulls[j])
        groups.append(
            _decode(
                schema,
                [np.asarray(c)[mask] for c in cols],
                [
                    None if nl is None else np.asarray(nl)[mask]
                    for nl in nulls
                ],
                time[mask],
                diff[mask],
            )
        )
    return groups


def serve_peek_groups(view, spec: dict) -> list:
    """Serve one batched fast-path peek against an installed dataflow's
    maintained arrangement. ``spec``: {"scan": bool, "bound_cols":
    tuple, "probes": [probe tuple, ...]} with probe values in INTERNAL
    representation (the same values MIR literals carry). Returns
    rows-groups: one decoded row list per probe (a single shared group
    for scans). Never renders, never compacts the spine."""
    df = view.df
    probes = [tuple(p) for p in (spec.get("probes") or [])]
    bound_cols = tuple(spec.get("bound_cols") or ())
    scan = bool(spec.get("scan"))
    from ..render.dataflow import Dataflow as _SingleDevice

    if type(df) is not _SingleDevice or getattr(
        df, "_basic_finalizers", None
    ):
        return _host_filter_groups(view, bound_cols, probes, scan)
    # Resolve any deferred overflow state first (no-op in steady
    # serving; a deferred span's provisional state must not serve).
    df.check_flags()
    if scan:
        return [_scan_rows(df)]
    if len(bound_cols) == df.out_schema.arity:
        return _point_groups(df, bound_cols, probes, view.upper - 1)
    return _lookup_groups(df, bound_cols, probes)


# ---------------------------------------------------------------------------
# static census (scripts/check_plans.py --bench + the -m analysis lane)
# ---------------------------------------------------------------------------


def trace_peek_programs(df, n_probes: int = 64, span: int = 8) -> dict:
    """Abstract-trace the serving programs over ``df``'s output spine
    shape (nothing compiles or runs): the batched-gather launch counts
    are budgeted in tests/kernel_budget.json exactly like the step
    program, so a serving-path launch-count regression fails CI
    statically."""
    import jax
    import jax.numpy as jnp

    schema = df.out_schema
    probes_all = tuple(
        jnp.zeros(n_probes, dtype=c.dtype) for c in schema.columns
    )
    ok = jnp.zeros(n_probes, bool)
    out = {
        "peek_scan": jax.make_jaxpr(_scan_core)(df.output),
        "peek_lookup": jax.make_jaxpr(_make_lookup_core((0,), span))(
            df.output, (probes_all[0],), ok
        ),
        "peek_point": jax.make_jaxpr(_make_point_core(schema, span))(
            df.output, probes_all, ok
        ),
    }
    return out
