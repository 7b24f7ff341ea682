"""Benchmark: the five BASELINE.json gate configs, maintained under churn.

Configs (BASELINE.json):
  index    — maintained INDEX on lineitem at sf=0.25: the >=2^20-row
             arrangement-maintenance proof (state_rows is reported and
             must exceed 1,048,576; the round-2 verdict's top ask).
  q1       — TPCH Q1 (pure accumulable Reduce).
  q15      — TPCH Q15 (join + SUM + global MAX).
  q9       — TPCH Q9 (6-relation delta join).
  auction  — windowed TopK + DISTINCT under bid inserts/retractions.
  pagerank — recursive PageRank (WITH MUTUALLY RECURSIVE): reported as
             per-step fixpoint latency + edge updates/s, excluded from
             the throughput gate (the 1M updates/s north star is defined
             on the lineitem stream, BASELINE.md; a whole-graph fixpoint
             per micro-batch measures freshness, not stream throughput).

Measures steady-state maintained-update throughput (updates/sec through
the full step) and p99 per-step completion latency (the freshness proxy)
on the available accelerator. Baseline: 1M lineitem updates/sec with
<100ms p99 lag (BASELINE.json).

Output protocol (round-3 verdict: a bench that cannot finish inside the
driver's budget is a bench that doesn't exist — results must STREAM):
- one JSON line per config, printed the moment that config finishes;
- after EVERY config, a cumulative aggregate line in the final schema
  (with "partial": true until all gate configs are in), so a timeout at
  any point still leaves a parseable aggregate as the last line;
- the last line of a full run is the final aggregate (partial: false).

What the code does today:
- Each config measures in its own subprocess (`--measure <name>`), so a
  config's compiles, device state and timing never share a process with
  another's. The parent that starts those children initialises no JAX
  backend itself: a chip belongs to one process at a time.
- Inside a child, a measured window contains no device->host readback:
  steps run with run_steps(defer_check=True), logical time rides as a
  device scalar, update counts come from host-side generation metadata,
  and the flags/validity readbacks happen after the last timestamp (a
  readback blocks on everything dispatched before it).
- Capacity tiers come from the checked-in bench_tiers.json, generated by
  CPU-platform probe subprocesses (`python bench.py --reprobe`): tier
  growth is driven by row counts, which are platform-independent, so a
  CPU probe discovers the exact tiers the TPU run needs WITHOUT paying
  TPU compiles at every intermediate growth tier. The measuring process
  compiles only the final-tier programs. A post-hoc check asserts no
  overflow occurred inside any measured span; on overflow (stale tiers
  after a code change) the driver run re-probes that config on CPU and
  re-measures once.
- Nothing here has run on the machine the chip tool provides; the
  benchmark proper is a later `benchmark` issue (ROADMAP A1).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time as _time

import numpy as np

BASELINE_UPDATES_PER_SEC = 1_000_000.0
BASELINE_P99_MS = 100.0

# Warmup is one full span (it also compiles the span program when
# hydration didn't share the shape).
WARMUP = 32
CHURN_CAP = 1 << 12
TIERS_PATH = os.path.join(os.path.dirname(__file__), "bench_tiers.json")
# Wall-clock cap per config subprocess: a hung config must not eat the
# evidence of the ones after it.
CONFIG_TIMEOUT_S = float(os.environ.get("BENCH_CONFIG_TIMEOUT_S", "900"))
# Whole-run wall-clock budget: once it is exhausted, remaining configs
# are SKIPPED (listed in the aggregate's skipped_configs) and the
# final gate line is still emitted — a slow config can no longer eat
# the driver's budget and leave rc=124 with no aggregate (BENCH_r05).
TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_BUDGET_S", "850"))
# Minimum useful remaining budget to even start a config.
MIN_CONFIG_S = 60.0


def _block(tree):
    import jax

    jax.block_until_ready(jax.tree_util.tree_leaves(tree))


# --------------------------------------------------------------------------
# capacity-tier snapshot/apply (probe subprocess -> measuring process)
# --------------------------------------------------------------------------


def snapshot_tiers(df) -> dict:
    from materialize_tpu.arrangement.spine import Spine

    st: dict = {"grow": []}
    for slot, parts in enumerate(df.states):
        for p, s in enumerate(parts):
            if isinstance(s, Spine):
                for ri, rb in enumerate(s.runs_b):
                    st["grow"].append(
                        [["state", slot, [p, ri]], rb.capacity]
                    )
                if s.slots:
                    st["grow"].append(
                        [["state", slot, [p, "tail"]],
                         s.slots[0].capacity]
                    )
            else:
                st["grow"].append([["state", slot, p], s.capacity])
    for ri, rb in enumerate(df.output.runs_b):
        st["grow"].append([["out", ri], rb.capacity])
    if df.output.slots:
        st["grow"].append(
            [["out", "tail"], df.output.slots[0].capacity]
        )
    st["grow"].append([["errout"], df.err_output.capacity])
    st["slot_cap"] = df._ctx.slot_cap
    st["out_delta_cap"] = df._ctx.out_delta_cap
    st["join_caps"] = list(df._ctx.join_caps)
    st["letrec_caps"] = list(df._ctx.letrec_caps)
    return st


def _tier_capacity(df, key):
    from materialize_tpu.arrangement.spine import Spine

    if key[0] == "state":
        part = key[2]
        s = df.states[key[1]][part[0] if isinstance(part, tuple) else part]
        if isinstance(s, Spine):
            if isinstance(part, tuple) and part[1] == "tail":
                return s.slots[0].capacity
            return s.runs_b[part[1]].capacity
        return s.capacity
    if key[0] == "out":
        if key[1] == "tail":
            return df.output.slots[0].capacity
        return df.output.runs_b[key[1]].capacity
    if key[0] == "errout":
        return df.err_output.capacity
    raise AssertionError(key)


def apply_tiers(df, st: dict) -> None:
    for key, want in st["grow"]:
        gkey = tuple(
            tuple(k) if isinstance(k, list) else k for k in key
        )
        if _tier_capacity(df, gkey) < want:
            # Single-hop growth: each doubling rung costs a pad-program
            # compile + dispatch (13 rungs to 2^21), one hop costs one.
            df._grow_for(gkey, target=want)
    df._ctx.slot_cap = max(df._ctx.slot_cap, st["slot_cap"])
    df._ctx.out_delta_cap = max(
        df._ctx.out_delta_cap, st["out_delta_cap"]
    )
    for i, c in enumerate(st["join_caps"]):
        df._ctx.join_caps[i] = max(df._ctx.join_caps[i], c)
    for i, c in enumerate(st["letrec_caps"]):
        df._ctx.letrec_caps[i] = max(df._ctx.letrec_caps[i], c)
    df._remake_jit()


# --------------------------------------------------------------------------
# configs: each returns (df, hydrate_inputs: list, churn: (i, t) ->
#                        (step inputs, host update count))
# --------------------------------------------------------------------------


def _empty_like(b):
    from materialize_tpu.repr.batch import Batch

    return Batch.empty(b.schema, 256)


def _tier(n: int) -> int:
    from materialize_tpu.repr.batch import capacity_tier

    return capacity_tier(n)


def _tpch_lineitem_config(mir_expr, sf: float, n_orders_per_tick: int,
                          extra_inputs_fn=None, state_cap: int = 256,
                          out_levels: int = 2, ce: int = 8,
                          ratio: int = 8, slots: bool = False):
    """Shared TPCH shape: hydrate the lineitem snapshot (+ static side
    tables on the first step), then churn lineitem. With out_levels > 2
    the output spine's geometric run ladder is pre-sized for the tick
    row rate (spine.py); `slots` adds the append-slot ingest ring
    (O(delta) per-step inserts; run_i then holds ce*ratio^(i+1) ticks
    because the slots absorb the first ce). Hydration batches are
    sized to the ingest tier."""
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.storage.generator.tpch import TpchGenerator

    gen = TpchGenerator(sf=sf, seed=42)
    rows_per_tick = int(n_orders_per_tick * 4.5 * 2)
    churn_cap = max(_tier(rows_per_tick), CHURN_CAP)
    df = Dataflow(
        mir_expr, state_cap=state_cap, out_levels=out_levels,
        out_slots=(ce if slots else 0),
    )
    df._compact_every = ce
    df._compact_ratio = ratio
    if out_levels > 2:
        base_cap = max(state_cap, 1 << 21)
        shift = 1 if slots else 0
        for ri in range(out_levels - 1):
            df._grow_for(
                ("out", ri),
                target=min(
                    _tier(2 * ce * ratio ** (ri + shift) * rows_per_tick),
                    base_cap,
                ),
            )
        df._grow_for(("out", out_levels - 1), target=base_cap)
        df._ctx.out_delta_cap = churn_cap
        if slots:
            # The slot ring was built at the default delta tier; size
            # it for the churn batches up front.
            df._grow_for(("out", "tail"), target=churn_cap)
        df._remake_jit()
        if slots:
            h_orders = max(896, churn_cap // 9)
        else:
            h_orders = max(
                896, df.output.runs_b[0].capacity // (ce * 9)
            )
    else:
        h_orders = 896
    extras = extra_inputs_fn(gen) if extra_inputs_fn else {}
    empty_extras = {name: _empty_like(b) for name, b in extras.items()}

    def hydrate():
        out = []
        first = True
        for b in gen.snapshot_lineitem_batches(
            batch_orders=h_orders, time=0
        ):
            inp = {"lineitem": b}
            inp.update(extras if first else empty_extras)
            first = False
            out.append(inp)
        return out

    def churn(i: int, t: int):
        b = gen.churn_lineitem_batch(
            n_orders_per_tick, tick=i, time=t, capacity=churn_cap
        )
        inp = {"lineitem": b}
        inp.update(empty_extras)
        return inp, b._host_count

    return df, hydrate, churn


def config_index():
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.storage.generator.tpch import LINEITEM_SCHEMA

    return _tpch_lineitem_config(
        mir.Get("lineitem", LINEITEM_SCHEMA), sf=0.25,
        n_orders_per_tick=1024, state_cap=1 << 21,
        out_levels=4, ce=4, ratio=4, slots=True,
    )


def config_q1():
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.tpch import q1_mir

    return _tpch_lineitem_config(
        optimize(q1_mir()), sf=0.1, n_orders_per_tick=256
    )


def config_q15():
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.tpch import q15_mir

    return _tpch_lineitem_config(
        optimize(q15_mir()), sf=0.05, n_orders_per_tick=256,
        extra_inputs_fn=lambda gen: {
            "supplier": gen.table_batch("supplier")
        },
        state_cap=1024,
    )


def config_q9():
    from materialize_tpu.repr.batch import Batch
    from materialize_tpu.storage.generator.tpch import ORDERS_SCHEMA
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.tpch import q9_mir

    def extras(gen):
        okeys = np.arange(1, gen.n_orders + 1, dtype=np.int64)
        ocols = gen.orders_rows(okeys)
        return {
            "part": gen.table_batch("part"),
            "supplier": gen.table_batch("supplier"),
            "partsupp": gen.table_batch("partsupp"),
            "nation": gen.table_batch("nation"),
            "orders": Batch.from_numpy(
                ORDERS_SCHEMA, ocols, np.uint64(0),
                np.ones(len(okeys), np.int64),
            ),
        }

    return _tpch_lineitem_config(
        optimize(q9_mir()), sf=0.01, n_orders_per_tick=256,
        extra_inputs_fn=extras, state_cap=1 << 16,
    )


def config_auction():
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.storage.generator.auction import AuctionGenerator
    from materialize_tpu.transform.optimizer import optimize
    from materialize_tpu.workloads.auction import (
        auction_winning_bidders_mir,
    )

    gen = AuctionGenerator(
        seed=42, n_users=512, auctions_per_tick=128,
        bids_per_auction=8, retract_after=4,
    )
    df = Dataflow(
        optimize(auction_winning_bidders_mir(k=3)), state_cap=1 << 13
    )

    def hydrate():
        out = []
        for i in range(8):  # reach steady state: retractions flowing
            tk = gen.tick(i, i)
            out.append({"bids": tk["bids"]})
        return out

    def churn(i: int, t: int):
        tk = gen.tick(8 + i, t)
        b = tk["bids"]
        return {"bids": b}, b._host_count

    return df, hydrate, churn


def config_pagerank():
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.repr.batch import Batch
    from materialize_tpu.repr.schema import Column, ColumnType, Schema
    from materialize_tpu.workloads.pagerank import pagerank_mir

    EDGE = Schema(
        (Column("src", ColumnType.INT64), Column("dst", ColumnType.INT64))
    )
    N_NODES, N_EDGES, PER_TICK = 2000, 10000, 64
    rng = np.random.default_rng(42)
    src = rng.integers(0, N_NODES, N_EDGES).astype(np.int64)
    dst = rng.integers(0, N_NODES, N_EDGES).astype(np.int64)

    df = Dataflow(pagerank_mir(EDGE, max_iters=60), state_cap=1 << 14)
    def hydrate():
        return [
            {
                "edges": Batch.from_numpy(
                    EDGE, [src, dst], np.uint64(0),
                    np.ones(N_EDGES, np.int64),
                )
            }
        ]

    def churn(i: int, t: int):
        # Replace PER_TICK edges: retract old, insert rewired.
        rng2 = np.random.default_rng(1000 + i)
        idx = rng2.choice(N_EDGES, PER_TICK, replace=False)
        new_dst = rng2.integers(0, N_NODES, PER_TICK).astype(np.int64)
        cols = [
            np.concatenate([src[idx], src[idx]]),
            np.concatenate([dst[idx], new_dst]),
        ]
        diffs = np.concatenate(
            [np.full(PER_TICK, -1, np.int64), np.ones(PER_TICK, np.int64)]
        )
        dst[idx] = new_dst
        b = Batch.from_numpy(EDGE, cols, np.uint64(t), diffs, capacity=256)
        return {"edges": b}, 2 * PER_TICK

    return df, hydrate, churn


# Gate configs first (index carries the state_rows gate), pagerank last:
# a timeout truncates the least-load-bearing evidence.
CONFIGS = {
    "index": config_index,
    "q1": config_q1,
    "q15": config_q15,
    "q9": config_q9,
    "auction": config_auction,
    "pagerank": config_pagerank,
}
GATED = ["index", "q1", "q15", "q9", "auction"]


# --------------------------------------------------------------------------
# measurement harness
# --------------------------------------------------------------------------


# Every measured span consumes FRESH churn ticks — replaying the same
# delta batches would retract rows twice (negative multiplicities:
# outside the differential contract, and visibly wrong under
# TopK/DISTINCT/fixpoint workloads).
K_SPAN = 32  # ticks per span-scan dispatch
N_TPUT_SPANS = 2
N_P99 = 16
# pagerank's WITH MUTUALLY RECURSIVE body (while_loop) nested inside
# the span program's scan+switch is a compile hazard; it measures on
# the per-step path (its number is excluded from the throughput gate).
SPAN_CONFIGS = {"index", "q1", "q15", "q9", "auction"}


def _hydrate(df, name: str, hydrate_fn) -> None:
    """Hydrate via span-scan dispatches where supported (one dispatch
    and readback per K_SPAN steps), per-step otherwise. The FIRST step
    goes per-step: it carries the side-table extras, whose batch shapes
    differ from the empty placeholders of later steps (a span stacks
    per-step inputs, so shapes must match within a span)."""
    batches = hydrate_fn()
    if batches:
        df.run_steps(batches[:1], defer_check=True)
        batches = batches[1:]
    if name in SPAN_CONFIGS and len(batches) >= K_SPAN:
        n_h = len(batches) - len(batches) % K_SPAN
        for i in range(0, n_h, K_SPAN):
            df.run_span(batches[i : i + K_SPAN])
        batches = batches[n_h:]
    if batches:
        df.run_steps(batches, defer_check=True)
    _block(df.output.base.diff)


def probe(name: str) -> None:
    """Run hydration + the full churn sequence with SYNCHRONOUS overflow
    checks (tier growth allowed) on the CPU platform — row-count-driven
    growth is platform-independent, and CPU compiles are cheap at every
    intermediate tier — then print the final tiers as JSON."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    df, hydrate_fn, churn = CONFIGS[name]()
    df.run_steps(hydrate_fn())  # sync: grows tiers as needed
    # Tier growth stabilizes within the first few dozen churn ticks;
    # the measure path re-probes on overflow, so a shorter probe span
    # is safe (CPU fixpoint configs pay seconds per tick).
    total = min(
        WARMUP + N_TPUT_SPANS * K_SPAN + N_P99,
        int(os.environ.get("BENCH_PROBE_TICKS", "48")),
    )
    span = []
    t0 = df.time
    for i in range(total):
        inp, _n = churn(i, t0 + i)
        span.append(inp)
    df.run_steps(span)  # sync: grows tiers as needed
    print(json.dumps(snapshot_tiers(df)))


def _freshness_summary(label: str) -> dict:
    """Steady-state wallclock-lag summary (ISSUE 15) for one config's
    timed window, read off the process-global freshness recorder —
    the same nearest-rank quantiles mz_wallclock_lag_summary serves,
    so bench emissions and the introspection relation cross-check."""
    from materialize_tpu.coord.freshness import FRESHNESS

    agg = {"p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0, "samples": 0}
    for (df, _rep), s in FRESHNESS.summary().items():
        if df != label:
            continue
        agg["samples"] += s["samples"]
        agg["p50_ms"] = max(agg["p50_ms"], s["p50_ms"])
        agg["p99_ms"] = max(agg["p99_ms"], s["p99_ms"])
        agg["max_ms"] = max(agg["max_ms"], s["max_ms"])
    return agg


def _bank_report(hydrate: dict | None = None):
    """Program-bank counters for this process (ISSUE 16): None when no
    bank is configured (the default), else the bank snapshot —
    hits/misses/stores/entries/seconds_recovered — plus the hydration
    window's cold-vs-banked split when the caller measured one. The
    "bank" key's shape is pinned in tests/test_bench_trace.py."""
    from materialize_tpu.compile.bank import get_bank

    bank = get_bank()
    if bank is None:
        return None
    out = bank.snapshot()
    if hydrate is not None:
        out["hydrate"] = hydrate
    return out


def compaction_bench(
    ticks: int = 160, rows_per_tick: int = 256
) -> dict:
    """Tick p99 with ``compaction_mode=inline`` vs ``background``,
    side by side (ISSUE 20): the same churn workload appends through
    the production tick path (``auto_compaction=True`` writer over
    FileBlob + SqliteConsensus, so the inline mode's merge reads /
    blob write / swap land INSIDE the timed tick), then with the
    background service, where the tick only enqueues a request and the
    merge runs off-path. The counted compaction stats ride along so
    the p99 delta is attributable: inline books its merges/blob-writes
    as merges_inline, background as merges_background with off_path_s
    of worker time the serving path never saw."""
    import shutil
    import tempfile

    from materialize_tpu.repr.schema import Column, ColumnType, Schema
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )
    from materialize_tpu.storage.persist.compactor import (
        STATS,
        compaction_service,
        reset_compaction_service,
    )
    from materialize_tpu.utils.dyncfg import COMPUTE_CONFIGS

    schema = Schema(
        [Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)]
    )
    rng = np.random.default_rng(7)
    # One pre-generated workload, replayed identically in both modes.
    work = [
        (
            rng.integers(0, 1 << 20, rows_per_tick).astype(np.int64),
            rng.integers(0, 1 << 30, rows_per_tick).astype(np.int64),
        )
        for _ in range(ticks)
    ]
    out: dict = {}
    for mode in ("inline", "background"):
        tmp = tempfile.mkdtemp(prefix=f"compaction-bench-{mode}-")
        reset_compaction_service()
        STATS.reset()
        COMPUTE_CONFIGS.update({"compaction_mode": mode})
        try:
            client = PersistClient(
                FileBlob(os.path.join(tmp, "blob")),
                SqliteConsensus(os.path.join(tmp, "consensus.db")),
                auto_compaction=True,
            )
            writer = client.open_writer("cbench", schema)
            lat = []
            t_all = _time.perf_counter()
            for t, (ks, vs) in enumerate(work):
                time_col = np.full(rows_per_tick, t, np.uint64)
                diff = np.ones(rows_per_tick, np.int64)
                t0 = _time.perf_counter()
                writer.compare_and_append(
                    [ks, vs], [None, None], time_col, diff, t, t + 1
                )
                lat.append(_time.perf_counter() - t0)
            wall_s = _time.perf_counter() - t_all
            if mode == "background":
                compaction_service().drain(timeout=30.0)
            tot = STATS.totals()
            out[mode] = {
                "tick_p50_ms": round(
                    1000.0 * float(np.percentile(lat, 50)), 3
                ),
                "tick_p99_ms": round(
                    1000.0 * float(np.percentile(lat, 99)), 3
                ),
                "tick_max_ms": round(1000.0 * max(lat), 3),
                "wall_s": round(wall_s, 3),
                "requests": tot["requests"],
                "merges_inline": tot["merges_inline"],
                "merges_background": tot["merges_background"],
                "blob_writes_inline": tot["blob_writes_inline"],
                "blob_writes_background": tot["blob_writes_background"],
                "off_path_s": round(tot["off_path_s"], 3),
                "final_batches": len(
                    writer.machine.reload().batches
                ),
            }
        finally:
            COMPUTE_CONFIGS.update({"compaction_mode": None})
            reset_compaction_service()
            STATS.reset()
            shutil.rmtree(tmp, ignore_errors=True)
    inl, bg = out["inline"]["tick_p99_ms"], out["background"]["tick_p99_ms"]
    out["p99_speedup"] = round(inl / bg, 2) if bg > 0 else None
    return out


def measure(name: str, tiers: dict | None) -> None:
    """Honest-mode measurement: enter the post-readback regime FIRST
    (pre-readback 'async' dispatch queues without executing and its
    drain costs seconds per dispatch — PERF_NOTES round 5), then
    hydrate and measure span-scan dispatches with truthful blocking.
    Prints one JSON result line."""
    import jax
    import jax.numpy as jnp

    np.asarray(jnp.zeros((1,)) + 1)  # one tiny readback: honest mode
    df, hydrate_fn, churn = CONFIGS[name]()
    if tiers is not None:
        apply_tiers(df, tiers)
    # Bank accounting around the hydrate window: with a bank (--bank
    # DIR or MZ_PROGRAM_BANK), the second run of a config hydrates
    # from banked executables — the cold-vs-banked hydrate_s delta IS
    # the compile wall the bank kills.
    from materialize_tpu.compile.bank import get_bank

    _pbank = get_bank()
    _pre = dict(_pbank.stats) if _pbank is not None else None
    # Hydration (snapshot load + its compiles + the blocking drain) is
    # setup, not steady state: it is timed and REPORTED SEPARATELY,
    # never inside the throughput or p99 windows (BENCH_r05's
    # index_p99_ms 76184 was this backlog drain booked as a step).
    t_h = _time.perf_counter()
    _hydrate(df, name, hydrate_fn)
    hyd_overflow = df.check_flags()
    hydrate_s = _time.perf_counter() - t_h
    hyd_bank = None
    if _pbank is not None:
        _h = _pbank.stats["hits"] - _pre["hits"]
        _m = _pbank.stats["misses"] - _pre["misses"]
        hyd_bank = {
            "bank_hits": _h,
            "bank_misses": _m,
            "mode": "banked" if (_h and not _m) else "cold",
            "hydrate_s": round(hydrate_s, 3),
        }

    use_spans = name in SPAN_CONFIGS
    t0 = df.time
    total = WARMUP + N_TPUT_SPANS * K_SPAN + N_P99
    span, counts = [], []
    for i in range(total):
        inp, n = churn(i, t0 + i)
        span.append(inp)
        counts.append(n)

    # Warmup (compiles the span program when hydration didn't share
    # the shape) — also timed out-of-band as the compile proxy. Span
    # configs warm up THROUGH the executor so the exact jit variant
    # the timed window runs (donated on TPU backends — the jit cache
    # key includes donation) is compiled here, not inside the window.
    ex = None
    if use_spans:
        from materialize_tpu.render.span_exec import SpanExecutor

        ex = SpanExecutor(df)
        # Freshness records key on the config name so the summary in
        # this emission matches --trace and the smoke gate.
        ex.freshness_label = name
    t_w = _time.perf_counter()
    if use_spans:
        ex.submit(span[:WARMUP])
        ex.sync()
    else:
        df.run_steps(span[:WARMUP], defer_check=True)
    _block(df.output.base.diff)
    warmup_s = _time.perf_counter() - t_w
    # Drop warmup-span lag records (compiles inflate them): the
    # freshness summary below is steady-state only (ISSUE 15).
    from materialize_tpu.coord.freshness import FRESHNESS

    FRESHNESS.forget(name)

    pos = WARMUP
    span_trace = None
    if use_spans:
        # Pipelined span window (ISSUE 7, the default): dispatch span
        # K+1 before reading span K's boundary flags, so the host-side
        # inter-span gap (churn prep, packing, upload) overlaps device
        # execution and each span pays exactly ONE readback. The
        # window wall-clock is the honest steady-state: the device
        # never drains between spans.
        ex.trace.clear()
        n_upd = 0
        t0 = _time.perf_counter()
        for _ in range(N_TPUT_SPANS):
            chunk = span[pos : pos + K_SPAN]
            n_upd += sum(counts[pos : pos + K_SPAN])
            pos += K_SPAN
            ex.submit(chunk)
        ex.sync()
        ups = n_upd / (_time.perf_counter() - t0)
        st = ex.stats()
        span_trace = {
            "readbacks_per_span": st["readbacks_per_span"],
            "donated": st["donated"],
            "host_gap_ms": [r["host_gap_ms"] for r in ex.trace],
            "upload_ms": [r["upload_ms"] for r in ex.trace],
            "dispatch_ms": [r["dispatch_ms"] for r in ex.trace],
            "readback_wait_ms": [
                r["readback_wait_ms"] for r in ex.trace
            ],
        }
    else:
        best_ups = 0.0
        for _ in range(N_TPUT_SPANS):
            chunk = span[pos : pos + K_SPAN]
            n_upd = sum(counts[pos : pos + K_SPAN])
            pos += K_SPAN
            t0 = _time.perf_counter()
            deltas = df.run_steps(chunk, defer_check=True)
            _block(deltas[-1])
            best_ups = max(
                best_ups, n_upd / (_time.perf_counter() - t0)
            )
        ups = best_ups

    # Per-step serving latency: single-step dispatch+block round trips,
    # STEADY-STATE ONLY — hydration and compiles are reported in
    # hydrate_s / warmup_s above, never in this window. Each single
    # includes the host's dispatch+block cost, reported as-is (not
    # re-measured on this machine). The first two singles are
    # UNTIMED: they compile the per-step program (span measurement
    # uses the span program) and flush compaction alignment.
    lat = []
    for j, inp in enumerate(span[pos : pos + N_P99]):
        t0 = _time.perf_counter()
        d = df.run_steps([inp], defer_check=True)
        _block(d[-1])
        if j >= 2:
            lat.append(_time.perf_counter() - t0)
    p99 = 1000.0 * float(np.percentile(lat, 99))

    # ---- readbacks -------------------------------------------------------
    overflowed = (
        df.check_flags()
        or hyd_overflow
        or bool(ex is not None and ex.overflows)
    )
    # Validity wants "the MV is non-empty", not all rows: to_rows() on
    # the 2^20-row index config would build millions of Python tuples.
    df._compact_now()
    state_rows = int(np.asarray(df.output.base.count).sum())
    nonempty = state_rows > 0
    print(
        json.dumps(
            {
                "ups": ups,
                "p99": p99,
                "hydrate_s": round(hydrate_s, 3),
                "warmup_s": round(warmup_s, 3),
                "steady_steps": len(lat),
                "valid": (not overflowed) and nonempty,
                "overflowed": bool(overflowed),
                "state_rows": state_rows if name == "index" else None,
                "pipelined": bool(use_spans),
                "span_trace": span_trace,
                # Wallclock-lag summary of the timed span window
                # (ISSUE 15); all-zero for non-span configs, which
                # never ride the span-commit recording path.
                "freshness": _freshness_summary(name),
                # ISSUE 16: program-bank counters (None = bank off)
                # with the hydrate window's cold-vs-banked split.
                "bank": _bank_report(hyd_bank),
                # ISSUE 20: persist tick p99 with compaction inline
                # vs off-path, side by side (counted attribution).
                "compaction": compaction_bench(ticks=120),
            }
        )
    )


# --------------------------------------------------------------------------
# serving-plane concurrency bench (ISSUE 6 tentpole c): hundreds of
# simulated clients point-looking-up a maintained index through the
# O(result) read plane, batched vs serial per-peek dispatch.
# --------------------------------------------------------------------------

SERVE_CLIENTS = int(os.environ.get("BENCH_SERVE_CLIENTS", "200"))
SERVE_DURATION_S = float(os.environ.get("BENCH_SERVE_DURATION_S", "4"))
SERVE_ROWS = int(os.environ.get("BENCH_SERVE_ROWS", "20000"))
SERVE_KEYS = int(os.environ.get("BENCH_SERVE_KEYS", "4096"))


def serve_bench(
    clients: int,
    duration: float,
    replicas: int = 1,
    serial_mode: bool = True,
) -> dict:
    """Measure read QPS against one maintained index at ``clients``
    concurrency, in two modes at EQUAL concurrency on the same host:

      batched — fast-path lookups fanned into one stacked device
                gather per batch window (one dispatch round trip
                shared by the batch);
      serial  — peek_batching off: one dispatch per peek (what a
                per-peek read plane pays).

    Reports QPS, p50/p99 client latency, batch occupancy, and shed
    counts per mode, plus the speedup. Runs on whatever accelerator is
    configured; CPU numbers are valid for the ratio (the amortized
    fixed cost is dispatch+protocol overhead on every backend).

    With ``replicas`` > 1 (ISSUE 19) the bench runs the elastic
    topology — N subprocess replicas behind lag-routed reads — and the
    emitted JSON carries the per-replica routing distribution plus the
    routed/broadcast/avoided/failover counters, so a sweep can show
    where reads actually landed rather than inferring it from QPS."""
    import threading

    import jax  # noqa: F401 — backend init before the clients hammer

    from materialize_tpu.coord.coordinator import Coordinator
    from materialize_tpu.coord.peek import ServerBusy
    from materialize_tpu.server.environmentd import (
        _free_port,
        spawn_replica,
    )
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )

    import tempfile

    # Hundreds of client THREADS simulate sessions in one interpreter;
    # the default 5ms GIL switch interval turns every batch-completion
    # wake wave into ~5ms-per-thread serialization — a harness
    # artifact, not a serving-plane cost. Tighten it for the bench.
    sys.setswitchinterval(0.0005)
    deadline_total = _time.monotonic() + min(
        TOTAL_BUDGET_S, 4 * duration + 120
    )
    tmp = tempfile.mkdtemp(prefix="mtpu-serve-")
    # SUBPROCESS replicas (the production topology): the gather
    # programs and result decode run outside this process, so hundreds
    # of client threads aren't fighting the serving loop for one GIL.
    procs = []
    ports = []
    for i in range(replicas):
        port = _free_port()
        procs.append(spawn_replica(tmp, port, f"r{i}"))
        ports.append(port)
    coord = Coordinator(
        PersistClient(
            FileBlob(tmp + "/blob"),
            SqliteConsensus(tmp + "/consensus.db"),
        ),
        tick_interval=None,
    )
    for i, port in enumerate(ports):
        coord.add_replica(f"r{i}", ("127.0.0.1", port))
    coord.execute("CREATE TABLE kv (k BIGINT, v BIGINT)")
    coord.copy_in_rows(
        "kv",
        ("k", "v"),
        [[str(i % SERVE_KEYS), str(i)] for i in range(SERVE_ROWS)],
    )
    coord.execute("CREATE VIEW kvv AS SELECT * FROM kv")
    coord.execute("CREATE INDEX kvi ON kvv")
    # Serving-mode tuning: reads within one serving tick share a
    # selected timestamp (bounded staleness vs per-peek consensus
    # reads; invalidated by writes), a short batch window, and a deep
    # in-flight pipeline (the replica coalesces in-flight batches into
    # MERGE_CAP-probe gathers anyway, so depth costs no extra compiles).
    coord.update_config(
        {
            "peek_ts_cache_ms": 5.0,
            "peek_batch_window_ms": 0.5,
            "peek_max_inflight": 16,
            "peek_max_batch": 128,
        }
    )
    # Hydrate the dataflow + compile the gather programs out-of-band.
    coord.execute("SELECT * FROM kvv WHERE k = 0")
    coord.fast_peek_values("kvv", (0, 0), (0, 1))
    # Elastic topology: wait until every replica is serving-capable so
    # the routed distribution reflects a fully hydrated fleet, not a
    # hydration race (the laggards would rank last and receive nothing
    # for a reason the sweep is not trying to measure).
    df_name = coord.peekable["kvv"]  # the index dataflow reads route to
    hydrate_deadline = _time.monotonic() + 90
    while (
        len(coord.controller.serving_replicas(df_name)) < replicas
        and _time.monotonic() < hydrate_deadline
    ):
        _time.sleep(0.2)
    serving_at_start = len(coord.controller.serving_replicas(df_name))

    def probe_for(i: int) -> tuple:
        # Full index-key lookup (the index is keyed by the whole row —
        # the reference fast path's "literal constraints on the index
        # key"): served by the hash-lane searchsorted point program,
        # whose per-probe device cost is O(log n), so batching
        # amortizes the WHOLE dispatch, not just the socket hop.
        return (i % SERVE_KEYS, i)

    def warm_burst(n: int) -> None:
        """Concurrent untimed lookups: compiles the pow2 batch-tier
        gather programs this mode will hit (a cold XLA compile inside
        the measured window would be booked as serving latency — the
        same hydrate/warmup separation the throughput bench keeps)."""
        ts = [
            threading.Thread(
                target=lambda i=i: coord.fast_peek_values(
                    "kvv", probe_for(i), (0, 1)
                ),
                daemon=True,
            )
            for i in range(n)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)

    def run_mode(batching: bool) -> dict:
        coord.update_config({"peek_batching": batching})
        for _ in range(3):
            warm_burst(min(clients, 96))
        stats0 = coord.controller.peek_stats()
        lats: list = []
        lock = threading.Lock()
        stop_at = [0.0]
        start = threading.Barrier(clients + 1)

        def client(tid: int):
            rng = np.random.default_rng(tid)
            my: list = []
            start.wait()
            while _time.monotonic() < stop_at[0]:
                i = int(rng.integers(SERVE_ROWS))
                t0 = _time.perf_counter()
                try:
                    rows = coord.fast_peek_values(
                        "kvv", probe_for(i), (0, 1)
                    )
                except ServerBusy:
                    # Shed: counted once, by the batcher's stats.
                    continue
                my.append(_time.perf_counter() - t0)
                assert rows, f"row {i} missing"
            with lock:
                lats.extend(my)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        stop_at[0] = _time.monotonic() + duration
        t_start = _time.perf_counter()
        start.wait()
        for t in threads:
            t.join(duration + 60)
        wall = _time.perf_counter() - t_start
        stats1 = coord.controller.peek_stats()
        batches = stats1["batches"] - stats0["batches"]
        probes = stats1["probes"] - stats0["probes"]
        arr = np.asarray(lats) if lats else np.asarray([float("nan")])
        return {
            "mode": "batched" if batching else "serial",
            "qps": round(len(lats) / wall, 1),
            "requests": len(lats),
            "p50_ms": round(1000 * float(np.percentile(arr, 50)), 3),
            "p99_ms": round(1000 * float(np.percentile(arr, 99)), 3),
            "batch_occupancy": round(probes / batches, 2)
            if batches
            else 0.0,
            "batches": batches,
            # Authoritative shed count is the batcher's (the client
            # counter observes the same events; summing them would
            # double-count).
            "shed": stats1["shed"] - stats0["shed"],
        }

    # Best-of-2 spans per mode (the protocol measure() already uses
    # for throughput: noise on a shared/loaded host only ever LOWERS a
    # span's number, so max-of-spans is the honest steady-state).
    batched = run_mode(True)
    if _time.monotonic() < deadline_total:
        second = run_mode(True)
        if second["qps"] > batched["qps"]:
            batched = second
    if serial_mode:
        serial = (
            run_mode(False)
            if _time.monotonic() < deadline_total
            else {"mode": "serial", "qps": 0.0, "skipped": True}
        )
        if serial.get("qps") and _time.monotonic() < deadline_total:
            second = run_mode(False)
            if second["qps"] > serial["qps"]:
                serial = second
    else:
        serial = {"mode": "serial", "qps": 0.0, "skipped": True}
    speedup = (
        batched["qps"] / serial["qps"] if serial.get("qps") else None
    )
    snap = coord.controller.routing_snapshot()
    total_routed = sum(snap["per_replica"].values()) or 1
    result = {
        "mode": "serve",
        "clients": clients,
        "duration_s": duration,
        "rows": SERVE_ROWS,
        "keys": SERVE_KEYS,
        "replicas": replicas,
        "serving_at_start": serving_at_start,
        "batched": batched,
        "serial": serial,
        "speedup_batched_vs_serial": (
            round(speedup, 2) if speedup else None
        ),
        # Where reads actually landed (ISSUE 19): dispatch counts per
        # replica plus the share, and the routed/broadcast/avoided/
        # failover counters — the observable behind the read-scaling
        # cost model in doc/perf.md.
        "routing": {
            "routed": snap["routed"],
            "broadcast": snap["broadcast"],
            "avoided": snap["avoided"],
            "failovers": snap["failovers"],
            "fallback_broadcasts": snap["fallback_broadcasts"],
            "per_replica": snap["per_replica"],
            "share": {
                r: round(n / total_routed, 3)
                for r, n in sorted(snap["per_replica"].items())
            },
        },
        "valid": bool(
            speedup and speedup >= 10.0
            if serial_mode
            else batched["qps"] > 0
        ),
    }
    _emit(result)
    coord.shutdown()
    for proc in procs:
        proc.terminate()
    for proc in procs:
        try:
            proc.wait(timeout=10)
        except Exception:
            proc.kill()
    return result


def serve_scaling_sweep(
    clients: int, duration: float, max_replicas: int
) -> None:
    """`bench.py --serve ... --replicas N` (ISSUE 19): the read-scaling
    sweep — the SAME batched workload at 1..N replicas, one JSON line
    per point with QPS and the per-replica routing distribution, plus
    a final aggregate with the N-vs-1 QPS ratio. Routing concentrates
    reads on the least-lagged replica, so the distribution (not an
    assumed uniform spread) is the honest scaling story; the serial
    mode is skipped per point (the batched-vs-serial ratio is the
    single-replica bench's claim, not this sweep's)."""
    points = []
    for n in range(1, max_replicas + 1):
        res = serve_bench(
            clients, duration, replicas=n, serial_mode=False
        )
        points.append(
            {
                "replicas": n,
                "qps": res["batched"]["qps"],
                "p99_ms": res["batched"].get("p99_ms"),
                "shed": res["batched"].get("shed"),
                "per_replica": res["routing"]["per_replica"],
                "share": res["routing"]["share"],
                "failovers": res["routing"]["failovers"],
            }
        )
    base = points[0]["qps"]
    _emit(
        {
            "mode": "serve-scaling",
            "clients": clients,
            "duration_s": duration,
            "max_replicas": max_replicas,
            "points": points,
            "qps_ratio_n_vs_1": (
                round(points[-1]["qps"] / base, 2) if base else None
            ),
            "valid": all(p["qps"] > 0 for p in points),
        }
    )


# --------------------------------------------------------------------------
# push-plane fan-out bench (ISSUE 11 deliverable gate): thousands of
# concurrent SUBSCRIBE sessions over ONE shared dataflow under live
# ingest churn — delta-delivery latency vs subscriber count, with the
# one-readback-per-span invariant and the single-install sharing fact
# counted in the JSON.
# --------------------------------------------------------------------------

SUBSCRIBE_SCHEMA_VERSION = 1
SUBSCRIBE_SUBS = int(os.environ.get("BENCH_SUBSCRIBE_SUBS", "1000"))
SUBSCRIBE_DURATION_S = float(
    os.environ.get("BENCH_SUBSCRIBE_DURATION_S", "8")
)
SUBSCRIBE_CONSUMER_THREADS = int(
    os.environ.get("BENCH_SUBSCRIBE_CONSUMERS", "24")
)


def subscribe_bench(subs: int, duration: float) -> None:
    """Measure push delivery at ``subs`` concurrent SUBSCRIBE sessions
    over ONE query dataflow while a writer churns the base table.

    The structural claims, all counted in the emitted JSON:

      dataflow_installs == 1   — N same-query SUBSCRIBEs share one
                                 dataflow (the hub's expr-fingerprint
                                 sharing);
      readbacks_per_span == 1.0 — each committed span window is read
                                 back from the sink shard ONCE and
                                 fanned out host-side (a per-session
                                 tail regression makes this N);
      delivery p50/p99         — enqueue-to-pop latency across every
                                 session's every chunk (the freshness
                                 a dashboard at fan-out N observes).

    Runs the production topology (subprocess replica) so the dataflow
    steps outside the consumer GIL."""
    import tempfile
    import threading

    import jax  # noqa: F401 — backend init before the storm

    from materialize_tpu.coord.coordinator import Coordinator
    from materialize_tpu.coord.peek import ServerBusy
    from materialize_tpu.server.environmentd import (
        _free_port,
        spawn_replica,
    )
    from materialize_tpu.storage.persist import (
        FileBlob,
        PersistClient,
        SqliteConsensus,
    )

    sys.setswitchinterval(0.0005)
    tmp = tempfile.mkdtemp(prefix="mtpu-subscribe-")
    port = _free_port()
    proc = spawn_replica(tmp, port, "r0")
    coord = Coordinator(
        PersistClient(
            FileBlob(tmp + "/blob"),
            SqliteConsensus(tmp + "/consensus.db"),
        ),
        tick_interval=None,
    )
    coord.add_replica("r0", ("127.0.0.1", port))
    coord.execute(
        "CREATE TABLE kv (k BIGINT NOT NULL, v BIGINT NOT NULL)"
    )
    coord.execute("INSERT INTO kv VALUES (0, 0)")
    coord.update_config(
        {
            "subscribe_max_sessions": subs + 64,
            # Sessions drain continuously; the bound exists to prove
            # backpressure, not to trip during the measured window.
            "subscribe_queue_depth": 1_000_000,
        }
    )
    sub_sql = "SUBSCRIBE TO (SELECT k, v FROM kv WHERE k >= 0)"
    t_join0 = _time.monotonic()
    sessions = []
    shed = 0
    for _ in range(subs):
        try:
            sessions.append(coord.execute(sub_sql).subscription)
        except ServerBusy:
            shed += 1
    join_s = _time.monotonic() - t_join0
    # Wait for the shared dataflow's hydration window to reach every
    # session (the join itself is host-only; the one install hydrates
    # out-of-band).
    deadline = _time.monotonic() + 120.0
    while (
        any(s.frontier == 0 for s in sessions)
        and _time.monotonic() < deadline
    ):
        for s in sessions:
            s.pop_ready()
        _time.sleep(0.01)

    stop = threading.Event()
    lats: list = []
    delivered = [0]
    lock = threading.Lock()

    def consumer(my_sessions):
        my_lats = []
        my_rows = 0
        while not stop.is_set():
            idle = True
            for s in my_sessions:
                chunks = s.pop_ready()
                now = _time.monotonic()
                for _kind, events, _up, stamp in chunks:
                    if events:
                        my_lats.append(now - stamp)
                        my_rows += len(events)
                    idle = False
            if idle:
                _time.sleep(0.001)
        with lock:
            lats.extend(my_lats)
            delivered[0] += my_rows

    nthreads = max(1, min(SUBSCRIBE_CONSUMER_THREADS, len(sessions)))
    groups = [sessions[i::nthreads] for i in range(nthreads)]
    threads = [
        threading.Thread(target=consumer, args=(g,), daemon=True)
        for g in groups
    ]
    snap0 = coord.subscribe_hub.snapshot()
    for t in threads:
        t.start()
    # Ingest churn: inserts + occasional deletes through group commit.
    rng = np.random.default_rng(0)
    ticks = 0
    rows_written = 0
    t0 = _time.monotonic()
    stop_at = t0 + duration
    while _time.monotonic() < stop_at:
        vals = ", ".join(
            f"({int(rng.integers(64))}, {int(rng.integers(1 << 20))})"
            for _ in range(8)
        )
        coord.execute(f"INSERT INTO kv VALUES {vals}")
        rows_written += 8
        if ticks % 7 == 6:
            coord.execute(
                f"DELETE FROM kv WHERE k = {int(rng.integers(64))}"
            )
        ticks += 1
    wall = _time.monotonic() - t0
    # Drain: let every session reach the final frontier.
    final = coord._table_writers["kv"].upper
    deadline = _time.monotonic() + 60.0
    while (
        any(s.frontier < final for s in sessions)
        and _time.monotonic() < deadline
    ):
        _time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(30)
    caught_up = sum(1 for s in sessions if s.frontier >= final)
    snap1 = coord.subscribe_hub.snapshot()
    readbacks = snap1["readbacks"] - snap0.get("readbacks", 0)
    spans = snap1["spans"] - snap0.get("spans", 0)
    rps = readbacks / spans if spans else 0.0
    arr = (
        np.asarray(lats) if lats else np.asarray([float("nan")])
    )
    installs = snap1["installs"]
    out = {
        "mode": "subscribe",
        "schema_version": SUBSCRIBE_SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "subscribers": len(sessions),
        "requested_subscribers": subs,
        "duration_s": round(wall, 2),
        "join_s": round(join_s, 2),
        "admission_shed": shed,
        "dataflow_installs": installs,
        "shared_joins": snap1["shared_joins"],
        "shared_tails": len(snap1["tails"]),
        "readbacks": readbacks,
        "spans": spans,
        "readbacks_per_span": round(rps, 4),
        "naive_readbacks_avoided": spans * max(len(sessions) - 1, 0),
        "ingest_ticks": ticks,
        "rows_written": rows_written,
        "updates_per_s": round(rows_written / wall, 1) if wall else 0,
        "deltas_delivered": delivered[0],
        "chunks_measured": len(lats),
        "delivery_p50_ms": round(
            1000 * float(np.percentile(arr, 50)), 3
        ),
        "delivery_p99_ms": round(
            1000 * float(np.percentile(arr, 99)), 3
        ),
        "slow_consumer_sheds": sum(s.sheds for s in sessions),
        "sessions_caught_up": caught_up,
        "valid": bool(
            installs == 1
            and spans > 0
            and abs(rps - 1.0) < 1e-9
            and caught_up == len(sessions)
            and len(sessions) == subs
        ),
    }
    _emit(out)
    for s in sessions:
        s.close()
    coord.shutdown()
    proc.terminate()
    proc.wait(timeout=10)


# --------------------------------------------------------------------------
# span timeline trace (ISSUE 7 deliverable gate): pipelined vs serial
# span execution of identical churn, with per-span
# upload/dispatch/readback timestamps, a readbacks-per-span counter,
# and host-gap accounting — proving device occupancy (not round-trip count)
# is the limiting factor.
# --------------------------------------------------------------------------

TRACE_SCHEMA_VERSION = 1
TRACE_SPANS = int(os.environ.get("BENCH_TRACE_SPANS", "6"))
TRACE_TICKS = int(os.environ.get("BENCH_TRACE_TICKS", "8"))


def _trace_smoke_config():
    """Tiny synthetic index config for the tier-1 CPU --trace smoke
    lane: a maintained bare-Get index over (k, v) under
    duplicate/retraction churn — seconds on CPU, exercises the full
    span pipeline (slot-ring ingest, on-device compaction cadence,
    double-buffered dispatch)."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import Dataflow
    from materialize_tpu.repr.batch import Batch
    from materialize_tpu.repr.schema import Column, ColumnType, Schema

    sch = Schema(
        (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
    )
    df = Dataflow(
        mir.Get("src", sch), out_levels=3, out_slots=4,
        state_cap=1 << 15,
    )
    df._compact_every = 4
    df._compact_ratio = 4
    # Pre-size the base run for the churn's reachable distinct rows
    # (the checked-in-tiers discipline measure() follows): an overflow
    # mid-window replays the window and invalidates the trace.
    df._grow_for(("out", 2), target=1 << 14)
    df._remake_jit()
    rng = np.random.default_rng(42)

    def hydrate():
        return []

    def churn(i: int, t: int):
        n = 96
        k = rng.integers(0, 1024, n).astype(np.int64)
        v = rng.integers(0, 16, n).astype(np.int64)
        d = rng.choice(np.asarray([1, 1, 1, -1]), n).astype(np.int64)
        return (
            {
                "src": Batch.from_numpy(
                    sch, [k, v], np.uint64(t), d, capacity=256
                )
            },
            n,
        )

    return df, hydrate, churn


def _trace_mk(name: str):
    if name == "smoke":
        return _trace_smoke_config
    return CONFIGS[name]


def _trace_window(
    mode: str, mk, spans_n: int, ticks: int, tiers: dict | None = None
) -> dict:
    """One measured window of ``spans_n`` spans (plus one untimed
    warmup/compile span) in the given mode. Fresh dataflow per call —
    churn ticks must never replay into the same state. ``tiers``
    applies the checked-in capacity tiers first (a mid-window
    overflow replays the window and invalidates the trace)."""
    import jax  # noqa: F401

    from materialize_tpu.render.span_exec import SpanExecutor

    df, hydrate_fn, churn = mk()
    if tiers is not None:
        apply_tiers(df, tiers)
    batches = hydrate_fn()
    if batches:
        df.run_steps(batches)  # sync: tier growth allowed
    t0 = df.time
    spans, counts = [], []
    idx = 0
    for _s in range(spans_n + 1):
        sp, c = [], 0
        for _i in range(ticks):
            inp, n = churn(idx, t0 + idx)
            idx += 1
            sp.append(inp)
            c += n
        spans.append(sp)
        counts.append(c)

    if mode == "pipelined":
        from materialize_tpu.coord.freshness import FRESHNESS

        ex = SpanExecutor(df)
        ex.submit(spans[0])  # warmup: compiles the span program
        ex.sync()
        df.check_flags()
        ex.trace.clear()
        # Drop the warmup span's lag record (compile time is not
        # maintenance lag): the window's freshness summary below
        # covers ONLY the timed steady-state spans (ISSUE 15).
        FRESHNESS.forget(ex.freshness_label)
        r0 = df._readbacks
        t_start = _time.perf_counter()
        for sp in spans[1:]:
            ex.submit(sp)
        ex.sync()
        wall = _time.perf_counter() - t_start
        trace = list(ex.trace)
        readbacks = df._readbacks - r0
        donated = ex.donate
        overflow = ex.overflows > 0 or df.check_flags()
        fresh = _freshness_summary(ex.freshness_label)
    else:
        # Serial baseline: dispatch, then BLOCK on this span's own
        # boundary readback before preparing the next span — the
        # device drains during every host-side gap.
        df.run_span(spans[0])
        df.read_flags_snapshot(df.flags_snapshot())
        df.check_flags()
        trace = []
        donated = False
        r0 = df._readbacks
        t_start = _time.perf_counter()
        for i, sp in enumerate(spans[1:]):
            g0 = _time.perf_counter()
            df.run_span(sp)
            g1 = _time.perf_counter()
            snap = df.flags_snapshot()
            df.read_flags_snapshot(snap)
            g2 = _time.perf_counter()
            trace.append(
                {
                    "span": i,
                    "ticks": ticks,
                    "host_gap_ms": 0.0,
                    "window_sync_ms": 0.0,
                    "upload_ms": 0.0,
                    "dispatch_ms": round((g1 - g0) * 1e3, 3),
                    "readback_wait_ms": round((g2 - g1) * 1e3, 3),
                    "readbacks": 1,
                    "overflow": False,
                    "donated": False,
                }
            )
        wall = _time.perf_counter() - t_start
        readbacks = df._readbacks - r0
        overflow = df.check_flags()
        # Serial mode drives run_span directly (no SpanExecutor), so
        # nothing feeds the freshness recorder: all-zero summary.
        fresh = {
            "p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0, "samples": 0
        }

    host_ms = sum(
        r["host_gap_ms"] + r["upload_ms"] + r["dispatch_ms"]
        for r in trace
    )
    # Window-boundary syncs are device wait (blocking validation
    # readbacks), never host/overlap work.
    wait_ms = sum(
        (r["readback_wait_ms"] or 0.0)
        + r.get("window_sync_ms", 0.0)
        for r in trace
    )
    wall_ms = wall * 1e3
    return {
        "ups": round(sum(counts[1:]) / wall, 1),
        "wall_s": round(wall, 4),
        "spans": trace,
        "readbacks": readbacks,
        "readbacks_per_span": round(readbacks / max(spans_n, 1), 3),
        "donated": donated,
        "overflow": bool(overflow),
        "freshness": fresh,
        "gap_accounting": {
            # host_ms: inter-span host work (prep + upload + dispatch);
            # device_wait_ms: time blocked on span boundaries;
            # overlapped_ms: host work hidden under device execution
            # (serial components minus actual wall — 0 when nothing
            # overlaps).
            "host_ms": round(host_ms, 3),
            "device_wait_ms": round(wait_ms, 3),
            "wall_ms": round(wall_ms, 3),
            "overlapped_ms": round(
                max(0.0, host_ms + wait_ms - wall_ms), 3
            ),
        },
    }


def _trace_tolerance() -> float:
    """Pipelined-vs-serial parity band for the trace gate. 0.98 (2%
    noise) in general; 0.90 on a single-core CPU host, where the host
    loop and the XLA threadpool compete for the same core — pipelining
    cannot win wall-clock there and scheduler interleaving alone
    swings spans by several percent."""
    import jax

    if jax.default_backend() == "cpu" and (os.cpu_count() or 1) <= 1:
        return 0.90
    return 0.98


def trace_bench(name: str) -> None:
    """Emit the per-span timeline trace JSON for one config (or the
    'smoke' synthetic): pipelined vs serial at identical churn,
    best-of-2 windows per mode (noise only ever lowers a window)."""
    import jax
    import jax.numpy as jnp

    np.asarray(jnp.zeros((1,)) + 1)  # honest mode first
    tiers = None
    if name != "smoke":
        if name not in SPAN_CONFIGS:
            raise SystemExit(
                f"--trace {name}: config is excluded from span "
                f"execution (SPAN_CONFIGS = {sorted(SPAN_CONFIGS)})"
            )
        if os.path.exists(TIERS_PATH):
            with open(TIERS_PATH) as f:
                tiers = json.load(f).get(name)
    mk = _trace_mk(name)

    # The whole trace run is ONE statement-style trace (ISSUE 12): the
    # id lands in the JSON so mz_trace_spans / exported spans can be
    # correlated with this emission.
    from materialize_tpu.utils.compile_ledger import LEDGER
    from materialize_tpu.utils.trace import TRACER

    trace_id = TRACER.new_trace()

    # INTERLEAVED best-of-2 windows per mode (p, s, p, s): external
    # load on a shared host then biases both modes alike instead of
    # whichever mode ran while the machine was busy. Windows run
    # UNDER the minted trace context so every span recorded during
    # them (the debug-level span_exec.commit cadence) carries the
    # emitted trace_id — mz_trace_spans / the export filter by it.
    from materialize_tpu.utils.trace import TRACER as _T

    best: dict = {}
    with _T.adopt({"t": trace_id, "s": 0}):
        for mode in ("pipelined", "serial", "pipelined", "serial"):
            r = _trace_window(
                mode, mk, TRACE_SPANS, TRACE_TICKS, tiers
            )
            if mode not in best or r["ups"] > best[mode]["ups"]:
                best[mode] = r
    pipelined = best["pipelined"]
    serial = best["serial"]
    speedup = (
        pipelined["ups"] / serial["ups"] if serial["ups"] else None
    )
    out = {
            "mode": "trace",
            "schema_version": TRACE_SCHEMA_VERSION,
            "config": name,
            "backend": jax.default_backend(),
            "ticks_per_span": TRACE_TICKS,
            "spans_per_mode": TRACE_SPANS,
            "pipelined": pipelined,
            "serial": serial,
            "speedup_pipelined_vs_serial": (
                round(speedup, 3) if speedup else None
            ),
            # ISSUE 12: the statement-trace id of this run, the
            # compile ledger's wall-clock attribution (the compile
            # wall item 4's program bank must kill, quantified per
            # run), and the Chrome/Perfetto export of the span
            # timeline (load perfetto_path at https://ui.perfetto.dev).
            "trace_id": trace_id,
            "compiles": LEDGER.summary(),
            # ISSUE 15: steady-state wallclock-lag quantiles of the
            # best pipelined window — the per-config freshness figure
            # dashboards and tests/test_bench_trace.py pin.
            "freshness": pipelined["freshness"],
            # The deliverable gate: exactly one readback per span AND
            # pipelined steady-state throughput at least serial's. On
            # CPU, host and "device" share cores, so the honest
            # expectation is parity within measurement noise
            # (best-of-2 windows absorb most of it); on a single-core
            # host the two compete for ONE core and scheduling noise
            # dominates, so the parity band widens. Where the host's
            # round trip bounds a span, the overlap is a real speedup.
            # ISSUE 16: program-bank counters for this trace run
            # (None = bank off — the default; --bank / MZ_PROGRAM_BANK
            # turns it on and warm reruns serve compiles from it).
            "bank": _bank_report(),
            "noise_tolerance": _trace_tolerance(),
            "valid": bool(
                pipelined["readbacks_per_span"] == 1.0
                and not pipelined["overflow"]
                and not serial["overflow"]
                and speedup is not None
                and pipelined["ups"]
                >= _trace_tolerance() * serial["ups"]
            ),
    }
    out["perfetto_path"] = _export_perfetto(name, out)
    _emit(out)


def _export_perfetto(name: str, out: dict) -> str | None:
    """Write the Chrome/Perfetto trace-event export of this trace run
    next to the JSON emission (scripts/trace_export.py is the shared
    converter). Never fails the bench — export is a convenience."""
    try:
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts"))
        import trace_export

        path = os.environ.get(
            "BENCH_TRACE_CHROME",
            os.path.join(os.getcwd(), f"TRACE_{name}.chrome.json"),
        )
        chrome = trace_export.bench_trace_to_chrome(out)
        if trace_export.validate_chrome_trace(chrome):
            return None
        return trace_export.write_chrome_trace(path, chrome)
    except Exception:
        return None


# --------------------------------------------------------------------------
# multichip mode (ISSUE 9): SPMD smoke bench with the comm census
# --------------------------------------------------------------------------

MULTICHIP_SCHEMA_VERSION = 1
MULTICHIP_SPANS = int(os.environ.get("BENCH_MULTICHIP_SPANS", "4"))
MULTICHIP_TICKS = int(os.environ.get("BENCH_MULTICHIP_TICKS", "16"))


def _multichip_smoke_config(mesh):
    """Sharded analog of _trace_smoke_config: a maintained bare-Get
    index SPMD over the worker mesh, big-state enough that the
    prover-gated append-slot ring engages (ISSUE 9) — the multi-chip
    O(delta) ingest path under duplicate/retraction churn."""
    from materialize_tpu.expr import relation as mir
    from materialize_tpu.render.dataflow import ShardedDataflow
    from materialize_tpu.repr.batch import Batch
    from materialize_tpu.repr.schema import Column, ColumnType, Schema

    sch = Schema(
        (Column("k", ColumnType.INT64), Column("v", ColumnType.INT64))
    )
    sdf = ShardedDataflow(
        mir.Get("src", sch), mesh, name="mc-index",
        out_levels=3, out_slots=8, state_cap=1 << 14,
    )
    rng = np.random.default_rng(42)

    def churn(i: int, t: int):
        n = 96
        k = rng.integers(0, 1024, n).astype(np.int64)
        v = rng.integers(0, 16, n).astype(np.int64)
        d = rng.choice(np.asarray([1, 1, 1, -1]), n).astype(np.int64)
        return (
            {
                "src": Batch.from_numpy(
                    sch, [k, v], np.uint64(t), d, capacity=256
                )
            },
            n,
        )

    return sdf, churn


def multichip_bench(config: str = "smoke") -> None:
    """bench.py --multichip: the SPMD span bench. Emits one JSON with
    the per-step AND per-span communication census (collective count +
    per-device bytes, from the shard-spec prover's walk of the actual
    step program), the prover's ingest verdict, and per-span records
    carrying the `donated` flag (honestly false on the SPMD path —
    shard_map carries don't alias through donate_argnums) — so a
    multi-chip run is self-evidencing about both its comm volume and
    its ingest mode. Schema pinned by tests/test_bench_trace.py."""
    import jax
    import jax.numpy as jnp

    n_devices = len(jax.devices())
    if n_devices < 2:
        _emit(
            {
                "mode": "multichip",
                "schema_version": MULTICHIP_SCHEMA_VERSION,
                "config": config,
                "skipped": True,
                "reason": f"need >= 2 devices, have {n_devices}",
            }
        )
        return
    if config != "smoke":
        raise SystemExit(
            "--multichip supports the 'smoke' config for now (the "
            "TPCH sharded scaling curve is ROADMAP item 2's bench "
            "follow-up)"
        )
    np.asarray(jnp.zeros((1,)) + 1)  # honest mode first
    from materialize_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    sdf, churn = _multichip_smoke_config(mesh)
    report = sdf.sharding_report()
    ticks = MULTICHIP_TICKS
    idx = 0
    spans = []
    total_updates = 0
    wall = 0.0
    prev_overflow = False
    for s in range(MULTICHIP_SPANS + 1):
        inputs, count = [], 0
        for _ in range(ticks):
            inp, n = churn(idx, idx)
            inputs.append(inp)
            idx += 1
            count += n
        t0 = _time.perf_counter()
        sdf.run_steps(inputs, defer_check=True)
        snap = sdf.flags_snapshot()
        overflow = bool(sdf.read_flags_snapshot(snap))
        t1 = _time.perf_counter()
        # The flags register OR-accumulates across the whole run, so
        # the per-span field marks the span where the flag FIRST rose
        # (a warmup-span overflow shows only in the run-level gate);
        # overflows after the first are not span-attributable.
        newly_overflowed = overflow and not prev_overflow
        prev_overflow = overflow
        if s == 0:
            continue  # warmup span: compiles the step program
        spans.append(
            {
                "span": s - 1,
                "ticks": ticks,
                "wall_ms": round((t1 - t0) * 1e3, 3),
                "updates": count,
                # SPMD carries cannot alias through shard_map specs:
                # honest false until that lands, pinned in the schema
                # so an A/B trace can prove which mode ran.
                "donated": bool(sdf._defer_donated),
                "overflow": newly_overflowed,
            }
        )
        total_updates += count
        wall += t1 - t0
    overflowed = sdf.check_flags()
    per_step = report["census"]
    per_span = {
        "collectives": per_step["collectives"] * ticks,
        "bytes": per_step["bytes"] * ticks,
        "kinds": {
            k: n * ticks for k, n in per_step["kinds"].items()
        },
    }
    _emit(
        {
            "mode": "multichip",
            "schema_version": MULTICHIP_SCHEMA_VERSION,
            "config": config,
            "backend": jax.default_backend(),
            "n_devices": n_devices,
            "workers": sdf.num_shards,
            "skipped": False,
            "ingest_mode": report["ingest_mode"],
            "spmd_safe": bool(report["safe"]),
            "comm_census": {
                "per_step": per_step,
                "per_span": per_span,
                "ticks_per_span": ticks,
            },
            "ticks_per_span": ticks,
            "spans_per_run": MULTICHIP_SPANS,
            "spans": spans,
            "ups": round(total_updates / wall, 1) if wall else None,
            # The deliverable gate: the prover verdicted the sharded
            # slot ring safe, it actually engaged, and no span
            # overflowed (stale tiers invalidate the window).
            "valid": bool(
                report["safe"]
                and report["ingest_mode"] == "append_slot"
                and not overflowed
                and all(not r["overflow"] for r in spans)
            ),
        }
    )


# --------------------------------------------------------------------------
# driver entry: per-config subprocesses, streaming output
# --------------------------------------------------------------------------


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _run_sub(args: list, timeout: float, env: dict | None = None):
    """Run a bench subprocess; return (parsed-last-line | None, err)."""
    try:
        out = subprocess.run(
            [sys.executable, __file__] + args,
            capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout:.0f}s"
    if out.returncode != 0:
        tail = out.stderr.strip().splitlines()
        return None, (tail[-1] if tail else f"rc={out.returncode}")
    lines = out.stdout.strip().splitlines()
    if not lines:
        return None, "no output"
    try:
        return json.loads(lines[-1]), None
    except json.JSONDecodeError:
        return None, f"unparseable output: {lines[-1][:200]}"


def _probe_config(name: str, timeout: float | None = None):
    return _run_sub(
        ["--probe", name],
        timeout=timeout if timeout is not None else CONFIG_TIMEOUT_S,
    )


def _aggregate(results: dict, partial: bool) -> dict:
    gated_done = [n for n in GATED if n in results]
    min_ups = (
        min(results[n]["ups"] for n in gated_done) if gated_done else 0.0
    )
    p99s = [r["p99"] for r in results.values() if np.isfinite(r["p99"])]
    p99 = max(p99s) if p99s else float("inf")
    state_rows = (results.get("index") or {}).get("state_rows") or 0
    # Validity is defined over the GATE configs; a skipped or broken
    # non-gated extra (pagerank) is reported but cannot flip the gate.
    valid = (
        not partial
        and all(n in results and results[n]["valid"] for n in GATED)
        and state_rows >= 1 << 20
    )
    extras = {}
    for n, r in results.items():
        extras[f"{n}_updates_per_sec"] = round(r["ups"], 1)
        extras[f"{n}_p99_ms"] = (
            round(r["p99"], 3) if np.isfinite(r["p99"]) else None
        )
        if r.get("hydrate_s") is not None:
            extras[f"{n}_hydrate_s"] = r["hydrate_s"]
        if r.get("warmup_s") is not None:
            extras[f"{n}_warmup_s"] = r["warmup_s"]
        if r.get("error"):
            extras[f"{n}_error"] = r["error"]
    extras["skipped_configs"] = sorted(
        n for n, r in results.items() if r.get("skipped")
    )
    return {
        "metric": "gate_min_maintained_updates_per_sec",
        "value": round(min_ups, 1),
        "unit": "updates/s",
        "vs_baseline": round(min_ups / BASELINE_UPDATES_PER_SEC, 4),
        "index_state_rows": state_rows,
        "p99_step_ms": round(p99, 3) if np.isfinite(p99) else None,
        "p99_vs_baseline_100ms": (
            round(p99 / BASELINE_P99_MS, 4) if np.isfinite(p99) else None
        ),
        "valid": bool(valid),
        "partial": partial,
        "configs_done": sorted(results),
        **extras,
    }


def _probe_timeout_marker(err: str, budget: float) -> dict:
    """The checked-in record of a probe that blew its budget: later
    sweeps SKIP the config cleanly instead of burning the full
    per-config cap re-discovering the same timeout (q9/pagerank on
    1-core hosts — ROADMAP 1/4c). Deleting the entry (or a successful
    `--reprobe <name>` on a faster host) re-enables the config."""
    return {
        "probe_timeout": round(float(budget), 1),
        "error": err,
    }


def reprobe(names=None) -> None:
    """Regenerate bench_tiers.json with CPU-platform probes. An
    explicit reprobe RETRIES configs with a recorded probe_timeout
    (the operator asked); a fresh timeout re-records the marker."""
    tiers = {}
    if os.path.exists(TIERS_PATH):
        with open(TIERS_PATH) as f:
            tiers = json.load(f)
    for name in names or CONFIGS:
        t0 = _time.perf_counter()
        result, err = _probe_config(name)
        if result is None:
            print(f"probe {name}: FAILED ({err})", file=sys.stderr)
            if err and err.startswith("timeout"):
                tiers[name] = _probe_timeout_marker(
                    err, CONFIG_TIMEOUT_S
                )
            else:
                continue
        else:
            tiers[name] = result
            print(
                f"probe {name}: ok in "
                f"{_time.perf_counter() - t0:.1f}s",
                file=sys.stderr,
            )
        # Write incrementally: a later config's slow/failed probe must
        # not discard the finished ones.
        with open(TIERS_PATH, "w") as f:
            json.dump(tiers, f, indent=1, sort_keys=True)


def main() -> None:
    # Global prefix flag: `bench.py --bank DIR --measure NAME` points
    # this process (and every probe/measure subprocess — they inherit
    # the env var) at a persistent AOT program bank (ISSUE 16), so
    # tier probes and repeat measurements reuse banked executables
    # instead of re-paying the compile wall.
    if len(sys.argv) > 2 and sys.argv[1] == "--bank":
        from materialize_tpu.compile.bank import (
            BANK_ENV_VAR, configure_bank,
        )

        os.environ[BANK_ENV_VAR] = sys.argv[2]
        configure_bank(sys.argv[2])
        del sys.argv[1:3]
    if len(sys.argv) > 1 and sys.argv[1] == "--probe":
        probe(sys.argv[2])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--measure":
        with open(TIERS_PATH) as f:
            tiers = json.load(f)[sys.argv[2]]
        if isinstance(tiers, dict) and tiers.get("probe_timeout"):
            raise SystemExit(
                f"--measure {sys.argv[2]}: bench_tiers.json records a "
                "probe_timeout for this config (no usable tiers); "
                f"re-probe with `bench.py --reprobe {sys.argv[2]}` on "
                "a host that can afford the probe"
            )
        measure(sys.argv[2], tiers)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--reprobe":
        reprobe(sys.argv[2:] or None)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--compaction":
        ticks = int(sys.argv[2]) if len(sys.argv) > 2 else 160
        print(json.dumps(compaction_bench(ticks=ticks)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--trace":
        trace_bench(sys.argv[2] if len(sys.argv) > 2 else "smoke")
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--multichip":
        multichip_bench(
            sys.argv[2] if len(sys.argv) > 2 else "smoke"
        )
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--serve":
        argv = sys.argv[2:]
        reps = 1
        if "--replicas" in argv:
            i = argv.index("--replicas")
            reps = int(argv[i + 1])
            del argv[i : i + 2]
        clients = int(argv[0]) if len(argv) > 0 else SERVE_CLIENTS
        duration = (
            float(argv[1]) if len(argv) > 1 else SERVE_DURATION_S
        )
        if reps > 1:
            serve_scaling_sweep(clients, duration, reps)
        else:
            serve_bench(clients, duration)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--subscribe":
        subs = (
            int(sys.argv[2]) if len(sys.argv) > 2 else SUBSCRIBE_SUBS
        )
        duration = (
            float(sys.argv[3])
            if len(sys.argv) > 3
            else SUBSCRIBE_DURATION_S
        )
        subscribe_bench(subs, duration)
        return

    stored: dict = {}
    if os.path.exists(TIERS_PATH):
        with open(TIERS_PATH) as f:
            stored = json.load(f)

    results: dict = {}
    deadline = _time.monotonic() + TOTAL_BUDGET_S

    def _skip_config(name: str, error: str) -> None:
        # Skip (listed in skipped_configs), keep emitting per-config +
        # aggregate lines so the run always ends on a parseable gate
        # line.
        results[name] = {
            "ups": 0.0, "p99": float("inf"), "valid": False,
            "state_rows": None, "skipped": True,
            "error": error,
        }
        _emit({"config": name, **results[name]})
        _emit(
            _aggregate(results, partial=len(results) < len(CONFIGS))
        )

    def _skip_exhausted(name: str) -> None:
        _skip_config(name, "total time budget exhausted")

    for name in CONFIGS:
        remaining = deadline - _time.monotonic()
        if remaining < MIN_CONFIG_S:
            _skip_exhausted(name)
            continue
        if isinstance(stored.get(name), dict) and stored[name].get(
            "probe_timeout"
        ):
            # A previous sweep already burned a probe budget proving
            # this config cannot probe on this class of host: skip
            # cleanly (seconds, not the 900s cap) and keep the final
            # gate line parseable. `bench.py --reprobe <name>` retries.
            _skip_config(
                name,
                "probe_timeout recorded in bench_tiers.json "
                f"({stored[name].get('error', 'probe timeout')})",
            )
            continue
        budget = min(CONFIG_TIMEOUT_S, remaining)
        err = "no checked-in tiers"
        if name not in stored and name in GATED:
            # Gate configs are worth a CPU probe even at driver time;
            # non-gated ones without checked-in tiers are skipped fast
            # so they can never eat the driver's budget.
            tiers_line, err = _probe_config(name, timeout=budget)
            if tiers_line is not None:
                stored[name] = tiers_line
            elif err and err.startswith("timeout"):
                # Record the blown budget so the NEXT sweep (and the
                # next config's budget math) never pays it again.
                stored[name] = _probe_timeout_marker(err, budget)
            if name in stored:
                with open(TIERS_PATH, "w") as f:
                    json.dump(stored, f, indent=1, sort_keys=True)
        if isinstance(stored.get(name), dict) and stored[name].get(
            "probe_timeout"
        ):
            _skip_config(name, f"probe_timeout: {err}")
            continue
        if name not in stored:
            results[name] = {
                "ups": 0.0, "p99": float("inf"), "valid": False,
                "state_rows": None, "error": f"probe failed: {err}",
            }
        else:
            # Recompute after the (possibly long) probe above: reusing
            # the pre-probe budget here could spend ~2x the remaining
            # total and overshoot TOTAL_BUDGET_S.
            remaining = deadline - _time.monotonic()
            if remaining < MIN_CONFIG_S:
                _skip_exhausted(name)
                continue
            budget = min(CONFIG_TIMEOUT_S, remaining)
            r, err = _run_sub(["--measure", name], budget)
            if r is not None and r.get("overflowed"):
                # Stale checked-in tiers (a code change moved capacity
                # behavior): re-probe on CPU, re-measure once — but
                # ONLY inside the remaining budget. No floors: granting
                # "at least 30s" here could overshoot TOTAL_BUDGET_S
                # and reproduce the rc=124/no-gate-line failure this
                # budget exists to prevent; with the budget gone the
                # overflowed (invalid) result stands.
                remaining = deadline - _time.monotonic()
                tiers_line = None
                if remaining >= MIN_CONFIG_S:
                    tiers_line, perr = _probe_config(
                        name, timeout=remaining
                    )
                if tiers_line is not None:
                    stored[name] = tiers_line
                    with open(TIERS_PATH, "w") as f:
                        json.dump(stored, f, indent=1, sort_keys=True)
                    remaining = deadline - _time.monotonic()
                    if remaining >= MIN_CONFIG_S:
                        r2, err2 = _run_sub(
                            ["--measure", name],
                            min(CONFIG_TIMEOUT_S, remaining),
                        )
                        if r2 is not None:
                            r, err = r2, err2
            if r is None:
                results[name] = {
                    "ups": 0.0, "p99": float("inf"), "valid": False,
                    "state_rows": None, "error": err,
                }
            else:
                results[name] = r
        _emit({"config": name, **results[name]})
        done_all = len(results) == len(CONFIGS)
        _emit(_aggregate(results, partial=not done_all))


if __name__ == "__main__":
    main()
