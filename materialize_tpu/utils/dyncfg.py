"""dyncfg: typed dynamic configuration flags.

Analog of the reference's ``mz_dyncfg`` (``dyncfg/src/lib.rs:10-30``):
typed ``Config``s registered into a shared ``ConfigSet``; values can be
updated at runtime (from a file, SQL, or the controller) and every
component reads the current value at use sites. Updates propagate to
replicas IN COMMAND-STREAM ORDER via ``UpdateConfiguration`` (see
coord/protocol.py), so all workers flip a flag at the same point in the
update stream (compute_state.rs:46-59 discipline).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from . import lockcheck


@dataclass
class Config:
    """One typed flag: name, default, help. Bind into a ConfigSet to
    read values."""

    name: str
    default: Any
    help: str = ""

    def __call__(self, config_set: "ConfigSet"):
        return config_set.get(self.name)

    def register(self, config_set: "ConfigSet") -> "Config":
        config_set.add(self)
        return self


class ConfigSet:
    def __init__(self):
        from .lockcheck import tracked_lock

        self._configs: dict[str, Config] = {}
        self._values: dict[str, Any] = {}
        self._lock = tracked_lock("dyncfg")

    def add(self, cfg: Config) -> None:
        with self._lock:
            existing = self._configs.get(cfg.name)
            if existing is not None and existing.default != cfg.default:
                raise ValueError(
                    f"config {cfg.name!r} re-registered with a "
                    "different default"
                )
            self._configs[cfg.name] = cfg

    def get(self, name: str):
        with self._lock:
            lockcheck.shared_read("dyncfg.values")
            if name in self._values:
                return self._values[name]
            return self._configs[name].default

    def update(self, values: dict) -> dict:
        """Apply updates (unknown keys are kept — a newer process may
        know them); returns the full current value map for shipping to
        replicas."""
        with self._lock:
            lockcheck.shared_write("dyncfg.values")
            for k, v in values.items():
                if v is None:
                    # None RESETS to the default (a stored None would
                    # permanently mask it).
                    self._values.pop(k, None)
                    continue
                cfg = self._configs.get(k)
                if cfg is not None:
                    # Coerce to the default's type (flags arrive as
                    # strings from SQL/files).
                    t = type(cfg.default)
                    if t is bool and isinstance(v, str):
                        v = v.lower() in ("true", "on", "1", "yes")
                    elif not isinstance(v, t):
                        v = t(v)
                self._values[k] = v
            return dict(self._values)

    def current(self) -> dict:
        with self._lock:
            lockcheck.shared_read("dyncfg.values")
            out = {n: c.default for n, c in self._configs.items()}
            out.update(self._values)
            return out


# The compute-layer flag set (compute-types/src/dyncfgs.rs analog).
COMPUTE_CONFIGS = ConfigSet()

ENABLE_TEMPORAL_FILTERS = Config(
    "enable_temporal_filters", True,
    "render mz_now() predicates as scheduled temporal filters",
).register(COMPUTE_CONFIGS)

DELTA_JOIN_MIN_INPUTS = Config(
    "delta_join_min_inputs", 3,
    "minimum join breadth for the delta-join plan (vs linear)",
).register(COMPUTE_CONFIGS)

ARRANGEMENT_COMPACTION_BATCHES = Config(
    "arrangement_compaction_batches", 8,
    "shard spine length that triggers background compaction",
).register(COMPUTE_CONFIGS)

COMPACTION_MODE = Config(
    "compaction_mode", "background",
    "where shard compaction runs when a writer's append grows the "
    "spine past arrangement_compaction_batches: 'background' enqueues "
    "to the leased compactor service (storage/persist/compactor.py; "
    "the tick path's entire cost is the O(1) request), 'inline' merges "
    "synchronously on the writer's path (the pre-ISSUE-20 behavior, "
    "kept as the bench comparison baseline), 'off' never triggers "
    "(manual maybe_compact only)",
).register(COMPUTE_CONFIGS)

COMPACTION_LEASE_S = Config(
    "compaction_lease_s", 5.0,
    "compaction lease duration: a crashed compactor's shard is "
    "reclaimable by a successor after this long; the holder renews "
    "before every swap, and epoch fencing rejects a stale holder that "
    "outlived its lease",
).register(COMPUTE_CONFIGS)

PART_TIERING = Config(
    "part_tiering", "auto",
    "batch-part hot/cold tiering: 'auto' keeps recently "
    "written/read decoded parts host-resident up to part_hot_bytes "
    "(LRU eviction to blob-only cold tier, lazy rehydration on first "
    "read), 'all_hot' never evicts, 'all_cold' caches nothing (every "
    "read rehydrates from blob — the worst-case latency baseline)",
).register(COMPUTE_CONFIGS)

PART_HOT_BYTES = Config(
    "part_hot_bytes", 64 << 20,
    "hot-tier budget in encoded part bytes per process (part_tiering="
    "auto); mz_arrangement_sizes' hot/cold byte split reports the "
    "resulting boundary per dataflow",
).register(COMPUTE_CONFIGS)

OPTIMIZER_TYPECHECK = Config(
    "optimizer_typecheck", False,
    "run the MIR typechecker (analysis/typecheck.py) between optimizer "
    "transforms so an invalid plan is blamed on the transform that "
    "produced it (transform/src/typecheck.rs analog); default-on in "
    "the test suite via tests/conftest.py",
).register(COMPUTE_CONFIGS)

FUSED_MERGE = Config(
    "fused_merge", "auto",
    "sorted-merge position search: 'auto' and 'lax' are the fused "
    "lax binary search (one row-gather per iteration) on every "
    "backend; 'unfused' keeps the legacy per-lane gather search "
    "(comparison baseline)",
).register(COMPUTE_CONFIGS)

CACHED_RUN_LANES = Config(
    "cached_run_lanes", True,
    "carry each frozen spine run's stacked sort lanes in the spine "
    "state, computed at fold time and maintained by the merge's own "
    "row-gather — per-step probes and folds then never re-derive "
    "lanes from columns of unchanged runs (round-6 O(delta) work)",
).register(COMPUTE_CONFIGS)

ARRANGEMENT_INGEST_MODE = Config(
    "arrangement_ingest_mode", "auto",
    "spine hot-path ingest: 'append_slot' lands each arranged delta "
    "in a run-0 append slot (O(delta) per step; the ladder's level-0 "
    "fold absorbs the ring on its amortized cadence), 'merge' merges "
    "into run 0 every step (O(run0)); 'auto' picks append_slot for "
    "big-state arrangements (plan/decisions.ingest_mode)",
).register(COMPUTE_CONFIGS)

COMPUTE_RETAIN_HISTORY = Config(
    "compute_retain_history", 32,
    "multiversion window: per-dataflow output-delta history retained "
    "for AS OF reads, in virtual timestamps (the read-policy lag "
    "analog, adapter/src/coord/read_policy.rs)",
).register(COMPUTE_CONFIGS)

# -- the O(result) serving plane (ISSUE 6 / ROADMAP item 3) -------------------

PEEK_FAST_PATH = Config(
    "peek_fast_path", True,
    "serve key-equality lookups and full scans over peekable "
    "(indexed/materialized) relations by row-gathering directly from "
    "the maintained spine (coord/peek.py) instead of rendering a "
    "transient dataflow — O(result) reads, zero installs (the "
    "adapter-layer peek fast path, coord/peek.rs analog)",
).register(COMPUTE_CONFIGS)

PEEK_BATCHING = Config(
    "peek_batching", True,
    "fan concurrent sessions' fast-path lookups against the same "
    "index into ONE stacked device gather per batch window, so the "
    "dispatch round trip is amortized "
    "across all waiting readers; off = one dispatch per peek",
).register(COMPUTE_CONFIGS)

PEEK_BATCH_WINDOW_MS = Config(
    "peek_batch_window_ms", 2.0,
    "batching span tick: how long queued fast-path lookups wait to be "
    "stacked into one device gather (latency floor of a batched read)",
).register(COMPUTE_CONFIGS)

PEEK_MAX_BATCH = Config(
    "peek_max_batch", 64,
    "max probes stacked into one gather dispatch (padded to a pow2 "
    "batch lane so the program compiles once per tier)",
).register(COMPUTE_CONFIGS)

PEEK_QUEUE_DEPTH = Config(
    "peek_queue_depth", 1024,
    "admission control: max fast-path lookups queued for batching; "
    "arrivals beyond this are shed with a clean 'server busy' error "
    "(SQLSTATE 53400 at pgwire, HTTP 503) instead of building an "
    "unbounded backlog",
).register(COMPUTE_CONFIGS)

PEEK_MAX_INFLIGHT = Config(
    "peek_max_inflight", 4,
    "admission control: max batched gather dispatches in flight; the "
    "flusher holds further batches (queue-depth shedding then "
    "backpressures arrivals)",
).register(COMPUTE_CONFIGS)

PEEK_TS_CACHE_MS = Config(
    "peek_ts_cache_ms", 0.0,
    "serving-mode timestamp selection: cache a peekable dataflow's "
    "selected read timestamp for this many milliseconds (invalidated "
    "by writes through this coordinator). 0 = strict (one consensus "
    "read per peek); >0 trades bounded staleness w.r.t. out-of-band "
    "source ticks for not paying a consensus read per peek under "
    "concurrency (reads within one serving tick share a timestamp)",
).register(COMPUTE_CONFIGS)

# -- the async pipelined control plane (ISSUE 7 / ROADMAP item 4) ------------

SPAN_PIPELINING = Config(
    "span_pipelining", True,
    "replica worker loop: step maintained views in SPANS of up to "
    "span_max_ticks ready micro-batches with deferred overflow checks "
    "— the span's ticks dispatch asynchronously and the span commits "
    "with ONE flags readback, overlapped with the NEXT span's ingest "
    "and dispatch (double-buffered: at most one span in flight ahead "
    "of the committed frontier). Off = the per-tick step loop (one "
    "readback per tick)",
).register(COMPUTE_CONFIGS)

SPAN_MAX_TICKS = Config(
    "span_max_ticks", 8,
    "max ready micro-batches dispatched per replica span; the span "
    "commit (frontier advance, subscriber publish, history record) "
    "happens once per span at the boundary readback",
).register(COMPUTE_CONFIGS)

SPAN_WINDOW_SPANS = Config(
    "span_window_spans", 16,
    "pipelined spans per rollback window: the deferred-overflow "
    "checkpoint and input log are retained across this many committed "
    "spans, then validated and cleared (bounds replay memory; the "
    "boundary validation is the window's one extra sync point)",
).register(COMPUTE_CONFIGS)

SPAN_DONATION = Config(
    "span_donation", "auto",
    "donate the step program's carry (operator states, output spine, "
    "err arrangement, device time) to XLA so each step of a span "
    "reuses the previous step's state buffers instead of allocating + "
    "copying state-sized arrays per dispatch. 'auto' = on for TPU "
    "backends; 'off' forces off; 'on' forces on WHERE the backend "
    "honors donation (CPU ignores donate_argnums, and jaxlib crashes "
    "lowering large donated programs on the forced multi-device host "
    "platform; reported state always reflects the EFFECTIVE value). "
    "The rollback checkpoint is CLONED to fresh buffers before the "
    "first donated dispatch of a window — donated buffers are never "
    "read back",
).register(COMPUTE_CONFIGS)

# -- the persistent AOT program bank (ISSUE 16) ------------------------------

PROGRAM_BANK_PATH = Config(
    "program_bank_path", "",
    "directory of the persistent cross-process AOT program bank "
    "(compile/bank.py): every ledger_jit site looks serialized "
    "executables up by (kind, fingerprint, tier) before compiling "
    "and writes misses back. Empty = bank off (dispatch is "
    "byte-identical to the pre-bank hot path). environmentd sets "
    "this to <data-dir>/blob/program_bank; SET propagates it to "
    "replicas like every dyncfg",
).register(COMPUTE_CONFIGS)

ENABLE_ASYNC_COMPILE = Config(
    "enable_async_compile", False,
    "async DDL compile + hot-swap (requires a program bank): CREATE "
    "INDEX / CREATE MATERIALIZED VIEW installs its dataflow in "
    "generic merge mode immediately (correct results, O(run0) "
    "ingest) while a background worker pre-compiles the specialized "
    "program into the bank; the replica hot-swaps at a span boundary "
    "(sync_spans sequencing — no half-applied carry). Surfaced in "
    "EXPLAIN ANALYSIS compiles: pending_swap, the hydration board, "
    "and mz_program_bank",
).register(COMPUTE_CONFIGS)

# -- buffer-provenance / donation safety (ISSUE 8) ---------------------------

BUFFER_SANITIZER = Config(
    "buffer_sanitizer", False,
    "use-after-donate sanitizer: every donated span/step dispatch "
    "records the killed carry leaves in a ledger (weakrefs — never "
    "extends a buffer's lifetime), and guarded read sites "
    "(IndexSource snapshots, multiversion rewinds, operand packing) "
    "raise UseAfterDonateError with the provenance chain naming who "
    "still held the alias. The donation CONTRACT is backend-"
    "independent, so the sanitizer enforces it on CPU too — the test "
    "suite (default ON under `pytest -m analysis`) catches "
    "use-after-donate bugs on hosts where real donation is not even "
    "wired. Production default off (one ledger walk per donated "
    "dispatch)",
).register(COMPUTE_CONFIGS)

RACE_DETECTOR = Config(
    "race_detector", False,
    "happens-before race detector (analysis/racecheck.py): vector-"
    "clock instrumentation layered on lockcheck's tracked-lock "
    "acquire/release hooks plus the declared-shared-state registry "
    "(controller maps, hub session tables, freshness rings, "
    "compile-ledger memory, this dyncfg store), reporting "
    "unsynchronized read/write pairs with both stack chains. Default "
    "ON under `pytest -m analysis` (tests/conftest.py) and in the "
    "check_plans.py --bench race-free gate; production default off "
    "(one module-global None check per declared access, same "
    "discipline as buffer_sanitizer)",
).register(COMPUTE_CONFIGS)

# -- the push serving plane (ISSUE 11 / ROADMAP item 3) ----------------------

SUBSCRIBE_MAX_SESSIONS = Config(
    "subscribe_max_sessions", 10000,
    "admission control for the push plane: max live SUBSCRIBE "
    "sessions across the coordinator; arrivals beyond this are shed "
    "with 'server busy' (SQLSTATE 53400 at pgwire, HTTP 503) instead "
    "of degrading every existing stream",
).register(COMPUTE_CONFIGS)

SUBSCRIBE_QUEUE_DEPTH = Config(
    "subscribe_queue_depth", 8192,
    "per-session delivery queue bound, in rows: a consumer that "
    "cannot drain its deltas this far behind the shared tail is "
    "handled by subscribe_slow_policy instead of buffering without "
    "bound (the hub's queues are the only per-subscriber state)",
).register(COMPUTE_CONFIGS)

SUBSCRIBE_SLOW_POLICY = Config(
    "subscribe_slow_policy", "disconnect",
    "what happens to a subscriber whose queue exceeds "
    "subscribe_queue_depth: 'disconnect' terminates the session with "
    "a retryable error; 'coalesce' drops the queued deltas and "
    "re-delivers a collapsed snapshot at the current frontier (state "
    "transfer — correct for dashboard-class consumers that only need "
    "current state, at the cost of one extra shard read)",
).register(COMPUTE_CONFIGS)

SUBSCRIBE_TAIL_POLL_MS = Config(
    "subscribe_tail_poll_ms", 50.0,
    "shared-tail wait granularity: how long one listen cycle blocks "
    "for the sink shard's upper to advance before re-checking for "
    "retirement (bounds tail-thread teardown latency, NOT delivery "
    "latency — data wakes the listen immediately)",
).register(COMPUTE_CONFIGS)

# -- the observability plane (ISSUE 12) --------------------------------------

TRACE_LEVEL = Config(
    "trace_level", "info",
    "statement-trace recording level (the log_filter system var "
    "analog): 'off' disables span recording entirely, 'error' < "
    "'info' < 'debug'. Statement/command spans record at info; the "
    "per-span pipeline cadence (dispatch, readback-wait, commit, "
    "fold) records at debug so the default level keeps the hot path "
    "recorder-free. Propagates to replicas via UpdateConfiguration "
    "like every dyncfg",
).register(COMPUTE_CONFIGS)

SLOW_STATEMENT_MS = Config(
    "slow_statement_ms", 0.0,
    "slow-statement log threshold in milliseconds: statements whose "
    "end-to-end sequencing exceeds it are recorded (sql, wall ms, "
    "trace_id) in the mz_slow_statements ring and counted in "
    "/metrics. 0 disables (production default: opt in per deployment)",
).register(COMPUTE_CONFIGS)

METRICS_REPORT_MS = Config(
    "metrics_report_ms", 2000.0,
    "how often a replica piggybacks its /metrics sample snapshot on a "
    "Frontiers response (deployment-wide scrape cadence): snapshots "
    "ship at most once per interval and only when some value changed",
).register(COMPUTE_CONFIGS)

FRESHNESS_SLO_MS = Config(
    "freshness_slo_ms", 0.0,
    "per-object wallclock-lag SLO in milliseconds (the freshness "
    "plane, coord/freshness.py): a committed span boundary whose lag "
    "exceeds it increments mz_freshness_breaches_total, and breach "
    "ONSETS append to the bounded mz_freshness_events ring; /api/"
    "readyz reports not-ready while any durable dataflow's latest lag "
    "breaches. 0 disables (production default: opt in per deployment)",
).register(COMPUTE_CONFIGS)

TRANSIENT_PEEK_CACHE = Config(
    "transient_peek_cache", 8,
    "memoize slow-path SELECT dataflows by description fingerprint: "
    "a repeated identical SELECT reuses the installed transient "
    "dataflow (skipping re-render/re-compile) instead of installing a "
    "uniquely-named copy; LRU-capped at this many installs, 0 "
    "disables (PR 1's fingerprint stability exists for exactly this)",
).register(COMPUTE_CONFIGS)

PEEK_ROUTING = Config(
    "peek_routing", "route",
    "read-plane dispatch mode (ISSUE 19): 'route' sends each peek / "
    "batched lookup to the single least-lagged hydrated replica "
    "(duplicate dispatches avoided are counted in "
    "mz_peek_broadcast_avoided_total) and fails over to the next "
    "candidate on disconnect/stall via retry_policy_failover; "
    "'broadcast' restores the legacy fan-out-to-all/first-response-"
    "wins path",
).register(COMPUTE_CONFIGS)

AUTOSCALE_POLICY = Config(
    "autoscale_policy", "",
    "SLO-driven replica autoscaler spec (coord/autoscaler.py), e.g. "
    "'min=1,max=3,up_sustain=2s,down_sustain=10s,cooldown=5s,"
    "headroom=0.25,interval=250ms': sustained mz_freshness_events "
    "breaches spawn a replica (up to max), sustained lag headroom "
    "(every durable dataflow's latest lag under headroom*slo) drains "
    "the most-lagged one (down to min), with cooldown hysteresis; "
    "every decision lands in the mz_autoscale_events ledger. Empty "
    "disables (production default: opt in per deployment)",
).register(COMPUTE_CONFIGS)
