"""Dataflow <-> shard bridges: shard_source and persist_sink.

Analog of ``storage-operators/src/persist_source.rs`` (shard -> dataflow
import, consumed at ``compute/src/render.rs:291``) and the MV persist
sink (``compute/src/sink/materialized_view.rs``): a ``MaintainedView``
reads update chunks from input shards, advances the dataflow one
micro-batch step per chunk, and compare-and-appends the output delta to
the view's shard. Resume is the reference's model exactly (SURVEY.md §5
checkpoint/resume): NO operator-state checkpoint — on restart the
dataflow re-renders and re-hydrates from input-shard snapshots at the
output shard's upper (``hydrate()`` below; sink-less indexes re-hydrate
from the inputs' latest readable time). Since ISSUE 10 this path is the
PROVEN recovery spine, not just the documented one: ``environmentd
--recover`` replays the durable catalog through it, the chaos harness
(testing/chaos.py) SIGKILLs processes mid-span and checks exact
oracles, and reconciliation is a counted invariant (``mz_recovery``
rebuilds == 0 for fingerprint-unchanged dataflows).
"""

from __future__ import annotations

import time as _time
from contextlib import contextmanager

import numpy as np

from ...arrangement.spine import device_nbytes
from ...render.dataflow import Dataflow
from ...repr.batch import Batch, capacity_tier
from ...repr.schema import Schema
from ...utils.metrics import REGISTRY
from ...utils.trace import TRACER
from .client import PersistClient, ReadHandle, WriteHandle
from .machine import TALLY, Fenced, UpperMismatch


class SinkConflict(RuntimeError):
    """The durable sink diverged from this replica's chunking (hydration
    race): the view must be rebuilt from the durable shard."""


class AsOfError(RuntimeError):
    """AS OF timestamp outside the readable multiversion window
    [since, upper). Deliberately NOT a ValueError: the replica's build
    retry loop retries transient compaction races — machine.py's
    dedicated ``CompactionRace``, no longer blanket ValueError, so a
    real codec/caller bug surfaces instead of retrying forever — and a
    bad user timestamp must fail immediately."""


@contextmanager
def persist_phase(name: str, shard_kind: str):
    """A phase of the open span (utils/trace.py) whose counts are what
    persist tallied on this thread while it ran: reloads, state bytes,
    parts, compare-and-sets. ``shard_kind``: ``source`` or ``sink``."""
    mark = TALLY.mark()
    with TRACER.phase(name) as ph:
        try:
            yield ph
        finally:
            ph.add(**TALLY.since(mark, shard_kind))


def updates_to_batch(
    schema: Schema, cols, nulls, time, diff, as_of: int,
    capacity: int | None = None,
) -> Batch:
    """Host update arrays -> device Batch with times forwarded to as_of
    (the step processes one virtual timestamp; logical compaction).

    A fetch that covered only empty upper-advances decodes to ZERO
    column arrays (there were no parts); the batch must still carry the
    declared schema's arity or downstream operators index out of range."""
    n = len(diff)
    if not cols and schema.arity:
        cols = [np.zeros(0, c.dtype) for c in schema.columns]
        nulls = [None] * schema.arity
    return Batch.from_numpy(
        schema,
        cols,
        np.full(n, as_of, np.uint64),
        diff,
        capacity=capacity,
        nulls=nulls,
    )


class ShardSource:
    """Import one shard into a dataflow: snapshot + listen chunks
    (persist_source analog)."""

    def __init__(self, reader: ReadHandle, schema: Schema):
        self.reader = reader
        self.schema = schema
        self.frontier: int | None = None  # set by snapshot()/resume_at()

    def snapshot(self, as_of: int) -> "tuple[Batch, int]":
        _sch, cols, nulls, time, diff = self.reader.snapshot(as_of)
        self.frontier = as_of + 1
        return (
            updates_to_batch(self.schema, cols, nulls, time, diff, as_of),
            as_of,
        )

    def resume_at(self, frontier: int) -> None:
        self.frontier = frontier

    def poll(self, timeout: float = 5.0):
        """Next chunk beyond the frontier, forwarded to the chunk's last
        time. Returns (batch, chunk_time, new_frontier) or None."""
        assert self.frontier is not None, "snapshot()/resume_at() first"
        got = self.reader.listen_next(self.frontier, timeout)
        if got is None:
            return None
        (_sch, cols, nulls, time, diff), new_upper = got
        t = new_upper - 1
        batch = updates_to_batch(self.schema, cols, nulls, time, diff, t)
        self.frontier = new_upper
        return batch, t, new_upper

    def fetch_to(self, target: int) -> Batch:
        """Chunk [frontier, target), forwarded to target-1. Caller must
        have confirmed target <= shard upper."""
        assert self.frontier is not None and target > self.frontier - 1
        with persist_phase("span.fetch", "source") as ph:
            _sch, cols, nulls, time, diff = self.reader.fetch(
                self.frontier, target
            )
            ph.add(rows=len(diff))
        with TRACER.phase("span.upload") as ph:
            batch = updates_to_batch(
                self.schema, cols, nulls, time, diff, target - 1
            )
            if ph:
                ph.add(bytes=device_nbytes(batch))
        self.frontier = target
        return batch


def _host_updates(batch: Batch):
    """Valid rows of a batch as host arrays (cols, nulls, time, diff)."""
    n = int(batch.count)
    cols = [np.asarray(a)[:n] for a in batch.cols]
    nulls = [
        None if nl is None else np.asarray(nl)[:n] for nl in batch.nulls
    ]
    return cols, nulls, np.asarray(batch.time)[:n], np.asarray(
        batch.diff
    )[:n]


def _hist_host(entry):
    """A multiversion-history entry as host update arrays. Entries are
    stored as the step's DEVICE delta batch (the pipelined span path
    records history with zero readbacks — PERF_NOTES round 8) and
    converted lazily on the rare rewind read; pre-existing host-tuple
    entries (SPMD gathers) pass through. Prefer :func:`_hist_host_at`
    when iterating a history list — it memoizes the conversion."""
    if isinstance(entry, tuple):
        return entry
    from ...analysis.donation import guard_read

    # The rewind read is a d2h conversion: under the buffer sanitizer
    # it must prove the retained delta was never donated (history
    # entries are span OUTPUTS, never the carry — an aliased entry
    # here means someone resurrected a donated leaf into history).
    guard_read(entry, "multiversion-history")
    return _host_updates(entry)


def _hist_host_at(history: list, i: int):
    """Host view of ``history[i]``'s update, MEMOIZED in place:
    repeated AS OF rewinds and multiple IndexSource subscribers then
    pay one d2h conversion per entry total, not one per read (each
    conversion is a blocking device-to-host readback)."""
    t, upd = history[i]
    host = _hist_host(upd)
    if host is not upd:
        history[i] = (t, host)
    return host


class IndexSource:
    """Import a live sibling dataflow's output arrangement as an input —
    the TraceManager sharing analog (compute/src/arrangement/manager.rs:33,
    index imports at compute/src/render.rs:384-403): hydration snapshots
    the publisher's device-resident arrangement instead of replaying its
    sources, and each publisher step pushes its output delta to every
    subscriber.

    Implements the ShardSource surface (reader shim with
    machine.reload()/wait_for_upper/expire, snapshot, fetch_to,
    resume_at) so MaintainedView consumes indexes and shards uniformly.
    """

    class _State:
        def __init__(self, since: int, upper: int):
            self.since = since
            self.upper = upper

    class _Reader:
        def __init__(self, src: "IndexSource"):
            self._src = src
            self.machine = self

        def reload(self):
            s = self._src
            # The readable floor is the PUBLISHER's multiversion since:
            # snapshot() rewinds below base_upper-1 within the window.
            return IndexSource._State(
                since=min(s.publisher.since, max(s.base_upper - 1, 0)),
                upper=s.publisher.upper,
            )

        def wait_for_upper(self, frontier: int, timeout: float = 30.0):
            """An upper > frontier. The publisher lives on the SAME
            replica loop, so instead of blocking we actively step it
            forward (its own inputs may still not be there — then
            None, like a shard that never advances)."""
            s = self._src
            deadline = _time.monotonic() + timeout
            while s.publisher.upper <= frontier:
                if _time.monotonic() > deadline:
                    return None
                if not s.publisher.step(
                    timeout=max(deadline - _time.monotonic(), 0.001)
                ):
                    return None
            return s.publisher.upper

        def expire(self) -> None:
            s = self._src
            if s in s.publisher._subscribers:
                s.publisher._subscribers.remove(s)

    def __init__(self, publisher: "MaintainedView", schema: Schema):
        if getattr(publisher.df, "_basic_finalizers", None):
            # The publisher's arrangement carries opaque basic-aggregate
            # digests; a subscriber could never finalize them. The
            # coordinator inlines such views instead of index-importing
            # (coordinator._inline_views); this guard catches direct
            # users.
            raise ValueError(
                "an index over basic aggregates (string_agg/array_agg/"
                "list_agg) cannot be imported by other dataflows"
            )
        self.publisher = publisher
        self.schema = schema
        self.reader = IndexSource._Reader(self)
        # Same-process single-device publishers share arrangements
        # DEVICE-RESIDENT: the base snapshot is the publisher's output
        # spine (compacted in HBM) and per-step deltas are handed over
        # as the very device batches the publisher's step produced —
        # zero host round-trips on the sharing path (round-2 weak #2;
        # the reference's TraceManager shares traces in memory, not
        # through a serialization hop). SPMD publishers gather across
        # workers, so they keep the host path.
        from ...render.dataflow import Dataflow as _SingleDevice

        self._device = type(publisher.df) is _SingleDevice
        self.host_transfers = 0  # observability for tests
        # The base snapshot must be a COMMITTED span boundary: a
        # pipelined publisher may hold an in-flight span whose carry
        # is not yet validated (ISSUE 7 sequencing rule).
        publisher.sync_spans()
        self.base_cloned = False
        if self._device:
            base = publisher.df.output_batch()
            if publisher.donation_requested():
                # Snapshot-at-subscribe (ISSUE 8): the publisher's
                # output spine rides its DONATED span carry — sharing
                # its buffers would hand this subscriber a reference
                # the next donated span kills (the exact aliasing that
                # blocked ROADMAP 4b). Copy-on-share at the subscriber
                # boundary: one state-sized clone HERE, paid only by
                # dataflows that are actually subscribed to AND only
                # when donation is requested — unsubscribed views pay
                # nothing, and the publisher's donation verdict stays
                # provably safe.
                from ...arrangement.spine import clone_state_tree

                base = clone_state_tree(base)
                self.base_cloned = True
            self.base_batch = base
        else:
            self.host_transfers += 1
            self.base = _host_updates(publisher.result_batch())
        self.base_upper = publisher.upper
        # device path: (t, Batch); host path: (t, host update arrays)
        self._pending: list = []
        self.frontier: int | None = None
        publisher._subscribers.append(self)

    def _push(self, t: int, update) -> None:
        self._pending.append((t, update))

    def _take_until(self, target: int):
        taken = [u for t, u in self._pending if t < target]
        self._pending = [
            (t, u) for t, u in self._pending if t >= target
        ]
        return taken

    @staticmethod
    def _concat(parts):
        if not parts:
            return None
        cols = [
            np.concatenate([p[0][i] for p in parts])
            for i in range(len(parts[0][0]))
        ]
        nulls = []
        for i in range(len(parts[0][1])):
            if all(p[1][i] is None for p in parts):
                nulls.append(None)
            else:
                nulls.append(
                    np.concatenate(
                        [
                            p[1][i]
                            if p[1][i] is not None
                            else np.zeros(len(p[3]), dtype=bool)
                            for p in parts
                        ]
                    )
                )
        time = np.concatenate([p[2] for p in parts])
        diff = np.concatenate([p[3] for p in parts])
        return cols, nulls, time, diff

    @staticmethod
    def _forward_times(b: Batch, t: int) -> Batch:
        """Forward every row's time to ``t`` (logical compaction to the
        snapshot/chunk timestamp) — a device-side constant fill; padding
        rows are masked by count downstream."""
        import jax.numpy as jnp

        return b.replace(
            time=jnp.full(b.capacity, t, dtype=jnp.uint64),
            schema=b.schema,
        )

    def _guard(self, tree) -> None:
        """Use-after-donate guard on every device read this subscriber
        performs: the base snapshot and pending deltas must never be
        buffers a publisher's donated span killed (buffer_sanitizer;
        no-op when off)."""
        from ...analysis.donation import guard_read

        guard_read(
            tree,
            f"IndexSource(subscriber of "
            f"{getattr(self.publisher.df, 'name', 'df')!r})",
        )

    def snapshot(self, as_of: int) -> "tuple[Batch, int]":
        if as_of < self.base_upper - 1:
            # Multiversion rewind: the publisher retains a bounded
            # window of output deltas (read-policy lag analog,
            # adapter/src/coord/read_policy.rs); inside it, the base
            # snapshot minus the deltas in (as_of, base_upper)
            # reconstructs the arrangement at as_of.
            pub = self.publisher
            if as_of < pub.since:
                raise AsOfError(
                    f"index import cannot rewind to {as_of}: the "
                    f"publisher's multiversion window is "
                    f"[{pub.since}, {pub.upper})"
                )
            self.frontier = as_of + 1
            if self._device:
                self._guard(self.base_batch)
            parts = [
                _host_updates(self.base_batch)
                if self._device
                else self.base
            ]
            # The rewound-past deltas must ALSO be queued for forward
            # replay: they are folded into the base (not in _pending),
            # and a subscriber stepping past as_of needs them back.
            replay = []
            for hi, (ht, _upd) in enumerate(pub._history):
                if as_of < ht <= self.base_upper - 1:
                    cols, nulls, htime, diff = _hist_host_at(
                        pub._history, hi
                    )
                    parts.append((cols, nulls, htime, np.negative(diff)))
                    replay.append(
                        (
                            ht,
                            updates_to_batch(
                                self.schema, cols, nulls, htime,
                                diff, ht,
                            )
                            if self._device
                            else (cols, nulls, htime, diff),
                        )
                    )
            self._pending = replay + self._pending
            cols, nulls, time, diff = self._concat(parts)
            return (
                updates_to_batch(
                    self.schema, cols, nulls, time, diff, as_of
                ),
                as_of,
            )
        self.frontier = as_of + 1
        if self._device:
            from ...ops.sort import concat_batches

            parts = [self.base_batch] + self._take_until(as_of + 1)
            self._guard(parts)
            b = concat_batches(parts) if len(parts) > 1 else parts[0]
            return (
                self._forward_times(b, as_of).replace(schema=self.schema),
                as_of,
            )
        parts = [self.base] + self._take_until(as_of + 1)
        cols, nulls, time, diff = self._concat(parts)
        return (
            updates_to_batch(
                self.schema, cols, nulls, time, diff, as_of
            ),
            as_of,
        )

    def resume_at(self, frontier: int) -> None:
        self.frontier = frontier

    def fetch_to(self, target: int) -> Batch:
        with TRACER.phase("span.fetch"):
            return self._fetch_to(target)

    def _fetch_to(self, target: int) -> Batch:
        assert self.frontier is not None and target > self.frontier - 1
        parts = self._take_until(target)
        self.frontier = target
        if self._device:
            from ...ops.sort import concat_batches

            if not parts:
                return Batch.empty(self.schema, 256)
            self._guard(parts)
            b = concat_batches(parts) if len(parts) > 1 else parts[0]
            return self._forward_times(b, target - 1).replace(
                schema=self.schema
            )
        got = self._concat(parts)
        if got is None:
            sch = self.schema
            cols = [np.zeros(0, c.dtype) for c in sch.columns]
            got = (
                cols,
                [None] * sch.arity,
                np.zeros(0, np.uint64),
                np.zeros(0, np.int64),
            )
        cols, nulls, time, diff = got
        return updates_to_batch(
            self.schema, cols, nulls, time, diff, target - 1
        )


class _ViewSpanBarrier:
    """What a MaintainedView registers as its dataflow's
    ``_span_barrier``: df-level state reads (``output_batch``/
    ``run_steps``/``peek_errors`` call ``span_barrier()``) then
    commit the view's in-flight span first, instead of relying only
    on the view-level ``sync_spans()`` call sites. ``in_dispatch`` is
    raised around the view's own span dispatch so dispatching never
    self-syncs (which would serialize the index view's double buffer,
    and write a sinked view's validated span before the dispatch it
    is meant to run beneath). The pieces
    the view's pipeline stands on (flags snapshots, one-readback
    commit, window rollback) live in ``_DataflowBase``."""

    __slots__ = ("view", "in_dispatch")

    def __init__(self, view: "MaintainedView"):
        self.view = view
        self.in_dispatch = False

    def sync(self) -> None:
        self.view.sync_spans()


class MaintainedView:
    """An installed dataflow maintained between shards: sources -> step ->
    optional output shard. One shard per source name; with a sink, the
    output shard's upper is the view's write frontier
    (sink/materialized_view_v2.rs analog — self-correcting via
    compare-and-append: on restart a partially written step is retried
    exactly because the upper didn't advance). Without a sink this is an
    INDEX: the output arrangement lives on device, peekable, and the
    frontier is in-memory (restart = full rehydration from inputs, the
    reference's index model). Other dataflows may import the index via
    IndexSource; each step's output delta is pushed to subscribers."""

    def __init__(
        self,
        client: PersistClient,
        dataflow: Dataflow,
        source_shards: dict[str, tuple[str, Schema]],
        output_shard: str | None,
        index_sources: dict[str, "IndexSource"] | None = None,
        replica_id: str = "r0",
        as_of: int | None = None,
    ):
        self.client = client
        self.replica_id = replica_id
        self.df = dataflow
        # Multiversion window (read-policy lag analog,
        # adapter/src/coord/read_policy.rs): retain the last N output
        # deltas as host arrays so reads can rewind to any time in
        # [since, upper). since advances as deltas are evicted.
        from ...utils.dyncfg import COMPUTE_CONFIGS, COMPUTE_RETAIN_HISTORY

        self._history: list = []  # [(t, (cols, nulls, time, diff))]
        self.retain = int(COMPUTE_RETAIN_HISTORY(COMPUTE_CONFIGS))
        self._since = 0
        self._as_of_override = as_of
        # MVs over basic aggregates persist MATERIALIZED VALUES: the
        # sink path finalizes each output delta's digest columns into
        # result strings (retractions resolve against the PRE-step
        # multiset) and dictionary-encodes them, so shard parts carry
        # real strings and readers never see a digest
        # (render/reduce.rs:369 + the materialized-view sink analog).
        self._sink_finalizes = bool(
            output_shard
            and getattr(dataflow, "_basic_finalizers", None)
        )
        self._pre_step_multisets = None
        self._subscribers: list = []
        self.sources = {
            name: ShardSource(client.open_reader(shard), schema)
            for name, (shard, schema) in source_shards.items()
        }
        if index_sources:
            self.sources.update(index_sources)
        self._output_shard = output_shard
        self.writer: WriteHandle | None = (
            client.open_writer(output_shard, dataflow.out_schema)
            if output_shard is not None
            else None
        )
        # The replica-LOCAL processed frontier. Never conflated with the
        # durable sink upper: an active-active sibling may advance the
        # shard ahead of this replica, and stepping from the shard upper
        # would skip inputs locally (stale peeks) and double-count deltas
        # in the sink. Appends behind the durable upper skip benignly
        # (identical content by determinism + 1-timestamp chunks).
        self._upper = 0
        # Pipelined span state (ISSUE 7): the DISPATCHED frontier runs
        # ahead of the committed one by at most one span;
        # `_inflight_span` holds (flags snapshot, [(t, delta)], target,
        # input-arrival monotonic stamp) until its boundary readback
        # commits it. `span_epoch` is the monotone span counter peeks
        # and compaction decisions sequence against (reported with
        # every Frontiers message).
        self._dispatched = 0
        self._inflight_span = None
        self._window_ticks: list = []
        self.span_epoch = 0
        # Input ticks a sinked span gathered for the span AFTER it
        # while the device ran (_step_span_sync): [(t, {name: batch},
        # arrival stamp)], contiguous from `upper` once that span has
        # committed. The sources' frontiers run ahead of `upper` by
        # these, so every stepping entry consumes them first.
        self._kept: list = []
        # A sinked span whose flags are read and whose deltas are not
        # yet written (_step_span_sync leaves one only while `_kept`
        # holds its successor, which the next call dispatches before
        # writing this one): (span record, [(t, delta)], arrival
        # stamp, replayed, prefetched). `_dispatched` runs ahead of
        # `upper` by its ticks; sync_spans() writes it.
        self._validated_span = None
        self._capacity_gauge = REGISTRY.get_or_create(
            "gauge_vec", "mz_dataflow_state_capacity_bytes",
            "bytes of device memory a maintained view's operator state "
            "and output spine reserve (capacities x stored row widths), "
            "as of its last committed span",
            label="dataflow",
        )
        # Register as the dataflow's span barrier: any df-level state
        # read sequences through sync_spans() automatically.
        self._barrier = _ViewSpanBarrier(self)
        dataflow._span_barrier = self._barrier
        # Donation state (ISSUE 8): the buffer-provenance prover's
        # verdict gates whether this view's run_steps span train
        # donates its carry. Recomputed when the sharing structure
        # (subscriber set / donation request) changes, and only at
        # defer-window boundaries — a window keeps its decision.
        self._donation_sig = None
        self._donation_verdict = None
        self._donation_info: dict | None = None
        self._donation_dirty = False
        self.donated_parts: tuple = ()
        # Sharding state (ISSUE 9): the shard-spec prover's report —
        # SPMD-safety verdict of the slot-ring cursors, resolved
        # ingest mode, communication census. Computed once at build
        # (the SPMD render already ran the prover to gate its ingest
        # mode; single-device dataflows report the trivial fact) and
        # piggybacked on the first frontier report, like donation.
        self._sharding_info: dict | None = None
        self._sharding_dirty = False
        try:
            self.hydrate()
        except BaseException:
            self.expire()  # release reader holds of a failed build
            raise
        self._dispatched = self._upper
        # Decide donation NOW so every installed dataflow has a
        # provenance/donation verdict from its very first frontier
        # report (EXPLAIN ANALYSIS / mz_donation must never be blind
        # on an idle dataflow).
        self._span_donation()
        # Same discipline for the sharding verdict (EXPLAIN ANALYSIS
        # `sharding:` / mz_sharding cover every installed dataflow).
        from ...analysis.shard_prop import dataflow_sharding_report

        self._sharding_info = dataflow_sharding_report(self.df)
        self._sharding_dirty = True

    @property
    def upper(self) -> int:
        """This replica's processed frontier: the local output reflects
        input times < upper."""
        return self._upper

    @property
    def since(self) -> int:
        """Earliest readable time: reads AS OF t are servable for
        since <= t < upper (the multiversion window)."""
        return self._since

    def _record_history(self, t: int, out: Batch) -> None:
        """Retain this step's output delta for the multiversion window;
        evicting the oldest delta advances since (logical compaction of
        the window, persist downgrade_since analog)."""
        if self.retain <= 0:
            self._since = t
            return
        # The delta is retained DEVICE-RESIDENT (host conversion is
        # lazy, on the rare AS OF rewind — _hist_host): recording
        # history must not put a d2h readback on the per-tick hot
        # path, or the pipelined span protocol's one-readback-per-span
        # invariant breaks. SPMD deltas arrive as gathered host
        # batches and convert for free.
        self._history.append((t, out))
        while len(self._history) > self.retain:
            evicted_t, _ = self._history.pop(0)
            self._since = evicted_t

    def device_bytes(self) -> dict:
        """Device-resident bytes by component (ISSUE 12: the
        mz_arrangement_sizes byte columns): the output spine's runs /
        ingest slots / cached lanes plus the multiversion history's
        retained device deltas. Pure aval metadata — no device read,
        safe on the frontier-report path."""
        from ...arrangement.spine import device_nbytes

        out = getattr(self.df, "output", None)
        if out is not None and hasattr(out, "device_bytes"):
            bytes_ = dict(out.device_bytes())
        else:
            bytes_ = {
                "runs": device_nbytes(out) if out is not None else 0,
                "slots": 0,
                "lanes": 0,
            }
        bytes_["history"] = device_nbytes(
            [upd for _t, upd in self._history]
        )
        # Batch-part tiering split (ISSUE 20): hot (host-resident in
        # the client's part cache) vs cold (blob-only, rehydrated on
        # first read) encoded bytes over this view's shards — the
        # mz_arrangement_sizes hot/cold columns that drive the
        # part_hot_bytes budget decision. Cached state only; no
        # consensus read on the frontier-report path.
        hot = cold = 0
        # Index imports have a reader SHIM (IndexSource._Reader) with
        # no shard behind it — only real shard sources tier.
        shards = {
            sh
            for s in self.sources.values()
            if hasattr(s, "reader")
            for sh in [getattr(s.reader.machine, "shard", None)]
            if sh is not None
        }
        if self.writer is not None:
            shards.add(self.writer.machine.shard)
        for shard in shards:
            h, c = self.client.tier_split(shard)
            hot += h
            cold += c
        bytes_["part_hot"] = hot
        bytes_["part_cold"] = cold
        return bytes_

    def updates_as_of(self, t: int):
        """Host update arrays (cols, nulls, time, diff) of the
        maintained result rewound to time ``t``: the current result
        plus the NEGATION of every retained delta in (t, upper). Times
        forward to t (logical compaction to the read time)."""
        if getattr(self.df, "_basic_finalizers", None):
            raise AsOfError(
                "AS OF is not supported over basic aggregates "
                "(string_agg/array_agg/list_agg): their digest "
                "accumulators cannot be rewound"
            )
        self.sync_spans()
        if not (self._since <= t < self._upper):
            raise AsOfError(
                f"Timestamp ({t}) is not valid for all inputs: the "
                f"readable window is [{self._since}, {self._upper})"
            )
        parts = [_host_updates(self.result_batch())]
        for hi, (ht, _upd) in enumerate(self._history):
            if ht > t:
                cols, nulls, htime, diff = _hist_host_at(
                    self._history, hi
                )
                parts.append((cols, nulls, htime, np.negative(diff)))
        cols, nulls, _time, diff = IndexSource._concat(parts)
        return cols, nulls, np.full(len(diff), t, np.uint64), diff

    def expire(self) -> None:
        """Release this view's shard read holds (must be called when the
        view is dropped or replaced, or the holds pin compaction forever)."""
        self._kept = []
        if self._validated_span is not None:
            # validated, never written: whoever resumes from the
            # durable upper computes it again from the sources
            self._validated_span = None
            self._dispatched = self._upper
        for s in self.sources.values():
            try:
                s.reader.expire()
            except Exception:
                pass

    # -- rehydration -------------------------------------------------------
    def hydrate(self) -> None:
        """Bring the dataflow to the output's upper.

        Fresh install: as-of selection picks the LATEST readable time,
        ``max(max input since, min input upper - 1)`` (collapse as much
        history into one snapshot step as possible —
        compute-client/src/as_of_selection.rs); if the inputs are all
        empty and uncompacted the dataflow simply starts at 0 and replays
        updates as they arrive. Resume: snapshot inputs at the durable
        upper-1 and rebuild arrangements without re-appending."""
        out_upper = (
            self.writer.machine.reload().upper
            if self.writer is not None
            else 0
        )
        if out_upper == 0:
            sts = [
                s.reader.machine.reload() for s in self.sources.values()
            ]
            max_since = max((st.since for st in sts), default=0)
            min_upper = min((st.upper for st in sts), default=0)
            if self._as_of_override is not None:
                # Explicit AS OF: hydrate at exactly t (as_of_selection
                # honors a user AS OF). Validate against input sinces
                # NOW — a too-old timestamp is a user error, not a
                # transient race to retry.
                as_of = self._as_of_override
                if as_of < max_since:
                    raise AsOfError(
                        f"Timestamp ({as_of}) is not valid for all "
                        f"inputs: less than the as-of frontier "
                        f"{max_since}"
                    )
            else:
                as_of = max(max_since, min_upper - 1)
            if as_of <= 0 and max_since == 0 and self._as_of_override is None:
                # Nothing (or only t=0) ingested and no compaction:
                # replay from scratch, no snapshot step needed.
                for s in self.sources.values():
                    s.resume_at(0)
                self._upper = 0
                return
            # Inputs must be readable at as_of; wait for uppers to pass
            # (can lag when one input is compacted ahead of another).
            for s in self.sources.values():
                if s.reader.wait_for_upper(as_of, timeout=30.0) is None:
                    raise TimeoutError(
                        "input shard upper never passed hydration as_of "
                        f"{as_of}"
                    )
            inputs = {}
            for name, s in self.sources.items():
                b, _ = s.snapshot(as_of)
                inputs[name] = b
            # The one step is compiled for these batches' capacities:
            # an arrangement that one of them feeds is given that tier
            # now, not one overflow and one compile at a time.
            caps = {name: b.capacity for name, b in inputs.items()}
            with TRACER.phase("hydrate.presize") as ph:
                ph.add(
                    arrangements=self.df.presize_for_snapshot(caps),
                    snapshot_capacity=sum(caps.values()),
                )
            self.df.time = as_of
            self.df.step(inputs)
            out = self.result_batch()
            # every run is folded into its base now: what presizing
            # grew for the snapshot goes back to a tick's size
            with TRACER.phase("hydrate.release") as ph:
                ph.add(**self.df.release_snapshot_tiers())
            self._append(out, 0, as_of + 1, as_of)
            self._upper = as_of + 1
            self._since = as_of  # the snapshot collapsed prior history
        else:
            as_of = out_upper - 1
            # Index imports cannot rewind: the publisher arrangement is
            # live at base_upper-1, which may be past the sink upper.
            # Hydrate at the furthest input instead and append ONE
            # correction chunk (desired snapshot ⊖ durable sink content)
            # covering the skipped interval — the reference's v2 sink
            # correction-buffer model (sink/correction_v2.rs).
            # With publisher multiversion windows, an index import can
            # rewind down to the publisher's since — only beyond that
            # does the correction-chunk path engage.
            min_feasible = max(
                (
                    s.publisher.since
                    for s in self.sources.values()
                    if isinstance(s, IndexSource)
                ),
                default=as_of,
            )
            corrected_as_of = max(as_of, min_feasible)
            for s in self.sources.values():
                if s.reader.wait_for_upper(
                    corrected_as_of, timeout=30.0
                ) is None:
                    raise TimeoutError(
                        "input upper never passed resume as_of "
                        f"{corrected_as_of}"
                    )
            inputs = {}
            for name, s in self.sources.items():
                b, _ = s.snapshot(corrected_as_of)
                inputs[name] = b
            self.df.time = corrected_as_of
            self.df.step(inputs)  # rebuild arrangements
            self._since = corrected_as_of
            if corrected_as_of == as_of:
                # output delta already durable — do NOT append.
                self._upper = out_upper
            else:
                self._append_correction(out_upper, corrected_as_of)
                self._upper = corrected_as_of + 1


    def result_batch(self) -> Batch:
        """The maintained output arrangement as a HOST-readable batch
        (SPMD dataflows gather their per-worker shards first). Always
        a COMMITTED span boundary: an in-flight pipelined span is
        completed first."""
        self.sync_spans()
        return self.df.gather_delta(self.df.output_batch())

    def _append_correction(self, out_upper: int, as_of: int) -> None:
        """One chunk [out_upper, as_of+1) bringing the durable sink to
        the freshly hydrated snapshot: correction = desired ⊖ durable
        (the v2 sink correction-buffer model, sink/correction_v2.rs).
        Used when an index import cannot rewind to the sink upper."""
        if self.writer is None:
            return

        def acc_multiset(cols, nulls, diff):
            acc: dict = {}
            n = len(diff)
            for i in range(n):
                key = tuple(
                    None
                    if nulls[j] is not None and nulls[j][i]
                    else cols[j][i].item()
                    for j in range(len(cols))
                )
                acc[key] = acc.get(key, 0) + int(diff[i])
            return acc

        cols, nulls, _t, diff = _host_updates(self.result_batch())
        if self._sink_finalizes:
            # Compare in VALUE space: finalize digests (the current
            # multiset matches result_batch exactly) and encode, so
            # desired keys are the same dictionary codes the durable
            # shard holds.
            cols = self._finalize_sink_columns(list(cols), nulls, diff)
        desired = acc_multiset(cols, nulls, diff)
        # Reader id is stable PER REPLICA: distinct across active-active
        # siblings (a shared identity would let one replica's expire()
        # release the other's since hold mid-snapshot), but stable across
        # restarts of the same replica so a hold leaked by a crash
        # between open and expire is re-registered and released by the
        # next hydration (this persist analog has no lease expiry).
        # Known caveat: a replica crashed in this window and then
        # decommissioned forever leaks its hold — fixing that needs
        # lease-based reader expiry (persist-client/src/read.rs leases),
        # tracked with the read-hold/read-policy work.
        reader = self.client.open_reader(
            self._output_shard, f"sink-correction-{self.replica_id}"
        )
        try:
            _sch, dcols, dnulls, _dt, ddiff = reader.snapshot(
                out_upper - 1
            )
        finally:
            reader.expire()
        durable = acc_multiset(dcols, dnulls, ddiff)
        delta: dict = {}
        for k in set(desired) | set(durable):
            d = desired.get(k, 0) - durable.get(k, 0)
            if d:
                delta[k] = d
        schema = self.df.out_schema
        rows = list(delta.items())
        out_cols, out_nulls = [], []
        for j, c in enumerate(schema.columns):
            vals = np.asarray(
                [0 if k[j] is None else k[j] for k, _ in rows],
                dtype=c.dtype,
            )
            out_cols.append(vals)
            out_nulls.append(
                np.asarray([k[j] is None for k, _ in rows])
                if any(k[j] is None for k, _ in rows)
                else None
            )
        batch = Batch.from_numpy(
            schema,
            out_cols,
            np.full(len(rows), as_of, np.uint64),
            np.asarray([d for _, d in rows], np.int64),
            nulls=out_nulls,
        )
        self._append(batch, out_upper, as_of + 1, as_of)

    def _append(self, batch: Batch, lower: int, upper: int, t: int) -> None:
        """Append the step's output delta. In active-active replication
        every replica computes every step deterministically and races the
        compare-and-append; losing the race (upper already advanced, or
        fenced by the other replica's writer) means the content is
        already durable — identical by determinism — so losing IS
        success (the reference's multi-replica persist-sink model,
        sink/materialized_view_v2.rs)."""
        if self.writer is None:
            return
        with TRACER.phase("span.readback") as ph:
            # the device-to-host copy: waits for the step that made it
            cols = batch.to_columns()
            data_cols, diff = cols[:-2], cols[-1]
            n = len(diff)
            nulls = [
                None if nl is None else np.asarray(nl)[:n]
                for nl in batch.nulls
            ]
            if ph:
                ph.add(bytes=device_nbytes(batch))
        with persist_phase("span.append", "sink") as ph:
            ph.add(rows=n)
            self._append_columns(data_cols, nulls, diff, lower, upper, t)

    def _append_columns(
        self, data_cols, nulls, diff, lower: int, upper: int, t: int
    ) -> None:
        n = len(diff)
        if self._sink_finalizes:
            data_cols = self._finalize_sink_columns(
                [np.asarray(c) for c in data_cols], nulls, diff
            )
        for attempt in range(5):
            try:
                self.writer.compare_and_append(
                    data_cols, nulls, np.full(n, t, np.uint64), diff,
                    lower, upper,
                )
                return
            except UpperMismatch as e:
                if e.actual >= upper:
                    # Another replica already wrote these times. Safe to
                    # skip: steady-state chunks are one timestamp and
                    # deltas are deterministic, so the durable content
                    # for [lower, upper) is identical to ours; our LOCAL
                    # frontier still advances only to `upper`.
                    return
                # Another replica durably wrote a SHORTER chunk (a
                # hydration race); our local state has advanced past it
                # and cannot produce the split — the owner must rebuild
                # from the durable shard.
                raise SinkConflict(
                    f"sink chunk [{lower},{upper}) conflicts with "
                    f"durable upper {e.actual}"
                )
            except Fenced:
                if self.writer.machine.reload().upper >= upper:
                    return  # the fencing writer covered it
                # Re-register and retry; jittered sleep breaks epoch
                # ping-pong between active-active siblings.
                self.writer.epoch = self.writer.machine.register_writer()
                _time.sleep(0.001 * (attempt + 1) * (1 + (id(self) % 7)))
        # The delta is NOT lost on this exit: the rebuild path re-derives
        # state from the durable shard and the sources.
        raise SinkConflict(
            f"sink append [{lower},{upper}) kept losing writer fencing"
        )

    def _finalize_sink_columns(self, data_cols, nulls, diff):
        """Digest columns -> materialized result strings -> dictionary
        codes, so the durable shard carries REAL values. Retraction
        rows (diff < 0) finalize against the pre-step multiset capture
        (their digests describe group states the post-step multiset no
        longer holds)."""
        from ...repr.schema import GLOBAL_DICT

        fin = self.df.finalize_basic_columns(
            data_cols, nulls, diffs=diff,
            old_multisets=self._pre_step_multisets,
        )
        for out_col, *_rest in self.df._basic_finalizers:
            fin[out_col] = np.asarray(
                [
                    0 if s is None else GLOBAL_DICT.encode(s)
                    for s in fin[out_col]
                ],
                dtype=np.int64,
            )
        return fin

    # -- steady state ------------------------------------------------------
    def step(self, timeout: float = 5.0) -> bool:
        """Process all sources' updates up to a COMMON target frontier
        (min over input uppers beyond our own): the micro-batch analog of
        frontier-joined progress. Returns False if the inputs did not
        advance within the timeout."""
        self.sync_spans()
        prefetched = 1 if self._kept else 0
        span = self._open_span(self.upper)
        with TRACER.within(span):
            if not self._step_tick(timeout):
                return False
        self._close_span(span, 1, prefetched=prefetched)
        return True

    def _open_span(self, lower: int):
        """The record of one committed span (doc/observability.md):
        the phases timed while it is the context are its children. A
        span that commits nothing is never closed and records nothing."""
        return TRACER.open(
            "span", dataflow=getattr(self.df, "name", "") or "df",
            lower=lower,
        )

    def _close_span(
        self, span, ticks: int, replayed: bool = False,
        prefetched: int = 0, overlapped: int = 0,
    ):
        """``prefetched``: how many of the span's ticks the span before
        it had gathered (``_kept``) while the device ran.
        ``overlapped``: how many were written with the span after it
        already dispatched. The bytes the view's operator state and
        output spine reserve on the device ride along (shapes, no
        device read)."""
        reserved = self.df.state_capacity_bytes()
        TRACER.close(
            span, upper=self._upper, ticks=ticks, epoch=self.span_epoch,
            replayed=replayed, prefetched_ticks=prefetched,
            overlapped_commit_ticks=overlapped,
            state_capacity_bytes=reserved,
        )
        self._capacity_gauge.set(
            getattr(self.df, "name", "") or "df", reserved
        )

    def _wait_for_inputs(self, frontier: int, timeout: float):
        """The least upper beyond ``frontier`` over every input, or
        None if one of them did not get there in time."""
        target = None
        for s in self.sources.values():
            with persist_phase("span.wait", "source"):
                upper = s.reader.wait_for_upper(frontier, timeout)
            if upper is None:
                return None
            target = upper if target is None else min(target, upper)
        return target

    def _commit_tick(self, t: int, out: Batch, lower: int) -> None:
        """One tick's validated delta: durable, then visible."""
        with TRACER.phase("span.readback"):
            out = self.df.gather_delta(out)  # no-op on single-device
        self._append(out, lower, t + 1, t)
        with TRACER.phase("span.publish"):
            self._publish(t, out)
            self._record_history(t, out)
        self._upper = t + 1

    def _step_tick(self, timeout: float) -> bool:
        lower = self.upper
        if not self.sources:
            # A source-less (pure constant) dataflow: one step at time 0
            # emits the constants, then the frontier is complete.
            if lower > 0:
                return False
            if self._sink_finalizes:
                self._pre_step_multisets = (
                    self.df.capture_basic_multisets()
                )
            arrived = _time.monotonic()
            self.df.time = 0
            self._commit_tick(0, self.df.step({}), 0)
            self._dispatched = 1
            self._record_freshness(1, arrived)
            return True
        ticks, _ = self._take_ready_ticks(lower, 1, timeout)
        if not ticks:
            return False
        ((t, polled, arrived),) = ticks
        if self._sink_finalizes:
            self._pre_step_multisets = (
                self.df.capture_basic_multisets()
            )
        self.df.time = t
        self._commit_tick(t, self.df.step(polled), lower)
        self._dispatched = t + 1
        self._record_freshness(t + 1, arrived)
        return True

    # -- pipelined span stepping (ISSUE 7: the async control plane) --------
    #
    # The per-tick step() pays one flags readback per tick (run_steps'
    # synchronous overflow check) and leaves the device idle while the
    # host fetches the next chunk. step_span() processes up to
    # span_max_ticks READY micro-batches as one deferred dispatch
    # train and commits them with ONE boundary readback. What overlaps
    # with the device's work on span K differs by the view's kind.
    # Index (sink-less) views: span K+1's ingest AND dispatch — the
    # commit readback for span K runs after span K+1 is already queued
    # on device (double buffering, at most one span in flight ahead of
    # the committed frontier). Sinked views: span K+1's ingest (wait,
    # fetch, upload: _prefetch_ticks) and, over a backlog, span K's
    # copy-out and appends — K's flags are read before K+1 is
    # dispatched, so K's deltas are final, K+1's rollback window
    # opens on K's validated carry and what a rollback undoes is the
    # per-tick path's; with K+1's ticks already kept, K+1 is
    # dispatched BEFORE K is written and the host writes K while the
    # device runs K+1 (a validated, unwritten span is all that ever
    # crosses a call: _validated_span). With nothing kept K is
    # written at once, as on the per-tick path. Peeks, AS OF reads,
    # and subscriber snapshots sequence against COMMITTED span
    # boundaries via sync_spans() — they can never observe a
    # half-applied carry.

    # -- donation decision (ISSUE 8: the prover-gated span train) ----------

    def donation_requested(self) -> bool:
        """Whether donation POLICY asks for a donated carry on this
        view's span train: the ``span_donation`` dyncfg resolved
        through the one shared backend predicate
        (render/dataflow.resolve_donation, over
        _donation_supported), restricted to single-device
        dataflows (SPMD carries cannot alias through shard_map
        boundary specs). The provenance PROVER decides whether the
        request is safe — see :meth:`_span_donation`."""
        from ...render.dataflow import (
            Dataflow as _SingleDevice,
            resolve_donation,
        )

        return type(self.df) is _SingleDevice and resolve_donation(None)

    def _span_donation(self) -> tuple:
        """The carry parts this view's next span train donates: the
        buffer-provenance prover's per-argnum verdict, recomputed only
        when the sharing signature (donation request, subscriber set)
        changes, and frozen for the duration of a defer window (a
        window that started un-donated must not start donating
        mid-window — run_steps enforces the same rule)."""
        if getattr(self.df, "_defer_ck", None) is not None:
            return self.donated_parts
        requested = self.donation_requested()
        sig = (requested, tuple(id(s) for s in self._subscribers))
        if sig != self._donation_sig:
            from ...analysis.donation import view_verdict
            from ...render.dataflow import _donation_supported

            name = getattr(self.df, "name", "df")
            v = view_verdict(name, self, requested=requested)
            self._donation_sig = sig
            self._donation_verdict = v
            self.donated_parts = v.donate_parts() if requested else ()
            info = v.to_dict()
            info["donated"] = list(self.donated_parts)
            info["wired"] = bool(
                self.donated_parts and _donation_supported()
            )
            self._donation_info = info
            self._donation_dirty = True
        return self.donated_parts

    def donation_info(self) -> dict | None:
        """The last provenance/donation verdict (replica frontier
        reports carry it to the controller for EXPLAIN ANALYSIS and
        the mz_donation introspection relation)."""
        return self._donation_info

    def sharding_info(self) -> dict | None:
        """The shard-spec prover's report (ISSUE 9: SPMD-safety
        verdict, resolved ingest mode, communication census) —
        replica frontier reports carry it to the controller for
        EXPLAIN ANALYSIS's ``sharding:`` block and the
        ``mz_sharding`` introspection relation."""
        return self._sharding_info

    def step_span(
        self, max_ticks: int | None = None, timeout: float = 0.0
    ) -> bool:
        """Span-batched stepping. Sinked and SPMD views validate at
        the span boundary (durability needs the deltas host-side, SPMD
        gathers them per tick), gather the next span's inputs while
        the device runs this one and, when that gather found ticks,
        leave this span's writes to the next call, which dispatches
        those ticks first (``upper`` then trails ``_dispatched`` by
        one validated span until the next call or ``sync_spans()``);
        index views pipeline (deferred commit). Views the span
        protocol cannot cover — pure constants, basic-aggregate sinks
        (per-step multiset captures) — fall back to the per-tick
        step."""
        from ...render.dataflow import Dataflow as _SingleDevice
        from ...utils.dyncfg import COMPUTE_CONFIGS, SPAN_MAX_TICKS

        if max_ticks is None:
            max_ticks = max(int(SPAN_MAX_TICKS(COMPUTE_CONFIGS)), 1)
        if not self.sources or self._sink_finalizes:
            return self.step(timeout)
        if self.writer is None and type(self.df) is _SingleDevice:
            # Index views pipeline: deferred commit, device-resident
            # history, at most one span in flight.
            return self._step_span_pipelined(max_ticks, timeout)
        # Sinked views (durability reads deltas host-side anyway) and
        # SPMD views (per-tick host gathers) commit synchronously at
        # the span boundary — still one flags readback per span
        # instead of one per tick.
        return self._step_span_sync(max_ticks, timeout)

    def _gather_ready_ticks(
        self, lower: int, max_ticks: int, timeout: float
    ) -> list:
        """Up to max_ticks consecutive one-timestamp input chunks
        beyond ``lower``: [(t, {name: batch}, arrival stamp)]. Only
        the FIRST tick may wait ``timeout``; later ticks take whatever
        is already ready (the span covers the backlog, it never stalls
        on it)."""
        ticks: list = []
        for k in range(max_ticks):
            want = lower + k
            target = self._wait_for_inputs(
                want, timeout if k == 0 else 0.0
            )
            if target is None:
                break
            # One timestamp per steady-state step: chunk boundaries are
            # then DETERMINISTIC across active-active replicas, so
            # racing sink appends are byte-identical and losing a race
            # is always safe. (Backlogs are collapsed by hydrate's
            # snapshot, not here; a correction-buffer sink,
            # correction_v2.rs, would lift this.)
            target = min(target, want + 1)
            polled = {
                name: s.fetch_to(target)
                for name, s in self.sources.items()
            }
            # Freshness arrival stamp: taken AFTER the fetch completes,
            # so the recorded lag is the maintenance delay this view
            # adds, not time spent waiting for input to exist
            # (coord/freshness.py). It stays with the tick: one kept
            # for the next span is no fresher for having waited here.
            ticks.append((target - 1, polled, _time.monotonic()))
        return ticks

    def _take_ready_ticks(
        self, lower: int, max_ticks: int, timeout: float
    ) -> tuple:
        """The next span's ticks: what the span before it kept, topped
        up from the sources to ``max_ticks`` (which wait ``timeout``
        only when nothing is kept), and how many of them were kept."""
        ticks = self._kept[:max_ticks]
        self._kept = self._kept[max_ticks:]
        kept = len(ticks)
        assert not ticks or ticks[0][0] == lower, (ticks[0][0], lower)
        ticks += self._gather_ready_ticks(
            lower + kept, max_ticks - kept, 0.0 if kept else timeout
        )
        return ticks, kept

    def _prefetch_ticks(self, lower: int, max_ticks: int) -> None:
        """Gather the ticks beyond ``lower`` that are ready NOW and keep
        them for the next span. Called with this span dispatched and
        its flags unread: reading source shards and putting batches on
        the device touches neither the carry nor the sink, and the
        host would otherwise only wait for the device. Never waits."""
        have = len(self._kept)
        self._kept += self._gather_ready_ticks(
            lower + have, max_ticks - have, 0.0
        )

    def _step_span_sync(self, max_ticks: int, timeout: float) -> bool:
        """Sinked span: dispatch every ready tick asynchronously,
        write the span before this one if it is still unwritten,
        gather the next span's ready ticks, ONE flags readback
        (check_flags — replays on overflow), then the per-tick durable
        appends from validated deltas: now if nothing was gathered,
        else under the next span's dispatch. The span before is
        unwritten only if its successor's ticks were kept, so the
        host writes while the device works and a view that keeps up
        with its sources writes every span in the call that ran it."""
        if self._validated_span is None or not self._kept:
            self.sync_spans()
        lower = self._dispatched
        span = self._open_span(lower)
        with TRACER.within(span):
            ticks, prefetched = self._take_ready_ticks(
                lower, max_ticks, timeout
            )
            if not ticks:
                return False
            if self.df.time != ticks[0][0]:
                self.df.time = ticks[0][0]
            # Our own dispatch must not flush the span before through
            # the registered span barrier: it is written beneath it.
            self._barrier.in_dispatch = True
            try:
                deltas = self.df.run_steps(
                    [inp for _, inp, _ in ticks],
                    defer_check=True,
                    donate=self._span_donation(),
                )
            finally:
                self._barrier.in_dispatch = False
            self._dispatched = ticks[-1][0] + 1
            # The device runs this span; the host writes the one
            # before it and gathers the one after.
            if self._validated_span is not None:
                self._commit_validated(overlapped=True)
            self._prefetch_ticks(self._dispatched, max_ticks)
            with TRACER.phase("span.readback"):
                replayed = self.df.check_flags()
            if replayed:
                deltas = self.df.replayed_deltas
        self._validated_span = (
            span, [(t, out) for (t, _, _), out in zip(ticks, deltas)],
            ticks[-1][2], replayed, prefetched,
        )
        if not self._kept:
            self._commit_validated(overlapped=False)
        return True

    def _commit_validated(self, overlapped: bool) -> None:
        """Write the validated span, tick by tick, under its own span
        record. ``overlapped``: the span after it is on the device."""
        span, entries, arrived, replayed, prefetched = (
            self._validated_span
        )
        self._validated_span = None
        lo = self._upper
        if overlapped:
            from ...analysis.donation import guard_read

            # read beneath a later (donated) dispatch: under the
            # buffer sanitizer, prove no delta is a donated carry leaf
            guard_read(entries, "validated span")
        with TRACER.within(span):
            for t, out in entries:
                self._commit_tick(t, out, lo)
                lo = t + 1
            self.span_epoch += 1
            self._record_freshness(lo, arrived)
        self._close_span(
            span, len(entries), replayed, prefetched,
            overlapped=len(entries) if overlapped else 0,
        )

    def _step_span_pipelined(
        self, max_ticks: int, timeout: float
    ) -> bool:
        """Index-view span: dispatch span K+1, then commit span K at
        its boundary readback — the readback waits for K while K+1
        executes. The committed frontier (`upper`, what peeks see)
        trails the dispatched one by at most one span."""
        from ...utils.dyncfg import COMPUTE_CONFIGS, SPAN_WINDOW_SPANS

        lower = self._dispatched
        span = self._open_span(lower)
        with TRACER.within(span):
            ticks = self._gather_ready_ticks(lower, max_ticks, timeout)
        if not ticks:
            # No new input: drain the in-flight span so the committed
            # frontier (and peeks waiting on it) still progresses.
            return self._commit_inflight()
        arrived = _time.monotonic()
        if (
            len(self.df._defer_log)
            >= int(SPAN_WINDOW_SPANS(COMPUTE_CONFIGS))
        ):
            # Rollback-window boundary: commit the in-flight span,
            # then validate + clear the defer log (bounds replay
            # memory). One extra readback per window, amortized; the
            # pipeline refills on this very dispatch.
            self.sync_spans()
            if self.df._defer_ck is not None and self.df.check_flags():
                self._recover_window()
            self._window_ticks = []
        if self.df._defer_ck is None:
            self._window_ticks = []
        if self.df.time != ticks[0][0]:
            self.df.time = ticks[0][0]
        # Our own dispatch must not self-sync through the registered
        # span barrier (that would serialize the double buffer).
        self._barrier.in_dispatch = True
        try:
            with TRACER.within(span):
                deltas = self.df.run_steps(
                    [inp for _, inp, _ in ticks],
                    defer_check=True,
                    donate=self._span_donation(),
                )
        finally:
            self._barrier.in_dispatch = False
        snap = self.df.flags_snapshot()
        entries = [(t, out) for (t, _, _), out in zip(ticks, deltas)]
        self._window_ticks.extend(entries)
        prev = self._inflight_span
        self._inflight_span = (
            snap, entries, ticks[-1][0] + 1, arrived, span,
        )
        self._dispatched = ticks[-1][0] + 1
        if prev is not None:
            self._commit_span(prev)
        return True

    def _commit_span(self, handle) -> None:
        """The span boundary: ONE fused flags readback; clean commits
        publish the span's deltas (device handoff), record history,
        and advance the committed frontier; an overflow triggers the
        whole-window rollback+replay."""
        snap, entries, target, arrived, span = handle
        with TRACER.within(span):
            with TRACER.phase("span.readback"):
                replayed = self.df.read_flags_snapshot(snap)
            if replayed:
                self._recover_window()
            else:
                with TRACER.phase("span.publish"):
                    for t, out in entries:
                        self._publish(t, out)
                        self._record_history(t, out)
                        self._upper = t + 1
                self.span_epoch += 1
                self._record_freshness(target, arrived)
        self._close_span(span, len(entries), replayed)

    def _record_freshness(self, frontier: int, arrived: float) -> None:
        """Committed-span-boundary lag recording: wallclock_lag_ms =
        commit time - arrival time of the newest input tick the span
        covers (one definition: coord/freshness.lag_ms). Pure host
        bookkeeping — this function is on the host-sync linter's
        RECORDER_PATH, so a hidden d2h sync here fails CI."""
        from ...coord.freshness import FRESHNESS, lag_ms

        FRESHNESS.record(
            getattr(self.df, "name", "") or "df",
            self.replica_id,
            frontier,
            lag_ms(arrived),
        )

    def _commit_inflight(self) -> bool:
        handle, self._inflight_span = self._inflight_span, None
        if handle is None:
            return False
        self._commit_span(handle)
        return True

    def _recover_window(self) -> None:
        """An overflow rolled the defer window back and replayed it
        against grown tiers (render/dataflow.check_flags). Spans
        committed earlier in the window were validated clean at their
        own boundary — the replay reproduces their deltas identically
        (steps are pure) — so only the uncommitted tail publishes."""
        if self.df._defer_ck is not None:
            self.df.check_flags()
        replayed = getattr(self.df, "replayed_deltas", [])
        for (t, _old), out in zip(self._window_ticks, replayed):
            if t >= self._upper:
                self._publish(t, out)
                self._record_history(t, out)
                self._upper = t + 1
        self._upper = max(self._upper, self._dispatched)
        self._inflight_span = None
        self._window_ticks = []
        self.span_epoch += 1

    def sync_spans(self) -> None:
        """The read barrier: complete + commit the in-flight span (an
        index view's) or write the validated one (a sinked view's), so
        callers (peeks, AS OF reads, subscriber snapshots, DML)
        observe a committed span boundary — never a half-applied
        carry, never a frontier behind the carry. No-op when nothing
        is in flight, and exactly ONE
        readback otherwise: the boundary commit's clean snapshot
        already proves every span <= it valid (flags OR-accumulate),
        so the serving path never pays a second validation round trip
        — window teardown happens at the span loop's own boundary
        (_step_span_pipelined) or inside df.check_flags when a
        df-level reader forces it."""
        if self._inflight_span is not None:
            self._commit_inflight()
        if self._validated_span is not None:
            # a sinked span that waited for its successor's dispatch:
            # a copy-out and appends, never a replay
            self._commit_validated(overlapped=False)

    def _publish(self, t: int, out: Batch) -> None:
        """Push this step's output delta to index-import subscribers
        (TraceManager sharing: the subscriber's dataflow sees exactly
        the arrangement's change stream). Device-path subscribers get
        the step's device batch itself (no host hop); host-path
        subscribers (SPMD publishers) get host arrays."""
        if not self._subscribers:
            return
        update = None
        for sub in self._subscribers:
            if getattr(sub, "_device", False):
                sub._push(t, out)
            else:
                if update is None:
                    sub.host_transfers += 1
                    update = _host_updates(out)
                sub._push(t, update)

    def run_until(self, frontier: int, timeout: float = 30.0) -> None:
        """Advance until the output upper reaches ``frontier``."""
        while self.upper < frontier:
            if not self.step(timeout):
                raise TimeoutError(
                    f"sources stalled below frontier {frontier}"
                )

    def peek(self) -> list[tuple]:
        self.sync_spans()
        return self.df.peek()
