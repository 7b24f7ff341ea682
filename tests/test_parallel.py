"""Multi-worker SPMD tests on the 8-virtual-device CPU mesh (conftest):
exchange routing and sharded dataflow vs the single-device result — the
analog of the reference's multi-process cluster tests without a cluster
(clusterd-test-driver, test/cluster)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from materialize_tpu.expr import relation as mir
from materialize_tpu.expr.relation import AggregateExpr, AggregateFunc
from materialize_tpu.expr.scalar import col
from materialize_tpu.parallel import compat as _compat
from materialize_tpu.parallel.exchange import exchange, shard_of
from materialize_tpu.parallel.mesh import make_mesh, worker_sharding
from materialize_tpu.render.dataflow import Dataflow, ShardedDataflow

from .oracle import net_rows
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema

from .oracle import as_multiset

SCHEMA = Schema(
    [Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)]
)


def _mk_batch(cols, diffs, time=0, schema=SCHEMA):
    n = len(diffs)
    return Batch.from_numpy(
        schema, cols, np.full(n, time, np.uint64), np.asarray(diffs)
    )


class TestExchange:
    def test_all_rows_arrive_at_key_owner(self):
        mesh = make_mesh(8)
        num = 8
        cap = 64
        rng = np.random.default_rng(7)
        n_per = 40
        # One local batch per worker with arbitrary keys.
        ks = rng.integers(0, 50, size=(num, n_per))
        vs = rng.integers(0, 1000, size=(num, n_per))

        def pack(a, dtype):
            out = np.zeros((num, cap), dtype=dtype)
            out[:, :n_per] = a
            return jax.device_put(
                out.reshape(num * cap), worker_sharding(mesh)
            )

        gb = Batch(
            cols=(pack(ks, np.int64), pack(vs, np.int64)),
            nulls=(None, None),
            time=pack(np.zeros((num, n_per)), np.uint64),
            diff=pack(np.ones((num, n_per)), np.int64),
            count=jax.device_put(
                np.full(num, n_per, np.int32), worker_sharding(mesh)
            ),
            schema=SCHEMA,
        )

        def per_worker(b):
            b = b.replace(count=b.count.reshape(()))
            routed, ovf = exchange(b, (0,), "workers", num, cap)
            return (
                routed.replace(count=routed.count.reshape((1,))),
                ovf.reshape((1,)),
            )

        routed, ovf = jax.jit(
            _compat.shard_map(
                per_worker,
                mesh=mesh,
                in_specs=(P("workers"),),
                out_specs=(P("workers"), P("workers")),
                check_vma=False,
            )
        )(gb)
        assert not np.any(np.asarray(ovf))

        counts = np.asarray(routed.count)
        out_cap = num * cap
        all_rows = []
        for p in range(num):
            k = np.asarray(routed.cols[0])[p * out_cap : p * out_cap + counts[p]]
            v = np.asarray(routed.cols[1])[p * out_cap : p * out_cap + counts[p]]
            # Every row on worker p has hash(key) % num == p.
            single = _mk_batch([k, np.zeros_like(k)], np.ones(len(k)))
            owners = np.asarray(shard_of(single, (0,), num))[: len(k)]
            assert (owners == p).all()
            all_rows += list(zip(k, v))
        # Nothing lost, nothing duplicated.
        want = sorted(zip(ks.reshape(-1), vs.reshape(-1)))
        assert sorted(all_rows) == want

    def test_overflow_flagged_on_skew(self):
        mesh = make_mesh(8)
        num = 8
        cap = 64
        slot = 4  # tiny slots; all keys identical -> guaranteed overflow
        ks = np.full((num, 32), 1)

        def pack(a, dtype):
            out = np.zeros((num, cap), dtype=dtype)
            out[:, :32] = a
            return jax.device_put(
                out.reshape(num * cap), worker_sharding(mesh)
            )

        gb = Batch(
            cols=(pack(ks, np.int64), pack(ks, np.int64)),
            nulls=(None, None),
            time=pack(np.zeros((num, 32)), np.uint64),
            diff=pack(np.ones((num, 32)), np.int64),
            count=jax.device_put(
                np.full(num, 32, np.int32), worker_sharding(mesh)
            ),
            schema=SCHEMA,
        )
        def per_worker(b):
            b = b.replace(count=b.count.reshape(()))
            routed, ovf = exchange(b, (0,), "workers", num, slot)
            return ovf.reshape((1,))

        ovf = jax.jit(
            _compat.shard_map(
                per_worker,
                mesh=mesh,
                in_specs=(P("workers"),),
                out_specs=P("workers"),
                check_vma=False,
            )
        )(gb)
        assert np.all(np.asarray(ovf))


class TestExchangeProperty:
    """Property tests for the all_to_all route (ISSUE 9 satellite):
    the route conserves rows (send/recv totals match, nothing lost or
    duplicated), per-key shard assignment is a stable pure function of
    the key, and the psum'd overflow flag trips EXACTLY when some
    sender's per-destination slot capacity is exceeded — matched
    against a host-side oracle on both sides of the boundary."""

    NUM = 8
    CAP = 64

    def _global_batch(self, mesh, ks, vs, ds, counts):
        """Pack per-worker row arrays ([NUM, CAP], valid prefix per
        `counts`) into one sharded global batch."""
        num, cap = self.NUM, self.CAP

        def pack(a, dtype):
            return jax.device_put(
                np.ascontiguousarray(a, dtype=dtype).reshape(
                    num * cap
                ),
                worker_sharding(mesh),
            )

        return Batch(
            cols=(pack(ks, np.int64), pack(vs, np.int64)),
            nulls=(None, None),
            time=pack(np.zeros((num, cap)), np.uint64),
            diff=pack(ds, np.int64),
            count=jax.device_put(
                np.asarray(counts, np.int32), worker_sharding(mesh)
            ),
            schema=SCHEMA,
        )

    def _run_exchange(self, mesh, gb, slot_cap):
        num = self.NUM

        def per_worker(b):
            b = b.replace(count=b.count.reshape(()))
            routed, ovf = exchange(b, (0,), "workers", num, slot_cap)
            return (
                routed.replace(count=routed.count.reshape((1,))),
                ovf.reshape((1,)),
            )

        return jax.jit(
            _compat.shard_map(
                per_worker,
                mesh=mesh,
                in_specs=(P("workers"),),
                out_specs=(P("workers"), P("workers")),
                check_vma=False,
            )
        )(gb)

    def _owners(self, keys) -> np.ndarray:
        """Host oracle: destination worker per key (same hash as the
        device route)."""
        keys = np.asarray(keys, np.int64)
        b = _mk_batch([keys, np.zeros_like(keys)], np.ones(len(keys)))
        return np.asarray(shard_of(b, (0,), self.NUM))[: len(keys)]

    def test_route_conserves_rows(self):
        mesh = make_mesh(self.NUM)
        num, cap = self.NUM, self.CAP
        owner_of: dict = {}  # key -> owner, stable ACROSS trials
        for seed in range(5):
            rng = np.random.default_rng(seed)
            counts = rng.integers(0, 61, num)
            ks = np.zeros((num, cap), np.int64)
            vs = np.zeros((num, cap), np.int64)
            ds = np.zeros((num, cap), np.int64)
            sent = []
            for p in range(num):
                n = counts[p]
                ks[p, :n] = rng.integers(0, 40, n)
                vs[p, :n] = rng.integers(0, 1000, n)
                # Retraction rows ride the same route as insertions.
                ds[p, :n] = rng.choice(np.asarray([1, 1, -1]), n)
                sent += list(
                    zip(ks[p, :n], vs[p, :n], ds[p, :n])
                )
            gb = self._global_batch(mesh, ks, vs, ds, counts)
            routed, ovf = self._run_exchange(mesh, gb, self.CAP)
            # slot_cap == per-worker input capacity: overflow impossible.
            assert not np.any(np.asarray(ovf))

            got_counts = np.asarray(routed.count)
            out_cap = num * self.CAP
            received = []
            for p in range(num):
                lo, n = p * out_cap, got_counts[p]
                k = np.asarray(routed.cols[0])[lo : lo + n]
                v = np.asarray(routed.cols[1])[lo : lo + n]
                d = np.asarray(routed.diff)[lo : lo + n]
                # Per-key assignment: every row received by worker p is
                # owned by p, under the SAME pure key hash every trial.
                assert (self._owners(k) == p).all()
                for key in k:
                    assert owner_of.setdefault(int(key), p) == p
                received += list(zip(k, v, d))
            # Send/recv totals match: nothing lost, nothing duplicated,
            # diffs intact.
            assert got_counts.sum() == counts.sum()
            assert sorted(map(tuple, received)) == sorted(
                map(tuple, sent)
            )
            # Per-worker receive counts match the host oracle.
            for p in range(num):
                want = sum(
                    (self._owners(ks[q, : counts[q]]) == p).sum()
                    for q in range(num)
                )
                assert got_counts[p] == want

    def test_overflow_trips_exactly_at_capacity(self):
        """The flag is a per-(sender, destination) slot-capacity fact:
        exactly slot_cap rows to one destination fit (no trip); one
        more trips it on EVERY worker (the psum makes the retry
        decision global). Random trials must agree with the host
        oracle in both directions."""
        mesh = make_mesh(self.NUM)
        num = self.NUM
        slot_cap = 8
        # Engineered boundary: every worker sends exactly `fill` rows
        # of ONE key (all to that key's owner).
        for fill, want_trip in ((slot_cap, False), (slot_cap + 1, True)):
            ks = np.full((num, self.CAP), 3, np.int64)
            vs = np.zeros((num, self.CAP), np.int64)
            ds = np.ones((num, self.CAP), np.int64)
            counts = np.full(num, fill, np.int64)
            gb = self._global_batch(mesh, ks, vs, ds, counts)
            _, ovf = self._run_exchange(mesh, gb, slot_cap)
            assert np.asarray(ovf).tolist() == [want_trip] * num, fill
        # Random trials vs the oracle.
        for seed in range(6):
            rng = np.random.default_rng(100 + seed)
            counts = rng.integers(0, 33, num)
            ks = np.zeros((num, self.CAP), np.int64)
            for p in range(num):
                ks[p, : counts[p]] = rng.integers(0, 6, counts[p])
            want = any(
                np.bincount(
                    self._owners(ks[p, : counts[p]]), minlength=num
                ).max(initial=0)
                > slot_cap
                for p in range(num)
            )
            gb = self._global_batch(
                mesh,
                ks,
                np.zeros_like(ks),
                np.ones_like(ks),
                counts,
            )
            _, ovf = self._run_exchange(mesh, gb, slot_cap)
            assert np.asarray(ovf).tolist() == [want] * num, seed


class TestShardedDataflow:
    def _expr(self):
        return mir.Get("in", SCHEMA).reduce(
            (0,),
            (
                AggregateExpr(AggregateFunc.SUM_INT, col(1)),
                AggregateExpr(AggregateFunc.COUNT, col(1)),
            ),
        )

    def test_matches_single_device(self):
        mesh = make_mesh(8)
        sdf = ShardedDataflow(self._expr(), mesh, slot_cap=64)
        df = Dataflow(self._expr())
        rng = np.random.default_rng(11)
        for step in range(4):
            n = 300
            k = rng.integers(0, 25, n)
            v = rng.integers(-50, 50, n)
            d = rng.integers(-1, 2, n)
            d[d == 0] = 1
            b = _mk_batch([k, v], d, time=step)
            sdf.step({"in": b})
            df.step({"in": b})
        got = sorted(r[:3] for r in sdf.peek())
        want = sorted(r[:3] for r in df.peek())
        assert got == want

    def test_constant_emitted_once_not_per_worker(self):
        mesh = make_mesh(8)
        const = mir.Constant(
            (((1, 10), 1), ((1, 20), 1), ((2, 5), 1)), SCHEMA
        )
        expr = const.reduce(
            (0,), (AggregateExpr(AggregateFunc.SUM_INT, col(1)),)
        )
        sdf = ShardedDataflow(expr, mesh, slot_cap=16)
        sdf.step({})
        sdf.step({})  # steady state: constant must not re-emit
        assert sorted(r[:2] for r in sdf.peek()) == [(1, 30), (2, 5)]

    def test_exchange_slot_overflow_recovers(self):
        mesh = make_mesh(8)
        # slot_cap=4 with 200 rows of ONE key: must grow and still be right.
        sdf = ShardedDataflow(self._expr(), mesh, slot_cap=4)
        k = np.zeros(200, np.int64)
        v = np.arange(200)
        b = _mk_batch([k, v], np.ones(200))
        sdf.step({"in": b})
        rows = sorted(r[:3] for r in sdf.peek())
        assert rows == [(0, int(v.sum()), 200)]


class TestShardedAggregates:
    """Sharded vs single-device aggregate equivalence under duplicate/
    retraction churn (ISSUE 9 satellite — the round-4 ask): every
    aggregate tier (accumulable SUM/COUNT, hierarchical MIN/MAX, basic
    string_agg/array_agg) pinned row-for-row against the single-device
    dataflow at EVERY step of a churn schedule that inserts duplicate
    rows, retracts them incrementally, and cancels a whole group."""

    def _churn_steps(self, val_pool):
        """(cols, diffs) per step: duplicates within and across steps,
        then retraction churn, then group 0 fully cancelled."""
        k = np.asarray
        steps = [
            # dup rows within one batch (same (k, v) twice), two groups
            ([k([0, 0, 0, 1, 1]), k(val_pool[:5])], [1, 1, 1, 1, 1]),
            # cross-step duplicates + a third group
            ([k([0, 1, 2, 2]), k(val_pool[5:9])], [1, 1, 1, 1]),
            # retract one copy of a duplicated row, add more churn
            ([k([0, 0, 2]), k(val_pool[:3])], [-1, 1, 1]),
            # cancel group 0 entirely (net count hits zero)
            (
                [k([0, 0, 0, 0]), k(val_pool[9:13])],
                [-1, -1, -1, -1],
            ),
        ]
        return steps

    def _check(self, expr, schema, steps):
        mesh = make_mesh(8)
        sdf = ShardedDataflow(expr, mesh, slot_cap=64)
        df = Dataflow(expr)
        for t, (cols, diffs) in enumerate(steps):
            b = _mk_batch(cols, diffs, time=t, schema=schema)
            sdf.step({"in": b})
            df.step({"in": b})
            got = net_rows(sdf.peek())
            want = net_rows(df.peek())
            assert got == want, (t, got, want)
        return got

    def test_all_aggregate_tiers_match_single_device(self):
        expr = mir.Get("in", SCHEMA).reduce(
            (0,),
            (
                AggregateExpr(AggregateFunc.SUM_INT, col(1)),
                AggregateExpr(AggregateFunc.COUNT, col(1)),
                AggregateExpr(AggregateFunc.MIN, col(1)),
                AggregateExpr(AggregateFunc.MAX, col(1)),
            ),
        )
        pool = [7, 7, 3, 10, 10, 7, 4, -2, -2, 7, 7, 3, 7]
        rows = self._check(expr, SCHEMA, self._churn_steps(pool))
        assert rows  # groups 1 and 2 survive
        # Group 0 was fully retracted: it must be GONE, not zeroed.
        assert all(r[0] != 0 for r in rows)

    def test_basic_aggregates_match_single_device(self):
        """The basic (collection) tier sharded: the reduce input
        exchange keys every group to one worker, so edge finalization
        over the gathered multiset must produce the same deterministic
        string as the single-device dataflow."""
        from materialize_tpu.repr.schema import GLOBAL_DICT

        schema = Schema(
            [
                Column("k", ColumnType.INT64),
                Column("s", ColumnType.STRING),
            ]
        )
        codes = [
            GLOBAL_DICT.encode(s)
            for s in (
                "a", "a", "b", "c", "c", "a", "d", "b", "b",
                "a", "a", "b", "e",
            )
        ]
        expr = mir.Get("in", schema).reduce(
            (0,),
            (
                AggregateExpr(
                    AggregateFunc.STRING_AGG, col(1), params=(",",)
                ),
                AggregateExpr(AggregateFunc.ARRAY_AGG, col(1)),
            ),
        )
        rows = self._check(
            expr, schema, self._churn_steps(codes)
        )
        assert all(r[0] != 0 for r in rows)
        # Finalized (not digest) output: real separator-joined text.
        assert any("," in str(r[1]) for r in rows)


class TestMultihost:
    def test_single_process_bootstrap(self):
        """The multi-host module's single-process path: no-op init and
        a global mesh over all local (virtual) devices."""
        from materialize_tpu.parallel.multihost import (
            global_worker_mesh,
            host_local_device_count,
            initialize_multihost,
        )

        initialize_multihost()  # num_processes=1: must be a no-op
        mesh = global_worker_mesh()
        assert mesh.shape["workers"] == host_local_device_count() == 8
