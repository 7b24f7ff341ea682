"""Property tests for the kernel substrate vs the NumPy oracle."""

import numpy as np
import pytest

from materialize_tpu.ops.consolidate import consolidate
from materialize_tpu.ops.lanes import column_lanes, key_lanes
from materialize_tpu.ops.merge import merge_sorted
from materialize_tpu.ops.search import lex_searchsorted
from materialize_tpu.ops.sort import apply_perm, sort_perm
from materialize_tpu.repr.batch import Batch, capacity_tier
from materialize_tpu.repr.schema import Column, ColumnType, Schema

from .oracle import consolidate_rows

RNG = np.random.default_rng(42)


def random_batch(n, n_keys=8, schema=None, seed=None):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    if schema is None:
        schema = Schema(
            [
                Column("k", ColumnType.INT64),
                Column("v", ColumnType.INT64),
            ]
        )
    k = rng.integers(-n_keys, n_keys, size=n)
    v = rng.integers(-3, 3, size=n)
    t = rng.integers(0, 3, size=n).astype(np.uint64)
    d = rng.integers(-2, 3, size=n)
    return Batch.from_numpy(schema, [k, v], t, d)


class TestLanes:
    def test_int_order_preserved(self):
        vals = np.array([-(2**62), -5, -1, 0, 1, 7, 2**62], dtype=np.int64)
        (lanes,) = column_lanes(vals, ColumnType.INT64)
        lanes = np.asarray(lanes)
        assert list(lanes) == sorted(lanes)

    @staticmethod
    def _f64_keys(vals):
        l1, l2 = column_lanes(vals, ColumnType.FLOAT64)
        return list(zip(np.asarray(l1).tolist(), np.asarray(l2).tolist()))

    def test_float_order_preserved(self):
        # NOTE: subnormals are excluded — XLA flushes them to zero
        # (FTZ/DAZ), so on device they ARE zero; the zero-bucket collapse
        # is consistent with device arithmetic.
        vals = np.array(
            [-np.inf, -1e300, -1e30, -1.5, 0.0, 2.5,
             1e30, 1e300, np.inf, np.nan]
        )
        keys = self._f64_keys(vals)
        assert keys == sorted(keys)
        # every distinct finite value gets a distinct key
        assert len(set(keys)) == len(keys)

    def test_float_zero_signs_equal(self):
        keys = self._f64_keys(np.array([-0.0, 0.0]))
        assert keys[0] == keys[1]  # SQL equality: -0.0 = 0.0

    def test_float_lane_distinguishes_low_mantissa_bits(self):
        base = 1.2345678901234567
        vals = np.array([base, np.nextafter(base, 2.0), base + 1e-12])
        keys = self._f64_keys(vals)
        assert keys[0] < keys[1] < keys[2]

    def test_float_random_order(self):
        rng = np.random.default_rng(11)
        vals = rng.normal(size=500) * np.exp(rng.uniform(-30, 30, size=500))
        keys = np.array(self._f64_keys(vals))
        order_by_lane = np.lexsort((keys[:, 1], keys[:, 0]))
        order_by_val = np.argsort(vals, kind="stable")
        np.testing.assert_array_equal(vals[order_by_lane], vals[order_by_val])

    def test_float_extreme_range_distinct(self):
        # regression: values outside f32 range / subnormals must not
        # collapse to equal lanes on the CPU backend
        vals = np.array([1e-300, 2e-300, 1e39, 2e39, 1e300, 1.0000001e300])
        keys = self._f64_keys(vals)
        assert len(set(keys)) == len(keys)
        assert keys == sorted(keys)


class TestSortConsolidate:
    @pytest.mark.parametrize("n", [0, 1, 17, 255, 256, 700])
    def test_consolidate_matches_oracle(self, n):
        batch = random_batch(n, seed=n)
        out = consolidate(batch)
        got = sorted(out.to_rows())
        want = consolidate_rows(batch.to_rows())
        assert got == want

    def test_consolidate_all_cancel(self):
        schema = Schema([Column("k", ColumnType.INT64)])
        batch = Batch.from_numpy(
            schema, [np.array([1, 1, 2, 2])], np.zeros(4, np.uint64),
            np.array([1, -1, 5, -5]),
        )
        out = consolidate(batch)
        assert int(out.count) == 0

    def test_sort_is_stable_and_pads_last(self):
        batch = random_batch(100, seed=7)
        lanes = key_lanes(batch, [0])
        perm = sort_perm(lanes, batch.count, batch.capacity)
        s = apply_perm(batch, perm)
        rows = s.to_rows()
        keys = [r[0] for r in rows]
        assert keys == sorted(keys)
        assert len(rows) == 100


    @pytest.mark.parametrize(
        "capacity,count",
        [(8192, 8000), (12288, 11511), (13192, 13192), (49152, 1),
         (49152, 2048), (49152, 2049), (65536, 0), (65536, 64759)],
    )
    def test_sort_above_the_direct_size_is_the_compilers_sort(
        self, capacity, count
    ):
        """Above ``SORT_DIRECT_MAX`` rows ``sort_perm`` sorts blocks
        and merges them on a TPU (ROADMAP A3: that compiler's own sort
        takes minutes to compile from 2^15 rows up): the same permutation,
        ties and padding rows included, at sizes that are no power of
        two as well (``concat_batches`` makes them). A batch whose
        valid rows fit one block sorts that block alone."""
        import jax
        import jax.numpy as jnp

        from materialize_tpu.ops.sort import _large_sort_perm
        rng = np.random.default_rng(capacity + count)
        lanes = [
            jnp.asarray(rng.integers(0, hi, capacity).astype(np.uint64))
            for hi in (50, 3)
        ]
        idx = jnp.arange(capacity, dtype=jnp.int32)
        invalid = (idx >= count).astype(jnp.uint64)
        want = jax.lax.sort(
            [invalid] + lanes + [idx], num_keys=3, is_stable=True
        )[-1]
        # on a TPU ``sort_perm`` takes this path above 8,192 rows
        got = jax.jit(
            lambda a, b, c: _large_sort_perm(
                [(idx >= c).astype(jnp.uint64), a, b], idx, c, capacity
            )
        )(lanes[0], lanes[1], jnp.asarray(count, jnp.int32))
        got, want = np.asarray(got), np.asarray(want)
        assert (got[:count] == want[:count]).all()
        # padding rows last, each once, in whatever order
        assert sorted(got[count:]) == sorted(want[count:])


class TestSearch:
    def test_searchsorted_matches_numpy(self):
        rng = np.random.default_rng(3)
        m, n = 128, 64
        sorted_vals = np.sort(rng.integers(0, 50, size=m))
        count = 100  # only first 100 valid
        queries = rng.integers(-5, 55, size=n)
        s_lanes = column_lanes(sorted_vals, ColumnType.INT64)
        q_lanes = column_lanes(queries, ColumnType.INT64)
        for side in ("left", "right"):
            got = np.asarray(
                lex_searchsorted(s_lanes, count, q_lanes, side=side)
            )
            want = np.searchsorted(sorted_vals[:count], queries, side=side)
            np.testing.assert_array_equal(got, want)

    def test_searchsorted_two_lanes(self):
        a = np.array([0, 0, 1, 1, 1, 2], dtype=np.int64)
        b = np.array([0, 5, 0, 5, 5, 0], dtype=np.int64)
        s_lanes = column_lanes(a, ColumnType.INT64) + column_lanes(
            b, ColumnType.INT64
        )
        q_lanes = column_lanes(
            np.array([1], dtype=np.int64), ColumnType.INT64
        ) + column_lanes(np.array([5], dtype=np.int64), ColumnType.INT64)
        lo = int(lex_searchsorted(s_lanes, 6, q_lanes, side="left")[0])
        hi = int(lex_searchsorted(s_lanes, 6, q_lanes, side="right")[0])
        assert (lo, hi) == (3, 5)


class TestMerge:
    def test_merge_sorted_matches_full_sort(self):
        # merge_sorted requires inputs sorted by the lanes passed;
        # consolidate() emits HASH order (round-5 redesign), so sort
        # the inputs into exact key order first.
        from materialize_tpu.arrangement.spine import arrange

        a = arrange(random_batch(100, seed=1), (0, 1)).batch
        b = arrange(random_batch(80, seed=2), (0, 1)).batch
        a_lanes = key_lanes(a, [0, 1])
        b_lanes = key_lanes(b, [0, 1])
        out_cap = capacity_tier(a.capacity + b.capacity)
        merged, overflowed = merge_sorted(a, a_lanes, b, b_lanes, out_cap)
        assert not bool(overflowed)
        got = merged.to_rows()
        want = sorted(
            a.to_rows() + b.to_rows(), key=lambda r: (r[0], r[1])
        )
        assert sorted(got) == sorted(want)
        keys = [(r[0], r[1]) for r in got]
        assert keys == sorted(keys)

    def test_merge_overflow_flag(self):
        schema = Schema([Column("k", ColumnType.INT64)])
        mk = lambda lo, n: consolidate(
            Batch.from_numpy(
                schema,
                [np.arange(lo, lo + n)],
                np.zeros(n, np.uint64),
                np.ones(n, np.int64),
            )
        )
        a, b = mk(0, 100), mk(100, 100)
        merged, overflowed = merge_sorted(
            a, key_lanes(a, [0]), b, key_lanes(b, [0]), 128
        )
        assert bool(overflowed)
        assert int(merged.count) == 128
