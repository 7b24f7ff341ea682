"""Error streams: the ok/err collection pair.

Reference: compute/src/render.rs:12-101 — scalar evaluation errors in a
maintained view surface as SQL errors on read and retract when the
offending rows are deleted.
"""

import numpy as np

from materialize_tpu.expr import errors as err
from materialize_tpu.expr import relation as mir
from materialize_tpu.expr.scalar import BinaryFunc, CallBinary, col, lit
from materialize_tpu.render.dataflow import Dataflow, ShardedDataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema

T = Schema([Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)])


def _batch(rows, diffs, time=0):
    cols = [np.asarray([r[i] for r in rows]) for i in range(2)]
    return Batch.from_numpy(
        T, cols, np.full(len(rows), time, np.uint64), np.asarray(diffs)
    )


def _div_df(cls=Dataflow, **kw):
    # SELECT k, 100 / v FROM t  (v = 0 rows error)
    expr = mir.Get("t", T).map(
        [CallBinary(BinaryFunc.DIV, lit(100, ColumnType.INT64), col(1))]
    ).project([0, 2])
    return cls(expr, **kw)


class TestErrorStream:
    def test_div_by_zero_surfaces_and_retracts(self):
        df = _div_df()
        df.step({"t": _batch([(1, 10), (2, 0), (3, 5)], [1, 1, 1])})
        assert df.peek_errors() == [(err.DIVISION_BY_ZERO, 1)]
        # another zero row: error count grows
        df.step({"t": _batch([(4, 0)], [1], time=1)})
        assert df.peek_errors() == [(err.DIVISION_BY_ZERO, 2)]
        # deleting the offending rows retracts the errors
        df.step({"t": _batch([(2, 0), (4, 0)], [-1, -1], time=2)})
        assert df.peek_errors() == []
        got = sorted(r[:-2] for r in df.peek())
        assert got == [(1, 10), (3, 20)]

    def test_null_operands_do_not_error(self):
        # NULL / 0 and x / NULL are NULL, not errors (pg semantics)
        schema = Schema(
            [
                Column("a", ColumnType.INT64, True),
                Column("b", ColumnType.INT64, True),
            ]
        )
        expr = mir.Get("t", schema).map(
            [CallBinary(BinaryFunc.DIV, col(0), col(1))]
        ).project([2])
        df = Dataflow(expr)
        b = Batch.from_numpy(
            schema,
            [np.asarray([1, 7]), np.asarray([0, 0])],
            np.zeros(2, np.uint64),
            np.ones(2, np.int64),
            nulls=[np.asarray([True, False]), np.asarray([False, True])],
        )
        df.step({"t": b})
        assert df.peek_errors() == []

    def test_case_guards_errors(self):
        # CASE WHEN v = 0 THEN NULL ELSE 100 / v END never errors
        from materialize_tpu.expr.scalar import If

        guard = If(
            col(1).eq(lit(0, ColumnType.INT64)),
            lit(None, ColumnType.INT64),
            CallBinary(
                BinaryFunc.DIV, lit(100, ColumnType.INT64), col(1)
            ),
        )
        expr = mir.Get("t", T).map([guard]).project([0, 2])
        df = Dataflow(expr)
        df.step({"t": _batch([(1, 0), (2, 4)], [1, 1])})
        assert df.peek_errors() == []

    def test_sharded_error_stream(self, eight_devices=None):
        import jax

        from materialize_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(len(jax.devices()))
        df = _div_df(ShardedDataflow, mesh=mesh)
        df.step({"t": _batch([(1, 10), (2, 0), (3, 5), (4, 0)], [1] * 4)})
        assert df.peek_errors() == [(err.DIVISION_BY_ZERO, 2)]
        df.step({"t": _batch([(2, 0)], [-1], time=1)})
        assert df.peek_errors() == [(err.DIVISION_BY_ZERO, 1)]
