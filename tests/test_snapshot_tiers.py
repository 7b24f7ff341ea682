"""``presize_for_snapshot`` and its counterpart (PR 31): the ingest
tiers that the one hydration step passes its snapshot through go back
to a tick's size once the step is folded into the bases, and nothing
else about the dataflow moves."""

import numpy as np
import pytest

from materialize_tpu.expr import relation as mir
from materialize_tpu.expr.scalar import col, lit
from materialize_tpu.parallel.mesh import make_mesh
from materialize_tpu.render.dataflow import Dataflow, ShardedDataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.utils.compile_ledger import tier_vector
from materialize_tpu.utils.metrics import REGISTRY

from .test_join import R_SCHEMA, S_SCHEMA, _mk, join_oracle

SNAPSHOT = 5000  # rows of ``r`` at hydration: the 8,192-row tier
RUN0 = 2048  # 8 ticks between two folds x the 256-row batch tier
NOTHING = {
    "arrangements": 0, "rows_released": 0, "bytes_released": 0,
    "join_sites": 0,
}


def _join():
    return mir.Join(
        (mir.Get("r", R_SCHEMA), mir.Get("s", S_SCHEMA)),
        equivalences=((col(0), col(2)),),  # rk = sk
    )


def _r(n: int, first: int, time: int) -> Batch:
    k = np.arange(first, first + n)
    return _mk(R_SCHEMA, [k % 97, k], np.ones(n, np.int64), time=time)


def _s(time: int = 0, n: int = 97) -> Batch:
    k = np.arange(n)
    return _mk(S_SCHEMA, [k, k * 10], np.ones(n, np.int64), time=time)


def _hydrate(df, fold: bool = True, snapshot: int = SNAPSHOT) -> dict:
    """What ``MaintainedView.hydrate`` does to its dataflow on a fresh
    install: presize, the one step, the fold into the bases
    (``result_batch``), the release."""
    r, s = _r(snapshot, 0, 0), _s()
    assert df._ctx.join_caps == [1024]
    assert df.presize_for_snapshot(
        {"r": r.capacity, "s": s.capacity}
    ) == 1
    # ``r`` reaches the join through no filter, and each of its rows
    # matches one of ``s``: the site holds r's whole snapshot
    assert df._ctx.join_caps == [r.capacity]
    df.step({"r": r, "s": s})
    assert df._ctx.join_caps == [r.capacity]  # and overflowed nothing
    if fold:
        df.output_batch()
    return df.release_snapshot_tiers()


def test_a_filtered_input_presizes_the_site_to_a_cut_delta():
    """Through a Filter, not every snapshot row need reach the join: the
    site holds what a snapshot-size delta is cut to (``out_delta_cap``),
    not ``r``'s whole snapshot."""
    df = Dataflow(mir.Join(
        (
            mir.Filter(mir.Get("r", R_SCHEMA), (col(1).gte(lit(0)),)),
            mir.Get("s", S_SCHEMA),
        ),
        equivalences=((col(0), col(2)),),
    ))
    r, s = _r(SNAPSHOT, 0, 0), _s()
    assert df._ctx.join_caps == [1024]
    assert df.presize_for_snapshot({"r": r.capacity, "s": s.capacity}) == 1
    assert df._ctx.join_caps == [4096]
    df.step({"r": r, "s": s})
    assert _net(df) == join_oracle(r.to_rows(), s.to_rows())


def _tiers(df) -> dict:
    return {
        name: [b.capacity for b in df.states[slot][part].runs_b]
        for (slot, part), (name, _site) in df._ctx.source_fed.items()
    }


def _net(df) -> dict:
    got = {}
    for x in df.peek():
        got[tuple(x[:-2])] = got.get(tuple(x[:-2]), 0) + x[-1]
    return {r: c for r, c in got.items() if c}


def _regrows() -> float:
    return REGISTRY.get_or_create(
        "counter", "mz_overflow_regrows_total"
    ).value


def test_release_takes_back_run_0_and_keeps_the_base():
    df = Dataflow(_join())
    rendered = _tiers(df)
    assert rendered == {"r": [1024, 256], "s": [1024, 256]}
    released = _hydrate(df)
    # ``s`` fits the tier it was rendered with: never grown, not touched
    assert _tiers(df) == {"r": [RUN0, 8192], "s": [1024, 256]}
    spine = df.states[0][0]
    assert [l.shape[0] for l in spine.lanes] == [RUN0, 8192]
    assert released["arrangements"] == 1
    assert released["rows_released"] == 8192 - RUN0
    assert released["bytes_released"] > (8192 - RUN0) * 4 * 8
    # and the join site pads a tick's matches to the rendered tier
    assert released["join_sites"] == 1
    assert df._ctx.join_caps == [1024]
    # asked again, nothing is remembered and nothing happens
    assert df.release_snapshot_tiers() == NOTHING
    # the rows are all there, and a tick lands on them
    df.step({"r": _r(100, SNAPSHOT, 1), "s": Batch.empty(S_SCHEMA)})
    assert _net(df) == join_oracle(
        _r(SNAPSHOT + 100, 0, 0).to_rows(), _s().to_rows()
    )


def test_a_delta_that_outgrows_a_released_tier_climbs_the_ladder():
    df = Dataflow(_join())
    _hydrate(df)
    before = _regrows()
    # one tick of 3,000 rows, each with its match: more than run 0
    # and the join site hold since the release
    df.step({"r": _r(3000, SNAPSHOT, 1), "s": Batch.empty(S_SCHEMA)})
    assert _regrows() >= before + 3  # run 0 once, the site twice
    assert _tiers(df)["r"] == [2 * RUN0, 8192]
    assert df._ctx.join_caps == [4096]
    assert _net(df) == join_oracle(
        _r(SNAPSHOT + 3000, 0, 0).to_rows(), _s().to_rows()
    )


def test_a_tier_that_holds_rows_is_left_alone():
    df = Dataflow(_join())
    # no fold before the release: run 0 still holds the snapshot
    released = _hydrate(df, fold=False)
    assert released == dict(NOTHING, join_sites=1)
    assert _tiers(df)["r"] == [8192, 8192]
    df.step({"r": _r(100, SNAPSHOT, 1), "s": Batch.empty(S_SCHEMA)})
    assert _net(df) == join_oracle(
        _r(SNAPSHOT + 100, 0, 0).to_rows(), _s().to_rows()
    )


def test_a_dataflow_that_presizing_did_not_grow_keeps_its_programs():
    """TPC-H Q15 at a small scale: ``lineitem`` reaches its join
    through a reduce and ``supplier`` fits the tier it was rendered
    with, so nothing is grown, nothing remembered, nothing released
    and no program remade: the keys are what they were."""
    from materialize_tpu.storage.generator.tpch import TpchGenerator
    from materialize_tpu.workloads.tpch import q15_mir

    gen = TpchGenerator(sf=0.001, seed=9)
    df = Dataflow(q15_mir())
    inputs = {
        "lineitem": next(
            iter(gen.snapshot_lineitem_batches(batch_orders=2048, time=0))
        ),
        "supplier": gen.table_batch("supplier"),
    }

    def key():
        args = (tuple(df.states), df.output, df.err_output, inputs)
        return tier_vector(args, df._static_tiers())

    rendered = (key(), df._step_jit)
    assert df.presize_for_snapshot(
        {n: b.capacity for n, b in inputs.items()}
    ) == 0
    assert (key(), df._step_jit) == rendered
    df.step(inputs)
    df.output_batch()
    hydrated = (key(), df._step_jit)  # the step's own ladder apart
    assert df.release_snapshot_tiers() == NOTHING
    assert (key(), df._step_jit) == hydrated


@pytest.mark.parametrize("outgrown", [False, True])
def test_sharded_release_cuts_every_shard(outgrown):
    """Four workers: capacities are global, a shard holds a quarter.
    20,000 rows take the 32,768-row tier, 8,192 a shard; the release
    leaves every shard's run 0 the 2,048 rows a tick needs."""
    sdf = ShardedDataflow(_join(), make_mesh(4), slot_cap=2048)
    assert _tiers(sdf)["r"] == [4 * 1024, 4 * 256]
    released = _hydrate(sdf, snapshot=20000)
    assert _tiers(sdf)["r"] == [4 * RUN0, 32768]
    assert released["arrangements"] == 1
    assert released["rows_released"] == 32768 - 4 * RUN0
    assert released["join_sites"] == 1
    run0 = sdf.states[0][0].runs_b[0]
    assert run0.time.shape == (4 * RUN0,)
    assert len(run0.time.sharding.device_set) == 4
    assert np.asarray(run0.count).tolist() == [0, 0, 0, 0]
    # 97 keys over four workers: 12,000 rows put more than 2,048 on one
    n = 12000 if outgrown else 100
    before = _regrows()
    sdf.step({"r": _r(n, 20000, 1), "s": Batch.empty(S_SCHEMA)})
    assert (_regrows() > before) == outgrown
    assert _net(sdf) == join_oracle(
        _r(20000 + n, 0, 0).to_rows(), _s().to_rows()
    )
