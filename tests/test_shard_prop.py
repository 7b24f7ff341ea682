"""Shard-spec abstract interpreter + collective-communication census
(ISSUE 9): the prover that gates shard-local slot ingest under SPMD.

The load-bearing claims pinned here:
- the sharding lattice propagates correctly through shard_map bodies:
  P() seeds replicated, P(axis) seeds shard-local, psum outputs are
  replicated, all_to_all/all_gather outputs are cross-worker, and
  scan/while/cond carries reach their fixpoint;
- a slot-ring cursor whose dataflow is pure per-worker arithmetic is
  verdicted SHARD-LOCAL; a cursor that mixes collective-moved data is
  verdicted CROSS-WORKER with the offending eqn blamed;
- the communication census counts every collective site with its
  per-device byte volume (the comm analog of PR 2's op_census);
- end to end on the forced 8-device CPU mesh: the index config's
  cursor proves shard-local, `state_ingest_mode` resolves to
  append-slot under SPMD, the sharded slot-mode output equals the
  single-device merge-mode output row-for-row under
  duplicate/retraction churn, and a REFUTED verdict re-renders the
  dataflow in merge mode (acceptance criteria);
- the coordinator surfaces (`EXPLAIN ANALYSIS` `sharding:` block,
  `mz_sharding`) cover every installed dataflow.

Runs in the `pytest -m analysis` lane on the conftest-forced 8-device
CPU platform; skips cleanly on JAX builds without shard_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from materialize_tpu.parallel import compat as _compat

pytestmark = pytest.mark.analysis

from materialize_tpu.analysis.shard_prop import (
    CROSS_WORKER,
    REPLICATED,
    SHARD_LOCAL,
    cursor_leaves,
    shard_map_analyses,
    spmd_safety,
)
from materialize_tpu.arrangement.spine import Spine
from materialize_tpu.expr import relation as mir
from materialize_tpu.render.dataflow import Dataflow, ShardedDataflow
from materialize_tpu.repr.batch import Batch
from materialize_tpu.repr.schema import Column, ColumnType, Schema

from .oracle import net_rows

SCHEMA = Schema(
    [Column("k", ColumnType.INT64), Column("v", ColumnType.INT64)]
)

AX = "workers"


def _trace(mesh, fn, in_specs, out_specs, *args):
    wrapped = lambda *a: _compat.shard_map(  # noqa: E731
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )(*a)
    return jax.make_jaxpr(wrapped)(*args)


def _one(closed):
    analyses = shard_map_analyses(closed)
    assert len(analyses) == 1, analyses
    return analyses[0]


# ---------------------------------------------------------------------------
# the lattice and the interpreter
# ---------------------------------------------------------------------------


class TestInterpreter:
    def test_seeds_follow_boundary_specs(self, eight_worker_mesh):
        def body(x, t):
            return x + t, t * 2

        an = _one(
            _trace(
                eight_worker_mesh, body,
                (P(AX), P()), (P(AX), P()),
                jnp.zeros(64, jnp.int64), jnp.zeros((), jnp.int64),
            )
        )
        assert an.in_classes == (SHARD_LOCAL, REPLICATED)
        # shard-local ⊔ replicated = shard-local; pure-replicated
        # arithmetic stays replicated.
        assert an.out_classes[0][0] == SHARD_LOCAL
        assert an.out_classes[1][0] == REPLICATED
        assert an.census.collectives == 0

    def test_psum_output_is_replicated_and_counted(
        self, eight_worker_mesh
    ):
        def body(x):
            s = jax.lax.psum(jnp.sum(x), AX)
            return x + s, s

        an = _one(
            _trace(
                eight_worker_mesh, body,
                (P(AX),), (P(AX), P()),
                jnp.zeros(64, jnp.int64),
            )
        )
        assert an.out_classes[0][0] == SHARD_LOCAL
        assert an.out_classes[1][0] == REPLICATED
        assert an.census.kinds() == {"psum": 1}
        (site,) = an.census.sites
        assert site.axes == (AX,)
        assert site.bytes_moved == 8  # one int64 scalar per device

    def test_all_to_all_taints_cross_worker_with_blame(
        self, eight_worker_mesh
    ):
        def body(x, c):
            r = jax.lax.all_to_all(
                x.reshape(8, -1), AX, split_axis=0, concat_axis=0
            ).reshape(-1)
            # The "cursor" mixes exchanged (cross-worker) data.
            return r, c + r[0]

        an = _one(
            _trace(
                eight_worker_mesh, body,
                (P(AX), P(AX)), (P(AX), P(AX)),
                jnp.zeros(64, jnp.int64), jnp.zeros(8, jnp.int32),
            )
        )
        cls, blame = an.out_classes[1]
        assert cls == CROSS_WORKER
        assert any("all_to_all" in b for b in blame)
        # Byte volume is PER DEVICE: the worker's [8, 1] int64 operand
        # (the global [64] splits 8 ways at the boundary).
        a2a = [
            s for s in an.census.sites if s.primitive == "all_to_all"
        ]
        assert len(a2a) == 1 and a2a[0].bytes_moved == 8 * 8

    def test_scan_carry_reaches_fixpoint(self, eight_worker_mesh):
        def body(x, c):
            def step(carry, xi):
                return carry + 1, xi * 2

            c2, ys = jax.lax.scan(step, c, x)
            return ys, c2

        an = _one(
            _trace(
                eight_worker_mesh, body,
                (P(AX), P(AX)), (P(AX), P(AX)),
                jnp.zeros(64, jnp.int64), jnp.zeros(8, jnp.int64),
            )
        )
        # A pure per-worker increment through a scan carry stays
        # shard-local.
        assert an.out_classes[1][0] == SHARD_LOCAL

    def test_scan_carry_poisoned_by_collective(
        self, eight_worker_mesh
    ):
        def body(x, c):
            r = jax.lax.all_to_all(
                x.reshape(8, -1), AX, split_axis=0, concat_axis=0
            ).reshape(-1)

            def step(carry, xi):
                return carry + xi, carry

            c2, _ys = jax.lax.scan(step, c, r)
            return x, c2

        an = _one(
            _trace(
                eight_worker_mesh, body,
                (P(AX), P(AX)), (P(AX), P(AX)),
                jnp.zeros(64, jnp.int64), jnp.zeros(8, jnp.int64),
            )
        )
        cls, blame = an.out_classes[1]
        assert cls == CROSS_WORKER
        assert any("all_to_all" in b for b in blame)

    def test_cond_joins_branches_and_predicate(
        self, eight_worker_mesh
    ):
        def body(x, c):
            pred = jax.lax.psum(jnp.sum(x), AX) > 0
            c2 = jax.lax.cond(pred, lambda a: a + 1, lambda a: a, c)
            return x, c2

        an = _one(
            _trace(
                eight_worker_mesh, body,
                (P(AX), P(AX)), (P(AX), P(AX)),
                jnp.zeros(64, jnp.int64), jnp.zeros(8, jnp.int32),
            )
        )
        # Predicate is psum-REPLICATED (mesh-uniform), carry is
        # shard-local: the join is shard-local — a uniform decision
        # applied to a per-worker value keeps it per-worker-pure.
        assert an.out_classes[1][0] == SHARD_LOCAL
        assert "psum" in an.census.kinds()


# ---------------------------------------------------------------------------
# cursor-leaf identification
# ---------------------------------------------------------------------------


class TestCursorLeaves:
    def test_cursor_is_last_spine_leaf(self):
        sp = Spine.empty(
            SCHEMA, (0, 1), capacity=256, ingest_slots=4, order="hash"
        )
        leaves = jax.tree_util.tree_leaves(sp)
        assert leaves[-1] is sp.cursor

    def test_indices_match_full_flatten(self):
        slotted = Spine.empty(
            SCHEMA, (0, 1), capacity=256, ingest_slots=4, order="hash"
        )
        slotless = Spine.empty(SCHEMA, (0, 1), capacity=256)
        out_shape = (
            jnp.zeros(4),  # delta stand-in
            ((slotted, jnp.zeros(2)), (slotless,)),  # states
            slotted,  # output
            jnp.zeros(3),  # err stand-in
            jnp.zeros(()),  # time
            jnp.zeros((2, 1)),  # flags
        )
        found = cursor_leaves(out_shape)
        flat = jax.tree_util.tree_leaves(out_shape)
        labels = [lab for _i, lab in found]
        assert labels == ["states[0][0].cursor", "output.cursor"]
        for i, _lab in found:
            # the identified flat index IS the cursor array (both
            # slotted spines here share one object)
            assert flat[i] is slotted.cursor


# ---------------------------------------------------------------------------
# the prover-gated render (acceptance criteria)
# ---------------------------------------------------------------------------


def _churn_steps(n_steps: int, seed: int = 3):
    """Duplicate/retraction churn batches (retraction-heavy, keys
    collide across steps)."""
    rng = np.random.default_rng(seed)
    out = []
    for t in range(n_steps):
        n = 64
        k = rng.integers(0, 200, n).astype(np.int64)
        v = rng.integers(0, 8, n).astype(np.int64)
        d = rng.choice(np.asarray([1, 1, 1, -1]), n).astype(np.int64)
        out.append(
            Batch.from_numpy(
                SCHEMA, [k, v], np.uint64(t), d, capacity=128
            )
        )
    return out


class TestProverGatedIngest:
    def test_index_cursor_proves_shard_local(self, eight_worker_mesh):
        """Acceptance: the index config's slot-ring cursor is
        verdicted shard-local on the forced 8-device mesh, the ring
        engages, and the ingest stage is communication-free (the only
        collective is the packed-flags psum)."""
        sdf = ShardedDataflow(
            mir.Get("src", SCHEMA), eight_worker_mesh,
            out_levels=3, out_slots=4, state_cap=1 << 14,
        )
        rep = sdf.sharding_report()
        assert rep["safe"] is True
        assert rep["ingest_mode"] == "append_slot"
        assert rep["error"] is None
        assert len(sdf.output.slots) == 4
        assert sdf.output.cursor.shape == (8,)
        (cur,) = rep["cursors"]
        assert cur["leaf"] == "output.cursor"
        assert cur["class"] == SHARD_LOCAL
        assert cur["safe"] is True and cur["blame"] == []
        assert rep["census"]["kinds"] == {"psum": 1}

    def test_state_ingest_mode_resolves_slot_under_spmd(self):
        """Acceptance: the decision function (the EXPLAIN-visible
        source of truth) resolves to append-slot under SPMD exactly
        when the prover verdicted the cursor safe."""
        from materialize_tpu.plan.decisions import (
            ingest_mode,
            state_ingest_mode,
        )

        for fn in (ingest_mode, state_ingest_mode):
            assert fn(1 << 15, 1024) == "append_slot"
            assert (
                fn(1 << 15, 1024, spmd=True, spmd_safe=True)
                == "append_slot"
            )
            # Unproven or refuted: conservative merge.
            assert fn(1 << 15, 1024, spmd=True) == "merge"
            assert (
                fn(1 << 15, 1024, spmd=True, spmd_safe=False)
                == "merge"
            )
            # Small state resolves merge regardless.
            assert fn(256, 1024, spmd=True, spmd_safe=True) == "merge"

    def test_auto_out_slots_engage_under_spmd(self, eight_worker_mesh):
        """out_slots=None + big state: the auto rule takes the ring
        under SPMD now that the prover verdicts it (the old hard
        force-to-merge is gone)."""
        from materialize_tpu.plan.decisions import INGEST_RING_SLOTS

        sdf = ShardedDataflow(
            mir.Get("src", SCHEMA), eight_worker_mesh,
            state_cap=1 << 15,
        )
        assert len(sdf.output.slots) == INGEST_RING_SLOTS
        assert sdf.sharding_report()["ingest_mode"] == "append_slot"

    def test_sharded_slot_mode_equals_single_device_merge(
        self, eight_worker_mesh
    ):
        """Acceptance: sharded slot-mode output == single-device
        merge-mode output, row for row, under duplicate/retraction
        churn (spanning several level-0 flushes)."""
        sdf = ShardedDataflow(
            mir.Get("src", SCHEMA), eight_worker_mesh,
            out_levels=3, out_slots=4, state_cap=1 << 14,
        )
        sdf._compact_every = 4
        assert sdf.output.slots  # slot mode actually engaged
        df = Dataflow(
            mir.Get("src", SCHEMA), out_levels=3, out_slots=0,
            state_cap=1 << 14,
        )
        df._compact_every = 4
        for b in _churn_steps(20):
            sdf.step({"src": b})
            df.step({"src": b})
        got = sorted(r[:2] + (r[-1],) for r in sdf.peek())
        want = net_rows(df.peek())
        assert got == want

    def test_refuted_verdict_falls_back_to_merge(
        self, eight_worker_mesh, monkeypatch
    ):
        """A refuted (or unprovable) cursor re-renders the dataflow in
        merge mode — an explicitly requested ring included — and the
        report carries the blame."""
        from materialize_tpu.analysis import shard_prop

        real = shard_prop.sharded_step_report

        def refute(sdf, input_cap=256):
            rep = real(sdf, input_cap)
            rep = dict(rep, safe=False)
            rep["cursors"] = [
                dict(
                    c,
                    safe=False,
                    **{"class": CROSS_WORKER},
                    blame=["all_to_all@shard_map/all_to_all (seeded)"],
                )
                for c in rep["cursors"]
            ]
            return rep

        monkeypatch.setattr(
            shard_prop, "sharded_step_report", refute
        )
        sdf = ShardedDataflow(
            mir.Get("src", SCHEMA), eight_worker_mesh,
            out_levels=3, out_slots=4, state_cap=1 << 14,
        )
        assert sdf.output.slots == ()  # ring refused
        rep = sdf._shard_prop_report
        assert rep["ingest_mode"] == "merge" and not rep["safe"]
        assert any(
            "all_to_all" in b
            for c in rep["cursors"]
            for b in c["blame"]
        )
        # Merge-mode fallback still computes the right answer.
        df = Dataflow(
            mir.Get("src", SCHEMA), out_levels=3, out_slots=0,
            state_cap=1 << 14,
        )
        for b in _churn_steps(8, seed=11):
            sdf.step({"src": b})
            df.step({"src": b})
        assert sorted(
            r[:2] + (r[-1],) for r in sdf.peek()
        ) == net_rows(df.peek())

    def test_spmd_safety_over_real_step_program(
        self, eight_worker_mesh
    ):
        """spmd_safety over the genuinely traced step program (not the
        cached report): one verdict per cursor, each shard-local."""
        from materialize_tpu.analysis.shard_prop import (
            trace_sharded_step,
        )

        sdf = ShardedDataflow(
            mir.Get("src", SCHEMA), eight_worker_mesh,
            out_levels=3, out_slots=4, state_cap=1 << 14,
        )
        closed, out_shape = trace_sharded_step(sdf)
        census, verdicts = spmd_safety(closed, out_shape)
        assert [v.leaf for v in verdicts] == ["output.cursor"]
        assert all(
            v.safe and v.cls == SHARD_LOCAL for v in verdicts
        )
        assert census.kinds() == {"psum": 1}


# ---------------------------------------------------------------------------
# the coordinator surface: EXPLAIN ANALYSIS `sharding:` + mz_sharding
# ---------------------------------------------------------------------------


class TestCoordinatorSurface:
    def test_explain_analysis_and_mz_sharding_cover_installs(
        self, tmp_path
    ):
        """EXPLAIN ANALYSIS appends a sharding report for EVERY
        installed dataflow, and mz_sharding serves the same rows
        relationally (single-device replica: spmd=0, workers=1,
        vacuously safe, zero collectives)."""
        import socket
        import threading
        import time

        from materialize_tpu.coord.coordinator import Coordinator
        from materialize_tpu.coord.protocol import PersistLocation
        from materialize_tpu.coord.replica import serve_forever
        from materialize_tpu.storage.persist import (
            FileBlob,
            PersistClient,
            SqliteConsensus,
        )

        loc = PersistLocation(
            str(tmp_path / "blob"), str(tmp_path / "c.db")
        )
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        ready = threading.Event()
        threading.Thread(
            target=serve_forever,
            args=(port, loc, "r0", ready),
            daemon=True,
        ).start()
        assert ready.wait(10)
        coord = Coordinator(
            PersistClient(
                FileBlob(loc.blob_root),
                SqliteConsensus(loc.consensus_path),
            ),
            tick_interval=None,
        )
        try:
            coord.add_replica("r0", ("127.0.0.1", port))
            coord.execute("CREATE TABLE t (a INT, b INT)")
            coord.execute("INSERT INTO t VALUES (1, 2), (3, 4)")
            coord.execute(
                "CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM t"
            )
            coord.execute("SELECT * FROM mv")
            with coord.controller._lock:
                installed = sorted(coord.controller._dataflows)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                with coord.controller._lock:
                    got = set(coord.controller.sharding_verdicts)
                if set(installed) <= got:
                    break
                time.sleep(0.05)
            text = coord.execute(
                "EXPLAIN ANALYSIS SELECT * FROM mv"
            ).text
            assert "sharding:" in text
            for name in installed:
                assert f"{name}@r0:" in text, (name, text)
            assert "spmd=false" in text
            assert "ingest=" in text and "comm(" in text
            rows = coord.execute("SELECT * FROM mz_sharding").rows
            assert {r[0] for r in rows} == set(installed)
            for r in rows:
                # spmd=0, workers=1, safe=1, zero collectives
                assert r[2] == 0 and r[3] == 1
                assert r[5] == 1 and r[6] == 0
        finally:
            coord.shutdown()
