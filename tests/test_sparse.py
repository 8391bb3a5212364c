"""paddle_tpu.sparse — recommender stack tests (8-device CPU mesh).

Pins the ISSUE 16 acceptance surface: sharded lookup == dense
replicated lookup, unique+segment_sum grads == the one-hot matmul
reference, padding_idx rows get exactly zero gradient through both
backwards, Embedding(sparse=True) routing, DLRM row-sharded training
matching the dense single-topology trajectory, topology-independent
sparse checkpoints, the planner's table placement term, the serving
rank path, and the ragged shm-ring descriptor.
"""
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import create_mesh
from paddle_tpu.parallel.mesh import set_mesh
from paddle_tpu.sparse import (
    EmbeddingRanker, ShardedEmbedding, SparseAdam, SparseTrainStep,
    sharded_lookup, sparse_lookup, to_logical, to_stored,
)

pytestmark = pytest.mark.recsys

ROWS, DIM = 37, 8


@pytest.fixture
def table():
    rng = np.random.default_rng(0)
    return rng.normal(size=(ROWS, DIM)).astype(np.float32)


@pytest.fixture
def ids():
    rng = np.random.default_rng(1)
    return rng.integers(0, ROWS, (6, 4)).astype(np.int32)


def _no_mesh():
    set_mesh(None)


# ==========================================================================
# storage layout + sharded lookup
# ==========================================================================

class TestShardedLookup:
    def test_stored_layout_roundtrip(self, table):
        for n in (1, 2, 4, 8):
            st = to_stored(table, n)
            np.testing.assert_array_equal(to_logical(st, ROWS, n), table)

    def test_lookup_matches_dense_replicated(self, table, ids):
        """The tentpole pin: all-to-all exchange lookup over the 8-dev
        mesh == the dense replicated nn.functional.embedding gather."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = create_mesh(dp=1, mp=8)
        try:
            dev = jax.device_put(
                to_stored(table, 8), NamedSharding(mesh, P("model", None)))
            out = sharded_lookup(dev, ids, mesh=mesh, rows=ROWS)
            np.testing.assert_allclose(np.asarray(out), table[ids],
                                       rtol=1e-6)
        finally:
            _no_mesh()

    def test_lookup_under_jit(self, table, ids):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = create_mesh(dp=1, mp=8)
        try:
            dev = jax.device_put(
                to_stored(table, 8), NamedSharding(mesh, P("model", None)))
            f = jax.jit(lambda t, i: sharded_lookup(t, i, mesh=mesh,
                                                    rows=ROWS))
            np.testing.assert_allclose(np.asarray(f(dev, ids)), table[ids],
                                       rtol=1e-6)
        finally:
            _no_mesh()

    def test_sharded_embedding_object(self, ids):
        mesh = create_mesh(dp=1, mp=8)
        try:
            emb = ShardedEmbedding(ROWS, DIM, mesh=mesh, padding_idx=0)
            vecs = np.asarray(emb.lookup(ids))
            logical = emb.logical_table()
            ref = logical[ids] * (ids != 0)[..., None]
            np.testing.assert_allclose(vecs, ref, rtol=1e-6)
            assert np.all(logical[0] == 0)          # padding row zeroed
            assert emb.bytes_per_device * 8 == emb.table.nbytes
        finally:
            _no_mesh()


# ==========================================================================
# sparse-gradient path
# ==========================================================================

class TestSparseGrads:
    def test_vjp_matches_one_hot_matmul(self, table, ids):
        """The acceptance pin: unique+segment_sum grads allclose to the
        dense one-hot-matmul reference."""
        w = jnp.asarray(table)

        def f_sparse(w):
            return (sparse_lookup(w, ids) ** 2).sum()

        def f_dense(w):
            oh = jax.nn.one_hot(ids, ROWS, dtype=w.dtype)
            return (jnp.einsum("blr,rd->bld", oh, w) ** 2).sum()

        g_s = jax.grad(f_sparse)(w)
        g_d = jax.grad(f_dense)(w)
        np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_d),
                                   rtol=1e-5, atol=1e-6)

    def test_duplicate_ids_aggregate_once(self, table):
        ids = jnp.asarray([3, 3, 3, 5])
        w = jnp.asarray(table)
        g = jax.grad(lambda w: sparse_lookup(w, ids).sum())(w)
        np.testing.assert_allclose(np.asarray(g)[3], 3.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g)[5], 1.0, rtol=1e-6)
        assert np.all(np.asarray(g)[[0, 1, 2, 4]] == 0)

    def test_padding_idx_zero_grad_both_backwards(self, table, ids):
        """Satellite pin: padding_idx rows receive EXACTLY zero gradient
        through the dense AND the sparse backward."""
        pad = int(ids.reshape(-1)[0])
        w = jnp.asarray(table)

        def f_dense(w):
            out = jnp.take(w, ids, axis=0)
            out = out * (ids != pad)[..., None].astype(out.dtype)
            return (out ** 2).sum()

        def f_sparse(w):
            return (sparse_lookup(w, ids, padding_idx=pad) ** 2).sum()

        g_d = np.asarray(jax.grad(f_dense)(w))
        g_s = np.asarray(jax.grad(f_sparse)(w))
        assert np.all(g_d[pad] == 0)
        assert np.all(g_s[pad] == 0)
        np.testing.assert_allclose(g_s, g_d, rtol=1e-5, atol=1e-6)


# ==========================================================================
# nn.Embedding(sparse=True) routing
# ==========================================================================

class TestEmbeddingSparseFlag:
    def _run(self, sparse, mesh):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        paddle.seed(2024)
        set_mesh(mesh)
        try:
            emb = nn.Embedding(10, 4, padding_idx=0, sparse=sparse)
            x = paddle.to_tensor(
                np.array([[1, 2, 2, 0], [3, 0, 1, 3]], np.int64))
            out = emb(x)
            (out * out).sum().backward()
            return (np.asarray(out.numpy()),
                    np.asarray(emb.weight.grad.numpy()))
        finally:
            set_mesh(None)

    def test_flag_off_bit_identical(self):
        o_ref, g_ref = self._run(False, None)
        o_again, g_again = self._run(False, None)
        np.testing.assert_array_equal(o_ref, o_again)
        np.testing.assert_array_equal(g_ref, g_again)

    def test_no_mesh_warns_once_and_matches_dense(self):
        import paddle_tpu.nn.functional.common as fc

        o_ref, g_ref = self._run(False, None)
        fc._sparse_warned[0] = False
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            o_s, g_s = self._run(True, None)
            o_s2, g_s2 = self._run(True, None)   # second call: no warning
        msgs = [str(w.message) for w in rec]
        assert sum("sparse-grad" in m for m in msgs) == 1, msgs
        np.testing.assert_array_equal(o_ref, o_s)
        np.testing.assert_array_equal(g_ref, g_s)

    def test_mesh_routes_sparse_and_matches(self):
        o_ref, g_ref = self._run(False, None)
        mesh = create_mesh(dp=1, mp=8)
        o_s, g_s = self._run(True, mesh)
        np.testing.assert_allclose(o_s, o_ref, rtol=1e-6)
        np.testing.assert_allclose(g_s, g_ref, rtol=1e-5, atol=1e-6)
        assert np.all(g_s[0] == 0)               # padding row

    def test_sparse_adam_lazy_rows(self):
        """Rows absent from the batch keep params AND moments untouched."""
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        paddle.seed(2024)
        emb = nn.Embedding(10, 4)
        w0 = np.asarray(emb.weight.numpy()).copy()
        opt = SparseAdam(learning_rate=0.1, parameters=emb.parameters())
        x = paddle.to_tensor(np.array([1, 3, 3], np.int64))
        for _ in range(2):
            out = emb(x)
            (out * out).sum().backward()
            opt.step()
            opt.clear_grad()
        w1 = np.asarray(emb.weight.numpy())
        touched = [1, 3]
        untouched = [i for i in range(10) if i not in touched]
        np.testing.assert_array_equal(w1[untouched], w0[untouched])
        assert np.all(w1[touched] != w0[touched])
        m1 = opt._accumulators["moment1"][id(emb.weight)]
        assert np.all(np.asarray(m1)[untouched] == 0)
        assert np.any(np.asarray(m1)[touched] != 0)


# ==========================================================================
# DLRM end-to-end: row-sharded == single-device dense trajectory
# ==========================================================================

def _dlrm_step(cfg, mp, lr=1e-2):
    from paddle_tpu.models import dlrm_init, dlrm_loss_from_emb

    mesh = create_mesh(dp=8 // mp, mp=mp)
    p = dlrm_init(cfg, 0)
    return SparseTrainStep(
        functools.partial(dlrm_loss_from_emb, cfg), p["dense"],
        {"table": p["table"]}, ids_fn=lambda b: {"table": b["slots"]},
        mesh=mesh, lr=lr)


class TestDLRM:
    def test_row_sharded_matches_dense_trajectory(self):
        """Acceptance pin: the mp=8 row-sharded run (table too large to
        replicate, per the planner's model — exercised separately in
        TestPlannerTablePlacement) follows the dense unsharded loss
        trajectory."""
        from paddle_tpu.models import dlrm_tiny, synthetic_ctr_batches

        cfg = dlrm_tiny()
        batches = list(synthetic_ctr_batches(cfg, 32, 5, seed=3))
        try:
            sa = _dlrm_step(cfg, 8)
            la = [float(sa(b)) for b in batches]
            sb = _dlrm_step(cfg, 1)
            lb = [float(sb(b)) for b in batches]
        finally:
            _no_mesh()
        np.testing.assert_allclose(la, lb, rtol=2e-4)
        assert la[-1] < la[0]            # planted structure is learnable

    def test_dense_reference_path_agrees(self):
        """dlrm_loss (plain take) == the from_emb path SparseTrainStep
        uses, on the same params."""
        from paddle_tpu.models import (dlrm_init, dlrm_loss,
                                       dlrm_loss_from_emb, dlrm_tiny,
                                       synthetic_ctr_batches)

        cfg = dlrm_tiny()
        p = dlrm_init(cfg, 0)
        b = next(iter(synthetic_ctr_batches(cfg, 16, 1)))
        emb = {"table": jnp.take(p["table"], b["slots"], axis=0)}
        np.testing.assert_allclose(
            float(dlrm_loss(cfg, p, b)),
            float(dlrm_loss_from_emb(cfg, p["dense"], emb, b)), rtol=1e-6)

    def test_deepfm_arch_trains(self):
        from paddle_tpu.models import dlrm_tiny, synthetic_ctr_batches

        cfg = dlrm_tiny(arch="deepfm")
        batches = list(synthetic_ctr_batches(cfg, 32, 3, seed=5))
        try:
            step = _dlrm_step(cfg, 8)
            losses = [float(step(b)) for b in batches]
        finally:
            _no_mesh()
        assert all(np.isfinite(losses))


# ==========================================================================
# sparse checkpointing: sharded <-> unsharded round trip
# ==========================================================================

class TestSparseCheckpoint:
    def test_cross_topology_resume_identical(self, tmp_path):
        """PR-12 harness shape: train 4 straight on mp=1 vs train 2 on
        mp=8 + save + restore into a FRESH mp=1 step + train 2 — the
        sparse state (table + lazy Adam moments) must carry over so the
        trajectories match."""
        import os

        from paddle_tpu.framework.checkpoint import (load_checkpoint,
                                                     save_checkpoint)
        from paddle_tpu.models import dlrm_tiny, synthetic_ctr_batches

        cfg = dlrm_tiny()
        batches = list(synthetic_ctr_batches(cfg, 32, 4, seed=7))
        try:
            ref = _dlrm_step(cfg, 1)
            losses_ref = [float(ref(b)) for b in batches]

            half = _dlrm_step(cfg, 8)
            for b in batches[:2]:
                float(half(b))
            state = half.state_dict()
            assert state["step"] == 2
            path = os.path.join(tmp_path, "sparse_ckpt")
            save_checkpoint(path, state["params"])
            restored_params = load_checkpoint(
                path, template=state["params"])

            fresh = _dlrm_step(cfg, 1)      # DIFFERENT topology
            state["params"] = restored_params
            fresh.set_state_dict(state)
            losses_resumed = [float(fresh(b)) for b in batches[2:]]
        finally:
            _no_mesh()
        np.testing.assert_allclose(losses_resumed, losses_ref[2:],
                                   rtol=1e-5)

    def test_state_dict_is_logical_layout(self, table):
        """state_dict must be shard-count independent (logical rows)."""
        def make(mp):
            mesh = create_mesh(dp=8 // mp, mp=mp)
            return SparseTrainStep(
                lambda d, e, b: (e["t"] ** 2).sum() * d["s"],
                {"s": np.float32(1.0)}, {"t": table},
                ids_fn=lambda b: {"t": b["ids"]}, mesh=mesh)

        try:
            a, b = make(8), make(1)
            batch = {"ids": np.array([1, 2, 3], np.int32)}
            float(a(batch)), float(b(batch))
            sa, sb = a.state_dict(), b.state_dict()
        finally:
            _no_mesh()
        np.testing.assert_allclose(sa["params"]["tables"]["t"],
                                   sb["params"]["tables"]["t"], rtol=1e-6)
        np.testing.assert_allclose(sa["opt_state"]["sparse"]["t"]["m"],
                                   sb["opt_state"]["sparse"]["t"]["m"],
                                   rtol=1e-6)


# ==========================================================================
# planner: embedding-table placement term
# ==========================================================================

class TestPlannerTablePlacement:
    STATS = dict(param_bytes=10 << 20, n_params=(10 << 20) // 4,
                 layer_bytes=0, layers=1, hidden=64, seq_len=1)

    def test_oversized_table_forces_row_sharding(self):
        """The acceptance criterion's sizing: replicated table (+ fp32
        m/v) exceeds the 16 GB HBM model, so every fitting plan must
        row-shard over "model"."""
        from paddle_tpu.distributed.fleet.auto import planner
        from paddle_tpu.distributed.fleet.auto.cost_model import ModelStats

        stats = ModelStats(**self.STATS, table_rows=100_000_000,
                           table_dim=64, table_lookups_per_sample=26)
        p = planner.plan(stats=stats, global_batch=4096, n_devices=8)
        assert p.mp > 1
        assert p.chosen.hbm_detail["table"] > 0
        # every candidate that fit sharded the table
        assert all(c.mp > 1 for c in p.candidates if c.fits)

    def test_small_table_stays_replicated(self):
        from paddle_tpu.distributed.fleet.auto import planner
        from paddle_tpu.distributed.fleet.auto.cost_model import ModelStats

        stats = ModelStats(**self.STATS, table_rows=1000, table_dim=16,
                           table_lookups_per_sample=4)
        p = planner.plan(stats=stats, global_batch=4096, n_devices=8)
        assert p.mp == 1

    def test_exchange_bytes_in_cost(self):
        from paddle_tpu.distributed.fleet.auto.cost_model import (
            HardwareSpec, ModelStats, PlanCandidate, estimate)

        stats = ModelStats(**self.STATS, table_rows=1 << 20, table_dim=32,
                           table_lookups_per_sample=26)
        flat = estimate(PlanCandidate(dp=8, sharding=1, pp=1, mp=1,
                                      n_micro=1, zero=0),
                        stats, 4096, HardwareSpec())
        shard = estimate(PlanCandidate(dp=1, sharding=1, pp=1, mp=8,
                                       n_micro=1, zero=0),
                         stats, 4096, HardwareSpec())
        # sharding divides the table HBM 8x and adds exchange traffic
        assert shard.hbm_detail["table"] < flat.hbm_detail["table"]
        assert shard.coll_bytes > flat.coll_bytes

    def test_plan_kwargs(self):
        from paddle_tpu.distributed.fleet.auto import planner

        p = planner.plan(params={"w": np.zeros((4, 64), np.float32)},
                         global_batch=64, n_devices=8,
                         table_rows=100_000_000, table_dim=64,
                         table_lookups_per_sample=26)
        assert p.stats.table_rows == 100_000_000
        assert p.mp > 1


# ==========================================================================
# serving: EmbeddingRanker + engine.rank
# ==========================================================================

class TestServingRank:
    def test_ranker_sharded_matches_unsharded(self, table):
        rng = np.random.default_rng(3)
        slots = {"t": rng.integers(0, ROWS, (5, 3)).astype(np.int32)}
        try:
            mesh = create_mesh(dp=1, mp=8)
            sharded = EmbeddingRanker({"t": table}, mesh=mesh)
            s1 = sharded.rank(slots)
        finally:
            _no_mesh()
        unsharded = EmbeddingRanker({"t": table}, mesh=None)
        s2 = unsharded.rank(slots)
        np.testing.assert_allclose(s1, s2, rtol=1e-5)

    def test_pow2_padding_consistent(self, table):
        rng = np.random.default_rng(4)
        rk = EmbeddingRanker({"t": table}, mesh=None)
        ids = rng.integers(0, ROWS, (7, 2)).astype(np.int32)
        full = rk.rank({"t": ids})
        head = rk.rank({"t": ids[:3]})
        np.testing.assert_allclose(full[:3], head, rtol=1e-6)

    def test_engine_rank_requires_arming(self):
        import jax.numpy as jnp

        from paddle_tpu.models import gpt_init, gpt_tiny
        from paddle_tpu.serving.engine import InferenceEngine

        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64)
        eng = InferenceEngine(cfg, gpt_init(cfg, 0), n_slots=2)
        try:
            with pytest.raises(RuntimeError, match="embedding_tables"):
                eng.rank({"t": [[1]]})
        finally:
            eng.shutdown(drain=False, timeout=30)

    def test_engine_rank_end_to_end(self, table):
        import jax.numpy as jnp

        from paddle_tpu.models import gpt_init, gpt_tiny
        from paddle_tpu.serving.engine import InferenceEngine

        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64)
        eng = InferenceEngine(cfg, gpt_init(cfg, 0), n_slots=2,
                              embedding_tables={"t": table})
        try:
            scores = eng.rank({"t": np.array([[1, 2], [3, 4]], np.int32)})
            assert scores.shape == (2,)
            assert np.all(np.isfinite(scores))
        finally:
            eng.shutdown(drain=False, timeout=30)


# ==========================================================================
# ragged shm-ring descriptor
# ==========================================================================

class TestRaggedShmRing:
    def test_offsets_values_roundtrip(self):
        from paddle_tpu.io.shm_ring import _decode, encode_into

        rng = np.random.default_rng(0)
        batch = {"dense": rng.normal(size=(8, 4)).astype(np.float32),
                 "multi_hot": [rng.integers(0, 100, n).astype(np.int64)
                               for n in (3, 0, 7, 1)],
                 "pair": (np.array([1, 2], np.int32),
                          np.array([9], np.int32)),
                 "label": 1}
        buf = bytearray(1 << 16)
        skel = encode_into(batch, memoryview(buf), len(buf))
        assert skel is not None
        # ragged lists use the flattened offsets+values descriptor:
        # 2 leaves on the wire, not n
        assert skel["multi_hot"][0] == "__shm_ragged__"
        assert skel["pair"][0] == "__shm_ragged__"
        out = _decode(skel, memoryview(buf))
        assert isinstance(out["multi_hot"], list)
        assert isinstance(out["pair"], tuple)
        for a, b in zip(batch["multi_hot"], out["multi_hot"]):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(batch["dense"], out["dense"])
        assert out["label"] == 1
        # decoded arrays own their memory (slot recycles underneath)
        out["multi_hot"][2][0] = -123
        assert batch["multi_hot"][2][0] != -123

    def test_non_flattenable_falls_back_to_pickle(self):
        """A batch the planner can't flatten must take the byte-identical
        pickle path (the pipe transport), not fail."""
        import pickle

        from paddle_tpu.io.shm_ring import _NotShmable, _plan, encode_into

        bad = {"x": np.array([{"nested": "object"}], dtype=object)}
        with pytest.raises(_NotShmable):
            _plan(bad, 0)
        buf = bytearray(1 << 12)
        assert encode_into(bad, memoryview(buf), len(buf)) is None
        # the fallback payload is plain pickle — byte-identical both ways
        assert pickle.loads(pickle.dumps(bad))["x"][0] == bad["x"][0]

    def test_mixed_dtype_list_keeps_per_leaf_encoding(self):
        from paddle_tpu.io.shm_ring import _plan

        sk, _, _ = _plan([np.array([1], np.int32),
                          np.array([2], np.int64)], 0)
        assert sk[0][0] == "__shm__" and sk[1][0] == "__shm__"

    def test_dataloader_ships_ragged_ctr_batches(self):
        """End to end through the worker ring: the dlrm synthetic stream
        (ragged multi_hot included) survives the shm transport."""
        from paddle_tpu.io.shm_ring import ShmRing, WorkerRing, _decode
        from paddle_tpu.models import dlrm_tiny, synthetic_ctr_batches

        cfg = dlrm_tiny()
        batch = next(iter(synthetic_ctr_batches(cfg, 16, 1, ragged=True)))
        import multiprocessing as mp

        ring = ShmRing(mp.get_context("spawn"), n_slots=2,
                       slot_bytes=1 << 20)
        try:
            worker = WorkerRing(ring.worker_config())
            desc = worker.put_batch(batch, None)
            assert desc is not None
            got = ring.read_batch(desc)
            np.testing.assert_array_equal(got["slots"], batch["slots"])
            np.testing.assert_array_equal(got["dense"], batch["dense"])
            assert len(got["multi_hot"]) == len(batch["multi_hot"])
            for a, b in zip(batch["multi_hot"], got["multi_hot"]):
                np.testing.assert_array_equal(a, b)
            worker.close()
        finally:
            ring.close()


# ==========================================================================
# observability: gauges + trace section
# ==========================================================================

class TestObservability:
    def test_gauges_move(self, table, ids):
        from paddle_tpu.monitor.stats import stat_snapshot

        try:
            mesh = create_mesh(dp=1, mp=8)
            before = stat_snapshot()
            emb = ShardedEmbedding(ROWS, DIM, mesh=mesh)
            emb.lookup(ids)
            after = stat_snapshot()
        finally:
            _no_mesh()
        assert after["embedding_lookup_ids"] - \
            before["embedding_lookup_ids"] == ids.size
        assert after["embedding_exchange_bytes"] > \
            before["embedding_exchange_bytes"]

    def test_train_step_gauges(self, table):
        from paddle_tpu.monitor.stats import stat_snapshot

        try:
            mesh = create_mesh(dp=1, mp=8)
            step = SparseTrainStep(
                lambda d, e, b: (e["t"] ** 2).sum() * d["s"],
                {"s": np.float32(1.0)}, {"t": table},
                ids_fn=lambda b: {"t": b["ids"]}, mesh=mesh)
            before = stat_snapshot()
            float(step({"ids": np.array([1, 1, 2], np.int32)}))
            after = stat_snapshot()
        finally:
            _no_mesh()
        assert after["embedding_lookup_ids"] - \
            before["embedding_lookup_ids"] == 3
        assert after["sparse_rows_touched"] - \
            before["sparse_rows_touched"] == 2
        # 2 unique of 3 ids -> 666666 ppm
        assert after["embedding_unique_ratio"] == 666666

    def test_embedding_report_section(self, capsys):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "trace_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "trace_report.py"))
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        assert "embedding" in tr.SECTIONS
        events = [
            {"name": "sparse.step", "cat": "step",
             "args": {"step": 0, "lookup_ids": 100, "unique_ids": 40,
                      "exchange_bytes": 5000, "shards": 8}},
            {"name": "sparse.lookup", "cat": "sparse",
             "args": {"ids": 20, "exchange_bytes": 900, "shards": 8}},
        ]
        out = tr.embedding_report(events)
        assert out["train_steps"] == 1
        assert out["serve_lookups"] == 1
        assert out["lookup_ids"] == 120
        assert out["exchange_bytes"] == 5900
        assert out["unique_ratio"] == pytest.approx(0.4)
        assert "duplicate-heavy" in out["verdict"]
        # empty events -> section drops (run_sections contract)
        assert tr.embedding_report([]) == {}
