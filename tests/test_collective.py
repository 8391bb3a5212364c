"""Collective API tests vs numpy semantics, per rank.

Pattern: reference test_collective_base.py:32 — run the collective for
every rank and compare each rank's result against numpy. Here "ranks" are
slots of the 8-device CPU mesh axis, and eager collectives use the
rank-major layout (tensor.shape[0] == nranks).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import distributed as dist
from paddle_tpu.parallel import create_mesh
from paddle_tpu.parallel.mesh import set_mesh

N = 8


@pytest.fixture(autouse=True)
def _mesh():
    # a default group another file left behind in this process holds its
    # own rank count
    dist.destroy_process_group()
    mesh = create_mesh(dp=N, devices=jax.devices()[:N])
    yield mesh
    set_mesh(None)
    dist.destroy_process_group()


def _rank_major(shape=(N, 4), seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=shape).astype(np.float32)


class TestEagerCollectives:
    def test_all_reduce_sum(self):
        x = _rank_major()
        t = paddle_tpu.to_tensor(x)
        out = dist.all_reduce(t)
        want = np.broadcast_to(x.sum(0, keepdims=True), x.shape)
        np.testing.assert_allclose(np.asarray(out._data), want, rtol=1e-6)

    def test_all_reduce_max(self):
        x = _rank_major(seed=1)
        out = dist.all_reduce(paddle_tpu.to_tensor(x), op=dist.ReduceOp.MAX)
        want = np.broadcast_to(x.max(0, keepdims=True), x.shape)
        np.testing.assert_allclose(np.asarray(out._data), want, rtol=1e-6)

    def test_reduce_to_dst(self):
        x = _rank_major(seed=2)
        out = dist.reduce(paddle_tpu.to_tensor(x), dst=3)
        want = x.copy()
        want[3] = x.sum(0)
        np.testing.assert_allclose(np.asarray(out._data), want, rtol=1e-6)

    def test_broadcast(self):
        x = _rank_major(seed=3)
        out = dist.broadcast(paddle_tpu.to_tensor(x), src=2)
        want = np.broadcast_to(x[2:3], x.shape)
        np.testing.assert_allclose(np.asarray(out._data), want, rtol=1e-6)

    def test_all_gather(self):
        x = _rank_major(seed=4)
        got = []
        dist.all_gather(got, paddle_tpu.to_tensor(x))
        assert len(got) == N
        for i in range(N):
            np.testing.assert_allclose(np.asarray(got[i]._data), x[i],
                                       rtol=1e-6)

    def test_sendrecv_moves_slice(self):
        x = _rank_major(seed=5)
        out = dist.sendrecv(paddle_tpu.to_tensor(x), [(1, 4)])
        # slice 4 now holds rank 1's data; ranks without a source got zeros
        np.testing.assert_allclose(np.asarray(out._data)[4], x[1], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out._data)[0], 0.0)

    def test_alltoall(self):
        x = [_rank_major(seed=10 + i) for i in range(N)]
        out = []
        dist.alltoall([paddle_tpu.to_tensor(xi) for xi in x], out)
        assert len(out) == N
        for j in range(N):
            want = np.stack([x[i][j] for i in range(N)])
            np.testing.assert_allclose(np.asarray(out[j]._data), want,
                                       rtol=1e-6)

    def test_scatter(self):
        parts = [_rank_major((4,), seed=20 + i) for i in range(N)]
        t = paddle_tpu.to_tensor(np.zeros((N, 4), np.float32))
        out = dist.scatter(t, [paddle_tpu.to_tensor(p) for p in parts], src=0)
        for i in range(N):
            np.testing.assert_allclose(np.asarray(out._data)[i], parts[i],
                                       rtol=1e-6)

    def test_wrong_layout_raises(self):
        bad = paddle_tpu.to_tensor(np.zeros((3, 4), np.float32))
        with pytest.raises(RuntimeError, match="rank-major"):
            dist.all_reduce(bad)

    def test_no_mesh_raises(self):
        set_mesh(None)
        with pytest.raises(RuntimeError, match="mesh"):
            dist.all_reduce(paddle_tpu.to_tensor(np.zeros((N, 2), np.float32)))

    def test_eager_send_without_src_raises(self):
        x = paddle_tpu.to_tensor(_rank_major(seed=6))
        with pytest.raises(NotImplementedError):
            dist.send(x, dst=1)


class TestTracedCollectives:
    """In-trace semantics through shard_map directly."""

    def test_psum_inside_shard_map(self, _mesh=None):
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
        x = _rank_major(seed=7)

        def body(x):
            return dist.psum(x, "data")

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False))
        out = np.asarray(f(x))
        want = np.broadcast_to(x.sum(0, keepdims=True), x.shape)
        np.testing.assert_allclose(out, want, rtol=1e-6)

    def test_send_with_explicit_src_in_trace(self):
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
        x = _rank_major(seed=8)

        def body(x):
            return dist.send(x, dst=2, src=0)

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False))
        out = np.asarray(f(x))
        np.testing.assert_allclose(out[2], x[0], rtol=1e-6)
