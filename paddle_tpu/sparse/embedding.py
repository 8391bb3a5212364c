"""Row-sharded embedding tables + the sparse-gradient lookup.

Parity surface: the reference's distributed lookup table
(``paddle.static.nn.sparse_embedding`` + fleet parameter-server mode,
python/paddle/incubate/distributed/fleet — ids hashed to a PS shard,
lookups batched per shard, gradients shipped back as SelectedRows).
On TPU there is no parameter server: the table is ONE array row-sharded
over the mesh's "model" axis and the id routing that the PS did over
RPC becomes an in-program all-to-all over ICI.

Layout — mod-sharding. Shard ``s`` of ``N`` owns the logical ids
``{i : i % N == s}``; logical id ``i`` is stored at row
``(i % N) * rows_per_shard + i // N`` of the backing array, so a plain
``P("model", None)`` row partition hands each shard exactly its mod
class. Mod (not block) sharding is what the reference PS uses: CTR id
spaces are frequency-sorted, so block sharding would pin every hot id
to shard 0 while mod spreads them evenly.

Lookup (:func:`sharded_lookup`) runs under shard_map with the batch
split over the table axis: each shard buckets its local ids by owner
(``id % N``), all-to-alls the buckets out, gathers its owned rows
(one-hot-free ``jnp.take``), and all-to-alls the vectors back — two
permutation collectives moving ``~B*(4 + dim*itemsize)`` bytes instead
of the ``B*dim`` all-reduce a masked-gather + psum would cost.

The sparse GRADIENT path (:func:`sparse_lookup`) is a custom-VJP gather
whose backward aggregates duplicate-id cotangents with ``jnp.unique`` +
``segment_sum`` and writes each touched row once — the SelectedRows
semantics of the reference's ``sparse=True`` embeddings, with the
rows+values pair consumed directly by :class:`~paddle_tpu.sparse.
optimizer.SparseAdam` in the compiled training path
(sparse/train_step.py) so the full dense gradient never materializes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..monitor import stats as _mstats
from ..monitor.trace import span as _trace_span
from ..parallel.mesh import get_mesh, mesh_shape

__all__ = ["ShardedEmbedding", "sharded_lookup", "sparse_lookup",
           "stored_rows", "to_stored", "to_logical"]


# -- mod-sharded storage layout ---------------------------------------------

def _padded_rows(rows: int, n_shards: int) -> int:
    return -(-rows // n_shards) * n_shards


def stored_rows(ids, rows: int, n_shards: int):
    """Stored-layout row index for logical ids (identity when unsharded)."""
    if n_shards <= 1:
        return ids
    rps = _padded_rows(rows, n_shards) // n_shards
    return (ids % n_shards) * rps + ids // n_shards


def to_stored(table, n_shards: int):
    """Permute a logical-order (rows, dim) table into the mod-sharded
    storage layout, padding rows up to a multiple of ``n_shards``."""
    table = np.asarray(table)
    rows = table.shape[0]
    if n_shards <= 1:
        return table
    padded = _padded_rows(rows, n_shards)
    out = np.zeros((padded,) + table.shape[1:], table.dtype)
    idx = np.asarray(stored_rows(np.arange(rows), rows, n_shards))
    out[idx] = table
    return out


def to_logical(table, rows: int, n_shards: int):
    """Inverse of :func:`to_stored`: recover logical order, drop padding.
    This is what checkpoints store — the on-disk layout is shard-count
    independent (sharding is placement, not content)."""
    table = np.asarray(table)
    if n_shards <= 1:
        return table[:rows]
    idx = np.asarray(stored_rows(np.arange(rows), rows, n_shards))
    return table[idx]


# -- sparse-gradient lookup (unique + segment_sum backward) -----------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sparse_lookup(padding_idx, rows, weight, ids):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        out = out * (ids != padding_idx)[..., None].astype(out.dtype)
    return out


def _sparse_lookup_fwd(padding_idx, rows, weight, ids):
    return _sparse_lookup(padding_idx, rows, weight, ids), (ids,)


def _sparse_lookup_bwd(padding_idx, rows, res, g):
    (ids,) = res
    flat = ids.reshape(-1)
    n = flat.size
    g2 = g.reshape(n, -1)
    if padding_idx is not None:
        g2 = g2 * (flat != padding_idx)[:, None].astype(g2.dtype)
    # duplicate ids aggregate ONCE (SelectedRows merge): unique rows +
    # per-row segment sums, then a single collision-free scatter. The
    # `rows` fill value is out of range, so padded entries drop.
    uids, inv = jnp.unique(flat, size=n, fill_value=rows,
                           return_inverse=True)
    seg = jax.ops.segment_sum(g2, inv.reshape(-1), num_segments=n)
    dw = jnp.zeros((rows, g2.shape[-1]), g.dtype).at[uids].set(
        seg, mode="drop")
    return dw, np.zeros(ids.shape, jax.dtypes.float0)


_sparse_lookup.defvjp(_sparse_lookup_fwd, _sparse_lookup_bwd)


def sparse_lookup(weight, ids, padding_idx: Optional[int] = None):
    """``weight[ids]`` whose backward aggregates duplicate-id cotangents
    via ``jnp.unique`` + ``segment_sum`` before one scatter — values and
    gradients match the dense ``nn.functional.embedding`` path exactly
    (pinned in tests/test_sparse.py against the one-hot matmul)."""
    return _sparse_lookup(padding_idx, int(weight.shape[0]), weight,
                          jnp.asarray(ids))


def unique_grad_rows(ids, grads, rows: int):
    """(unique_rows, summed_grads) for a batch of per-id cotangents —
    the SelectedRows pair the sparse optimizer consumes. ``rows`` is the
    fill value for the padding tail (out of range, scatters drop it)."""
    flat = jnp.asarray(ids).reshape(-1)
    n = flat.size
    g2 = grads.reshape(n, -1)
    uids, inv = jnp.unique(flat, size=n, fill_value=rows,
                           return_inverse=True)
    seg = jax.ops.segment_sum(g2, inv.reshape(-1), num_segments=n)
    return uids, seg


# -- all-to-all exchange lookup under shard_map -----------------------------

def _exchange_body(table_shard, ids_local, *, axis, n_shards, rows, rps):
    """Per-shard lookup body. ``ids_local``: this shard's slice of the
    batch (logical ids, sentinel ``rows`` marks padding). Buckets ids by
    owner shard, exchanges them, gathers owned rows, exchanges back."""
    b = ids_local.shape[0]
    owner = ids_local % n_shards
    # slot within the destination bucket: rank among earlier same-owner
    # ids (cumsum over the one-hot owner matrix — O(b*N), fully static)
    onehot = (owner[:, None] == jnp.arange(n_shards)[None, :])
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    slot = jnp.take_along_axis(rank, owner[:, None], axis=1)[:, 0]
    # worst case every local id belongs to one owner: bucket cap = b
    pos = owner * b + slot
    send = jnp.full((n_shards * b,), rows, ids_local.dtype).at[pos].set(
        ids_local).reshape(n_shards, b)
    # row j of recv = the ids shard j wants from us
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
    valid = recv < rows
    local = jnp.clip(recv // n_shards, 0, rps - 1)
    vals = jnp.take(table_shard, local.reshape(-1), axis=0).reshape(
        n_shards, b, -1)
    vals = vals * valid[..., None].astype(vals.dtype)
    # send each requester its rows back; undo the bucket permutation
    back = jax.lax.all_to_all(vals, axis, split_axis=0, concat_axis=0)
    return back.reshape(n_shards * b, -1)[pos]


def sharded_lookup(table, ids, mesh=None, axis: str = "model",
                   rows: Optional[int] = None):
    """Gather logical ``ids`` from a mod-sharded ``P(axis, None)`` table.

    Traceable (use inside jit with the mesh installed). ``table`` is in
    STORED layout (``to_stored``); ``rows`` is the logical row count
    (defaults to the stored row count). The batch is split over ``axis``
    so each shard routes only its slice; output is the full (ids.shape,
    dim) array, allclose-pinned to the dense replicated lookup."""
    mesh = mesh or get_mesh()
    n_shards = mesh_shape(mesh).get(axis, 1) if mesh is not None else 1
    ids = jnp.asarray(ids)
    if rows is None:
        rows = int(table.shape[0])
    if n_shards <= 1:
        return jnp.take(table, ids.reshape(-1), axis=0).reshape(
            ids.shape + (table.shape[-1],))
    rps = _padded_rows(rows, n_shards) // n_shards
    flat = ids.reshape(-1).astype(jnp.int32)
    n = flat.size
    pad = (-n) % n_shards
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), rows, flat.dtype)])
    body = functools.partial(_exchange_body, axis=axis, n_shards=n_shards,
                             rows=rows, rps=rps)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(P(axis, None), P(axis)),
                        out_specs=P(axis, None))(table, flat)
    if pad:
        out = out[:n]
    return out.reshape(ids.shape + (out.shape[-1],))


def exchange_bytes(n_ids: int, dim: int, n_shards: int,
                   itemsize: int = 4) -> int:
    """Wire bytes one sharded lookup moves: the id buckets out and the
    gathered vectors back, counting only off-shard traffic."""
    if n_shards <= 1:
        return 0
    off = (n_shards - 1) / n_shards
    return int(n_ids * off * (4 + dim * itemsize))


# -- the table object -------------------------------------------------------

class ShardedEmbedding:
    """A giant embedding table row-sharded over the mesh.

    ::

        mesh = create_mesh(dp=1, mp=8)
        emb = ShardedEmbedding(1 << 24, 64, mesh=mesh)
        vecs = emb.lookup(ids)            # (ids.shape, 64), exchange path

    The table lives once across the mesh (``P("model", None)``,
    mod-permuted rows — see module docstring); per-device HBM is
    ``rows * dim * itemsize / n_shards``. ``lookup`` runs the jitted
    all-to-all exchange and feeds the embedding_report gauges.
    """

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 mesh=None, axis: str = "model", padding_idx=None,
                 dtype=jnp.float32, seed: int = 0, scale: float = 0.01):
        self.mesh = mesh or get_mesh()
        self.axis = axis
        self.rows = int(num_embeddings)
        self.dim = int(embedding_dim)
        self.n_shards = (mesh_shape(self.mesh).get(axis, 1)
                         if self.mesh is not None else 1)
        self.padding_idx = (None if padding_idx is None else
                            padding_idx if padding_idx >= 0
                            else self.rows + padding_idx)
        key = jax.random.key(seed)
        logical = (scale * jax.random.normal(
            key, (self.rows, self.dim))).astype(dtype)
        if self.padding_idx is not None:
            logical = logical.at[self.padding_idx].set(0.0)
        self.spec = P(axis, None)
        stored = to_stored(np.asarray(logical), self.n_shards)
        if self.mesh is not None:
            self.table = jax.device_put(
                stored, NamedSharding(self.mesh, self.spec))
        else:
            self.table = jnp.asarray(stored)
        self._lookup_jit = None

    @property
    def bytes_per_device(self) -> int:
        return int(self.table.nbytes) // max(self.n_shards, 1)

    def logical_table(self) -> np.ndarray:
        """Host copy in logical row order (checkpoint layout)."""
        return to_logical(np.asarray(self.table), self.rows, self.n_shards)

    def _fn(self, table, ids):
        out = sharded_lookup(table, ids, mesh=self.mesh, axis=self.axis,
                             rows=self.rows)
        if self.padding_idx is not None:
            out = out * (ids != self.padding_idx)[..., None].astype(
                out.dtype)
        return out

    def lookup(self, ids):
        """Eager lookup: jitted exchange + observability. For use inside
        a larger jitted program call :func:`sharded_lookup` directly."""
        ids = jnp.asarray(ids)
        if self._lookup_jit is None:
            self._lookup_jit = jax.jit(self._fn)
        n = int(np.prod(ids.shape) or 0)
        xbytes = exchange_bytes(n, self.dim, self.n_shards,
                                np.dtype(self.table.dtype).itemsize)
        _mstats.EMBEDDING_LOOKUP_IDS.add(n)
        _mstats.EMBEDDING_EXCHANGE_BYTES.add(xbytes)
        with _trace_span("sparse.lookup", cat="sparse",
                         args={"ids": n, "exchange_bytes": xbytes,
                               "shards": self.n_shards,
                               "table_rows": self.rows}):
            if self.mesh is not None:
                with self.mesh:
                    return self._lookup_jit(self.table, ids)
            return self._lookup_jit(self.table, ids)
