"""Decode attention for grouped, differential heads over a block pool.

Differential attention (arXiv:2410.05258) as SambaY's layers use it
(``models/sambay.py``): query heads come in pairs ``(q1, q2)``, key
heads in pairs ``(k1, k2)``, and a pair of query heads reads one value
head of twice the width,

    o = softmax(q1 k1^T s) V - lambda softmax(q2 k2^T s) V.

This module computes the two softmaxes' read-outs for one query a lane,
``a1 = softmax(q1 k1^T s) V`` and ``a2`` alike, over the rows a lane's
table names in a pool of blocks ``(n_blocks, L, block_size, width)``,
at one layer. A row is every key head side by side (``n_kv * d``
lanes) in one array and every value head (``n_v * dv`` lanes) in
another: for SambaY 20 key heads of 64 and 10 value heads of 128, 1,280
lanes each, whole 128-lane tiles. It has two callers: the window ring
of a slot (its ``window / block_size`` blocks, a table the model makes)
and the paged pool of the one full-attention layer, whose rows eight
layers read.

- :func:`wide_queries` places each query head in the lanes of its key
  head and zeroes the rest: ``q_wide . k_row`` is then the head's dot
  product with its own key head, and one matmul scores every head of a
  lane against every row.
- :func:`hybrid_decode` — the routed entry. On a TPU it is the Pallas
  kernel ``hybrid_diff_decode``; anywhere else (unless
  ``interpret=True``) the composed ``jax.numpy`` it is pinned to
  (tests/test_sambay.py).

Kernel design (the walk and the index maps are ``ops/paged_attention``'s):
- the grid is ``ops/block_walk.live_steps``: ``group`` blocks of a slot
  a step, no step for padding or a lane of length 0;
- a step reads each of its K and V blocks ONCE for every query head of
  the lane, both halves of every pair: scores ``(nq, group * bs)`` in
  float32 from one matmul of the wide queries with the key rows, one
  online-softmax update, and ``p V`` into a ``(nq, n_v * dv)`` float32
  accumulator;
- on a lane's last step each query row keeps the lanes of its own value
  head (``row // (nq // n_v)``) and is divided by its softmax sum: the
  output is ``(B, nq, dv)`` float32, row ``j`` head ``j``'s read-out.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .block_walk import LiveSteps, check_walk, live_steps, step_block
from .flash_attention import NEG_INF, _on_tpu

__all__ = ["wide_queries", "hybrid_decode", "decode_walk",
           "BLOCKS_PER_STEP"]

# blocks of 64 rows a grid step: 512 rows, a whole 512-token window ring
BLOCKS_PER_STEP = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def decode_walk(lengths, width: int, block_size: int) -> LiveSteps:
    """The kernel's work-list for lanes of ``lengths`` (B,) rows and
    tables ``width`` wide; the same at every layer that reads the same
    pool, so a model builds it once a tick."""
    return live_steps(lengths, width, block_size,
                      math.gcd(width, BLOCKS_PER_STEP))


def wide_queries(q, n_kv: int):
    """q (B, nq, d), query heads in order; head ``j`` reads key head
    ``2 (j // 4) + j % 2`` (pairs of query heads over pairs of key heads,
    two query pairs a key pair). -> (B, nq, n_kv * d): each head in its
    key head's lanes, zeros elsewhere."""
    B, nq, d = q.shape
    if nq != 2 * n_kv:
        raise ValueError(f"{nq} query heads over {n_kv} key heads: the "
                         "differential layout pairs two query pairs with "
                         "each key pair")
    lane = jnp.arange(n_kv * d)
    head = jnp.arange(nq)
    mine = (2 * (head[:, None] // 4) + head[:, None] % 2) == lane // d
    return jnp.where(mine, jnp.tile(q, (1, 1, n_kv)), 0).astype(q.dtype)


def _pick_heads(acc, n_v: int):
    """acc (..., nq, n_v * dv) -> (..., nq, dv): row ``j`` keeps the lanes
    of value head ``j // (nq // n_v)``."""
    nq, wv = acc.shape[-2:]
    dv = wv // n_v
    row = jax.lax.broadcasted_iota(jnp.int32, acc.shape[:-1] + (dv,),
                                   acc.ndim - 2) // (nq // n_v)
    out = jnp.zeros(acc.shape[:-1] + (dv,), acc.dtype)
    for g in range(n_v):
        out = out + jnp.where(row == g, acc[..., g * dv:(g + 1) * dv], 0)
    return out


def _composed(qw, kpool, vpool, tables, lengths, layer, scale, n_v):
    k = kpool[tables, layer]                     # (B, W, bs, Wk)
    v = vpool[tables, layer]
    B, W, bs = k.shape[:3]
    k = k.reshape(B, W * bs, -1)
    v = v.reshape(B, W * bs, -1)
    s = jnp.einsum("bjw,bkw->bjk", qw, k.astype(qw.dtype),
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(W * bs)[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    acc = jnp.einsum("bjk,bkv->bjv", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = _pick_heads(acc, n_v)
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


def _kernel(tables_ref, lengths_ref, layer_ref, slot_ref, col_ref,
            first_ref, last_ref, q_ref, *refs, block_size, group, scale,
            n_v):
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, m_s, l_s, acc_s = refs[2 * group:]
    n = pl.program_id(0)

    @pl.when(first_ref[n] == 1)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def tile(blocks):
        return blocks[0][...] if group == 1 else jnp.concatenate(
            [r[...] for r in blocks], axis=0)

    q = q_ref[0]                                  # (nq, Wk)
    k, v = tile(k_refs), tile(v_refs)             # (group * bs, W*)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = col_ref[n] * block_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(pos < lengths_ref[slot_ref[n]], s, NEG_INF)
    m_prev = m_s[...]                             # (nq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = alpha * l_s[...] + jnp.sum(p, -1, keepdims=True)
    m_s[...] = m_new
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[n] == 1)
    def _finalize():
        o_ref[0] = _pick_heads(acc_s[...], n_v) / l_s[...]


@functools.partial(jax.jit, static_argnames=("scale", "n_v", "interpret"))
def _call(qw, kpool, vpool, tables, lengths, layer, walk, scale, n_v,
          interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nq, wk = qw.shape
    bs, wv = kpool.shape[2], vpool.shape[3]
    W = tables.shape[1]
    group = math.gcd(W, BLOCKS_PER_STEP)
    check_walk(walk, B, W, group)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def kv_idx(j):
        def idx(n, tbl, ln, lay, slot, col, first, last):
            return (step_block(n, j, tbl, ln, slot, col, bs), lay[0], 0, 0)
        return idx

    def lane(n, tbl, ln, lay, slot, col, first, last):
        return (slot[n], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(walk.count[0],),
        in_specs=[pl.BlockSpec((1, nq, wk), lane)]
        + [pl.BlockSpec((None, None, bs, wk), kv_idx(j))
           for j in range(group)]
        + [pl.BlockSpec((None, None, bs, wv), kv_idx(j))
           for j in range(group)],
        out_specs=pl.BlockSpec((1, nq, wv // n_v), lane),
        scratch_shapes=[pltpu.VMEM((nq, 1), jnp.float32),
                        pltpu.VMEM((nq, 1), jnp.float32),
                        pltpu.VMEM((nq, wv), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=bs, group=group, scale=scale,
                          n_v=n_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nq, wv // n_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hybrid_diff_decode",
    )(tables, lengths, layer, walk.slot, walk.col, walk.first, walk.last,
      qw, *([kpool] * group), *([vpool] * group))
    # a lane of length 0 had no step: its rows are undefined
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


def hybrid_decode(qw, kpool, vpool, tables, lengths, layer, n_v: int,
                  scale: float, walk=None, interpret=None):
    """Both softmax read-outs of every query head, one query a lane.

    qw (B, nq, n_kv * d) — :func:`wide_queries`; kpool (n_blocks, L, bs,
    n_kv * d) and vpool (n_blocks, L, bs, n_v * dv) the WHOLE pools,
    read in place at ``layer`` (an int or a traced int32 scalar); tables
    (B, W) int32, each lane's blocks in row order (an entry past its
    rows names a safe block); lengths (B,) int32 rows a lane reads from
    the start of its table (0: none). ``walk`` is :func:`decode_walk` of
    these lengths and this width, built once where the call is made at
    several layers. Returns (B, nq, dv) float32: row ``j`` head ``j``'s
    read-out over its value head, zeros for a lane of length 0.
    """
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _composed(qw, kpool, vpool, tables, lengths, layer,
                             scale, n_v)
    if walk is None:
        walk = decode_walk(lengths, tables.shape[1], kpool.shape[2])
    return _call(qw, kpool, vpool, tables, lengths, layer, walk,
                 float(scale), n_v, interpret=bool(interpret))
