"""Paged-attention decode kernel — Pallas TPU, block-table gather.

The serving engine's paged KV cache (ISSUE 7) keeps every layer's K/V
in one shared block pool ``(n_blocks, n_layers, n_heads, block_size,
head_dim)``; a slot's tokens live in the blocks its block table names,
in table order. The batched one-token decode step then needs attention
of a single query per slot over that slot's *scattered* blocks, at one
layer — this module provides it:

- :func:`paged_attention_arrays` — the routed entry every caller uses.
  It takes the whole 5-D pool and a ``layer`` and addresses blocks by
  ``(block, layer)``: a layer's ``(n_blocks, nh, bs, hd)`` slab is never
  cut out of the pool. (A 4-D one-layer pool, ``layer`` left out, is
  the same call on a pool of one layer.) On TPU it runs the Pallas
  kernel; anywhere else (CPU/GPU) it runs the IDENTICAL composed jnp
  math (gather blocks by table, mask, softmax), pinned by
  interpret-mode parity tests (tests/test_paged_attention.py,
  ``-m kernels``).

Kernel design (mirrors the flash forward):
- grid ``(batch, max_blocks_per_slot)``, kv-block innermost so the VMEM
  scratch (m, l, acc) carries across one slot's block sweep;
- the block table, per-slot lengths and the layer ride as SCALAR
  PREFETCH (pltpu.PrefetchScalarGridSpec): the K/V BlockSpec index_map
  reads ``(tables[b, i], layer)`` to DMA that block of that layer — one
  contiguous ``(nh, bs, hd)`` run of the pool — directly: no gather and
  no slab materialization, HBM traffic is exactly the live blocks;
- blocks past a slot's length are skipped with ``pl.when`` (their table
  entries point at reserved garbage block 0, so the dead DMA is safe);
- scores/softmax statistics in f32, accumulator f32, output cast back.

Ragged decode (ISSUE 17, ``FLAGS_ragged_decode``): the compute guard
skips dead blocks, but the K/V DMAs still sweep the PADDED table width —
a slot with 1 live block in a W=64 table pays 64 block fetches. With the
flag on, the K/V index map clamps dead iterations to the slot's LAST
live block (``tbl[b, min(i, max((len-1)//bs, 0))]``); consecutive grid
steps that name the same block elide the DMA on TPU, so HBM traffic
tracks live tokens instead of table width. Output is bit-identical: the
clamp only changes which block dead (compute-guarded) iterations would
have fetched, never what is computed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..core import native as _native
from . import autotune as _autotune
from .flash_attention import NEG_INF, _on_tpu

__all__ = ["paged_attention_arrays", "gather_blocks"]

# Module-local mirror of FLAGS_ragged_decode (no core.native subscript in
# jit-reachable code); set_flags syncs it through the watcher list.
_ragged = [bool(_native.ragged_decode[0])]
_native.ragged_decode_watchers.append(
    lambda v: _ragged.__setitem__(0, bool(v)))


def _whole_pool(kb, vb, layer):
    """(kb, vb, layer) of the 5-D pool: a one-layer (n_blocks, nh, bs,
    hd) pool, ``layer`` left out, is the pool with L = 1 (a free
    reshape), read at layer 0."""
    if layer is None:
        return kb[:, None], vb[:, None], 0
    return kb, vb, layer


def gather_blocks(pool, tables, layer):
    """The blocks ``tables`` (..., W) names at ``layer`` of the pool
    (n_blocks, L, nh, bs, hd), in table order, as one contiguous
    (..., nh, W * bs, hd) context.

    Each block is read in place: W contiguous runs of the pool a table
    row, never the layer's slab. The pool is pinned to its own row-major
    layout here: left free, XLA re-lays the whole carried pool out to
    suit a consumer's transpose, which costs two copies of it a program
    (and does not fit the chip)."""
    pool = with_layout_constraint(
        pool, Layout(major_to_minor=tuple(range(pool.ndim))))
    g = jnp.moveaxis(pool[tables, layer], -3, -4)    # (..., nh, W, bs, hd)
    return g.reshape(g.shape[:-3] + (-1, g.shape[-1]))


def _paged_attention_reference(q, kb, vb, tables, lengths, scale,
                               layer=None):
    """Composed jnp fallback: gather each slot's blocks into a contiguous
    (nh, W*bs, hd) view, mask positions >= length, softmax in f32.

    q (B, nh, hd); kb/vb (n_blocks, nh, bs, hd), or with ``layer`` the
    whole pool (n_blocks, L, nh, bs, hd), of which only the W blocks a
    table names are gathered at that layer; tables (B, W) int32;
    lengths (B,) int32 — live tokens per slot (including the token whose
    K/V was just written). Returns (B, nh, hd) in q.dtype."""
    kb, vb, layer = _whole_pool(kb, vb, layer)
    k = gather_blocks(kb, tables, layer)
    v = gather_blocks(vb, tables, layer)
    s = jnp.einsum("bhd,bhkd->bhk", q, k.astype(q.dtype)) * scale
    live = jnp.arange(k.shape[2])[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bhkd->bhd", w, v.astype(q.dtype))


def _decode_kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_ref, v_ref,
                   o_ref, m_s, l_s, acc_s, *, block_size, n_blocks, scale):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    ln = lengths_ref[b]

    @pl.when(i * block_size < ln)
    def _compute():
        # heads ride the leading (untiled) dim and the single query is a
        # one-row matrix: Mosaic takes a batched matmul only with rank-3
        # operands, and this way no head count or block size is refused
        q = q_ref[0]                                   # (nh, 1, hd)
        k = k_ref[...]                                 # (nh, bs, hd)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < ln, s, NEG_INF)            # (nh, 1, bs) f32
        m_prev = m_s[...]                              # (nh, 1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, -1, keepdims=True)
        m_s[...] = m_new
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l = l_s[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "ragged"))
def _paged_decode(q, kb, vb, tables, lengths, scale, interpret=False,
                  ragged=False, layer=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, hd = q.shape
    kb, vb, layer = _whole_pool(kb, vb, layer)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    bs = kb.shape[3]
    W = tables.shape[1]
    if ragged:
        # Clamp dead sweep iterations to the slot's last LIVE block: the
        # index map then repeats that block index for every i past the
        # live range, and repeated consecutive indices elide the DMA —
        # decode HBM traffic tracks live tokens, not padded table width.
        # Compute stays guarded by pl.when(i*bs < len), so which block a
        # dead iteration names never affects the output.
        def _kv_idx(b, i, tbl, ln, lay):
            last = jnp.maximum((ln[b] - 1) // bs, 0)
            return (tbl[b, jnp.minimum(i, last)], lay[0], 0, 0, 0)
    else:
        def _kv_idx(b, i, tbl, ln, lay):
            return (tbl[b, i], lay[0], 0, 0, 0)
    q_spec = pl.BlockSpec((1, nh, 1, hd),
                          lambda b, i, tbl, ln, lay: (b, 0, 0, 0))
    # block and layer are squeezed: the kernel sees the same rank-3
    # (nh, bs, hd) operand, one contiguous run of the pool, per DMA
    kv_spec = pl.BlockSpec((None, None, nh, bs, hd), _kv_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, W),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((nh, 1, 1), jnp.float32),    # running max
            pltpu.VMEM((nh, 1, 1), jnp.float32),    # running sum
            pltpu.VMEM((nh, 1, hd), jnp.float32),   # output accumulator
        ],
    )
    kernel = functools.partial(_decode_kernel, block_size=bs, n_blocks=W,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="pallas_paged_decode",
    )(tables, lengths, layer, q.reshape(B, nh, 1, hd), kb, vb)
    return out.reshape(B, nh, hd)


def paged_attention_arrays(q, kb, vb, tables, lengths, scale=None,
                           interpret=None, ragged=None, layer=None):
    """Single-token paged attention over a block pool (routed entry).

    q (B, nh, hd) — one query per slot; kb/vb — the WHOLE pool
    (n_blocks, L, nh, bs, hd) with ``layer`` (an int or a traced int32
    scalar) naming the layer to attend at: blocks are read in place by
    ``(block, layer)``. With ``layer=None`` kb/vb are a one-layer pool
    (n_blocks, nh, bs, hd). tables (B, W) int32 block tables (entries
    past a slot's live blocks must point at a safe block, the engine
    reserves pool block 0); lengths (B,) int32 live tokens.

    ``ragged=None`` follows ``FLAGS_ragged_decode``; True/False forces
    the live-length-clamped (resp. full-width) K/V sweep. Either way the
    result is bit-identical — ragged only changes DMA traffic.

    Off-TPU (unless ``interpret=True`` is forced) this returns the
    identical composed jnp math, so callers never branch. On a TPU every
    shape takes the kernel: there is no shape fallback.
    """
    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if ragged is None:
        ragged = _ragged[0]
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _paged_attention_reference(q, kb, vb, tables, lengths,
                                              scale, layer=layer)
    return _paged_decode(q, kb, vb, jnp.asarray(tables, jnp.int32),
                         jnp.asarray(lengths, jnp.int32), float(scale),
                         interpret=bool(interpret), ragged=bool(ragged),
                         layer=layer)


# -- autotune family (ISSUE 17) ---------------------------------------------
# Single-candidate: the decode kernel has no free block knob (block_size
# is fixed by the pool layout). Registered so ``python -m tools.autotune``
# can pre-warm the key and --check covers committed entries.

def _paged_candidates(shape, dtype):
    return [{}]


def _paged_bench(shape, dtype, config):
    import numpy as np

    B, nh, hd, bs, W = (int(d) for d in shape)
    rng = np.random.default_rng(0)
    n_blocks = B * W + 1
    q = jnp.asarray(rng.standard_normal((B, nh, hd)).astype(dtype))
    kb = jnp.asarray(
        rng.standard_normal((n_blocks, nh, bs, hd)).astype(dtype))
    vb = jnp.asarray(
        rng.standard_normal((n_blocks, nh, bs, hd)).astype(dtype))
    tables = jnp.asarray(
        1 + np.arange(B * W, dtype=np.int32).reshape(B, W))
    lengths = jnp.full((B,), W * bs, jnp.int32)
    out = _paged_decode(q, kb, vb, tables, lengths,
                        1.0 / math.sqrt(hd), interpret=not _on_tpu(),
                        ragged=bool(_ragged[0]))
    jax.block_until_ready(out)


_autotune.register_family("paged_attention", _paged_candidates,
                          _paged_bench)
