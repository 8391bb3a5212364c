"""Paged-attention decode kernel — Pallas TPU, block-table gather.

The serving engine's paged KV cache (ISSUE 7) keeps every layer's K/V
in one shared block pool ``(n_blocks, n_layers, n_heads, block_size,
head_dim)``; a slot's tokens live in the blocks its block table names,
in table order. The batched one-token decode step then needs attention
of a single query per slot over that slot's *scattered* blocks, at one
layer — this module provides it. (The step's new row reaches the pool
before this kernel reads it: ``ops/pool_write.py`` writes the live
lanes' rows in place, one call a layer; this kernel only reads.)

- :func:`paged_attention_arrays` — the routed entry every caller uses.
  It takes the whole 5-D pool and a ``layer`` and addresses blocks by
  ``(block, layer)``: a layer's ``(n_blocks, nh, bs, hd)`` slab is never
  cut out of the pool. (A 4-D one-layer pool, ``layer`` left out, is
  the same call on a pool of one layer.) On TPU it runs the Pallas
  kernel; anywhere else (CPU/GPU) it runs the IDENTICAL composed jnp
  math (gather blocks by table, mask, softmax), pinned by
  interpret-mode parity tests (tests/test_paged_attention.py,
  ``-m kernels``).

Kernel design:
- the grid is a flat list of LIVE work, ``ops/block_walk.live_steps``:
  one step for each group of ``G`` live blocks of a slot, no step for a
  table's padding and none for a slot of length 0. Its length is the
  list's ``count``, a dynamic grid bound: a tick with two short
  requests in 32 slots runs a handful of steps a layer, not ``32 x
  table width``. The list is the same at every layer, so the model
  builds it once a tick (:func:`decode_walk`) and hands it in;
- tables, lengths, the layer and the list ride as SCALAR PREFETCH
  (pltpu.PrefetchScalarGridSpec). The pool is handed to the call ``G``
  times for keys and ``G`` times for values; input ``j``'s index map
  reads ``(tables[slot[n], col[n] + j], layer)``, clamped to the slot's
  last live block (a repeated block index elides the DMA): ``G``
  contiguous ``(nh, bs, hd)`` runs of the pool a step, read in place
  (no gather, no slab of a layer), and worked on as ONE ``(nh, G * bs,
  hd)`` tile with one softmax update;
- the VMEM scratch (m, l, acc) is reset on a slot's first step and
  carries across its steps; the output row is written on its last;
- scores/softmax statistics in f32, accumulator f32, output cast back;
- a slot of length 0 has no step: its output row is zeros, set outside
  the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from . import autotune as _autotune
from .block_walk import LiveSteps, check_walk, live_steps, step_block
from .flash_attention import NEG_INF, _on_tpu

__all__ = ["paged_attention_arrays", "gather_blocks", "decode_walk"]

# Blocks a grid step, before ``gcd`` with the table width. Chosen on the
# chip from {4, 8, 16} (PERF.md section 6, PR 31): 16 reads 79-83% of the
# HBM roofline with every slot full (8: 72-75%, 4: 58-60%) and within
# 0.06 ms a tick of 8 with one or two slots live.
BLOCKS_PER_STEP = 16


def decode_walk(lengths, width: int, block_size: int) -> LiveSteps:
    """The kernel's work-list for slots of ``lengths`` (B,) live tokens
    and tables ``width`` wide. The same for every layer: build it once a
    tick, outside the layer loop, and pass it as ``walk=``."""
    return live_steps(lengths, width, block_size,
                      math.gcd(width, BLOCKS_PER_STEP))


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


def _decode_kernel(tables_ref, lengths_ref, layer_ref, slot_ref, col_ref,
                   first_ref, last_ref, q_ref, *refs, block_size, group,
                   scale):
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
        # the step's G blocks as ONE (nh, G * bs, hd) tile (a block past
        # the slot's last live one holds that block again: masked)
        return blocks[0][...] if group == 1 else jnp.concatenate(
            [r[...] for r in blocks], axis=1)

    # heads ride the leading (untiled) dim and the single query is a
    # one-row matrix: Mosaic takes a batched matmul only with rank-3
    # operands, and this way no head count or block size is refused
    q = q_ref[0]                                       # (nh, 1, hd)
    k, v = tile(k_refs), tile(v_refs)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    pos = col_ref[n] * block_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 2)
    s = jnp.where(pos < lengths_ref[slot_ref[n]], s, NEG_INF)
    m_prev = m_s[...]                                  # (nh, 1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # (nh, 1, G * bs) f32
    l_s[...] = alpha * l_s[...] + jnp.sum(p, -1, keepdims=True)
    m_s[...] = m_new
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[n] == 1)
    def _finalize():
        o_ref[0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "group"))
def _paged_decode(q, kb, vb, tables, lengths, scale, interpret=False,
                  layer=None, walk=None, group=BLOCKS_PER_STEP):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, hd = q.shape
    kb, vb, layer = _whole_pool(kb, vb, layer)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    bs = kb.shape[3]
    W = tables.shape[1]
    group = math.gcd(W, group)
    if walk is None:
        walk = live_steps(lengths, W, bs, group)
    check_walk(walk, B, W, group)

    def kv_idx(j):
        def idx(n, tbl, ln, lay, slot, col, first, last):
            return (step_block(n, j, tbl, ln, slot, col, bs), lay[0], 0, 0, 0)
        return idx

    q_spec = pl.BlockSpec(
        (1, nh, 1, hd),
        lambda n, tbl, ln, lay, slot, col, first, last: (slot[n], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(walk.count[0],),
        # block and layer are squeezed: each DMA is one contiguous
        # (nh, bs, hd) run of the pool
        in_specs=[q_spec] + 2 * [
            pl.BlockSpec((None, None, nh, bs, hd), kv_idx(j))
            for j in range(group)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((nh, 1, 1), jnp.float32),    # running max
            pltpu.VMEM((nh, 1, 1), jnp.float32),    # running sum
            pltpu.VMEM((nh, 1, hd), jnp.float32),   # output accumulator
        ],
    )
    kernel = functools.partial(_decode_kernel, block_size=bs, group=group,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="pallas_paged_decode",
    )(tables, lengths, layer, walk.slot, walk.col, walk.first, walk.last,
      q.reshape(B, nh, 1, hd), *([kb] * group), *([vb] * group))
    # a slot of length 0 had no step: its row of the output is undefined
    return jnp.where((lengths > 0)[:, None, None], out.reshape(B, nh, hd), 0)


def paged_attention_arrays(q, kb, vb, tables, lengths, scale=None,
                           interpret=None, layer=None, walk=None):
    """Single-token paged attention over a block pool (routed entry).

    q (B, nh, hd) — one query per slot; kb/vb — the WHOLE pool
    (n_blocks, L, nh, bs, hd) with ``layer`` (an int or a traced int32
    scalar) naming the layer to attend at: blocks are read in place by
    ``(block, layer)``. With ``layer=None`` kb/vb are a one-layer pool
    (n_blocks, nh, bs, hd). tables (B, W) int32 block tables (entries
    past a slot's live blocks must point at a safe block, the engine
    reserves pool block 0); lengths (B,) int32 live tokens.

    ``walk`` is :func:`decode_walk` of these lengths and this table
    width, built once where the call is made at every layer of a model;
    left out, the kernel builds it. A slot of length 0 costs no grid
    step and its output row is zeros (the composed path returns the
    mean of its table's values there; no caller reads such a row).

    Off-TPU (unless ``interpret=True`` is forced) this returns the
    identical composed jnp math, so callers never branch. On a TPU every
    shape takes the kernel: there is no shape fallback.
    """
    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _paged_attention_reference(q, kb, vb, tables, lengths,
                                              scale, layer=layer)
    return _paged_decode(q, kb, vb, jnp.asarray(tables, jnp.int32),
                         jnp.asarray(lengths, jnp.int32), float(scale),
                         interpret=bool(interpret), layer=layer, walk=walk)


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
                        1.0 / math.sqrt(hd), interpret=not _on_tpu())
    jax.block_until_ready(out)


_autotune.register_family("paged_attention", _paged_candidates,
                          _paged_bench)
