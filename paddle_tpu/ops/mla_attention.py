"""Absorbed-weights latent-attention decode over a paged latent pool.

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) caches one
row a token a layer: the normed latent ``c`` (``R`` wide) and the
rotated shared key ``k_rope`` (``Dr`` wide), side by side. The serving
engine keeps them in one block pool

    pool : (n_blocks, n_layers, block_size, R + Dr [+ padding])

and a slot's tokens live in the blocks its block table names. With the
key up-projection absorbed into the query (``q_lat = W_kvb_K^T q_nope``)
every head reads the SAME cached row, so one block feeds an
``(n_heads, R + Dr) x (R + Dr, block_size)`` matmul: the heads are the
rows of a real matrix product, not a batch of one-row products.

- :func:`mla_decode_arrays` — the routed entry. On a TPU the Pallas
  kernel; anywhere else the identical composed ``jax.numpy`` (gather the
  table's rows at the layer, mask, softmax), pinned against it by
  interpret-mode tests (tests/test_mla.py, ``-m kernels``).

Kernel design (after ops/paged_attention.py):
- the grid is the flat list of live work of ``ops/block_walk.py``: one
  step for each group of ``G`` live blocks of a slot, none for a
  table's padding or a slot of length 0, its length the list's
  (dynamic) ``count``. The model builds it once a tick
  (:func:`decode_walk`) and hands it in at every layer;
- tables, lengths, the layer and the list ride as scalar prefetch; the
  pool is handed to the call ``G`` times, input ``j`` with an index map
  that reads ``(tables[slot[n], col[n] + j], layer)``, clamped to the
  slot's last live block (a repeated block index elides the DMA): ``G``
  blocks a grid step, each one contiguous ``(block_size, R + Dr)`` run
  of the pool, read in place (a layer's slab is never cut out), and
  worked on as one ``(G * block_size, .)`` tile (at one block a step
  the softmax's rescaling of the accumulator, not the reads, sets the
  time: PERF.md section 6);
- the VMEM scratch (m, l, acc) is reset on a slot's first step and the
  output row written on its last;
- scores and softmax statistics in f32, f32 accumulator, output in the
  query's type. The output is the attention-weighted LATENT (``R`` wide
  a head); the caller applies the value up-projection. A slot of length
  0 has no step: its row is zeros, set outside the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental.layout import Layout, with_layout_constraint

from .block_walk import LiveSteps, check_walk, live_steps, step_block
from .flash_attention import NEG_INF, _on_tpu

__all__ = ["mla_decode_arrays", "gather_rows", "decode_walk"]

BLOCKS_PER_STEP = 16


def decode_walk(lengths, width: int, block_size: int) -> LiveSteps:
    """The kernel's work-list for slots of ``lengths`` (B,) live tokens
    and tables ``width`` wide. The same for every layer: build it once a
    tick, outside the layer loop, and pass it as ``walk=``."""
    return live_steps(lengths, width, block_size,
                      math.gcd(width, BLOCKS_PER_STEP))


def gather_rows(pool, tables, layer):
    """The rows ``tables`` (..., W) names at ``layer`` of the latent
    pool (n_blocks, L, bs, D), in table order: (..., W * bs, D)."""
    # pinned to its own row-major layout, as ``gather_blocks`` pins the
    # K/V pool: left free, XLA re-lays the whole carried pool out
    pool = with_layout_constraint(
        pool, Layout(major_to_minor=tuple(range(pool.ndim))))
    g = pool[tables, layer]                          # (..., W, bs, D)
    return g.reshape(g.shape[:-3] + (-1, g.shape[-1]))


def _mla_decode_reference(q_lat, q_rope, pool, tables, lengths, scale,
                          layer):
    """Composed fallback. q_lat (B, nh, R), q_rope (B, nh, Dr); pool
    (n_blocks, L, bs, >= R + Dr: a row may be padded); tables (B, W);
    lengths (B,) live tokens (the row just written included). Returns
    (B, nh, R)."""
    R = q_lat.shape[-1]
    rows = gather_rows(pool, tables, layer).astype(q_lat.dtype)
    c, kr = rows[..., :R], rows[..., R:R + q_rope.shape[-1]]
    s = (jnp.einsum("bhr,bkr->bhk", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bkd->bhk", q_rope, kr,
                      preferred_element_type=jnp.float32)) * scale
    live = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhk,bkr->bhr", w, c)


def _decode_kernel(tables_ref, lengths_ref, layer_ref, slot_ref, col_ref,
                   first_ref, last_ref, ql_ref, qr_ref, *refs, block_size,
                   group, rank, scale):
    from jax.experimental import pallas as pl

    kv_refs, o_ref = refs[:group], refs[group]
    m_s, l_s, acc_s = refs[group + 1:]
    n = pl.program_id(0)

    @pl.when(first_ref[n] == 1)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    nt = (((1,), (1,)), ((), ()))          # A @ B^T
    # the step's G blocks as ONE (G * bs, .) tile: one score product,
    # one softmax update and one value product a step (a block past the
    # slot's last live one holds that block again: masked)
    blk = kv_refs[0][...] if group == 1 else jnp.concatenate(
        [r[...] for r in kv_refs], axis=0)
    c, kr = blk[:, :rank], blk[:, rank:rank + qr_ref.shape[-1]]
    s = (jax.lax.dot_general(ql_ref[...], c, nt,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr_ref[...], kr, nt,
                               preferred_element_type=jnp.float32)
         ) * scale                                     # (nh, G * bs) f32
    pos = col_ref[n] * block_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(pos < lengths_ref[slot_ref[n]], s, NEG_INF)
    m_prev = m_s[...]                                  # (nh, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = alpha * l_s[...] + jnp.sum(p, -1, keepdims=True)
    m_s[...] = m_new
    acc_s[...] = acc_s[...] * alpha + jnp.dot(
        p.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when(last_ref[n] == 1)
    def _finalize():
        o_ref[...] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "group"))
def _mla_decode(q_lat, q_rope, pool, tables, lengths, layer, scale,
                interpret=False, walk=None, group=BLOCKS_PER_STEP):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, R = q_lat.shape
    Dr = q_rope.shape[-1]
    bs, D = pool.shape[2:]
    W = tables.shape[1]
    group = math.gcd(W, group)
    if walk is None:
        walk = live_steps(lengths, W, bs, group)
    check_walk(walk, B, W, group)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def kv_idx(j):
        def idx(n, tbl, ln, lay, slot, col, first, last):
            return (step_block(n, j, tbl, ln, slot, col, bs), lay[0], 0, 0)
        return idx

    def per_slot(width):
        return pl.BlockSpec(
            (None, nh, width),
            lambda n, tbl, ln, lay, slot, col, first, last: (slot[n], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(walk.count[0],),
        in_specs=[per_slot(R), per_slot(Dr)] + [
            pl.BlockSpec((None, None, bs, D), kv_idx(j))
            for j in range(group)],
        out_specs=per_slot(R),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),     # running max
            pltpu.VMEM((nh, 1), jnp.float32),     # running sum
            pltpu.VMEM((nh, R), jnp.float32),     # latent accumulator
        ],
    )
    kernel = functools.partial(_decode_kernel, block_size=bs, group=group,
                               rank=R, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, R), q_lat.dtype),
        interpret=interpret,
        name="pallas_mla_latent_decode",
    )(tables, lengths, layer, walk.slot, walk.col, walk.first, walk.last,
      q_lat, q_rope, *([pool] * group))
    # a slot of length 0 had no step: its row of the output is undefined
    return jnp.where((lengths > 0)[:, None, None], out, 0)


def mla_decode_arrays(q_lat, q_rope, pool, tables, lengths, scale, layer,
                      interpret=None, walk=None):
    """One absorbed query a slot over its paged latent rows.

    q_lat (B, nh, R) — the no-position query parts through the key
    up-projection; q_rope (B, nh, Dr) — the rotated parts; pool the WHOLE
    latent pool (n_blocks, L, bs, R + Dr) with ``layer`` (an int or a
    traced int32 scalar) naming the layer to read (a row may be padded
    past R + Dr: the padding is never read); tables (B, W) int32
    (entries past a slot's live blocks point at the reserved sink block);
    lengths (B,) int32 live tokens; ``walk`` is :func:`decode_walk` of
    these lengths and this table width, built once where the call is
    made at every layer (left out, the kernel builds it). Returns the
    attention-weighted latent (B, nh, R); a slot of length 0 costs no
    grid step and its row is zeros. Off-TPU (unless ``interpret=True``)
    the identical composed math runs, so callers never branch."""
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _mla_decode_reference(q_lat, q_rope, pool, tables,
                                         lengths, scale, layer)
    return _mla_decode(q_lat, q_rope, pool, jnp.asarray(tables, jnp.int32),
                       jnp.asarray(lengths, jnp.int32), layer,
                       scale=float(scale), interpret=bool(interpret),
                       walk=walk)
