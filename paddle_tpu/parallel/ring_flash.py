"""Ring attention with FLASH-KERNEL blocks — the full Ring Attention
design (context parallelism whose per-hop block computation is the fused
online-softmax kernel, not a materialized S_loc x S_loc einsum).

This supersedes ring_attention.py's jnp blockwise path for performance:
- per hop, the local Q chunk attends to the visiting K/V chunk through
  the Pallas flash kernel (ops/flash_attention.py) — bf16 MXU matmuls,
  f32 softmax stats, no S^2 buffer even locally;
- hops merge via the (out, lse) log-sum-exp recurrence;
- the BACKWARD is the hand-written ring-attention backward (the
  published algorithm): the forward saves only (out, lse); the backward
  re-rotates K/V and calls the flash BACKWARD kernel per hop with the
  GLOBAL lse/delta — p = exp(s - lse_global) makes every per-hop ds
  exact without storing per-hop probabilities — while dK/dV partial sums
  ride the same ring and arrive home after n hops.

Causality per hop is the chunk relation (earlier = full attention,
own = triangular, later = dead) dispatched by lax.switch over three
statically-compiled block variants — compile-time control flow, not a
runtime mask over dead work.

Off-TPU the block computation falls back to a jnp reference with
identical (out, lse) semantics, so the same code path is testable on the
virtual CPU mesh.

Reference relation: the 2021-era reference has NO sequence/context
parallelism (SURVEY §5) — this is a new capability; the kernel reuse
mirrors how its fused ops share CUDA kernels between fwd/bwd
(operators/fused/fmha_ref.h).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import (_flash_backward, _flash_forward, _on_tpu,
                                   flash_attention_arrays)
from .mesh import get_mesh

__all__ = ["ring_flash_attention", "ring_flash_attention_sharded"]

_NEG = -1e30

# chunk relations (lax.switch branch indices)
_FULL, _DIAG, _DEAD = 0, 1, 2


def _pick_block(s, cap):
    """Largest multiple of 128 <= cap that tiles s exactly, or None.

    The flash kernels floor-divide the sequence into a grid of
    ``s // block`` blocks — a chunk length that is NOT a multiple of the
    block size (S_local = 640/768/896 with the default 512/1024 blocks)
    would silently compute only the first ``n * block`` rows."""
    for b in range(min(cap, s), 127, -128):
        if s % b == 0:
            return b
    return None


def _supported_by_kernel(q):
    b, h, s, d = q.shape
    return _on_tpu() and s >= 128 and s % 128 == 0 and \
        (d == 64 or d % 128 == 0) and \
        _pick_block(s, 512) is not None and _pick_block(s, 1024) is not None


# -- per-hop forward blocks: (q, k, v) -> (out, lse) -----------------------

def _ref_block_fwd(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sl = s.shape[-1]
        mask = jnp.tril(jnp.ones((sl, sl), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, _NEG / 2)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32) / l
    lse = (m + jnp.log(l))[..., 0]
    return out.astype(q.dtype), lse


def _block_fwd(q, k, v, causal, scale):
    """One block: normalized out + log-sum-exp, both per query row."""
    if _supported_by_kernel(q):
        b, h, s, _ = q.shape
        out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                                  block_q=_pick_block(s, 512),
                                  block_k=_pick_block(k.shape[2], 1024))
        return out, lse.reshape(b, h, s)
    return _ref_block_fwd(q, k, v, causal, scale)


# -- per-hop backward blocks -----------------------------------------------

def _ref_block_bwd(q, k, v, out, lse, g, delta, causal, scale):
    """Gradients of one hop given GLOBAL lse/delta (ring-attn backward):
    p = exp(s - lse) is each entry's GLOBAL softmax weight, so per-hop
    contributions sum exactly to the full-attention gradient."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sl = s.shape[-1]
        mask = jnp.tril(jnp.ones((sl, sl), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    p = jnp.exp(s - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p.astype(g.dtype), g,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds.astype(q.dtype), q,
                    preferred_element_type=jnp.float32)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def _block_bwd(q, k, v, out, lse, g, causal, scale):
    """(dq, dk, dv) for one hop. The TPU path is the Pallas backward
    kernel with the GLOBAL lse (it computes delta = rowsum(g*out)
    internally from the global out, which equals the global delta)."""
    if _supported_by_kernel(q):
        b, h, sq = q.shape[0], q.shape[1], q.shape[2]
        return _flash_backward(q, k, v, out,
                               lse.reshape(b * h, sq, 1), g,
                               causal=causal, scale=scale,
                               block_q=_pick_block(sq, 512),
                               block_k=_pick_block(k.shape[2], 1024))
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    return _ref_block_bwd(q, k, v, out, lse, g, delta, causal, scale)


# -- ring forward/backward (inside shard_map, axis bound) ------------------

def _rel_of(step, idx, n, causal):
    """Chunk relation for the hop holding chunk (idx - step) % n.
    Non-causal attention has no dead hops — every chunk attends fully."""
    k_chunk = (idx - step) % n
    if not causal:
        return jnp.where(k_chunk == idx, _DIAG, _FULL)
    return jnp.where(k_chunk == idx, _DIAG,
                     jnp.where(k_chunk < idx, _FULL, _DEAD))


def _merge(o1, lse1, o2, lse2):
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return (o1.astype(jnp.float32) * w1
            + o2.astype(jnp.float32) * w2), lse


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def full_b(q, kc, vc):
        return _block_fwd(q, kc, vc, False, scale)

    def diag_b(q, kc, vc):
        return _block_fwd(q, kc, vc, causal, scale)

    def dead_b(q, kc, vc):
        # fresh constants need the same varying manual axes as the live
        # branches' outputs (shard_map vma typing)
        return _pv_like((jnp.zeros_like(q),
                         jnp.full((b, h, s_loc), _NEG, jnp.float32)),
                        (q, kc, vc))

    def tick(carry, step):
        o, lse, kc, vc = carry
        rel = _rel_of(step, idx, n, causal)
        ob, lseb = jax.lax.switch(rel, (full_b, diag_b, dead_b), q, kc, vc)
        o, lse = _merge(o, lse, ob, lseb)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (o, lse, kc, vc), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
    o0, lse0 = _pv_like((o0, lse0), (q, k, v))
    (o, lse, _, _), _ = jax.lax.scan(tick, (o0, lse0, k, v),
                                     jnp.arange(n))
    return o.astype(q.dtype), lse


def _ring_bwd_impl(q, k, v, out, lse, g, axis_name, causal, scale):
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def full_b(q, kc, vc):
        return _block_bwd(q, kc, vc, out, lse, g, False, scale)

    def diag_b(q, kc, vc):
        return _block_bwd(q, kc, vc, out, lse, g, causal, scale)

    def dead_b(q, kc, vc):
        return _pv_like((jnp.zeros_like(q), jnp.zeros_like(kc),
                         jnp.zeros_like(vc)), (q, kc, vc))

    def tick(carry, step):
        dq, kc, vc, dkc, dvc = carry
        rel = _rel_of(step, idx, n, causal)
        dqb, dkb, dvb = jax.lax.switch(rel, (full_b, diag_b, dead_b),
                                       q, kc, vc)
        dq = dq + dqb.astype(jnp.float32)
        dkc = dkc + dkb.astype(jnp.float32)
        dvc = dvc + dvb.astype(jnp.float32)
        # rotate K/V AND their gradient accumulators together: after n
        # hops the accumulators arrive back at the chunk's owner with
        # every hop's contribution summed
        kc, vc, dkc, dvc = (jax.lax.ppermute(x, axis_name, perm)
                            for x in (kc, vc, dkc, dvc))
        return (dq, kc, vc, dkc, dvc), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0, dk0, dv0 = _pv_like((dq0, dk0, dv0), (q, k, v))
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        tick, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _pv_like(zeros_trees, ref_trees):
    """Mark fresh zero carries device-varying over the same manual axes
    as the real inputs (shard_map vma typing)."""
    vma = set()
    for r in ref_trees:
        vma |= set(jax.typeof(r).vma)
    out = []
    for z in zeros_trees:
        need = tuple(vma - set(jax.typeof(z).vma))
        out.append(jax.lax.pcast(z, need, to="varying") if need else z)
    return tuple(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention(q, k, v, axis_name, causal=True,
                         scale: Optional[float] = None):
    """Call INSIDE shard_map with the seq dim of q/k/v sharded over
    ``axis_name``. Shapes (B, H, S_local, D); returns (B, H, S_local, D).
    """
    out, _ = _ring_fwd_rule(q, k, v, axis_name, causal, scale)
    return out


def _ring_fwd_rule(q, k, v, axis_name, causal, scale):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, float(scale))
    return out, (q, k, v, out, lse)


def _ring_bwd_rule(axis_name, causal, scale, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_bwd_impl(q, k, v, out, lse, g, axis_name, causal,
                          float(scale))


ring_flash_attention.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_flash_attention_sharded(q, k, v, causal: bool = True,
                                 seq_axis: str = "sharding",
                                 batch_axis: Optional[str] = "data",
                                 head_axis: Optional[str] = "model",
                                 mesh: Optional[Mesh] = None,
                                 scale: Optional[float] = None):
    """shard_map wrapper mirroring ring_attention_sharded: global
    (B, H, S, D) arrays, seq dim sharded over ``seq_axis``."""
    mesh = mesh or get_mesh()
    if mesh is None:
        raise RuntimeError("ring_flash_attention_sharded needs a mesh")
    spec = P(batch_axis, head_axis, seq_axis, None)
    if dict(mesh.shape).get(seq_axis, 1) == 1 and _on_tpu():
        # degenerate ring (context degree 1): no hop to take — the block
        # computation IS full flash attention
        fn = functools.partial(flash_attention_arrays, causal=causal,
                               scale=scale)
        if mesh.size == 1:
            return fn(q, k, v)
    else:
        fn = functools.partial(ring_flash_attention, axis_name=seq_axis,
                               causal=causal, scale=scale)
    # also at degree 1 on a multi-chip mesh: GSPMD cannot partition a
    # Mosaic kernel, each device runs it on its own shard
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec)
    return mapped(q, k, v)
