"""Ring attention — sequence/context parallelism over the mesh.

NEW capability relative to the reference (SURVEY.md §5: the 2021-era
reference has no sequence/context parallelism or ring attention — its
longest-sequence answer is fused attention kernels + TP head splitting).
This is the TPU-native long-context design:

- the sequence dim of Q/K/V is sharded over a mesh axis (any of the Fleet
  axes; by convention "sharding" doubles as the context axis the way
  Megatron-CP reuses a dp subgroup);
- each device computes blockwise attention of its local Q chunk against a
  rotating K/V chunk, accumulating with the online-softmax recurrence (the
  flash-attention update), while K/V hop device-to-device with
  lax.ppermute — XLA lowers the hop to a CollectivePermute over ICI, and
  the [S, S] score matrix never exists globally NOR locally beyond one
  (S_loc × S_loc) block pair;
- the whole ring is a lax.scan, so jax.grad differentiates it (the
  transpose of ppermute is the reverse ring) — no hand-written backward
  schedule.

Causality is enforced per block pair from global chunk indices: a device's
Q chunk attends fully to earlier chunks, triangularly to its own, not at
all to later ones (compute is masked, not skipped — the ring must rotate
anyway; a skip-ahead schedule is a later optimisation).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import get_mesh

__all__ = ["ring_attention", "ring_attention_sharded"]

_NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One (S_q × S_k) block: scores + masked logits, returns
    (unnormalised out, rowmax, rowsum) for the online-softmax merge.

    Matmuls run at the INPUT dtype's MXU rate (bf16 in training) with f32
    accumulation (preferred_element_type); softmax statistics and the
    running accumulator stay f32 — same numerics contract as the Pallas
    flash kernel."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)           # (b,h,q,1)
    # guard fully-masked rows (m = -inf → exp(nan))
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None):
    """Blockwise ring attention; call INSIDE shard_map with the seq dim of
    q/k/v sharded over ``axis_name``. Shapes: (B, H, S_local, D)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]
    if scale is None:
        scale = q.shape[-1] ** -0.5

    q_pos = idx * s_loc + jnp.arange(s_loc)          # global q positions

    def tick(carry, step):
        o, m, l, kc, vc = carry
        # the chunk we currently hold started at device (idx - step) % n
        k_chunk = (idx - step) % n
        k_pos = k_chunk * s_loc + jnp.arange(s_loc)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((s_loc, s_loc), bool)
        ob, mb, lb = _block_attn(q, kc, vc, mask[None, None], scale)
        # online-softmax merge of (o,m,l) with the new block
        m_new = jnp.maximum(m, mb)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(mb - m_new)
        o = o * alpha + ob * beta
        l = l * alpha + lb * beta
        # rotate K/V one hop around the ring
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (o, m_new, l, kc, vc), None

    b, h, _, d = q.shape
    # mark the zero-init carries as device-varying over the same manual
    # axes as the inputs so the scan carry type matches its output
    # (shard_map vma typing)
    vma = tuple(set(jax.typeof(q).vma) | set(jax.typeof(k).vma)
                | set(jax.typeof(v).vma))
    pv = lambda x: jax.lax.pcast(x, vma, to="varying")
    o0 = pv(jnp.zeros((b, h, s_loc, d), jnp.float32))
    m0 = pv(jnp.full((b, h, s_loc, 1), _NEG_INF, jnp.float32))
    l0 = pv(jnp.zeros((b, h, s_loc, 1), jnp.float32))
    (o, m, l, _, _), _ = jax.lax.scan(
        tick, (o0, m0, l0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, causal: bool = True,
                           seq_axis: str = "sharding",
                           batch_axis: Optional[str] = "data",
                           head_axis: Optional[str] = "model",
                           mesh: Optional[Mesh] = None,
                           scale: Optional[float] = None):
    """shard_map wrapper: q/k/v are global (B, H, S, D) arrays; seq dim
    sharded over ``seq_axis``, batch over ``batch_axis``, heads over
    ``head_axis`` (pass None to keep an axis replicated)."""
    mesh = mesh or get_mesh()
    if mesh is None:
        raise RuntimeError("ring_attention_sharded needs a mesh")
    spec = P(batch_axis, head_axis, seq_axis, None)

    fn = functools.partial(ring_attention, axis_name=seq_axis,
                           causal=causal, scale=scale)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec)
    return mapped(q, k, v)
