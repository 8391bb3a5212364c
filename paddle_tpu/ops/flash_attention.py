"""Flash attention — Pallas TPU kernels with KV blocking (fwd + bwd).

TPU-native answer to the reference's fused attention
(operators/fused/fused_transformer_op.cu, fmha_ref.h): instead of a CUDA
fMHA, Pallas kernels that stream K/V through VMEM in blocks with
online-softmax accumulation, so neither the [S, S] score matrix nor the
full K/V ever needs to sit in fast memory at once.

Design notes (tuned on a v5e chip):
- grid (bh/block_b, q blocks, kv blocks), kv innermost so the VMEM
  scratch (m, l, acc) carries across the kv sweep; block_b batches
  several batch*head rows per grid step to amortize per-step overhead
  at short sequence lengths.
- matmuls run at the input dtype's MXU rate (bf16 in training) with f32
  accumulation; softmax statistics stay f32.
- backward is ONE fused kernel: dK/dV accumulate in scratch over the
  inner q sweep, while dQ per-kv partials go to HBM and are summed by
  XLA — S and dP are computed once instead of twice (4 matmuls, the
  same count as XLA's saved-P backward, but without materializing P).
- lse/delta travel as [.., seq, 1] f32 — no lane-broadcast HBM waste.

Layout: [batch, heads, seq, head_dim] (matches MultiHeadAttention internals).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import autotune as _autotune

NEG_INF = -1e30


@functools.cache
def _on_tpu() -> bool:
    """True when the default backend is a TPU. Asked once per process; a
    backend that fails to initialize raises here instead of reading as
    "not a TPU" and routing every kernel entry to its composed math."""
    return jax.default_backend() == "tpu"


def _vma(*arrays):
    """Mesh axes the arrays vary over under shard_map (empty outside
    it): pallas_call cannot infer it for its outputs, so the kernels
    declare that theirs vary like their inputs."""
    return frozenset().union(*(jax.typeof(x).vma for x in arrays))


def _attention_reference(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), k=klen - qlen)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _pick_block_b(bh: int, bq: int, bk: int) -> int:
    """Largest divisor of bh keeping the f32 score block under ~8MB.
    The backward kernel holds ~3 score-sized f32 intermediates (s, p, dp)
    plus double-buffered input blocks inside the 64MB VMEM scoped limit;
    measured on v5e: bb=8 at 512x512 blocks beats bb=4 by ~7%."""
    budget = 8 * 1024 * 1024
    bb = 1
    for cand in (2, 4, 8, 16):
        if bh % cand == 0 and cand * bq * bk * 4 <= budget:
            bb = cand
    return bb


def _auto_block(s: int, cap: int = 2048) -> int:
    """Largest power-of-two block <= cap dividing s. Measured on v5e
    (BERT-base shapes): whole-sequence blocks win up to 2048 (41.0 vs 38.0
    sps at seq 2048) — the online-softmax streaming only pays once S*S
    no longer fits VMEM comfortably."""
    for b in (cap, cap // 2, cap // 4, cap // 8, 128):
        if b <= s and s % b == 0:
            return b
    return s


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                scale, causal, block_q, block_k, n_kv, off=0):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # Causal: skip kv blocks strictly above this q block's diagonal.
    live = (qi * block_q + block_q - 1 + off >= ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[...]                                  # [bb, bq, d]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(q_pos + off >= k_pos, s, NEG_INF)
        m_prev = m_s[:, :, 0:1]                         # [bb, bq, 1]
        l_prev = l_s[:, :, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # [bb, bq, bk] f32
        l_s[:] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, -1, keepdims=True),
                                  l_s.shape)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_s[:, :, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[...] = m_s[:, :, 0:1] + jnp.log(l)      # [bb, bq, 1]


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "block_b", "interpret"))
def _flash_forward(q, k, v, causal=False, scale=None, block_q=512,
                   block_k=1024, block_b=None, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    n_q, n_kv = sq // bq, sk // bk
    bh = b * h
    bb = block_b if block_b else _pick_block_b(bh, bq, bk)
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    off = sk - sq
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, n_kv=n_kv, off=off)
    if causal:
        # FlashAttention-2-style DMA clamp: kv blocks strictly above the
        # q block's diagonal are pl.when-skipped in the kernel, but the
        # plain (i, kk, 0) map still DMAs them. Clamping dead kk to the
        # last LIVE kv block makes consecutive dead steps re-reference
        # the same block, so the pipeline elides their copies — the
        # compute (and output) is bit-identical, only dead traffic goes.
        def _kv_idx(i, j, kk):
            return (i, jnp.minimum(
                kk, jnp.clip((j * bq + bq - 1 + off) // bk, 0, n_kv - 1)), 0)
    else:
        def _kv_idx(i, j, kk):
            return (i, kk, 0)
    vma = _vma(q, k, v)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32, vma=vma)),
        grid=(bh // bb, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((bb, bq, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((bb, bk, d), _kv_idx),
            pl.BlockSpec((bb, bk, d), _kv_idx),
        ],
        out_specs=(pl.BlockSpec((bb, bq, d), lambda i, j, kk: (i, j, 0)),
                   pl.BlockSpec((bb, bq, 1), lambda i, j, kk: (i, j, 0))),
        scratch_shapes=[
            pltpu.VMEM((bb, bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bb, bq, 128), jnp.float32),   # running sum
            pltpu.VMEM((bb, bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="flash_forward",
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse


# --------------------------------------------------------------------------
# backward, one fused kernel (see module docstring). delta = rowsum(dO*O)
# is one fused XLA pass producing a tiny [bh, sq, 1] input.
# --------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dqp_ref, dk_s, dv_s, *,
                scale, causal, block_q, block_k, n_q, off=0):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    live = (qi * block_q + block_q - 1 + off >= ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[...]                                  # [bb, bq, d]
        k = k_ref[...]                                  # [bb, bk, d]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...]                              # [bb, bq, 1]
        delta = delta_ref[...]                          # [bb, bq, 1]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                            # [bb, bq, bk] f32
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, p.shape, 2)
            p = jnp.where(q_pos + off >= k_pos, p, 0.0)
        pb = p.astype(do.dtype)
        dv_s[:] += jax.lax.dot_general(pb, do, (((1,), (1,)), ((0,), (0,))),
                                       preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [bb, bq, bk]
        dk_s[:] += jax.lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                                       preferred_element_type=jnp.float32)
        dqp_ref[0] = jax.lax.dot_general(
            ds, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).astype(dqp_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        dqp_ref[0] = jnp.zeros_like(dqp_ref[0])

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "block_b", "interpret"))
def _flash_backward(q, k, v, o, lse, g, causal=False, scale=None,
                    block_q=512, block_k=1024, block_b=None, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    n_q, n_kv = sq // bq, sk // bk
    bh = b * h
    bb = block_b if block_b else _pick_block_b(bh, bq, bk)
    qr, kr, vr = (x.reshape(bh, -1, d) for x in (q, k, v))
    dor = g.reshape(bh, sq, d)
    # delta = rowsum(dO * O): one fused XLA pass, tiny [bh, sq, 1] output
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, sq, 1)
    dqp_dtype = q.dtype if n_kv == 1 else jnp.float32

    off = sk - sq
    if causal:
        # mirror of the forward DMA clamp: with q innermost, the dead
        # iterations are q blocks strictly BELOW this kv block's
        # diagonal (j < first live block ceil((kk*bk - off - bq + 1)/bq)
        # = (kk*bk - off) // bq); pin them to that first live block so
        # their q/do/lse/delta copies elide. Dead steps only write the
        # zero dqp block, so the outputs are bit-identical.
        def _q_idx(i, kk, j):
            return (i, jnp.maximum(
                j, jnp.clip((kk * bk - off) // bq, 0, n_q - 1)), 0)
    else:
        def _q_idx(i, kk, j):
            return (i, j, 0)
    vma = _vma(q, k, v, o, lse, g)
    dk, dv, dqp = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_q=n_q, off=off),
        out_shape=(jax.ShapeDtypeStruct((bh, sk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype, vma=vma),
                   jax.ShapeDtypeStruct((n_kv, bh, sq, d), dqp_dtype,
                                        vma=vma)),
        grid=(bh // bb, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((bb, bq, d), _q_idx),
            pl.BlockSpec((bb, bk, d), lambda i, kk, j: (i, kk, 0)),
            pl.BlockSpec((bb, bk, d), lambda i, kk, j: (i, kk, 0)),
            pl.BlockSpec((bb, bq, d), _q_idx),
            pl.BlockSpec((bb, bq, 1), _q_idx),
            pl.BlockSpec((bb, bq, 1), _q_idx),
        ],
        out_specs=(pl.BlockSpec((bb, bk, d), lambda i, kk, j: (i, kk, 0)),
                   pl.BlockSpec((bb, bk, d), lambda i, kk, j: (i, kk, 0)),
                   pl.BlockSpec((1, bb, bq, d),
                                lambda i, kk, j: (kk, i, j, 0))),
        scratch_shapes=[pltpu.VMEM((bb, bk, d), jnp.float32),
                        pltpu.VMEM((bb, bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="flash_backward",
    )(qr, kr, vr, dor, lse, delta)

    dq = jnp.sum(dqp, axis=0).astype(q.dtype) if n_kv > 1 else dqp[0]
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# --------------------------------------------------------------------------
# differentiable entry
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, block_b, interpret):
    out, _ = _flash_forward(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            block_b=block_b, interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, block_b,
                    interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              block_b=block_b, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, block_b, interpret,
                    res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           block_b=block_b, interpret=interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_arrays(q, k, v, causal=False, scale=None, block_q=None,
                           block_k=None, block_b=None, interpret=None):
    """Array-level entry (used inside jit traces / functional code).

    Differentiable end to end in Pallas: KV-blocked online-softmax forward,
    delta-trick fused backward. block_q/block_k default to the measured
    v5e auto policy (_auto_block); pass explicitly to override.

    head_dim handling: the MXU wants the minor dim in {64, k·128}. Other
    widths (e.g. 96 = 1536/16 in GPT-760M shapes) are zero-padded to the
    next multiple of 128 — zero columns change neither the q·k scores nor
    add output mass, the padded output columns are sliced off, and their
    cotangents are zero, so gradients match the unpadded math exactly.
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    auto = block_q is None and block_k is None and block_b is None
    if block_q is None:
        block_q = _auto_block(q.shape[2])
    if block_k is None:
        block_k = _auto_block(k.shape[2])
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _attention_reference(q, k, v, causal, scale)
    sq, sk = q.shape[2], k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    if not (sq % bq == 0 and sk % bk == 0 and sq >= 128 and sk >= 128):
        _autotune.note_fallback(
            "flash", q.shape,
            "seq_q=%d/seq_k=%d not tileable by block %dx%d (needs seq >= "
            "128 and block-divisible)" % (sq, sk, bq, bk))
        return _attention_reference(q, k, v, causal, scale)
    if d % 128 != 0 and d != 64:
        dp = -(-d // 128) * 128
        pad = ((0, 0), (0, 0), (0, 0), (0, dp - d))
        out = flash_attention_arrays(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), causal=causal,
            scale=scale,
            block_q=None if auto else block_q,
            block_k=None if auto else block_k,
            block_b=None if auto else block_b,
            interpret=interpret)
        return out[..., :d]
    if auto and _autotune.enabled():
        bh = q.shape[0] * q.shape[1]
        cfg = _autotune.get_config(
            "flash.causal" if causal else "flash", (bh, sq, sk, d),
            str(jnp.dtype(q.dtype)),
            {"block_q": bq, "block_k": bk,
             "block_b": _pick_block_b(bh, bq, bk)})
        tq, tk = int(cfg.get("block_q", bq)), int(cfg.get("block_k", bk))
        if sq % tq == 0 and sk % tk == 0:   # never trust a cache into
            bq, bk = tq, tk                  # an untileable config
            tb = cfg.get("block_b")
            block_b = int(tb) if tb and bh % int(tb) == 0 else None
    return _flash(q, k, v, bool(causal), float(scale), int(bq),
                  int(bk), block_b and int(block_b), bool(interpret))


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, training=True, name=None):
    """Tensor-level API, paddle.incubate.nn.functional.fused-attention-like.

    query/key/value: [batch, num_heads, seq, head_dim] Tensors.
    """
    from ..framework.core import Tensor, apply_op

    if return_softmax:
        raise NotImplementedError("flash_attention does not materialize softmax")
    out = apply_op(_flash_entry, query, key, value, causal=bool(causal))
    if dropout and training:
        from ..nn import functional as F

        out = F.dropout(out, dropout, training=training)
    return out


def _flash_entry(q, k, v, causal):
    return flash_attention_arrays(q, k, v, causal=causal)


# -- autotune family (ISSUE 17) --------------------------------------------
# Candidates walk the power-of-two block ladder the hand policy picks
# from, so the hand-picked default is always in the trial set and the
# S=2048 whole-sequence degenerate block has to EARN its slot.

def _flash_candidates(shape, dtype):
    bh, sq, sk, d = shape
    out, seen = [], set()
    for cap in (2048, 1024, 512, 256, 128):
        bq = min(_auto_block(sq, cap), sq)
        bk = min(_auto_block(sk, cap), sk)
        if sq % bq or sk % bk or (bq, bk) in seen:
            continue
        seen.add((bq, bk))
        out.append({"block_q": bq, "block_k": bk,
                    "block_b": _pick_block_b(bh, bq, bk)})
    return out[:5]


def _flash_bench(causal):
    def bench(shape, dtype, config):
        import numpy as np

        bh, sq, sk, d = shape
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        q = jnp.asarray(rng.standard_normal((1, bh, sq, d)), dt)
        k = jnp.asarray(rng.standard_normal((1, bh, sk, d)), dt)
        v = jnp.asarray(rng.standard_normal((1, bh, sk, d)), dt)
        out, _ = _flash_forward(
            q, k, v, causal=causal, scale=1.0 / math.sqrt(d),
            block_q=int(config["block_q"]), block_k=int(config["block_k"]),
            block_b=int(config.get("block_b") or 0) or None,
            interpret=not _on_tpu())
        jax.block_until_ready(out)
    return bench


_autotune.register_family("flash", _flash_candidates, _flash_bench(False))
_autotune.register_family("flash.causal", _flash_candidates,
                          _flash_bench(True))
