"""int8 weight-quantized matmul — Pallas dot kernel with fused dequant.

The kernel behind ``quantization.quantized_linear`` (the reference's slim
int8 inference path over cuDNN int8 convs): int8 activations x int8
weights on the MXU (v5e runs int8 at 2x the bf16 rate) with int32
accumulation, and the per-channel dequant (``acc * xscale * wscale[n]``)
plus bias fused into the kernel epilogue — the dequantized fp tensor is
written once, never the int32 accumulator.

Entry points:
- :func:`int8_matmul_arrays` — already-quantized operands
  ``(xq int8 [.., K], wq int8 [K, N], wscale [N], xscale scalar)``.
- :func:`dynamic_int8_matmul` — fp activations, per-tensor abs-max
  quantized on the fly (weight-only-quantized serving decode).

Fallback contract matches flash_attention: off-TPU (or on untileable
shapes) the identical XLA math runs (``lax.dot_general`` int8 path);
``interpret=True`` forces the Pallas kernel for CPU parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..monitor.stats import INT8_MATMUL_CALLS
from . import autotune as _autotune
from .flash_attention import _on_tpu

__all__ = ["int8_matmul_arrays", "dynamic_int8_matmul"]


def _int8_matmul_ref(xq, wq, wscale, xscale, bias, out_dtype):
    acc = jax.lax.dot_general(
        xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (xscale * wscale)
    if bias is not None:
        out = out + bias
    return out.astype(out_dtype)


def _int8_kernel(xs_ref, xq_ref, wq_ref, ws_ref, b_ref, o_ref, acc_s, *,
                 n_k, out_dtype):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    acc_s[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        out = acc_s[...].astype(jnp.float32) * (xs_ref[0] * ws_ref[...])
        out = out + b_ref[...]
        o_ref[...] = out.astype(out_dtype)


def _pick(n, cands):
    for c in cands:
        if n % c == 0 and c <= n:
            return c
    return None


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "bm", "bn", "bk"))
def _int8_matmul_2d(xq, wq, wscale, xscale, bias, out_dtype,
                    interpret=False, bm=None, bn=None, bk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = xq.shape
    N = wq.shape[1]
    # int8 min tile is (32, 128): pad rows to 32 (decode batches are tiny)
    Mp = -(-M // 32) * 32
    if Mp != M:
        xq = jnp.pad(xq, ((0, Mp - M), (0, 0)))
    bm = bm or _pick(Mp, (256, 128, 64, 32))
    bn = bn or _pick(N, (512, 256, 128))
    bk = bk or _pick(K, (512, 256, 128))
    ws2 = wscale.reshape(1, N).astype(jnp.float32)
    b2 = (bias.reshape(1, N).astype(jnp.float32) if bias is not None
          else jnp.zeros((1, N), jnp.float32))
    xs = xscale.reshape(1).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_int8_kernel, n_k=K // bk, out_dtype=out_dtype),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        grid=(Mp // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="int8_matmul",
    )(xs, xq, wq, ws2, b2)
    return out[:M]


def int8_matmul_arrays(xq, wq, wscale, xscale, bias=None,
                       out_dtype=jnp.float32, interpret=None):
    """``dequant(xq @ wq)`` with per-channel dequant fused in-epilogue.

    xq int8 [..., K]; wq int8 [K, N]; wscale [N] (dequant multiplier,
    i.e. scale/qmax); xscale scalar. Falls back to the identical XLA
    int8 dot off-TPU or on untileable shapes."""
    xscale = jnp.asarray(xscale, jnp.float32)
    if interpret is None:
        if not _on_tpu():
            return _int8_matmul_ref(xq, wq, wscale, xscale, bias, out_dtype)
        interpret = False
    lead = xq.shape[:-1]
    K = xq.shape[-1]
    N = wq.shape[1]
    M = 1
    for d in lead:
        M *= int(d)
    if xscale.size != 1:
        # per-row activation scales: a design choice, not a fallback
        return _int8_matmul_ref(xq, wq, wscale, xscale, bias, out_dtype)
    if (_pick(N, (512, 256, 128)) is None
            or _pick(K, (512, 256, 128)) is None):
        _autotune.note_fallback(
            "int8_matmul", (M, K, N),
            "K=%d or N=%d has no 128-divisible block" % (K, N))
        return _int8_matmul_ref(xq, wq, wscale, xscale, bias, out_dtype)
    if not isinstance(xq, jax.core.Tracer):
        INT8_MATMUL_CALLS.add()
    blocks = {}
    if _autotune.enabled():
        Mp = -(-M // 32) * 32
        cfg = _autotune.get_config(
            "int8_matmul", (M, K, N), "int8",
            {"bm": _pick(Mp, (256, 128, 64, 32)),
             "bn": _pick(N, (512, 256, 128)),
             "bk": _pick(K, (512, 256, 128))})
        tm, tn, tk = (int(cfg.get(k, 0) or 0) for k in ("bm", "bn", "bk"))
        if (tm and Mp % tm == 0 and tn and N % tn == 0
                and tk and K % tk == 0):
            blocks = {"bm": tm, "bn": tn, "bk": tk}
    out = _int8_matmul_2d(xq.reshape(M, K), wq, wscale, xscale, bias,
                          out_dtype=jnp.dtype(out_dtype).name,
                          interpret=interpret, **blocks)
    return out.reshape(*lead, N)


def dynamic_int8_matmul(x, wq, wscale, bias=None, interpret=None):
    """Weight-only int8 matmul for fp activations: per-tensor abs-max
    dynamic activation quantization, then the fused dequant kernel.
    First consumer: the serving engine's int8 decode path
    (``InferenceEngine(int8_weights=True)``)."""
    xscale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                         1e-8) / 127.0
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / xscale),
                  -127, 127).astype(jnp.int8)
    return int8_matmul_arrays(xq, wq, wscale, xscale, bias=bias,
                              out_dtype=x.dtype, interpret=interpret)


# -- autotune family (ISSUE 17) ---------------------------------------------

def _int8_candidates(shape, dtype):
    M, K, N = shape
    Mp = -(-int(M) // 32) * 32
    bms = [c for c in (256, 128, 64, 32) if Mp % c == 0][:2]
    bns = [c for c in (512, 256, 128) if int(N) % c == 0][:2]
    bk = _pick(int(K), (512, 256, 128))
    if not bms or not bns or bk is None:
        return []
    out = []
    for bm in bms:
        for bn in bns:
            out.append({"bm": bm, "bn": bn, "bk": bk})
    return out[:5]


def _int8_bench(shape, dtype, config):
    import numpy as np

    M, K, N = (int(d) for d in shape)
    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.integers(-127, 128, (M, K), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-127, 128, (K, N), dtype=np.int8))
    ws = jnp.full((N,), 0.01, jnp.float32)
    xs = jnp.asarray(0.01, jnp.float32)
    out = _int8_matmul_2d(xq, wq, ws, xs, None, out_dtype="float32",
                          interpret=not _on_tpu(), **config)
    jax.block_until_ready(out)


_autotune.register_family("int8_matmul", _int8_candidates, _int8_bench)
