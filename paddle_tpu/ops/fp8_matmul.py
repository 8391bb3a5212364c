"""fp8 (e4m3) matmul — Pallas dot kernel with fused dequant epilogue.

The fp8 leg of the quantized-matmul family (ISSUE 17): float8_e4m3fn
storage (max 448, 8x less HBM weight traffic than f32, 2x less than
bf16) with per-TENSOR scales, f32 accumulation, and the dequant
(``acc * sx * sw``) plus bias fused into the kernel epilogue — the same
shape as ops/int8_matmul.py, with the per-channel int8 rescale replaced
by the two scalar scales fp8 training uses.

The operands are upcast e4m3 -> bf16 inside the kernel before the dot:
e4m3 values are exactly representable in bf16, so the product is exact
and the MXU runs at its bf16 rate on hardware without a native fp8 dot.
The composed jnp fallback runs the SAME op sequence (bf16 dot, f32
accumulate, dequant, cast), so on/off-TPU numerics are identical.

Scale management (delayed amax-history scaling, checkpointable state)
lives in ``amp/fp8.py``; this module is pure kernel.

Fallback contract matches flash_attention: off-TPU (or on untileable
shapes) the identical XLA math runs; ``interpret=True`` forces the
Pallas kernel for CPU parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..monitor.stats import FP8_MATMUL_CALLS
from . import autotune as _autotune
from .flash_attention import _on_tpu

__all__ = ["fp8_matmul_arrays", "E4M3_MAX"]

E4M3_MAX = 448.0


def _fp8_matmul_ref(xq, wq, sx, sw, bias, out_dtype):
    """jnp reference — the SAME op sequence the kernel runs."""
    acc = jax.lax.dot_general(
        xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
        (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out = acc * (sx * sw)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype)


def _fp8_kernel(sc_ref, xq_ref, wq_ref, b_ref, o_ref, acc_s, *,
                n_k, out_dtype):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    acc_s[...] += jax.lax.dot_general(
        xq_ref[...].astype(jnp.bfloat16), wq_ref[...].astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        out = acc_s[...] * (sc_ref[0] * sc_ref[1]) + b_ref[...]
        o_ref[...] = out.astype(out_dtype)


def _pick(n, cands):
    for c in cands:
        if n % c == 0 and c <= n:
            return c
    return None


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "bm", "bn", "bk"))
def _fp8_matmul_2d(xq, wq, sx, sw, bias, out_dtype, interpret=False,
                   bm=None, bn=None, bk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = xq.shape
    N = wq.shape[1]
    # fp8 min tile is (32, 128): pad rows to 32 (decode batches are tiny)
    Mp = -(-M // 32) * 32
    if Mp != M:
        xq = jnp.pad(xq, ((0, Mp - M), (0, 0)))
    bm = bm or _pick(Mp, (256, 128, 64, 32))
    bn = bn or _pick(N, (512, 256, 128))
    bk = bk or _pick(K, (512, 256, 128))
    b2 = (bias.reshape(1, N).astype(jnp.float32) if bias is not None
          else jnp.zeros((1, N), jnp.float32))
    sc = jnp.stack([jnp.asarray(sx, jnp.float32).reshape(()),
                    jnp.asarray(sw, jnp.float32).reshape(())])
    out = pl.pallas_call(
        functools.partial(_fp8_kernel, n_k=K // bk, out_dtype=out_dtype),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        grid=(Mp // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="fp8_matmul",
    )(sc, xq, wq, b2)
    return out[:M]


def fp8_matmul_arrays(xq, wq, sx, sw, bias=None, out_dtype=jnp.float32,
                      interpret=None):
    """``(xq @ wq) * sx * sw (+ bias)`` with the dequant fused in-epilogue.

    xq e4m3 [..., K]; wq e4m3 [K, N]; sx/sw f32 per-tensor scales (the
    values each operand was DIVIDED by at quantization; the epilogue
    multiplies them back). Falls back to the identical composed jnp math
    off-TPU or on untileable shapes."""
    sx = jnp.asarray(sx, jnp.float32)
    sw = jnp.asarray(sw, jnp.float32)
    if interpret is None:
        if not _on_tpu():
            return _fp8_matmul_ref(xq, wq, sx, sw, bias, out_dtype)
        interpret = False
    lead = xq.shape[:-1]
    K = xq.shape[-1]
    N = wq.shape[1]
    M = 1
    for d in lead:
        M *= int(d)
    if (_pick(N, (512, 256, 128)) is None
            or _pick(K, (512, 256, 128)) is None):
        _autotune.note_fallback(
            "fp8_matmul", (M, K, N),
            "K=%d or N=%d has no 128-divisible block" % (K, N))
        return _fp8_matmul_ref(xq, wq, sx, sw, bias, out_dtype)
    if not isinstance(xq, jax.core.Tracer):
        FP8_MATMUL_CALLS.add()
    blocks = {}
    if _autotune.enabled():
        Mp = -(-M // 32) * 32
        cfg = _autotune.get_config(
            "fp8_matmul", (M, K, N), "float8_e4m3fn",
            {"bm": _pick(Mp, (256, 128, 64, 32)),
             "bn": _pick(N, (512, 256, 128)),
             "bk": _pick(K, (512, 256, 128))})
        tm, tn, tk = (int(cfg.get(k, 0) or 0) for k in ("bm", "bn", "bk"))
        if (tm and Mp % tm == 0 and tn and N % tn == 0
                and tk and K % tk == 0):
            blocks = {"bm": tm, "bn": tn, "bk": tk}
    out = _fp8_matmul_2d(xq.reshape(M, K), wq, sx, sw, bias,
                         out_dtype=jnp.dtype(out_dtype).name,
                         interpret=interpret, **blocks)
    return out.reshape(*lead, N)


# -- autotune family (ISSUE 17) ---------------------------------------------

def _fp8_candidates(shape, dtype):
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


def _fp8_bench(shape, dtype, config):
    import numpy as np

    M, K, N = (int(d) for d in shape)
    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32)
                     ).astype(jnp.float8_e4m3fn)
    wq = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32)
                     ).astype(jnp.float8_e4m3fn)
    out = _fp8_matmul_2d(xq, wq, jnp.float32(0.1), jnp.float32(0.1), None,
                         out_dtype="float32", interpret=not _on_tpu(),
                         **config)
    jax.block_until_ready(out)


_autotune.register_family("fp8_matmul", _fp8_candidates, _fp8_bench)
