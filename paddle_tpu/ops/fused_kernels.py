"""Fused residual+layernorm and GeLU/SwiGLU-MLP Pallas kernels (fwd+bwd).

The TPU answer to the reference's operators/fused/fused_feedforward and
fused_bias_dropout_residual_layer_norm kernels: the transformer block's
non-attention half — ``y = x + act(LN(x) @ W1 + b1) @ W2 + b2`` — runs as
ONE Pallas kernel streaming the MLP hidden dim through VMEM in blocks,
with a custom-VJP backward kernel that recomputes z per block (flash-style
recompute; the [R, M] activation never round-trips HBM) and accumulates
dW1/dW2 in VMEM scratch across the row sweep.

Kernels:
- :func:`fused_ln_mlp` — pre-LN residual MLP (GeLU / ReLU / SwiGLU). LN
  optional (``ln_scale=None`` skips it), residual optional — this one
  shape covers the gpt/bert block MLP half and both fused_feedforward
  layouts.
- :func:`fused_add_layernorm` — LN(x + y), the post-LN residual pattern.

Both follow the flash-attention fallback contract: off-TPU the entry
points run the IDENTICAL composed jnp math (so ``FLAGS_fused_kernels``
flips nothing numerically on CPU), ``interpret=True`` forces the Pallas
kernels through the interpreter for CPU parity tests, and shapes the
kernel can't tile (H not a lane multiple, odd row counts) fall back to
the composed math automatically.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import autotune as _autotune
from .flash_attention import _on_tpu

__all__ = ["fused_ln_mlp", "fused_add_layernorm"]

_SQRT_2_PI = math.sqrt(2.0 / math.pi)


# --------------------------------------------------------------------------
# activations (closed-form derivatives: the backward kernel can't call AD)
# --------------------------------------------------------------------------

def _act(z, kind):
    if kind == "relu":
        return jnp.maximum(z, 0.0)
    # tanh-approx gelu (jax.nn.gelu default)
    u = _SQRT_2_PI * (z + 0.044715 * z * z * z)
    return 0.5 * z * (1.0 + jnp.tanh(u))


def _act_grad(z, kind):
    if kind == "relu":
        return (z > 0.0).astype(z.dtype)
    u = _SQRT_2_PI * (z + 0.044715 * z * z * z)
    t = jnp.tanh(u)
    du = _SQRT_2_PI * (1.0 + 3.0 * 0.044715 * z * z)
    return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du


def _silu(z):
    return z * jax.nn.sigmoid(z)


def _silu_grad(z):
    s = jax.nn.sigmoid(z)
    return s * (1.0 + z * (1.0 - s))


# --------------------------------------------------------------------------
# composed references — EXACTLY the op sequence the unfused model code
# runs (models/gpt.py _block_kv, ops/fused.py _fused_ffn), so the
# off-TPU fallback is bit-identical to the flag-off path.
# --------------------------------------------------------------------------

def _layer_norm_ref(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _ln_mlp_reference(x, ln_scale, ln_bias, w1, b1, w2, b2, wg, bg,
                      act, residual, has_ln, eps):
    h = _layer_norm_ref(x, ln_scale, ln_bias, eps) if has_ln else x
    if act == "swiglu":
        a = _silu(h @ wg + bg) * (h @ w1 + b1)
    elif act == "relu":
        a = jax.nn.relu(h @ w1 + b1)
    else:
        a = jax.nn.gelu(h @ w1 + b1)
    out = a @ w2 + b2
    return x + out if residual else out


# --------------------------------------------------------------------------
# forward kernel: grid (row blocks, mlp blocks), mlp innermost; the
# LN'd input and the output accumulator live in VMEM scratch across the
# mlp sweep, so x is normalized once and y written once.
# --------------------------------------------------------------------------

def _fmlp_fwd_kernel(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, w2_ref,
                     b2_ref, wg_ref, bg_ref, y_ref, mu_ref, rs_ref,
                     lnx_s, acc_s, *, act, residual, has_ln, eps, n_j):
    from jax.experimental import pallas as pl

    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        x32 = x_ref[...].astype(jnp.float32)
        if has_ln:
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
            rstd = jax.lax.rsqrt(var + eps)
            lnx = (x32 - mu) * rstd * lns_ref[...] + lnb_ref[...]
        else:
            mu = jnp.zeros((x32.shape[0], 1), jnp.float32)
            rstd = jnp.ones((x32.shape[0], 1), jnp.float32)
            lnx = x32
        mu_ref[...] = mu
        rs_ref[...] = rstd
        lnx_s[...] = lnx.astype(lnx_s.dtype)
        acc_s[...] = jnp.zeros_like(acc_s)

    lnx = lnx_s[...]
    z = jax.lax.dot(lnx, w1_ref[...],
                    preferred_element_type=jnp.float32) + b1_ref[...]
    if act == "swiglu":
        zg = jax.lax.dot(lnx, wg_ref[...],
                         preferred_element_type=jnp.float32) + bg_ref[...]
        a = _silu(zg) * z
    else:
        a = _act(z, act)
    acc_s[...] += jax.lax.dot(a.astype(lnx.dtype), w2_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(ji == n_j - 1)
    def _finalize():
        out = acc_s[...] + b2_ref[...]
        if residual:
            out = out + x_ref[...].astype(jnp.float32)
        y_ref[...] = out.astype(y_ref.dtype)


# --------------------------------------------------------------------------
# backward kernel: grid (mlp blocks, row blocks), rows innermost; dW1/dW2
# accumulate in scratch over the row sweep; per-mlp-block d(lnx) partials
# go to HBM and are summed by XLA (the flash dQ-partials pattern). The
# LN backward + residual add + db2 are cheap row-local jnp afterwards.
# --------------------------------------------------------------------------

def _fmlp_bwd_kernel(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, w2_ref,
                     wg_ref, bg_ref, mu_ref, rs_ref, dy_ref,
                     dw1_ref, db1_ref, dwg_ref, dbg_ref, dlnxp_ref,
                     dw1_s, db1_s, dwg_s, dbg_s, *,
                     act, has_ln, eps, n_r):
    from jax.experimental import pallas as pl

    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        dw1_s[...] = jnp.zeros_like(dw1_s)
        db1_s[...] = jnp.zeros_like(db1_s)
        dwg_s[...] = jnp.zeros_like(dwg_s)
        dbg_s[...] = jnp.zeros_like(dbg_s)

    x32 = x_ref[...].astype(jnp.float32)
    if has_ln:
        lnx = ((x32 - mu_ref[...]) * rs_ref[...] * lns_ref[...]
               + lnb_ref[...])
    else:
        lnx = x32
    lnx = lnx.astype(x_ref.dtype)
    dy = dy_ref[...].astype(jnp.float32)

    dim = lambda lc, rc: (((lc,), (rc,)), ((), ()))
    z = jax.lax.dot(lnx, w1_ref[...],
                    preferred_element_type=jnp.float32) + b1_ref[...]
    # da = dy @ w2^T, contracting the H dims (no in-kernel transpose)
    da = jax.lax.dot_general(dy.astype(x_ref.dtype), w2_ref[...],
                             dim(1, 1), preferred_element_type=jnp.float32)
    if act == "swiglu":
        zg = jax.lax.dot(lnx, wg_ref[...],
                         preferred_element_type=jnp.float32) + bg_ref[...]
        sg = _silu(zg)
        dz = da * sg
        dzg = da * z * _silu_grad(zg)
        dwg_s[...] += jax.lax.dot_general(      # lnx^T @ dzg
            lnx, dzg.astype(x_ref.dtype), dim(0, 0),
            preferred_element_type=jnp.float32)
        dbg_s[...] += jnp.sum(dzg, axis=0, keepdims=True)
    else:
        dz = da * _act_grad(z, act)
        dzg = None
    db1_s[...] += jnp.sum(dz, axis=0, keepdims=True)
    dw1_s[...] += jax.lax.dot_general(          # lnx^T @ dz
        lnx, dz.astype(x_ref.dtype), dim(0, 0),
        preferred_element_type=jnp.float32)
    dlnx = jax.lax.dot_general(                 # dz @ w1^T
        dz.astype(x_ref.dtype), w1_ref[...], dim(1, 1),
        preferred_element_type=jnp.float32)
    if act == "swiglu":
        dlnx = dlnx + jax.lax.dot_general(
            dzg.astype(x_ref.dtype), wg_ref[...], dim(1, 1),
            preferred_element_type=jnp.float32)
    dlnxp_ref[0] = dlnx

    @pl.when(ri == n_r - 1)
    def _finalize():
        dw1_ref[...] = dw1_s[...]
        db1_ref[...] = db1_s[...]
        dwg_ref[...] = dwg_s[...]
        dbg_ref[...] = dbg_s[...]


def _fmlp_bwd_dw2_kernel(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, wg_ref,
                         bg_ref, mu_ref, rs_ref, dy_ref, dw2_ref, dw2_s, *,
                         act, has_ln, eps, n_r):
    """dW2 = a^T dy, recomputing a per (mlp block, row block); separate
    kernel so the main backward's scratch budget stays within VMEM at
    large H·bj."""
    from jax.experimental import pallas as pl

    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        dw2_s[...] = jnp.zeros_like(dw2_s)

    x32 = x_ref[...].astype(jnp.float32)
    if has_ln:
        lnx = ((x32 - mu_ref[...]) * rs_ref[...] * lns_ref[...]
               + lnb_ref[...])
    else:
        lnx = x32
    lnx = lnx.astype(x_ref.dtype)
    z = jax.lax.dot(lnx, w1_ref[...],
                    preferred_element_type=jnp.float32) + b1_ref[...]
    if act == "swiglu":
        zg = jax.lax.dot(lnx, wg_ref[...],
                         preferred_element_type=jnp.float32) + bg_ref[...]
        a = _silu(zg) * z
    else:
        a = _act(z, act)
    dw2_s[...] += jax.lax.dot_general(          # a^T @ dy
        a.astype(x_ref.dtype), dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ri == n_r - 1)
    def _finalize():
        dw2_ref[...] = dw2_s[...]


# --------------------------------------------------------------------------
# pallas_call plumbing
# --------------------------------------------------------------------------

def _pick(n, cands):
    for c in cands:
        if n % c == 0 and c <= n:
            return c
    return None


def _tileable(R, H, M, dtype):
    # bf16/int8 blocks need >=16 sublanes (min tile); f32 allows 8
    cands = ((256, 128, 64, 32, 16) if jnp.dtype(dtype).itemsize < 4
             else (256, 128, 64, 32, 16, 8))
    br = _pick(R, cands)
    bj = _pick(M, (512, 256, 128))
    if br is None or bj is None or H % 128 != 0:
        return None
    return br, bj


def _fmlp_forward(x2, lns, lnb, w1, b1, w2, b2, wg, bg, act, residual,
                  has_ln, eps, br, bj, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H = x2.shape
    M = w1.shape[1]
    n_r, n_j = R // br, M // bj
    row = lambda: pl.BlockSpec((br, H), lambda i, j: (i, 0))
    kernel = functools.partial(_fmlp_fwd_kernel, act=act,
                               residual=residual, has_ln=has_ln,
                               eps=eps, n_j=n_j)
    y, mu, rstd = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((R, H), x2.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        grid=(n_r, n_j),
        in_specs=[
            row(),
            pl.BlockSpec((1, H), lambda i, j: (0, 0)),
            pl.BlockSpec((1, H), lambda i, j: (0, 0)),
            pl.BlockSpec((H, bj), lambda i, j: (0, j)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
            pl.BlockSpec((bj, H), lambda i, j: (j, 0)),
            pl.BlockSpec((1, H), lambda i, j: (0, 0)),
            pl.BlockSpec((H, bj), lambda i, j: (0, j)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
        ],
        out_specs=(row(),
                   pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i, j: (i, 0))),
        scratch_shapes=[pltpu.VMEM((br, H), x2.dtype),
                        pltpu.VMEM((br, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="fused_mlp_forward",
    )(x2, lns, lnb, w1, b1, w2, b2, wg, bg)
    return y, mu, rstd


def _fmlp_backward(x2, lns, lnb, w1, b1, w2, wg, bg, mu, rstd, dy2,
                   act, residual, has_ln, eps, br, bj, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H = x2.shape
    M = w1.shape[1]
    n_r, n_j = R // br, M // bj
    dy2 = dy2.astype(x2.dtype)

    common = [
        pl.BlockSpec((br, H), lambda j, i: (i, 0)),          # x
        pl.BlockSpec((1, H), lambda j, i: (0, 0)),           # ln scale
        pl.BlockSpec((1, H), lambda j, i: (0, 0)),           # ln bias
        pl.BlockSpec((H, bj), lambda j, i: (0, j)),          # w1
        pl.BlockSpec((1, bj), lambda j, i: (0, j)),          # b1
    ]
    tail = [
        pl.BlockSpec((H, bj), lambda j, i: (0, j)),          # wg
        pl.BlockSpec((1, bj), lambda j, i: (0, j)),          # bg
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),          # mu
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),          # rstd
        pl.BlockSpec((br, H), lambda j, i: (i, 0)),          # dy
    ]
    kernel = functools.partial(_fmlp_bwd_kernel, act=act, has_ln=has_ln,
                               eps=eps, n_r=n_r)
    dw1, db1, dwg, dbg, dlnxp = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((H, M), jnp.float32),
                   jax.ShapeDtypeStruct((1, M), jnp.float32),
                   jax.ShapeDtypeStruct((H, M), jnp.float32),
                   jax.ShapeDtypeStruct((1, M), jnp.float32),
                   jax.ShapeDtypeStruct((n_j, R, H), jnp.float32)),
        grid=(n_j, n_r),
        in_specs=common
        + [pl.BlockSpec((bj, H), lambda j, i: (j, 0))]       # w2
        + tail,
        out_specs=(pl.BlockSpec((H, bj), lambda j, i: (0, j)),
                   pl.BlockSpec((1, bj), lambda j, i: (0, j)),
                   pl.BlockSpec((H, bj), lambda j, i: (0, j)),
                   pl.BlockSpec((1, bj), lambda j, i: (0, j)),
                   pl.BlockSpec((1, br, H), lambda j, i: (j, i, 0))),
        scratch_shapes=[pltpu.VMEM((H, bj), jnp.float32),
                        pltpu.VMEM((1, bj), jnp.float32),
                        pltpu.VMEM((H, bj), jnp.float32),
                        pltpu.VMEM((1, bj), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="fused_mlp_backward",
    )(x2, lns, lnb, w1, b1, w2, wg, bg, mu, rstd, dy2)

    dw2 = pl.pallas_call(
        functools.partial(_fmlp_bwd_dw2_kernel, act=act, has_ln=has_ln,
                          eps=eps, n_r=n_r),
        out_shape=jax.ShapeDtypeStruct((M, H), jnp.float32),
        grid=(n_j, n_r),
        in_specs=common + tail,
        out_specs=pl.BlockSpec((bj, H), lambda j, i: (j, 0)),
        scratch_shapes=[pltpu.VMEM((bj, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="fused_mlp_backward_dw2",
    )(x2, lns, lnb, w1, b1, wg, bg, mu, rstd, dy2)

    dy32 = dy2.astype(jnp.float32)
    db2 = jnp.sum(dy32, axis=0, keepdims=True)               # [1, H]
    dlnx = jnp.sum(dlnxp, axis=0)                            # [R, H] f32
    x32 = x2.astype(jnp.float32)
    if has_ln:
        xhat = (x32 - mu) * rstd
        dscale = jnp.sum(dlnx * xhat, axis=0)
        dbias = jnp.sum(dlnx, axis=0)
        dxhat = dlnx * lns.astype(jnp.float32)
        mean1 = jnp.mean(dxhat, axis=-1, keepdims=True)
        mean2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
        dx = rstd * (dxhat - mean1 - xhat * mean2)
    else:
        dscale = jnp.zeros((H,), jnp.float32)
        dbias = jnp.zeros((H,), jnp.float32)
        dx = dlnx
    if residual:
        dx = dx + dy32
    return (dx.astype(x2.dtype), dscale, dbias, dw1, db1, dw2, db2,
            dwg, dbg)


# -- differentiable entry ---------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13, 14,
                                                    15))
def _fmlp(x2, lns, lnb, w1, b1, w2, b2, wg, bg, act, residual, has_ln,
          eps, br, bj, interpret):
    y, _, _ = _fmlp_forward(x2, lns, lnb, w1, b1, w2, b2, wg, bg, act,
                            residual, has_ln, eps, br, bj, interpret)
    return y


def _fmlp_fwd_rule(x2, lns, lnb, w1, b1, w2, b2, wg, bg, act, residual,
                   has_ln, eps, br, bj, interpret):
    y, mu, rstd = _fmlp_forward(x2, lns, lnb, w1, b1, w2, b2, wg, bg, act,
                                residual, has_ln, eps, br, bj, interpret)
    return y, (x2, lns, lnb, w1, b1, w2, wg, bg, mu, rstd)


def _fmlp_bwd_rule(act, residual, has_ln, eps, br, bj, interpret, res, g):
    x2, lns, lnb, w1, b1, w2, wg, bg, mu, rstd = res
    dx, dscale, dbias, dw1, db1, dw2, db2, dwg, dbg = _fmlp_backward(
        x2, lns, lnb, w1, b1, w2, wg, bg, mu, rstd, g, act, residual,
        has_ln, eps, br, bj, interpret)
    return (dx, dscale.reshape(lns.shape).astype(lns.dtype),
            dbias.reshape(lnb.shape).astype(lnb.dtype),
            dw1.astype(w1.dtype), db1.reshape(b1.shape).astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(w2.dtype),
            dwg.astype(wg.dtype), dbg.reshape(bg.shape).astype(bg.dtype))


_fmlp.defvjp(_fmlp_fwd_rule, _fmlp_bwd_rule)


def fused_ln_mlp(x, w1, b1, w2, b2, *, ln_scale=None, ln_bias=None,
                 residual=True, act="gelu", w_gate=None, b_gate=None,
                 eps=1e-5, interpret=None):
    """``(x if residual) + act(LN?(x) @ w1 + b1) @ w2 + b2`` — fused.

    x: [..., H]; w1 [H, M]; w2 [M, H]. ``act``: "gelu" | "relu" |
    "swiglu" (swiglu takes the gate projection via w_gate/b_gate:
    ``silu(h@w_gate+b_gate) * (h@w1+b1)``). ``ln_scale=None`` skips the
    input LayerNorm. Off-TPU (or on untileable shapes) this is the
    identical composed jnp math; ``interpret=True`` forces the Pallas
    kernels (parity tests)."""
    has_ln = ln_scale is not None
    H = x.shape[-1]
    lns = (jnp.asarray(ln_scale, jnp.float32).reshape(1, H) if has_ln
           else jnp.ones((1, H), jnp.float32))
    lnb = (jnp.asarray(ln_bias, jnp.float32).reshape(1, H) if has_ln
           else jnp.zeros((1, H), jnp.float32))
    swiglu = act == "swiglu"
    wg = w_gate if swiglu else jnp.zeros_like(w1)
    bg = (b_gate if (swiglu and b_gate is not None)
          else jnp.zeros((w1.shape[1],), w1.dtype))

    ref = lambda: _ln_mlp_reference(
        x, lns.reshape(H) if has_ln else None,
        lnb.reshape(H) if has_ln else None,
        w1, b1, w2, b2, wg, bg, act, residual, has_ln, eps)
    if interpret is None:
        if not _on_tpu():
            return ref()
        interpret = False
    lead = x.shape[:-1]
    R = 1
    for d in lead:
        R *= int(d)
    M = w1.shape[1]
    tiles = _tileable(R, H, M, x.dtype)
    if tiles is None:
        _autotune.note_fallback(
            "fused_ln_mlp", (R, H, M),
            "rows=%d / mlp=%d not tileable or hidden=%d %% 128 != 0"
            % (R, M, H))
        return ref()
    br, bj = tiles
    if _autotune.enabled():
        cfg = _autotune.get_config(
            "fused_ln_mlp", (R, H, M), str(jnp.dtype(x.dtype)),
            {"br": br, "bj": bj})
        tr, tj = int(cfg.get("br", br)), int(cfg.get("bj", bj))
        if R % tr == 0 and M % tj == 0:
            br, bj = tr, tj
    y = _fmlp(x.reshape(R, H), lns, lnb, w1, b1.reshape(1, -1), w2,
              b2.reshape(1, -1), wg, bg.reshape(1, -1), act,
              bool(residual), has_ln, float(eps), br, bj, bool(interpret))
    return y.reshape(*lead, H)


# --------------------------------------------------------------------------
# fused residual + layernorm: LN(x + y)
# --------------------------------------------------------------------------

def _addln_fwd_kernel(x_ref, y_ref, s_ref, b_ref, o_ref, mu_ref, rs_ref,
                      *, eps):
    t = x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    mu = jnp.mean(t, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(t - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    mu_ref[...] = mu
    rs_ref[...] = rstd
    o_ref[...] = ((t - mu) * rstd * s_ref[...] + b_ref[...]).astype(
        o_ref.dtype)


def _addln_bwd_kernel(x_ref, y_ref, s_ref, mu_ref, rs_ref, do_ref,
                      dx_ref, ds_ref, db_ref, ds_s, db_s, *, eps, n_r):
    from jax.experimental import pallas as pl

    ri = pl.program_id(0)

    @pl.when(ri == 0)
    def _init():
        ds_s[...] = jnp.zeros_like(ds_s)
        db_s[...] = jnp.zeros_like(db_s)

    t = x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    rstd = rs_ref[...]
    xhat = (t - mu) * rstd
    do = do_ref[...].astype(jnp.float32)
    ds_s[...] += jnp.sum(do * xhat, axis=0, keepdims=True)
    db_s[...] += jnp.sum(do, axis=0, keepdims=True)
    dxhat = do * s_ref[...]
    mean1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    mean2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - mean1 - xhat * mean2)).astype(
        dx_ref.dtype)

    @pl.when(ri == n_r - 1)
    def _finalize():
        ds_ref[...] = ds_s[...]
        db_ref[...] = db_s[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _addln(x2, y2, s, b, eps, br, interpret):
    out, _, _ = _addln_forward(x2, y2, s, b, eps, br, interpret)
    return out


def _addln_forward(x2, y2, s, b, eps, br, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H = x2.shape
    row = lambda: pl.BlockSpec((br, H), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_addln_fwd_kernel, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((R, H), x2.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        grid=(R // br,),
        in_specs=[row(), row(),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=(row(),
                   pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="add_ln_forward",
    )(x2, y2, s, b)


def _addln_fwd_rule(x2, y2, s, b, eps, br, interpret):
    out, mu, rstd = _addln_forward(x2, y2, s, b, eps, br, interpret)
    return out, (x2, y2, s, mu, rstd)


def _addln_bwd_rule(eps, br, interpret, res, g):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x2, y2, s, mu, rstd = res
    R, H = x2.shape
    row = lambda: pl.BlockSpec((br, H), lambda i: (i, 0))
    dx, ds, db = pl.pallas_call(
        functools.partial(_addln_bwd_kernel, eps=eps, n_r=R // br),
        out_shape=(jax.ShapeDtypeStruct((R, H), x2.dtype),
                   jax.ShapeDtypeStruct((1, H), jnp.float32),
                   jax.ShapeDtypeStruct((1, H), jnp.float32)),
        grid=(R // br,),
        in_specs=[row(), row(),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  row()],
        out_specs=(row(),
                   pl.BlockSpec((1, H), lambda i: (0, 0)),
                   pl.BlockSpec((1, H), lambda i: (0, 0))),
        scratch_shapes=[pltpu.VMEM((1, H), jnp.float32),
                        pltpu.VMEM((1, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="add_ln_backward",
    )(x2, y2, s, mu, rstd, g.astype(x2.dtype))
    return dx, dx, ds.reshape(s.shape).astype(s.dtype), \
        db.reshape(s.shape).astype(s.dtype)


_addln.defvjp(_addln_fwd_rule, _addln_bwd_rule)


def fused_add_layernorm(x, y, scale, bias, eps=1e-5, interpret=None):
    """LN(x + y) — the post-LN residual pattern, fused.

    Same fallback contract as :func:`fused_ln_mlp`: composed jnp off-TPU
    or on untileable shapes; ``interpret=True`` for parity tests."""
    H = x.shape[-1]
    # composed reference = the exact unfused pattern (residual add in the
    # compute dtype, then the fp32-stats LayerNorm)
    ref = lambda: _layer_norm_ref(x + y, scale, bias, eps)
    if interpret is None:
        if not _on_tpu():
            return ref()
        interpret = False
    lead = x.shape[:-1]
    R = 1
    for d in lead:
        R *= int(d)
    br = _pick(R, (256, 128, 64, 32, 16, 8))
    if br is None or H % 128 != 0:
        _autotune.note_fallback(
            "fused_add_ln", (R, H),
            "rows=%d has no legal row block or hidden=%d %% 128 != 0"
            % (R, H))
        return ref()
    if _autotune.enabled():
        cfg = _autotune.get_config("fused_add_ln", (R, H),
                                   str(jnp.dtype(x.dtype)), {"br": br})
        tr = int(cfg.get("br", br))
        if R % tr == 0:
            br = tr
    out = _addln(x.reshape(R, H), y.reshape(R, H),
                 jnp.asarray(scale, jnp.float32).reshape(1, H),
                 jnp.asarray(bias, jnp.float32).reshape(1, H),
                 float(eps), br, bool(interpret))
    return out.reshape(*lead, H)


# -- autotune families (ISSUE 17) ------------------------------------------

def _fmlp_candidates(shape, dtype):
    R, H, M = shape
    if _tileable(R, H, M, jnp.dtype(dtype)) is None:
        return []
    row_cands = ((256, 128, 64, 32, 16)
                 if jnp.dtype(dtype).itemsize < 4
                 else (256, 128, 64, 32, 16, 8))
    brs = [c for c in row_cands if R % c == 0][:2]
    bjs = [c for c in (512, 256, 128) if M % c == 0][:2]
    return [{"br": br, "bj": bj} for br in brs for bj in bjs][:5]


def _fmlp_bench(shape, dtype, config):
    import numpy as np

    R, H, M = shape
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((R, H)), dt)
    ones = jnp.ones((1, H), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((H, M)) * 0.05, dt)
    w2 = jnp.asarray(rng.standard_normal((M, H)) * 0.05, dt)
    zb1 = jnp.zeros((1, M), dt)
    zb2 = jnp.zeros((1, H), dt)
    y, _, _ = _fmlp_forward(
        x, ones, jnp.zeros((1, H), jnp.float32), w1, zb1, w2, zb2,
        jnp.zeros_like(w1), zb1, "gelu", True, True, 1e-5,
        int(config["br"]), int(config["bj"]), not _on_tpu())
    jax.block_until_ready(y)


def _addln_candidates(shape, dtype):
    R, H = shape
    if H % 128 != 0:
        return []
    return [{"br": c} for c in (256, 128, 64, 32, 16, 8)
            if R % c == 0][:4]


def _addln_bench(shape, dtype, config):
    import numpy as np

    R, H = shape
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((R, H)), dt)
    y = jnp.asarray(rng.standard_normal((R, H)), dt)
    out, _, _ = _addln_forward(
        x, y, jnp.ones((1, H), jnp.float32), jnp.zeros((1, H), jnp.float32),
        1e-5, int(config["br"]), not _on_tpu())
    jax.block_until_ready(out)


_autotune.register_family("fused_ln_mlp", _fmlp_candidates, _fmlp_bench)
_autotune.register_family("fused_add_ln", _addln_candidates, _addln_bench)
