"""Fused AdamW/LAMB update — one pass over flat param/moment buffers.

The analog of the reference's fused optimizer kernels
(operators/optimizers/adam_op.cu run once per parameter, and
operators/fused/fused_adam_op multi-tensor form): instead of an unfused
per-leaf ``tree_map`` — one XLA kernel launch per parameter, each reading
p/g/m/v and writing p/m/v with poor occupancy on small leaves — the
param/grad/moment pytrees are flattened into a few contiguous
dtype-homogeneous buffers ("buckets") and updated in ONE Pallas pass per
bucket (one HBM round-trip, full-width VPU blocks).

Two consumers, two shapes of the same math:

- **in-jit** (:func:`fused_adamw_update` / :func:`fused_lamb_update`):
  drop-in replacements for ``pure_adamw_update`` / ``pure_lamb_update``
  (parallel/train_step.py) with identical signatures AND identical state
  layout (m/v stay per-leaf trees, so checkpoints and ZeRO specs are
  unchanged); leaves are bucketed/concatenated inside the jit.
- **eager** (:func:`fused_eager_step`): replaces ``Optimizer.step``'s
  per-parameter jit-dispatch loop (N device round-trips per step) with
  ONE jitted dispatch over device-resident moments — the big win for
  eager training, where dispatch dominates.

Backend split (measured): on TPU each bucket runs the flat Pallas pass;
off-TPU the same formula stays per-leaf INSIDE the single program —
XLA CPU materializes every concat/split as a real copy (~8ms per
100-leaf round-trip vs ~2ms for the per-leaf math), so flattening there
would eat the dispatch win. Numerics are identical either way (the flat
reference is the per-leaf formula applied elementwise); the Pallas
kernels themselves are covered by interpret-mode parity tests
(tests/test_fused_kernels.py). ``FLAGS_fused_optimizer`` gates all
wiring; unset, every caller keeps the historical unfused path untouched.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from ..monitor import benchmark as _bench
from ..monitor.stats import FUSED_OPTIMIZER_STEPS
from ..monitor.trace import span as _trace_span
from . import autotune as _autotune
from .flash_attention import _on_tpu

__all__ = ["adamw_flat", "lamb_moments_flat", "fused_adamw_update",
           "fused_lamb_update", "fused_update_from_slots",
           "fused_eager_step", "flatten_bucket", "unflatten_bucket"]

_LANE = 1024          # 8 f32 sublanes x 128 lanes
_SUB = 16             # row padding multiple (bf16 min tile sublanes)


# --------------------------------------------------------------------------
# flat buffer helpers
# --------------------------------------------------------------------------

def flatten_bucket(leaves):
    """Concat raveled leaves into one 1-D buffer (shared dtype)."""
    if len(leaves) == 1:
        return jnp.ravel(leaves[0])
    return jnp.concatenate([jnp.ravel(x) for x in leaves])


def unflatten_bucket(flat, shapes, dtype=None):
    """Split a flat buffer back into leaves of the given shapes."""
    out, off = [], 0
    for s in shapes:
        n = 1
        for d in s:
            n *= int(d)
        leaf = jax.lax.dynamic_slice_in_dim(flat, off, n).reshape(s)
        out.append(leaf if dtype is None else leaf.astype(dtype))
        off += n
    return out


def _pad_2d(flat):
    """1-D buffer → (R, 1024) with R a multiple of 16 (tile-aligned)."""
    n = flat.shape[0]
    rows = -(-n // _LANE)
    rows = -(-rows // _SUB) * _SUB
    pad = rows * _LANE - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANE), n


def _block_rows(rows: int) -> int:
    for bb in (512, 256, 128, 64, 32, 16):
        if rows % bb == 0:
            return bb
    return rows


# --------------------------------------------------------------------------
# AdamW flat update (Pallas kernel + identical jnp fallback)
# --------------------------------------------------------------------------
#
# Math (f32 regardless of storage dtype):
#   g' = g + l2*p                                  (classic-Adam L2)
#   m' = b1*m + (1-b1)*g' ;  v' = b2*v + (1-b2)*g'^2
#   step = (m'/bc1) / (sqrt(v'/bc2) + eps)         [pure form], or
#   step = sqrt(bc2)/bc1 * m' / (sqrt(v') + eps)   [eager form — matches
#                                                   Adam._pure_update's
#                                                   lr_t algebra exactly]
#   p' = p*(1 - lr*wd) - lr*step                   (decoupled decay first)
#
# Scalars (lr, bc1, bc2) ride in SMEM so schedules never recompile.

def _adamw_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                  np_ref, nm_ref, nv_ref, *, b1, b2, eps, wd, l2,
                  eager_form):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    if l2:
        g = g + l2 * p
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * (g * g)
    if eager_form:
        step = (jnp.sqrt(bc2) / bc1) * m / (jnp.sqrt(v) + eps)
    else:
        step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    p = p * (1.0 - lr * wd) - lr * step
    np_ref[...] = p.astype(np_ref.dtype)
    nm_ref[...] = m.astype(nm_ref.dtype)
    nv_ref[...] = v.astype(nv_ref.dtype)


def _adamw_flat_ref(p, g, m, v, lr, bc1, bc2, *, b1, b2, eps, wd, l2,
                    eager_form):
    """jnp reference — the SAME op sequence the kernel runs."""
    p32 = p.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    m32 = m.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    if l2:
        g32 = g32 + l2 * p32
    m32 = b1 * m32 + (1.0 - b1) * g32
    v32 = b2 * v32 + (1.0 - b2) * (g32 * g32)
    if eager_form:
        step = (jnp.sqrt(bc2) / bc1) * m32 / (jnp.sqrt(v32) + eps)
    else:
        step = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
    p32 = p32 * (1.0 - lr * wd) - lr * step
    return p32.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)


def adamw_flat(p, g, m, v, lr, bc1, bc2, *, b1=0.9, b2=0.999, eps=1e-8,
               wd=0.0, l2=0.0, eager_form=False, interpret=None):
    """One-pass AdamW over a flat 1-D bucket → (new_p, new_m, new_v).

    ``interpret=None`` auto-selects: the Pallas kernel on TPU, the
    identical jnp math elsewhere; ``interpret=True`` forces the kernel
    through the Pallas interpreter (parity tests)."""
    kw = dict(b1=float(b1), b2=float(b2), eps=float(eps), wd=float(wd),
              l2=float(l2), eager_form=bool(eager_form))
    if interpret is None:
        if not _on_tpu():
            return _adamw_flat_ref(p, g, m, v, lr, bc1, bc2, **kw)
        interpret = False
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = p.shape[0]
    p2, _ = _pad_2d(p)
    g2, _ = _pad_2d(g)
    m2, _ = _pad_2d(m)
    v2, _ = _pad_2d(v)
    rows = p2.shape[0]
    bb = _block_rows(rows)
    if _autotune.enabled():
        cfg = _autotune.get_config("fused_adamw", (rows,),
                                   str(jnp.dtype(p.dtype)), {"bb": bb})
        tb = int(cfg.get("bb", 0) or 0)
        if tb and rows % tb == 0:
            bb = tb
    sc = jnp.stack([jnp.asarray(lr, jnp.float32),
                    jnp.asarray(bc1, jnp.float32),
                    jnp.asarray(bc2, jnp.float32)])
    blk = lambda: pl.BlockSpec((bb, _LANE), lambda i: (i, 0))
    np2, nm2, nv2 = pl.pallas_call(
        functools.partial(_adamw_kernel, **kw),
        out_shape=(jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(m2.shape, m.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)),
        grid=(rows // bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  blk(), blk(), blk(), blk()],
        out_specs=(blk(), blk(), blk()),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="fused_adamw",
    )(sc, p2, g2, m2, v2)
    return (np2.reshape(-1)[:n], nm2.reshape(-1)[:n], nv2.reshape(-1)[:n])


# --------------------------------------------------------------------------
# LAMB: fused moment/trust-ratio-dividend pass; the per-parameter trust
# ratio (a per-leaf norm pair) is applied outside the kernel — still one
# HBM pass for the moment math, then cheap reductions.
# --------------------------------------------------------------------------

def _lamb_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                 nm_ref, nv_ref, r_ref, *, b1, b2, eps, wd):
    bc1, bc2 = sc_ref[1], sc_ref[2]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * (g * g)
    r = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
    nm_ref[...] = m.astype(nm_ref.dtype)
    nv_ref[...] = v.astype(nv_ref.dtype)
    r_ref[...] = r


def _lamb_flat_ref(p, g, m, v, bc1, bc2, *, b1, b2, eps, wd):
    p32 = p.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
    v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * (g32 * g32)
    r = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps) + wd * p32
    return m32.astype(m.dtype), v32.astype(v.dtype), r


def lamb_moments_flat(p, g, m, v, bc1, bc2, *, b1=0.9, b2=0.999, eps=1e-6,
                      wd=0.0, interpret=None):
    """Fused LAMB moment update → (new_m, new_v, trust_dividend r)."""
    kw = dict(b1=float(b1), b2=float(b2), eps=float(eps), wd=float(wd))
    if interpret is None:
        if not _on_tpu():
            return _lamb_flat_ref(p, g, m, v, bc1, bc2, **kw)
        interpret = False
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = p.shape[0]
    p2, _ = _pad_2d(p)
    g2, _ = _pad_2d(g)
    m2, _ = _pad_2d(m)
    v2, _ = _pad_2d(v)
    rows = p2.shape[0]
    bb = _block_rows(rows)
    sc = jnp.stack([jnp.float32(0.0), jnp.asarray(bc1, jnp.float32),
                    jnp.asarray(bc2, jnp.float32)])
    blk = lambda: pl.BlockSpec((bb, _LANE), lambda i: (i, 0))
    nm2, nv2, r2 = pl.pallas_call(
        functools.partial(_lamb_kernel, **kw),
        out_shape=(jax.ShapeDtypeStruct(m2.shape, m.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype),
                   jax.ShapeDtypeStruct(p2.shape, jnp.float32)),
        grid=(rows // bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  blk(), blk(), blk(), blk()],
        out_specs=(blk(), blk(), blk()),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="fused_lamb_moments",
    )(sc, p2, g2, m2, v2)
    return (nm2.reshape(-1)[:n], nv2.reshape(-1)[:n], r2.reshape(-1)[:n])


# --------------------------------------------------------------------------
# bucket executors: ONE program either way, but the flat concat/kernel
# layout only on TPU — XLA CPU materializes every concat/split as a real
# copy (measured ~8ms per 100-leaf round-trip vs ~2ms for the same math
# left per-leaf inside one program), while on TPU the flat Pallas pass
# is the whole point. Numerics are identical: the flat reference IS the
# per-leaf formula applied elementwise.
# --------------------------------------------------------------------------

def _bucket_adamw(ps, gs, ms, vs, lr, bc1, bc2, *, b1, b2, eps, wd,
                  l2=0.0, eager_form=False, store=None):
    """AdamW over one bucket's leaf lists → (new_ps, new_ms, new_vs)."""
    kw = dict(b1=b1, b2=b2, eps=eps, wd=wd, l2=l2, eager_form=eager_form)
    if _on_tpu():
        sdt = store or ms[0].dtype
        npf, nmf, nvf = adamw_flat(
            flatten_bucket(ps), flatten_bucket(gs),
            flatten_bucket([m.astype(sdt) for m in ms]),
            flatten_bucket([v.astype(sdt) for v in vs]),
            lr, bc1, bc2, **kw)
        shapes = [p.shape for p in ps]
        return (unflatten_bucket(npf, shapes),
                unflatten_bucket(nmf, shapes),
                unflatten_bucket(nvf, shapes))
    out = [_adamw_flat_ref(p, g,
                           m if store is None else m.astype(store),
                           v if store is None else v.astype(store),
                           lr, bc1, bc2, **kw)
           for p, g, m, v in zip(ps, gs, ms, vs)]
    return ([o[0] for o in out], [o[1] for o in out],
            [o[2] for o in out])


def _bucket_lamb(ps, gs, ms, vs, bc1, bc2, *, b1, b2, eps, wd):
    """LAMB moments over one bucket → (new_ms, new_vs, rs)."""
    kw = dict(b1=b1, b2=b2, eps=eps, wd=wd)
    if _on_tpu():
        nmf, nvf, rf = lamb_moments_flat(
            flatten_bucket(ps), flatten_bucket(gs), flatten_bucket(ms),
            flatten_bucket(vs), bc1, bc2, **kw)
        shapes = [p.shape for p in ps]
        return (unflatten_bucket(nmf, shapes),
                unflatten_bucket(nvf, shapes),
                unflatten_bucket(rf, shapes))
    out = [_lamb_flat_ref(p, g, m, v, bc1, bc2, **kw)
           for p, g, m, v in zip(ps, gs, ms, vs)]
    return ([o[0] for o in out], [o[1] for o in out],
            [o[2] for o in out])


# --------------------------------------------------------------------------
# in-jit tree-level updates (pure_adamw_update / pure_lamb_update parity)
# --------------------------------------------------------------------------

def _bucket_indices(flat_p, flat_m, flat_wd):
    """Group leaf indices by (param dtype, moment dtype, decay coeff)."""
    buckets: dict = {}
    for i, (p, m, wd) in enumerate(zip(flat_p, flat_m, flat_wd)):
        buckets.setdefault(
            (jnp.dtype(p.dtype), jnp.dtype(m.dtype), float(wd)),
            []).append(i)
    return buckets


def fused_adamw_update(params, grads, state, lr, beta1=0.9, beta2=0.999,
                       eps=1e-8, weight_decay=0.01, l2_coeff=0.0,
                       mv_dtype=None, decay_mask=None):
    """pure_adamw_update drop-in: same signature, same state layout
    (per-leaf m/v trees), the math executed as one flat pass per
    (dtype, decay) bucket. FLAGS_fused_optimizer selects it inside
    jit.TrainStep / DistributedTrainStep."""
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    bc1 = 1.0 - beta1 ** c
    bc2 = 1.0 - beta2 ** c

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_wd = ([weight_decay] * len(flat_p) if decay_mask is None else
               [weight_decay if dm else 0.0
                for dm in treedef.flatten_up_to(decay_mask)])
    store = [(m.dtype if mv_dtype is None else mv_dtype) for m in flat_m]

    new_p = [None] * len(flat_p)
    new_m = [None] * len(flat_p)
    new_v = [None] * len(flat_p)
    for (pdt, mdt, wd), idx in _bucket_indices(flat_p, flat_m,
                                               flat_wd).items():
        nps, nms, nvs = _bucket_adamw(
            [flat_p[i] for i in idx],
            [flat_g[i].astype(jnp.float32) for i in idx],
            [flat_m[i] for i in idx], [flat_v[i] for i in idx],
            lr, bc1, bc2, b1=beta1, b2=beta2, eps=eps, wd=wd,
            l2=l2_coeff, store=store[idx[0]])
        for i, pl_, ml_, vl_ in zip(idx, nps, nms, nvs):
            new_p[i], new_m[i], new_v[i] = pl_, ml_, vl_
    unflat = functools.partial(jax.tree_util.tree_unflatten, treedef)
    return unflat(new_p), {"m": unflat(new_m), "v": unflat(new_v),
                           "count": count}


def fused_lamb_update(params, grads, state, lr, beta1=0.9, beta2=0.999,
                      eps=1e-6, weight_decay=0.01, decay_mask=None, **_):
    """pure_lamb_update drop-in: fused moment/dividend pass per bucket,
    then the per-PARAMETER trust ratio ‖p‖/‖r‖ applied per leaf."""
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    bc1 = 1.0 - beta1 ** c
    bc2 = 1.0 - beta2 ** c

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_wd = ([weight_decay] * len(flat_p) if decay_mask is None else
               [weight_decay if dm else 0.0
                for dm in treedef.flatten_up_to(decay_mask)])

    new_p = [None] * len(flat_p)
    new_m = [None] * len(flat_p)
    new_v = [None] * len(flat_p)
    for (pdt, mdt, wd), idx in _bucket_indices(flat_p, flat_m,
                                               flat_wd).items():
        ms, vs, rs = _bucket_lamb(
            [flat_p[i] for i in idx],
            [flat_g[i].astype(jnp.float32) for i in idx],
            [flat_m[i] for i in idx], [flat_v[i] for i in idx],
            bc1, bc2, b1=beta1, b2=beta2, eps=eps, wd=wd)
        for j, i in enumerate(idx):
            p32 = flat_p[i].astype(jnp.float32)
            r = rs[j]
            p_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
            r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
            trust = jnp.where((p_norm > 0) & (r_norm > 0),
                              p_norm / r_norm, 1.0)
            new_p[i] = (p32 - lr * trust * r).astype(flat_p[i].dtype)
            new_m[i], new_v[i] = ms[j], vs[j]
    unflat = functools.partial(jax.tree_util.tree_unflatten, treedef)
    return unflat(new_p), {"m": unflat(new_m), "v": unflat(new_v),
                           "count": count}


# --------------------------------------------------------------------------
# jit.TrainStep bridge: same per-param slot layout (m1, m2, b1p, b2p),
# fused execution. Slots are materialized together at TrainStep build, so
# every param's beta-pow pair advances in lockstep — the first leaf's pair
# is the bucket's bias correction.
# --------------------------------------------------------------------------

def fused_update_from_slots(opt, param_names, params, grads, slots, lr,
                            hyper):
    """Fused Adam/AdamW update over TrainStep's named state dicts.

    ``slots[k] = [m1, m2, b1p, b2p]``; returns (new_params, new_slots)
    with the identical layout. ``hyper[k]`` is the param's static hyper
    tuple (b1/b2/eps[/coeff]) — part of the bucket key, so AdamW's
    apply_decay_param_fun exclusions land in their own buckets."""
    k0 = param_names[0]
    b1p, b2p = slots[k0][2], slots[k0][3]
    h0 = dict(hyper[k0])
    b1, b2 = h0["b1"], h0["b2"]
    # slot convention (Adam._init_slot/_pure_update): b1p already holds
    # beta1^t when the step runs; the pow advances AFTER use
    bc1 = 1.0 - b1p
    bc2 = 1.0 - b2p

    buckets: dict = {}
    for k in param_names:
        h = dict(hyper[k])
        key = (jnp.dtype(params[k].dtype), float(h.get("coeff", 0.0)),
               float(h["eps"]))
        buckets.setdefault(key, []).append(k)

    new_params, new_slots = {}, {}
    for (pdt, wd, eps), keys in buckets.items():
        nps, nms, nvs = _bucket_adamw(
            [params[k] for k in keys],
            [grads[k].astype(jnp.float32) for k in keys],
            [slots[k][0] for k in keys], [slots[k][1] for k in keys],
            jnp.asarray(lr, jnp.float32), bc1, bc2,
            b1=b1, b2=b2, eps=eps, wd=wd, eager_form=True)
        for k, pl_, ml_, vl_ in zip(keys, nps, nms, nvs):
            new_params[k] = pl_
            new_slots[k] = [ml_, vl_, b1p * b1, b2p * b2]
    return new_params, new_slots


# --------------------------------------------------------------------------
# eager Optimizer.step fast path: ONE device dispatch per step over
# persistent flat moment buffers (vs N per-param jit calls).
# --------------------------------------------------------------------------

class _FusedEagerState:
    """Per-optimizer cache: bucket layout + device-resident moments.

    Built lazily from the optimizer's existing per-param slots (so a
    half-trained optimizer can switch the flag on mid-run), kept in
    lockstep afterwards; ``sync_slots`` writes the moments back into
    ``opt._accumulators`` for state_dict/checkpoint readers. The whole
    step is ONE jitted dispatch; inside it each bucket runs through
    :func:`_bucket_adamw`/:func:`_bucket_lamb` (flat Pallas pass on
    TPU, per-leaf math elsewhere)."""

    def __init__(self, opt, params_grads, kind):
        self.kind = kind                      # "adam" | "lamb"
        self.params = [p for p, _ in params_grads]
        self.sig = tuple((id(p), tuple(p._data.shape), str(p._data.dtype))
                         for p in self.params)
        buckets: dict = {}
        for i, p in enumerate(self.params):
            h = dict(opt._hyper(p))
            l2 = 0.0
            reg = (p.regularizer if p.regularizer is not None
                   else opt._weight_decay)
            from ..regularizer import L2Decay
            if isinstance(reg, L2Decay) and not opt._decoupled_wd():
                l2 = float(reg.coeff)
            lr_mult = float(p.optimize_attr.get("learning_rate", 1.0))
            slots = opt._get_slots(p)
            key = (str(p._data.dtype), str(slots[0].dtype),
                   float(h.get("coeff", h.get("wd", 0.0))),
                   float(h["eps"]), l2, lr_mult)
            buckets.setdefault(key, []).append(i)
        self.buckets = [(key, idx) for key, idx in buckets.items()]
        self.b1 = float(opt._beta1)
        self.b2 = float(opt._beta2)
        # device-resident moments per bucket (leaf lists, slot order)
        self.ms, self.vs = [], []
        for _, idx in self.buckets:
            ms, vs = [], []
            for i in idx:
                s = opt._get_slots(self.params[i])
                ms.append(s[0])
                vs.append(s[1])
            self.ms.append(ms)
            self.vs.append(vs)
        s0 = opt._get_slots(self.params[0])
        self.b1p, self.b2p = s0[2], s0[3]
        self._fn = None

    def _build(self):
        buckets, b1, b2, kind = self.buckets, self.b1, self.b2, self.kind

        def run(plist, glist, mlist, vlist, b1p, b2p, lr):
            # b1p/b2p already hold beta^t at use time (slot convention)
            bc1 = 1.0 - b1p
            bc2 = 1.0 - b2p
            new_p = list(plist)
            new_m, new_v = [], []
            for bi, (key, idx) in enumerate(buckets):
                _, _, wd, eps, l2, lr_mult = key
                ps = [plist[i] for i in idx]
                gs = [glist[i].astype(jnp.float32) for i in idx]
                blr = lr * lr_mult
                if kind == "adam":
                    nps, nms, nvs = _bucket_adamw(
                        ps, gs, mlist[bi], vlist[bi], blr, bc1, bc2,
                        b1=b1, b2=b2, eps=eps, wd=wd, l2=l2,
                        eager_form=True)
                    for i, leaf in zip(idx, nps):
                        new_p[i] = leaf
                else:
                    nms, nvs, rs = _bucket_lamb(
                        ps, gs, mlist[bi], vlist[bi], bc1, bc2,
                        b1=b1, b2=b2, eps=eps, wd=wd)
                    for i, r in zip(idx, rs):
                        p32 = plist[i].astype(jnp.float32)
                        p_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
                        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
                        trust = jnp.where((p_norm > 0) & (r_norm > 0),
                                          p_norm / r_norm, 1.0)
                        new_p[i] = (p32 - blr * trust * r).astype(
                            plist[i].dtype)
                new_m.append(nms)
                new_v.append(nvs)
            return new_p, new_m, new_v, b1p * b1, b2p * b2

        self._fn = jax.jit(run, donate_argnums=(2, 3))

    def step(self, grads, lr):
        if self._fn is None:
            self._build()
        plist = [p._data for p in self.params]
        new_p, self.ms, self.vs, self.b1p, self.b2p = self._fn(
            plist, grads, self.ms, self.vs, self.b1p, self.b2p,
            jnp.asarray(lr, jnp.float32))
        for p, arr in zip(self.params, new_p):
            p._data = arr

    def sync_slots(self, opt):
        """Write the moments + beta-pows back into opt._accumulators."""
        names = opt._slot_names()
        for bi, (_, idx) in enumerate(self.buckets):
            for i, m, v in zip(idx, self.ms[bi], self.vs[bi]):
                p = self.params[i]
                vals = [m, v]
                if "beta1_pow" in names:
                    vals += [self.b1p, self.b2p]
                opt._set_slots(p, vals)


def fused_eager_step(opt, params_grads, lr) -> bool:
    """One-dispatch fused step for Adam/AdamW/Lamb eager ``step()``.

    Returns False (caller falls back to the unfused per-param loop) when
    the param set uses features the flat path doesn't cover (L1
    regularizers). On success: params updated in place, slot mirrors
    marked dirty (synced lazily by state_dict)."""
    from ..regularizer import L1Decay

    if not params_grads:
        return True
    for p, _ in params_grads:
        reg = p.regularizer if p.regularizer is not None else \
            opt._weight_decay
        if isinstance(reg, L1Decay):
            return False
    kind = "lamb" if type(opt).__name__ == "Lamb" else "adam"
    sig = tuple((id(p), tuple(p._data.shape), str(p._data.dtype))
                for p, _ in params_grads)
    st = getattr(opt, "_fused_state", None)
    if st is None or st.sig != sig:
        st = _FusedEagerState(opt, params_grads, kind)
        opt._fused_state = st
    grads = []
    for p, g in params_grads:
        garr = g._data if hasattr(g, "_data") else g
        grads.append(garr)
    t0 = time.perf_counter()
    with _trace_span("kernel.fused_%s" % kind, cat="kernel"):
        st.step(grads, lr)
    if _bench.enabled():
        _bench.record_op("fused_%s@step" % kind, time.perf_counter() - t0)
    FUSED_OPTIMIZER_STEPS.add()
    opt._slots_stale = True
    return True


# -- autotune family (ISSUE 17) ---------------------------------------------

def _adamw_candidates(shape, dtype):
    rows = int(shape[0])
    cands = [{"bb": c} for c in (512, 256, 128, 64, 32, 16)
             if rows % c == 0]
    return (cands or [{"bb": rows}])[:4]


def _adamw_bench(shape, dtype, config):
    import numpy as np

    rows = int(shape[0])
    n = rows * _LANE
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.standard_normal(n).astype(dtype))
    g = jnp.asarray(rng.standard_normal(n).astype(dtype) * 0.01)
    m = jnp.zeros((n,), dtype)
    v = jnp.zeros((n,), dtype)
    # bench through the padded 2-D kernel body directly at this block
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, _ = _pad_2d(p)
    g2, _ = _pad_2d(g)
    m2, _ = _pad_2d(m)
    v2, _ = _pad_2d(v)
    bb = int(config["bb"])
    sc = jnp.stack([jnp.float32(1e-3), jnp.float32(0.9),
                    jnp.float32(0.999)])
    blk = lambda: pl.BlockSpec((bb, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_adamw_kernel, b1=0.9, b2=0.999, eps=1e-8,
                          wd=0.0, l2=0.0, eager_form=False),
        out_shape=(jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(m2.shape, m.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)),
        grid=(rows // bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  blk(), blk(), blk(), blk()],
        out_specs=(blk(), blk(), blk()),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=not _on_tpu(),
        name="fused_adamw_bench",
    )(sc, p2, g2, m2, v2)
    jax.block_until_ready(out)


_autotune.register_family("fused_adamw", _adamw_candidates, _adamw_bench)
