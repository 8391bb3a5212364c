"""DistributedTrainStep — the whole training step as one sharded XLA program.

Replaces the reference's hybrid-parallel step choreography
(fleet/meta_optimizers/dygraph_optimizer/hybrid_parallel_optimizer.py:207:
sharding_reduce_gradients → fused_allreduce_gradients(dp) → inner step, plus
HybridParallelClipGrad's cross-group allreduced global norm :45) with a
single jit: value_and_grad + global-norm clip + a pure optimizer update,
compiled with NamedShardings so XLA emits every reduction the reference
inserted by hand — dp/sharding grad psum, ZeRO reduce-scatter/all-gather,
TP activation collectives.

Optimizer state is sharded by :func:`zero_shard_specs` (ZeRO-1): the update
math runs 1/Nth per device along "sharding"; XLA all-gathers fresh params.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis import sanitizers as _san
from ..core import native as _native
from ..core.native import fast_step as _fast_step
from ..core.native import sanitize as _sanitize
from ..framework.core import AsyncLoss as _AsyncLoss
from ..monitor import benchmark as _bench
from ..monitor import stats as _mstats
from ..monitor import trace as _trace
from ..monitor.trace import span as _trace_span
from ..resilience import faults as _faults
from ..resilience import sentinel as _sentinel
from .mesh import get_mesh, mesh_shape
from .sharding import zero_shard_specs

__all__ = ["DistributedTrainStep", "pure_adamw_init", "pure_adamw_update",
           "pure_sgd_init", "pure_sgd_update", "pure_momentum_init",
           "pure_momentum_update", "pure_lamb_init", "pure_lamb_update",
           "pure_lars_init", "pure_lars_update", "global_norm_clip"]


# -- pure optimizers (tree-level) ------------------------------------------

def pure_adamw_init(params, mv_dtype=jnp.float32):
    # m/v default to fp32 regardless of the param dtype (the update math is
    # always fp32). mv_dtype=bf16 halves optimizer-state HBM footprint AND
    # per-step optimizer traffic — bf16 keeps fp32's exponent range, so
    # m/v never over/underflow, only lose mantissa; at LLM scale the freed
    # memory buys a larger batch, which dominates the precision cost (the
    # update still computes in fp32 and stores back rounded). Pass the same
    # mv_dtype to pure_adamw_update so the scan carry dtype is stable.
    zeros = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.zeros(jnp.shape(x), mv_dtype), t)
    return {"m": zeros(params), "v": zeros(params),
            "count": jnp.zeros((), jnp.int32)}


def pure_adamw_update(params, grads, state, lr, beta1=0.9, beta2=0.999,
                      eps=1e-8, weight_decay=0.01, l2_coeff=0.0,
                      mv_dtype=None, decay_mask=None):
    """weight_decay is AdamW's decoupled decay; l2_coeff is classic Adam's
    grad-side L2 (added before the moments, reference Optimizer
    _regularized_grad path). mv_dtype: storage dtype for the moments (None
    = keep whatever pure_adamw_init allocated); math is fp32 either way.
    decay_mask: optional pytree of bools matching params — False leaves
    skip the decoupled decay (reference AdamW apply_decay_param_fun,
    python/paddle/optimizer/adamw.py _append_decoupled_weight_decay)."""
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    bc1 = 1.0 - beta1 ** c
    bc2 = 1.0 - beta2 ** c

    def upd(p, g, m, v, wd):
        g32 = g.astype(jnp.float32)
        store = m.dtype if mv_dtype is None else mv_dtype
        m, v = m.astype(jnp.float32), v.astype(jnp.float32)
        if l2_coeff:
            g32 = g32 + l2_coeff * p.astype(jnp.float32)
        m = beta1 * m + (1 - beta1) * g32
        v = beta2 * v + (1 - beta2) * (g32 * g32)
        step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        # decay BEFORE the adam step, matching the reference op order
        # (adamw.py _append_decoupled_weight_decay scales the param first)
        p32 = p.astype(jnp.float32) * (1.0 - lr * wd)
        p32 = p32 - lr * step
        return p32.astype(p.dtype), m.astype(store), v.astype(store)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_wd = ([weight_decay] * len(flat_p) if decay_mask is None else
               [weight_decay if dm else 0.0
                for dm in treedef.flatten_up_to(decay_mask)])
    out = [upd(p, g, m, v, wd) for p, g, m, v, wd
           in zip(flat_p, flat_g, flat_m, flat_v, flat_wd)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}


def pure_lamb_init(params):
    return pure_adamw_init(params)


def pure_lamb_update(params, grads, state, lr, beta1=0.9, beta2=0.999,
                     eps=1e-6, weight_decay=0.01, decay_mask=None, **_):
    """LAMB (reference operators/optimizers/lamb_op.h
    LambMomentREGUpdateFunctor + LambParamUpateFunctor): Adam moments →
    trust_ratio_div r = m̂/(√v̂+ε) + λp, then a PER-PARAMETER trust ratio
    ‖p‖/‖r‖ (1 when either norm is 0) rescales lr. decay_mask=False
    leaves λ=0 for that leaf (exclude_from_weight_decay_fn)."""
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    bc1 = 1.0 - beta1 ** c
    bc2 = 1.0 - beta2 ** c

    def upd(p, g, m, v, wd):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        m = beta1 * m + (1 - beta1) * g32
        v = beta2 * v + (1 - beta2) * (g32 * g32)
        r = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p32
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        p32 = p32 - lr * trust * r
        return p32.astype(p.dtype), m, v

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_wd = ([weight_decay] * len(flat_p) if decay_mask is None else
               [weight_decay if dm else 0.0
                for dm in treedef.flatten_up_to(decay_mask)])
    out = [upd(p, g, m, v, wd) for p, g, m, v, wd
           in zip(flat_p, flat_g, flat_m, flat_v, flat_wd)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}


def pure_lars_init(params):
    return pure_momentum_init(params)


def pure_lars_update(params, grads, state, lr, momentum=0.9,
                     lars_coeff=0.001, lars_weight_decay=0.0005,
                     epsilon=0.0, **_):
    """LARS momentum (reference operators/optimizers/lars_momentum_op.h):
    per-parameter local_lr = lr·coeff·‖p‖ / (‖g‖ + λ‖p‖ + ε) when
    λ>0 and both norms >0, else the global lr; velocity over the
    L2-regularized gradient."""

    def upd(p, g, v):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g32)))
        local_lr = jnp.where(
            (lars_weight_decay > 0) & (p_norm > 0) & (g_norm > 0),
            lr * lars_coeff * p_norm
            / (g_norm + lars_weight_decay * p_norm + epsilon),
            lr)
        nv = momentum * v + local_lr * (g32 + lars_weight_decay * p32)
        p32 = p32 - nv
        return p32.astype(p.dtype), nv

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_v = treedef.flatten_up_to(state["velocity"])
    out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return new_p, {"velocity": new_v, "count": state["count"] + 1}


def pure_sgd_init(params):
    return {"count": jnp.zeros((), jnp.int32)}


def pure_sgd_update(params, grads, state, lr, weight_decay=0.0, **_):
    def upd(p, g):
        g32 = g.astype(jnp.float32)
        if weight_decay:
            g32 = g32 + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * g32).astype(p.dtype)

    new_p = jax.tree_util.tree_map(upd, params, grads)
    return new_p, {"count": state["count"] + 1}


def pure_momentum_init(params):
    # velocity in fp32, like adamw's m/v (see pure_adamw_init)
    return {"velocity": jax.tree_util.tree_map(
        lambda x: jnp.zeros(jnp.shape(x), jnp.float32), params),
        "count": jnp.zeros((), jnp.int32)}


def pure_momentum_update(params, grads, state, lr, momentum=0.9,
                         use_nesterov=False, weight_decay=0.0):
    """SGD with (Nesterov) momentum — matches Momentum._pure_update
    (reference operators/optimizers/momentum_op.h velocity recurrence)."""

    def upd(p, g, v):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if weight_decay:
            g32 = g32 + weight_decay * p32
        nv = momentum * v + g32
        if use_nesterov:
            p32 = p32 - lr * (g32 + momentum * nv)
        else:
            p32 = p32 - lr * nv
        return p32.astype(p.dtype), nv

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_v = treedef.flatten_up_to(state["velocity"])
    out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return new_p, {"velocity": new_v, "count": state["count"] + 1}


def global_norm_clip(grads, clip_norm: float):
    """Global-norm clip across the WHOLE param set — inside the sharded
    program the partial norms are combined by XLA, which is exactly the
    reference HybridParallelClipGrad's allreduce-across-groups (:45-170)."""
    leaves = jax.tree_util.tree_leaves(grads)
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, clip_norm / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), grads), norm


_OPTS = {
    "adamw": (pure_adamw_init, pure_adamw_update),
    "sgd": (pure_sgd_init, pure_sgd_update),
    "momentum": (pure_momentum_init, pure_momentum_update),
    "lamb": (pure_lamb_init, pure_lamb_update),
    "lars": (pure_lars_init, pure_lars_update),
}


def _san_batch_sig(sig):
    """Batch aval sig -> sanitizers leaf-signature format."""
    return tuple((str(i), shape, dtype, False)
                 for i, (shape, dtype) in enumerate(sig))


def _pmean_in_bwd(axes):
    """Identity whose BACKWARD all-reduces the cotangent over ``axes`` —
    applied per param bucket inside shard_map, it issues the dp-grad
    pmean at the exact point the backward produces that bucket's grad,
    so XLA's async collectives overlap it with the REMAINING backward
    compute (the ring-attention per-hop overlap idea applied to the
    gradient all-reduce; FLAGS_overlap_grads)."""

    @jax.custom_vjp
    def ident(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jax.lax.pmean(g, axes),)

    ident.defvjp(fwd, bwd)
    return ident


def _spec_shard_dim(spec, axis="sharding"):
    """Index of the dim ``axis`` shards in a PartitionSpec, else None."""
    if not isinstance(spec, P):
        return None
    for d, e in enumerate(tuple(spec)):
        if e == axis or (isinstance(e, (tuple, list)) and axis in e):
            return d
    return None


def _rs_in_bwd(data_axes, shard_axis, dim, deg):
    """Identity whose BACKWARD reduce-scatters the cotangent over
    ``shard_axis`` (and pmeans over ``data_axes``) — the ZeRO-2 form of
    :func:`_pmean_in_bwd` (FLAGS_overlap_zero2): each device keeps only
    ITS 1/deg shard of the bucket's grad, issued in-backward so the
    scatter overlaps remaining backward compute, and the full-size
    reduced gradient never materializes. The cotangent must match the
    primal (full) shape inside shard_map, so the shard lands in a zero
    buffer at this device's offset; the caller slices it back out before
    the shard_map boundary."""

    @jax.custom_vjp
    def ident(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        shard = jax.lax.psum_scatter(g, shard_axis, scatter_dimension=dim,
                                     tiled=True)
        if data_axes:
            shard = jax.lax.pmean(shard, data_axes)
        # psum_scatter SUMS over the shard group; match pmean semantics
        shard = shard / deg
        size = shard.shape[dim]
        idx = jax.lax.axis_index(shard_axis)
        buf = jnp.zeros_like(g)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, shard, idx * size,
                                                  dim)
        return (buf,)

    ident.defvjp(fwd, bwd)
    return ident


class DistributedTrainStep:
    """jit(value_and_grad(loss) + clip + optimizer) with Fleet shardings.

    Args:
      loss_fn: pure ``(params, batch) -> scalar loss``.
      params: param pytree (jax arrays).
      param_specs: matching pytree of PartitionSpec (TP/PP placement).
      optimizer: "adamw" | "sgd" | (init_fn, update_fn) pair.
      lr: learning rate — a float, or a callable ``step_index -> float``
        (schedule); either way it enters the compiled step as a traced
        scalar, so schedules do not trigger recompilation.
      batch_spec: PartitionSpec for each batch leaf; default shards the
        leading dim over ("data", "sharding") — the sharding group doubles
        as extra data parallelism, as in reference sharding_optimizer
        hybrid-dp mode (sharding_optimizer.py, hybrid with dp).
      clip_norm: optional global-norm clip.
      zero: ZeRO stage over the "sharding" axis (Rajbhandari et al. 2020).
        ``True``/1 shards optimizer state (the historical default);
        2 additionally pins gradients to the sharded layout (XLA's grad
        reduction becomes a reduce-scatter and the full-size gradient
        never materializes); 3 additionally stores the PARAMETERS
        1/Nth-sharded (all-gathered where the forward consumes them).
        ``False``/0 disables. A ``fleet.auto.ShardedOptimizer`` passed as
        ``optimizer`` carries its own level (and hyperparameters), which
        wins over this argument.
      zero_min_size: parameters smaller than this stay replicated under
        ZeRO (the reference's greedy partition likewise skips tiny
        tensors).
      aux: optional non-trainable state pytree (buffers: BatchNorm running
        stats, quant scales) threaded through the step. When given,
        ``loss_fn`` is ``(params, aux, batch) -> (loss, new_aux)`` and the
        step keeps ``self.aux`` updated — the functional analog of the
        reference's in-place persistable-variable mutation. Default
        replicated; pass aux_specs to shard.
      dynamic_scale: optional dict enabling COMPILED dynamic loss scaling
        (fp16 training) — the in-jit analog of the reference's
        check_finite_and_unscale + update_loss_scaling op pair
        (operators/amp/check_finite_and_unscale_op.cc,
        update_loss_scaling_op.cc): the loss is scaled before the
        backward, grads unscaled, a single all-reduced finite flag gates
        the whole parameter/optimizer update with ``where`` (a skipped
        step costs nothing), and the scale/good/bad counters update in the
        same program. Keys (GradScaler names): init_scale, incr_ratio,
        decr_ratio, incr_every_n_steps, decr_every_n. State lives in
        ``self.scaler_state`` {"scale","good","bad"} (host-readable).
      sentinel: optional resilience.sentinel config (True for defaults):
        a per-step health verdict (loss/grad-norm finiteness + EMA
        z-score spike) computed INSIDE the compiled step; the whole
        update is gated on it (a tripped step is a no-op,
        GradScaler-style) and a device trip counter is carried in
        ``self.sentinel_state`` — no host syncs are added; TrainGuardian
        reads the counter at its own cadence.
    """

    def __init__(self, loss_fn: Callable, params, param_specs,
                 optimizer="adamw", lr: float = 1e-3,
                 batch_spec: P = P(("data", "sharding")),
                 clip_norm: Optional[float] = None, zero=True,
                 mesh=None, opt_kwargs: Optional[dict] = None,
                 aux=None, aux_specs=None,
                 dynamic_scale: Optional[dict] = None,
                 sentinel=None, zero_min_size: int = 2 ** 12):
        self.mesh = mesh or get_mesh()
        if self.mesh is None:
            raise RuntimeError("DistributedTrainStep needs a mesh "
                               "(parallel.create_mesh)")
        if hasattr(optimizer, "fns") and hasattr(optimizer, "level"):
            # fleet.auto.ShardedOptimizer: carries (init, update), the
            # ZeRO level and its hyperparameters
            zero = optimizer.level
            opt_kwargs = {**optimizer.opt_kwargs, **(opt_kwargs or {})}
            optimizer = optimizer.fns()
        if isinstance(optimizer, str):
            init_fn, update_fn = _OPTS[optimizer]
            if _native.fused_optimizer[0] and optimizer in ("adamw",
                                                            "lamb"):
                # FLAGS_fused_optimizer: same init/state layout, the
                # update math as flat-bucket passes (Pallas on TPU)
                from ..ops.fused_optimizer import (fused_adamw_update,
                                                   fused_lamb_update)

                update_fn = (fused_adamw_update if optimizer == "adamw"
                             else fused_lamb_update)
        else:
            init_fn, update_fn = optimizer
        self._update_fn = update_fn
        self._loss_fn = loss_fn
        self._lr = lr
        self._clip = clip_norm
        self._opt_kwargs = dict(opt_kwargs or {})
        self.param_specs = param_specs

        shard_deg = mesh_shape(self.mesh).get("sharding", 1)
        zero_level = (1 if zero is True else 0 if zero is False
                      else int(zero))
        if shard_deg <= 1:
            zero_level = 0
        self.zero_level = zero_level
        opt_state = init_fn(params)
        shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), params)
        if zero_level >= 1:
            zspecs = zero_shard_specs(param_specs, shapes, shard_deg,
                                      min_size=zero_min_size)
        else:
            zspecs = param_specs
        self._zspecs = zspecs
        # ZeRO-3: parameter STORAGE is 1/Nth-sharded — the jit boundary
        # shardings do the partitioning, XLA all-gathers at first use
        storage_specs = zspecs if zero_level >= 3 else param_specs
        # per-param moment trees (m/v/velocity/...) mirror the
        # (zero-)sharded param layout; scalars (count) replicated
        param_treedef = jax.tree_util.tree_structure(params)

        def _state_spec(v):
            try:
                if jax.tree_util.tree_structure(v) == param_treedef:
                    return zspecs
            except Exception:
                pass
            return jax.tree_util.tree_map(lambda _: P(), v)

        self.opt_specs = {k: _state_spec(v) for k, v in opt_state.items()}

        ns = lambda tree: jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))
        self._param_sh = ns(storage_specs)
        # ZeRO-2: gradients pinned to the sharded layout — the dp/sharding
        # grad reduction lowers to a reduce-scatter at this boundary and
        # the full-size grad buffer never materializes
        self._grad_sh = ns(zspecs) if zero_level >= 2 else self._param_sh
        self._opt_sh = ns(self.opt_specs)
        self._batch_spec = batch_spec

        # defensive copy: device_put may alias caller buffers, and our jit
        # donates params/opt_state — without the copy the caller's arrays
        # would be deleted on the first step.
        params_copy = jax.tree_util.tree_map(lambda x: jnp.array(x), params)
        self.params = jax.device_put(params_copy, self._param_sh)
        self.opt_state = jax.device_put(opt_state, self._opt_sh)

        self._has_aux = aux is not None
        if self._has_aux:
            if aux_specs is None:
                aux_specs = jax.tree_util.tree_map(lambda _: P(), aux)
            self._aux_sh = ns(aux_specs)
            aux_copy = jax.tree_util.tree_map(lambda x: jnp.array(x), aux)
            self.aux = jax.device_put(aux_copy, self._aux_sh)
        else:
            self.aux = None

        batch_sh = NamedSharding(self.mesh, batch_spec)
        self._batch_sh = batch_sh

        self._dyn = dict(dynamic_scale) if dynamic_scale else None
        if self._dyn is not None:
            self.scaler_state = {
                "scale": jnp.float32(self._dyn.get("init_scale", 2.0 ** 15)),
                "good": jnp.int32(0),
                "bad": jnp.int32(0),
            }
        else:
            self.scaler_state = None

        self._sentinel_cfg = (_sentinel.normalize_config(sentinel)
                              if sentinel else None)
        self.sentinel_state = (_sentinel.init_state()
                               if self._sentinel_cfg is not None else None)

        # FLAGS_overlap_grads (read at construction): grads computed
        # under shard_map with a per-bucket pmean issued INSIDE the
        # backward (_pmean_in_bwd), overlapping the dp all-reduce with
        # the remaining backward compute. Only sound when every param is
        # replicated (pure data/sharding mesh, no aux) — other
        # topologies keep the GSPMD path.
        self._overlap_axes = None
        if _native.overlap_grads[0]:
            shape = mesh_shape(self.mesh)
            spec_leaves = jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P))
            replicated = all(
                isinstance(s, P) and all(e is None for e in tuple(s))
                for s in spec_leaves)
            if (shape.get("model", 1) == 1 and shape.get("pipe", 1) == 1
                    and not self._has_aux and replicated):
                self._overlap_axes = tuple(
                    a for a in ("data", "sharding") if shape.get(a, 1) > 0)
                n_buckets = len(jax.tree_util.tree_leaves(params))
                _mstats.GRAD_OVERLAP_BUCKETS.add(n_buckets)
        # FLAGS_overlap_zero2 (ISSUE 17): at ZeRO-2+ the in-backward
        # pmean becomes an in-backward reduce-scatter over "sharding" —
        # each bucket's grad leaves the backward already 1/Nth-sharded
        # (the layout ZeRO-2 pins grads to) and the scatter overlaps the
        # remaining backward compute. Off, the overlap path keeps the
        # full pmean exactly as before.
        self._overlap_zero2 = bool(
            _native.overlap_zero2[0] and self._overlap_axes is not None
            and zero_level >= 2 and shard_deg > 1)
        self._shard_deg = shard_deg
        # zspec leaves aligned with the params-tree leaf order (zspecs is
        # built by tree_map over param_specs, so orders agree)
        self._zspec_leaves = jax.tree_util.tree_leaves(
            zspecs, is_leaf=lambda x: isinstance(x, P))

        def step(params, opt_state, aux, batch, lr, scaler_state,
                 sent_state):
            scale = (scaler_state["scale"] if scaler_state is not None
                     else jnp.float32(1.0))

            if self._overlap_axes is not None:
                axes = self._overlap_axes
                ident = _pmean_in_bwd(axes)
                rs2 = self._overlap_zero2
                deg = self._shard_deg
                data_axes = tuple(a for a in axes if a != "sharding")
                zleaves = self._zspec_leaves

                def leaf_ident(spec):
                    d = _spec_shard_dim(spec)
                    if rs2 and d is not None:
                        return _rs_in_bwd(data_axes, "sharding", d, deg)
                    return ident

                def local_step(p, b, sc):
                    def run_local(pp):
                        # per-bucket in-backward collective: each leaf's
                        # grad pmean (or, under FLAGS_overlap_zero2, its
                        # reduce-scatter) launches as soon as the
                        # backward produces it
                        flat, td = jax.tree_util.tree_flatten(pp)
                        flat = [leaf_ident(s)(x)
                                for x, s in zip(flat, zleaves)]
                        pp = jax.tree_util.tree_unflatten(td, flat)
                        loss = self._loss_fn(pp, b)
                        return loss * sc.astype(loss.dtype), loss

                    (_, loss), g = jax.value_and_grad(
                        run_local, has_aux=True)(p)
                    if rs2:
                        # keep only this device's shard of each sharded
                        # bucket (the rest of the zero buffer is dead);
                        # the zspec out_specs reassemble the global grad
                        # in the ZeRO-2 sharded layout
                        idx = jax.lax.axis_index("sharding")
                        flat, td = jax.tree_util.tree_flatten(g)
                        out = []
                        for x, s in zip(flat, zleaves):
                            d = _spec_shard_dim(s)
                            if d is None:
                                out.append(x)
                            else:
                                size = x.shape[d] // deg
                                out.append(jax.lax.dynamic_slice_in_dim(
                                    x, idx * size, size, d))
                        g = jax.tree_util.tree_unflatten(td, out)
                    return jax.lax.pmean(loss, axes), g

                g_specs = self._zspecs if rs2 else P()
                loss, grads = jax.shard_map(
                    local_step, mesh=self.mesh,
                    in_specs=(P(), self._batch_spec, P()),
                    out_specs=(P(), g_specs))(params, batch, scale)
                new_aux = aux
            else:
                def run_loss(p):
                    if self._has_aux:
                        loss, new_aux = self._loss_fn(p, aux, batch)
                    else:
                        loss, new_aux = self._loss_fn(p, batch), aux
                    return loss * scale.astype(loss.dtype), (loss, new_aux)

                (_, (loss, new_aux)), grads = jax.value_and_grad(
                    run_loss, has_aux=True)(params)
                # pin grads to the PARAM layout (ZeRO-0/1: the m/v
                # reshard happens here as a reduce-scatter instead of
                # GSPMD propagating the opt-state sharding backward
                # through the loss) or, at ZeRO-2+, directly to the
                # SHARDED layout so the full-size gradient never exists
                grads = jax.tree_util.tree_map(
                    lambda g, s: jax.lax.with_sharding_constraint(g, s),
                    grads, self._grad_sh)
            if scaler_state is not None:
                with jax.named_scope("optimizer"), \
                        jax.named_scope("loss_scale"):
                    inv = (1.0 / scale)
                    grads = jax.tree_util.tree_map(
                        lambda g: (g.astype(jnp.float32)
                                   * inv).astype(g.dtype), grads)
                    finite = jnp.array(True)
                    for g in jax.tree_util.tree_leaves(grads):
                        finite &= jnp.all(
                            jnp.isfinite(g.astype(jnp.float32)))
            # raw (pre-clip) global grad norm: clipping would cap exactly
            # the spikes the sentinel exists to catch
            sent_gnorm = (_sentinel.global_grad_norm(grads)
                          if sent_state is not None else None)
            # phase scopes (metadata only): monitor.trace.op_scopes
            # tells forward, backward and optimizer apart by these
            if self._clip is not None:
                with jax.named_scope("optimizer"), \
                        jax.named_scope("grad_clip"):
                    grads, _ = global_norm_clip(grads, self._clip)
            with jax.named_scope("optimizer"):
                new_params, new_opt = self._update_fn(
                    params, grads, opt_state, lr, **self._opt_kwargs)
            if scaler_state is not None:
                # gate the whole update on the finite flag (reference
                # check_finite_and_unscale semantics: a skipped step leaves
                # params and optimizer state untouched)
                pick = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(finite, a, b), new, old)
                new_params = pick(new_params, params)
                new_opt = pick(new_opt, opt_state)
                # update_loss_scaling_op counters
                d = self._dyn
                good = jnp.where(finite, scaler_state["good"] + 1, 0)
                bad = jnp.where(finite, 0, scaler_state["bad"] + 1)
                incr = good >= int(d.get("incr_every_n_steps", 1000))
                decr = bad >= int(d.get("decr_every_n", 2))
                new_scale = jnp.where(
                    incr, scale * float(d.get("incr_ratio", 2.0)), scale)
                new_scale = jnp.where(
                    decr,
                    jnp.maximum(scale * float(d.get("decr_ratio", 0.5)), 1.0),
                    new_scale)
                scaler_state = {"scale": new_scale,
                                "good": jnp.where(incr, 0, good),
                                "bad": jnp.where(decr, 0, bad)}
            if sent_state is not None:
                # in-jit health verdict (resilience.sentinel): finiteness
                # + EMA z-spike on the raw global grad norm, then the
                # GradScaler-style gate — a tripped step leaves params,
                # optimizer state and buffers untouched
                sent_state = _sentinel.update(sent_state, loss, sent_gnorm,
                                              self._sentinel_cfg)
                trip = sent_state["last_trip"]
                new_params = _sentinel.gate(trip, new_params, params)
                new_opt = _sentinel.gate(trip, new_opt, opt_state)
                if self._has_aux:
                    new_aux = _sentinel.gate(trip, new_aux, aux)
            return new_params, new_opt, new_aux, loss, scaler_state, \
                sent_state

        repl = NamedSharding(self.mesh, P())
        aux_sh = self._aux_sh if self._has_aux else None
        scaler_sh = ({"scale": repl, "good": repl, "bad": repl}
                     if self._dyn is not None else None)
        sent_sh = (jax.tree_util.tree_map(lambda _: repl,
                                          self.sentinel_state)
                   if self.sentinel_state is not None else None)
        self._step = jax.jit(
            step,
            in_shardings=(self._param_sh, self._opt_sh, aux_sh, batch_sh,
                          repl, scaler_sh, sent_sh),
            out_shardings=(self._param_sh, self._opt_sh, aux_sh, repl,
                           scaler_sh, sent_sh),
            donate_argnums=(0, 1, 2) if self._has_aux else (0, 1),
        )
        self._step_count = 0
        # batch aval signatures already compiled for: keeps the jit
        # cache-hit/compile gauges honest for the compiled-step path (a
        # shape-churning data loader shows up as a jit_compile storm here
        # exactly like an eager recompile storm does in grad_jit_compile)
        self._seen_batch_avals: set = set()
        # FLAGS_fast_step: device-cache the lr scalar between steps — a
        # fresh jnp.float32 per call is a host->device transfer per step
        # that the compiled program then waits on
        self._lr_cache = (None, None)
        # guardian lr_backoff multiplier (scale_lr); 1.0 = untouched
        self._lr_scale = 1.0
        # avals of the last batch stepped while tracing, until the
        # window's stop turns them into the op_scopes table
        self._traced_batch = None

    def current_lr(self) -> float:
        if callable(self._lr):
            return float(self._lr(self._step_count)) * self._lr_scale
        return float(self._lr) * self._lr_scale

    def scale_lr(self, scale: float) -> None:
        """Set the ABSOLUTE learning-rate multiplier (TrainGuardian's
        post-rollback backoff). The lr enters the compiled step as a
        traced scalar, so rescaling never recompiles; schedules keep
        their shape, scaled."""
        self._lr_scale = float(scale)

    def __call__(self, batch):
        if _faults.ENABLED[0]:
            # fault-injection hook (FLAGS_fault_inject): may corrupt the
            # batch (nan_grad), sleep (stall), raise (crash), or SIGTERM
            # ourselves (preempt); one list-index check when idle
            batch = _faults.FAULTS.on_train_step(self._step_count, batch)
        lrf = self.current_lr()
        if _fast_step[0]:
            if self._lr_cache[0] != lrf:
                self._lr_cache = (lrf, jnp.float32(lrf))
            lr = self._lr_cache[1]
        else:
            lr = jnp.float32(lrf)
        sig = tuple(
            (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", "?")))
            for x in jax.tree_util.tree_leaves(batch))
        if sig in self._seen_batch_avals:
            _mstats.JIT_CACHE_HIT.add()
        else:
            if _sanitize[0] and self._seen_batch_avals:
                # recompile explainer (FLAGS_sanitize): name the batch
                # leaf whose aval churned vs the nearest compiled sig
                _san.note_recompile(
                    "DistributedTrainStep", _san_batch_sig(sig),
                    [_san_batch_sig(s) for s in self._seen_batch_avals])
            self._seen_batch_avals.add(sig)
            _mstats.JIT_CACHE_MISS.add()
            _mstats.JIT_COMPILE.add()
        if _trace.TRACING[0] and self._traced_batch is None:
            self._traced_batch = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            _trace.on_stop(self._emit_op_scopes)
        donated = (self.params, self.opt_state,
                   self.aux if self._has_aux else None) \
            if _sanitize[0] else None
        with _trace_span("DistributedTrainStep.step", cat="step",
                         args={"step": self._step_count}):
            with self.mesh:
                (self.params, self.opt_state, self.aux, loss,
                 self.scaler_state, self.sentinel_state) = self._step(
                    self.params, self.opt_state, self.aux, batch, lr,
                    self.scaler_state, self.sentinel_state)
        if donated is not None:
            _san.tombstone_tree(donated)
        self._step_count += 1
        _mstats.TRAIN_STEPS.add()
        if _fast_step[0]:
            # async handle: params/opt-state stay device-resident and the
            # dispatch is not awaited; the first host read of the loss is
            # the sync point (step_async_syncs gauge)
            out = _AsyncLoss(loss)
            if self.sentinel_state is not None:
                out.health = {"trip": self.sentinel_state["last_trip"],
                              "trips": self.sentinel_state["trips"]}
            return out
        return loss

    def loss_scale(self) -> Optional[float]:
        """Current dynamic loss scale (None when scaling is off)."""
        if self.scaler_state is None:
            return None
        return float(self.scaler_state["scale"])

    def state_dict(self) -> dict:
        """Host snapshot {params, opt_state, step}. Sharded leaves
        (ZeRO m/v, ZeRO-3 params) GATHER on the host read, so the
        checkpoint layout is identical to an unsharded run's — sharding
        is placement, not content."""
        import numpy as np

        host = lambda tree: jax.tree_util.tree_map(
            lambda x: np.asarray(x), tree)
        return {"params": host(self.params),
                "opt_state": host(self.opt_state),
                "step": self._step_count}

    def set_state_dict(self, state: dict) -> None:
        """Restore a state_dict (this run's or an unsharded one's): full
        arrays are device_put back through the step's NamedShardings, so
        a ZeRO-sharded step resumes from any checkpoint and vice versa."""
        self.params = jax.device_put(state["params"], self._param_sh)
        self.opt_state = jax.device_put(state["opt_state"], self._opt_sh)
        self._step_count = int(state.get("step", self._step_count))

    def lower(self, batch):
        """Expose the lowered/compiled artifact (assert-on-HLO testing —
        the TPU analog of the reference's assert-on-op-list meta-optimizer
        tests, SURVEY.md §4.6). ``batch`` may be avals. Lowered under
        the mesh as ``__call__`` runs it, so this IS the step's program
        (and compiling it again finds jax's own copy, no compiler run)."""
        return self._lower(batch, jnp.float32(self.current_lr()))

    def _lower(self, batch, lr):
        with self.mesh:
            return self._step.lower(
                self.params, self.opt_state, self.aux, batch, lr,
                self.scaler_state, self.sentinel_state)

    def _emit_op_scopes(self, writer) -> None:
        """``on_stop`` callback: the compiled step's instruction ->
        phase/scope table as one metadata event. The caller has blocked
        on every step in flight before stopping, so the device is quiet
        while the step's executable is looked up again (jax keeps it:
        no compiler runs, no compile event fires)."""
        batch, self._traced_batch = self._traced_batch, None
        # an aval for lr too: nothing runs on the device from here
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        _trace.emit_op_scopes(writer, "jit_step",
                              self._lower(batch, lr).compile().as_text())

    def measure_overlap(self, batch, reps: int = 2) -> dict:
        """Comm-vs-compute overlap diagnostic (FLAGS_overlap_grads).

        Times three programs over the real mesh/batch: (a) the full
        loss+grads including the dp all-reduce, (b) backward COMPUTE
        only (shard_map local grads, no grad collective), (c) the grad
        all-reduce COMM alone over grad-shaped buffers. Overlap quality
        = how much of (c) hides inside (a):
        ``hidden_frac = clamp((compute + comm - step) / comm, 0, 1)``.
        Emits ``overlap.step`` / ``overlap.compute`` / ``overlap.comm``
        trace spans (tools/trace_report.py turns them into a verdict)
        and FLAGS_benchmark rows. Does NOT touch training state."""
        import time as _time

        axes = self._overlap_axes or tuple(
            a for a in ("data", "sharding")
            if mesh_shape(self.mesh).get(a, 1) > 0)
        loss_fn = self._loss_fn
        if self._has_aux:
            aux = self.aux
            loss_fn = lambda p, b: self._loss_fn(p, aux, b)[0]  # noqa: E731

        def full(p, b):
            return jax.grad(lambda pp: loss_fn(pp, b))(p)

        def compute_only(p, b):
            g = jax.grad(lambda pp: loss_fn(pp, b))(p)
            # cheap scalar reduce so nothing is all-gathered: the grad
            # collectives themselves are what (c) measures
            return jax.lax.pmean(
                sum(jnp.sum(jnp.abs(t.astype(jnp.float32)))
                    for t in jax.tree_util.tree_leaves(g)), axes)

        rs2 = getattr(self, "_overlap_zero2", False)
        deg = getattr(self, "_shard_deg", 1)
        data_axes = tuple(a for a in axes if a != "sharding")
        zleaves = getattr(self, "_zspec_leaves", None)

        def comm_only(g):
            if not rs2:
                return jax.tree_util.tree_map(
                    lambda t: jax.lax.pmean(t, axes), g)
            # the EXACT collectives the ZeRO-2 overlap backward issues:
            # reduce-scatter for sharded buckets, pmean for the rest;
            # reduced to a replicated scalar so shapes stay uniform
            flat, _ = jax.tree_util.tree_flatten(g)
            acc = jnp.float32(0.0)
            for x, s in zip(flat, zleaves):
                d = _spec_shard_dim(s)
                if d is None:
                    r = jax.lax.pmean(x, axes)
                else:
                    r = jax.lax.psum_scatter(
                        x, "sharding", scatter_dimension=d, tiled=True)
                    if data_axes:
                        r = jax.lax.pmean(r, data_axes)
                    r = r / deg
                acc += jnp.sum(jnp.abs(r.astype(jnp.float32)))
            return jax.lax.pmean(acc, axes)

        param_sh = self._param_sh
        full_j = jax.jit(full, in_shardings=(param_sh, self._batch_sh),
                         out_shardings=param_sh)
        comp_j = jax.jit(jax.shard_map(
            compute_only, mesh=self.mesh,
            in_specs=(P(), self._batch_spec), out_specs=P()))
        comm_j = jax.jit(jax.shard_map(
            comm_only, mesh=self.mesh, in_specs=(P(),), out_specs=P()))
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), self.params)

        from ..monitor import trace as _trace

        def timed(name, fn, *args):
            with self.mesh:
                jax.block_until_ready(fn(*args))          # compile+warm
                best = float("inf")
                for _ in range(max(1, reps)):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    best = min(best, _time.perf_counter() - t0)
            if _trace.is_tracing():
                # span duration == measured device time (not re-run)
                _trace.get_writer().add_complete(
                    "overlap.%s" % name, _time.perf_counter() - best,
                    best, cat="overlap", args={"ms": best * 1e3})
            if _bench.enabled():
                _bench.record_op("grad_overlap@%s" % name, best)
            return best * 1e3

        step_ms = timed("step", full_j, self.params, batch)
        compute_ms = timed("compute", comp_j, self.params, batch)
        comm_ms = timed("comm", comm_j, zeros)
        out = {"step_ms": step_ms, "compute_ms": compute_ms,
               "comm_ms": comm_ms, "buckets": len(
                   jax.tree_util.tree_leaves(self.params)),
               "overlap_enabled": self._overlap_axes is not None}
        if comm_ms > 0:
            out["hidden_frac"] = max(
                0.0, min(1.0, (compute_ms + comm_ms - step_ms) / comm_ms))
        return out
