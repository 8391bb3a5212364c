"""Mixture-of-Experts layer: top-k router + capacity dispatch (ISSUE 18).

GShard (Lepikhin et al., 2020) / Switch Transformer (Fedus et al., 2021)
sparse FFN, TPU-first. The routing math lives in pure functions so
models/gpt.py can call it layer-by-layer inside jit; :class:`MoELayer`
wraps them for the paddle-style eager surface.

Routing contract (:func:`moe_route`):
- softmax gating in fp32, top-k experts per token, gates renormalized
  over the chosen k;
- aux load-balancing loss ``E · Σ_e mean_prob_e · top1_frac_e`` (GShard
  eq. 4 — differentiable through mean_prob, pushes the router toward
  uniform load) and router z-loss ``mean(logsumexp(logits)²)`` (ST-MoE:
  keeps logits bounded);
- capacity-factor dispatch: expert ``e`` accepts the first
  ``C = ceil(cf · k · T / E)`` assignments in token order, rank-0
  before rank-1 (GShard's priority order). Overflow assignments are
  DROPPED — their gate contributes nothing and the residual connection
  passes the token through unchanged (the caller owns the residual).
  ``capacity_factor=None`` is DROPLESS (C = T): serving uses it so
  decode quality never depends on batch composition.

Dispatch executes in one of two numerically identical formulations:
- ``expert_axis=None`` (single shard): the fused Pallas permute kernel
  (ops/moe_dispatch.py) gathers routed rows straight into the (E·C, H)
  grid — O(E·C·H) moved bytes, no (T, E, C) one-hot;
- ``expert_axis="model"`` (expert parallelism): the one-hot einsum
  dispatch with a sharding constraint on the expert dim, which GSPMD
  lowers to the AllToAll the fleet.auto cost model prices.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops import moe_gmm
from ..ops.moe_dispatch import moe_combine_scatter, moe_dispatch_gather

__all__ = ["moe_route", "moe_ffn", "moe_capacity", "MoELayer",
           "moe_route_sigmoid", "moe_ffn_held"]


def moe_capacity(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: Optional[float]) -> int:
    """Per-expert capacity C. ``None`` = dropless (C = T: a token sends
    at most one assignment per expert, so T slots can never overflow)."""
    if capacity_factor is None:
        return max(1, int(n_tokens))
    return max(1, min(int(n_tokens),
                      int(math.ceil(float(capacity_factor) * top_k
                                    * n_tokens / n_experts))))


def moe_route(router_w, x, *, top_k: int,
              capacity_factor: Optional[float] = None):
    """Route tokens to experts. x (T, H); router_w (H, E).

    Returns ``(gates (T,k) f32, slots (T,k) i32, src (E·C,) i32,
    aux f32, z f32, counts (E,) i32, dropped i32)``:

    - ``slots[t, r]`` — the flat capacity slot ``e·C + c`` token t's
      rank-r assignment landed in, or −1 if dropped;
    - ``src[n]`` — the inverse permutation (token filling slot n, −1 =
      empty) for the gather kernel;
    - ``counts`` — tokens accepted per expert (the load gauge);
    - ``dropped`` — assignments past capacity (the drop counter).
    """
    T = x.shape[0]
    E = router_w.shape[-1]
    k = int(top_k)
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} outside [1, n_experts={E}]")
    C = moe_capacity(T, E, k, capacity_factor)

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    # top-k by iterated argmax, NOT jax.lax.top_k: the mhlo.topk custom
    # call fails to legalize under the GSPMD partitioner (the ep path
    # shards the token dim), and k is tiny; tie-breaking (lowest index
    # first) and descending order match top_k exactly
    vals, idxs, masked = [], [], probs
    for _ in range(k):
        i = jnp.argmax(masked, axis=-1)                            # (T,)
        vals.append(jnp.take_along_axis(probs, i[:, None], axis=-1)[:, 0])
        idxs.append(i)
        masked = masked - jax.nn.one_hot(i, E, dtype=masked.dtype) * 2.0
    gate_vals = jnp.stack(vals, axis=-1)                           # (T, k)
    gate_idx = jnp.stack(idxs, axis=-1).astype(jnp.int32)          # (T, k)
    gates = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # aux load balance: mean router prob × fraction of top-1 traffic,
    # summed over experts and scaled by E (uniform routing → aux = 1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32),
                  axis=0)
    aux = E * jnp.sum(me * ce)

    # capacity slots, rank-major priority: rank r claims positions after
    # every kept rank<r assignment; within a rank, token order (cumsum)
    counts = jnp.zeros((E,), jnp.int32)
    src = jnp.full((E * C,), -1, jnp.int32)
    tok = jnp.arange(T, dtype=jnp.int32)
    slots = []
    for r in range(k):
        idx = gate_idx[:, r]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.int32)             # (T, E)
        pos = counts[None, :] + jnp.cumsum(mask, axis=0) - mask
        pos_t = jnp.sum(pos * mask, axis=1)                        # (T,)
        kept = pos_t < C
        slot_r = jnp.where(kept, idx * C + pos_t, -1)
        # out-of-range writes (dropped assignments) fall off the end
        src = src.at[jnp.where(kept, slot_r, E * C)].set(
            tok, mode="drop")
        counts = counts + jnp.sum(mask * kept[:, None].astype(jnp.int32),
                                  axis=0)
        slots.append(slot_r)
    slots = jnp.stack(slots, axis=1)                               # (T, k)
    gates = jnp.where(slots >= 0, gates, 0.0)
    dropped = jnp.int32(T * k) - jnp.sum(counts)
    return gates, slots, src, aux, z, counts, dropped


def _expert_ffn(p, expert_in, cd):
    """Per-expert gelu MLP over the packed grid. expert_in (E, C, H)."""
    h = jax.nn.gelu(
        jnp.einsum("ech,ehm->ecm", expert_in, p["w_in"].astype(cd))
        + p["b_in"].astype(cd)[:, None, :])
    return (jnp.einsum("ecm,emh->ech", h, p["w_out"].astype(cd))
            + p["b_out"].astype(cd)[:, None, :])


def moe_ffn(p, x, *, top_k: int, capacity_factor: Optional[float] = None,
            expert_axis: Optional[str] = None, interpret=None):
    """The routed expert FFN. x (T, H) in compute dtype; ``p`` holds
    ``router_w (H, E)``, ``w_in (E, H, M)``, ``b_in (E, M)``,
    ``w_out (E, M, H)``, ``b_out (E, H)``.

    Returns ``(y (T, H), aux, z, counts (E,), dropped)`` — y is the
    expert mix ONLY (zero for fully dropped tokens); the caller adds the
    residual. ``expert_axis`` selects the einsum/AllToAll formulation
    with the expert dim constraint-pinned to that mesh axis; None takes
    the fused Pallas gather. Both formulations make identical routing
    decisions and agree to FMA-reassociation tolerance (parity-pinned
    in tests/test_moe.py; the gather kernel itself is bit-exact against
    its composed-jnp reference).
    """
    cd = x.dtype
    E = p["router_w"].shape[-1]
    gates, slots, src, aux, z, counts, dropped = moe_route(
        p["router_w"], x, top_k=top_k, capacity_factor=capacity_factor)
    C = src.shape[0] // E

    if expert_axis is not None:
        from ..parallel.sharding import constraint

        # one-hot dispatch/combine einsums: GSPMD turns the constraint
        # on the expert dim into the dispatch/return AllToAll pair.
        # The token dim must be co-sharded over the expert axis first —
        # the t-sharded → e-sharded reshard over the SAME axis is what
        # lowers to the AllToAll (a token dim left on "data" alone
        # lowers to plain partial-sum reduces instead); "data" stays in
        # the product so dp keeps its factor of the contraction.
        xs = constraint(x, ("data", expert_axis), None)
        oh = [jax.nn.one_hot(slots[:, r], E * C, dtype=cd)
              for r in range(top_k)]                         # -1 → zeros
        disp = oh[0]
        for o in oh[1:]:
            disp = disp + o
        expert_in = jnp.einsum("tn,th->nh", disp, xs).reshape(E, C, -1)
        expert_in = constraint(expert_in, expert_axis, None, None)
        out = _expert_ffn(p, expert_in, cd)
        out = constraint(out, expert_axis, None, None)
        comb = sum(o * gates[:, r:r + 1].astype(cd)
                   for r, o in enumerate(oh))
        y = jnp.einsum("tn,nh->th", comb, out.reshape(E * C, -1))
    else:
        expert_in = moe_dispatch_gather(x, src,
                                        interpret=interpret).reshape(E, C, -1)
        out = _expert_ffn(p, expert_in, cd)
        y = moe_combine_scatter(out.reshape(E * C, -1), slots, gates)
    return y, aux, z, counts, dropped


def moe_route_sigmoid(router_w, router_b, x, *, top_k: int, scale: float):
    """Sigmoid routing with a selection-only bias (the auxiliary-loss-free
    balancing of DeepSeek-V3, arXiv:2412.19437 eq. 12-16). x (T, H);
    router_w (H, E); router_b (E,).

    ``s = sigmoid(x W)`` in float32; the ``top_k`` largest of ``s + b``
    are chosen (ties to the lower index) and ``b`` takes no further
    part; ``g_e = scale * s_e / sum of the chosen s``. Returns
    ``(gates (T, k) f32, idx (T, k) i32)`` over ALL E experts, whichever
    of them are held here."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                  router_w.astype(jnp.float32),
                                  precision="highest"))
    _, idx = jax.lax.top_k(s + router_b.astype(jnp.float32), int(top_k))
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gates = float(scale) * chosen / jnp.maximum(
        jnp.sum(chosen, axis=-1, keepdims=True), 1e-20)
    return gates, idx.astype(jnp.int32)


def moe_ffn_held(w_gate, w_up, w_down, x, gates, idx, *, n_experts: int,
                 expert_offset: int, n_held: int, group_base=0, live=None,
                 out_dtype=None, interpret: bool = False):
    """The part of a routed gated-SiLU expert layer that the experts
    HELD here give: ``sum over chosen e in [expert_offset, expert_offset
    + n_held) of g_e E_e(x)``. What the other experts would add is left
    out (they live on other chips). With ``n_held = n_experts`` this is
    the whole routed layer.

    x (T, H); gates / idx (T, k) from a router over all ``n_experts``;
    w_gate / w_up (G, H, M) and w_down (G, M, H) with ``G >= n_held``:
    the held experts are groups ``[group_base, group_base + n_held)`` of
    the stack (several layers' experts in one array, so that a layer's
    weights are addressed in place and never sliced out; ``group_base``
    may be traced). ``live`` (T,) bool leaves tokens out (batch lanes
    that hold no request). ``out_dtype``: the type of ``y`` and of the
    down-projection's rows (x's if None).

    Dropless by construction: every assignment that falls on a held
    expert becomes one row; the rows are sorted by expert and multiplied
    group by group. On a TPU (shapes ``ops/moe_gmm.tileable`` takes) that
    is ``ops/moe_gmm``'s kernel, which reads each held expert with a row
    once a row tile, an expert with no row not at all, and adds each
    row's gated output into its token's row itself; anywhere else
    ``jax.lax.ragged_dot``, the rows put back in assignment order and
    summed by gate. ``interpret`` runs the kernel in the interpreter, on
    any backend. Returns ``(y (T, H),
    counts (n_experts,) i32 assignments per expert over live tokens,
    held i32 rows computed, reads i32 held experts with a row, tiles i32
    the kernel's row tiles, 0 where it did not run)``: ``tiles / reads``
    is how often a held expert's weights were read."""
    T, k = idx.shape
    cd = x.dtype
    G = w_gate.shape[0]
    lo, hi = int(expert_offset), int(expert_offset) + int(n_held)
    on = jnp.ones((T,), bool) if live is None else live
    held = (idx >= lo) & (idx < hi) & on[:, None]
    counts = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)
                     * on[:, None, None].astype(jnp.int32), axis=(0, 1))
    sizes = counts[lo:hi]
    n_rows = jnp.sum(sizes)
    reads = jnp.sum((sizes > 0).astype(jnp.int32))
    # held assignments first, by expert; the rest (key n_held) after
    key = jnp.where(held, idx - lo, n_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    od = cd if out_dtype is None else out_dtype
    w = jnp.where(held, gates, 0.0)
    if moe_gmm.use_kernel(x, w_gate, w_down, k, n_experts, interpret):
        tm = moe_gmm.row_tile(T * k, n_experts)
        walk = moe_gmm.expert_tiles(sizes, tm,
                                    moe_gmm.n_tiles(T * k, n_held, tm))
        y = moe_gmm.grouped_ffn(
            x, w_gate, w_up, w_down, (order // k).astype(jnp.int32),
            w.reshape(-1)[order], walk, group_base, tm=tm,
            interpret=interpret).astype(od)
        return y, counts, n_rows, reads, walk.count[0]
    rows = x[order // k]                                       # (T*k, H)
    group_sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((G,), jnp.int32), sizes, (group_base,))
    valid = (jnp.arange(T * k) < n_rows)[:, None]
    g = jax.lax.ragged_dot(rows, w_gate.astype(cd), group_sizes)
    u = jax.lax.ragged_dot(rows, w_up.astype(cd), group_sizes)
    # rows past the last group are not the kernel's to define
    h = jnp.where(valid, jax.nn.silu(g) * u, 0).astype(cd)
    out = jnp.where(valid, jax.lax.ragged_dot(
        h, w_down.astype(cd), group_sizes, preferred_element_type=od), 0)
    out = out[jnp.argsort(order)].reshape(T, k, -1)            # unsorted
    y = jnp.einsum("tkh,tk->th", out, w.astype(od),
                   preferred_element_type=jnp.float32).astype(od)
    return y, counts, n_rows, reads, jnp.int32(0)


class MoELayer:
    """Eager-surface MoE FFN (paddle ``incubate.distributed.models.moe``
    parity shape): ``y = MoELayer(...)(x)`` with the residual OUTSIDE.

    Thin stateful wrapper over :func:`moe_ffn`; after each call the
    router diagnostics are on ``aux_loss`` / ``z_loss`` /
    ``expert_counts`` / ``tokens_dropped``. Parameters live in
    ``.params`` as a plain pytree so the functional training loops can
    grad through it.
    """

    def __init__(self, hidden: int, mlp_hidden: int, n_experts: int,
                 top_k: int = 2, capacity_factor: Optional[float] = 1.25,
                 expert_axis: Optional[str] = None, seed: int = 0,
                 param_dtype=jnp.float32):
        if n_experts < 1:
            raise ValueError(f"n_experts={n_experts} must be >= 1")
        if not 1 <= top_k <= n_experts:
            raise ValueError(
                f"top_k={top_k} outside [1, n_experts={n_experts}]")
        self.hidden, self.mlp_hidden = int(hidden), int(mlp_hidden)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.capacity_factor = capacity_factor
        self.expert_axis = expert_axis
        ks = jax.random.split(jax.random.key(seed), 3)
        std = 0.02
        H, M, Ex = self.hidden, self.mlp_hidden, self.n_experts
        self.params = {
            "router_w": (std * jax.random.normal(ks[0], (H, Ex))
                         ).astype(param_dtype),
            "w_in": (std * jax.random.normal(ks[1], (Ex, H, M))
                     ).astype(param_dtype),
            "b_in": jnp.zeros((Ex, M), param_dtype),
            "w_out": (std * jax.random.normal(ks[2], (Ex, M, H))
                      ).astype(param_dtype),
            "b_out": jnp.zeros((Ex, H), param_dtype),
        }
        self.aux_loss = None
        self.z_loss = None
        self.expert_counts = None
        self.tokens_dropped = None

    def __call__(self, x):
        """x (..., H) → expert mix (..., H) (add your own residual)."""
        lead = x.shape[:-1]
        y, aux, z, counts, dropped = moe_ffn(
            self.params, x.reshape(-1, self.hidden), top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            expert_axis=self.expert_axis)
        self.aux_loss, self.z_loss = aux, z
        self.expert_counts, self.tokens_dropped = counts, dropped
        return y.reshape(*lead, self.hidden)
