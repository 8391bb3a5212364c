"""Latent-attention mixture-of-experts decoder (functional, serving).

The block of DeepSeek-V2/V3 (arXiv:2405.04434, 2412.19437) as the
``sarvam_mla`` checkpoints configure it: RMSNorm, multi-head latent
attention with an uncompressed query and a decoupled rotary key (YaRN),
a leading dense SiLU-gated MLP layer and then expert layers (sigmoid
router with a selection-only bias, top-k of E routed experts with
normalised, scaled gates, one shared expert), untied output head.

With ``x`` the residual stream, ``N`` RMSNorm with a learned scale:

    h = x + Attn(N1(x));  y = h + FFN(N2(h));  logits = head(Nf(y))

    q_i = W_q^i u = [q_i^nope (dn); q_i^rope (dr)]     (every head i)
    [c_raw (R); k_raw^rope (dr)] = W_kva u
    c = N_kv(c_raw);  k^rope = RoPE_t(k_raw^rope);  q_i^rope = RoPE_t(.)
    [k_i^nope (dn); v_i (dv)] = W_kvb^i c
    s_i(t, j) = (q_i^nope(t).k_i^nope(j) + q_i^rope(t).k^rope(j)) * scale
    scale = (dn + dr)^-1/2 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1

**The cache holds [c; k^rope], R + dr values a token a layer, after the
norm and after the rotation** (``serving/kv_cache.py``: the model says
what its pool is). The decode step is ABSORBED: ``q~_i = (W_kvb^{i,K})^T
q_i^nope`` meets the cached latent directly (``ops/mla_attention.py``)
and the value up-projection is applied to the attention-weighted
latent. The chunk step EXPANDS the context's latent rows to per-head
keys and values (fewer operations at a 512-token chunk: 2 R nh (dn + dv)
once a context row against 2 nh (R - dn + R - dv) a query-row pair).

Expert layers may hold a SHARE of the experts (``experts_held`` of
``n_experts`` from ``expert_offset``): the router scores and chooses
over all of them, this chip computes its own experts' part
(``nn/moe.py: moe_ffn_held``), and what the absent experts would add is
left out: one chip's part of an expert-parallel deployment, run without
its exchange. The shared expert is computed whole.

Departures are listed where they are made: the rotary pairing (i, i +
dr/2) (the checkpoints' interleaved pairs are a fixed permutation of
W_q's and W_kva's rotary rows), and a cached row padded from R + dr to a
multiple of 128 lanes (``pool_row``). Weights, matmul operands and the
cache are ``cfg.dtype`` (bf16); the residual stream, the norms, the
router's scores and the softmaxes are float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.moe import moe_ffn_held, moe_route_sigmoid
from ..ops.flash_attention import NEG_INF
from ..ops.mla_attention import (decode_walk, gather_rows,
                                 mla_decode_arrays)
from ..ops.pool_write import live_lanes, pool_put, pool_write_rows
from .serving_api import ServingModel

__all__ = ["MLAConfig", "sarvam_105b", "mla_tiny", "mla_init",
           "mla_forward", "mla_prefill_chunk", "mla_decode_step_paged",
           "mla_param_specs", "yarn_inv_freq", "yarn_mscale"]


@dataclasses.dataclass
class MLAConfig:
    vocab_size: int = 262144
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 64
    seq_len: int = 131072            # the engine's cap a slot
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    dense_ffn: int = 16384           # the leading dense layers' width
    first_dense: int = 1             # how many layers are dense
    expert_ffn: int = 2048
    n_experts: int = 128             # the router's width
    experts_held: int = 128          # of which live here, from
    expert_offset: int = 0           # this one on
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 2.5
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0        # YaRN (``deepseek_yarn``)
    rope_orig_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: Any = jnp.bfloat16        # compute and cache
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 < self.first_dense < self.n_layers:
            raise ValueError(f"first_dense={self.first_dense} must leave "
                             f"expert layers in n_layers={self.n_layers}")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among {self.n_experts}")

    @property
    def cache_row(self):
        """Values a token a layer that the algorithm caches."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def pool_row(self):
        """Width of a cached row as stored: ``cache_row`` padded to whole
        128-lane tiles. A TPU array whose minor dimension is not a
        multiple of 128 gets another dimension as its minor one by
        default, and every kernel call would re-lay the pool out."""
        return -(-self.cache_row // 128) * 128

    @property
    def softmax_scale(self):
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5 * m * m

    @property
    def n_moe_layers(self):
        return self.n_layers - self.first_dense

    def serving_model(self):
        return _SERVING


def sarvam_105b(**kw):
    """``sarvamai/sarvam-105b`` (``model_type`` ``sarvam_mla``) at its
    published sizes, which are the defaults; the cut to a chip's share
    comes as arguments (``n_layers``, ``experts_held``,
    ``expert_offset``, ``vocab_size``, ``seq_len``, the two dtypes)."""
    return MLAConfig(**kw)


def mla_tiny(**kw):
    """A toy of the same block for CPU tests."""
    base = dict(vocab_size=256, hidden=64, n_layers=3, n_heads=4,
                seq_len=128, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                kv_lora_rank=32, dense_ffn=128, expert_ffn=32,
                n_experts=8, experts_held=8, top_k=2, rope_orig_len=32,
                dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return MLAConfig(**base)


# -- rotary positions (YaRN) -------------------------------------------------

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, factor, orig_len, beta_fast, beta_slow):
    """YaRN-corrected inverse frequencies (dim / 2,), float64 numpy: the
    plain ones where a dimension turns more than ``beta_fast`` times over
    ``orig_len`` positions, divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, a linear ramp between."""
    def turns_dim(n_rot):
        return dim * math.log(orig_len / (n_rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rope_tables(cfg: MLAConfig, positions):
    """cos, sin (..., dr / 2) float32 for int positions (...)."""
    inv = jnp.asarray(yarn_inv_freq(
        cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor, cfg.rope_orig_len,
        cfg.rope_beta_fast, cfg.rope_beta_slow), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rope(x, cos, sin):
    """Rotate pairs (i, i + dr/2) of the last axis; cos/sin broadcast."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# -- parameters ---------------------------------------------------------------

def _attn_shapes(cfg: MLAConfig):
    H, nh = cfg.hidden, cfg.n_heads
    return {
        "ln1": (H,), "ln2": (H,), "kv_norm": (cfg.kv_lora_rank,),
        "wq": (H, nh * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
        "wkva": (H, cfg.cache_row),
        "wkvb": (cfg.kv_lora_rank, nh * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": (nh * cfg.v_head_dim, H),
    }


def mla_param_shapes(cfg: MLAConfig) -> Dict[str, Any]:
    """The parameter tree's shapes. ``dense`` and ``moe`` stack their
    layers on a leading axis; an expert layer's routed weights are
    ``(layers, experts_held, ...)``."""
    H, V = cfg.hidden, cfg.vocab_size
    Ld, Lm = cfg.first_dense, cfg.n_moe_layers
    Eh, M, Ms = cfg.experts_held, cfg.expert_ffn, \
        cfg.expert_ffn * cfg.n_shared
    dense = {k: (Ld,) + s for k, s in _attn_shapes(cfg).items()}
    dense.update(w_gate=(Ld, H, cfg.dense_ffn), w_up=(Ld, H, cfg.dense_ffn),
                 w_down=(Ld, cfg.dense_ffn, H))
    moe = {k: (Lm,) + s for k, s in _attn_shapes(cfg).items()}
    moe.update(router_w=(Lm, H, cfg.n_experts), router_b=(Lm, cfg.n_experts),
               w_gate=(Lm, Eh, H, M), w_up=(Lm, Eh, H, M),
               w_down=(Lm, Eh, M, H),
               s_gate=(Lm, H, Ms), s_up=(Lm, H, Ms), s_down=(Lm, Ms, H))
    return {"wte": (V, H), "head": (H, V), "lnf": (H,),
            "dense": dense, "moe": moe}


_NORMS = ("ln1", "ln2", "kv_norm", "lnf")
_RESIDUAL = ("wo", "w_down", "s_down")


def mla_init(cfg: MLAConfig, seed: int = 0, std: float = 0.02,
             bias_std: float = 0.02):
    """Seeded weights: normal(0, std), projections back into the
    residual stream scaled by 1 / sqrt(2 L), norm scales 1, the router's
    selection bias normal(0, bias_std)."""
    shapes = mla_param_shapes(cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        if name in _NORMS:
            v = jnp.ones(shape, jnp.float32)
        else:
            scale = bias_std if name == "router_b" else std
            if name in _RESIDUAL:
                scale = std / math.sqrt(2 * cfg.n_layers)
            v = scale * jax.random.normal(k, shape, jnp.float32)
        out.append(v.astype(cfg.param_dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def mla_param_specs(cfg: MLAConfig):
    """Every leaf replicated: this model runs on one chip (the engine
    refuses ``mesh=``)."""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), mla_param_shapes(cfg),
                                  is_leaf=lambda s: isinstance(s, tuple))


# -- the block's halves -------------------------------------------------------

@jax.named_scope("ln")
def _rms(x, scale, eps, out=None):
    """RMSNorm in float32; the result in ``out`` (``x``'s type if None)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(out or x.dtype)


def _into_residual(a, w):
    """A projection back into the residual stream, which is float32: the
    product's float32 accumulator is added as it is. The stream's own
    rounding, a layer after a layer, is what moves a router's near ties
    (PERF.md section 6, PR 30)."""
    return jnp.matmul(a, w.astype(a.dtype),
                      preferred_element_type=jnp.float32)


def _project(cfg: MLAConfig, p, u, positions):
    """u (T, H) normed; positions (T,). -> q_nope (T, nh, dn), q_rope
    (T, nh, dr) rotated, row (T, R + dr) = [N_kv(c_raw); RoPE(k^rope)]."""
    cd, nh, R = cfg.dtype, cfg.n_heads, cfg.kv_lora_rank
    T = u.shape[0]
    q = (u @ p["wq"].astype(cd)).reshape(T, nh, -1)
    kva = u @ p["wkva"].astype(cd)
    cos, sin = _rope_tables(cfg, positions)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = _rope(q_rope, cos[:, None], sin[:, None])
    c = _rms(kva[:, :R], p["kv_norm"], cfg.rms_eps)
    row = jnp.concatenate([c, _rope(kva[:, R:], cos, sin)], axis=-1)
    return q_nope, q_rope, row


def _wkvb(cfg: MLAConfig, p):
    """W_kvb as (R, nh, dn + dv): [..., :dn] makes keys, the rest values."""
    return p["wkvb"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, -1)


_CONTEXT_TILE = 4096     # rows of context a softmax is taken over at once
_HEAD_GROUP = 4          # heads whose scores exist at once


def _expanded_attention(cfg: MLAConfig, q_nope, q_rope, rows, wkvb, live,
                        head_group=_HEAD_GROUP):
    """q_* (C, nh, .); rows (K, >= R + dr) cached rows; live (C, K) bool.
    Keys and values are made from the latent, ``head_group`` heads at a
    time and ``_CONTEXT_TILE`` rows of context at a time (tiles joined by
    the online softmax), so that no (nh, C, K) score tensor exists: on
    the chip a group of 4 heads over 4,096 rows runs at 57% of the
    matmul peak, and 8 heads or 8,192 rows at once fall off a cliff
    (PERF.md section 6, PR 30). -> (C, nh * dv)."""
    cd, R, dn = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_dim
    C, nh = q_nope.shape[:2]
    K = rows.shape[0]
    hg = math.gcd(nh, head_group)
    tile = _CONTEXT_TILE if K % _CONTEXT_TILE == 0 else K
    tiled = lambda t: t.reshape((K // tile, tile) + t.shape[1:])  # noqa: E731
    context = (tiled(rows[:, :R].astype(cd)),
               tiled(rows[:, R:cfg.cache_row].astype(cd)),
               jnp.moveaxis(live.reshape(C, K // tile, tile), 1, 0))

    def group(args):
        qn, qr, w = args            # (hg, C, dn), (hg, C, dr), (hg, R, .)

        def scores(c, kr, lv):
            kv = jnp.einsum("kr,hrd->hkd", c, w)
            s = (jnp.einsum("hqd,hkd->hqk", qn, kv[..., :dn],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("hqd,kd->hqk", qr, kr,
                              preferred_element_type=jnp.float32))
            return jnp.where(lv[None], s * cfg.softmax_scale, NEG_INF), \
                kv[..., dn:]

        if K == tile:
            s, v = scores(*(t[0] for t in context))
            return jnp.einsum("hqk,hkd->hqd",
                              jax.nn.softmax(s, axis=-1).astype(cd), v)

        def step(carry, tile_of):
            m, l, acc = carry
            s, v = scores(*tile_of)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
            acc = acc * alpha + jnp.einsum(
                "hqk,hkd->hqd", p.astype(cd), v,
                preferred_element_type=jnp.float32)
            return (m_new, alpha * l + jnp.sum(p, -1, keepdims=True),
                    acc), None

        first = (jnp.full((hg, C, 1), NEG_INF, jnp.float32),
                 jnp.zeros((hg, C, 1), jnp.float32),
                 jnp.zeros((hg, C, cfg.v_head_dim), jnp.float32))
        (_, l, acc), _ = jax.lax.scan(step, first, context)
        return (acc / l).astype(cd)    # row 0 of the context is live

    heads = lambda t, ax: jnp.moveaxis(t, ax, 0).reshape(    # noqa: E731
        (nh // hg, hg) + t.shape[:ax] + t.shape[ax + 1:])
    o = jax.lax.map(group, (heads(q_nope, 1), heads(q_rope, 1),
                            heads(wkvb, 1)))                # (ng, hg, C, dv)
    return jnp.moveaxis(o.reshape(nh, C, -1), 0, 1).reshape(C, -1)


@jax.named_scope("mlp")
def _gated_mlp(cd, z, w_gate, w_up, w_down):
    return _into_residual(
        jax.nn.silu(z @ w_gate.astype(cd)) * (z @ w_up.astype(cd)), w_down)


def _dense_ffn(cfg: MLAConfig, p, x):
    z = _rms(x, p["ln2"], cfg.rms_eps, cfg.dtype)
    return x + _gated_mlp(cfg.dtype, z, p["w_gate"], p["w_up"], p["w_down"])


def _expert_weights(params):
    """The routed experts of every expert layer as one stack (layers *
    held, ...): a free reshape; a layer is addressed in it by group."""
    m = params["moe"]
    flat = lambda w: w.reshape((-1,) + w.shape[2:])         # noqa: E731
    return flat(m["w_gate"]), flat(m["w_up"]), flat(m["w_down"])


def _moe_ffn(cfg: MLAConfig, p, experts, mi, x, live=None):
    """x (T, H) -> (x + routed part held here + shared expert, stats).
    ``mi``: which expert layer (traced), ``experts``: the stack."""
    z32 = _rms(x, p["ln2"], cfg.rms_eps, jnp.float32)
    z = z32.astype(cfg.dtype)
    with jax.named_scope("router"):      # scores from the unrounded input
        gates, idx = moe_route_sigmoid(
            p["router_w"], p["router_b"], z32, top_k=cfg.top_k,
            scale=cfg.routed_scale)
    with jax.named_scope("experts"):
        y, *stats = moe_ffn_held(
            *experts, z, gates, idx, n_experts=cfg.n_experts,
            expert_offset=cfg.expert_offset, n_held=cfg.experts_held,
            group_base=mi * cfg.experts_held, live=live,
            out_dtype=jnp.float32)
    shared = _gated_mlp(cfg.dtype, z, p["s_gate"], p["s_up"], p["s_down"])
    return x + y + shared, tuple(stats)


def _no_stats(cfg: MLAConfig):
    return ((jnp.zeros((cfg.n_experts,), jnp.int32),)
            + (jnp.int32(0),) * 3)


def _add_stats(a, b):
    return tuple(x + y for x, y in zip(a, b))


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


_ATTN = ("ln1", "ln2", "kv_norm", "wq", "wkva", "wkvb", "wo")
_SCANNED = _ATTN + ("router_w", "router_b", "s_gate", "s_up", "s_down")


def _run_layers(cfg: MLAConfig, params, x, carry, attn, live=None):
    """Every layer over ``x``: ``attn(p, x, carry, li) -> (x, carry)`` is
    the attention half (``carry``: the pool, or nothing); the dense
    layers are unrolled, the expert layers scanned. -> (x, carry, stats).
    """
    for i in range(cfg.first_dense):
        p = _layer(params["dense"], i)
        x, carry = attn(p, x, carry, i)
        x = _dense_ffn(cfg, p, x)
    experts = _expert_weights(params)

    def step(c, inp):
        x, carry, stats = c
        p, mi = inp
        x, carry = attn(p, x, carry, cfg.first_dense + mi)
        x, st = _moe_ffn(cfg, p, experts, mi, x, live)
        return (x, carry, _add_stats(stats, st)), None

    scanned = {k: params["moe"][k] for k in _SCANNED}
    (x, carry, stats), _ = jax.lax.scan(
        step, (x, carry, _no_stats(cfg)),
        (scanned, jnp.arange(cfg.n_moe_layers)))
    return x, carry, stats


@jax.named_scope("head")
def _head(cfg: MLAConfig, params, x):
    x = _rms(x, params["lnf"], cfg.rms_eps, cfg.dtype)
    return jnp.matmul(x, params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


@jax.named_scope("embed")
def _embed(cfg: MLAConfig, params, tokens):
    """The residual stream starts, and stays, float32."""
    return params["wte"][tokens].astype(jnp.float32)


# -- full-sequence forward ----------------------------------------------------

def mla_forward(cfg: MLAConfig, params, tokens):
    """tokens (B, S) int32 -> (logits (B, S, V) f32, (expert counts,
    held rows, expert reads, kernel row tiles)). Expanded causal
    attention, no cache."""
    B, S = tokens.shape
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]

    @jax.named_scope("attn")
    def attn(p, x, carry, li):
        u = _rms(x, p["ln1"], cfg.rms_eps, cfg.dtype)

        def one(u_b):
            q_nope, q_rope, row = _project(cfg, p, u_b, pos)
            return _expanded_attention(cfg, q_nope, q_rope, row,
                                       _wkvb(cfg, p), causal)

        o = jax.vmap(one)(u.reshape(B, S, -1)).reshape(B * S, -1)
        return x + _into_residual(o, p["wo"]), carry

    x = _embed(cfg, params, tokens).reshape(B * S, -1)
    x, _, stats = _run_layers(cfg, params, x, None, attn)
    return _head(cfg, params, x).reshape(B, S, -1), stats


# -- the paged latent pool ----------------------------------------------------

def mla_pool_spec(cfg: MLAConfig, n_blocks: int, block_size: int):
    """One latent array: a block holds ``block_size`` rows of every
    layer; a row is [c (R); k^rope (dr); padding to ``pool_row``]."""
    return (jax.ShapeDtypeStruct(
        (n_blocks, cfg.n_layers, block_size, cfg.pool_row), cfg.dtype),)


def _pool_row(cfg: MLAConfig, row):
    pad = cfg.pool_row - cfg.cache_row
    return jnp.pad(row, ((0, 0), (0, pad))) if pad else row


def mla_prefill_chunk(cfg: MLAConfig, params, pool, table_row, tokens,
                      start, n_true=None):
    """One chunk of a paged, chunked prefill (the contract of
    ``gpt_prefill_chunk``): tokens (1, C) end-padded to whole blocks
    (``n_true`` of them real: unused, a padded row is never read),
    ``start`` block-aligned, table_row (W,) covering ``start + C``.
    Writes the chunk's rows into the pool, then attends over every
    cached row. -> (logits (1, C, V) f32, pool, router stats)."""
    (lat,) = pool
    C = tokens.shape[1]
    bs = lat.shape[2]
    W = table_row.shape[0]
    pos = start + jnp.arange(C)
    live = jnp.arange(W * bs)[None, :] <= pos[:, None]

    @jax.named_scope("attn")
    def attn(p, x, lat, li):
        u = _rms(x, p["ln1"], cfg.rms_eps, cfg.dtype)
        q_nope, q_rope, row = _project(cfg, p, u, pos)
        with jax.named_scope("kv_pool"):
            row = _pool_row(cfg, row)
            bids = jnp.take(table_row, start // bs + jnp.arange(C // bs))
            for j in range(C // bs):
                lat = pool_put(lat, row[None, None, j * bs:(j + 1) * bs],
                               (bids[j], li, 0, 0))
            rows = gather_rows(lat, table_row, li)
        o = _expanded_attention(cfg, q_nope, q_rope, rows, _wkvb(cfg, p),
                                live)
        return x + _into_residual(o, p["wo"]), lat

    x = _embed(cfg, params, tokens[0])
    x, lat, stats = _run_layers(cfg, params, x, lat, attn)
    return _head(cfg, params, x)[None], (lat,), stats


def mla_decode_step_paged(cfg: MLAConfig, params, pool, tables, positions,
                          tokens):
    """Batched one-token decode against the paged latent pool (the
    contract of ``gpt_decode_step_paged``): tables (B, W), positions and
    tokens (B,). A lane whose table is all sink (block 0) holds no
    request: it is left out of the expert layers, so it reads no expert.
    -> (logits (B, V) f32, pool, router stats)."""
    (lat,) = pool
    B = tokens.shape[0]
    bs = lat.shape[2]
    dn = cfg.qk_nope_dim
    blk = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
    off = positions % bs
    live = tables[:, 0] > 0
    # such a lane has length 0: no step of either kernel, so zeros and
    # no row written. The lists of live blocks and of live lanes are the
    # same at every layer
    lengths = jnp.where(live, positions + 1, 0)
    walk = decode_walk(lengths, tables.shape[1], bs)
    lanes = live_lanes(lengths)

    @jax.named_scope("attn")
    def attn(p, x, lat, li):
        u = _rms(x, p["ln1"], cfg.rms_eps, cfg.dtype)
        q_nope, q_rope, row = _project(cfg, p, u, positions)
        with jax.named_scope("kv_pool"):
            (lat,) = pool_write_rows((lat,), (_pool_row(cfg, row),), blk,
                                     off, li, lanes=lanes)
        w = _wkvb(cfg, p)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w[..., :dn])
        o_lat = mla_decode_arrays(q_lat, q_rope, lat, tables, lengths,
                                  cfg.softmax_scale, li, walk=walk)
        o = jnp.einsum("bhr,rhd->bhd", o_lat, w[..., dn:]).reshape(B, -1)
        return x + _into_residual(o, p["wo"]), lat

    x = _embed(cfg, params, tokens)
    x, lat, stats = _run_layers(cfg, params, x, lat, attn, live=live)
    return _head(cfg, params, x), (lat,), stats


_CANNOT = ("latent-attention models (MLAConfig) cannot {what} yet: {why}")
_SERVING = ServingModel(
    name="mla",
    pool_spec=mla_pool_spec,
    param_specs=mla_param_specs,
    prefill_chunk=mla_prefill_chunk,
    decode_step_paged=mla_decode_step_paged,
    routed=True,
    refuses={
        "draft": _CANNOT.format(
            what="take draft=",
            why="there is no latent verify step for speculative decoding"),
        "prefix_cache": _CANNOT.format(
            what="use prefix_cache",
            why="prefix reuse continues from an unaligned length through a "
                "verify step this model lacks"),
        "int8_weights": _CANNOT.format(
            what="take int8_weights",
            why="there is no quantized layout for its projections and "
                "experts"),
        "mesh": _CANNOT.format(
            what="take mesh=",
            why="its expert layer runs one chip's share without the "
                "exchange between chips, and it has no sharded parameter "
                "specs"),
    })
