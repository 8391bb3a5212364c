"""Power-retention decoder (functional, serving).

``manifestai/Brumby-14B-Base`` (``model_type`` ``brumby``): Qwen3-14B's
block with the softmax attention swapped for power retention of degree
2 (arXiv:2507.04239; ``ops/power_retention.py`` has the mechanism). With
``x`` the residual stream, ``N`` RMSNorm with a learned scale, 40 query
heads over 8 key/value heads of 128 (``g = h // 5``):

    n   = N1(x)
    q_h = RoPE_t(N_q(W_q n)_h) d^(-1/4)     k_g = RoPE_t(N_k(W_k n)_g) d^(-1/4)
    v_g = (W_v n)_g       lg = log sigmoid(W_g n) in R^8, float32, <= 0
    S_t = exp(lg_t) S_{t-1} + phi(k_t) [v_t; 1]^T      (a head's state)
    [num; den] = phi(q_t)^T S_t;   y_h = num / (den + eps)
    h = x + W_o [y_1; ...];   m = N2(h)
    out = h + W_down(silu(W_gate m) * W_up m)

**The cache is one fixed-size state a sequence**: every layer's and
every key/value head's ``S`` (with its normaliser as a row), float32,
``(n_layers, n_kv_heads, 128 + 8, 9216)``, whatever the context's
length. ``serving_model().state_pad`` says so to the cache manager
(``serving/kv_cache.py``): a block of the pool is a sequence's whole
state, a slot owns exactly one, and nothing grows.

What the published ``config.json`` does not name is listed in the
benchmark's configuration file under ``assumed`` and is the default
here: degree 2, the normalised output and its ``ret_eps``, one bias-free
gate a key/value head, Qwen3's per-head RMSNorm on queries and keys and
its rotary positions (pairs (i, i + 64), theta 1e6). Weights and matmul
operands are ``cfg.dtype`` (bf16); the residual stream, the norms, the
gates, their cumulative sums, the state and the read-out of a decode
step are float32. The norm, rotary, MLP, embedding and head code is
``models/mla.py``'s.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.power_retention import (phi_width, retention_chunk,
                                   retention_decode, state_rows)
from .mla import (_dense_ffn, _embed, _head, _into_residual, _rms, _rope)
from .serving_api import ServingModel

__all__ = ["RetentionConfig", "brumby_14b", "retention_tiny",
           "retention_init", "retention_forward", "retention_prefill_chunk",
           "retention_decode_step_paged", "retention_param_specs"]

STATE_PAD = 128      # tokens a prefill chunk is padded to a multiple of


@dataclasses.dataclass
class RetentionConfig:
    vocab_size: int = 151936
    hidden: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn: int = 17408
    seq_len: int = 32768             # the engine's cap a slot
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    ret_eps: float = 1e-6            # added to the read-out's normaliser
    dtype: Any = jnp.bfloat16        # weights' and matmul operands' type
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads={self.n_heads} is not a multiple of "
                             f"n_kv_heads={self.n_kv_heads}")

    @property
    def phi_dim(self):
        """Distinct products of a head's key: what the algorithm keeps
        a value column (the pool stores ``phi_width``)."""
        return self.head_dim * (self.head_dim + 1) // 2

    def serving_model(self):
        return _SERVING


def brumby_14b(**kw):
    """``manifestai/Brumby-14B-Base`` at its published sizes, which are
    the defaults; a cut comes as arguments (``n_layers``, the dtypes)."""
    return RetentionConfig(**kw)


def retention_tiny(**kw):
    """A toy of the same block for CPU tests: 4 query heads over 2
    key/value heads of 16 (136 distinct products a key)."""
    base = dict(vocab_size=256, hidden=64, n_layers=3, n_heads=4,
                n_kv_heads=2, head_dim=16, ffn=128, seq_len=256,
                rope_theta=10000.0, dtype=jnp.float32,
                param_dtype=jnp.float32)
    base.update(kw)
    return RetentionConfig(**base)


# -- parameters ---------------------------------------------------------------

def retention_param_shapes(cfg: RetentionConfig):
    """The parameter tree's shapes; ``layers`` stacks on a leading axis."""
    H, V, L, d = cfg.hidden, cfg.vocab_size, cfg.n_layers, cfg.head_dim
    nq, nkv = cfg.n_heads * d, cfg.n_kv_heads * d
    layers = {"ln1": (L, H), "ln2": (L, H), "q_norm": (L, d),
              "k_norm": (L, d), "wq": (L, H, nq), "wk": (L, H, nkv),
              "wv": (L, H, nkv), "wg": (L, H, cfg.n_kv_heads),
              "wo": (L, nq, H), "w_gate": (L, H, cfg.ffn),
              "w_up": (L, H, cfg.ffn), "w_down": (L, cfg.ffn, H)}
    return {"wte": (V, H), "head": (H, V), "lnf": (H,), "layers": layers}


_NORMS = ("ln1", "ln2", "q_norm", "k_norm", "lnf")
_RESIDUAL = ("wo", "w_down")


def retention_init(cfg: RetentionConfig, seed: int = 0, std: float = 0.02):
    """Seeded weights: normal(0, std), projections back into the
    residual stream scaled by 1 / sqrt(2 L), norm scales 1."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        retention_param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        if name in _NORMS:
            v = jnp.ones(shape, jnp.float32)
        else:
            scale = std / math.sqrt(2 * cfg.n_layers) \
                if name in _RESIDUAL else std
            v = scale * jax.random.normal(k, shape, jnp.float32)
        out.append(v.astype(cfg.param_dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def retention_param_specs(cfg: RetentionConfig):
    """Every leaf replicated: this model runs on one chip (the engine
    refuses ``mesh=``)."""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), retention_param_shapes(cfg),
                                  is_leaf=lambda s: isinstance(s, tuple))


# -- the layer ----------------------------------------------------------------

def _rope_tables(cfg: RetentionConfig, positions):
    """cos, sin (..., d / 2) float32 for int positions (...)."""
    d = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _project(cfg: RetentionConfig, p, n, cos, sin):
    """n (T, H) normed; cos/sin (T, d / 2). -> q (T, Hq, d) and k (T,
    Hkv, d), head-normed, rotated and scaled by d^(-1/4); v (T, Hkv,
    d); lg (T, Hkv) float32 log gates."""
    cd, d = cfg.dtype, cfg.head_dim
    T = n.shape[0]
    q = (n @ p["wq"].astype(cd)).reshape(T, cfg.n_heads, d)
    k = (n @ p["wk"].astype(cd)).reshape(T, cfg.n_kv_heads, d)
    v = (n @ p["wv"].astype(cd)).reshape(T, cfg.n_kv_heads, d)
    lg = jax.nn.log_sigmoid(jnp.matmul(
        n, p["wg"].astype(cd), preferred_element_type=jnp.float32))
    scale = d ** -0.25
    q = _rope(_rms(q, p["q_norm"], cfg.rms_eps, jnp.float32),
              cos[:, None], sin[:, None])
    k = _rope(_rms(k, p["k_norm"], cfg.rms_eps, jnp.float32),
              cos[:, None], sin[:, None])
    return (q * scale).astype(cd), (k * scale).astype(cd), v, lg


def _run_layers(cfg: RetentionConfig, params, x, state, mix):
    """Every layer over ``x``: ``mix(p, n, state, li) -> (y (T, Hq * d),
    state)`` projects the normed ``n`` with the layer's ``p`` and runs
    the retention against the state pool at layer ``li``; the layers are
    scanned, the pool carried. -> (x, state)."""
    def step(c, inp):
        x, state = c
        p, li = inp
        n = _rms(x, p["ln1"], cfg.rms_eps, cfg.dtype)
        with jax.named_scope("retention"):
            y, state = mix(p, n, state, li)
            y = _into_residual(y.astype(cfg.dtype), p["wo"])
        x = x + y
        return (_dense_ffn(cfg, p, x), state), None

    (x, state), _ = jax.lax.scan(
        step, (x, state), (params["layers"], jnp.arange(cfg.n_layers)))
    return x, state


# -- the state pool -----------------------------------------------------------

def retention_pool_spec(cfg: RetentionConfig, n_blocks: int, block_size: int):
    """One float32 array; a block is a SEQUENCE'S WHOLE STATE: every
    layer's and key/value head's ``(d + 8, phi_width(d))`` (the values'
    rows, the normaliser's, padding to the sublane tile). ``block_size``
    sizes nothing here."""
    return (jax.ShapeDtypeStruct(
        (n_blocks, cfg.n_layers, cfg.n_kv_heads, state_rows(cfg.head_dim),
         phi_width(cfg.head_dim)), jnp.float32),)


def retention_prefill_chunk(cfg: RetentionConfig, params, pool, table_row,
                            tokens, start, n_true):
    """One chunk of a chunked prefill (the contract of
    ``gpt_prefill_chunk``): tokens (1, C) end-padded, of which the first
    ``n_true`` are real; ``table_row[0]`` names the sequence's state.
    The padding is not folded into the state, and ``start == 0`` begins
    from a zero state whatever the block held (a released slot's, or
    the one a preempted request left). -> (logits (1, C, V) f32, pool)."""
    (st,) = pool
    C = tokens.shape[1]
    cos, sin = _rope_tables(cfg, start + jnp.arange(C))
    block = table_row[0]

    def mix(p, n, st, li):
        q, k, v, lg = _project(cfg, p, n, cos, sin)
        y, st = retention_chunk(q, k, v, lg, st, block, li, start, n_true,
                                cfg.ret_eps)
        return y.reshape(C, -1), st

    x = _embed(cfg, params, tokens[0])
    x, st = _run_layers(cfg, params, x, st, mix)
    return _head(cfg, params, x)[None], (st,)


def retention_decode_step_paged(cfg: RetentionConfig, params, pool, tables,
                                positions, tokens):
    """Batched one-token decode against the lanes' states (the contract
    of ``gpt_decode_step_paged``): tables (B, 1), positions and tokens
    (B,). A lane whose table names the sink (block 0) holds no request,
    or one still in prefill: it moves no state. -> (logits (B, V) f32,
    pool)."""
    (st,) = pool
    B = tokens.shape[0]
    blocks = tables[:, 0]
    live = blocks > 0
    cos, sin = _rope_tables(cfg, positions)

    def mix(p, n, st, li):
        q, k, v, lg = _project(cfg, p, n, cos, sin)
        y, st = retention_decode(q, k, v, lg, st, blocks, live, li,
                                 cfg.ret_eps)
        return y.reshape(B, -1), st

    x = _embed(cfg, params, tokens)
    x, st = _run_layers(cfg, params, x, st, mix)
    return _head(cfg, params, x), (st,)


def retention_forward(cfg: RetentionConfig, params, tokens, chunk=None):
    """tokens (B, S) int32 -> logits (B, S, V) f32: each sequence through
    the chunked form, ``chunk`` tokens at a time (all at once if None),
    its state carried from chunk to chunk in a pool of its own."""
    B, S = tokens.shape
    chunk = S if chunk is None else int(chunk)
    row = jnp.ones((1,), jnp.int32)
    out = []
    for b in range(B):
        pool = tuple(jnp.zeros(a.shape, a.dtype)
                     for a in retention_pool_spec(cfg, 2, chunk))
        logits = []
        for at in range(0, S, chunk):
            n = min(chunk, S - at)
            toks = jnp.zeros((1, chunk), jnp.int32).at[0, :n].set(
                tokens[b, at:at + n])
            lg, pool = retention_prefill_chunk(
                cfg, params, pool, row, toks, jnp.int32(at), jnp.int32(n))
            logits.append(lg[0, :n])
        out.append(jnp.concatenate(logits, axis=0))
    return jnp.stack(out)


_CANNOT = ("power-retention models (RetentionConfig) cannot {what} yet: "
           "{why}")
_SERVING = ServingModel(
    name="retention",
    pool_spec=retention_pool_spec,
    param_specs=retention_param_specs,
    prefill_chunk=retention_prefill_chunk,
    decode_step_paged=retention_decode_step_paged,
    state_pad=STATE_PAD,
    refuses={
        "draft": _CANNOT.format(
            what="take draft=",
            why="a verify step folds every proposed token into the "
                "sequence's one state, and there is no roll-back of the "
                "state to the last accepted token"),
        "prefix_cache": _CANNOT.format(
            what="use prefix_cache",
            why="a prefix is reusable only as a snapshot of the state at "
                "its last token, and no snapshots are kept at chunk "
                "boundaries: the pool holds one state a sequence"),
        "int8_weights": _CANNOT.format(
            what="take int8_weights",
            why="there is no quantized layout for its projections"),
        "mesh": _CANNOT.format(
            what="take mesh=",
            why="its state pool and its kernels have no sharded layout, "
                "and it has no sharded parameter specs"),
    })
