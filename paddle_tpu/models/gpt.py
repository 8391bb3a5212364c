"""GPT-family decoder LM, TPU-first.

Capability parity with the reference's Fleet GPT path (driver BASELINE
config 5: "GPT-3 1.3B Fleet hybrid-parallel mp×pp×dp") and its parallel
layers (reference fleet/meta_parallel/parallel_layers/mp_layers.py:30
VocabParallelEmbedding, :97 ColumnParallelLinear, :170 RowParallelLinear)
— but instead of hand-written collectives, the model is a pure function
over a param pytree plus a PartitionSpec table (:func:`gpt_param_specs`);
GSPMD derives the identity/allreduce pattern the reference codes by hand.

Design notes (TPU):
- blocks are STACKED (leading layer dim) and applied with lax.scan — one
  compiled block body regardless of depth; with pipeline stages the leading
  dim reshapes to (n_stages, layers_per_stage) and shards over "pipe"
  (paddle_tpu.parallel.pipeline).
- matmul dims padded to MXU-friendly multiples (vocab 50304 = 128·393).
- compute dtype bf16, params fp32 (master weights — reference AMP O2
  semantics, contrib/mixed_precision/fp16_utils.py), softmax/loss in fp32.
- attention uses the Pallas flash kernel on TPU (ops/flash_attention.py),
  jnp reference path elsewhere.
- remat (jax.checkpoint) per block — the reference's RecomputeOptimizer /
  recompute_interval (fleet/utils/recompute.py:63) as a one-flag rematerialisation.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import native as _native
from ..ops.flash_attention import NEG_INF, _attention_reference, _on_tpu
from ..ops.pool_write import (live_lanes, pool_put, pool_write_rows,
                              write_rows_composed)
from .serving_api import ServingModel

__all__ = ["GPTConfig", "gpt_init", "gpt_forward", "gpt_loss",
           "gpt_param_specs", "gpt_tiny", "gpt_small", "gpt_1p3b",
           "gpt_nano", "gpt_truncate", "bert_base_config", "gpt_prefill",
           "gpt_decode_step", "gpt_decode_step_paged", "gpt_prefill_chunk",
           "gpt_prefill_prefix", "gpt_verify_step", "gpt_verify_step_paged",
           "quantize_gpt_weights"]

# Module-local mirror of FLAGS_fp8_matmul (no core.native subscript in
# jit-reachable code); set_flags syncs it through the watcher list.
_fp8 = [bool(_native.fp8_matmul[0])]
_native.fp8_matmul_watchers.append(
    lambda v: _fp8.__setitem__(0, bool(v)))


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden: int = 768
    n_layers: int = 12
    n_heads: int = 12
    seq_len: int = 1024
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.float32   # master weights
    n_stages: int = 1                # pipeline depth (mesh "pipe")
    remat: bool = False
    use_flash: Optional[bool] = None  # None = auto (TPU only)
    # lax.scan unroll over the layer dim. None = FULL unroll: XLA then
    # fuses/pipelines across layer boundaries — measured on v5e (bf16,
    # remat on): BERT-base 234->242 sps, ERNIE-large 73->88 sps (+19%),
    # GPT-1.3B MFU 0.54->0.60. Costs compile time (~3x); 1 keeps the
    # rolled one-body scan (fastest compile, e.g. for tests).
    scan_unroll: Optional[int] = None
    # long-context: ring attention with the seq dim sharded over seq_axis
    # (context parallelism — new capability vs the reference, SURVEY.md §5)
    ring_attention: bool = False
    seq_axis: str = "sharding"
    # fused residual+LN+MLP block half (ops/fused_kernels.py Pallas
    # kernels with custom-VJP backward). None = follow
    # FLAGS_fused_kernels at trace time; off-TPU the fused entry runs the
    # identical composed math, so this is numerics-neutral on CPU.
    fused_mlp: Optional[bool] = None
    # fp8 (e4m3) MLP matmuls (ops/fp8_matmul.py kernel, amp/fp8.py
    # just-in-time per-tensor scaling, STE gradients). None = follow
    # FLAGS_fp8_matmul at trace time. NOT numerics-neutral (that is the
    # point); takes the unfused MLP path when both fp8 and fused are on.
    fp8: Optional[bool] = None
    # mixture of experts (ISSUE 18): moe_experts=E routes every
    # moe_every-th block's MLP through an E-expert top-k MoE (nn/moe.py)
    # — ~moe_every·E/(moe_every-1+E)x the MLP parameters at near-dense
    # step FLOPs. The default moe_experts=0 keeps the dense model
    # BIT-IDENTICAL: params, forward, loss and every serving path take
    # the exact pre-MoE code (pinned by tests/test_moe.py).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    # training dispatch capacity (C = ceil(cf·k·T/E), overflow dropped
    # with residual passthrough); inference paths are always DROPLESS
    # so decode quality never depends on batch composition
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2     # load-balance loss weight in gpt_loss
    moe_z_weight: float = 1e-3       # router z-loss weight in gpt_loss
    # mesh axis carrying expert parallelism (fleet.auto plans ep onto
    # "model"). Set → the one-hot einsum dispatch with the expert dim
    # constraint-pinned there (GSPMD lowers it to an AllToAll pair);
    # None → the fused Pallas permute kernel (ops/moe_dispatch.py).
    moe_axis: Optional[str] = None

    @property
    def head_dim(self):
        return self.hidden // self.n_heads

    @property
    def mlp_hidden(self):
        return self.hidden * self.mlp_ratio

    @property
    def moe_layer_ids(self):
        """Indices of MoE blocks: every moe_every-th layer (1-based), so
        moe_every=2 → layers 1, 3, 5, ...; moe_every=1 → all layers."""
        if self.moe_experts <= 0:
            return ()
        n = max(1, int(self.moe_every))
        return tuple(i for i in range(self.n_layers) if i % n == n - 1)

    def serving_model(self):
        """What the serving engine needs of this model
        (``models/serving_api.py``): today's two-array per-head pool and
        the ``gpt_*`` step functions."""
        return _SERVING


def gpt_tiny(**kw):
    d = dict(vocab_size=512, hidden=64, n_layers=4, n_heads=4, seq_len=64)
    d.update(kw)
    return GPTConfig(**d)


def gpt_small(**kw):
    d = dict(hidden=768, n_layers=12, n_heads=12, seq_len=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt_1p3b(**kw):
    # GPT-3 1.3B: the reference Fleet hybrid benchmark config
    d = dict(hidden=2048, n_layers=24, n_heads=16, seq_len=2048)
    d.update(kw)
    return GPTConfig(**d)


def gpt_nano(**kw):
    # draft-model scale for speculative decoding (ISSUE 10): small enough
    # that k draft steps cost less than the one target pass they save
    d = dict(vocab_size=512, hidden=64, n_layers=2, n_heads=4, seq_len=64)
    d.update(kw)
    return GPTConfig(**d)


def gpt_truncate(cfg: GPTConfig, params, n_layers: int):
    """Layer-truncated draft model: the first ``n_layers`` blocks of
    ``params`` with the embeddings/final-LN/tied head SHARED with the
    target. Returns ``(draft_cfg, draft_params)`` ready for
    ``serving.InferenceEngine(draft=...)``.

    Sharing wte/wpe/lnf keeps the truncated model's logits correlated
    with the target's without any extra training — the cheapest useful
    speculative-decoding draft (a separately trained gpt_nano-class
    model slots into the same contract). ``params`` must be the plain
    gpt_init layout (quantize AFTER truncation, not before)."""
    if not 1 <= n_layers <= cfg.n_layers:
        raise ValueError(
            f"n_layers={n_layers} outside [1, {cfg.n_layers}]")
    if cfg.moe_layer_ids:
        raise ValueError(
            "gpt_truncate does not support MoE configs: the dense-MLP "
            "and expert subtrees stack over different layer subsets, so "
            "a [:n_layers] slice has no single meaning")
    draft = dict(params)
    draft["blocks"] = {name: leaf[:n_layers]
                      for name, leaf in params["blocks"].items()}
    return dataclasses.replace(cfg, n_layers=n_layers), draft


def bert_base_config(**kw):
    # BERT-base shapes (the benchmark's `bert_base` configuration: an
    # encoder-sized LM)
    d = dict(vocab_size=30592, hidden=768, n_layers=12, n_heads=12,
             seq_len=512)
    d.update(kw)
    return GPTConfig(**d)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def gpt_init(cfg: GPTConfig, seed: int = 0) -> Dict[str, Any]:
    """Init a param pytree; block leaves carry a leading layer dim.

    With ``moe_experts=E``: the dense MLP leaves shrink to the non-MoE
    layer count and a ``params["moe"]`` subtree (leading MoE-layer dim)
    holds the router + expert weights — attention/LN leaves keep the
    full layer stack either way. ``moe_experts=0`` draws the exact
    pre-MoE tree bit-for-bit (the dense key schedule is untouched)."""
    key = jax.random.key(seed)
    H, L, M, V, S = cfg.hidden, cfg.n_layers, cfg.mlp_hidden, cfg.vocab_size, cfg.seq_len
    pd = cfg.param_dtype
    std = 0.02
    ks = jax.random.split(key, 8)

    def nrm(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape)).astype(pd)

    moe_ids = cfg.moe_layer_ids
    Ld = L - len(moe_ids)                 # dense-MLP layer count (== L
    #                                       when MoE is off: bit-identical)
    blocks = {
        "ln1_s": jnp.ones((L, H), pd),
        "ln1_b": jnp.zeros((L, H), pd),
        "qkv_w": nrm(ks[0], (L, H, 3 * H)),
        "qkv_b": jnp.zeros((L, 3 * H), pd),
        "proj_w": nrm(ks[1], (L, H, H), std / math.sqrt(2 * L)),
        "proj_b": jnp.zeros((L, H), pd),
        "ln2_s": jnp.ones((L, H), pd),
        "ln2_b": jnp.zeros((L, H), pd),
        "fc_w": nrm(ks[2], (Ld, H, M)),
        "fc_b": jnp.zeros((Ld, M), pd),
        "out_w": nrm(ks[3], (Ld, M, H), std / math.sqrt(2 * L)),
        "out_b": jnp.zeros((Ld, H), pd),
    }
    out = {
        "wte": nrm(ks[4], (V, H)),
        "wpe": nrm(ks[5], (S, H), 0.01),
        "blocks": blocks,
        "lnf_s": jnp.ones((H,), pd),
        "lnf_b": jnp.zeros((H,), pd),
    }
    if moe_ids:
        # moe keys derive from ks[6] (dense path never consumes it, so
        # the dense leaves above match the moe_experts=0 tree exactly)
        Lm, E = len(moe_ids), cfg.moe_experts
        mks = jax.random.split(ks[6], 3)
        out["moe"] = {
            "router_w": nrm(mks[0], (Lm, H, E)),
            "w_in": nrm(mks[1], (Lm, E, H, M)),
            "b_in": jnp.zeros((Lm, E, M), pd),
            "w_out": nrm(mks[2], (Lm, E, M, H), std / math.sqrt(2 * L)),
            "b_out": jnp.zeros((Lm, E, H), pd),
        }
    return out


def gpt_param_specs(cfg: GPTConfig) -> Dict[str, Any]:
    """PartitionSpec table: Megatron-style TP over "model", stages over
    "pipe". Mirrors what reference mp_layers + PipelineLayer produce.
    MoE expert leaves shard their EXPERT dim over "model" (expert
    parallelism — each shard holds E/ep whole experts, the layout the
    fleet.auto ``ep`` plans and the serving mesh decode assume)."""
    pipe = ("pipe",) if cfg.n_stages > 1 else ()
    b = lambda *rest: P(*(pipe + (None,) + rest))  # (stage?, layer, ...)
    out = {
        "wte": P("model", None),            # vocab-parallel embedding
        "wpe": P(),
        "blocks": {
            "ln1_s": b(None), "ln1_b": b(None),
            "qkv_w": b(None, "model"),      # column-parallel
            "qkv_b": b("model"),
            "proj_w": b("model", None),     # row-parallel
            "proj_b": b(None),
            "ln2_s": b(None), "ln2_b": b(None),
            "fc_w": b(None, "model"),       # column-parallel
            "fc_b": b("model"),
            "out_w": b("model", None),      # row-parallel
            "out_b": b(None),
        },
        "lnf_s": P(), "lnf_b": P(),
    }
    if cfg.moe_layer_ids:
        if len(cfg.moe_layer_ids) == cfg.n_layers:
            # every MLP routed: the dense leaves are zero-length stubs
            # (leading dim 0) and XLA pins zero-sized outputs replicated
            # — the TP spec would trip the out-sharding check
            for k in ("fc_w", "fc_b", "out_w", "out_b"):
                out["blocks"][k] = P()
        out["moe"] = {
            "router_w": P(),                       # tiny, replicated
            "w_in": P(None, "model", None, None),  # expert-parallel
            "b_in": P(None, "model", None),
            "w_out": P(None, "model", None, None),
            "b_out": P(None, "model", None),
        }
    return out


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

@jax.named_scope("ln")
def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attention(cfg: GPTConfig, q, k, v):
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if cfg.ring_attention:
        # ring+flash: per-hop block compute is the Pallas kernel
        # (parallel/ring_flash.py); jnp blockwise reference off-TPU
        from ..parallel.ring_flash import ring_flash_attention_sharded
        return ring_flash_attention_sharded(q, k, v, causal=True, scale=scale,
                                      seq_axis=cfg.seq_axis,
                                      batch_axis="data", head_axis="model")
    # auto: on the v5e flash won at seq >= 1024 always, and at 512
    # whenever remat is off (the 512 loss only appears under remat, which
    # recomputes the fused kernel in the backward). Read before the
    # benchmark existed; no ledger line re-measures the choice (ROADMAP D4)
    use_flash = (cfg.use_flash if cfg.use_flash is not None
                 else (_on_tpu() and (q.shape[2] >= 1024
                                      or (q.shape[2] >= 512
                                          and not cfg.remat))))
    if use_flash:
        from ..ops.flash_attention import flash_attention_arrays
        from ..parallel.mesh import get_mesh

        flash = functools.partial(flash_attention_arrays, causal=True,
                                  scale=scale)
        mesh = get_mesh()
        if _on_tpu() and mesh is not None and mesh.size > 1:
            # GSPMD cannot partition a Mosaic kernel (jax raises at
            # lowering), so on a multi-chip mesh each device runs the
            # kernel on its own shard: batch over the Fleet batch axes,
            # heads over "model" — the layout gpt_param_specs and
            # DistributedTrainStep's default batch_spec already produce
            spec = P(("data", "sharding"), "model", None, None)
            flash = jax.shard_map(flash, mesh=mesh, in_specs=(spec,) * 3,
                                  out_specs=spec)
        return flash(q, k, v)
    return _attention_reference(q, k, v, causal=True, scale=scale)


@jax.named_scope("attn")
def _attn_half(cfg: GPTConfig, p, x):
    """Attention half of a block (LN1 → QKV → attention → proj +
    residual); p leaves have no layer dim. Returns (x, (kh, vh))."""
    B, S, H = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    cd = cfg.dtype

    h = _layer_norm(x, p["ln1_s"], p["ln1_b"])
    qkv = h @ p["qkv_w"].astype(cd) + p["qkv_b"].astype(cd)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    to_heads = lambda t: t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
    kh, vh = to_heads(k), to_heads(v)
    o = _attention(cfg, to_heads(q), kh, vh)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H)
    return x + o @ p["proj_w"].astype(cd) + p["proj_b"].astype(cd), (kh, vh)


@jax.named_scope("mlp")
def _mlp_half(cfg: GPTConfig, p, x):
    """Dense MLP half of a block (LN2 → gelu MLP + residual)."""
    cd = cfg.dtype
    fused = (cfg.fused_mlp if cfg.fused_mlp is not None
             else _native.fused_kernels[0])
    fp8 = cfg.fp8 if cfg.fp8 is not None else _fp8[0]
    if fp8:
        from ..amp.fp8 import fp8_linear

        h = _layer_norm(x, p["ln2_s"], p["ln2_b"])
        h = jax.nn.gelu(fp8_linear(h, p["fc_w"].astype(cd),
                                   p["fc_b"].astype(cd)))
        x = x + fp8_linear(h, p["out_w"].astype(cd), p["out_b"].astype(cd))
    elif fused:
        from ..ops.fused_kernels import fused_ln_mlp

        x = fused_ln_mlp(x, p["fc_w"].astype(cd), p["fc_b"].astype(cd),
                         p["out_w"].astype(cd), p["out_b"].astype(cd),
                         ln_scale=p["ln2_s"], ln_bias=p["ln2_b"],
                         residual=True, act="gelu")
    else:
        h = _layer_norm(x, p["ln2_s"], p["ln2_b"])
        h = jax.nn.gelu(h @ p["fc_w"].astype(cd) + p["fc_b"].astype(cd))
        x = x + h @ p["out_w"].astype(cd) + p["out_b"].astype(cd)
    return x


def _block_kv(cfg: GPTConfig, p, x):
    """One transformer block; p leaves have no layer dim. Also returns the
    per-head K/V ((B, nh, S, hd) each) so the prefill path can seed a KV
    cache; gpt_forward discards them (XLA DCEs the dead outputs)."""
    x, (kh, vh) = _attn_half(cfg, p, x)
    return _mlp_half(cfg, p, x), (kh, vh)


def _block(cfg: GPTConfig, p, x):
    """One transformer block; p leaves have no layer dim."""
    return _block_kv(cfg, p, x)[0]


def _block_stack(cfg: GPTConfig, blocks, x):
    """lax.scan over the leading layer dim (unrolled per cfg.scan_unroll)."""
    body = _block
    if cfg.remat:
        # keep non-batch matmul results (weights-only dots), recompute the
        # rest: measured equal to full remat at batch 16 and ~10% faster at
        # batch 32 on v5e (BERT-base)
        body = jax.checkpoint(
            body, static_argnums=(0,),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    def step(h, layer_p):
        return body(cfg, layer_p, h), None

    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    unroll = n_layers if cfg.scan_unroll is None \
        else max(1, min(int(cfg.scan_unroll), n_layers))
    x, _ = jax.lax.scan(step, x, blocks, unroll=unroll)
    return x


# -- mixture-of-experts blocks (ISSUE 18) -----------------------------------
# MoE layers break the homogeneous lax.scan stack (their MLP params live
# in a separate subtree with a different leading dim), so the MoE forward
# is a Python loop over per-layer leaves: one compiled body per layer.

_ATTN_KEYS = ("ln1_s", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_s", "ln2_b")
_MLP_KEYS = ("fc_w", "fc_b", "out_w", "out_b")
_MOE_KEYS = ("router_w", "w_in", "b_in", "w_out", "b_out")


def _layer_params(tree, i, keys):
    return {k: tree[k][i] for k in keys}


@jax.named_scope("mlp")
def _moe_mlp_half(cfg: GPTConfig, p, pm, x, capacity_factor):
    """MoE MLP half (LN2 → routed expert FFN + residual). x (B, S, H);
    returns (x, aux, z, counts (E,), dropped). Dropped assignments
    contribute nothing to y, so the residual passes those tokens
    through unchanged."""
    from ..nn.moe import moe_ffn

    B, S, H = x.shape
    h = _layer_norm(x, p["ln2_s"], p["ln2_b"])
    y, aux, z, counts, dropped = moe_ffn(
        pm, h.reshape(B * S, H), top_k=cfg.moe_top_k,
        capacity_factor=capacity_factor, expert_axis=cfg.moe_axis)
    return x + y.reshape(B, S, H), aux, z, counts, dropped


def _block_moe(cfg: GPTConfig, p, pm, x, capacity_factor):
    """One MoE transformer block (attention half + routed MLP half)."""
    x, _ = _attn_half(cfg, p, x)
    return _moe_mlp_half(cfg, p, pm, x, capacity_factor)


def _hidden_moe(cfg: GPTConfig, params, x, capacity_factor):
    """Block stack with MoE layers interleaved (Python loop — see module
    note above). Returns (x, aux_sum, z_sum, counts, dropped); aux/z
    are SUMS over the MoE layers, callers average by len(moe_layer_ids).
    ``capacity_factor=None`` routes droplessly (the inference mode)."""
    moe_ids = set(cfg.moe_layer_ids)
    blocks = params["blocks"]
    aux = jnp.float32(0.0)
    zl = jnp.float32(0.0)
    counts = jnp.zeros((cfg.moe_experts,), jnp.int32)
    dropped = jnp.int32(0)
    dense = _block
    moe = _block_moe
    if cfg.remat:
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        dense = jax.checkpoint(dense, static_argnums=(0,), policy=policy)
        moe = jax.checkpoint(moe, static_argnums=(0, 4), policy=policy)
    di = mi = 0
    for i in range(cfg.n_layers):
        pa = _layer_params(blocks, i, _ATTN_KEYS)
        if i in moe_ids:
            pm = _layer_params(params["moe"], mi, _MOE_KEYS)
            mi += 1
            x, a, z, c, d = moe(cfg, pa, pm, x, capacity_factor)
            aux, zl = aux + a, zl + z
            counts, dropped = counts + c, dropped + d
        else:
            pd = _layer_params(blocks, di, _MLP_KEYS)
            di += 1
            x = dense(cfg, {**pa, **pd}, x)
    return x, aux, zl, counts, dropped


@jax.named_scope("embed")
def _embed(cfg: GPTConfig, params, tokens):
    emb = params["wte"].astype(cfg.dtype)[tokens]
    pos = params["wpe"].astype(cfg.dtype)[: tokens.shape[1]]
    return emb + pos[None, :, :]


def _logits(params, x, compute_dtype=jnp.bfloat16):
    # tied head. The matmul runs in bf16 on the MXU with fp32 ACCUMULATION
    # (preferred_element_type) — fp32 operands would run at 1/4 the MXU
    # rate for the single biggest matmul in the model (B·S×H×V), while the
    # fp32 accumulator keeps the softmax numerically stable. The returned
    # logits are fp32.
    return jnp.einsum("bsh,vh->bsv", x.astype(compute_dtype),
                      params["wte"].astype(compute_dtype),
                      preferred_element_type=jnp.float32)


@jax.named_scope("head")
def _head(cfg: GPTConfig, params, x):
    x = _layer_norm(x, params["lnf_s"], params["lnf_b"])
    return _logits(params, x)


def gpt_forward(cfg: GPTConfig, params, tokens):
    """tokens (B, S) int32 → logits (B, S, V).

    With cfg.n_stages > 1 the caller is expected to reshape the batch into
    microbatches and use parallel.pipeline_forward (see gpt_loss).
    MoE blocks route DROPLESSLY here (inference semantics — identical
    routing to every serving path regardless of batch composition).
    """
    x = _embed(cfg, params, tokens)
    if cfg.moe_layer_ids:
        x = _hidden_moe(cfg, params, x, None)[0]
    else:
        x = _block_stack(cfg, params["blocks"], x)
    return _head(cfg, params, x)


def _pipeline_hidden(cfg: GPTConfig, params, tokens, n_micro):
    """Embed → SPMD pipeline over stage-stacked blocks → hidden states."""
    from ..parallel.pipeline import pipeline_forward, stack_stages

    B, S = tokens.shape
    if B % n_micro != 0:
        raise ValueError(f"batch {B} not divisible by n_micro {n_micro}")
    x = _embed(cfg, params, tokens)
    # microbatch index on the INNER dim (x[i] interleaves the batch):
    # the batch's data/sharding tiling stays on the major dim through the
    # reshape, so forward and backward layouts cross the pipeline scan
    # without the SPMD partitioner's replicate-and-repartition fallback.
    mb = B // n_micro
    x_micro = x.reshape(mb, n_micro, S, cfg.hidden).transpose(1, 0, 2, 3)
    stage_params = params["blocks"]
    if stage_params["qkv_w"].ndim == 3:  # flat (L, H, 3H) — not yet staged
        stage_params = stack_stages(stage_params, cfg.n_stages)

    def stage_fn(sp, h):
        return _block_stack(cfg, sp, h)

    h = pipeline_forward(stage_fn, stage_params, x_micro, cfg.n_stages)
    return h.transpose(1, 0, 2, 3).reshape(B, S, cfg.hidden)


def _chunked_ce(params, x, labels, chunk: int):
    """Cross entropy without materializing (B, S, V) logits: the final LN'd
    hiddens are processed in sequence chunks; each chunk's logits live only
    inside its scan iteration (remat'd), so peak memory is (B, chunk, V) —
    the (B,S,V) fp32 logits buffer (~1GB at BERT-base/batch16) never
    exists. HBM-bound loss → big memory headroom for larger batch."""
    B, S, H = x.shape
    n_chunks = S // chunk
    xc = x.reshape(B, n_chunks, chunk, H).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(args):
        xs, ls = args
        logp = jax.nn.log_softmax(_logits(params, xs), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ls[..., None], axis=-1))

    total = jnp.sum(jax.lax.map(one, (xc, lc)))
    return total / (B * S)


def gpt_loss(cfg: GPTConfig, params, batch, n_micro: int = 1,
             loss_chunk: Optional[int] = None):
    """Causal-LM cross entropy. batch = (tokens, labels), both (B, S).

    ``loss_chunk``: sequence-chunked CE — peak-memory saver for huge vocab
    or long seq (full (B,S,V) fp32 logits never materialize); measured
    ~10% slower than the fused full-logits path at BERT-base scale, so off
    by default.

    MoE configs add the router regularizers to the CE:
    ``moe_aux_weight · mean-layer aux + moe_z_weight · mean-layer z``,
    with capacity-factor dispatch (drops + residual passthrough)."""
    tokens, labels = batch
    moe_ids = cfg.moe_layer_ids
    aux = zl = None
    if cfg.n_stages > 1:
        if moe_ids:
            raise ValueError(
                "MoE (moe_experts>0) and pipeline stages (n_stages>1) "
                "are not combinable yet — the MoE subtree has no stage "
                "stacking")
        if n_micro < cfg.n_stages:
            raise ValueError(
                f"n_micro={n_micro} must be >= n_stages={cfg.n_stages} "
                "(fewer microbatches than stages leaves the pipeline idle)")
        x = _pipeline_hidden(cfg, params, tokens, n_micro)
    else:
        x = _embed(cfg, params, tokens)
        if moe_ids:
            x, aux, zl, _, _ = _hidden_moe(cfg, params, x,
                                           cfg.moe_capacity_factor)
        else:
            x = _block_stack(cfg, params["blocks"], x)
    if loss_chunk and tokens.shape[1] > loss_chunk \
            and tokens.shape[1] % loss_chunk != 0:
        raise ValueError(
            f"loss_chunk={loss_chunk} must divide seq_len="
            f"{tokens.shape[1]} (the memory saver would otherwise be "
            "silently disabled)")
    with jax.named_scope("head_loss"):
        x = _layer_norm(x, params["lnf_s"], params["lnf_b"])
        if loss_chunk and tokens.shape[1] > loss_chunk:
            ce = _chunked_ce(params, x, labels, loss_chunk)
        else:
            logp = jax.nn.log_softmax(_logits(params, x), axis=-1)
            ll = jnp.take_along_axis(logp, labels[..., None],
                                     axis=-1)[..., 0]
            ce = -jnp.mean(ll)
    if aux is not None:
        n = len(moe_ids)
        ce = ce + cfg.moe_aux_weight * (aux / n) \
            + cfg.moe_z_weight * (zl / n)
    return ce


# --------------------------------------------------------------------------
# KV-cache autoregressive serving path (paddle_tpu.serving, ISSUE 4)
# --------------------------------------------------------------------------
#
# The reference's inference stack recomputes nothing either — its
# AnalysisPredictor serves a compiled program; generation loops over it.
# Here the generation loop gets its own pair of pure functions so the
# serving engine can jit them once:
#
# - gpt_prefill: one causal pass over the whole prompt that ALSO emits the
#   per-layer K/V it computed, so a cache slot can be seeded in the same
#   program (causality makes those K/V exact: hidden state at position s
#   never sees positions > s, so end-padding a prompt is safe).
# - gpt_decode_step: batched one-token step — each sequence's new K/V is
#   scattered into its cache slot at ``positions`` and the single query
#   attends over the slot masked to ``pos <= positions``. O(S·H) per token
#   instead of gpt_forward's O(S·H² + S²·H) full recompute.
#
# Both run over the cache layout paddle_tpu.serving.KVCache owns:
# (slots, layers, heads, max_len, head_dim). Stage-stacked (n_stages > 1)
# param trees are a training layout; serving expects the flat (L, ...)
# blocks gpt_init produces.

def quantize_gpt_weights(params, names=("qkv_w", "proj_w", "fc_w",
                                        "out_w")):
    """Per-channel int8 weight quantization of the block matmuls.

    Each named (L, K, N) block weight becomes ``{"q": int8 (L, K, N),
    "s": f32 (L, N)}`` (s is the dequant multiplier absmax/127, reduced
    over the contraction dim). The resulting tree feeds
    :func:`gpt_decode_step` — ``_block_decode`` routes dict-typed
    weights through the Pallas int8 matmul with dynamic per-tensor
    activation quantization (ops/int8_matmul.py). Embedding/logits stay
    fp (the tied wte doubles as the lookup table). First consumer:
    ``serving.InferenceEngine(int8_weights=True)``."""
    out = dict(params)
    blocks = dict(params["blocks"])
    for name in names:
        w = jnp.asarray(blocks[name], jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=1), 1e-8) / 127.0
        q = jnp.clip(jnp.round(w / s[:, None, :]), -127, 127)
        blocks[name] = {"q": q.astype(jnp.int8), "s": s}
    out["blocks"] = blocks
    return out


def _dec_mm(x, w, cd):
    """x @ w for a maybe-int8-quantized decode weight (see
    quantize_gpt_weights)."""
    if isinstance(w, dict):
        from ..ops.int8_matmul import dynamic_int8_matmul

        return dynamic_int8_matmul(x, w["q"], w["s"]).astype(cd)
    return x @ w.astype(cd)


@jax.named_scope("attn")
def _dec_attn(cfg: GPTConfig, p, x, kc_l, vc_l, positions):
    """Attention half of the one-token block step (cache write + attend
    + proj residual). x (B, 1, H); kc_l/vc_l (B, nh, max_len, hd);
    positions (B,) int32. Returns (x, updated kc_l, updated vc_l)."""
    B = x.shape[0]
    nh, hd = cfg.n_heads, cfg.head_dim
    cd = cfg.dtype

    h = _layer_norm(x, p["ln1_s"], p["ln1_b"])
    qkv = _dec_mm(h, p["qkv_w"], cd) + p["qkv_b"].astype(cd)
    q, k, v = jnp.split(qkv, 3, axis=-1)         # each (B, 1, H)
    to_heads = lambda t: t.reshape(B, nh, hd)
    q, k, v = to_heads(q), to_heads(k), to_heads(v)

    def write(c, new, pos):  # c (nh, max_len, hd), new (nh, hd)
        return jax.lax.dynamic_update_slice(c, new[:, None, :], (0, pos, 0))

    kc_l = jax.vmap(write)(kc_l, k, positions)
    vc_l = jax.vmap(write)(vc_l, v, positions)

    # same numerics as _attention_reference: scores in compute dtype,
    # softmax in fp32; padded/garbage cache positions are masked off
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhd,bhkd->bhk", q, kc_l) * scale
    live = jnp.arange(kc_l.shape[2])[None, :] <= positions[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhk,bhkd->bhd", w, vc_l).reshape(B, 1, nh * hd)

    x = x + _dec_mm(o, p["proj_w"], cd) + p["proj_b"].astype(cd)
    return x, kc_l, vc_l


@jax.named_scope("mlp")
def _dec_mlp(cfg: GPTConfig, p, x):
    """Dense MLP half of the one-token block step (LN2 → gelu MLP +
    residual; weights may be int8-quantized dicts)."""
    cd = cfg.dtype
    h = _layer_norm(x, p["ln2_s"], p["ln2_b"])
    h = jax.nn.gelu(_dec_mm(h, p["fc_w"], cd) + p["fc_b"].astype(cd))
    return x + _dec_mm(h, p["out_w"], cd) + p["out_b"].astype(cd)


@jax.named_scope("mlp")
def _dec_moe_mlp(cfg: GPTConfig, pa, pm, x):
    """MoE MLP half of the one-token block step — DROPLESS, so decode
    quality never depends on which requests share the tick. x (B, 1, H);
    returns (x, counts (E,) i32, dropped i32)."""
    from ..nn.moe import moe_ffn

    B = x.shape[0]
    h = _layer_norm(x, pa["ln2_s"], pa["ln2_b"])
    y, _, _, counts, dropped = moe_ffn(
        pm, h.reshape(B, -1), top_k=cfg.moe_top_k, capacity_factor=None,
        expert_axis=cfg.moe_axis)
    return x + y.reshape(x.shape), counts, dropped


def _block_decode(cfg: GPTConfig, p, x, kc_l, vc_l, positions):
    """One-token block step against one layer's cache slice.

    x (B, 1, H); kc_l/vc_l (B, nh, max_len, hd) — this layer's cache for
    every slot; positions (B,) int32 — where each slot's incoming token
    lands. Block weights may be int8-quantized dicts (see
    quantize_gpt_weights). Returns (x, updated kc_l, updated vc_l)."""
    x, kc_l, vc_l = _dec_attn(cfg, p, x, kc_l, vc_l, positions)
    return _dec_mlp(cfg, p, x), kc_l, vc_l


def gpt_prefill(cfg: GPTConfig, params, tokens):
    """tokens (B, S) int32 → (logits (B, S, V) fp32, cache_entries).

    cache_entries = (k, v), each (B, L, nh, S, hd) in cfg.dtype — exactly
    the K/V gpt_forward computes for those positions, slot-major so a
    whole prompt drops into a KVCache slot with one dynamic_update_slice
    (serving.kv_cache.cache_insert)."""
    x = _embed(cfg, params, tokens)

    if cfg.moe_layer_ids:
        # MoE stacks are heterogeneous (see _hidden_moe) — Python loop,
        # dropless routing, K/V collected per layer then stacked
        moe_ids = set(cfg.moe_layer_ids)
        blocks = params["blocks"]
        ks, vs = [], []
        di = mi = 0
        for i in range(cfg.n_layers):
            pa = _layer_params(blocks, i, _ATTN_KEYS)
            x, (kh, vh) = _attn_half(cfg, pa, x)
            ks.append(kh)
            vs.append(vh)
            if i in moe_ids:
                pm = _layer_params(params["moe"], mi, _MOE_KEYS)
                mi += 1
                x = _moe_mlp_half(cfg, pa, pm, x, None)[0]
            else:
                pd = _layer_params(blocks, di, _MLP_KEYS)
                di += 1
                x = _mlp_half(cfg, {**pa, **pd}, x)
        return _head(cfg, params, x), (jnp.stack(ks, axis=1),
                                       jnp.stack(vs, axis=1))

    def step(h, layer_p):
        h, kv = _block_kv(cfg, layer_p, h)
        return h, kv

    x, (ks, vs) = jax.lax.scan(step, x, params["blocks"])
    # (L, B, nh, S, hd) → (B, L, nh, S, hd)
    return _head(cfg, params, x), (jnp.moveaxis(ks, 0, 1),
                                   jnp.moveaxis(vs, 0, 1))


def gpt_decode_step(cfg: GPTConfig, params, cache, positions, tokens):
    """Batched one-token decode against a slotted KV cache.

    cache = (k, v), each (B, L, nh, max_len, hd); positions (B,) int32 —
    the index each incoming token occupies (== tokens already cached in
    that slot); tokens (B,) int32. Returns (logits (B, V) fp32, new cache)
    with the new tokens' K/V written at ``positions``. Slots whose
    position/token are stale (unoccupied engine slots) compute garbage
    that later prefills overwrite — callers mask host-side.

    MoE configs return a THIRD element ``(counts (E,) i32, dropped i32)``
    — per-tick router load for the serving gauges (dropless routing, so
    dropped stays 0 by construction; the counter is a guard)."""
    k_cache, v_cache = cache
    cd = cfg.dtype
    L = k_cache.shape[1]
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cd)[tokens]
             + params["wpe"].astype(cd)[positions])[:, None, :]  # (B, 1, H)

    if cfg.moe_layer_ids:
        moe_ids = set(cfg.moe_layer_ids)
        blocks = params["blocks"]
        counts = jnp.zeros((cfg.moe_experts,), jnp.int32)
        dropped = jnp.int32(0)
        di = mi = 0
        for i in range(cfg.n_layers):
            pa = _layer_params(blocks, i, _ATTN_KEYS)
            x, kc_l, vc_l = _dec_attn(cfg, pa, x, k_cache[:, i],
                                      v_cache[:, i], positions)
            k_cache = k_cache.at[:, i].set(kc_l)
            v_cache = v_cache.at[:, i].set(vc_l)
            if i in moe_ids:
                pm = _layer_params(params["moe"], mi, _MOE_KEYS)
                mi += 1
                x, c, d = _dec_moe_mlp(cfg, pa, pm, x)
                counts, dropped = counts + c, dropped + d
            else:
                pd = _layer_params(blocks, di, _MLP_KEYS)
                di += 1
                x = _dec_mlp(cfg, {**pa, **pd}, x)
        return (_head(cfg, params, x)[:, 0], (k_cache, v_cache),
                (counts, dropped))

    def step(carry, inp):
        x, kc, vc = carry
        layer_p, li = inp
        kc_l = jnp.take(kc, li, axis=1)
        vc_l = jnp.take(vc, li, axis=1)
        x, kc_l, vc_l = _block_decode(cfg, layer_p, x, kc_l, vc_l, positions)
        kc = jax.lax.dynamic_update_index_in_dim(kc, kc_l, li, 1)
        vc = jax.lax.dynamic_update_index_in_dim(vc, vc_l, li, 1)
        return (x, kc, vc), None

    (x, k_cache, v_cache), _ = jax.lax.scan(
        step, (x, k_cache, v_cache), (params["blocks"], jnp.arange(L)))
    return _head(cfg, params, x)[:, 0], (k_cache, v_cache)


def _block_verify(cfg: GPTConfig, p, x, kc_l, vc_l, positions):
    """C-token block step against one layer's cache slice (ISSUE 10 —
    the speculative-decoding verify shape, also a batched chunk append).

    x (B, C, H); kc_l/vc_l (B, nh, max_len, hd); positions (B,) int32 —
    the index the FIRST incoming token occupies; token j of a row lands
    at ``positions + j``. The C new K/V rows are one contiguous span, so
    ONE dynamic_update_slice per slot writes them all; each query j then
    attends over the slot masked to ``pos <= positions + j`` — the math
    per query equals :func:`_block_decode` run token-by-token."""
    x, kc_l, vc_l = _verify_attn(cfg, p, x, kc_l, vc_l, positions)
    return _dec_mlp(cfg, p, x), kc_l, vc_l


@jax.named_scope("attn")
def _verify_attn(cfg: GPTConfig, p, x, kc_l, vc_l, positions):
    """Attention half of :func:`_block_verify`."""
    B, C, _ = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    cd = cfg.dtype

    h = _layer_norm(x, p["ln1_s"], p["ln1_b"])
    qkv = _dec_mm(h, p["qkv_w"], cd) + p["qkv_b"].astype(cd)
    q, k, v = jnp.split(qkv, 3, axis=-1)          # each (B, C, H)
    to_heads = lambda t: t.reshape(B, C, nh, hd).transpose(0, 2, 1, 3)
    q, k, v = to_heads(q), to_heads(k), to_heads(v)   # (B, nh, C, hd)

    def write(c, new, pos):  # c (nh, max_len, hd), new (nh, C, hd)
        return jax.lax.dynamic_update_slice(c, new, (0, pos, 0))

    kc_l = jax.vmap(write)(kc_l, k.astype(kc_l.dtype), positions)
    vc_l = jax.vmap(write)(vc_l, v.astype(vc_l.dtype), positions)

    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kc_l.astype(q.dtype)) * scale
    qpos = positions[:, None] + jnp.arange(C)[None, :]        # (B, C)
    live = jnp.arange(kc_l.shape[2])[None, None, :] <= qpos[:, :, None]
    s = jnp.where(live[:, None], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, vc_l.astype(q.dtype))
    o = o.transpose(0, 2, 1, 3).reshape(B, C, nh * hd)

    x = x + _dec_mm(o, p["proj_w"], cd) + p["proj_b"].astype(cd)
    return x, kc_l, vc_l


def gpt_verify_step(cfg: GPTConfig, params, cache, positions, tokens):
    """Batched MULTI-token decode against a slotted KV cache (ISSUE 10).

    cache = (k, v), each (B, L, nh, max_len, hd); positions (B,) int32 —
    where each row's FIRST token lands (token j at ``positions + j``);
    tokens (B, C) int32. Returns (logits (B, C, V) fp32, new cache):
    logits[:, j] is the next-token distribution after consuming tokens
    ``[..j]`` — exactly what gpt_decode_step would return fed the same
    tokens one at a time, in ONE program. This is the
    speculative-decoding verify pass: the target model scores a draft's
    k proposals plus the bonus position in a single dispatch. The caller
    must guarantee ``positions + C <= max_len`` (the engine's headroom
    check); rows whose later entries are rejected leave stale K/V past
    the accepted length, which the position mask hides until the next
    step overwrites them."""
    if cfg.moe_layer_ids:
        raise ValueError(
            "gpt_verify_step does not support MoE configs (the engine "
            "rejects speculative decoding with moe_experts > 0)")
    k_cache, v_cache = cache
    cd = cfg.dtype
    L = k_cache.shape[1]
    C = tokens.shape[1]
    qpos = positions[:, None] + jnp.arange(C)[None, :]
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[tokens] \
            + params["wpe"].astype(cd)[qpos]

    def step(carry, inp):
        x, kc, vc = carry
        layer_p, li = inp
        kc_l = jnp.take(kc, li, axis=1)
        vc_l = jnp.take(vc, li, axis=1)
        x, kc_l, vc_l = _block_verify(cfg, layer_p, x, kc_l, vc_l, positions)
        kc = jax.lax.dynamic_update_index_in_dim(kc, kc_l, li, 1)
        vc = jax.lax.dynamic_update_index_in_dim(vc, vc_l, li, 1)
        return (x, kc, vc), None

    (x, k_cache, v_cache), _ = jax.lax.scan(
        step, (x, k_cache, v_cache), (params["blocks"], jnp.arange(L)))
    return _head(cfg, params, x), (k_cache, v_cache)


# --------------------------------------------------------------------------
# Paged KV cache variants (serving.PagedKVCache, ISSUE 7)
# --------------------------------------------------------------------------
#
# Same contract as gpt_prefill/gpt_decode_step, but the cache is a shared
# BLOCK POOL (n_blocks, L, nh, block_size, hd) addressed through per-slot
# block tables instead of one contiguous max_len strip per slot, so cache
# memory is proportional to live tokens. Pool block 0 is reserved as the
# garbage sink: table padding (and whole tables of unoccupied slots)
# point at it, so stale batch lanes scatter their garbage K/V somewhere
# no live slot ever reads.
#
# Every paged step addresses the WHOLE pool by (block, layer): the pool
# is the layer scan's carry (donated by the engine's programs, so XLA
# updates it in place), the new tokens' rows are written straight into
# it and the table's blocks are read straight out of it. A layer's
# (n_blocks, nh, block_size, hd) slab is never cut out, copied or put
# back. ``li`` below is the layer: the scan's traced index, or a Python
# int in the unrolled MoE branches.
#
# Who writes: the decode tick's one new row a lane goes through
# ``ops.pool_write.pool_write_rows`` (on a TPU one kernel call a layer
# for K and V and the live lanes only; elsewhere the composed loop of
# ``pool_put``); the verify step's several rows a lane keep that
# composed loop (``_pool_write_rows``); a prefill chunk's whole blocks
# are one ``pool_put`` each (``_pool_write_blocks``).

@jax.named_scope("kv_pool")
def _pool_write_rows(kb, vb, li, blk, off, k, v):
    """Write new tokens' K/V into the pool at layer ``li``, in place,
    one ``pool_put`` a token and an array.

    blk/off (...,) int32 — each token's block and offset in it; k/v
    (..., nh, hd). Live slots own their blocks exclusively, so the only
    collisions are stale lanes piling onto a garbage sink."""
    nh, hd = k.shape[-2:]
    return write_rows_composed(
        (kb, vb), (k.reshape(-1, nh, hd), v.reshape(-1, nh, hd)),
        blk.reshape(-1), off.reshape(-1), li)


@jax.named_scope("kv_pool")
def _pool_write_blocks(kb, vb, li, bids, k, v):
    """Write whole blocks of K/V into the pool at layer ``li``, in
    place: k/v (nh, n * bs, hd) fill blocks ``bids`` (n,) in order."""
    bs = kb.shape[3]
    for j in range(bids.shape[0]):
        at = (bids[j], li, 0, 0, 0)
        rows = slice(j * bs, (j + 1) * bs)
        kb = pool_put(kb, k[None, None, :, rows], at)
        vb = pool_put(vb, v[None, None, :, rows], at)
    return kb, vb


@jax.named_scope("kv_pool")
def _pool_gather(kb, vb, li, tables):
    """The blocks ``tables`` (..., W) names at layer ``li``, as
    contiguous K and V (..., nh, W * bs, hd): W blocks a row are read,
    not the layer's slab."""
    from ..ops.paged_attention import gather_blocks
    return gather_blocks(kb, tables, li), gather_blocks(vb, tables, li)


@jax.named_scope("attn")
def _dec_attn_paged(cfg: GPTConfig, p, x, kb, vb, li, tables, positions,
                    lengths, walk, lanes):
    """Attention half of the paged one-token block step at layer ``li``
    (pool write + paged attention + proj residual); ``lengths`` (B,) are
    the tokens each slot attends over, ``walk`` the tick's
    ``ops.paged_attention.decode_walk`` of them and ``lanes`` its
    ``ops.pool_write.live_lanes``. Returns (x, kb, vb)."""
    B = x.shape[0]
    nh, hd = cfg.n_heads, cfg.head_dim
    bs = kb.shape[3]
    cd = cfg.dtype

    h = _layer_norm(x, p["ln1_s"], p["ln1_b"])
    qkv = _dec_mm(h, p["qkv_w"], cd) + p["qkv_b"].astype(cd)
    q, k, v = jnp.split(qkv, 3, axis=-1)         # each (B, 1, H)
    to_heads = lambda t: t.reshape(B, nh, hd)
    q, k, v = to_heads(q), to_heads(k), to_heads(v)

    blk = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
    with jax.named_scope("kv_pool"):
        kb, vb = pool_write_rows((kb, vb), (k, v), blk, positions % bs, li,
                                 lanes=lanes)

    from ..ops.paged_attention import paged_attention_arrays
    o = paged_attention_arrays(q, kb, vb, tables, lengths,
                               scale=1.0 / math.sqrt(hd), layer=li,
                               walk=walk)
    o = o.reshape(B, 1, nh * hd)

    x = x + _dec_mm(o, p["proj_w"], cd) + p["proj_b"].astype(cd)
    return x, kb, vb


def _block_decode_paged(cfg: GPTConfig, p, x, kb, vb, li, tables, positions,
                        lengths, walk, lanes):
    """One-token block step at layer ``li`` of the block pool.

    x (B, 1, H); kb/vb the whole pool (n_blocks, L, nh, block_size, hd);
    tables (B, W) int32; positions (B,) int32 — where each slot's
    incoming token lands; ``lengths`` (B,) the tokens it attends over,
    ``walk`` the tick's list of live blocks and ``lanes`` of live lanes.
    Attention routes through ops.paged_attention (Pallas kernel on TPU,
    identical composed gather elsewhere)."""
    x, kb, vb = _dec_attn_paged(cfg, p, x, kb, vb, li, tables, positions,
                                lengths, walk, lanes)
    return _dec_mlp(cfg, p, x), kb, vb


def gpt_decode_step_paged(cfg: GPTConfig, params, pool, tables, positions,
                          tokens):
    """Batched one-token decode against a paged block pool.

    pool = (kb, vb), each (n_blocks, L, nh, block_size, hd); tables
    (B, W) int32 per-slot block tables (padding/stale rows point at
    reserved block 0: a row that starts with it holds no request, costs
    the attention kernel nothing and yields logits nobody reads);
    positions/tokens (B,) int32. Returns
    (logits (B, V) fp32, new pool) with the new tokens' K/V written at
    block ``tables[b, positions[b] // block_size]``, offset
    ``positions[b] % block_size``. Numerics match gpt_decode_step over
    the same live positions; MoE configs return the same third
    ``(counts, dropped)`` element gpt_decode_step does."""
    from ..ops.paged_attention import decode_walk

    kb, vb = pool
    cd = cfg.dtype
    L = kb.shape[1]
    # a lane whose table row is the sink (block 0) holds no request:
    # length 0, which costs the kernels no step: it reads as zeros and
    # writes no row. The attention kernel's list of live blocks and the
    # row writer's list of live lanes are the same at every layer, so
    # they are built here, once a tick, and not inside the layer loop
    lengths = jnp.where(tables[:, 0] > 0, positions + 1, 0)
    walk = decode_walk(lengths, tables.shape[1], kb.shape[3])
    lanes = live_lanes(lengths)
    with jax.named_scope("embed"):
        x = (params["wte"].astype(cd)[tokens]
             + params["wpe"].astype(cd)[positions])[:, None, :]  # (B, 1, H)

    if cfg.moe_layer_ids:
        moe_ids = set(cfg.moe_layer_ids)
        blocks = params["blocks"]
        counts = jnp.zeros((cfg.moe_experts,), jnp.int32)
        dropped = jnp.int32(0)
        di = mi = 0
        for i in range(cfg.n_layers):
            pa = _layer_params(blocks, i, _ATTN_KEYS)
            x, kb, vb = _dec_attn_paged(cfg, pa, x, kb, vb, i, tables,
                                        positions, lengths, walk, lanes)
            if i in moe_ids:
                pm = _layer_params(params["moe"], mi, _MOE_KEYS)
                mi += 1
                x, c, d = _dec_moe_mlp(cfg, pa, pm, x)
                counts, dropped = counts + c, dropped + d
            else:
                pd = _layer_params(blocks, di, _MLP_KEYS)
                di += 1
                x = _dec_mlp(cfg, {**pa, **pd}, x)
        return (_head(cfg, params, x)[:, 0], (kb, vb), (counts, dropped))

    def step(carry, inp):
        x, kb, vb = carry
        layer_p, li = inp
        x, kb, vb = _block_decode_paged(cfg, layer_p, x, kb, vb, li,
                                        tables, positions, lengths, walk,
                                        lanes)
        return (x, kb, vb), None

    (x, kb, vb), _ = jax.lax.scan(
        step, (x, kb, vb), (params["blocks"], jnp.arange(L)))
    return _head(cfg, params, x)[:, 0], (kb, vb)


def _block_verify_paged(cfg: GPTConfig, p, x, kb, vb, li, tables,
                        positions):
    """C-token block step at layer ``li`` of the block pool.

    x (B, C, H); kb/vb the whole pool (n_blocks, L, nh, block_size, hd);
    tables (B, W) int32; positions (B,) int32 — token j of row b lands
    at block ``tables[b, (positions[b]+j) // bs]``, offset
    ``(positions[b]+j) % bs``. Attention is the composed table gather
    (the multi-query shape the Pallas decode kernel does not cover); the
    table width W is already bucketed by the engine, so gather work
    tracks live tokens."""
    x, kb, vb = _verify_attn_paged(cfg, p, x, kb, vb, li, tables, positions)
    return _dec_mlp(cfg, p, x), kb, vb


@jax.named_scope("attn")
def _verify_attn_paged(cfg: GPTConfig, p, x, kb, vb, li, tables, positions):
    """Attention half of :func:`_block_verify_paged`."""
    B, C, _ = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    bs = kb.shape[3]
    cd = cfg.dtype
    W = tables.shape[1]

    h = _layer_norm(x, p["ln1_s"], p["ln1_b"])
    qkv = _dec_mm(h, p["qkv_w"], cd) + p["qkv_b"].astype(cd)
    q, k, v = jnp.split(qkv, 3, axis=-1)          # each (B, C, H)
    qh = q.reshape(B, C, nh, hd).transpose(0, 2, 1, 3)   # (B, nh, C, hd)
    kh = k.reshape(B, C, nh, hd)
    vh = v.reshape(B, C, nh, hd)

    # the C new K/V of every row (positions contiguous)
    qpos = positions[:, None] + jnp.arange(C)[None, :]        # (B, C)
    blk = jnp.take_along_axis(tables, qpos // bs, axis=1)
    kb, vb = _pool_write_rows(kb, vb, li, blk, qpos % bs, kh, vh)

    kg, vg = _pool_gather(kb, vb, li, tables)     # (B, nh, W * bs, hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kg.astype(qh.dtype)) \
        * (1.0 / math.sqrt(hd))
    live = jnp.arange(W * bs)[None, None, :] <= qpos[:, :, None]
    s = jnp.where(live[:, None], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(qh.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, vg.astype(qh.dtype))
    o = o.transpose(0, 2, 1, 3).reshape(B, C, nh * hd)

    x = x + _dec_mm(o, p["proj_w"], cd) + p["proj_b"].astype(cd)
    return x, kb, vb


def gpt_verify_step_paged(cfg: GPTConfig, params, pool, tables, positions,
                          tokens):
    """Batched multi-token decode against a paged block pool (ISSUE 10).

    pool = (kb, vb), each (n_blocks, L, nh, block_size, hd); tables
    (B, W) int32; positions (B,) int32 — the first token's index per
    row; tokens (B, C) int32. Returns (logits (B, C, V) fp32, new pool).
    Same per-query math as gpt_decode_step_paged; the caller must have
    grown each live row's table to cover ``positions + C`` tokens (the
    engine's speculative grow), and stale lanes scatter onto their
    garbage sink exactly like the single-token step."""
    if cfg.moe_layer_ids:
        raise ValueError(
            "gpt_verify_step_paged does not support MoE configs (the "
            "engine rejects speculative decoding and prefix caching "
            "with moe_experts > 0)")
    kb, vb = pool
    L = kb.shape[1]

    def step(carry, inp):
        x, kb, vb = carry
        layer_p, li = inp
        x, kb, vb = _block_verify_paged(cfg, layer_p, x, kb, vb, li,
                                        tables, positions)
        return (x, kb, vb), None

    cd = cfg.dtype
    C = tokens.shape[1]
    qpos = positions[:, None] + jnp.arange(C)[None, :]
    with jax.named_scope("embed"):
        x = params["wte"].astype(cd)[tokens] \
            + params["wpe"].astype(cd)[qpos]
    (x, kb, vb), _ = jax.lax.scan(
        step, (x, kb, vb), (params["blocks"], jnp.arange(L)))
    return _head(cfg, params, x), (kb, vb)


def gpt_prefill_prefix(cfg: GPTConfig, params, pool, table_row, tokens,
                       start):
    """Prefill continuing from an arbitrary cached prefix (ISSUE 11 —
    the radix prefix cache's tail entry point).

    Like :func:`gpt_prefill_chunk`, but ``start`` (tokens already cached
    for this slot) need NOT be block-aligned: a prefix-cache match ends
    wherever the shared prompt diverges, often mid-block (the engine has
    already copy-on-write-duplicated that block, so the scatter below
    writes a private copy). Routes through the batched verify math
    (:func:`gpt_verify_step_paged` at B=1): token j of ``tokens``
    (1, C) lands at position ``start + j`` through ``table_row``'s
    block/offset lookup, and each query attends over the WHOLE cached
    prefix — matched blocks included — masked to ``pos <= start + j``,
    so logits at chunk position i equal :func:`gpt_prefill`'s at global
    position ``start + i`` over the same tokens. Padded tail positions
    scatter garbage through sink-padded table entries nobody reads.
    Returns (logits (1, C, V) fp32, updated pool)."""
    return gpt_verify_step_paged(cfg, params, pool, table_row[None, :],
                                 jnp.reshape(start, (1,)).astype(jnp.int32),
                                 tokens)


@jax.named_scope("attn")
def _chunk_attn(cfg: GPTConfig, p, x, kb, vb, li, table_row, start):
    """Attention half of the chunked-prefill block step at layer ``li``
    (pool write + full-prefix attention + proj residual). Returns
    (x, kb, vb)."""
    _, C, H = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    bs = kb.shape[3]
    cd = cfg.dtype
    W = table_row.shape[0]

    h = _layer_norm(x, p["ln1_s"], p["ln1_b"])
    qkv = h @ p["qkv_w"].astype(cd) + p["qkv_b"].astype(cd)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    to_heads = lambda t: t[0].reshape(C, nh, hd).transpose(1, 0, 2)
    q, k, v = to_heads(q), to_heads(k), to_heads(v)   # (nh, C, hd)

    bids = jnp.take(table_row, start // bs + jnp.arange(C // bs))
    kb, vb = _pool_write_blocks(kb, vb, li, bids, k, v)

    kg, vg = _pool_gather(kb, vb, li, table_row)      # (nh, W * bs, hd)
    s = jnp.einsum("hqd,hkd->hqk", q, kg.astype(q.dtype)) \
        * (1.0 / math.sqrt(hd))
    live = jnp.arange(W * bs)[None, :] <= (start + jnp.arange(C))[:, None]
    s = jnp.where(live[None], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("hqk,hkd->hqd", w, vg.astype(q.dtype))
    o = o.transpose(1, 0, 2).reshape(1, C, H)

    return x + o @ p["proj_w"].astype(cd) + p["proj_b"].astype(cd), kb, vb


@jax.named_scope("mlp")
def _chunk_mlp(cfg: GPTConfig, p, x):
    """Dense MLP half of the chunked-prefill block step."""
    cd = cfg.dtype
    h = _layer_norm(x, p["ln2_s"], p["ln2_b"])
    h = jax.nn.gelu(h @ p["fc_w"].astype(cd) + p["fc_b"].astype(cd))
    return x + h @ p["out_w"].astype(cd) + p["out_b"].astype(cd)


def _block_chunk(cfg: GPTConfig, p, x, kb, vb, li, table_row, start):
    """One transformer block over one prefill CHUNK at layer ``li`` of
    the pool.

    x (1, C, H) — C is the block_size-padded chunk length; kb/vb the
    whole pool (n_blocks, L, nh, block_size, hd); table_row (W,) int32
    — this slot's table; start — tokens already cached (block-aligned,
    traced). The chunk's K/V are written into the pool FIRST, then chunk
    queries attend over every cached position (previous chunks + the
    chunk itself) under the global causal mask, so the math equals one
    whole causal pass over the same prefix."""
    x, kb, vb = _chunk_attn(cfg, p, x, kb, vb, li, table_row, start)
    return _chunk_mlp(cfg, p, x), kb, vb


def gpt_prefill_chunk(cfg: GPTConfig, params, pool, table_row, tokens,
                      start, n_true=None):
    """One chunk of a paged, chunked prefill. ``n_true`` (how many of
    the chunk's tokens are real) is the step contract's and unused here:
    rows past a slot's length are never read from a paged pool.

    tokens (1, C) int32 — the next C prompt tokens, end-padded to a
    multiple of block_size (one compile per padded chunk length); start
    — tokens already cached for this slot, a block_size multiple (the
    engine chunks at prefill_chunk % block_size == 0 boundaries);
    table_row (W,) int32 must already cover positions < start + C.
    Returns (logits (1, C, V) fp32, updated pool): logits at position i
    equal gpt_prefill's at global position start + i, because every
    chunk attends over the full cached prefix (padded tail positions
    produce garbage nobody reads — decode overwrites them before ever
    attending)."""
    kb, vb = pool
    cd = cfg.dtype
    C = tokens.shape[1]
    L = kb.shape[1]

    with jax.named_scope("embed"):
        pos_emb = jax.lax.dynamic_slice(
            params["wpe"], (start, 0), (C, params["wpe"].shape[1]))
        x = params["wte"].astype(cd)[tokens] + pos_emb.astype(cd)[None]

    if cfg.moe_layer_ids:
        moe_ids = set(cfg.moe_layer_ids)
        blocks = params["blocks"]
        di = mi = 0
        for i in range(cfg.n_layers):
            pa = _layer_params(blocks, i, _ATTN_KEYS)
            x, kb, vb = _chunk_attn(cfg, pa, x, kb, vb, i, table_row,
                                    start)
            if i in moe_ids:
                pm = _layer_params(params["moe"], mi, _MOE_KEYS)
                mi += 1
                x = _moe_mlp_half(cfg, pa, pm, x, None)[0]
            else:
                pd = _layer_params(blocks, di, _MLP_KEYS)
                di += 1
                x = _chunk_mlp(cfg, {**pa, **pd}, x)
        return _head(cfg, params, x), (kb, vb)

    def step(carry, inp):
        x, kb, vb = carry
        layer_p, li = inp
        x, kb, vb = _block_chunk(cfg, layer_p, x, kb, vb, li, table_row,
                                 start)
        return (x, kb, vb), None

    (x, kb, vb), _ = jax.lax.scan(
        step, (x, kb, vb), (params["blocks"], jnp.arange(L)))
    return _head(cfg, params, x), (kb, vb)


def gpt_pool_spec(cfg: GPTConfig, n_blocks: int, block_size: int):
    """The paged pool's two arrays, keys and values: (n_blocks, L, nh,
    block_size, hd) each."""
    shape = (n_blocks, cfg.n_layers, cfg.n_heads, block_size, cfg.head_dim)
    return (jax.ShapeDtypeStruct(shape, cfg.dtype),) * 2


_SERVING = ServingModel(
    name="gpt", pool_spec=gpt_pool_spec, param_specs=gpt_param_specs,
    prefill_chunk=gpt_prefill_chunk,
    decode_step_paged=gpt_decode_step_paged,
    verify_step_paged=gpt_verify_step_paged,
    prefill_prefix=gpt_prefill_prefix,
    decode_step=gpt_decode_step, verify_step=gpt_verify_step)
