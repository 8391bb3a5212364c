"""SambaY decoder-hybrid-decoder (functional, serving).

``microsoft/Phi-4-mini-flash-reasoning`` (``model_type`` ``phi4flash``;
arXiv:2507.06607). Every layer is pre-LN with a gated SiLU MLP,

    h += mixer(LN1(h));   h += W_down(silu(W_gate LN2(h)) * W_up LN2(h))

then a final LN and the head, which is the embedding. Of ``L`` layers
(``L = 32``; ``P = L / 4``):

- **self-decoder**, layers ``0 .. L/2 - 1``, ``P`` pairs: an even layer
  is Mamba (``[x, z] = W_in u``; ``x = silu(conv4(x))``; ``dt, B, C``
  from ``x``; the selective scan of ``ops/selective_scan.py``; out
  ``W_out (y * silu(z))``), an odd one differential attention over the
  keys ``t - window + 1 .. t``;
- layer ``L/2``: Mamba whose ``y`` (before the ``silu(z)`` gate) is the
  MEMORY ``m`` the gated memory units read;
- layer ``L/2 + 1``: differential attention over the whole context. Its
  keys and values are the only rows the cross-decoder has;
- **cross-decoder**, ``P - 1`` pairs: a gated memory unit
  ``W_out2(silu(W_in2 u) * m)``, then cross-attention: the same
  differential mixer with a query projection only, over layer
  ``L/2 + 1``'s keys and values.

Differential attention: 40 query heads of 64 are 20 pairs ``(q1, q2)``,
20 key heads 10 pairs ``(k1, k2)``, two query pairs a key pair, and 10
value heads of 128; ``o = softmax(q1 k1^T / 8) V - lambda softmax(q2
k2^T / 8) V``, an RMSNorm over the 128, times ``1 - lambda_init``;
``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` and
``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` at layer ``i``. There are no
positions: the model has no rotary key.

**The cache is of two kinds** (``serving_model()``): the pool's first
two arrays are PAGED, layer ``L/2 + 1``'s key and value rows in blocks
a slot's table names, ``(n_blocks, 1, block_size, 1280)`` each; the
other four are a SLOT'S OWN, whatever its length (``slot_spec``): the
window layers' rings, ``((n_slots + 1) * window / block_size, P,
block_size, 1280)`` (a slot's ring is ``window / block_size`` blocks of
the same shape, the first ring is the sink), and the Mamba layers'
convolution and scan states, ``(P + 1, n_slots, 8, inner)`` (the
``d_conv - 1`` inputs in a whole sublane tile) and ``(P + 1, n_slots,
d_state, inner)`` float32, layer-major: a tick reads a layer's states
of every lane, a chunk one lane's. Every minor dimension
is whole 128-lane tiles.

**A prefill chunk** runs every row through the self-decoder and layer
``L/2``, and layer ``L/2 + 1`` as far as its key/value projection,
written to the pool; only a chunk that ends the prompt runs the rest,
for its last real row: layer ``L/2 + 1``'s attention and the
cross-decoder, whose queries read the pool through the decode kernel,
and the head. A padded row enters no state and no ring.

**A decode tick** runs every layer for one token a lane: the Mamba step
on the lane's states, the window layers over its ring, and layer ``L/2
+ 1`` and the cross-attention layers over its table, all with
``ops/hybrid_attention.py``'s kernel; new rows reach the ring and the
pool through ``ops/pool_write.py``.

What ``config.json`` does not fix is listed in the benchmark's
configuration file under ``assumed`` and is the default here. Weights
and matmul operands are ``cfg.dtype``; the residual stream, the norms,
the softmaxes, the states and the scan are float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import NEG_INF
from ..ops.hybrid_attention import decode_walk, hybrid_decode, wide_queries
from ..ops.pool_write import live_lanes, pool_write_rows
from ..ops.selective_scan import selective_scan, selective_step
from .mla import _embed, _gated_mlp, _into_residual
from .serving_api import ServingModel

__all__ = ["SambaYConfig", "phi4_mini_flash", "sambay_tiny", "sambay_init",
           "sambay_param_shapes", "sambay_param_specs",
           "sambay_prefill_chunk", "sambay_decode_step_paged"]

F32 = jnp.float32


@dataclasses.dataclass
class SambaYConfig:
    vocab_size: int = 200064
    hidden: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    ffn: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    seq_len: int = 262144            # the engine's cap a slot
    ln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # weights' and matmul operands' type
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(f"n_layers={self.n_layers}: SambaY needs a "
                             "multiple of 4, at least 8")
        if self.n_heads != 2 * self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} key heads: differential "
                             "attention pairs two query pairs a key pair")

    @property
    def inner(self):
        return self.expand * self.hidden

    @property
    def pairs(self):
        """Mamba/window pairs of the self-decoder; the cross-decoder has
        one pair fewer of GMU/cross-attention."""
        return self.n_layers // 4

    @property
    def n_v(self):
        """Value heads, each ``2 * head_dim`` wide."""
        return self.n_kv_heads // 2

    def serving_model(self):
        return _SERVING


def phi4_mini_flash(**kw):
    """``microsoft/Phi-4-mini-flash-reasoning`` at its published sizes,
    which are the defaults."""
    return SambaYConfig(**kw)


def sambay_tiny(**kw):
    """A toy of the same block for CPU tests: 8 layers (Mamba, window,
    Mamba, window, Mamba with memory, full, GMU, cross), hidden 64, 8
    query heads over 4 key heads of 8, window 8."""
    base = dict(vocab_size=256, hidden=64, n_layers=8, n_heads=8,
                n_kv_heads=4, head_dim=8, ffn=128, window=8, d_state=4,
                dt_rank=8, seq_len=512, dtype=jnp.float32,
                param_dtype=jnp.float32)
    base.update(kw)
    return SambaYConfig(**base)


# -- parameters ---------------------------------------------------------------

def _layer_shapes(cfg: SambaYConfig, kind: str):
    H, F, Di, d = cfg.hidden, cfg.ffn, cfg.inner, cfg.head_dim
    N, R, K = cfg.d_state, cfg.dt_rank, cfg.d_conv
    nq, nkv = cfg.n_heads * d, cfg.n_kv_heads * d
    out = {"ln1_w": (H,), "ln1_b": (H,), "ln2_w": (H,), "ln2_b": (H,),
           "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}
    if kind == "ssm":
        out.update(in_x=(H, Di), in_z=(H, Di), conv_w=(K, Di),
                   conv_b=(Di,), x_dt=(Di, R), x_b=(Di, N), x_c=(Di, N),
                   dt_w=(R, Di), dt_b=(Di,), a_log=(N, Di), d=(Di,),
                   out=(Di, H))
    elif kind == "gmu":
        out.update(w_in=(H, Di), w_out=(Di, H))
    else:                       # "attn" (window or full) or "xattn"
        out.update(wq=(H, nq), bq=(nq,), wo=(nq, H), bo=(H,), lq1=(d,),
                   lk1=(d,), lq2=(d,), lk2=(d,), subln=(2 * d,))
        if kind == "attn":
            out.update(wkv=(H, 2 * nkv), bkv=(2 * nkv,))
    return out


def sambay_param_shapes(cfg: SambaYConfig):
    """The parameter tree's shapes: the self-decoder's pairs and the
    cross-decoder's stacked on a leading axis, layers ``L/2`` (``mem``)
    and ``L/2 + 1`` (``kv``) on their own."""
    def stack(n, kind):
        return {k: (n,) + s for k, s in _layer_shapes(cfg, kind).items()}

    P = cfg.pairs
    return {"wte": (cfg.vocab_size, cfg.hidden), "lnf_w": (cfg.hidden,),
            "lnf_b": (cfg.hidden,),
            "self": {"ssm": stack(P, "ssm"), "attn": stack(P, "attn")},
            "mem": _layer_shapes(cfg, "ssm"),
            "kv": _layer_shapes(cfg, "attn"),
            "cross": {"gmu": stack(P - 1, "gmu"),
                      "xattn": stack(P - 1, "xattn")}}


def _draw(cfg: SambaYConfig, name, shape, key, std):
    """One leaf of the seeded initialisation (the configuration file's
    ``assumed.init`` says the same)."""
    N, K = cfg.d_state, cfg.d_conv
    if name in ("ln1_w", "ln2_w", "lnf_w", "subln", "d"):
        return jnp.ones(shape, F32)
    if name in ("ln1_b", "ln2_b", "lnf_b"):
        return jnp.zeros(shape, F32)
    if name == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], shape)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1
    if name == "wte":
        return jax.random.normal(key, shape, F32)
    scale = {"conv_w": 1.0 / math.sqrt(K), "lq1": 0.1, "lk1": 0.1,
             "lq2": 0.1, "lk2": 0.1}.get(name, std)
    if name in ("out", "wo", "w_down", "w_out"):
        scale = std / math.sqrt(2 * cfg.n_layers)
    return scale * jax.random.normal(key, shape, F32)


def sambay_init(cfg: SambaYConfig, seed: int = 0, std: float = 0.02):
    """Seeded weights in the tree of :func:`sambay_param_shapes`."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        sambay_param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = [_draw(cfg, path[-1].key, shape, k, std).astype(cfg.param_dtype)
           for k, (path, shape) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


def sambay_param_specs(cfg: SambaYConfig):
    """Every leaf replicated: this model runs on one chip."""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), sambay_param_shapes(cfg),
                                  is_leaf=lambda s: isinstance(s, tuple))


# -- the cache ----------------------------------------------------------------

def sambay_pool_spec(cfg: SambaYConfig, n_blocks: int, block_size: int):
    """The paged arrays: layer ``L/2 + 1``'s key and value rows."""
    row = cfg.n_kv_heads * cfg.head_dim
    return tuple(jax.ShapeDtypeStruct((n_blocks, 1, block_size, row),
                                      cfg.dtype) for _ in range(2))


def sambay_slot_spec(cfg: SambaYConfig, n_slots: int, block_size: int):
    """A slot's own arrays: the window rings (key, value), then the
    Mamba layers' convolution and scan states."""
    if cfg.window % block_size:
        raise ValueError(f"window {cfg.window} is not whole blocks of "
                         f"{block_size}")
    row = cfg.n_kv_heads * cfg.head_dim
    ring = ((n_slots + 1) * (cfg.window // block_size), cfg.pairs,
            block_size, row)
    m = cfg.pairs + 1
    return (jax.ShapeDtypeStruct(ring, cfg.dtype),
            jax.ShapeDtypeStruct(ring, cfg.dtype),
            jax.ShapeDtypeStruct((m, n_slots, _conv_rows(cfg), cfg.inner),
                                 F32),
            jax.ShapeDtypeStruct((m, n_slots, cfg.d_state, cfg.inner), F32))


def _conv_rows(cfg: SambaYConfig):
    """Rows a convolution state is stored in: its ``d_conv - 1`` inputs,
    then zeros to a whole sublane tile (a 3-row array is laid out anew
    around every update at a slot)."""
    return -(-(cfg.d_conv - 1) // 8) * 8


def _tick_args(cfg: SambaYConfig, contexts):
    """What a tick's span records beside ``state_slots_live``: the rows
    the shared pool's readers see, and the rows of the window rings."""
    return {"shared_kv_contexts": int(np.sum(contexts)),
            "window_rows": int(np.minimum(contexts, cfg.window).sum())}


# -- the pieces of a layer ----------------------------------------------------

@jax.named_scope("ln")
def _ln(x, w, b, eps, out):
    x32 = x.astype(F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(F32) + b.astype(F32)).astype(out)


def _mlp(cfg: SambaYConfig, p, x):
    z = _ln(x, p["ln2_w"], p["ln2_b"], cfg.ln_eps, cfg.dtype)
    return x + _gated_mlp(cfg.dtype, z, p["w_gate"], p["w_up"], p["w_down"])


def _mm(a, w, cd):
    return jnp.matmul(a.astype(cd), w.astype(cd), preferred_element_type=F32)


def _ssm_in(cfg: SambaYConfig, p, x):
    """x (T, H) stream -> the pre-convolution ``x`` and the gate ``z``
    (T, inner) float32."""
    u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.ln_eps, cfg.dtype)
    return _mm(u, p["in_x"], cfg.dtype), _mm(u, p["in_z"], cfg.dtype)


def _ssm_dbc(cfg: SambaYConfig, p, xs):
    """xs (T, inner) after the convolution -> dt (T, inner), B, C (T, N),
    A (N, inner), D (inner,), all float32."""
    cd = cfg.dtype
    dt = jax.nn.softplus(_mm(_mm(xs, p["x_dt"], cd), p["dt_w"], cd)
                         + p["dt_b"].astype(F32))
    A = -jnp.exp(p["a_log"].astype(F32))
    return (dt, _mm(xs, p["x_b"], cd), _mm(xs, p["x_c"], cd), A,
            p["d"].astype(F32))


def _ssm_out(cfg: SambaYConfig, p, y, z):
    return _into_residual((y * jax.nn.silu(z)).astype(cfg.dtype), p["out"])


def _lambda(p, depth):
    """(lambda, lambda_init) of the layer at ``depth`` (float32)."""
    init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    dot = lambda a, b: jnp.sum(p[a].astype(F32) * p[b].astype(F32))  # noqa
    return jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + init, \
        init


def _diff_out(cfg: SambaYConfig, p, a, depth):
    """a (T, nq, dv): every query head's read-out over its value head,
    ``q1`` and ``q2`` of each pair side by side -> the layer's output
    into the residual stream (T, H)."""
    T = a.shape[0]
    a = a.reshape(T, cfg.n_heads // 2, 2, -1)
    lam, init = _lambda(p, depth)
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg.ln_eps) * p["subln"].astype(F32)
    o = (o * (1.0 - init)).reshape(T, -1).astype(cfg.dtype)
    return _into_residual(o, p["wo"]) + p["bo"].astype(F32)


def _diff_rows(cfg: SambaYConfig, q, k, v, mask):
    """The composed read-outs over explicit rows: q (T, nq, d); k (S,
    n_kv, d); v (S, n_v, dv); mask (T, S). -> (T, nq, dv) float32, head
    ``j`` over value head ``j // 4`` (``ops/hybrid_attention``'s order)."""
    T, nq, d = q.shape
    S, nv = k.shape[0], cfg.n_v
    qg = q.reshape(T, nv, 2, 2, d)                 # (t, g, pair, half, d)
    kg = k.reshape(S, nv, 2, d)                    # (s, g, half, d)
    s = jnp.einsum("tgaid,sgid->gaits", qg, kg.astype(q.dtype),
                   preferred_element_type=F32) / math.sqrt(d)
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("gaits,sgv->tgaiv", w.astype(v.dtype), v,
                   preferred_element_type=F32)
    return o.reshape(T, nq, -1)


def _query(cfg: SambaYConfig, p, u):
    """u (T, H) normed -> the query heads (T, nq, d), ``cfg.dtype``."""
    q = _mm(u, p["wq"], cfg.dtype) + p["bq"].astype(F32)
    return q.astype(cfg.dtype).reshape(u.shape[0], cfg.n_heads, -1)


def _keys_values(cfg: SambaYConfig, p, u):
    """u (T, H) normed -> k, v rows (T, n_kv * d) each, ``cfg.dtype``."""
    kv = (_mm(u, p["wkv"], cfg.dtype) + p["bkv"].astype(F32)).astype(
        cfg.dtype)
    w = cfg.n_kv_heads * cfg.head_dim
    return kv[:, :w], kv[:, w:]


def _read_pool(cfg: SambaYConfig, p, u, depth, pool, tables, lengths,
               layer, walk):
    """The queries of ``u`` (B, H) normed over the rows ``tables`` name
    in ``pool`` (key, value) -> the layer's output (B, H)."""
    q = _query(cfg, p, u)
    a = hybrid_decode(wide_queries(q, cfg.n_kv_heads), pool[0], pool[1],
                      tables, lengths, layer, cfg.n_v,
                      1.0 / math.sqrt(cfg.head_dim), walk=walk)
    return _diff_out(cfg, p, a, depth)


@jax.named_scope("gmu")
def _gmu(cfg: SambaYConfig, p, x, m):
    u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.ln_eps, cfg.dtype)
    g = jax.nn.silu(_mm(u, p["w_in"], cfg.dtype)) * m
    return x + _into_residual(g.astype(cfg.dtype), p["w_out"])


def _tail(cfg: SambaYConfig, params, x, m, pool, tables, lengths, walk):
    """Layer ``L/2 + 1`` from its attention on, the cross-decoder and
    the head, for rows x (B, H) whose keys and values are in ``pool``
    (written). m (B, inner): the memory. -> logits (B, V) float32."""
    kv = params["kv"]
    with jax.named_scope("attn"):
        u = _ln(x, kv["ln1_w"], kv["ln1_b"], cfg.ln_eps, cfg.dtype)
        x = x + _read_pool(cfg, kv, u, 2 * cfg.pairs + 1, pool, tables,
                           lengths, 0, walk)
    x = _mlp(cfg, kv, x)

    def pair(x, inp):
        p, depth = inp
        x = _mlp(cfg, p["gmu"], _gmu(cfg, p["gmu"], x, m))
        xa = p["xattn"]
        with jax.named_scope("attn"):
            u = _ln(x, xa["ln1_w"], xa["ln1_b"], cfg.ln_eps, cfg.dtype)
            x = x + _read_pool(cfg, xa, u, depth, pool, tables, lengths, 0,
                               walk)
        return _mlp(cfg, xa, x), None

    first = 2 * cfg.pairs + 3            # depth of the first cross layer
    depths = first + 2 * jnp.arange(cfg.pairs - 1, dtype=F32)
    x, _ = jax.lax.scan(pair, x, (params["cross"], depths))
    return _head(cfg, params, x)


@jax.named_scope("head")
def _head(cfg: SambaYConfig, params, x):
    x = _ln(x, params["lnf_w"], params["lnf_b"], cfg.ln_eps, cfg.dtype)
    return jnp.einsum("th,vh->tv", x, params["wte"].astype(cfg.dtype),
                      preferred_element_type=F32)


# -- a prefill chunk ----------------------------------------------------------

def _slot_row(a, slot, li):
    """``a[li, slot]`` of a Mamba state array, read in place (a slice,
    no gather)."""
    return jax.lax.dynamic_slice(a, (li, slot, 0, 0),
                                 (1, 1) + a.shape[2:])[0, 0]


def _slot_put(a, value, slot, li):
    """``a[li, slot] = value``, in place (an update slice, no scatter:
    a scatter makes XLA lay the whole array out anew)."""
    return jax.lax.dynamic_update_slice(a, value[None, None].astype(a.dtype),
                                        (li, slot, 0, 0))


def _ssm_chunk(cfg: SambaYConfig, p, x, conv, ssm, slot, li, fresh, n_true):
    """One Mamba layer over a chunk's rows x (C, H); the slot's states
    at index ``li`` are read (zeros where ``fresh``) and written back
    with what the first ``n_true`` rows leave. -> (x, conv, ssm, y)."""
    with jax.named_scope("ssm"):
        xin, z = _ssm_in(cfg, p, x)
        C, K = x.shape[0], cfg.d_conv
        prev = jnp.where(fresh, 0.0, _slot_row(conv, slot, li)[:K - 1])
        xx = jnp.concatenate([prev, xin], axis=0)          # (C + K - 1, Di)
        w = p["conv_w"].astype(F32)
        xs = jax.nn.silu(p["conv_b"].astype(F32) + sum(
            w[k] * xx[k:k + C] for k in range(K)))
        dt, B, Cm, A, D = _ssm_dbc(cfg, p, xs)
        s0 = jnp.where(fresh, 0.0, _slot_row(ssm, slot, li))
        y, s1 = selective_scan(xs, dt, A, B, Cm, D, s0, n_true)
        rows = jax.lax.dynamic_slice_in_dim(xx, n_true, K - 1, 0)
        conv = _slot_put(conv, jnp.pad(rows, ((0, conv.shape[2] - K + 1),
                                              (0, 0))), slot, li)
        ssm = _slot_put(ssm, s1, slot, li)
        x = x + _ssm_out(cfg, p, y, z)
    return x, conv, ssm, y


def _window_chunk(cfg: SambaYConfig, p, x, ring, base, li, depth, start,
                  n_true):
    """One window layer over a chunk's rows x (C, H): the queries read
    the slot's ring (blocks ``base ..``, layer ``li``) and the chunk's
    own rows, keys ``t - window + 1 .. t``; the ring then keeps the
    newest ``window`` real rows. -> (x, ring)."""
    W, C = cfg.window, x.shape[0]
    with jax.named_scope("attn"):
        u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.ln_eps, cfg.dtype)
        q = _query(cfg, p, u)
        k, v = _keys_values(cfg, p, u)
        rk, rv = ring
        nb, bs = W // rk.shape[2], rk.shape[2]
        with jax.named_scope("kv_pool"):
            old = [jax.lax.dynamic_slice(
                a, (base, li, 0, 0), (nb, 1, bs, a.shape[3])).reshape(W, -1)
                for a in (rk, rv)]
        r = jnp.arange(W)
        # ring row r holds the newest position before the chunk that is
        # r modulo the window; none where that would be negative
        pr = start - 1 - jnp.mod(start - 1 - r, W)
        t = jnp.arange(C)
        kpos = jnp.concatenate([pr, start + t])
        kval = jnp.concatenate([pr >= 0, t < n_true])
        qpos = start + t
        mask = kval[None] & (kpos[None] <= qpos[:, None]) \
            & (kpos[None] > qpos[:, None] - W)
        keys = jnp.concatenate([old[0], k]).reshape(W + C, cfg.n_kv_heads, -1)
        vals = jnp.concatenate([old[1], v]).reshape(W + C, cfg.n_v, -1)
        a = _diff_rows(cfg, q, keys, vals, mask)
        x = x + _diff_out(cfg, p, a, depth)
        # the chunk row that lands in ring row r: the newest real one
        # that is r modulo the window
        src = n_true - 1 - jnp.mod(start + n_true - 1 - r, W)
        keep = (src >= 0)[:, None]
        with jax.named_scope("kv_pool"):
            new = [jnp.where(keep, jnp.take(c, jnp.clip(src, 0, C - 1),
                                            axis=0), o)
                   for c, o in zip((k, v), old)]
            ring = tuple(jax.lax.dynamic_update_slice(
                a, n.reshape(nb, 1, bs, -1), (base, li, 0, 0))
                for a, n in zip((rk, rv), new))
    return x, ring


def _write_blocks(pool, rows, table_row, start):
    """A chunk's rows (C, width) each into the pool's whole blocks that
    the table names from ``start`` on (padding included: a row past the
    slot's length is never read)."""
    bs = pool[0].shape[2]
    C = rows[0].shape[0]
    out = list(pool)
    with jax.named_scope("kv_pool"):
        for j in range(C // bs):
            blk = table_row[start // bs + j]
            for i, r in enumerate(rows):
                out[i] = jax.lax.dynamic_update_slice(
                    out[i], r[j * bs:(j + 1) * bs][None, None],
                    (blk, 0, 0, 0))
    return tuple(out)


def sambay_prefill_chunk(cfg: SambaYConfig, params, pool, table_row, tokens,
                         start, n_true, slot, last):
    """One chunk of a chunked prefill: tokens (1, C) end-padded, the
    first ``n_true`` real; ``slot`` names the slot's own arrays,
    ``table_row`` its blocks; ``start == 0`` begins from zero states and
    an empty ring, whatever the slot held. ``last``: the chunk ends the
    prompt. -> (logits (1, 1, V) float32 of the last real token where
    ``last``, zeros where not; pool)."""
    kp, vp, rk, rv, conv, ssm = pool
    bs = kp.shape[2]
    fresh = start == 0
    base = (slot + 1) * (cfg.window // bs)
    x = _embed(cfg, params, tokens[0])

    def pair(carry, inp):
        x, ring, conv, ssm = carry
        p, li = inp
        x, conv, ssm, _ = _ssm_chunk(cfg, p["ssm"], x, conv, ssm, slot, li,
                                     fresh, n_true)
        x = _mlp(cfg, p["ssm"], x)
        x, ring = _window_chunk(cfg, p["attn"], x, ring, base, li,
                                2.0 * li + 1, start, n_true)
        return (_mlp(cfg, p["attn"], x), ring, conv, ssm), None

    (x, (rk, rv), conv, ssm), _ = jax.lax.scan(
        pair, (x, (rk, rv), conv, ssm),
        (params["self"], jnp.arange(cfg.pairs)))
    x, conv, ssm, mem = _ssm_chunk(cfg, params["mem"], x, conv, ssm, slot,
                                   cfg.pairs, fresh, n_true)
    x = _mlp(cfg, params["mem"], x)
    kv = params["kv"]
    with jax.named_scope("attn"):
        u = _ln(x, kv["ln1_w"], kv["ln1_b"], cfg.ln_eps, cfg.dtype)
        kp, vp = _write_blocks((kp, vp), _keys_values(cfg, kv, u),
                               table_row, start)

    def tail(ops):
        x, mem, kp, vp = ops
        at = n_true - 1
        xr = jax.lax.dynamic_slice_in_dim(x, at, 1, 0)
        mr = jax.lax.dynamic_slice_in_dim(mem, at, 1, 0)
        lengths = jnp.reshape(start + n_true, (1,))
        tables = table_row[None]
        walk = decode_walk(lengths, tables.shape[1], bs)
        return _tail(cfg, params, xr, mr, (kp, vp), tables, lengths,
                     walk)[None]

    logits = jax.lax.cond(
        last, tail, lambda ops: jnp.zeros((1, 1, cfg.vocab_size), F32),
        (x, mem, kp, vp))
    return logits, (kp, vp, rk, rv, conv, ssm)


# -- a decode tick ------------------------------------------------------------

def _ssm_step(cfg: SambaYConfig, p, x, conv, ssm, li, live):
    """One Mamba layer for one token a lane; the lanes' states at index
    ``li`` move where ``live``. -> (x, conv, ssm, y)."""
    with jax.named_scope("ssm"):
        xin, z = _ssm_in(cfg, p, x)
        K = cfg.d_conv
        cs = jax.lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
        ss = jax.lax.dynamic_index_in_dim(ssm, li, 0, keepdims=False)
        xx = jnp.concatenate([cs[:, :K - 1], xin[:, None]], axis=1)
        xs = jax.nn.silu(p["conv_b"].astype(F32) + jnp.sum(
            p["conv_w"].astype(F32) * xx, axis=1))
        dt, B, Cm, A, D = _ssm_dbc(cfg, p, xs)
        y, s1 = selective_step(xs, dt, A, B, Cm, D, ss)
        keep = live[:, None, None]
        new = jnp.pad(xx[:, 1:], ((0, 0), (0, cs.shape[1] - K + 1), (0, 0)))
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(keep, new, cs), li, 0)
        ssm = jax.lax.dynamic_update_index_in_dim(
            ssm, jnp.where(keep, s1, ss), li, 0)
        x = x + _ssm_out(cfg, p, y, z)
    return x, conv, ssm, y


def sambay_decode_step_paged(cfg: SambaYConfig, params, pool, tables,
                             positions, tokens):
    """Batched one-token decode (the contract of
    ``gpt_decode_step_paged``): lane ``b`` is slot ``b``; tables (B, W),
    positions and tokens (B,). A lane whose table names the sink holds
    no request, or one still in prefill: it moves no state and writes no
    row. -> (logits (B, V) float32, pool)."""
    kp, vp, rk, rv, conv, ssm = pool
    B = tokens.shape[0]
    bs, W = kp.shape[2], cfg.window
    nb = W // bs
    live = tables[:, 0] > 0
    ctx = jnp.where(live, positions + 1, 0)
    lanes = live_lanes(ctx)
    base = jnp.where(live, (jnp.arange(B) + 1) * nb, 0)
    ring_tables = base[:, None] + jnp.arange(nb)
    ring_len = jnp.minimum(ctx, W)
    ring_walk = decode_walk(ring_len, nb, bs)
    ring_blk = base + jnp.mod(positions, W) // bs
    off = jnp.mod(positions, bs)
    pool_blk = jnp.take_along_axis(tables, (positions // bs)[:, None],
                                   axis=1)[:, 0]
    pool_walk = decode_walk(ctx, tables.shape[1], bs)
    x = _embed(cfg, params, tokens)

    def pair(carry, inp):
        x, ring, conv, ssm = carry
        p, li = inp
        x, conv, ssm, _ = _ssm_step(cfg, p["ssm"], x, conv, ssm, li, live)
        x = _mlp(cfg, p["ssm"], x)
        pa = p["attn"]
        with jax.named_scope("attn"):
            u = _ln(x, pa["ln1_w"], pa["ln1_b"], cfg.ln_eps, cfg.dtype)
            with jax.named_scope("kv_pool"):
                ring = pool_write_rows(ring, _keys_values(cfg, pa, u),
                                       ring_blk, off, li, lanes)
            x = x + _read_pool(cfg, pa, u, 2.0 * li + 1, ring, ring_tables,
                               ring_len, li, ring_walk)
        return (_mlp(cfg, pa, x), ring, conv, ssm), None

    (x, (rk, rv), conv, ssm), _ = jax.lax.scan(
        pair, (x, (rk, rv), conv, ssm),
        (params["self"], jnp.arange(cfg.pairs)))
    x, conv, ssm, mem = _ssm_step(cfg, params["mem"], x, conv, ssm,
                                  cfg.pairs, live)
    x = _mlp(cfg, params["mem"], x)
    kv = params["kv"]
    with jax.named_scope("attn"):
        u = _ln(x, kv["ln1_w"], kv["ln1_b"], cfg.ln_eps, cfg.dtype)
        with jax.named_scope("kv_pool"):
            kp, vp = pool_write_rows((kp, vp), _keys_values(cfg, kv, u),
                                     pool_blk, off, 0, lanes)
    logits = _tail(cfg, params, x, mem, (kp, vp), tables, ctx, pool_walk)
    return logits, (kp, vp, rk, rv, conv, ssm)


_CANNOT = ("SambaY models (SambaYConfig) cannot {what} yet: {why}")
_SERVING = ServingModel(
    name="sambay",
    pool_spec=sambay_pool_spec,
    param_specs=sambay_param_specs,
    prefill_chunk=sambay_prefill_chunk,
    decode_step_paged=sambay_decode_step_paged,
    slot_spec=sambay_slot_spec,
    chunk_pad=lambda cfg: cfg.window,
    last_only=True,
    tick_args=_tick_args,
    refuses={
        "draft": _CANNOT.format(
            what="take draft=",
            why="a verify step folds every proposed token into the Mamba "
                "states and the window rings, and there is no roll-back of "
                "them to the last accepted token"),
        "prefix_cache": _CANNOT.format(
            what="use prefix_cache",
            why="a prefix is reusable only with a snapshot of the Mamba "
                "states and the window rings at its last token, and none "
                "is kept: a slot holds one of each"),
        "int8_weights": _CANNOT.format(
            what="take int8_weights",
            why="there is no quantized layout for its projections"),
        "mesh": _CANNOT.format(
            what="take mesh=",
            why="its slot arrays and its kernels have no sharded layout, "
                "and it has no sharded parameter specs"),
    })
