"""The plain reference: a GPT-2-style decoder in straightforward
``jax.numpy`` and float32, with no kernel, cache or batching trick
(moved here from ``benchmarks/lib/reference.py`` letter for letter; the
two entry points now take the configuration's ``sizes`` and read the
head count from them).

It imports nothing of the program. Departures from the published models
are the program's own and are listed in the configuration files: learned
positions, pre-LN blocks, tanh-approximated GELU, a tied output head,
next-token cross entropy.

``lowp`` computes every matmul's operands in a lower precision (the
control of "How correct is decided"): "bf16", "fp8" (e4m3) or None.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import F32, _mm, _q, _sq_sums, adamw


def _ln(x, s, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * s + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(n_heads, lowp, x, p):
    """x (B, S, H) float32; p one layer's leaves, any float type."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    B, S, H = x.shape
    hd = H // n_heads
    h = _ln(x, p["ln1_s"], p["ln1_b"])
    qkv = _mm(h, p["qkv_w"], lowp) + p["qkv_b"]
    q, k, v = (t.reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bhqd,bhkd->bhqk", _q(q, lowp), _q(k, lowp),
                   precision="highest") / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", _q(w, lowp), _q(v, lowp),
                   precision="highest")
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H)
    x = x + _mm(o, p["proj_w"], lowp) + p["proj_b"]
    h = _ln(x, p["ln2_s"], p["ln2_b"])
    h = _gelu(_mm(h, p["fc_w"], lowp) + p["fc_b"])
    return x + _mm(h, p["out_w"], lowp) + p["out_b"]


def hidden(params, tokens, n_heads, lowp=None):
    """tokens (B, S) -> final layer-normed hidden states (B, S, H)."""
    S = tokens.shape[1]
    x = params["wte"].astype(F32)[tokens] + params["wpe"].astype(F32)[:S]

    def step(x, p):
        return _block(n_heads, lowp, x, p), None

    x, _ = jax.lax.scan(step, x, params["blocks"])
    return _ln(x, params["lnf_s"].astype(F32), params["lnf_b"].astype(F32))


def logits(params, tokens, n_heads, lowp=None):
    x = hidden(params, tokens, n_heads, lowp)
    return _mm(x, params["wte"].astype(F32).T, lowp)


def loss(params, tokens, labels, n_heads, lowp=None):
    """Mean next-token cross entropy over every position of every row."""
    lg = logits(params, tokens, n_heads, lowp)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


# -- training: gradients in blocks of rows, AdamW --------------------------

@functools.partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grad(params, tokens, labels, n_heads, lowp):
    return jax.value_and_grad(loss)(params, tokens, labels, n_heads, lowp)


def loss_and_grad(params, tokens, labels, n_heads, rows, lowp=None):
    """Mean loss and its gradient over all rows, ``rows`` at a time."""
    n = tokens.shape[0]
    if n % rows:
        raise ValueError(f"{rows} rows do not divide the batch of {n}")
    total, grads = 0.0, None
    for i in range(0, n, rows):
        l, g = _loss_and_grad(params, tokens[i:i + rows],
                              labels[i:i + rows], n_heads, lowp)
        total += float(l)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    k = n // rows
    return total / k, jax.tree_util.tree_map(lambda g: g / k, grads)


def leaf_norms(tree):
    """{leaf path: L2 norm}. The fused ``qkv`` leaves count as three
    (query, key, value thirds of the last axis): a key's bias has no
    gradient under softmax, and must be a leaf of its own for the rule
    that leaves such leaves out."""
    parts = {}
    for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(k)
        if "qkv" in name:
            for tag, third in zip("qkv", jnp.split(v, 3, axis=-1)):
                parts[f"{name}.{tag}"] = third
        else:
            parts[name] = v
    return {k: float(np.sqrt(np.float64(x)))
            for k, x in _sq_sums(parts).items()}


def train_readings(params0, batches, sizes, hyper, rows, lowp=None,
                   drop_half=False):
    """Follow the first len(batches) steps. Returns the losses, the leaf
    norms of the first gradient, and the leaf norms of the parameters'
    change after the last step. ``drop_half`` plants the fault "half of
    the batch left out, the mean taken over the rest"."""
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params0)
    start = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses, grad_norms = [], None
    for tokens, labels in batches:
        if drop_half:
            tokens, labels = tokens[:len(tokens) // 2], \
                labels[:len(labels) // 2]
        l, g = loss_and_grad(params, jnp.asarray(tokens),
                             jnp.asarray(labels), sizes["n_heads"],
                             min(rows, len(tokens)), lowp)
        losses.append(l)
        if grad_norms is None:
            grad_norms = leaf_norms(g)
        params, m, v, count = adamw(
            params, g, m, v, count, F32(hyper["lr"]), F32(hyper["beta1"]),
            F32(hyper["beta2"]), F32(hyper["eps"]),
            F32(hyper["weight_decay"]))
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# -- serving: the gap of each served token under the reference -------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _token_gaps(params, tokens, n_heads, lowp):
    """tokens (1, S). For every position i: the reference's best logit
    minus its logit of tokens[i+1] (``gap``), and — for the control —
    the same for the token a ``lowp`` forward pass puts first."""
    ref = logits(params, tokens, n_heads, None)[0]            # (S, V)
    best = jnp.max(ref, axis=-1)
    nxt = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])
    gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    if lowp is None:
        return gap, gap
    low = jnp.argmax(logits(params, tokens, n_heads, lowp)[0], axis=-1)
    low_gap = best - jnp.take_along_axis(ref, low[:, None], -1)[:, 0]
    return gap, low_gap


def served_gaps(params, prompt, served, sizes, pad_to, lowp=None):
    """Per served token, how far its reference logit lies below the
    reference's best at that position. One forward pass over the prompt
    and the served tokens, end-padded to ``pad_to`` (causal: padding
    cannot reach back). Returns (gaps, control gaps), each len(served).
    """
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad_to={pad_to}")
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(seq)] = seq
    gap, low = _token_gaps(params, jnp.asarray(buf), sizes["n_heads"],
                           lowp)
    lo, hi = len(prompt) - 1, len(seq) - 1
    return np.asarray(gap[lo:hi], np.float64), \
        np.asarray(low[lo:hi], np.float64)
