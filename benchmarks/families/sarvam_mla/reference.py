"""The plain reference: the latent-attention expert block in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
absorbed weights, no grouped matmul, no batching. It imports nothing of
the program and follows the published description (``config.json`` of
``sarvamai/sarvam-105b`` and the equations of DeepSeek-V2/V3 that
``model_type: sarvam_mla`` names), layer by layer:

    h = x + Attn(N1(x));  y = h + FFN(N2(h));  logits = head(Nf(y))
    q_i = W_q^i u = [q_i^nope; q_i^rope]; [c_raw; k_raw^rope] = W_kva u
    c = N_kv(c_raw); k^rope = RoPE_t(k_raw^rope); q_i^rope = RoPE_t(.)
    [k_i^nope; v_i] = W_kvb^i c
    s_i(t, j) = (q_i^nope(t).k_i^nope(j) + q_i^rope(t).k^rope(j))
                * (dn + dr)^-1/2 * m^2,  m = 0.1 mscale_all_dim ln(40) + 1
    o_i = sum_j softmax_j<=t(s_i) v_i(j);  Attn = W_o [o_1; ...]
    dense FFN: W_down(silu(W_gate z) * W_up z)
    expert FFN: s = sigmoid(W_r z); S = top-8 of s + b (ties to the lower
    index); g_e = 2.5 s_e / sum_{S} s; sum_{e in S} g_e E_e(z) + E_shared(z)

Departures, each the configuration's (``assumed`` in its file): the
share (only experts ``[expert_offset, expert_offset + experts_held)``
contribute; ``S`` and ``g`` are over all experts, and what the absent
ones would add is left out, as in the program); the vocabulary slice;
rotary pairs (i, i + 32) (the checkpoint's interleaved pairs are a fixed
permutation of the rotary rows of W_q and W_kva).

It keeps the seed's bf16 weights (float32 copies of 5.4 B parameters
would not fit a chip) and lifts one layer, or one expert, to float32 at
a time. Queries go through attention in blocks of rows and heads in
groups only so that no (heads, S, S) tensor exists; every row still
sees every earlier row of the one sequence.

``lowp`` computes every matmul's operands in a lower precision (the
control of "How correct is decided"): "bf16", "fp8" (e4m3) or None. The
router's product stays float32 in the control too, as a deployment in
that precision would keep it.

**Which positions are judged.** The reference keeps its own routing.
Where, in some expert layer, the last chosen and the first unchosen
score lie within ``NEAR_TIE`` of each other, which expert comes eighth
is decided below the resolution of the precision the configuration
states (bf16 numbers near 0.9 lie 0.0039 apart), and the swap of one
routed expert moves the logits by more than the fp8 control moves them
anywhere else: at the cell's sizes 45% of positions have such a layer,
the program's widest gap there read up to 0.32 and at all other
positions at most 0.013 (PERF.md section 2, PR 30). ``served_gaps``
prints both and returns the gaps of the positions WITHOUT such a layer:
those are what ``correct`` judges, for the program and the control
alike.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import F32, _mm, _q

from .program import NO_TRAINING

NEAR_TIE = 1e-3      # a margin between the 8th and 9th score under this


def leaf_norms(tree):
    raise NotImplementedError(NO_TRAINING.format(name="leaf_norms"))


def train_readings(params0, batches, sizes, hyper, rows, lowp=None,
                   drop_half=False):
    raise NotImplementedError(NO_TRAINING.format(name="train_readings"))


# -- rotary positions: YaRN as ``deepseek_yarn`` defines it ------------------

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(s):
    """(dr / 2,) float64. Dimension d of dr turns orig_len * base^(-d/dr)
    / 2 pi times over the original context; those that turn more than
    ``beta_fast`` times keep the plain frequency, those under
    ``beta_slow`` are divided by ``factor``, a linear ramp between."""
    dim, base = s["qk_rope_dim"], s["rope_theta"]

    def dim_of(turns):
        return dim * math.log(s["rope_orig_len"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(s["rope_beta_fast"])), 0)
    high = min(math.ceil(dim_of(s["rope_beta_slow"])), dim - 1)
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / s["rope_factor"] * ramp + plain * (1 - ramp)


def softmax_scale(s):
    m = yarn_mscale(s["rope_factor"], s["rope_mscale_all_dim"])
    return (s["qk_nope_dim"] + s["qk_rope_dim"]) ** -0.5 * m * m


def _rope(x, s):
    """x (S, ..., dr) at positions 0..S-1."""
    S = x.shape[0]
    ang = jnp.arange(S, dtype=F32)[:, None] \
        * jnp.asarray(yarn_inv_freq(s), F32)
    m = yarn_mscale(s["rope_factor"], s["rope_mscale"]) \
        / yarn_mscale(s["rope_factor"], s["rope_mscale_all_dim"])
    shape = (S,) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), \
        (jnp.sin(ang) * m).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _frozen(s):
    return tuple(sorted((k, v) for k, v in s.items()
                        if isinstance(v, (int, float, str))))


# -- one layer's halves -------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention(x, p, sz, lowp):
    """x (S, H) float32 -> x + Attn(N1(x)); p: the layer's attention
    leaves, lifted here."""
    s = dict(sz)
    p = _f32(p)
    S = x.shape[0]
    nh, R = s["n_heads"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_dim"], s["qk_rope_dim"], s["v_head_dim"]
    u = _rms(x, p["ln1"], s["rms_eps"])
    q = _mm(u, p["wq"], lowp).reshape(S, nh, dn + dr)
    kva = _mm(u, p["wkva"], lowp)
    c = _rms(kva[:, :R], p["kv_norm"], s["rms_eps"])
    k_rope = _rope(kva[:, R:], s)                          # (S, dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], s)
    kv = _mm(c, p["wkvb"], lowp).reshape(S, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = softmax_scale(s)
    hg, qb = math.gcd(nh, 8), min(S, 1024)

    def heads(args):
        qn, qr, kn, vv = args          # (S, hg, .) a group of heads

        def rows(i):
            at = i * qb
            qn_b = jax.lax.dynamic_slice_in_dim(qn, at, qb, 0)
            qr_b = jax.lax.dynamic_slice_in_dim(qr, at, qb, 0)
            sc = (jnp.einsum("qhd,khd->hqk", _q(qn_b, lowp), _q(kn, lowp),
                             precision="highest")
                  + jnp.einsum("qhd,kd->hqk", _q(qr_b, lowp),
                               _q(k_rope, lowp), precision="highest")
                  ) * scale
            causal = (at + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
            w = jax.nn.softmax(jnp.where(causal[None], sc, -1e30), axis=-1)
            return jnp.einsum("hqk,khd->qhd", _q(w, lowp), _q(vv, lowp),
                              precision="highest")

        return jax.lax.map(rows, jnp.arange(S // qb)).reshape(S, hg, dv)

    split = lambda t: jnp.moveaxis(                        # noqa: E731
        t.reshape(S, nh // hg, hg, t.shape[-1]), 1, 0)
    o = jax.lax.map(heads, (split(q_nope), split(q_rope), split(k_nope),
                            split(v)))                     # (ng, S, hg, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(S, nh * dv)
    return x + _mm(o, p["wo"], lowp)


@functools.partial(jax.jit, static_argnums=(4,))
def _gated(z, w_gate, w_up, w_down, lowp):
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    return _mm(jax.nn.silu(_mm(z, w_gate, lowp)) * _mm(z, w_up, lowp),
               w_down, lowp)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _route(z, router_w, router_b, top_k, scale):
    """Scores, choice and gates over ALL experts; float32 throughout."""
    s = jax.nn.sigmoid(jnp.matmul(z, router_w.astype(F32),
                                  precision="highest"))
    biased = s + router_b.astype(F32)
    order = jnp.argsort(-biased, axis=-1, stable=True)     # ties: lower
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(s, chosen, -1)
    gates = scale * picked / jnp.sum(picked, -1, keepdims=True)
    ranked = jnp.take_along_axis(biased, order[:, :top_k + 1], -1)
    return chosen, gates, ranked[:, top_k - 1] - ranked[:, top_k]


def _expert_layer(x, p, s, lowp):
    """x + the held experts' part of the routed sum + the shared expert.
    One expert at a time, over the rows routed to it. Returns (x,
    margin (S,) between the last chosen and the first unchosen score)."""
    z = _rms(x, p["ln2"].astype(F32), s["rms_eps"])
    chosen, gates, margin = _route(z, p["router_w"], p["router_b"],
                                   s["top_k"], float(s["routed_scale"]))
    chosen, gates = np.asarray(chosen), np.asarray(gates)
    y = _gated(z, p["s_gate"], p["s_up"], p["s_down"], lowp)
    lo = s["expert_offset"]
    for e in range(s["experts_held"]):
        tok, rank = np.nonzero(chosen == lo + e)
        if tok.size == 0:
            continue
        n = max(256, 1 << (tok.size - 1).bit_length())   # few shapes
        rows = np.zeros(n, np.int64)
        rows[:tok.size] = tok
        g = np.zeros(n, np.float32)
        g[:tok.size] = gates[tok, rank]
        out = _gated(z[rows], p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                     lowp)
        y = y.at[rows].add(jnp.asarray(g)[:, None] * out)
    return x + y, margin


def logits_at(params, tokens, sizes, lo, hi, lowp=None):
    """Logits (hi - lo, V) at rows [lo, hi) of ONE sequence ``tokens``
    (S,), and per expert layer the routing margin at those rows."""
    s, sz = sizes, _frozen(sizes)
    x = params["wte"].astype(F32)[jnp.asarray(tokens)]
    layer = lambda tree, i: jax.tree_util.tree_map(       # noqa: E731
        lambda a: a[i], tree)
    attn_keys = ("ln1", "kv_norm", "wq", "wkva", "wkvb", "wo")
    margins = []
    for i in range(s["n_layers"]):
        dense = i < s["first_dense"]
        p = layer(params["dense"], i) if dense else \
            layer(params["moe"], i - s["first_dense"])
        x = _attention(x, {k: p[k] for k in attn_keys}, sz, lowp)
        if dense:
            z = _rms(x, p["ln2"].astype(F32), s["rms_eps"])
            x = x + _gated(z, p["w_gate"], p["w_up"], p["w_down"], lowp)
        else:
            x, margin = _expert_layer(x, p, s, lowp)
            margins.append(np.asarray(margin[lo:hi]))
    x = _rms(x[lo:hi], params["lnf"].astype(F32), s["rms_eps"])
    return _mm(x, params["head"].astype(F32), lowp), margins


def _padded(n):
    """Whole blocks of query rows (causal: padding cannot reach back)."""
    return -(-n // 2048) * 2048 if n > 1024 else -(-n // 64) * 64


def served_gaps(params, prompt, served, sizes, pad_to, lowp=None):
    """Per served token, how far its reference logit lies below the
    reference's best at that position. One forward pass over the prompt
    and the served tokens. Returns (gaps, control gaps) at the judged
    positions (those with no near tie in any expert layer: the module's
    docstring); with ``lowp`` the control is the token a forward pass in
    that precision puts first."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad_to={pad_to}")
    buf = np.zeros(_padded(len(seq)), np.int32)
    buf[:len(seq)] = seq
    lo, hi = len(prompt) - 1, len(seq) - 1
    with jax.default_matmul_precision("highest"):
        ref, margins = logits_at(params, buf, sizes, lo, hi)
        best = jnp.max(ref, axis=-1)
        nxt = jnp.asarray(seq[lo + 1:hi + 1])
        gap = np.asarray(best - jnp.take_along_axis(
            ref, nxt[:, None], -1)[:, 0], np.float64)
        low_gap = gap
        if lowp is not None:
            low = jnp.argmax(logits_at(params, buf, sizes, lo, hi, lowp)[0],
                             axis=-1)
            low_gap = np.asarray(best - jnp.take_along_axis(
                ref, low[:, None], -1)[:, 0], np.float64)
    tie = np.any(np.stack(margins) < NEAR_TIE, axis=0)       # (positions,)
    print(f"[bench] reference routing over {hi - lo} compared positions x "
          f"{len(margins)} expert layers: "
          f"{100.0 * float(np.mean(np.stack(margins) < NEAR_TIE)):.2f}% of "
          f"token-layers have the last chosen and the first unchosen score "
          f"within {NEAR_TIE}; widest gap at the {int(tie.sum())} positions "
          f"with such a tie (not judged) "
          f"{float(gap[tie].max(initial=0.0)):.4f}, at the others "
          f"{float(gap[~tie].max(initial=0.0)):.4f}", flush=True)
    judged = ~tie if not tie.all() else tie
    return gap[judged], low_gap[judged]
