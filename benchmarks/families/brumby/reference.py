"""The plain reference: the power-retention block in its ATTENTION form,
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no state, no ``phi``, no
chunks, no kernel, no batching. It imports nothing of the program and
shares no formulation with it (the program runs the recurrence; this
sums over the context), layer by layer:

    n = N1(x);  q_h = RoPE_t(N_q(W_q n)_h),  k_g = RoPE_t(N_k(W_k n)_g)
    v_g = (W_v n)_g;  lg = log sigmoid(W_g n) in R^8 (<= 0);  g = h // 5
    A_ij = (q_i . k_j / sqrt(d))^2 exp(sum_{s = j + 1 .. i} lg_s),  i >= j
    y_i  = sum_j A_ij v_j / (sum_j A_ij + eps)
    h = x + W_o [y_1; ...];  out = h + W_down(silu(W_gate m) * W_up m)
    logits = head(Nf(out))

Departures, each the configuration's (``assumed`` in its file): degree 2,
the normalised output and ``eps``, the bias-free gate, per-head RMSNorm
and rotary pairs (i, i + 64).

It keeps the seed's bf16 weights and lifts one layer to float32 at a
time. Queries go through the sum in blocks of rows only so that no
(heads, S, S) tensor exists; every row still sees every earlier row of
the one sequence. The gates' running sum is taken on the host in
float64 and handed to each block of rows relative to the block's first
row: a float32 running sum over 17,000 tokens resolves 0.001, which
would be this reference's error and not the program's.

``lowp`` computes every matmul's operands in a lower precision (the
control of "How correct is decided"): "bf16", "fp8" (e4m3) or None. The
gate's product stays float32 in the control too, as a deployment in
that precision would keep it.

**Which positions are judged.** ``y_i`` divides by the sum of the
weights. Where that sum is small in some head of some layer (one
squared product near zero is most of it), the quotient is decided below
the resolution of any precision under float32: a bf16 product of
unit-normed 128-wide heads is uncertain by 0.004 before it is squared.
``served_gaps`` records each position's smallest normaliser over layers
and heads, prints the widest gap on both sides of ``DEN_FLOOR``, and
returns the gaps of the positions at or above it (all of them while
``DEN_FLOOR`` is 0).
"""
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import F32, _mm, _q

from .program import NO_TRAINING

DEN_FLOOR = 0.0      # a position whose smallest normaliser is under this


def leaf_norms(tree):
    raise NotImplementedError(NO_TRAINING.format(name="leaf_norms"))


def train_readings(params0, batches, sizes, hyper, rows, lowp=None,
                   drop_half=False):
    raise NotImplementedError(NO_TRAINING.format(name="train_readings"))


def _rope(x, theta):
    """x (S, heads, d) at positions 0..S-1; pairs (i, i + d / 2)."""
    S, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
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


@functools.partial(jax.jit, static_argnums=(2, 3))
def _project(x, p, sz, lowp):
    """x (S, H) -> q (S, Hq, d), k, v (S, Hkv, d), lg (S, Hkv)."""
    s = dict(sz)
    p = _f32(p)
    S, d = x.shape[0], s["head_dim"]
    n = _rms(x, p["ln1"], s["rms_eps"])
    q = _mm(n, p["wq"], lowp).reshape(S, s["n_heads"], d)
    k = _mm(n, p["wk"], lowp).reshape(S, s["n_kv_heads"], d)
    v = _mm(n, p["wv"], lowp).reshape(S, s["n_kv_heads"], d)
    lg = jax.nn.log_sigmoid(jnp.matmul(n, p["wg"], precision="highest"))
    q = _rope(_rms(q, p["q_norm"], s["rms_eps"]), s["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], s["rms_eps"]), s["rope_theta"])
    return q, k, v, lg


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _rows(q_b, k, v, bq, bk, at, sz, lowp, qb):
    """One block of ``qb`` query rows starting at ``at`` against every
    key: q_b (qb, Hq, d); bq (qb, Hkv) and bk (S, Hkv) the gates'
    running sum relative to row ``at``. -> (y (qb, Hq, d), den (qb,
    Hq))."""
    s = dict(sz)
    S, Hkv, d = k.shape
    G = s["n_heads"] // Hkv
    qg = q_b.reshape(qb, Hkv, G, d)
    sc = jnp.einsum("qhgd,khd->hgqk", _q(qg, lowp), _q(k, lowp),
                    precision="highest") / math.sqrt(d)
    seen = (at + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
    decay = jnp.exp(jnp.where(seen[None], bq.T[:, :, None] - bk.T[:, None, :],
                              -jnp.inf))                    # (Hkv, qb, S)
    a = sc * sc * decay[:, None]
    num = jnp.einsum("hgqk,khd->qhgd", _q(a, lowp), _q(v, lowp),
                     precision="highest")
    den = jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)           # (qb, Hkv, G)
    y = num / (den[..., None] + s["ret_eps"])
    return y.reshape(qb, Hkv * G, d), den.reshape(qb, Hkv * G)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _out(x, y, p, rms_eps, lowp):
    """The output projection and the gated MLP. -> the next x."""
    p = _f32(p)
    h = x + _mm(y.reshape(y.shape[0], -1), p["wo"], lowp)
    m = _rms(h, p["ln2"], rms_eps)
    return h + _mm(jax.nn.silu(_mm(m, p["w_gate"], lowp))
                   * _mm(m, p["w_up"], lowp), p["w_down"], lowp)


def _layer(x, p, s, sz, lowp):
    """One block over x (S, H). -> (x, the smallest normaliser a row)."""
    S = x.shape[0]
    q, k, v, lg = _project(
        x, {n: p[n] for n in ("ln1", "wq", "wk", "wv", "wg", "q_norm",
                              "k_norm")}, sz, lowp)
    b = np.cumsum(np.asarray(lg, np.float64), axis=0)       # (S, Hkv)
    qb = min(S, 1024)
    ys, dens = [], []
    for at in range(0, S, qb):
        rel = (b - b[at]).astype(np.float32)
        y, den = _rows(q[at:at + qb], k, v, jnp.asarray(rel[at:at + qb]),
                       jnp.asarray(rel), at, sz, lowp, qb)
        ys.append(y)
        dens.append(jnp.min(den, axis=-1))
    x = _out(x, jnp.concatenate(ys), {
        n: p[n] for n in ("wo", "ln2", "w_gate", "w_up", "w_down")},
        s["rms_eps"], lowp)
    return x, np.asarray(jnp.concatenate(dens))


ROWS = 256           # compared rows go through the head in blocks of this
HEAD_BLOCKS = 8      # and the head's columns in this many blocks


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, lnf, head, rms_eps, lowp):
    """Final norm and output head over x (R, H): the head's columns a
    block at a time, so that beside the seed's bf16 weights only one
    block's float32 copies exist (the whole head's are 3.1 GB, three
    times over in the low-precision control). The control's scale is
    one a tensor, as ``_q``'s: the largest entry of the whole head."""
    x = _q(_rms(x, lnf.astype(F32), rms_eps), lowp)
    H, V = head.shape
    nb = math.gcd(V, HEAD_BLOCKS)
    peak = jnp.max(jnp.abs(head)).astype(F32)

    def block(w):
        w = w.astype(F32)
        if lowp == "fp8":
            step = jnp.maximum(peak, 1e-12) / 448.0
            w = (w / step).astype(jnp.float8_e4m3fn).astype(F32) * step
        else:
            w = _q(w, lowp)
        return jnp.matmul(x, w, precision="highest")

    out = jax.lax.map(block, jnp.moveaxis(head.reshape(H, nb, V // nb), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def logits_at(params, tokens, sizes, lo, hi, lowp=None):
    """Logits (hi - lo, V) at rows [lo, hi) of ONE sequence ``tokens``
    (S,), and the smallest normaliser over layers and heads at those
    rows."""
    s, sz = sizes, _frozen(sizes)
    x = params["wte"][jnp.asarray(tokens)].astype(F32)
    den = np.full(hi - lo, np.inf)
    for i in range(s["n_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x, d = _layer(x, p, s, sz, lowp)
        den = np.minimum(den, d[lo:hi])
    # whole blocks of rows: a new row count would compile the head anew
    rows = np.minimum(lo + np.arange(-(-(hi - lo) // ROWS) * ROWS),
                      len(tokens) - 1)
    logits = _head(x[jnp.asarray(rows)], params["lnf"], params["head"],
                   s["rms_eps"], lowp)
    return logits[:hi - lo], den


def _padded(n):
    """Whole blocks of query rows (causal: padding cannot reach back),
    and few lengths: each compiles three programs at float32 "highest"
    (a quarter of a minute a length on the chip), so a length over 1024
    is padded to the next power of two, or past 16,384 to the next
    2,048: six lengths for the digest mix, where blocks of 2,048 gave a
    new one for nearly every sampled request."""
    if n <= 1024:
        return -(-n // 64) * 64
    if n > 16384:
        return -(-n // 2048) * 2048
    return 1 << (n - 1).bit_length()


def served_gaps(params, prompt, served, sizes, pad_to, lowp=None):
    """Per served token, how far its reference logit lies below the
    reference's best at that position. One forward pass over the prompt
    and the served tokens. Returns (gaps, control gaps) at the judged
    positions (the module's docstring); with ``lowp`` the control is the
    token a forward pass in that precision puts first."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad_to={pad_to}")
    buf = np.zeros(_padded(len(seq)), np.int32)
    buf[:len(seq)] = seq
    lo, hi = len(prompt) - 1, len(seq) - 1
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref, den = logits_at(params, buf, sizes, lo, hi)
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
    thin = den < DEN_FLOOR
    print(f"[bench] reference normalisers over {hi - lo} compared "
          f"positions: smallest {float(den.min()):.3e}, median "
          f"{float(np.median(den)):.3e}; widest gap at the "
          f"{int(thin.sum())} positions under {DEN_FLOOR} (not judged) "
          f"{float(gap[thin].max(initial=0.0)):.4f}, at the others "
          f"{float(gap[~thin].max(initial=0.0)):.4f}; at the position of "
          f"the smallest normaliser {float(gap[int(den.argmin())]):.4f}; "
          f"{len(seq)} tokens as {len(buf)} took "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    judged = ~thin if not thin.all() else thin
    return gap[judged], low_gap[judged]
