"""The plain reference: the SambaY block as one full forward pass over
the whole sequence, straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no chunks, no ring, no pool,
no kernel, no batching, and no layer skipped for any row. It imports
nothing of the program, layer by layer (``L`` layers, ``P = L / 4``):

    Mamba:   u = LN1(x);  a = W_x u;  z = W_z u
             c = silu(b + sum_k w_k a_{t - 3 + k})            (zeros before 0)
             dt = softplus(W_dt (W_r c) + b_dt);  B = W_B c;  C = W_C c
             s_t = exp(dt_t A) s_{t-1} + B_t (dt_t c_t),  A = -exp(A_log)
             y_t = C_t . s_t + D c_t   (a loop over the tokens: lax.scan)
             x += W_out (y * silu(z))
    Attention (pair h of query heads (2h, 2h+1) over key heads
             (2g, 2g+1) and value head g, g = h // 2):
             a1 = softmax(q_2h k_2g^T / sqrt(d) + mask) v_g, a2 alike
             o_h = RMS(a1 - lambda a2) * subln * (1 - lambda_init)
             x += W_o [o_1; ...] + b_o
             mask: causal, and in layers 1, 3, .., L/2 - 1 only the keys
             t - window + 1 .. t; layer L/2 + 1 makes keys and values,
             the cross-attention layers take them and project queries
    GMU:     x += W_out2 (silu(W_in2 LN1(x)) * y of layer L/2)
    MLP:     x += W_down (silu(W_gate LN2(x)) * W_up LN2(x))
    logits = LN_f(x) W_emb^T

Departures, each the configuration's (``assumed`` in its file): the
memory is ``y`` before the ``silu(z)`` gate, the pairing of heads, the
depth in ``lambda_init`` is the layer's index, the window counts the
query itself.

It keeps the seed's bf16 weights and lifts one layer to float32 at a
time. Queries go through attention in blocks of rows only so that no
(heads, S, S) tensor exists; every row still sees every key the mask
allows. ``lowp`` computes every matmul's operands in a lower precision
(the control of "How correct is decided"): "bf16", "fp8" (e4m3) or None.

**Which positions are judged:** the prompt's last (whose logits give
the first served token) and every position whose next token was
served.
"""
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import F32, _mm, _q

from .program import NO_TRAINING

QB = 512             # query rows a block of attention


def leaf_norms(tree):
    raise NotImplementedError(NO_TRAINING.format(name="leaf_norms"))


def train_readings(params0, batches, sizes, hyper, rows, lowp=None,
                   drop_half=False):
    raise NotImplementedError(NO_TRAINING.format(name="train_readings"))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _frozen(s):
    return tuple(sorted((k, v) for k, v in s.items()
                        if isinstance(v, (int, float, str))))


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mlp(x, p, eps, lowp):
    p = _f32(p)
    m = _ln(x, p["ln2_w"], p["ln2_b"], eps)
    return x + _mm(jax.nn.silu(_mm(m, p["w_gate"], lowp))
                   * _mm(m, p["w_up"], lowp), p["w_down"], lowp)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mamba(x, p, sz, lowp):
    """One Mamba layer over x (S, H). -> (x, y (S, inner))."""
    s = dict(sz)
    p = _f32(p)
    S = x.shape[0]
    u = _ln(x, p["ln1_w"], p["ln1_b"], s["ln_eps"])
    a, z = _mm(u, p["in_x"], lowp), _mm(u, p["in_z"], lowp)
    K = p["conv_w"].shape[0]
    ap = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), F32), a])
    c = p["conv_b"]
    for k in range(K):
        c = c + p["conv_w"][k] * ap[k:k + S]
    c = jax.nn.silu(c)
    dt = jax.nn.softplus(_mm(_mm(c, p["x_dt"], lowp), p["dt_w"], lowp)
                         + p["dt_b"])
    Bm, Cm = _mm(c, p["x_b"], lowp), _mm(c, p["x_c"], lowp)
    A = -jnp.exp(p["a_log"])                              # (N, inner)

    def step(st, inp):
        dt_t, b_t, c_t, x_t = inp
        st = jnp.exp(dt_t[None, :] * A) * st + b_t[:, None] * (dt_t * x_t)
        return st, jnp.sum(c_t[:, None] * st, 0) + p["d"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros(A.shape, F32), (dt, Bm, Cm, c))
    return x + _mm(y * jax.nn.silu(z), p["out"], lowp), y


@functools.partial(jax.jit, static_argnums=(2, 3))
def _qkv(x, p, sz, lowp):
    """LN1 and the projections: q (S, nq, d), and where the layer has
    them k (S, n_kv, d), v (S, n_kv / 2, 2 d); else None."""
    s = dict(sz)
    p = _f32(p)
    S, d = x.shape[0], s["head_dim"]
    u = _ln(x, p["ln1_w"], p["ln1_b"], s["ln_eps"])
    q = (_mm(u, p["wq"], lowp) + p["bq"]).reshape(S, s["n_heads"], d)
    if "wkv" not in p:
        return q, None, None
    kv = _mm(u, p["wkv"], lowp) + p["bkv"]
    w = s["n_kv_heads"] * d
    return (q, kv[:, :w].reshape(S, s["n_kv_heads"], d),
            kv[:, w:].reshape(S, s["n_kv_heads"] // 2, 2 * d))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _attn_rows(q_b, k, v, at, window, d, lowp, qb):
    """Query rows [at, at + qb) against every key. -> (a1, a2) (qb,
    n_pairs, 2 d): each pair's two softmax read-outs of its value head."""
    S = k.shape[0]
    n_pairs = q_b.shape[1] // 2
    g = jnp.arange(n_pairs) // 2
    qpos = at + jnp.arange(qb)
    kpos = jnp.arange(S)
    ok = kpos[None, :] <= qpos[:, None]
    if window:
        ok = ok & (kpos[None, :] > qpos[:, None] - window)
    vh = _q(v[:, g], lowp)                               # (S, pairs, 2d)

    def read(qh, kh):
        sc = jnp.einsum("qhd,khd->hqk", _q(qh, lowp), _q(kh, lowp),
                        precision="highest") / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", _q(w, lowp), vh,
                          precision="highest")

    return read(q_b[:, 0::2], k[:, 2 * g]), read(q_b[:, 1::2], k[:, 2 * g + 1])


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _attn_out(x, a1, a2, p, depth, eps, lowp):
    p = _f32(p)
    init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.dot(p["lq1"], p["lk1"])) \
        - jnp.exp(jnp.dot(p["lq2"], p["lk2"])) + init
    o = a1 - lam * a2
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) \
        * p["subln"] * (1.0 - init)
    return x + _mm(o.reshape(o.shape[0], -1), p["wo"], lowp) + p["bo"]


def _attention(x, p, q, k, v, s, depth, window, lowp):
    S = x.shape[0]
    qb = math.gcd(S, QB)           # whole blocks of rows: S is 64-row whole
    parts = [_attn_rows(q[at:at + qb], k, v, at, window, s["head_dim"],
                        lowp, qb) for at in range(0, S, qb)]
    a1 = jnp.concatenate([a for a, _ in parts])
    a2 = jnp.concatenate([b for _, b in parts])
    keep = ("lq1", "lk1", "lq2", "lk2", "subln", "wo", "bo")
    return _attn_out(x, a1, a2, {n: p[n] for n in keep}, float(depth),
                     s["ln_eps"], lowp)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gmu(x, m, p, eps, lowp):
    p = _f32(p)
    u = _ln(x, p["ln1_w"], p["ln1_b"], eps)
    return x + _mm(jax.nn.silu(_mm(u, p["w_in"], lowp)) * m, p["w_out"],
                   lowp)


ROWS = 256           # compared rows go through the head in blocks of this
HEAD_BLOCKS = 8      # and the tied head's rows in this many blocks


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, w, b, emb, eps, lowp):
    """Final LayerNorm and the tied head over x (R, H), the embedding's
    rows a block at a time (beside the seed's bf16 weights only one
    block's float32 copy exists). The control's scale is one a tensor:
    the largest entry of the whole embedding."""
    x = _q(_ln(x, w.astype(F32), b.astype(F32), eps), lowp)
    V, H = emb.shape
    nb = math.gcd(V, HEAD_BLOCKS)
    peak = jnp.max(jnp.abs(emb)).astype(F32)

    def block(e):
        e = e.astype(F32)
        if lowp == "fp8":
            step = jnp.maximum(peak, 1e-12) / 448.0
            e = (e / step).astype(jnp.float8_e4m3fn).astype(F32) * step
        else:
            e = _q(e, lowp)
        return jnp.matmul(x, e.T, precision="highest")

    out = jax.lax.map(block, emb.reshape(nb, V // nb, H))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def logits_at(params, tokens, sizes, lo, hi, lowp=None):
    """Logits (hi - lo, V) at rows [lo, hi) of ONE sequence ``tokens``
    (S,), from a forward pass of every layer over every row."""
    s, sz = sizes, _frozen(sizes)
    P, eps, W = s["n_layers"] // 4, s["ln_eps"], s["window"]
    at = lambda tree, i: jax.tree_util.tree_map(       # noqa: E731
        lambda a: a[i], tree)
    x = params["wte"][jnp.asarray(tokens)].astype(F32)
    for i in range(P):
        p = at(params["self"]["ssm"], i)
        x, _ = _mamba(x, p, sz, lowp)
        x = _mlp(x, p, eps, lowp)
        p = at(params["self"]["attn"], i)
        q, k, v = _qkv(x, p, sz, lowp)
        x = _mlp(_attention(x, p, q, k, v, s, 2 * i + 1, W, lowp), p, eps,
                 lowp)
    x, m = _mamba(x, params["mem"], sz, lowp)
    x = _mlp(x, params["mem"], eps, lowp)
    p = params["kv"]
    q, k, v = _qkv(x, p, sz, lowp)
    x = _mlp(_attention(x, p, q, k, v, s, 2 * P + 1, 0, lowp), p, eps, lowp)
    for i in range(P - 1):
        p = at(params["cross"]["gmu"], i)
        x = _mlp(_gmu(x, m, p, eps, lowp), p, eps, lowp)
        p = at(params["cross"]["xattn"], i)
        q, _, _ = _qkv(x, p, sz, lowp)
        x = _mlp(_attention(x, p, q, k, v, s, 2 * P + 3 + 2 * i, 0, lowp),
                 p, eps, lowp)
    # whole blocks of rows: a new row count would compile the head anew
    rows = np.minimum(lo + np.arange(-(-(hi - lo) // ROWS) * ROWS),
                      len(tokens) - 1)
    logits = _head(x[jnp.asarray(rows)], params["lnf_w"], params["lnf_b"],
                   params["wte"], eps, lowp)
    return logits[:hi - lo]


def served_gaps(params, prompt, served, sizes, pad_to, lowp=None):
    """Per served token, how far its reference logit lies below the
    reference's best at that position: the prompt's last position and
    every one whose next token was served. One forward pass over the
    prompt and the served tokens. Returns (gaps, control gaps); with
    ``lowp`` the control is the token a forward pass in that precision
    puts first."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad_to={pad_to}")
    # every sequence padded to ``pad_to`` (causal: padding cannot reach
    # back): one length compiles the layers once a run, where each new
    # length cost about 95 s on a TPU v5e at float32 "highest" and a run
    # met three or four; a forward pass at 8,192 takes ~9 s there
    buf = np.zeros(pad_to, np.int32)
    buf[:len(seq)] = seq
    lo, hi = len(prompt) - 1, len(seq) - 1
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = logits_at(params, buf, sizes, lo, hi)
        best = jnp.max(ref, axis=-1)
        nxt = jnp.asarray(seq[lo + 1:hi + 1])
        gap = np.asarray(best - jnp.take_along_axis(
            ref, nxt[:, None], -1)[:, 0], np.float64)
        low_gap = gap
        if lowp is not None:
            low = jnp.argmax(logits_at(params, buf, sizes, lo, hi, lowp),
                             axis=-1)
            low_gap = np.asarray(best - jnp.take_along_axis(
                ref, low[:, None], -1)[:, 0], np.float64)
    print(f"[bench] reference over {hi - lo} compared positions: widest "
          f"gap {float(gap.max(initial=0.0)):.4f}, at "
          f"{int((gap > 0).sum())} of them above 0; {len(seq)} tokens as "
          f"{len(buf)} took {time.perf_counter() - t0:.1f} s", flush=True)
    return gap, low_gap
