"""Token sampling for the serving engine — vectorized, per-slot params.

One fused filter chain covers greedy, temperature, top-k and top-p so it
can ride inside the jitted decode step: every slot in the batch carries
its OWN (temperature, top_k, top_p) triple, which is what continuous
batching needs — requests with different sampling settings share one
compiled program. ``temperature <= 0`` means greedy (argmax of the raw
logits), ``top_k <= 0`` and ``top_p >= 1`` disable those filters.

Per-slot RNG streams (ISSUE 10): :func:`stream_keys` folds each slot's
REQUEST id and per-request draw index into the engine's base key, so a
stream's sampled tokens depend only on (seed, request id, draw index) —
never on which neighbors happen to share the batch, which slot index the
request landed in, or how many scheduler ticks the engine has run.
Eviction/admission of a neighbor therefore cannot perturb a stream, and
a preempted-and-resumed request replays its remaining draws exactly.

Speculative decoding (ISSUE 10): :func:`spec_accept` applies the
standard rejection-sampling rule (Leviathan et al., 2023) to a draft's k
proposals against the target's k+1 verify logits. Both distributions go
through the SAME filter chain, so temperature/top-k/top-p sampling keeps
the target distribution exactly, and greedy reduces to "accept while the
draft token equals the target argmax" — token-identical to the
non-speculative engine by construction.

Constrained decoding (ISSUE 11): every sampling entry point takes an
optional per-row token MASK (B, V) bool — False entries are suppressed
BEFORE temperature/top-k/top-p, so the filter chain renormalizes over
the allowed set and greedy rows argmax the masked logits. The serving
engine feeds masks from per-request token-mask automata
(serving.constrained); ``mask=None`` (and an all-True mask) leave every
path bit-identical to the unmasked code.

Selection, not sorting (ISSUE 33): the filter chain finds its two
cut-offs (the k-th value, the nucleus's least kept value) among a row's
``K_CAP`` largest entries whenever every row that filters has
``0 < top_k <= K_CAP``, and sorts the vocabulary, once, only for
parameters that force it (top_p alone, a larger top_k, ties at the k-th
value that overflow the candidates). The kept set is the same either
way; only rows that sample AND enable a filter are filtered at all.
The candidates come from a few groups of columns, not from the whole
row (:func:`_candidates`): the same values.

Everything here is pure jnp: the engine's eager first-token sample
(:func:`sample_one`, which picks its path on the host) and its jitted
ticks run the SAME code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["sample_tokens", "sample_tokens_streams", "sample_one",
           "sample_path", "stream_keys", "spec_accept", "MASKED_LOGIT",
           "K_CAP"]

# suppression value for masked-out vocabulary entries: finite (softmax
# over an all-masked row stays NaN-free long enough to be caught
# host-side) but far below any real logit
MASKED_LOGIT = -1e30


def _apply_mask(logits, mask):
    """Suppress disallowed tokens; ``mask`` (B, V) bool or None. An
    all-True mask is the identity (jnp.where copies through), keeping
    unconstrained engines bit-identical."""
    if mask is None:
        return logits
    return jnp.where(mask, logits, jnp.float32(MASKED_LOGIT))


# the bounded path's candidate count: a row whose top_k is at most this
# finds both cut-offs among its K_CAP largest entries. Chosen by timing
# the filter alone on the chip (PERF.md §6, PR 33)
K_CAP = 64


def _scale(logits, temperature):
    return logits / jnp.maximum(temperature, 1e-6)[:, None]


def _nucleus_cutoff(desc, kth, top_p):
    """The least value both filters keep: ``desc`` (B, N) holds a row's
    largest entries in descending order and ``kth`` (B, 1) its k-th
    value. Of the entries >= kth the smallest prefix whose mass reaches
    top_p survives (the top token always); never below kth, which a
    nucleus that keeps every survivor would otherwise let through."""
    desc = jnp.where(desc >= kth, desc, -jnp.inf)
    probs = jax.nn.softmax(desc, axis=-1)
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    keep = exclusive_cum < top_p[:, None]
    return jnp.maximum(kth, jnp.min(jnp.where(keep, desc, jnp.inf),
                                    axis=-1, keepdims=True))


def _sort_cutoff(scaled, top_k, top_p):
    """The one-sort path: any top_k, any top_p. One descending sort gives
    the k-th value, and with its tail set to -inf it IS the k-filtered
    row sorted, which the nucleus needs. Values alone are sorted, so
    stability buys nothing; on the chip a stable sort carries an index
    along and takes 2.2 times as long (PERF.md §6, PR 33)."""
    V = scaled.shape[-1]
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    desc = -jnp.sort(-scaled, axis=-1, stable=False)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    return _nucleus_cutoff(desc, kth, top_p)


# the grouped candidate search's group: one lane tile of columns
GROUP_W = 128


def _candidates(scaled, n):
    """A row's ``n`` largest values, descending: (B, V) -> (B, n). The
    row is viewed as groups of ``w = min(GROUP_W, V // n)`` columns (the
    last padded with -inf), at least ``n`` of them; the ``n`` groups
    with the largest maxima are gathered and the values taken among
    their ``n * w`` columns. On the chip a top_k's time follows the
    columns it reads, so this reads ``V / w`` maxima and ``n * w``
    candidates, not the row (PERF.md §6). Exact, ties included: with t
    the n-th largest value, fewer than n groups hold a value above t,
    each group whose maximum is t holds one equal to t, and groups are
    taken by their maxima, so the chosen groups hold the row's n
    largest values."""
    return _grouped_top(scaled, n, min(GROUP_W, scaled.shape[-1] // n))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _grouped_top(scaled, n, w):
    """:func:`_candidates`' search, one program: called eagerly
    (:func:`sample_one`) it is one dispatch and one compile, not six;
    inside a jitted step it is inlined."""
    B, V = scaled.shape
    G = -(-V // w)
    groups = jnp.pad(scaled, ((0, 0), (0, G * w - V)),
                     constant_values=-jnp.inf).reshape(B, G, w)
    best = jax.lax.top_k(jnp.max(groups, axis=-1), n)[1]          # (B, n)
    pool = jnp.take_along_axis(groups, best[:, :, None], axis=1)
    return jax.lax.top_k(pool.reshape(B, n * w), n)[0]


def _select_cutoff(scaled, top_k, top_p):
    """The bounded path, for rows with 0 < top_k <= K_CAP: the same two
    cut-offs found among the row's K_CAP largest entries
    (:func:`_candidates`), no sort of the vocabulary. Returns
    ``(cutoff (B, 1), fits (B,))``; a row fits when every value >= its
    k-th is among the candidates (ties at the k-th can overflow them,
    and the nucleus would then lose mass): only a row that fits may use
    its cutoff."""
    n = min(K_CAP, scaled.shape[-1])
    cand = _candidates(scaled, n)                         # descending
    kth = jnp.take_along_axis(
        cand, (jnp.clip(top_k, 1, n) - 1)[:, None], axis=-1)
    fits = jnp.sum(scaled >= kth, axis=-1) <= n
    return _nucleus_cutoff(cand, kth, top_p), fits


def _keep(scaled, cutoff):
    return jnp.where(scaled >= cutoff, scaled, -jnp.inf)


def _filter_logits(logits, temperature, top_k, top_p):
    """Temperature scale → top-k → top-p (nucleus, on the k-filtered
    distribution); logits (B, V) fp32, per-row params. Returns filtered
    logits with suppressed entries at -inf. The usual serving filter
    order — shared by the sampling draw AND the speculative
    accept/residual math so both see the same distribution.

    Pure unconditional math, every row through the one-sort path — safe
    to call eagerly (``lax.cond`` in eager mode re-traces and
    re-compiles per call, a ~0.3s stall each time; see
    :func:`_filter_logits_cond` for the jit-context variant that picks a
    path from the rows' parameters)."""
    scaled = _scale(logits, temperature)
    return _keep(scaled, _sort_cutoff(scaled, top_k, top_p))


def _filter_logits_cond(logits, temperature, top_k, top_p):
    """JIT-CONTEXT filter: the work follows the rows' own parameters.
    Only rows that sample (``temperature > 0``) with a filter enabled
    are filtered; greedy rows (never read: callers argmax the raw
    logits) and unfiltered rows come back scaled. With no row to filter
    nothing else runs; with every such row at ``0 < top_k <= K_CAP`` the
    cut-offs come from :func:`_select_cutoff`; any other parameters, or
    ties that overflow the candidates, take :func:`_sort_cutoff`. Same
    kept set either way, up to the nucleus's summation order. Only call
    from inside a jitted program — eager ``lax.cond`` re-compiles per
    call."""
    scaled = _scale(logits, temperature)
    filt = (temperature > 0.0) & ((top_k > 0) | (top_p < 1.0))

    def cut(cutoff):
        return _keep(scaled, jnp.where(filt[:, None], cutoff, -jnp.inf))

    def sort_path(_):
        return cut(_sort_cutoff(scaled, top_k, top_p))

    def select_path(_):
        cutoff, fits = _select_cutoff(scaled, top_k, top_p)
        return jax.lax.cond(jnp.all(fits | ~filt),
                            lambda _: cut(cutoff), sort_path, None)

    def filtered(_):
        bounded = jnp.all(~filt | ((top_k > 0) & (top_k <= K_CAP)))
        return jax.lax.cond(bounded, select_path, sort_path, None)

    return jax.lax.cond(jnp.any(filt), filtered, lambda _: scaled, None)


def _finish(logits, scaled, gumbel, temperature):
    """Greedy rows take the raw argmax; sampled rows the Gumbel draw."""
    sampled = jnp.argmax(scaled + gumbel, axis=-1)
    return jnp.where(temperature <= 0.0, jnp.argmax(logits, axis=-1),
                     sampled).astype(jnp.int32)


def sample_tokens(logits, key, temperature, top_k, top_p, mask=None):
    """logits (B, V) fp32 → token ids (B,) int32; ONE key for the batch.

    temperature/top_p: (B,) float32; top_k: (B,) int32; ``mask`` (B, V)
    bool suppresses disallowed tokens ahead of the filter chain
    (constrained decoding). The historical shared-key entry point —
    unconditional math, safe to call eagerly (the reference-decode
    escape hatch and one-off host-side draws); the engine's jitted
    steps use :func:`sample_tokens_streams`, which adds the runtime
    greedy/filter short-circuits."""
    logits = _apply_mask(logits.astype(jnp.float32), mask)
    scaled = _filter_logits(logits, temperature, top_k, top_p)
    gumbel = jax.random.gumbel(key, logits.shape, jnp.float32)
    return _finish(logits, scaled, gumbel, temperature)


def sample_path(temperature, top_k, top_p):
    """Which path a batch's sampling takes, from its parameters on the
    HOST (numpy arrays or Python numbers): ``"greedy"`` (no row
    samples), ``"select"`` (every row that filters has
    ``0 < top_k <= K_CAP``; also a batch that samples with no filter)
    or ``"sort"``. Mirrors :func:`_filter_logits_cond`'s predicates;
    it cannot see a tie that overflows the candidates, which sorts."""
    temperature, top_k, top_p = (np.asarray(temperature),
                                 np.asarray(top_k), np.asarray(top_p))
    samples = temperature > 0.0
    if not samples.any():
        return "greedy"
    filt = samples & ((top_k > 0) | (top_p < 1.0))
    bounded = (top_k > 0) & (top_k <= K_CAP)
    return "select" if (~filt | bounded).all() else "sort"


def sample_one(logits, key, temperature, top_k, top_p, mask=None):
    """logits (1, V) → one token id (a Python int), EAGERLY: the row's
    parameters are Python numbers, so the path is picked here on the
    host (an eager ``lax.cond`` would re-compile per call). A greedy
    row is an argmax: no sort, no Gumbel draw. The same math, key and
    draw as a one-row :func:`sample_tokens`."""
    logits = _apply_mask(logits.astype(jnp.float32), mask)
    if temperature <= 0.0:
        return int(jnp.argmax(logits, axis=-1)[0])
    k, p = jnp.int32(top_k)[None], jnp.float32(top_p)[None]
    scaled = _scale(logits, jnp.float32(temperature)[None])
    gumbel = jax.random.gumbel(key, logits.shape, jnp.float32)

    def draw(filtered):
        return jnp.argmax(filtered + gumbel, axis=-1)[0]

    if top_k <= 0 and top_p >= 1.0:
        return int(draw(scaled))
    if 0 < top_k <= K_CAP:
        cutoff, fits = _select_cutoff(scaled, k, p)
        # one wait for the token and for whether it may be used
        tok, fits = jax.device_get((draw(_keep(scaled, cutoff)), fits))
        if fits[0]:
            return int(tok)
    return int(draw(_keep(scaled, _sort_cutoff(scaled, k, p))))


def stream_keys(base_key, req_ids, draws):
    """Per-slot sampling keys: fold (request id, per-request draw index)
    into the engine's base key. req_ids/draws (B,) int32 → keys (B,).

    The draw index is the number of tokens the request has sampled so
    far, so a stream is a pure function of (seed, request id) — batch
    composition, slot placement and tick count cannot perturb it."""
    def one(rid, d):
        return jax.random.fold_in(jax.random.fold_in(base_key, rid), d)

    return jax.vmap(one)(req_ids, draws)


def sample_tokens_streams(logits, keys, temperature, top_k, top_p,
                          mask=None):
    """Like :func:`sample_tokens` but each row draws from its OWN key
    (see :func:`stream_keys`); logits (B, V), keys (B,); ``mask``
    (B, V) bool suppresses disallowed tokens first (greedy rows argmax
    the masked logits). All-greedy batches short-circuit to argmax (no
    filters, no RNG). JIT-context only — the short-circuits are
    ``lax.cond``, which re-compiles per call when run eagerly."""
    logits = _apply_mask(logits.astype(jnp.float32), mask)
    V = logits.shape[1]

    def sampled(logits):
        scaled = _filter_logits_cond(logits, temperature, top_k, top_p)
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (V,), jnp.float32))(keys)
        return _finish(logits, scaled, gumbel, temperature)

    return jax.lax.cond(
        jnp.any(temperature > 0.0), sampled,
        lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32), logits)


# salts separating the independent draws a speculative tick makes from
# one request's stream (draft proposal / accept uniform / residual)
DRAFT_SALT = 1
ACCEPT_SALT = 2
RESIDUAL_SALT = 3


def spec_accept(target_logits, draft_logits, draft_tokens, keys,
                temperature, top_k, top_p):
    """Speculative accept/resample (Leviathan et al., 2023 rule).

    target_logits (B, K+1, V) fp32 — the verify pass over [last_token,
    d_1..d_K]: row j is the target's distribution for the token AFTER
    consuming j proposals. draft_logits (B, K, V) — the distributions the
    draft sampled d_{j+1} from. draft_tokens (B, K). keys (B,) — one
    acceptance stream per slot (fold ACCEPT_SALT/RESIDUAL_SALT inside).

    Returns ``(tokens (B, K+1) int32, n_emit (B,) int32)``: row b emits
    ``tokens[b, :n_emit[b]]`` — the accepted prefix of the draft plus ONE
    token from the target (the rejection-resample at the first miss, or
    the bonus draw when everything passed), so every tick advances every
    row by at least one token. Greedy rows accept while the proposal
    equals the target argmax; sampled rows accept d with probability
    ``min(1, p(d)/q(d))`` and resample from ``normalize(max(0, p - q))``
    — both p and q are the FILTERED distributions, so the emitted stream
    keeps the target distribution exactly."""
    B, K1, V = target_logits.shape
    K = K1 - 1
    target_logits = target_logits.astype(jnp.float32)
    greedy = temperature <= 0.0                                    # (B,)
    tgt_argmax = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
    acc_greedy = draft_tokens == tgt_argmax[:, :K]

    def emit(m, correction):
        idx = jnp.arange(K1)[None, :]
        d_pad = jnp.concatenate(
            [draft_tokens, jnp.zeros((B, 1), jnp.int32)], axis=1)
        tokens = jnp.where(
            idx < m[:, None], d_pad,
            jnp.where(idx == m[:, None], correction[:, None], 0))
        return tokens.astype(jnp.int32), (m + 1).astype(jnp.int32)

    def greedy_path(_):
        # accept while the proposal IS the target argmax; the correction
        # is the argmax at the first miss (or the bonus row) — no
        # softmax, no filters, no RNG
        m = jnp.sum(jnp.cumprod(acc_greedy.astype(jnp.int32), axis=-1),
                    axis=-1)
        correction = jnp.take_along_axis(tgt_argmax, m[:, None],
                                         axis=-1)[:, 0]
        return emit(m, correction)

    def sampled_path(_):
        dl = draft_logits.astype(jnp.float32)

        def filt(lg):  # (B, N, V) → filtered, per-row params broadcast
            N = lg.shape[1]
            flat = _filter_logits_cond(lg.reshape(B * N, V),
                                       jnp.repeat(temperature, N),
                                       jnp.repeat(top_k, N),
                                       jnp.repeat(top_p, N))
            return flat.reshape(B, N, V)

        p = jax.nn.softmax(filt(target_logits), axis=-1)   # (B, K+1, V)
        q = jax.nn.softmax(filt(dl), axis=-1)              # (B, K, V)

        # acceptance per proposal
        p_d = jnp.take_along_axis(p[:, :K], draft_tokens[..., None],
                                  axis=-1)[..., 0]         # (B, K)
        q_d = jnp.take_along_axis(q, draft_tokens[..., None],
                                  axis=-1)[..., 0]
        u = jax.vmap(lambda k: jax.random.uniform(
            k, (K,), jnp.float32))(jax.vmap(
                lambda k: jax.random.fold_in(k, ACCEPT_SALT))(keys))
        acc_sampled = u * jnp.maximum(q_d, 1e-20) < p_d
        acc = jnp.where(greedy[:, None], acc_greedy, acc_sampled)
        m = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=-1),
                    axis=-1)                               # (B,) in [0, K]

        # resample ONLY at the selected position m: residual
        # max(0, p_m - q_m) after a rejection, plain p_K at the bonus
        # (q padded with 0 makes that the same formula)
        q_pad = jnp.concatenate([q, jnp.zeros_like(p[:, :1])], axis=1)
        p_m = jnp.take_along_axis(p, m[:, None, None],
                                  axis=1)[:, 0]            # (B, V)
        q_m = jnp.take_along_axis(q_pad, m[:, None, None], axis=1)[:, 0]
        res = jnp.maximum(p_m - q_m, 0.0)
        res_ok = jnp.sum(res, axis=-1, keepdims=True) > 1e-9
        res = jnp.where(res_ok, res, p_m)  # p == q exactly → draw from p
        g = jax.vmap(lambda k: jax.random.gumbel(
            k, (V,), jnp.float32))(jax.vmap(
                lambda k: jax.random.fold_in(k, RESIDUAL_SALT))(keys))
        resampled = jnp.argmax(jnp.log(jnp.maximum(res, 1e-30)) + g,
                               axis=-1).astype(jnp.int32)  # (B,)
        tgt_m = jnp.take_along_axis(tgt_argmax, m[:, None], axis=-1)[:, 0]
        correction = jnp.where(greedy, tgt_m, resampled)
        return emit(m, correction)

    return jax.lax.cond(jnp.any(temperature > 0.0), sampled_path,
                        greedy_path, None)
