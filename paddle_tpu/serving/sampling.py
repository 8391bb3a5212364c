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

Everything here is pure jnp: the engine's eager first-token sample and
its jitted ticks run the SAME code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sample_tokens", "sample_tokens_streams", "stream_keys",
           "spec_accept", "MASKED_LOGIT"]

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


def _filter_logits(logits, temperature, top_k, top_p):
    """Temperature scale → top-k → top-p (nucleus, on the k-filtered
    distribution); logits (B, V) fp32, per-row params. Returns filtered
    logits with suppressed entries at -inf. The usual serving filter
    order — shared by the sampling draw AND the speculative
    accept/residual math so both see the same distribution.

    Pure unconditional math — safe to call eagerly (``lax.cond`` in
    eager mode re-traces and re-compiles per call, a ~0.3s stall each
    time; see :func:`_filter_logits_cond` for the jit-context variant
    that skips the sorts when no row enables the filters)."""
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]

    # top-k with per-row k: keep values >= the k-th largest
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)

    # top-p: keep the smallest prefix of the sorted distribution whose
    # mass reaches top_p (the top token always survives)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    keep = exclusive_cum < top_p[:, None]
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(scaled >= cutoff, scaled, -jnp.inf)


def _filter_logits_cond(logits, temperature, top_k, top_p):
    """JIT-CONTEXT filter: the sort-based k/p filters only RUN when some
    row enables them (with every top_k <= 0 and top_p >= 1 they are
    mathematically the identity, and two (B, V) sorts per draw is real
    money on a CPU host). Only call from inside a jitted program —
    eager ``lax.cond`` re-compiles per call."""
    need = jnp.any(top_k > 0) | jnp.any(top_p < 1.0)
    return jax.lax.cond(
        need,
        lambda lg: _filter_logits(lg, temperature, top_k, top_p),
        lambda lg: lg / jnp.maximum(temperature, 1e-6)[:, None],
        logits)


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
