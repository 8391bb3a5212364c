"""Distill a gpt_nano-class speculative-decoding draft from a target
model (ISSUE 11 satellite — the PR-10 carry-over).

The layer-truncated draft (``models.gpt_truncate``) proves the
speculative MACHINERY — it literally shares the target's weights, so
its acceptance rate says nothing about how a real, separately-trained
draft would fare. This tool produces that real draft on CPU in seconds:

    from tools.distill_draft import distill_draft
    draft, info = distill_draft(cfg, params, steps=300)
    eng = InferenceEngine(cfg, params, draft=draft, spec_k=6)

Recipe (short by design — seconds, not GPU-days):

1. student = ``gpt_nano`` shape at the TARGET's hidden/vocab/seq_len
   (``n_layers`` defaults to 2), with wte/wpe/final-LN INITIALIZED from
   the teacher — the embedding geometry is the hard-won part of a tiny
   LM, and seeding it is what makes a few hundred steps enough;
2. data = uniform random token sequences (the acceptance rule only
   needs argmax agreement per CONTEXT, and random contexts cover the
   prefix distribution a serving mix induces better than any single
   corpus would for an untrained teacher);
3. loss = KL(teacher ‖ student) over the temperature-1 distributions at
   every position, minimized with Adam (one jitted step, donated
   state).

Returns ``((draft_cfg, draft_params), info)`` where ``info`` carries
the final KL and the held-out argmax-agreement rate — a proxy for the
distilled draft's acceptance.
"""
from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from paddle_tpu.models.gpt import (GPTConfig, gpt_forward,  # noqa: E402
                                   gpt_init)

__all__ = ["distill_draft"]


def _student_cfg(cfg: GPTConfig, n_layers: int) -> GPTConfig:
    import dataclasses

    return dataclasses.replace(cfg, n_layers=n_layers,
                               remat=False, n_stages=1)


def _kl_loss(s_cfg, t_cfg, s_params, t_params, tokens):
    # GPTConfig is closed over, not a static argnum (it is unhashable);
    # the jit boundary is grad_fn below
    t_logits = gpt_forward(t_cfg, t_params, tokens)
    s_logits = gpt_forward(s_cfg, s_params, tokens)
    t_logp = jax.nn.log_softmax(t_logits.astype(jnp.float32), axis=-1)
    s_logp = jax.nn.log_softmax(s_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.sum(jnp.exp(t_logp) * (t_logp - s_logp), axis=-1))


def distill_draft(cfg: GPTConfig, params, n_layers: int = 2,
                  steps: int = 300, batch: int = 8, seq: int = 32,
                  lr: float = 3e-3, seed: int = 0):
    """Train a distilled draft against ``(cfg, params)`` as teacher.

    Returns ``((draft_cfg, draft_params), info)`` ready for
    ``InferenceEngine(draft=...)``; ``info`` = {"kl_first", "kl_last",
    "argmax_agreement", "steps", "params"}."""
    s_cfg = _student_cfg(cfg, n_layers)
    s_params = gpt_init(s_cfg, seed=seed + 1)
    # seed the embedding geometry from the teacher: the tied head means
    # wte IS the output space, and matching it is most of the battle
    s_params["wte"] = params["wte"]
    s_params["wpe"] = params["wpe"]
    s_params["lnf_s"] = params["lnf_s"]
    s_params["lnf_b"] = params["lnf_b"]

    grad_fn = jax.jit(
        jax.value_and_grad(
            lambda sp, tokens: _kl_loss(s_cfg, cfg, sp, params, tokens)))

    def zeros_like_tree(tree):
        return jax.tree_util.tree_map(jnp.zeros_like, tree)

    @jax.jit
    def adam_step(sp, m, v, t, grads):
        b1, b2, eps = 0.9, 0.999, 1e-8
        t = t + 1
        m = jax.tree_util.tree_map(
            lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree_util.tree_map(
            lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        scale = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        sp = jax.tree_util.tree_map(
            lambda p, mm, vv: p - lr * scale * mm / (jnp.sqrt(vv) + eps),
            sp, m, v)
        return sp, m, v, t

    m, v = zeros_like_tree(s_params), zeros_like_tree(s_params)
    t = jnp.int32(0)
    key = jax.random.key(seed)
    kl_first = kl_last = None
    for i in range(int(steps)):
        key, sub = jax.random.split(key)
        tokens = jax.random.randint(sub, (batch, seq), 0, cfg.vocab_size,
                                    jnp.int32)
        loss, grads = grad_fn(s_params, tokens)
        s_params, m, v, t = adam_step(s_params, m, v, t, grads)
        if i == 0:
            kl_first = float(loss)
        kl_last = float(loss)

    # held-out argmax agreement: the greedy acceptance proxy
    key, sub = jax.random.split(key)
    tokens = jax.random.randint(sub, (batch, seq), 0, cfg.vocab_size,
                                jnp.int32)
    t_am = jnp.argmax(gpt_forward(cfg, params, tokens), axis=-1)
    s_am = jnp.argmax(gpt_forward(s_cfg, s_params, tokens), axis=-1)
    agree = float(jnp.mean((t_am == s_am).astype(jnp.float32)))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(s_params))
    info = {"kl_first": kl_first, "kl_last": kl_last,
            "argmax_agreement": agree, "steps": int(steps),
            "params": n_params}
    return (s_cfg, s_params), info


def main(argv=None) -> int:
    import argparse
    import json

    from paddle_tpu.models.gpt import gpt_tiny

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=256)
    args = ap.parse_args(argv)
    cfg = gpt_tiny(seq_len=args.seq_len, dtype=jnp.float32)
    params = gpt_init(cfg, seed=0)
    _, info = distill_draft(cfg, params, n_layers=args.layers,
                            steps=args.steps)
    print(json.dumps(info, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
