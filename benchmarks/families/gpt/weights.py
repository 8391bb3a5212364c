"""Seeded weights of the GPT block, made on the device in one jitted
call (moved here from ``benchmarks/lib/weights.py`` letter for letter:
the same key split and the same order of draws, so a seed's weights are
the same bits).

The tree has the layout the program's GPT family takes (`wte`, `wpe`,
`blocks` with a leading layer dimension, `lnf_s`, `lnf_b`); biases
start at zero and layer-norm scales at one, as the published GPT-2
initialisation has them (normal, std 0.02, residual projections scaled
by 1/sqrt(2L)).
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import DTYPES, seed_key


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _make(key, V, H, L, S, M, dtype, std):
    ks = jax.random.split(key, 6)

    def nrm(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    res = std / math.sqrt(2 * L)
    ones = lambda *s: jnp.ones(s, dtype)      # noqa: E731
    zeros = lambda *s: jnp.zeros(s, dtype)    # noqa: E731
    blocks = {
        "ln1_s": ones(L, H), "ln1_b": zeros(L, H),
        "qkv_w": nrm(ks[0], (L, H, 3 * H)), "qkv_b": zeros(L, 3 * H),
        "proj_w": nrm(ks[1], (L, H, H), res), "proj_b": zeros(L, H),
        "ln2_s": ones(L, H), "ln2_b": zeros(L, H),
        "fc_w": nrm(ks[2], (L, H, M)), "fc_b": zeros(L, M),
        "out_w": nrm(ks[3], (L, M, H), res), "out_b": zeros(L, H),
    }
    return {"wte": nrm(ks[4], (V, H)), "wpe": nrm(ks[5], (S, H), std / 2),
            "blocks": blocks, "lnf_s": ones(H), "lnf_b": zeros(H)}


def make_params(sizes, seed):
    """sizes: the configuration file's ``sizes``. Weights are drawn in
    float32 and rounded once to the type they are held in. ``init_std``
    (0.02, GPT-2's, unless the file says otherwise) is not a size of the
    program's: a tiny test configuration raises it so that its few
    narrow layers, not the token's own embedding, decide the logits."""
    return _make(seed_key(seed), sizes["vocab_size"], sizes["hidden"],
                 sizes["n_layers"], sizes["seq_len"],
                 sizes["hidden"] * sizes["mlp_ratio"],
                 DTYPES[sizes["param_dtype"]],
                 float(sizes.get("init_std", 0.02)))
