"""Seeded weights of the power-retention block, made on the device in
one jitted call, in the type the configuration holds them in (bf16), in
the layout ``paddle_tpu.models.retention`` takes: ``wte``, ``head``,
``lnf`` and ``layers`` with a leading layer axis.

Normal(0, ``init_std``); projections back into the residual stream
(``wo``, ``w_down``) scaled by 1 / sqrt(2 L); embedding rows normal(0,
``EMBED_STD`` = 1), so that a block's update is a fraction of the stream
as in a trained model (PERF.md section 6, PR 30's lesson); norm scales
1. The gate's ``wg`` is drawn like every other projection: bias-free,
so a token's gates are sigmoid of a zero-mean number (the
configuration's ``assumed``). Every leaf is drawn a slab at a time (a
layer, or as many rows of the embedding or the head as stay under 2^27
values), so the float32 draw held beside the weights is at most 537 MB.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import DTYPES, seed_key

EMBED_STD = 1.0
NORMS = ("ln1", "ln2", "q_norm", "k_norm", "lnf")
RESIDUAL = ("wo", "w_down")


def shapes(s):
    """{leaf path: shape} from the configuration's ``sizes``."""
    H, V, L, d = s["hidden"], s["vocab_size"], s["n_layers"], s["head_dim"]
    nq, nkv, F = s["n_heads"] * d, s["n_kv_heads"] * d, s["ffn"]
    layers = {"ln1": (L, H), "ln2": (L, H), "q_norm": (L, d),
              "k_norm": (L, d), "wq": (L, H, nq), "wk": (L, H, nkv),
              "wv": (L, H, nkv), "wg": (L, H, s["n_kv_heads"]),
              "wo": (L, nq, H), "w_gate": (L, H, F), "w_up": (L, H, F),
              "w_down": (L, F, H)}
    return {"wte": (V, H), "head": (H, V), "lnf": (H,), "layers": layers}


SLAB = 2 ** 27       # values of one float32 draw


def _slab_rows(shape):
    """The most leading rows of ``shape`` a draw may hold: the largest
    divisor of ``shape[0]`` whose slab stays under ``SLAB`` values."""
    cap = max(1, SLAB // max(1, math.prod(shape[1:])))
    return max(k for k in range(1, min(shape[0], cap) + 1)
               if shape[0] % k == 0)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, frozen_sizes):
    sizes = dict(frozen_sizes)
    dtype = DTYPES[sizes["param_dtype"]]
    std = float(sizes.get("init_std", 0.02))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(sizes), is_leaf=lambda v: isinstance(v, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        if name in NORMS:
            out.append(jnp.ones(shape, dtype))
            continue
        scale = EMBED_STD if name == "wte" else \
            std / math.sqrt(2 * sizes["n_layers"]) if name in RESIDUAL \
            else std
        # a slab of the leading axis at a time: a layer, or rows
        rows = 1 if len(path) > 1 else _slab_rows(shape)
        slab = (rows,) + shape[1:]

        def draw(kk, slab=slab, scale=scale):
            return (scale * jax.random.normal(kk, slab, jnp.float32)
                    ).astype(dtype)

        out.append(jax.lax.map(
            draw, jax.random.split(k, shape[0] // rows)).reshape(shape))
    return jax.tree_util.tree_unflatten(tree, out)


def make_params(sizes, seed):
    """sizes: the configuration file's ``sizes``; ``init_std`` (0.02
    unless the file says otherwise) is the benchmark's own, not the
    program's."""
    return _make(seed_key(seed), tuple(sorted(sizes.items())))
