"""Seeded weights of the latent-attention expert block, made on the
device in one jitted call, in the type the configuration holds them in
(bf16), in the layout ``paddle_tpu.models.mla`` takes: ``wte``,
``head``, ``lnf``, ``dense`` and ``moe`` with a leading layer axis, an
expert layer's routed weights ``(layers, experts_held, ...)``: the held
experts and the vocabulary slice only.

Normal(0, ``init_std``); projections back into the residual stream
(``wo``, ``w_down``, ``s_down``) scaled by 1 / sqrt(2 L); embedding rows
normal(0, ``EMBED_STD`` = 1): with rows of std 0.02 the residual stream
IS the first block's output, one routed expert is a tenth of it, and
which expert comes eighth (a near tie on a tenth of token-layers)
decides the logits in any precision: at unit rows a block's update is a
fraction of the stream, as in a trained model (PERF.md section 6, PR
30). Norm scales 1; the router's selection bias normal(0,
``ROUTER_BIAS_STD``) (a trained balancing bias is not published; see
the configuration's ``assumed``). A leaf with a layer axis is drawn a layer at a time, so
the float32 draw of the largest (one layer's experts of one
projection, 1.07 GB) is all that is held beside the weights.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import DTYPES, seed_key

ROUTER_BIAS_STD = 0.02
EMBED_STD = 1.0
NORMS = ("ln1", "ln2", "kv_norm", "lnf")
RESIDUAL = ("wo", "w_down", "s_down")


def shapes(s):
    """{leaf path: shape} from the configuration's ``sizes``."""
    H, V, nh = s["hidden"], s["vocab_size"], s["n_heads"]
    R, dn, dr, dv = (s["kv_lora_rank"], s["qk_nope_dim"], s["qk_rope_dim"],
                     s["v_head_dim"])
    Ld = s["first_dense"]
    Lm = s["n_layers"] - Ld
    Eh, M, F = s["experts_held"], s["expert_ffn"], s["dense_ffn"]
    attn = {"ln1": (H,), "ln2": (H,), "kv_norm": (R,),
            "wq": (H, nh * (dn + dr)), "wkva": (H, R + dr),
            "wkvb": (R, nh * (dn + dv)), "wo": (nh * dv, H)}
    dense = {k: (Ld,) + v for k, v in attn.items()}
    dense.update(w_gate=(Ld, H, F), w_up=(Ld, H, F), w_down=(Ld, F, H))
    moe = {k: (Lm,) + v for k, v in attn.items()}
    Ms = M * s["n_shared"]
    moe.update(router_w=(Lm, H, s["n_experts"]),
               router_b=(Lm, s["n_experts"]),
               w_gate=(Lm, Eh, H, M), w_up=(Lm, Eh, H, M),
               w_down=(Lm, Eh, M, H),
               s_gate=(Lm, H, Ms), s_up=(Lm, H, Ms), s_down=(Lm, Ms, H))
    return {"wte": (V, H), "head": (H, V), "lnf": (H,),
            "dense": dense, "moe": moe}


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
        scale = ROUTER_BIAS_STD if name == "router_b" else \
            EMBED_STD if name == "wte" else \
            std / math.sqrt(2 * sizes["n_layers"]) if name in RESIDUAL \
            else std

        def draw(kk, shape=shape, scale=scale):
            return (scale * jax.random.normal(kk, shape, jnp.float32)
                    ).astype(dtype)

        if len(path) > 1:          # a layer axis: one layer at a time
            out.append(jax.lax.map(
                functools.partial(draw, shape=shape[1:]),
                jax.random.split(k, shape[0])))
        else:
            out.append(draw(k))
    return jax.tree_util.tree_unflatten(tree, out)


def make_params(sizes, seed):
    """sizes: the configuration file's ``sizes``; ``init_std`` (0.02
    unless the file says otherwise) is the benchmark's own, not the
    program's."""
    return _make(seed_key(seed), tuple(sorted(sizes.items())))
