"""Seeded weights of the SambaY block, made on the device in one jitted
call, in the type the configuration holds them in (bf16), in the layout
``paddle_tpu.models.sambay`` takes: ``wte``, ``lnf_w``, ``lnf_b``, the
self-decoder's pairs under ``self`` (``ssm``, ``attn``) and the
cross-decoder's under ``cross`` (``gmu``, ``xattn``) with a leading pair
axis, layer L/2 under ``mem`` and layer L/2 + 1 under ``kv``.

Normal(0, ``init_std``), the embedding's rows too (the published
``initializer_range``, 0.02: through the final LayerNorm and the tied
head the logits are then about one wide, a trained model's scale),
projections back into the residual stream (``out``, ``wo``, ``w_out``,
``w_down``) scaled by 1 / sqrt(2 L); LayerNorm scales 1 and shifts 0, the RMSNorm of a pair's
output 1; Mamba's ``A_log = log(1 .. d_state)`` for every channel, ``D =
1``, the convolution normal(0, 1 / sqrt(d_conv)), the bias of ``dt`` the
inverse softplus of a ``dt`` log-uniform in [1e-3, 1e-1]; the lambda
vectors normal(0, 0.1). Every leaf is drawn a slab at a time (a layer,
or as many rows of the embedding as stay under 2^27 values).
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import DTYPES, seed_key

RESIDUAL = ("out", "wo", "w_out", "w_down")
LAMBDAS = ("lq1", "lk1", "lq2", "lk2")
SLAB = 2 ** 27       # values of one float32 draw


def _layer(s, kind):
    H, F, d = s["hidden"], s["ffn"], s["head_dim"]
    Di, N, R, K = s["expand"] * H, s["d_state"], s["dt_rank"], s["d_conv"]
    nq, nkv = s["n_heads"] * d, s["n_kv_heads"] * d
    out = {"ln1_w": (H,), "ln1_b": (H,), "ln2_w": (H,), "ln2_b": (H,),
           "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}
    if kind == "ssm":
        out.update(in_x=(H, Di), in_z=(H, Di), conv_w=(K, Di),
                   conv_b=(Di,), x_dt=(Di, R), x_b=(Di, N), x_c=(Di, N),
                   dt_w=(R, Di), dt_b=(Di,), a_log=(N, Di), d=(Di,),
                   out=(Di, H))
    elif kind == "gmu":
        out.update(w_in=(H, Di), w_out=(Di, H))
    else:
        out.update(wq=(H, nq), bq=(nq,), wo=(nq, H), bo=(H,),
                   subln=(2 * d,), **{k: (d,) for k in LAMBDAS})
        if kind == "attn":
            out.update(wkv=(H, 2 * nkv), bkv=(2 * nkv,))
    return out


def shapes(s):
    """{leaf path: shape} from the configuration's ``sizes``."""
    P = s["n_layers"] // 4

    def stack(n, kind):
        return {k: (n,) + v for k, v in _layer(s, kind).items()}

    return {"wte": (s["vocab_size"], s["hidden"]), "lnf_w": (s["hidden"],),
            "lnf_b": (s["hidden"],),
            "self": {"ssm": stack(P, "ssm"), "attn": stack(P, "attn")},
            "mem": _layer(s, "ssm"), "kv": _layer(s, "attn"),
            "cross": {"gmu": stack(P - 1, "gmu"),
                      "xattn": stack(P - 1, "xattn")}}


def _slab_rows(shape):
    """The largest divisor of ``shape[0]`` whose slab stays under
    ``SLAB`` values."""
    cap = max(1, SLAB // max(1, math.prod(shape[1:])))
    return max(k for k in range(1, min(shape[0], cap) + 1)
               if shape[0] % k == 0)


def _leaf(name, kk, slab, s, std):
    """One slab of leaf ``name``, float32."""
    N = s["d_state"]
    if name in ("ln1_w", "ln2_w", "lnf_w", "subln", "d"):
        return jnp.ones(slab, jnp.float32)
    if name in ("ln1_b", "ln2_b", "lnf_b"):
        return jnp.zeros(slab, jnp.float32)
    if name == "a_log":
        row = jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row.reshape((1,) * (len(slab) - 2)
                                            + (N, 1)), slab)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(kk, slab, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    scale = 1.0 / math.sqrt(s["d_conv"]) if name == "conv_w" else \
        0.1 if name in LAMBDAS else \
        std / math.sqrt(2 * s["n_layers"]) if name in RESIDUAL else std
    return scale * jax.random.normal(kk, slab, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, frozen_sizes):
    s = dict(frozen_sizes)
    dtype = DTYPES[s["param_dtype"]]
    std = float(s.get("init_std", 0.02))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(s), is_leaf=lambda v: isinstance(v, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        stacked = path[0].key in ("self", "cross")
        rows = 1 if stacked else _slab_rows(shape)
        slab = (rows,) + shape[1:]

        def draw(kk, name=name, slab=slab):
            return _leaf(name, kk, slab, s, std).astype(dtype)

        out.append(jax.lax.map(
            draw, jax.random.split(k, shape[0] // rows)).reshape(shape))
    return jax.tree_util.tree_unflatten(tree, out)


def make_params(sizes, seed):
    """sizes: the configuration file's ``sizes``; ``init_std`` (0.02
    unless the file says otherwise) is the benchmark's own."""
    return _make(seed_key(seed), tuple(sorted(sizes.items())))
