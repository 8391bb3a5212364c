"""What every family's plain reference shares: the low-precision
control, AdamW, and the sums under a leaf norm.

A family's reference (``benchmarks/families/<family>/reference.py``) is
the model in straightforward ``jax.numpy`` and float32, with no kernel,
cache or batching trick; it imports nothing of the program, and takes
these from here. The GPT-2-style decoder that stood here until PR 29 is
in ``benchmarks/families/gpt/reference.py``.

``lowp`` computes every matmul's operands in a lower precision (the
control of "How correct is decided"): "bf16", "fp8" (e4m3) or None.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
_LOWP = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def _q(x, lowp):
    if lowp is None:
        return x
    if lowp == "fp8":
        # per-tensor scaling into e4m3's range, as fp8 recipes do
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
        return (x / s).astype(_LOWP[lowp]).astype(F32) * s
    return x.astype(_LOWP[lowp]).astype(F32)


def _mm(x, w, lowp):
    return jnp.matmul(_q(x, lowp), _q(w, lowp), precision="highest")


@jax.jit
def adamw(params, grads, m, v, count, lr, b1, b2, eps, wd):
    """Decoupled weight decay applied to every leaf before the Adam
    step (the order the program's optimizer documents)."""
    count = count + 1
    c = count.astype(F32)
    bc1, bc2 = 1.0 - b1 ** c, 1.0 - b2 ** c

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        p = p * (1.0 - lr * wd) - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        return p, m, v

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(     # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), count


@jax.jit
def _sq_sums(tree):
    return jax.tree_util.tree_map(
        lambda v: jnp.sum(jnp.square(v.astype(F32))), tree)
