"""What every family's weights share: the key a seed gives and the
types a configuration file may name.

The benchmark makes the weights, hands them to the program, and makes
them again for the plain reference: the reference takes nothing the
program has made. Which tree that is belongs to the model's family: its
``make_params(sizes, seed)`` is in ``benchmarks/families/<family>/``
(the GPT block's, which stood here until PR 29, is in
``benchmarks/families/gpt/weights.py``), one jitted call on the device
that draws from ``seed_key(seed)``.
"""
import jax
import jax.numpy as jnp

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed):
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
