"""The work-list of the table-walking decode kernels: live blocks only.

A paged decode kernel serves one query a slot over the blocks the
slot's table row names. A grid of ``(slots, table width)`` spends a
step on every table entry, and at a serving engine's usual occupancy
nearly all of them are padding: a slot with no request, or the columns
past a short context. :func:`live_steps` turns the slots' lengths into
a flat list of the steps that carry work, ``group`` blocks a step, for
the kernels' scalar prefetch:

- a slot with ``ceil(length / block_size)`` live blocks gets
  ``ceil(blocks / group)`` steps, in table order, and a slot of length
  0 none;
- ``count`` is how many steps are live, the kernels' (dynamic) grid
  bound: a step past it never runs;
- the arrays have the static length ``n_slots * ceil(width / group)``,
  every slot full; the entries past ``count`` name the last live step
  again, marked neither first nor last.

It is the same for every layer of a tick, so a model builds it once,
outside its layer loop, and hands it to the kernel at each layer.
Both callers (``ops/paged_attention.py``, ``ops/mla_attention.py``)
read :func:`step_block` for the step's ``j``-th block, reset their
softmax state where ``first[n]`` is set and write the slot's output row
where ``last[n]`` is.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["LiveSteps", "live_steps", "step_block", "check_walk"]


class LiveSteps(NamedTuple):
    """``slot``, ``col``, ``first``, ``last``: (N,) int32, a grid step
    each; ``count``: (1,) int32, the live steps."""
    slot: jax.Array     # the slot the step serves
    col: jax.Array      # the first table column of the step's group
    first: jax.Array    # 1 on a slot's first step
    last: jax.Array     # 1 on a slot's last step
    count: jax.Array


def live_steps(lengths, width: int, block_size: int, group: int) -> LiveSteps:
    """The live steps of slots holding ``lengths`` (B,) tokens in tables
    ``width`` columns wide, ``group`` blocks of ``block_size`` a step."""
    lengths = jnp.asarray(lengths, jnp.int32)
    n_slots = lengths.shape[0]
    blocks = jnp.clip(-(-lengths // block_size), 0, width)
    steps = -(-blocks // group)
    ends = jnp.cumsum(steps)
    count = ends[-1]
    every = jnp.arange(n_slots * -(-width // group), dtype=jnp.int32)
    n = jnp.minimum(every, jnp.maximum(count - 1, 0))
    # the slot whose run of steps holds n: as many runs end at or before it
    slot = jnp.minimum(jnp.sum(n[:, None] >= ends[None, :], axis=1),
                       n_slots - 1).astype(jnp.int32)
    col = (n - (ends - steps)[slot]) * group
    live = every < count
    return LiveSteps(slot, col,
                     (live & (col == 0)).astype(jnp.int32),
                     (live & (n == ends[slot] - 1)).astype(jnp.int32),
                     count.reshape(1))


def step_block(n, j, tables, lengths, slot, col, block_size: int):
    """In a kernel's index map (the arguments are its scalar-prefetch
    refs): the pool block that step ``n`` reads as its ``j``-th, column
    ``col[n] + j`` of the slot's table row, clamped to the slot's last
    live column (a repeated block index elides the DMA; the kernel masks
    its positions)."""
    s = slot[n]
    last = jnp.maximum((lengths[s] - 1) // block_size, 0)
    return tables[s, jnp.minimum(col[n] + j, last)]


def check_walk(walk: LiveSteps, n_slots: int, width: int, group: int):
    """Refuse a walk that was built for other slots, another table width
    or another group than the kernel's."""
    want = n_slots * (width // group)
    if walk.slot.shape[0] != want:
        raise ValueError(
            f"walk of {walk.slot.shape[0]} steps is not decode_walk's for "
            f"{n_slots} slots and table width {width}: {want} steps")
