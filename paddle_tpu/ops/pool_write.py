"""The decode tick's row writer: new rows into a paged pool, in place.

A paged pool is ``(n_blocks, n_layers, *block)`` and a block is
``(..., block_size, width)``: ``(n_heads, block_size, head_dim)`` for the
per-head K and V pools of ``models/gpt.py``, ``(block_size, pool_row)``
for the latent pool of ``models/mla.py``. A one-token decode step has
one new row a lane and a pool array, ``(..., width)``, that belongs at
row ``off`` of block ``blk`` at the step's layer. This module puts them
there:

- :func:`pool_write_rows` — the routed entry. On a TPU it is ONE Pallas
  call a layer for every array of the pool and every live lane; anywhere
  else it is :func:`write_rows_composed`, one
  ``dynamic_update_slice`` a lane and an array (:func:`pool_put`), each
  on the value the last returned: the reference the kernel is pinned to
  in interpret mode (tests/test_paged_attention.py), and what the verify
  step of speculative decoding keeps (several rows a lane).

Kernel design:
- a row is not a tile: a bf16 row is half a packed sublane of a
  ``(16, 128)`` tile, so a one-row copy at a dynamic offset is not
  aligned. A block is whole tiles and is what the decode kernel reads
  next anyway. So a step is a whole-block read-modify-write,
  ``where(row == off, new_row, block)``;
- the grid is the tick's list of LIVE lanes (:func:`live_lanes`), a
  dynamic grid bound as in ``ops/block_walk.py``: a lane with no request
  gets no step and writes nothing, not even into the sink block. The
  list is the same at every layer, so the model builds it once a tick;
- the list, each lane's ``blk`` and ``off`` and the layer ride as scalar
  prefetch. Every pool array is handed in once, its block and layer
  squeezed, indexed ``(blk[lane], layer)`` for input and output alike,
  and aliased to its output: the carried (donated) pool is updated in
  place and no other block of it is touched;
- live lanes own their blocks exclusively, so no two steps of a call
  touch one block.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .flash_attention import _on_tpu

__all__ = ["LiveLanes", "live_lanes", "pool_put", "pool_write_rows",
           "write_rows_composed"]


class LiveLanes(NamedTuple):
    """``lane``: (B + 1,) int32, the live lanes first, in lane order;
    the entries past ``count`` are 0, and the one past the last lane
    keeps a list of every lane live one entry longer than its steps (the
    spare step of ``ops/block_walk.live_steps``, for the same reason);
    ``count``: (1,) int32, how many are live."""
    lane: jax.Array
    count: jax.Array


def live_lanes(lengths) -> LiveLanes:
    """The lanes of ``lengths`` (B,) that hold a request (length > 0):
    the writer's work-list. The same for every layer of a tick: build it
    once, outside the layer loop, and pass it as ``lanes=``."""
    live = jnp.asarray(lengths, jnp.int32) > 0
    ids = jnp.arange(live.shape[0], dtype=jnp.int32)
    # a live lane's place in the list: the live lanes before it
    place = jnp.cumsum(live.astype(jnp.int32)) - 1
    at = jnp.arange(live.shape[0] + 1, dtype=jnp.int32)
    here = live[None, :] & (place[None, :] == at[:, None])
    lane = jnp.sum(jnp.where(here, ids[None, :], 0), axis=1)
    return LiveLanes(lane.astype(jnp.int32),
                     jnp.sum(live, dtype=jnp.int32).reshape(1))


def pool_put(pool, update, at):
    """One in-place write of ``update`` into the pool at ``at`` (block,
    layer, ..., offset, 0). A dynamic-update-slice keeps the pool's own
    layout (a scatter makes XLA re-lay the whole pool out around it);
    block ids and offsets are never negative, so no index is wrapped."""
    return jax.lax.dynamic_update_slice(
        pool, update.astype(pool.dtype), at, allow_negative_indices=False)


def write_rows_composed(pools, rows, blk, off, layer):
    """The composed path: ``rows[i]`` (N, ..., width) into ``pools[i]``
    (n_blocks, L, ..., bs, width) at ``(blk[n], layer, ..., off[n], 0)``,
    one :func:`pool_put` a row and an array, in row order. Every row is
    written: a lane with no request points at the sink block."""
    pools = list(pools)
    for n in range(blk.shape[0]):
        for i, row in enumerate(rows):
            pools[i] = pool_put(
                pools[i], jnp.expand_dims(row[n], -2)[None, None],
                (blk[n], layer, *(0,) * (row.ndim - 2), off[n], 0))
    return tuple(pools)


def _write_kernel(lane_ref, blk_ref, off_ref, layer_ref, *refs):
    from jax.experimental import pallas as pl

    n = len(refs) // 3
    off = off_ref[lane_ref[pl.program_id(0)]]
    for row_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                        refs[2 * n:]):
        block = in_ref[...]                       # (..., bs, width)
        at = jax.lax.broadcasted_iota(
            jnp.int32, block.shape, block.ndim - 2) == off
        out_ref[...] = jnp.where(at, row_ref[...], block)


def _pool_write(pools, rows, blk, off, layer, lanes, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    n_scalars = 4

    def row_spec(row):
        # (B, ..., 1, width): the lane squeezed, one row to broadcast
        # down the block's rows
        shape = row.shape[1:-1] + (1, row.shape[-1])
        return pl.BlockSpec(
            (None,) + shape,
            lambda n, lane, blk, off, lay: (lane[n],) + (0,) * len(shape))

    def block_spec(pool):
        block = pool.shape[2:]
        return pl.BlockSpec(
            (None, None) + block,
            lambda n, lane, blk, off, lay:
                (blk[lane[n]], lay[0]) + (0,) * len(block))

    blocks = [block_spec(p) for p in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(lanes.count[0],),
        in_specs=[row_spec(r) for r in rows] + blocks,
        out_specs=blocks,
    )
    return tuple(pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands count from the first scalar: pool i is updated in place
        input_output_aliases={n_scalars + len(rows) + i: i
                              for i in range(len(pools))},
        interpret=interpret,
        name="pool_write_rows",
    )(lanes.lane, blk, off, layer,
      *(jnp.expand_dims(r, -2).astype(p.dtype) for r, p in zip(rows, pools)),
      *pools))


def pool_write_rows(pools, rows, blk, off, layer, lanes, interpret=None):
    """Write one new row a lane into every array of a paged pool, in
    place (routed entry).

    pools — a tuple of arrays ``(n_blocks, L, ..., bs, width)``, the
    WHOLE pool; rows — as many arrays ``(B, ..., width)``, lane ``b``'s
    new row of each; blk/off (B,) int32 — the block and the row in it
    that lane ``b`` writes; ``layer`` an int or a traced int32 scalar.
    ``lanes`` is :func:`live_lanes` of the tick's lengths, built once
    where the call is made at every layer of a model. Returns the pools.

    On a TPU a lane not in ``lanes`` writes nothing. Off-TPU (unless
    ``interpret=True`` is forced) this is :func:`write_rows_composed`,
    which writes every lane's row: a lane with no request names the sink
    block, which nothing reads.
    """
    blk = jnp.asarray(blk, jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return write_rows_composed(pools, rows, blk, off, layer)
    return _pool_write(tuple(pools), tuple(rows), blk, off, layer, lanes,
                       interpret=bool(interpret))
