"""The held experts' gated FFN as one grouped-matmul kernel.

An expert layer that holds ``n_held`` experts multiplies each held row
(one token's assignment to a held expert) by that expert's three
matrices, ``(silu(x @ w_gate) * (x @ w_up)) @ w_down``, and adds the
result, times the assignment's gate, to the token's output. In a serving
step the rows are few and the weights are large (a held expert of the
``sarvam_mla`` model is 50.3 MB, a row 8 KB), so the work is a stream
of weights: what counts is that each held expert with a row is read
once a layer, and an expert with no row not at all.

- :func:`expert_tiles` — the kernel's work-list. The rows, sorted by
  expert, are cut into tiles of ``tm`` rows that each start a group or
  follow a full tile of it, so no tile holds two experts: a group of
  ``s`` rows takes ``ceil(s / tm)`` tiles and a group of none takes no
  tile. In the manner of ``ops/block_walk.py``: static length, a
  dynamic ``count`` of live tiles (the grid bound), entries past it
  repeating the last live tile.
- :func:`row_tile` — ``tm`` from the static shape: twice the rows a
  group gets when routing is even, between 16 (a bf16 tile's rows) and
  128. A group larger than ``tm`` takes several tiles and reads its
  weights once for each.
- :func:`grouped_ffn` — the kernel. Grid ``(live tile, block of the
  expert's hidden width)``. The tokens are loaded once, for the whole
  grid, and widened to float32 in VMEM, where a row can be picked out
  by a dynamic index. A tile's first step gathers its rows there; each
  step loads the block's columns of ``w_gate`` and ``w_up`` and rows of
  ``w_down`` of the tile's expert, forms ``h = silu(x wg) * (x wu)`` in
  float32, rounds it to the tokens' type and adds ``h wd`` into a
  float32 accumulator; the tile's last step adds each row, times its
  gate, into its token's row of the output, which stays in VMEM for the
  whole grid and is written once. The experts are groups ``group_base +
  g`` of stacked ``(G, H, M)`` / ``(G, M, H)`` arrays (several layers'
  experts), addressed in place through scalar prefetch: no expert's
  weights are sliced or copied. Its ``pallas_call`` is named
  ``ragged-dot-experts`` on the trace.

The routed entry is ``nn/moe.py: moe_ffn_held``: on a TPU, for shapes
:func:`tileable` accepts, this kernel; anywhere else
``jax.lax.ragged_dot``, which the interpret-mode tests pin it against
(tests/test_moe_gmm.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .flash_attention import _on_tpu

__all__ = ["ExpertTiles", "expert_tiles", "row_tile", "n_tiles",
           "tileable", "use_kernel", "grouped_ffn"]

# columns of an expert's hidden width a grid step reads: 3 x 4096 x 512
# x 2 B = 12.6 MB of weights a step for the sarvam_mla experts
HIDDEN_BLOCK = 512
# what the kernel may hold in VMEM (of v5e's 128 MiB)
_VMEM_CAP = 100 * 1024 * 1024


class ExpertTiles(NamedTuple):
    """A grid step each, (N,) int32: ``group``, the held expert of tile
    ``n``; ``first``, its first row in the expert-sorted order; ``rows``,
    how many of its ``tm`` rows hold an assignment (fewer in a group's
    last tile, 0 in an empty list). ``count``: (1,) int32, the live
    tiles."""
    group: jax.Array
    first: jax.Array
    rows: jax.Array
    count: jax.Array


def row_tile(rows: int, n_experts: int) -> int:
    """The row tile for ``rows`` assignments (tokens x top-k) routed over
    ``n_experts``: the smallest power of two from 16 to 128 that holds
    twice a group's rows under even routing."""
    tm = 16
    while tm < 128 and tm < 2 * rows / n_experts:
        tm *= 2
    return tm


def n_tiles(rows: int, n_held: int, tm: int) -> int:
    """The static length of the list: every held group may take one
    tile more than its rows fill."""
    return -(-(rows + n_held * (tm - 1)) // tm)


def expert_tiles(sizes, tm: int, length: int) -> ExpertTiles:
    """The live tiles of groups of ``sizes`` (n_held,) rows, the groups
    one after another in the sorted order, ``tm`` rows a tile, in a list
    of static ``length``."""
    sizes = jnp.asarray(sizes, jnp.int32)
    tiles = -(-sizes // tm)
    ends = jnp.cumsum(tiles)
    count = ends[-1]
    n = jnp.minimum(jnp.arange(length, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the group whose run of tiles holds n: as many runs end at or before it
    group = jnp.minimum(jnp.sum(n[:, None] >= ends[None, :], axis=1),
                        sizes.shape[0] - 1).astype(jnp.int32)
    begin = (jnp.cumsum(sizes) - sizes)[group]      # the group's first row
    first = begin + (n - (ends - tiles)[group]) * tm
    rows = jnp.clip(begin + sizes[group] - first, 0, tm)
    return ExpertTiles(group, first.astype(jnp.int32),
                       rows.astype(jnp.int32), count.reshape(1))


def _vmem_bytes(T, H, block, tm, x_bytes, w_bytes):
    """What the kernel holds in VMEM: the tokens (two buffers, and the
    float32 copy), the output (two buffers), the three weight blocks
    (two buffers each), the tile's rows and its accumulator."""
    return (T * H * (2 * x_bytes + 4 + 2 * 4) + 2 * 3 * H * block * w_bytes
            + 2 * tm * H * 4)


def tileable(x, w_gate, w_down, k: int, n_experts: int) -> bool:
    """Shapes the kernel takes on a TPU: a hidden size of whole lanes, an
    expert width of whole blocks, bf16 or f32 operands, and tokens few
    enough that they and the output stay in VMEM."""
    (T, H), M = x.shape, w_gate.shape[2]
    block = min(HIDDEN_BLOCK, M)
    if not (H % 128 == 0 and block % 128 == 0 and M % block == 0
            and w_down.shape[1:] == (M, H)
            and x.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    tm = row_tile(T * k, n_experts)
    return _vmem_bytes(T, H, block, tm, x.dtype.itemsize,
                       w_gate.dtype.itemsize) <= _VMEM_CAP


def use_kernel(x, w_gate, w_down, k: int, n_experts: int,
               interpret: bool = False) -> bool:
    """Whether ``moe_ffn_held`` takes the kernel: always in the
    interpreter (``interpret``), else on a TPU for :func:`tileable`
    shapes."""
    return interpret or (_on_tpu()
                         and tileable(x, w_gate, w_down, k, n_experts))


def _ffn_kernel(group_ref, first_ref, rows_ref, tok_ref, base_ref, gate_ref,
                x_ref, wg_ref, wu_ref, wd_ref, o_ref, xf_ref, xt_ref,
                acc_ref, *, dtype):
    from jax.experimental import pallas as pl

    i, n = pl.program_id(0), pl.program_id(1)
    first, live = first_ref[i], rows_ref[i]

    def each_row(fn):
        # row r of the tile is sorted row first + r
        def body(r, carry):
            fn(r, first + r)
            return carry
        jax.lax.fori_loop(0, live, body, 0)

    @pl.when((i == 0) & (n == 0))
    def _start():
        xf_ref[...] = x_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live > 0)
    def _tile():
        @pl.when(n == 0)
        def _gather():
            xt_ref[...] = jnp.zeros_like(xt_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def row_in(r, s):
                xt_ref[pl.ds(r, 1), :] = xf_ref[pl.ds(tok_ref[s], 1), :]
            each_row(row_in)

        x = xt_ref[...].astype(dtype)
        g = jnp.dot(x, wg_ref[...].astype(dtype),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...].astype(dtype),
                    preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[...].astype(dtype),
                                preferred_element_type=jnp.float32)

        @pl.when(n == pl.num_programs(1) - 1)
        def _combine():
            def row_out(r, s):
                t = tok_ref[s]
                o_ref[pl.ds(t, 1), :] += gate_ref[s] * acc_ref[pl.ds(r, 1), :]
            each_row(row_out)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_ffn(x, w_gate, w_up, w_down, tok, gate, tiles: ExpertTiles,
                group_base, *, tm: int, interpret=False):
    """``y[t] = sum over the sorted rows s of token t (tok[s] = t) of
    gate[s] * E(x[t])``, E the gated FFN of the row's expert. x (T, H):
    the tokens; ``tok`` (R,) int32 and ``gate`` (R,) float32: each
    sorted row's token and gate, the held rows grouped by expert as
    ``tiles`` walks them (rows no tile names take no part); w_gate /
    w_up (G, H, M), w_down (G, M, H); tile ``n`` uses expert
    ``group_base + tiles.group[n]`` (``group_base`` may be traced).
    Returns y (T, H) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H = x.shape
    M = w_gate.shape[2]
    block = min(HIDDEN_BLOCK, M)
    base = jnp.reshape(jnp.asarray(group_base, jnp.int32), (1,))

    def whole(*_):
        return 0, 0

    def weight(shape, at):
        return pl.BlockSpec(
            (None,) + shape,
            lambda i, n, grp, first, rows, tok, gb: (gb[0] + grp[i],) + at(n))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # an empty list still takes a step: the output is zeroed there
        grid=(jnp.maximum(tiles.count[0], 1), M // block),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((T, H), whole),
            weight((H, block), lambda n: (0, n)),
            weight((H, block), lambda n: (0, n)),
            weight((block, H), lambda n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((T, H), whole),
        scratch_shapes=[pltpu.VMEM((T, H), jnp.float32),
                        pltpu.VMEM((tm, H), jnp.float32),
                        pltpu.VMEM((tm, H), jnp.float32)],
    )
    need = _vmem_bytes(T, H, block, tm, x.dtype.itemsize,
                       w_gate.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, dtype=x.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(need + need // 4 + (4 << 20),
                                 _VMEM_CAP + (16 << 20))),
        interpret=interpret,
        name="ragged-dot-experts",
    )(tiles.group, tiles.first, tiles.rows, tok, base,
      gate.astype(jnp.float32), x, w_gate, w_up, w_down)
