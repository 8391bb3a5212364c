"""Mamba's selective scan (arXiv:2312.00752) for a serving engine.

Per channel ``c`` of the inner width and state row ``n`` of ``d_state``,
with ``A = -exp(A_log)`` (d_state, inner):

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[n, c] + D[c] x_t[c]

The state is ``(d_state, inner)`` float32: the inner width on lanes
(5,120 is 40 whole tiles), the 16 state rows on sublanes.

- :func:`selective_scan` — a prefill chunk of one sequence: rows at or
  past ``n_true`` do not enter the state (``dt`` is 0 there), so one
  program serves every length of a padded chunk. On a TPU the Pallas
  kernel ``selective_scan_chunk``: the grid is the inner width in tiles
  of ``TILE`` lanes; a step holds its tile of the state in registers
  across the chunk's tokens, eight rows of ``x`` and ``dt`` a load, and
  writes ``y`` eight rows at a time and the state once. ``B_t`` and
  ``C_t`` arrive broadcast over one 128-lane tile, so a token's column
  of them is a whole vector register. Anywhere else (unless
  ``interpret=True``) a ``lax.scan`` over the tokens, the composed path
  the kernel is pinned to (tests/test_sambay.py).
- :func:`selective_step` — one token a lane: plain ``jax.numpy``, which
  XLA fuses into one pass over the lanes' states (the decode step's
  states are updated in place by the caller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _on_tpu

__all__ = ["selective_scan", "selective_step", "TILE"]

TILE = 512           # inner-width lanes a grid step of the chunk kernel
LANES = 128
ROWS = 8             # tokens a load of x and dt: one sublane tile
_VMEM_LIMIT = 64 * 1024 * 1024


def selective_step(x, dt, A, B, C, D, state):
    """x, dt (b, inner) float32; A (N, inner); B, C (b, N); D (inner,);
    state (b, N, inner) float32. -> (y (b, inner), state)."""
    state = jnp.exp(dt[:, None, :] * A) * state \
        + (dt * x)[:, None, :] * B[:, :, None]
    y = jnp.sum(C[:, :, None] * state, axis=1) + D * x
    return y, state


def _composed(x, dt, A, B, C, D, state, n_true):
    def step(s, inp):
        t, xt, dtt, bt, ct = inp
        dtt = jnp.where(t < n_true, dtt, 0.0)
        y, s = selective_step(xt[None], dtt[None], A, bt[None], ct[None], D,
                              s[None])
        return s[0], y[0]

    T = x.shape[0]
    state, y = jax.lax.scan(step, state, (jnp.arange(T), x, dt, B, C))
    return y, state


def _kernel(n_true_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_ref,
            y_ref, so_ref, *, reps):
    from jax.experimental import pallas as pl

    A = a_ref[...]                                     # (N, TILE)
    D = d_ref[...]                                     # (1, TILE)
    n_true = n_true_ref[0]

    def rows(i, s):
        t0 = pl.multiple_of(i * ROWS, ROWS)
        xs = x_ref[pl.ds(t0, ROWS), :]                 # (ROWS, TILE)
        dts = dt_ref[pl.ds(t0, ROWS), :]
        bs = b_ref[pl.ds(t0, ROWS)]                    # (ROWS, N, LANES)
        cs = c_ref[pl.ds(t0, ROWS)]
        ys = []
        for j in range(ROWS):
            xt = xs[j:j + 1]
            dtt = jnp.where(t0 + j < n_true, dts[j:j + 1], 0.0)
            bt = jnp.tile(bs[j], (1, reps))            # (N, TILE)
            ct = jnp.tile(cs[j], (1, reps))
            s = jnp.exp(dtt * A) * s + (dtt * xt) * bt
            ys.append(jnp.sum(ct * s, axis=0, keepdims=True) + D * xt)
        y_ref[pl.ds(t0, ROWS), :] = jnp.concatenate(ys, axis=0)
        return s

    so_ref[...] = jax.lax.fori_loop(0, x_ref.shape[0] // ROWS, rows,
                                    s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(x, dt, A, B, C, D, state, n_true, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, inner = x.shape
    N = A.shape[0]
    tile = min(TILE, inner)
    if T % ROWS or inner % tile or tile % LANES:
        raise ValueError(f"selective_scan_chunk: {T} tokens, inner width "
                         f"{inner}: tokens must be whole {ROWS}-row tiles "
                         f"and the width whole {LANES}-lane tiles")

    def cols(i, n):
        return (0, i)

    def whole(i, n):
        return (0, 0, 0)

    wide = lambda m: jnp.broadcast_to(                  # noqa: E731
        m[:, :, None], (T, N, LANES)).astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(inner // tile,),
        in_specs=[pl.BlockSpec((T, tile), cols),        # x
                  pl.BlockSpec((T, tile), cols),        # dt
                  pl.BlockSpec((T, N, LANES), whole),   # B, lane-broadcast
                  pl.BlockSpec((T, N, LANES), whole),   # C
                  pl.BlockSpec((N, tile), cols),        # A
                  pl.BlockSpec((1, tile), cols),        # D
                  pl.BlockSpec((N, tile), cols)],       # state in
        out_specs=[pl.BlockSpec((T, tile), cols),
                   pl.BlockSpec((N, tile), cols)],
    )
    y, state = pl.pallas_call(
        functools.partial(_kernel, reps=tile // LANES),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, inner), jnp.float32),
                   jax.ShapeDtypeStruct((N, inner), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="selective_scan_chunk",
    )(jnp.reshape(n_true, (1,)).astype(jnp.int32), x, dt, wide(B), wide(C),
      A, D[None], state)
    return y, state


def selective_scan(x, dt, A, B, C, D, state, n_true, interpret=None):
    """A chunk of one sequence through the recurrence.

    x, dt (T, inner) float32 (``dt`` after its softplus); A (N, inner)
    float32, negative; B, C (T, N) float32; D (inner,) float32; state
    (N, inner) float32, the state before the chunk; ``n_true`` (a traced
    int32 scalar): rows ``t >= n_true`` leave the state as it is. On a
    TPU ``T`` must be whole 8-row tiles and ``inner`` whole 128-lane
    tiles. Returns (y (T, inner) float32, the state after the last real
    row)."""
    n_true = jnp.asarray(n_true, jnp.int32)
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _composed(x, dt, A, B, C, D, state, n_true)
    return _call(x, dt, A, B, C, D, state, n_true,
                 interpret=bool(interpret))
