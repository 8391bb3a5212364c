"""Power retention of degree 2 over a pool of per-sequence states.

Power retention (arXiv:2507.04239) replaces a softmax over the context
by ``(q.k)^2``, which factors: ``(q.k)^2 = phi(q).phi(k)`` with ``phi(u)
= (c_ab u_a u_b)_{a <= b}``, so a causal, gated sum over the context is
a recurrence over one fixed-size state a key/value head

    S_t = g_t S_{t-1} + phi(k_t) [v_t; 1]^T        g_t = exp(lg_t) <= 1
    [num_t; den_t] = phi(q_t)^T S_t;   y_t = num_t / (den_t + eps)

``q`` and ``k`` arrive scaled by ``d^(-1/4)``, so ``phi(q).phi(k) =
(q.k)^2 / d``. The normaliser ``z_t = g_t z_{t-1} + phi(k_t)`` rides in
the state as the row of the constant 1 that is appended to ``v``.

**The state as stored.** ``phi`` is laid out in blocks of ``PHI_BLOCK``
rows of the (a, b) triangle: block i holds ``u_a u_b`` for ``a`` in the
block and every ``b`` from the block's first on (weight 1 inside the
block, where (a, b) and (b, a) both appear; sqrt 2 beyond it), a-major:
``phi_width(128) = 9216`` columns for the 8256 distinct products, every
piece a multiple of 128 lanes, built from static slices alone. A state
is ``(dv + NORM_ROWS, phi_width)`` float32: the values' dimension in
sublanes (row ``dv`` the normaliser, the rest of the last sublane tile
zero), ``phi`` in lanes. So the update ``S += [v; 1] phi(k)^T`` needs
``phi(k)`` as a row (a sublane broadcast, free) and ``v`` as a column
that is broadcast once a step, and the pool

    pool : (n_blocks, n_layers, n_kv_heads, dv + NORM_ROWS, phi_width)

has a minor dimension that is whole 128-lane tiles (one that is not
made every kernel call copy PR 30's pool). A block of the pool is ONE
SEQUENCE'S WHOLE STATE at every layer; block 0 is the sink.

- :func:`retention_decode`: one token a lane. On a TPU the Pallas
  kernel ``power_retention_decode``: the grid is (live lanes, key/value
  heads, lane tiles of the state), a dead lane costs no step; each step
  reads a tile of the state from the pool, decays it, adds the outer
  product, writes it back IN PLACE (the pool is aliased to the output;
  blocks no step names keep what they held) and accumulates the
  group's query heads' read-out from the tile while it is in VMEM, on
  the VPU in float32.
- :func:`retention_chunk`: a prefill chunk of one sequence. The part
  inside the chunk is the quadratic form (``jax.numpy``: it is 2% of
  the chunk's work); the part that meets the state is the Pallas kernel
  ``power_retention_chunk``: per key/value head and lane tile, ``phi(q)
  S^T`` and ``S' = exp(b_C) S + [v w; w]^T phi(k)`` on the MXU, bf16
  operands and a float32 accumulator, the state float32 in the pool.
  The true token count masks the padded tail (gate 1, no contribution),
  so one program serves every length of a pad bucket; ``start == 0``
  ignores what the state held.

Anywhere else than a TPU (unless ``interpret=True``) the identical
composed ``jax.numpy`` runs, pinned against the kernels by
interpret-mode tests (tests/test_retention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash_attention import _on_tpu

__all__ = ["phi", "phi_width", "state_rows", "retention_decode",
           "retention_chunk", "NORM_ROWS"]

PHI_BLOCK = 16       # rows of the (a, b) triangle a block of phi holds
NORM_ROWS = 8        # a sublane tile under the values: row 0 the normaliser
LANES = 128
_SQRT2 = math.sqrt(2.0)
_VMEM_LIMIT = 64 * 1024 * 1024


def _phi_block(d: int) -> int:
    if d > PHI_BLOCK and d % PHI_BLOCK == 0:
        return PHI_BLOCK
    return max(d // 2, 1)


def phi_width(d: int) -> int:
    """Columns ``phi`` of a ``d``-wide vector is stored in: the blocked
    triangle, padded to whole 128-lane tiles (9216 for d = 128, where
    nothing is padded; d (d + 1) / 2 = 8256 of them are distinct)."""
    blk = _phi_block(d)
    n = sum(blk * (d - i * blk) for i in range(d // blk))
    return -(-n // LANES) * LANES


def state_rows(dv: int) -> int:
    return dv + NORM_ROWS


def phi(x, scale=None, dtype=jnp.float32):
    """(..., d) -> (..., phi_width(d)) with ``phi(u).phi(w) == (u.w)^2``
    (up to float32 rounding of sqrt 2): the products are float32,
    multiplied by ``scale`` (...,) where given, and each piece is cast
    to ``dtype`` before the pieces are joined, so that no float32 copy
    of a chunk's expansion exists."""
    d = x.shape[-1]
    blk = _phi_block(d)
    x = x.astype(jnp.float32)
    lead = x if scale is None else x * scale[..., None]
    pieces = []
    for i in range(d // blk):
        lo = i * blk
        w = jnp.concatenate([jnp.ones((blk,), jnp.float32),
                             jnp.full((d - lo - blk,), _SQRT2, jnp.float32)])
        outer = lead[..., lo:lo + blk, None] * (x[..., lo:] * w)[..., None, :]
        pieces.append(outer.astype(dtype).reshape(x.shape[:-1] + (-1,)))
    out = jnp.concatenate(pieces, axis=-1)
    pad = phi_width(d) - out.shape[-1]
    if pad:
        out = jnp.pad(out, [(0, 0)] * (out.ndim - 1) + [(0, pad)])
    return out


def _with_one(v, dtype):
    """(..., dv) -> (..., dv + NORM_ROWS): the constant 1 whose sum is
    the normaliser, then zeros to the sublane tile's end."""
    one = jnp.zeros(v.shape[:-1] + (NORM_ROWS,), dtype).at[..., 0].set(1)
    return jnp.concatenate([v.astype(dtype), one], axis=-1)


def _lane_tile(width: int, cap: int) -> int:
    """The largest divisor of ``width`` that is whole lane tiles and at
    most ``cap``."""
    n = width // LANES
    best = 1
    for k in range(1, n + 1):
        if n % k == 0 and k * LANES <= cap:
            best = k
    return best * LANES


# -- decode -------------------------------------------------------------------

def _decode_kernel(slots_ref, blocks_ref, layer_ref, gate_ref, phik_ref,
                   phiq_ref, vb_ref, s_ref, o_ref, y_ref, *, group):
    from jax.experimental import pallas as pl

    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    g = gate_ref[...]                                  # (1, 128)
    vb = vb_ref[...]                                   # (R, 128)

    def tile(c, carry):
        at = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
        s = g * s_ref[:, at] + vb * phik_ref[:, at]    # (R, 128)
        o_ref[:, at] = s
        for j in range(group):
            y_ref[j] += s * phiq_ref[pl.ds(j, 1), at]
        return carry

    jax.lax.fori_loop(0, s_ref.shape[-1] // LANES, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(gate, phik, phiq, vb, pool, slots, blocks, count, layer,
                 interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, G, DP = phiq.shape
    R = pool.shape[3]
    DT = _lane_tile(DP, 2304)

    def lane(n, h, t, slots, blocks, layer):
        return (slots[n], h, 0, 0)

    def lane_t(n, h, t, slots, blocks, layer):
        return (slots[n], h, 0, t)

    def state(n, h, t, slots, blocks, layer):
        return (blocks[slots[n]], layer[0], h, 0, t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(count[0], Hkv, DP // DT),
        in_specs=[
            pl.BlockSpec((None, None, 1, LANES), lane),        # gate
            pl.BlockSpec((None, None, 1, DT), lane_t),         # phi(k)
            pl.BlockSpec((None, None, G, DT), lane_t),         # phi(q)
            pl.BlockSpec((None, None, R, LANES), lane),        # [v; 1]
            pl.BlockSpec((None, None, None, R, DT), state),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, R, DT), state),
            pl.BlockSpec((None, None, G, R, LANES),
                         lambda n, h, t, slots, blocks, layer:
                         (slots[n], h, 0, 0, 0)),
        ],
    )
    pool, y = pl.pallas_call(
        functools.partial(_decode_kernel, group=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, R, LANES), jnp.float32)],
        # operand 7 (after the 3 scalar-prefetch ones): the pool
        input_output_aliases={7: 0},
        interpret=interpret,
        name="power_retention_decode",
    )(slots, blocks, layer, gate, phik, phiq, vb, pool)
    return pool, y


def _decode_composed(gate, phik, phiq, vaug, pool, blocks, live, layer):
    """gate (B, Hkv); phik (B, Hkv, DP); phiq (B, Hkv, G, DP); vaug (B,
    Hkv, R). -> (pool, [num; den] (B, Hkv, G, R))."""
    s = pool[blocks, layer]                            # (B, Hkv, R, DP)
    new = gate[..., None, None] * s + vaug[..., :, None] * phik[..., None, :]
    out = jnp.einsum("bhgp,bhrp->bhgr", phiq, new,
                     precision=jax.lax.Precision.HIGHEST)
    # a dead lane names the sink: it keeps what it held
    new = jnp.where(live[:, None, None, None], new, s)
    return pool.at[blocks, layer].set(new), out


def retention_decode(q, k, v, lg, pool, blocks, live, layer, eps,
                     interpret=None, composed=None):
    """One token a lane against the lanes' states, in place.

    q (B, Hq, d) and k (B, Hkv, d), both already scaled by d^(-1/4); v
    (B, Hkv, dv); lg (B, Hkv) float32 log gates (<= 0); pool the WHOLE
    state pool (n_blocks, L, Hkv, dv + NORM_ROWS, phi_width(d)) float32
    with ``layer`` (an int or a traced int32 scalar) naming the layer;
    blocks (B,) int32 the state each lane owns; live (B,) bool: a lane
    that is not live (no request, or one still in prefill) costs no
    grid step, its state is not touched and its output row is zeros.
    ``composed`` forces the ``jax.numpy`` path (True) or the kernel
    (False); left None, the kernel runs on a TPU or where ``interpret``
    is given. Returns (y (B, Hq, dv) float32, pool)."""
    B, Hq, _ = q.shape
    Hkv, dv = v.shape[1:]
    G = Hq // Hkv
    gate = jnp.exp(lg.astype(jnp.float32))
    phik = phi(k)                                      # (B, Hkv, DP)
    phiq = phi(q).reshape(B, Hkv, G, -1)
    vaug = _with_one(v, jnp.float32)                   # (B, Hkv, R)
    blocks = jnp.asarray(blocks, jnp.int32)
    if composed is None:
        composed = interpret is None and not _on_tpu()
    if composed:
        pool, out = _decode_composed(gate, phik, phiq, vaug, pool, blocks,
                                     live, layer)
    else:
        # the live lanes first, in lane order: the kernel's work-list
        order = jnp.argsort(~live, stable=True).astype(jnp.int32)
        count = jnp.sum(live).astype(jnp.int32).reshape(1)
        pool, acc = _decode_call(
            jnp.broadcast_to(gate[..., None, None], (B, Hkv, 1, LANES)),
            phik[:, :, None, :], phiq,
            jnp.broadcast_to(vaug[..., None], vaug.shape + (LANES,)),
            pool, order, blocks,
            count, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
            interpret=bool(interpret))
        out = jnp.sum(acc, axis=-1)                    # (B, Hkv, G, R)
    y = out[..., :dv] / (out[..., dv:dv + 1] + eps)
    y = jnp.where(live[:, None, None, None], y, 0.0)
    return y.reshape(B, Hq, dv), pool


# -- a prefill chunk ----------------------------------------------------------

def _chunk_kernel(block_ref, layer_ref, fresh_ref, dec_ref, phiq_ref,
                  phik_ref, vwt_ref, s_ref, o_ref, inter_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        inter_ref[...] = jnp.zeros_like(inter_ref)

    s = jnp.where(fresh_ref[0] == 1, 0.0, s_ref[...])  # (R, DT) f32
    nt = (((1,), (1,)), ((), ()))                      # A @ B^T
    inter_ref[...] += jax.lax.dot_general(
        phiq_ref[...], s.astype(phiq_ref.dtype), nt,
        preferred_element_type=jnp.float32)            # (G * C, R)
    o_ref[...] = dec_ref[...] * s + jnp.dot(
        vwt_ref[...], phik_ref[...], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_call(dec, phiq, phik, vwt, pool, block, layer, fresh,
                interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hkv, GC, DP = phiq.shape
    C = phik.shape[1]
    R = pool.shape[3]
    DT = _lane_tile(DP, 1024)

    def head_t(h, t, block, layer, fresh):
        return (h, 0, t)

    def state(h, t, block, layer, fresh):
        return (block[0], layer[0], h, 0, t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Hkv, DP // DT),
        in_specs=[
            pl.BlockSpec((None, 1, DT), lambda h, t, *_: (h, 0, 0)),   # decay
            pl.BlockSpec((None, GC, DT), head_t),                      # phi(q)
            pl.BlockSpec((None, C, DT), head_t),                       # phi(k)
            pl.BlockSpec((None, R, C), lambda h, t, *_: (h, 0, 0)),   # [vw; w]
            pl.BlockSpec((None, None, None, R, DT), state),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, R, DT), state),
            pl.BlockSpec((None, GC, R), lambda h, t, *_: (h, 0, 0)),
        ],
    )
    pool, inter = pl.pallas_call(
        _chunk_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((Hkv, GC, R), jnp.float32)],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="power_retention_chunk",
    )(block, layer, fresh, dec, phiq, phik, vwt, pool)
    return pool, inter


def _chunk_composed(dec, phiq, phik, vwt, pool, block, layer, fresh):
    s = jnp.where(fresh == 1, 0.0, pool[block, layer])   # (Hkv, R, DP)
    inter = jnp.einsum("hqp,hrp->hqr", phiq, s.astype(phiq.dtype),
                       preferred_element_type=jnp.float32)
    new = dec[..., None, None] * s + jnp.einsum(
        "hrc,hcp->hrp", vwt, phik, preferred_element_type=jnp.float32)
    return pool.at[block, layer].set(new), inter


def retention_chunk(q, k, v, lg, pool, block, layer, start, n_true, eps,
                    interpret=None, composed=None):
    """``C`` tokens of ONE sequence entering with the state in ``block``.

    q (C, Hq, d), k (C, Hkv, d), both scaled by d^(-1/4), in the
    matmuls' type (bf16 on the chip); v (C, Hkv, dv); lg (C, Hkv)
    float32 log gates; ``n_true`` (traced) of the C tokens are real: the
    rest are padding (gate 1, no contribution, so the state that leaves
    is the state after ``n_true`` tokens; their output rows are not
    meaningful); ``start == 0`` enters with a zero state whatever the
    block held. ``composed`` as in :func:`retention_decode`. Returns (y
    (C, Hq, dv) float32, pool)."""
    C, Hq, d = q.shape
    Hkv, dv = v.shape[1:]
    G = Hq // Hkv
    cd = q.dtype
    real = jnp.arange(C) < n_true
    b = jnp.cumsum(jnp.where(real[:, None], lg.astype(jnp.float32), 0.0),
                   axis=0)                             # (C, Hkv)
    vaug = _with_one(v, jnp.float32)                   # (C, Hkv, R)
    qh = jnp.moveaxis(q.reshape(C, Hkv, G, d), 0, 2)   # (Hkv, G, C, d)
    kh = jnp.moveaxis(k, 0, 1)                         # (Hkv, C, d)
    bh = b.T                                           # (Hkv, C)

    # inside the chunk: the quadratic form, key j <= query i, j real
    sc = jnp.einsum("hgid,hjd->hgij", qh, kh,
                    preferred_element_type=jnp.float32)
    seen = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]) & real[None, :]
    decay = jnp.exp(jnp.where(seen, bh[:, :, None] - bh[:, None, :],
                              -jnp.inf))               # (Hkv, C, C)
    a = (sc * sc * decay[:, None]).astype(cd)
    intra = jnp.einsum("hgij,jhr->hgir", a, vaug.astype(cd),
                       preferred_element_type=jnp.float32)

    # against the state that enters, and the state that leaves
    phiq = phi(qh, jnp.broadcast_to(jnp.exp(bh)[:, None], qh.shape[:-1]),
               cd).reshape(Hkv, G * C, -1)
    phik = phi(kh, dtype=cd)                           # (Hkv, C, DP)
    w = jnp.where(real[None, :], jnp.exp(bh[:, -1:] - bh), 0.0)
    vwt = (jnp.moveaxis(vaug, 0, 2) * w[:, None, :]).astype(cd)
    dec = jnp.exp(bh[:, -1])                           # (Hkv,)
    block = jnp.reshape(jnp.asarray(block, jnp.int32), (1,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    fresh = jnp.reshape((jnp.asarray(start) == 0).astype(jnp.int32), (1,))
    if composed is None:
        composed = interpret is None and not _on_tpu()
    if composed:
        pool, inter = _chunk_composed(dec, phiq, phik, vwt, pool, block[0],
                                      layer[0], fresh[0])
    else:
        DT = _lane_tile(phiq.shape[-1], 1024)
        pool, inter = _chunk_call(
            jnp.broadcast_to(dec[:, None, None], (Hkv, 1, DT)),
            phiq, phik, vwt, pool, block, layer, fresh,
            interpret=bool(interpret))
    out = intra + inter.reshape(Hkv, G, C, -1)
    y = out[..., :dv] / (out[..., dv:dv + 1] + eps)
    return jnp.moveaxis(y, 2, 0).reshape(C, Hq, dv), pool
