"""MoE fused permute/dispatch kernel — Pallas TPU, capacity-slot gather.

The MoE layer (nn/moe.py) routes each token to its top-k experts and
packs the survivors into a dense ``(E, C)`` capacity grid. The textbook
GShard formulation materializes a one-hot dispatch tensor ``(T, E, C)``
and contracts it with the tokens — ``O(T·E·C·H)`` FLOPs and a
``(T, E, C)`` buffer just to MOVE rows. This module replaces that with
the permutation it actually is:

- :func:`moe_dispatch_gather` — the routed entry. ``src`` (E·C,) int32
  names the token row filling each capacity slot (−1 = empty slot);
  the result is the ``(E·C, H)`` packed expert input, empty slots
  zeroed. On TPU with tileable shapes it runs the Pallas kernel;
  anywhere else (CPU/GPU, untileable H) the IDENTICAL composed jnp
  gather — the flash/paged fallback contract, pinned by interpret-mode
  parity tests (tests/test_moe.py, ``-m kernels``).

Kernel design:
- grid ``(E·C, H/hb)`` — one output row per major grid step, the hidden
  dim split at ``hb`` lanes (the autotune knob);
- ``src`` rides as SCALAR PREFETCH (pltpu.PrefetchScalarGridSpec): the
  token BlockSpec index_map reads ``src[i]`` (clamped to row 0 for
  empty slots) to DMA exactly the routed row — the permutation happens
  in the DMA engine, no ``(T, E, C)`` one-hot ever exists;
- empty slots (src[i] < 0) write zeros instead of the clamp row, so the
  packed grid matches the one-hot einsum bit-for-bit;
- backward is the transpose permutation: a scatter-add of the slot
  cotangents back to their source rows (dropped/empty slots contribute
  nothing), expressed as composed jnp — it is the same gather pattern
  mirrored, and XLA already emits a single dynamic-update stream for it.

Autotune family ``moe_dispatch`` (ops/autotune.py): candidates ladder
over the lane block ``hb`` ∈ {128, 256, 512, H} (legal divisors only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import autotune as _autotune
from .flash_attention import _on_tpu

__all__ = ["moe_dispatch_gather", "moe_combine_scatter"]


def _gather_reference(x, src):
    """Composed jnp fallback: rows of ``x`` at ``src`` with empty
    (negative) slots zeroed. x (T, H); src (N,) int32 → (N, H)."""
    rows = x[jnp.maximum(src, 0)]
    return jnp.where((src >= 0)[:, None], rows, jnp.zeros_like(rows))


def _gather_kernel(src_ref, x_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    row = x_ref[...]
    o_ref[...] = jnp.where(src_ref[i] >= 0, row, jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _gather_pallas(x, src, hb, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H = x.shape
    N = src.shape[0]
    # rows ride a leading (untiled) dim — (T, 1, H) with (1, 1, hb)
    # blocks — because Mosaic refuses a one-row block of a 2-D array
    # (the second-minor block dim must be a multiple of 8 or the whole
    # dim), and a row gather moves exactly one row per step
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, H // hb),
        in_specs=[
            pl.BlockSpec((1, 1, hb),
                         lambda i, j, src: (jnp.maximum(src[i], 0), 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, hb), lambda i, j, src: (i, 0, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, 1, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="moe_gather",
    )(src, x.reshape(T, 1, H))
    return out.reshape(N, H)


def _pick_hb(N, T, H, dtype) -> int:
    """Hand-picked default lane block, overridable by the autotuner."""
    default = H if H % 512 else 512
    cfg = _autotune.get_config("moe_dispatch", (N, T, H), dtype,
                               {"hb": default})
    hb = int(cfg.get("hb", default))
    return hb if H % hb == 0 else default


def _gather_impl(x, src, interpret):
    T, H = x.shape
    N = src.shape[0]
    if interpret is None:
        interpret = False
        if not _on_tpu():
            return _gather_reference(x, src)
    if not interpret and H % 128 != 0:
        _autotune.note_fallback(
            "moe_dispatch", (N, T, H),
            "hidden=%d not a multiple of 128 lanes" % H)
        return _gather_reference(x, src)
    hb = _pick_hb(N, T, H, jnp.dtype(x.dtype).name)
    return _gather_pallas(x, src, hb=hb, interpret=bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather(x, src, interpret):
    return _gather_impl(x, src, interpret)


def _gather_fwd(x, src, interpret):
    return _gather_impl(x, src, interpret), (x.shape[0], src)


def _gather_bwd(interpret, res, dy):
    T, src = res
    # transpose of the permutation: scatter slot cotangents back to their
    # source rows; empty slots (clamped to row 0) add exact zeros there
    dy = jnp.where((src >= 0)[:, None], dy, jnp.zeros_like(dy))
    dx = jnp.zeros((T, dy.shape[1]), dy.dtype)
    return dx.at[jnp.maximum(src, 0)].add(dy), None


_gather.defvjp(_gather_fwd, _gather_bwd)


def moe_dispatch_gather(x, src, interpret=None):
    """Pack routed token rows into the dense (E·C, H) expert grid.

    x (T, H) — the token activations; src (E·C,) int32 — for capacity
    slot ``e*C + c``, the token row that fills it, or −1 for an empty
    slot (under-capacity expert or dropped assignment). Returns
    (E·C, H) in x.dtype with empty slots zeroed — bit-identical to the
    one-hot einsum ``einsum("tec,th->ech", dispatch, x)`` flattened,
    without ever building the (T, E, C) one-hot.

    Differentiable in ``x`` (custom VJP: the transpose scatter-add).
    Same routing contract as flash/paged attention: off-TPU (unless
    ``interpret=True`` forces the kernel) and on untileable hidden
    sizes this returns the identical composed jnp gather.
    """
    return _gather(x, jnp.asarray(src, jnp.int32), interpret)


def moe_combine_scatter(out, slot, gates):
    """Un-permute expert outputs back to token order and mix the top-k.

    out (E·C, H) — packed expert outputs; slot (T, k) int32 — the
    capacity slot ``e*C + c`` each token's rank-r assignment landed in
    (−1 = dropped); gates (T, k) f32 — the normalized router weights.
    Returns (T, H) in out.dtype: ``sum_r gates[t,r] * out[slot[t,r]]``
    with dropped ranks contributing zero (residual passthrough happens
    in the caller). The transpose of :func:`moe_dispatch_gather` — k
    gathers instead of a (T, E, C) combine einsum.
    """
    T, k = slot.shape
    y = jnp.zeros((T, out.shape[1]), out.dtype)
    for r in range(k):
        rows = _gather_reference(out, slot[:, r])
        y = y + rows * gates[:, r:r + 1].astype(out.dtype)
    return y


# -- autotune family (ISSUE 18) ---------------------------------------------
# Ladder over the lane block hb: small blocks pipeline more grid steps
# per row (better DMA overlap at huge H), H keeps one DMA per row.

def _dispatch_candidates(shape, dtype):
    N, T, H = (int(d) for d in shape)
    if H % 128 != 0:
        raise ValueError("hidden=%d not tileable (needs 128 lanes)" % H)
    # dict.fromkeys dedupes the H rung when H is already on the ladder
    return [{"hb": hb} for hb in dict.fromkeys((128, 256, 512, H))
            if hb <= H and H % hb == 0]


def _dispatch_bench(shape, dtype, config):
    import numpy as np

    N, T, H = (int(d) for d in shape)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, H)).astype(dtype))
    src = jnp.asarray(rng.integers(-1, T, size=(N,)).astype(np.int32))
    out = _gather_pallas(x, src, hb=int(config["hb"]),
                         interpret=not _on_tpu())
    jax.block_until_ready(out)


_autotune.register_family("moe_dispatch", _dispatch_candidates,
                         _dispatch_bench)
