#!/usr/bin/env python3
"""Time the held experts' FFN alone on the chip: one jitted
``moe_ffn_held`` layer at the ``sarvam_mla`` cell's widths (hidden 4096,
expert width 2048, 32 of 128 experts held, top 8, a stack of 5 layers'
experts addressed at layer 2), through ``jax.lax.ragged_dot`` and
through ``ops/moe_gmm``'s kernel at each candidate row tile and hidden
block. One JSON line a reading: ``ms`` the mean over ``--reps`` calls
dispatched back to back and awaited once, the kernel's row ``tiles`` and
the held experts read (``reads``), what a read of each such expert once
at the HBM's peak would take (``floor_ms``), and the widest gap between
the reading's output and ``ragged_dot``'s over the largest entry.

Shapes: ``chunk`` is a 512-token prefill chunk, ``tick`` a decode tick
of 32 lanes of which ``--live`` hold a request. Routing: each token's
top 8 of seeded normal scores plus a seeded per-expert bias of std
``--skew`` (0: even routing).

    python3 tools/moe_gmm_bench.py [--tiles 16,32,64,128] [--blocks 256,512,1024]

Refuses to run off the TPU: a time taken elsewhere is no device metric.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.nn.moe import moe_ffn_held  # noqa: E402
from paddle_tpu.ops import moe_gmm  # noqa: E402

H, M, E, HELD, K, LAYERS = 4096, 2048, 128, 32, 8, 5
HBM = 819e9


def weights(seed):
    keys = jax.random.split(jax.random.key(seed), 3)
    make = jax.jit(lambda key, shape: 0.02 * jax.random.normal(
        key, shape, jnp.bfloat16), static_argnums=1)
    return (make(keys[0], (LAYERS * HELD, H, M)),
            make(keys[1], (LAYERS * HELD, H, M)),
            make(keys[2], (LAYERS * HELD, M, H)))


def inputs(seed, tokens, live, skew):
    kx, ks, kb = jax.random.split(jax.random.key(seed + 1), 3)
    x = jax.random.normal(kx, (tokens, H), jnp.float32).astype(jnp.bfloat16)
    scores = jax.random.normal(ks, (tokens, E)) \
        + skew * jax.random.normal(kb, (E,))
    vals, idx = jax.lax.top_k(scores, K)
    gates = jax.nn.softmax(vals, axis=-1)
    return x, gates, idx.astype(jnp.int32), jnp.arange(tokens) < live


def reading(w, x, gates, idx, on, reps, on_tpu):
    moe_gmm._on_tpu = lambda: on_tpu
    jax.clear_caches()          # the row tile and block are read in tracing
    fn = jax.jit(lambda wg, wu, wd, x, g, i, on, gb: moe_ffn_held(
        wg, wu, wd, x, g, i, n_experts=E, expert_offset=HELD, n_held=HELD,
        group_base=gb, live=on, out_dtype=jnp.float32))
    args = (*w, x, gates, idx, on, jnp.int32(2 * HELD))
    y, _, held, reads, tiles = fn(*args)
    y.block_until_ready()
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out[0].block_until_ready()
    return ((time.perf_counter() - t) / reps * 1e3, y, int(held), int(reads),
            int(tiles))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="16,32,64,128")
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--live", type=int, default=4)
    ap.add_argument("--skew", type=float, default=0.3)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"moe_gmm_bench: no TPU here (platform {dev.platform})")
    w = weights(a.seed)
    shipped = (moe_gmm.row_tile, moe_gmm.HIDDEN_BLOCK)
    for shape, tokens, live in (("chunk", 512, 512), ("tick", 32, a.live),
                                ("tick", 32, 32)):
        x, gates, idx, on = inputs(a.seed, tokens, live, a.skew)
        ms, ref, held, reads, _ = reading(w, x, gates, idx, on, a.reps, False)
        floor = reads * 3 * H * M * 2 / HBM * 1e3
        line = {"shape": shape, "tokens": tokens, "live": live,
                "held_rows": held, "reads": reads, "floor_ms": round(floor, 4),
                "device_kind": dev.device_kind}
        print(json.dumps(dict(line, path="ragged_dot", ms=round(ms, 4))),
              flush=True)
        cands = [(None, None)] + [
            (int(t), int(b)) for t in a.tiles.split(",") if t
            for b in a.blocks.split(",") if b]
        for tm, block in cands:
            moe_gmm.row_tile = (shipped[0] if tm is None
                                else lambda rows, n, tm=tm: tm)
            moe_gmm.HIDDEN_BLOCK = shipped[1] if block is None else block
            cand = dict(line, path="kernel",
                        row_tile=tm or shipped[0](tokens * K, E),
                        block=block or shipped[1], shipped=tm is None)
            try:
                ms, y, _, _, tiles = reading(w, x, gates, idx, on, a.reps,
                                             True)
            except Exception as e:      # one refused candidate ends no sweep
                print(json.dumps(dict(cand, error=str(e)[:400])), flush=True)
                continue
            gap = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
            print(json.dumps(dict(cand, ms=round(ms, 4), tiles=tiles,
                                  gap=gap)), flush=True)
        moe_gmm.row_tile, moe_gmm.HIDDEN_BLOCK = shipped


if __name__ == "__main__":
    main()
