#!/usr/bin/env python3
"""Time the serving sampler alone on the chip: one jitted
``sample_tokens_streams`` over ``(32, V)`` float32 logits, 16 of the 32
rows sampling and the rest greedy, as a decode tick of the benchmark's
mixes calls it. One JSON line a reading, ``ms`` the mean over ``--reps``
calls dispatched back to back and awaited once:

    greedy   no row samples (argmax alone: the floor)
    select   the 16 rows at 0.8 / 40 / 0.95: the bounded path
    sort     the 16 rows at 0.8 / 0 / 0.95: top_p alone forces the sort

``--k-caps 64,128,256`` reads ``select`` once for each candidate count
(it sets the module's ``K_CAP`` before tracing; a tree without the
constant reads its one way). Run from the root of the tree to be timed:

    python3 tools/sampling_bench.py [--widths 50304,65536] [--reps 200]

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
import numpy as np  # noqa: E402

from paddle_tpu.serving import sampling  # noqa: E402

ROWS, SAMPLED = 32, 16
MIXES = {"greedy": (0.0, 0, 1.0), "select": (0.8, 40, 0.95),
         "sort": (0.8, 0, 0.95)}


def reading(width, mix, reps, seed):
    temperature, top_k, top_p = MIXES[mix]
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0.0, 2.0, (ROWS, width)), jnp.float32)
    on = np.arange(ROWS) % (ROWS // SAMPLED) == 0
    temps = jnp.asarray(np.where(on, temperature, 0.0), jnp.float32)
    top_ks = jnp.asarray(np.where(on, top_k, 0), jnp.int32)
    top_ps = jnp.asarray(np.where(on, top_p, 1.0), jnp.float32)
    keys = sampling.stream_keys(jax.random.key(seed),
                                jnp.arange(ROWS, dtype=jnp.int32),
                                jnp.zeros(ROWS, jnp.int32))
    # a fresh jit each reading: K_CAP is read while tracing
    fn = jax.jit(lambda *a: sampling.sample_tokens_streams(*a))
    args = (logits, keys, temps, top_ks, top_ps)
    fn(*args).block_until_ready()
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default="50304,65536")
    ap.add_argument("--k-caps", default="")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"sampling_bench: no TPU here (platform {dev.platform})")
    shipped = getattr(sampling, "K_CAP", None)
    caps = [int(k) for k in a.k_caps.split(",") if k and shipped]
    for width in (int(w) for w in a.widths.split(",")):
        for mix in MIXES:
            for cap in (caps if mix == "select" and caps else [shipped]):
                if cap is not None:
                    sampling.K_CAP = cap
                ms = reading(width, mix, a.reps, a.seed)
                print(json.dumps({
                    "width": width, "mix": mix, "ms": round(ms, 4),
                    "k_cap": cap, "reps": a.reps,
                    "device_kind": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
