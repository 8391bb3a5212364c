#!/usr/bin/env python3
"""Time the serving sampler alone on the chip: one jitted
``sample_tokens_streams`` over ``(rows, V)`` float32 logits, half the
rows sampling and the rest greedy, as a decode tick of the benchmark's
mixes calls it. One JSON line a reading, ``ms`` the mean over ``--reps``
calls dispatched back to back and awaited once:

    greedy   no row samples (argmax alone: the floor)
    select   the 16 rows at 0.8 / 40 / 0.95: the bounded path
    sort     the 16 rows at 0.8 / 0 / 0.95: top_p alone forces the sort

``--k-caps 64,128,256`` reads ``select`` once for each candidate count
(it sets the module's ``K_CAP`` before tracing; a tree without the
constant reads its one way). ``--group-widths 0,128,256`` reads
``select`` once for each width of the grouped candidate search (it sets
the module's ``GROUP_W``; 0 swaps the search for one top_k over the
row; a tree without the search reads its one way). Run from the root of the
tree to be timed:

    python3 tools/sampling_bench.py [--widths 50304,65536] [--rows 32]
        [--reps 200]

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

MIXES = {"greedy": (0.0, 0, 1.0), "select": (0.8, 40, 0.95),
         "sort": (0.8, 0, 0.95)}


def reading(width, rows, mix, reps, seed):
    temperature, top_k, top_p = MIXES[mix]
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0.0, 2.0, (rows, width)), jnp.float32)
    on = np.arange(rows) % 2 == 0
    temps = jnp.asarray(np.where(on, temperature, 0.0), jnp.float32)
    top_ks = jnp.asarray(np.where(on, top_k, 0), jnp.int32)
    top_ps = jnp.asarray(np.where(on, top_p, 1.0), jnp.float32)
    keys = sampling.stream_keys(jax.random.key(seed),
                                jnp.arange(rows, dtype=jnp.int32),
                                jnp.zeros(rows, jnp.int32))
    # a fresh jit each reading: K_CAP and GROUP_W are read while tracing
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
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--k-caps", default="")
    ap.add_argument("--group-widths", default="")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"sampling_bench: no TPU here (platform {dev.platform})")
    shipped = getattr(sampling, "K_CAP", None)
    caps = [int(k) for k in a.k_caps.split(",") if k and shipped]
    grouped = getattr(sampling, "GROUP_W", None)
    widths = [int(w) for w in a.group_widths.split(",") if w and grouped]
    search = getattr(sampling, "_candidates", None)
    for width in (int(w) for w in a.widths.split(",")):
        for mix in MIXES:
            selects = mix == "select"
            for cap in (caps if selects and caps else [shipped]):
                for gw in (widths if selects and widths else [grouped]):
                    if cap is not None:
                        sampling.K_CAP = cap
                    if gw is not None:
                        sampling.GROUP_W = gw
                        sampling._candidates = search if gw else (
                            lambda s, n: jax.lax.top_k(s, n)[0])
                    ms = reading(width, a.rows, mix, a.reps, a.seed)
                    print(json.dumps({
                        "width": width, "rows": a.rows, "mix": mix,
                        "ms": round(ms, 4), "k_cap": cap, "group_w": gw,
                        "reps": a.reps, "device_kind": dev.device_kind}),
                        flush=True)


if __name__ == "__main__":
    main()
