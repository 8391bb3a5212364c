#!/usr/bin/env python3
"""Compile-only check, no chip, of the ``brumby`` family's cell: do the
engine's decode program (the model's power-retention decode step over
the lanes' states and the sampler) and its prefill chunk programs (one a
padded chunk length), at the cell's sizes (16 slots, 17 states), fit a
described v5e device, and is the state pool updated in place?

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_check_brumby.py \
        [--workload serve.brumby_14b_l8.digest] [--chunks 128,512]

For each program it prints the bytes of its arguments, outputs and
temporaries, what is aliased (the donated pool), the seconds the compile
took, the custom calls in it and every ``copy`` whose result has the
pool's shape (there should be none: a kernel call that copied the pool
would move 5.5 GB a layer), and it exits 1 if any program needs more than
15.5 GB or copies the pool. Run by hand before a chip call (it loads
libtpu's compiler, so it is a script, never imported by a test). This
process sees the CPU, so the kernels' router
(``ops/power_retention._on_tpu``) is steered here to take the Pallas
path, as the chip would. Compiling is not running."""
import argparse
import functools
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

LIMIT = 15.5e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve.brumby_14b_l8.digest")
    ap.add_argument("--chunks", default="128,512")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import program, spec as spec_mod
    from paddle_tpu.ops import power_retention
    from paddle_tpu.serving.sampling import (sample_tokens_streams,
                                             stream_keys)

    power_retention._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    sp = spec_mod.Spec(a.workload)
    sizes, eng = sp.config["sizes"], sp.workload["engine"]
    cfg = program.build_config(sp.config)
    model = cfg.serving_model()
    params = sds(jax.eval_shape(lambda: sp.family.make_params(sizes, 0)))
    pool = sds(model.pool_spec(cfg, eng["n_blocks"], eng["block_size"]))
    B, V = eng["n_slots"], cfg.vocab_size
    arr = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa
    i32 = functools.partial(arr, jnp.int32)
    key = sds(jax.eval_shape(lambda: jax.random.key(0)))

    def decode(params, pool, tables, positions, tokens, base_key, rids,
               steps, temps, top_ks, top_ps, mask):
        logits, pool = model.decode_step_paged(
            cfg, params, pool, tables, positions, tokens)
        keys = stream_keys(base_key, rids, steps)
        return sample_tokens_streams(logits, keys, temps, top_ks, top_ps,
                                     mask=mask), pool

    def chunk(params, pool, table_row, tokens, start, n_true):
        return model.prefill_chunk(cfg, params, pool, table_row, tokens,
                                   start, n_true)

    worst = 0
    copies = 0
    pool_shape = "f32[" + ",".join(str(d) for d in pool[0].shape) + "]"

    def report(name, lowered):
        nonlocal worst, copies
        t = time.perf_counter()
        compiled = lowered.compile()
        pool_copies = [ln.strip()[:160]
                       for ln in compiled.as_text().splitlines()
                       if re.search(r"= " + re.escape(pool_shape)
                                    + r"[^ ]* copy\(", ln)]
        copies += len(pool_copies)
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        worst = max(worst, total)
        calls = sorted(set(re.findall(
            r"%([\w.\-]+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"",
            compiled.as_text())))
        print(json.dumps({
            "program": name, "GB": round(total / 1e9, 3),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "compile_s": round(time.perf_counter() - t, 1),
            "custom_calls": calls, "pool_copies": pool_copies}),
            flush=True)

    report("decode", jax.jit(decode, donate_argnums=(1,)).lower(
        params, pool, i32(B, 1), i32(B), i32(B), key, i32(B), i32(B),
        arr(jnp.float32, B), i32(B), arr(jnp.float32, B),
        arr(jnp.bool_, B, V)))
    for c in (int(c) for c in a.chunks.split(",")):
        report(f"chunk{c}", jax.jit(chunk, donate_argnums=(1,)).lower(
            params, pool, i32(1), i32(1, c), i32(), i32()))
    print(json.dumps({"largest_GB": round(worst / 1e9, 3),
                      "limit_GB": LIMIT / 1e9, "fits": worst <= LIMIT,
                      "pool_copies": copies}))
    return 0 if worst <= LIMIT and not copies else 1


if __name__ == "__main__":
    sys.exit(main())
