#!/usr/bin/env python3
"""Compile-only check, no chip, of the ``sambay`` family's cell: do the
engine's decode programs (the model's decode step and the sampler, one a
table width) and its prefill chunk programs (the padded chunk at a table
width), at the cell's sizes (64 slots, 8,193 blocks of 64), fit a
described v5e device, and are the paged pool, the window rings and the
Mamba states used in place?

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_check_sambay.py \
        [--workload serve.phi4_mini_flash.reasoning] [--widths 8,128] \
        [--chunk-widths 8,128]

For each program it prints the bytes of its arguments, outputs and
temporaries, what is aliased, the seconds the compile took, the custom
calls in it, and every ``copy``, ``transpose`` or ``slice`` whose result
has the shape of an array of the pool (there should be none: a kernel
call that copied the pool would move gigabytes a layer). It exits 1 if
any program needs more than 15.5 GB or cuts such an array whole. Run by
hand before a chip call (it loads libtpu's compiler, so it is a script,
never imported by a test). This process sees the CPU, so the kernels'
routers (``ops/hybrid_attention``, ``ops/selective_scan``,
``ops/pool_write``) are steered here to take the Pallas path, as the
chip would. Compiling is not running."""
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


def _dims(shape):
    return ",".join(str(d) for d in shape)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve.phi4_mini_flash.reasoning")
    ap.add_argument("--widths", default="8,128")
    ap.add_argument("--chunk-widths", default="8,128")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import program, spec as spec_mod
    from paddle_tpu.ops import hybrid_attention, pool_write, selective_scan
    from paddle_tpu.serving.sampling import (sample_tokens_streams,
                                             stream_keys)

    for mod in (hybrid_attention, pool_write, selective_scan):
        mod._on_tpu = lambda: True
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
    B, V, bs = eng["n_slots"], cfg.vocab_size, eng["block_size"]
    pool = sds(model.pool_spec(cfg, eng["n_blocks"], bs)
               + model.slot_spec(cfg, B, bs))
    arr = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa
    i32 = functools.partial(arr, jnp.int32)
    key = sds(jax.eval_shape(lambda: jax.random.key(0)))
    C = int(model.chunk_pad(cfg))

    def decode(params, pool, tables, positions, tokens, base_key, rids,
               steps, temps, top_ks, top_ps, mask):
        logits, pool = model.decode_step_paged(
            cfg, params, pool, tables, positions, tokens)
        keys = stream_keys(base_key, rids, steps)
        return sample_tokens_streams(logits, keys, temps, top_ks, top_ps,
                                     mask=mask), pool

    def chunk(params, pool, table_row, tokens, start, n_true, slot, last):
        return model.prefill_chunk(cfg, params, pool, table_row, tokens,
                                   start, n_true, slot, last)

    worst = 0
    cuts = 0
    shapes = {_dims(p.shape) for p in pool}

    def report(name, lowered):
        nonlocal worst, cuts
        t = time.perf_counter()
        compiled = lowered.compile()
        text = compiled.as_text()
        whole = [ln.strip()[:160] for ln in text.splitlines()
                 if (m := re.search(r"= \w+\[([\d,]+)\][^ ]* "
                                    r"(copy|transpose|slice)\(", ln))
                 and m.group(1) in shapes]
        cuts += len(whole)
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        worst = max(worst, total)
        calls = sorted(set(re.findall(
            r"%([\w.\-]+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)))
        print(json.dumps({
            "program": name, "GB": round(total / 1e9, 3),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "compile_s": round(time.perf_counter() - t, 1),
            "custom_calls": calls, "whole_array_cuts": whole}),
            flush=True)

    for w in (int(w) for w in a.widths.split(",")):
        report(f"decode.w{w}", jax.jit(decode, donate_argnums=(1,)).lower(
            params, pool, i32(B, w), i32(B), i32(B), key, i32(B), i32(B),
            arr(jnp.float32, B), i32(B), arr(jnp.float32, B),
            arr(jnp.bool_, B, V)))
    for w in (int(w) for w in a.chunk_widths.split(",")):
        report(f"chunk{C}.w{w}", jax.jit(chunk, donate_argnums=(1,)).lower(
            params, pool, i32(w), i32(1, C), i32(), i32(), i32(),
            arr(jnp.bool_)))
    print(json.dumps({"largest_GB": round(worst / 1e9, 3),
                      "limit_GB": LIMIT / 1e9, "fits": worst <= LIMIT,
                      "whole_array_cuts": cuts}))
    return 0 if worst <= LIMIT and not cuts else 1


if __name__ == "__main__":
    sys.exit(main())
