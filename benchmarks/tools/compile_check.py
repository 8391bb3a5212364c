#!/usr/bin/env python3
"""Compile-only check, no chip, of the ``gpt`` family's cells (it names
them, calls the family's model functions itself and writes its per-head
pool's shape; another family brings a check of its own): do the engine's
paged decode and prefill chunk programs at the serve cells' sizes (32
slots, a pool of 2049 blocks) and the BERT-base train step fit a
described v5e device?

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_check.py [--train]

Run by hand before a chip call (it loads libtpu's compiler, so it is a
script, never imported by a test). Compiling is not running: it says
what the chip's compiler refuses and how many bytes a program needs,
nothing about results or times."""
import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import program, spec as spec_mod
    from paddle_tpu.models.gpt import (gpt_decode_step_paged, gpt_loss,
                                       gpt_prefill_chunk)
    from paddle_tpu.parallel.train_step import (pure_adamw_init,
                                                pure_adamw_update)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(json.dumps({"program": name, "bytes": total,
                          "GB": round(total / 1e9, 2),
                          "mosaic": "tpu_custom_call" in compiled.as_text()}),
              flush=True)

    sp = spec_mod.Spec("serve.gpt_1p3b.chat")
    sizes, eng = sp.config["sizes"], sp.workload["engine"]
    cfg = program.build_config(sp.config)
    params = sds(jax.eval_shape(lambda: sp.family.make_params(sizes, 0)))
    shape = (eng["n_blocks"], cfg.n_layers, cfg.n_heads, eng["block_size"],
             cfg.head_dim)
    pool = (jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one),) * 2
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    B = eng["n_slots"]
    for width in (8, 128):
        dec = jax.jit(functools.partial(gpt_decode_step_paged, cfg),
                      donate_argnums=(1,))
        report(f"decode.w{width}", dec.lower(
            params, pool, i32(B, width), i32(B), i32(B)).compile())
        chk = jax.jit(functools.partial(gpt_prefill_chunk, cfg),
                      donate_argnums=(1,))
        report(f"chunk128.w{width}", chk.lower(
            params, pool, i32(width), i32(1, eng["prefill_chunk"]),
            i32()).compile())

    if "--train" in sys.argv:
        sp = spec_mod.Spec("train.bert_base.b32")
        cfg = program.build_config(sp.config)
        mix = sp.traffic
        p = jax.eval_shape(lambda: sp.family.make_params(
            sp.config["sizes"], 0))
        st = jax.eval_shape(pure_adamw_init, p)

        def step(p, st, batch):
            loss, g = jax.value_and_grad(
                lambda q: gpt_loss(cfg, q, batch))(p)
            p, st = pure_adamw_update(p, g, st, jnp.float32(2e-4))
            return p, st, loss

        batch = (i32(mix["batch"], mix["seq"]),) * 2
        # use_flash is auto (TPU only) and this process sees the CPU:
        # force it as the chip would choose at seq 512 without remat
        import dataclasses
        cfg = dataclasses.replace(cfg, use_flash=True)
        report("bert_base.step.b32", jax.jit(step, donate_argnums=(0, 1))
               .lower(sds(p), sds(st), batch).compile())


if __name__ == "__main__":
    main()
