"""Compile-level scaling report: the hybrid training step lowered for
8/16/32/64 virtual CPU devices (BASELINE.json's "Fleet scaling efficiency
8->64 chips" axis at the compiler level: the program partitions, the
collective mix stays per-chip-bounded, and no replicate-and-repartition
fallback appears at any size). Counts only; a CPU mesh gives no speed.

    python tools/scaling_report.py

Prints one row per mesh size.
"""
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    # before the first `import jax` below: 64 virtual CPU devices
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=64")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def one_size(n):
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.models import gpt_tiny, gpt_init, gpt_loss, gpt_param_specs
    from paddle_tpu.parallel import DistributedTrainStep, create_mesh
    from paddle_tpu.parallel.pipeline import stack_stages

    # scale the mesh the way a pod slice would: grow dp first, keep the
    # model axes (sharding/pp/mp) fixed at 2 — weak scaling over data
    dp = n // 8
    mesh = create_mesh(dp=dp, sharding=2, pp=2, mp=2,
                       devices=jax.devices()[:n])
    cfg = gpt_tiny(n_stages=2, use_flash=False)
    params = gpt_init(cfg, seed=0)
    params["blocks"] = stack_stages(params["blocks"], 2)
    n_micro = 4
    batch = 2 * dp * 2 * n_micro
    step = DistributedTrainStep(
        lambda p, b: gpt_loss(cfg, p, b, n_micro=n_micro),
        params, gpt_param_specs(cfg), optimizer="adamw", lr=1e-3,
        clip_norm=1.0, zero=True, mesh=mesh)
    rng = np.random.default_rng(0)
    b = (rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)).astype(np.int32),
         rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)).astype(np.int32))
    compiled = step.lower(b).compile()
    hlo = compiled.as_text()
    counts = {c: len(re.findall(re.escape(c) + r"[-.(]", hlo))
              for c in COLLECTIVES}
    mem = compiled.memory_analysis()
    per_chip = getattr(mem, "temp_size_in_bytes", None)
    tm = monitor.TrainerMonitor()
    tm.step_begin()
    loss = float(step(b))
    tele = tm.step_end(examples=batch)
    assert np.isfinite(loss)
    return {"n": n, "mesh": f"dp={dp},sh=2,pp=2,mp=2",
            "global_batch": batch, "collectives": counts,
            "temp_bytes_per_chip": per_chip, "loss": loss,
            "step_time_s": round(tele["step_time_s"], 4),
            "examples_per_sec": round(tele.get("examples_per_sec", 0.0), 1),
            "recompiles": tele["recompiles"],
            "grad_recompiles": tele.get("grad_recompiles", 0),
            "host_memory_bytes":
                monitor.update_memory_stats()["host_memory_bytes"]}


def main():
    import jax

    for n in (8, 16, 32, 64):
        if len(jax.devices()) < n:
            print(f"n={n}: only {len(jax.devices())} devices visible "
                  "(set --xla_force_host_platform_device_count)")
            continue
        print(one_size(n), flush=True)


if __name__ == "__main__":
    main()
