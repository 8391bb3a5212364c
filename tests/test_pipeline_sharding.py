"""Pipeline scan-carry sharding (VERDICT r2 item 3).

Asserts the compiled hybrid pipeline step:
- emits a CollectivePermute for the stage rotation (the pipeline really
  crosses devices), and
- compiles WITHOUT the SPMD partitioner's "Involuntary full
  rematerialization" fallback (scan-carry and param shardings agree across
  the while-loop boundary).

The warning is emitted by XLA's C++ logging, so the check runs in a
subprocess and greps stderr — the same signal MULTICHIP_r*.json records.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    from paddle_tpu.models import gpt_tiny, gpt_init, gpt_loss, gpt_param_specs
    from paddle_tpu.parallel import DistributedTrainStep, create_mesh
    from paddle_tpu.parallel.pipeline import stack_stages

    mesh = create_mesh(dp=2, sharding=2, pp=2, mp=1)
    cfg = gpt_tiny(n_stages=2, use_flash=False)
    params = gpt_init(cfg, seed=0)
    params["blocks"] = stack_stages(params["blocks"], 2)
    step = DistributedTrainStep(
        lambda p, b: gpt_loss(cfg, p, b, n_micro=4),
        params, gpt_param_specs(cfg), optimizer="adamw", lr=1e-3,
        clip_norm=1.0, zero=True, mesh=mesh)
    rng = np.random.default_rng(0)
    batch = (rng.integers(0, cfg.vocab_size, (32, cfg.seq_len)).astype(np.int32),
             rng.integers(0, cfg.vocab_size, (32, cfg.seq_len)).astype(np.int32))
    lowered = step.lower(batch)
    hlo = lowered.compile().as_text()
    assert "collective-permute" in hlo, "no CollectivePermute in pipeline step"
    loss = step(batch)
    assert np.isfinite(float(loss))
    print("PIPELINE_OK")
""")


class TestPipelineShardingClean:
    def test_no_involuntary_rematerialization(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8")
        proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "PIPELINE_OK" in proc.stdout
        assert "Involuntary full rematerialization" not in proc.stderr, (
            "SPMD replicate-and-repartition fallback reappeared:\n"
            + "\n".join(l for l in proc.stderr.splitlines()
                        if "Involuntary" in l))
