"""Headline benchmark: BERT-base-sized LM pretraining step, samples/sec/chip.

Matches driver BASELINE.json config 3 ("BERT-base pretraining via Fleet
collective") on whatever single chip is available, plus configs 1 (MNIST
LeNet), 2 (ResNet-50, AMP), 4 (ERNIE-large, AMP/bf16) and 5 (GPT-1.3B,
bf16 + flash + chunked CE) from BASELINE.md.

Timing method (transformer configs): K training steps inside ONE jitted
lax.fori_loop — pure device time, no per-step dispatch. The previous
"two-point marginal" host-loop method was shown to misreport some variants
by 2x (dispatch pipelining aliases into the difference), so it is kept only
for the eager-TrainStep configs (LeNet/ResNet), where per-step dispatch is
genuinely part of what an eager user pays.

Flash-vs-XLA A/B: both attention paths are measured at seq 512 and 2048
with the same method; the headline config runs the measured winner at its
sequence length (XLA fused attention at 512, the Pallas flash kernel at
2048 — ~+40% there). Both numbers are reported in the JSON.

MFU: 6*N*T model FLOPs over the v5e bf16 peak of 197 TFLOP/s/chip (Cloud
TPU v5e spec: 197 TFLOPs bf16, 394 TOPs int8 — round-2 used the int8
number as the denominator, understating MFU 2x).

Baseline (derived — the reference repo publishes no numbers, BASELINE.md):
the driver's target is >=90% of Paddle A100+NCCL throughput for the same
config. Derivation from the public record: NVIDIA DeepLearningExamples
BERT pretraining phase 2 (seq 512, fp16, DGX A100 8x A100-80GB) reports
~600 sequences/s for BERT-large => ~75 seq/s per A100. That implies
MFU = 6*336e6*512*75 / 312e12 = 0.248 of A100's 312 TFLOP/s bf16 peak.
Transferring the same MFU to BERT-base shapes (110M params):
0.248 * 312e12 / (6*110e6*512) = 229 seq/s per A100. PaddlePaddle's A100
BERT implementation (also shipped in DeepLearningExamples) tracks the
PyTorch one, so 229 samples/sec/chip is the derived A100 Paddle-equivalent
baseline; the JSON carries baseline: "derived: ..." with this provenance.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline",
"baseline", "mfu", "flash_ab", "configs"}.
"""
from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

# derived A100 BERT-base pretraining figure — see module docstring
A100_BASELINE_SAMPLES_PER_SEC = 229.0
BASELINE_PROVENANCE = (
    "derived: NVIDIA DeepLearningExamples BERT-large phase-2 (seq 512, "
    "fp16, DGX A100) ~75 seq/s/GPU => MFU 0.248 of 312 TF; same-MFU "
    "BERT-base (110M) equivalent = 229 seq/s per A100")
V5E_PEAK_BF16_FLOPS = 197e12  # Cloud TPU v5e: 197 TFLOPs bf16 per chip


# -- pure-device timing for jittable train steps ---------------------------

def _device_step_seconds(cfg, batch, K=10, reps=2, loss_chunk=None,
                         optimizer="adamw", mv_dtype=None):
    """K optimizer steps inside one jit; returns (sec/step, n_params).

    mv_dtype: AdamW moment storage dtype (bf16 halves optimizer-state HBM
    footprint/traffic; update math stays fp32 — train_step.py)."""
    import functools as _ft

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_loss
    from paddle_tpu.parallel.train_step import (pure_adamw_init,
                                                pure_adamw_update,
                                                pure_sgd_init,
                                                pure_sgd_update)

    if optimizer == "adamw":
        init_fn = (pure_adamw_init if mv_dtype is None else
                   _ft.partial(pure_adamw_init, mv_dtype=mv_dtype))
        upd_fn = (pure_adamw_update if mv_dtype is None else
                  _ft.partial(pure_adamw_update, mv_dtype=mv_dtype))
    else:
        init_fn, upd_fn = pure_sgd_init, pure_sgd_update
    rng = np.random.default_rng(0)
    params = jax.device_put(gpt_init(cfg, seed=0))
    opt = init_fn(params)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)
    labels = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)

    # donation matters: without it params+opt live twice (input and
    # output buffers) — AdamW at >=760M params OOMs a 16GB chip on the
    # duplicate alone
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def k_steps(params, opt):
        def body(_, carry):
            p, o = carry
            _, grads = jax.value_and_grad(
                lambda pp: gpt_loss(cfg, pp, (tokens, labels),
                                    loss_chunk=loss_chunk))(p)
            return upd_fn(p, grads, o, 1e-4)

        return jax.lax.fori_loop(0, K, body, (params, opt))

    p2, o2 = k_steps(params, opt)
    jax.block_until_ready(p2)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        p2, o2 = k_steps(p2, o2)
        jax.block_until_ready(p2)
        best = min(best, (time.perf_counter() - t0) / K)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    del p2, o2, params, opt
    return best, n_params


def _mfu(n_params, seq, sps):
    return 6.0 * n_params * seq * sps / V5E_PEAK_BF16_FLOPS


# -- config 3 (headline): BERT-base + flash A/B ----------------------------

def bench_bert(on_accel, which=("xla_512", "flash_512", "xla_2048",
                                "flash_2048"), ab=None):
    from paddle_tpu.models import bert_base_config

    if not on_accel:  # CPU smoke mode so the bench always completes
        cfg = bert_base_config(hidden=128, n_layers=2, n_heads=2, seq_len=128,
                               vocab_size=1024, use_flash=False, remat=True)
        dt, n = _device_step_seconds(cfg, 4, K=2, reps=1)
        return 4 / dt, None, {}

    ab = {} if ab is None else ab
    # seq-512 configs compile with the FULL layer unroll (+3-8% measured);
    # the 2048 A/B keeps the rolled scan — its unrolled compile alone costs
    # minutes and the flash-vs-XLA comparison is unaffected by unroll.
    # r4 sweep (tools/exp_bert.py): batch 32 + remat OFF + chunked CE is
    # the single-chip sweet spot; under it flash beats XLA at 512 too
    # (278 vs 260 sps) — the r3 flash-512 loss was remat-induced.
    # r5 (tools/exp_flash.py noremat2048): the flash regime at 2048 is
    # batch 8 + remat OFF + chunked CE — BERT-base activations fit
    # because the flash kernel never materializes the S^2 score matrices;
    # 0.2605 -> 0.358 MFU. The XLA leg CANNOT run that regime (12 layers
    # of saved fp32 [8,12,2048,2048] scores = 19GB, OOM), so it keeps
    # remat+b4 — the memory headroom that unlocks the faster regime IS
    # part of flash's win and is reported as such. Block-shape tuning
    # itself was noise (512/1024 blocked == whole-seq within 0.3%).
    # full unroll matters at 2048 too: rolled-scan flash_2048 measured
    # 40.0 sps vs 52.1 unrolled (the scan boundary blocks cross-layer
    # fusion); 12-layer BERT unroll compiles in tens of seconds (the
    # minutes-long unroll warning applies to 24-layer GPT configs)
    for name, use_flash, seq, b, k, unroll, remat, chunk in (
            ("xla_512", False, 512, 32, 10, None, False, 256),
            ("flash_512", True, 512, 32, 10, None, False, 256),
            ("xla_2048", False, 2048, 4, 6, None, True, 256),
            ("flash_2048", True, 2048, 8, 6, None, False, 256)):
        if name not in which:
            continue
        cfg = bert_base_config(remat=remat, use_flash=use_flash, seq_len=seq,
                               scan_unroll=unroll)
        dt, n = _device_step_seconds(cfg, b, K=k, loss_chunk=chunk)
        ab[name] = {"sps": round(b / dt, 2),
                    "mfu": round(_mfu(n, seq, b / dt), 4)}

    # headline: the measured winner at seq 512
    win_flash = (ab.get("flash_512", {"sps": 0})["sps"]
                 > ab.get("xla_512", {"sps": 0})["sps"])
    head = ab["flash_512" if win_flash else "xla_512"]
    return head["sps"], head["mfu"], ab


# -- config 4: ERNIE-large (BERT-large shapes), bf16/AMP -------------------

def bench_ernie_large(on_accel):
    from paddle_tpu.models import GPTConfig

    if not on_accel:
        return None
    # r4 sweep: flash + remat OFF + batch 24 + chunked CE, 83.6 -> 99.4
    # sps on one chip (MFU 0.52)
    cfg = GPTConfig(vocab_size=30592, hidden=1024, n_layers=24, n_heads=16,
                    seq_len=512, remat=False, use_flash=True)
    batch = 24
    dt, n = _device_step_seconds(cfg, batch, K=8, loss_chunk=256)
    sps = batch / dt
    return {"sps": round(sps, 2), "mfu": round(_mfu(n, 512, sps), 4),
            "vs_baseline": round(sps / 75.0, 4),
            "baseline": "derived: ERNIE-large = BERT-large shapes; NVIDIA "
                        "DeepLearningExamples BERT-large phase-2 (seq 512, "
                        "fp16) ~75 seq/s per A100",
            "note": "bf16 compute + fp32 master, single chip; sharding+AMP "
                    "multi-chip path validated by dryrun_multichip"}


# -- config 5: GPT-1.3B ----------------------------------------------------

def bench_gpt_1p3b(on_accel):
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_1p3b

    if not on_accel:
        return None
    # rolled scan (scan_unroll=1): the 24-layer seq-2048 unrolled compile
    # costs minutes and would blow the bench budget for ~8%
    cfg = gpt_1p3b(remat=True, use_flash=True, param_dtype=jnp.bfloat16,
                   scan_unroll=1)
    batch = 4  # r4 sweep: 6.85 sps vs 6.71 at b2
    dt, n = _device_step_seconds(cfg, batch, K=4, loss_chunk=256,
                                 optimizer="sgd")
    sps = batch / dt
    # GPT A100 baseline: published Megatron-LM-class A100 GPT training
    # sustains ~150 TFLOP/s/GPU (0.48 of 312 peak); same-MFU transfer to
    # v5e = 0.48*197e12/(6*N*T) samples/sec
    base = 0.48 * 197e12 / (6.0 * n * cfg.seq_len)
    return {"sps": round(sps, 2), "mfu": round(_mfu(n, cfg.seq_len, sps), 4),
            "vs_baseline": round(sps / base, 4),
            "baseline": "derived: Megatron-LM-class A100 GPT training "
                        "~150 TFLOP/s/GPU (0.48 MFU), same-MFU transfer "
                        f"to v5e = {base:.2f} sps",
            "note": "bf16 params + flash + chunked CE, SGD: AdamW fp32 m/v "
                    "for 1.3B (10.6GB) exceeds one 16GB chip even with "
                    "donation; with ZeRO over 8 chips the per-chip state is "
                    "2.6GB bf16 params + 1.9GB m/v shard — the dryrun's "
                    "AdamW+ZeRO hybrid mesh validates exactly that path. "
                    "See gpt_760m_adamw for the real-optimizer number at "
                    "the largest single-chip-feasible scale."}


def bench_gpt_1p3b_auto(on_accel):
    """fleet.auto planner config (ISSUE 9): planner-chosen hybrid plan vs
    a hand-written dp x mp baseline.

    Two legs:
    - ANALYTIC (any backend): the cost model plans the REAL 1.3B config
      over an 8 x 16GB v5e slice from `jax.eval_shape` shapes (no arrays
      materialize); the row records the chosen plan, the top of the
      ranked table, and the predicted per-device param+opt bytes of the
      ZeRO-3 pick vs the unsharded candidate — the analytic form of the
      "AdamW at 1.3B needs ZeRO on 16GB chips" bench note.
    - MEASURED (needs a multi-device mesh — a TPU slice, or the 8-device
      virtual CPU mesh main() forces): a GPT-tiny proxy trained through
      DistributedTrainStep under the planner's plan vs the hand dp-only
      baseline: sps + MFU, plus the MEASURED per-device param+optimizer
      storage bytes at ZeRO-3 vs unsharded (the <= 40% acceptance row).
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.fleet import auto as fleet_auto
    from paddle_tpu.models import gpt_1p3b, gpt_init, gpt_loss, gpt_param_specs, gpt_tiny

    out = {}

    # -- analytic leg ------------------------------------------------------
    cfg = gpt_1p3b(param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: gpt_init(cfg))
    stats = fleet_auto.ModelStats.from_params(
        shapes, specs=gpt_param_specs(cfg), layers=cfg.n_layers,
        hidden=cfg.hidden, seq_len=cfg.seq_len)
    plan = fleet_auto.plan(stats=stats, global_batch=64, n_devices=8,
                           hardware=fleet_auto.HardwareSpec(),
                           allow_mp=True, max_micro=16)
    z3 = [c for c in plan.candidates if c.fits and c.zero == 3]

    def _po(c):
        return c.hbm_detail["params"] + c.hbm_detail["opt_state"]

    out["plan"] = plan.chosen.describe()
    out["plan_table"] = plan.table(top=6)
    out["predicted_hbm_per_dev_bytes"] = plan.chosen.hbm_bytes
    out["predicted_bubble_frac"] = round(plan.chosen.bubble_frac, 4)
    if z3:
        # deepest-sharded ZeRO-3 candidate vs the SAME mesh unsharded
        c3 = max(z3, key=lambda c: c.sharding)
        z0 = [c for c in plan.candidates if c.zero == 0 and
              (c.dp, c.sharding, c.pp, c.mp) ==
              (c3.dp, c3.sharding, c3.pp, c3.mp)]
        if z0:
            out["predicted_zero3_param_opt_frac"] = round(
                _po(c3) / _po(z0[0]), 4)
    out["note"] = ("analytic leg plans the real 1.3B config over 8x16GB "
                   "from eval_shape; unsharded AdamW (10.6GB fp32 m/v + "
                   "params) cannot fit one 16GB chip — the table shows "
                   "which ZeRO/pp splits do")

    # -- measured leg (proxy) ---------------------------------------------
    if len(jax.devices()) < 8:
        out["measured"] = ("skipped: needs an 8-device mesh (TPU slice or "
                           "the forced CPU virtual mesh)")
        return out

    from paddle_tpu.parallel.mesh import create_mesh, set_mesh
    from paddle_tpu.parallel.train_step import DistributedTrainStep

    tcfg = gpt_tiny(param_dtype=jnp.float32)
    tshapes = jax.eval_shape(lambda: gpt_init(tcfg))
    tstats = fleet_auto.ModelStats.from_params(
        tshapes, specs=gpt_param_specs(tcfg), layers=tcfg.n_layers,
        hidden=tcfg.hidden, seq_len=tcfg.seq_len)
    # scarce-HBM budget so the planner exercises the hybrid axes on the
    # proxy the way 16GB does on the real model
    tbudget = int(1.2 * (tstats.param_bytes
                         + tstats.n_params * tstats.opt_state_bytes_per_param))
    tplan = fleet_auto.plan(stats=tstats, global_batch=16, n_devices=8,
                            hardware=fleet_auto.HardwareSpec(
                                hbm_bytes=tbudget),
                            max_micro=4)
    out["proxy_plan"] = tplan.chosen.describe()

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, tcfg.vocab_size,
                                      (16, tcfg.seq_len)).astype("int32"))
    labels = jnp.asarray(rng.integers(0, tcfg.vocab_size,
                                      (16, tcfg.seq_len)).astype("int32"))
    n_params = tstats.n_params

    def dev_bytes(step):
        tot = 0
        for a in (jax.tree_util.tree_leaves(step.params)
                  + jax.tree_util.tree_leaves(step.opt_state)):
            if hasattr(a, "addressable_shards"):
                sh = a.addressable_shards[0].data
                tot += int(np.prod(sh.shape) or 1) * a.dtype.itemsize
        return tot

    def leg(name, dims, zero, n_micro=1):
        set_mesh(None)
        mesh = create_mesh(**dims)
        pcfg = gpt_tiny(param_dtype=jnp.float32,
                        n_stages=dims.get("pp", 1))
        params = gpt_init(pcfg, seed=0)
        specs = gpt_param_specs(pcfg)
        if dims.get("pp", 1) > 1:
            from paddle_tpu.parallel.pipeline import stack_stages

            params["blocks"] = stack_stages(params["blocks"],
                                            dims["pp"])

        def loss_fn(p, batch):
            return gpt_loss(pcfg, p, batch, n_micro=max(n_micro, 1))

        step = DistributedTrainStep(loss_fn, params, specs,
                                    optimizer="adamw", lr=1e-4,
                                    zero=zero, mesh=mesh)
        with mesh:
            step((tokens, labels))  # compile
            t0 = time.perf_counter()
            K = 4
            for _ in range(K):
                loss = step((tokens, labels))
            jax.block_until_ready(loss._data if hasattr(loss, "_data")
                                  else loss)
            dt = (time.perf_counter() - t0) / K
        sps = 16 / dt
        return {"sps": round(sps, 2),
                "mfu": round(_mfu(n_params, tcfg.seq_len, sps), 5),
                "param_opt_bytes_per_dev": dev_bytes(step)}

    planned = leg("auto", {"dp": tplan.dp, "sharding": tplan.sharding,
                           "pp": tplan.pp, "mp": tplan.mp},
                  tplan.zero, tplan.n_micro)
    baseline = leg("hand_dp_mp", {"dp": 4, "mp": 2}, 0)
    zero3 = leg("zero3", {"dp": 2, "sharding": 4}, 3)
    unsharded = leg("unsharded", {"dp": 8}, 0)
    out["measured"] = {
        "planner": planned, "hand_dp4_mp2": baseline,
        "vs_hand_baseline": round(planned["sps"] / baseline["sps"], 4),
        "zero3_param_opt_bytes_per_dev": zero3["param_opt_bytes_per_dev"],
        "unsharded_param_opt_bytes_per_dev":
            unsharded["param_opt_bytes_per_dev"],
        "measured_zero3_param_opt_frac": round(
            zero3["param_opt_bytes_per_dev"]
            / unsharded["param_opt_bytes_per_dev"], 4),
    }
    out["sps"] = planned["sps"]
    out["mfu"] = planned["mfu"]
    set_mesh(None)
    return out


def bench_gpt_760m_adamw(on_accel):
    """Largest GPT config whose FULL AdamW state fits one chip: the
    real-optimizer counterpart to gpt_1p3b's SGD constraint (VERDICT r3
    item 9 — report the target optimizer's number, not just SGD's)."""
    import jax.numpy as jnp

    from paddle_tpu.models import GPTConfig

    if not on_accel:
        return None
    # r5 (tools/exp_gpt760.py): 0.302 -> 0.502 MFU. What moved it:
    # (1) head_dim support in the flash kernel — the r4 config (16 heads,
    #     head_dim 96) silently fell back to XLA reference attention
    #     (96 % 128 != 0); zero-padding to 128 inside the kernel wrapper
    #     re-enabled flash and alone took b2 6.37 -> 8.33 sps;
    # (2) n_heads=12 => head_dim 128 = MXU lane width (same params, same
    #     6NT FLOPs, no pad waste): b4 9.46 -> 10.58 sps;
    # (3) bf16 AdamW moments (fp32 update math) halve optimizer-state HBM
    #     traffic and footprint, unlocking batch 4 without spills.
    cfg = GPTConfig(vocab_size=50304, hidden=1536, n_layers=24, n_heads=12,
                    seq_len=2048, remat=True, use_flash=True,
                    param_dtype=jnp.bfloat16, scan_unroll=1)
    batch = 4
    dt, n = _device_step_seconds(cfg, batch, K=4, loss_chunk=256,
                                 optimizer="adamw", mv_dtype=jnp.bfloat16)
    sps = batch / dt
    base = 0.48 * 197e12 / (6.0 * n * cfg.seq_len)
    return {"sps": round(sps, 2), "mfu": round(_mfu(n, cfg.seq_len, sps), 4),
            "vs_baseline": round(sps / base, 4),
            "baseline": "derived: Megatron-LM-class A100 GPT training "
                        "~150 TFLOP/s/GPU (0.48 MFU), same-MFU transfer "
                        f"to v5e = {base:.2f} sps",
            "note": "GPT-3 760M (head_dim 128), AdamW (bf16 m/v, fp32 "
                    "math) + bf16 params + flash + chunked CE on one chip; "
                    "r5: flash head-dim fix + MXU-width heads + bf16 "
                    "moments moved 0.302 -> ~0.50 MFU"}


def bench_gpt_tiny_serving(on_accel):
    """ISSUE 4: the serving engine's micro-config — prefill latency and
    steady-state continuous-batching decode tokens/s on gpt_tiny. Small
    enough to run on ANY backend (it is the CPU-CI-visible serving
    number); the engine/scheduler/jit-surface it exercises is exactly
    what a real model serves through."""
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_tiny
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.serving import InferenceEngine

    cfg = gpt_tiny(seq_len=256,
                   dtype=jnp.bfloat16 if on_accel else jnp.float32)
    params = gpt_init(cfg, seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 128).astype(np.int32)
    n_req, max_new = 4, 64
    eng = InferenceEngine(cfg, params, n_slots=4, max_len=256)
    try:
        # compile warmup at the measured bucket (prompt 128) so the
        # reported prefill latency is the steady-state one
        eng.generate(prompt, max_new_tokens=4)
        pre0, dec0 = stat_get("serving_prefill_ms"), stat_get("serving_decode_ms")
        t0 = time.perf_counter()
        reqs = [eng.submit(prompt, max_new_tokens=max_new)
                for _ in range(n_req)]
        toks = sum(len(r.result(timeout=600)) for r in reqs)
        wall = time.perf_counter() - t0
        decode_ms = stat_get("serving_decode_ms") - dec0
        tps = toks / (decode_ms / 1e3) if decode_ms > 0 else toks / wall
        return {
            "prefill_ms_per_req":
                round((stat_get("serving_prefill_ms") - pre0) / n_req, 3),
            "decode_tokens_per_s": round(tps, 2),
            "value": round(tps, 2),
            "unit": "tokens/s",
            "note": f"continuous batching, {n_req} concurrent requests x "
                    f"{max_new} new tokens, prompt 128, 4 slots; "
                    "decode_tokens_per_s is steady-state (prefill "
                    "excluded), wall-clock end-to-end "
                    f"{toks / wall:.1f} tok/s"}
    finally:
        eng.shutdown(drain=False)


def bench_resilience(on_accel):
    """Guardian snapshot overhead A/B at gpt_tiny (ISSUE 12): steps/s of
    (a) an unguarded loop, (b) a guardian with BLOCKING interval-gated
    disk snapshots, (c) the same cadence with async double-buffered
    snapshots — the orbax serialization moves to the snapshot thread, so
    (c) should sit near (a) while (b) pays the write on the loop."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import (gpt_init, gpt_loss, gpt_param_specs,
                                   gpt_tiny)
    from paddle_tpu.parallel.mesh import create_mesh, set_mesh
    from paddle_tpu.parallel.train_step import DistributedTrainStep
    from paddle_tpu.resilience.guardian import TrainGuardian

    cfg = gpt_tiny(seq_len=128, param_dtype=jnp.float32)
    B = 8
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (B, cfg.seq_len)).astype("int32"))
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (B, cfg.seq_len)).astype("int32"))

    def loss_fn(params, batch):
        return gpt_loss(cfg, params, batch)

    n_steps, warm, cadence = 16, 3, 4

    def leg(mode):
        set_mesh(None)
        mesh = create_mesh(dp=min(len(jax.devices()), B))
        step = DistributedTrainStep(loss_fn, gpt_init(cfg, seed=0),
                                    gpt_param_specs(cfg),
                                    optimizer="adamw", lr=1e-3, mesh=mesh,
                                    sentinel=True)
        g = None
        if mode != "no_guardian":
            g = TrainGuardian(step, ckpt_dir=tempfile.mkdtemp(),
                              snapshot_every=cadence,
                              save_interval_steps=cadence,
                              async_snapshot=(mode == "async_snapshot"))
        for i in range(warm):
            loss = step((tokens, labels))
            if g is not None:
                g.after_step(i, loss)
        jax.block_until_ready(step.params)
        t0 = time.perf_counter()
        for i in range(warm, warm + n_steps):
            loss = step((tokens, labels))
            if g is not None:
                g.after_step(i, loss)
        jax.block_until_ready(step.params)
        dt = time.perf_counter() - t0
        if g is not None:
            g.drain_snapshots()
            g.close()
        set_mesh(None)
        return n_steps / dt

    sps = {m: round(leg(m), 3)
           for m in ("no_guardian", "blocking_snapshot", "async_snapshot")}
    return {
        "steps_per_s": sps,
        "snapshot_every": cadence,
        "async_vs_blocking": round(
            sps["async_snapshot"] / sps["blocking_snapshot"], 3),
        "async_overhead_frac": round(
            1.0 - sps["async_snapshot"] / sps["no_guardian"], 3),
        "note": ("interval-gated orbax writes: blocking pays them on the "
                 "step loop, async only pays the in-loop device->host "
                 "offload (guardian double buffer + snapshot thread)"),
    }


def _serving_hist_snap():
    """Snapshot the source-recorded serving latency histograms
    (ISSUE 15) so a bench leg can be scoped by delta."""
    from paddle_tpu.monitor import get_histogram

    return {name: get_histogram(name).snapshot()
            for name in ("serving_first_token_ms", "serving_per_token_ms")}


def _serving_hist_pcts(before, after, hand_p50_ms, what):
    """p50/p99 from the histogram delta, cross-checked against the
    hand-collected p50: the two measurement paths (client-side
    perf_counter lists vs source-recorded log2-bucket histograms) must
    land within ONE bucket of each other — the agreement gate that
    guards the histogram math (bucketing, cumulative counts, quantile
    interpolation) with real traffic."""
    import math

    from paddle_tpu.monitor import hist_delta, hist_quantile

    out = {}
    for name, key in (("serving_first_token_ms", "first_token_ms"),
                      ("serving_per_token_ms", "per_token_ms")):
        d = hist_delta(before[name], after[name])
        out[f"{key}_p50"] = round(hist_quantile(d, 0.50), 3)
        out[f"{key}_p99"] = round(hist_quantile(d, 0.99), 3)
        out[f"{key}_samples"] = d["count"]
    hist_p50 = out["first_token_ms_p50"]
    if hand_p50_ms > 0 and hist_p50 > 0 \
            and out["first_token_ms_samples"] >= 8:
        drift = abs(math.log2(hist_p50 / hand_p50_ms))
        out["first_token_p50_hand_ms"] = round(hand_p50_ms, 3)
        out["p50_bucket_drift"] = round(drift, 3)
        # one log2 bucket of resolution + boundary slack
        assert drift <= 1.1, (
            f"{what}: histogram first-token p50 {hist_p50:.2f}ms "
            f"disagrees with the hand-collected {hand_p50_ms:.2f}ms by "
            f"{drift:.2f} buckets (> 1 bucket) — histogram math or "
            "source recording is wrong")
    return out


def bench_serving_load(on_accel):
    """ISSUE 7: serving load generator — Poisson arrivals at several
    offered-load levels against (a) the fixed-slot engine and (b) the
    paged engine given the SAME KV pool memory. The paged cache packs
    more live streams into the same cache tokens (block granularity vs a
    reserved max_len strip per slot), so its decode batch is wider at
    high concurrency; chunked prefill additionally keeps long prompts
    from stalling open streams, which shows up in the first-token tail.

    Reported per (leg, level): p50/p99 first-token latency, p50/p99
    per-token decode latency, end-to-end tokens/s — plus the
    paged-vs-fixed tokens/s speedup at the highest level (the A/B the
    acceptance gate reads)."""
    import threading

    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_tiny
    from paddle_tpu.serving import InferenceEngine

    cfg = gpt_tiny(seq_len=256,
                   dtype=jnp.bfloat16 if on_accel else jnp.float32)
    params = gpt_init(cfg, seed=0)
    max_new = 24
    n_req = 16
    # mixed prompt lengths; 160 is the long prompt whose serial prefill
    # stalls every stream on the fixed engine
    plens = [16, 24, 48, 160]
    # same KV memory both legs: fixed 4 slots x 256 = paged 64x16 blocks
    pool_tokens = 4 * 256
    block = 16

    def make_engine(paged):
        return InferenceEngine(
            cfg, params, n_slots=8 if paged else 4, max_len=256,
            paged=paged, block_size=block,
            n_blocks=1 + pool_tokens // block, prefill_chunk=64,
            queue_size=4 * n_req)

    # one shared arrival/workload schedule so both legs serve identical
    # traffic per level
    sched_rng = np.random.default_rng(42)
    prompts = [sched_rng.integers(0, cfg.vocab_size,
                                  plens[i % len(plens)]).astype(np.int32)
               for i in range(n_req)]
    levels = {"low_4rps": sched_rng.exponential(1 / 4.0, n_req),
              "high_32rps": sched_rng.exponential(1 / 32.0, n_req),
              "burst": np.zeros(n_req)}

    def run_level(eng, gaps):
        first_t = [None] * n_req
        done_t = [None] * n_req
        sub_t = [None] * n_req
        h0 = _serving_hist_snap()

        def consume(i, req):
            it = req.stream(timeout=600)
            next(it)
            first_t[i] = time.perf_counter()
            for _ in it:
                pass
            done_t[i] = time.perf_counter()

        threads = []
        t0 = time.perf_counter()
        for i in range(n_req):
            sub_t[i] = time.perf_counter()
            req = eng.submit(prompts[i], max_new_tokens=max_new)
            th = threading.Thread(target=consume, args=(i, req))
            th.start()
            threads.append(th)
            if gaps[i] > 0:
                time.sleep(gaps[i])
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        ftl = np.asarray([f - s for f, s in zip(first_t, sub_t)]) * 1e3
        ptl = np.asarray([(d - f) / (max_new - 1)
                          for d, f in zip(done_t, first_t)]) * 1e3
        # headline percentiles come from the SOURCE-recorded histograms
        # (ISSUE 15) — the same series GET /metrics scrapes — with the
        # hand-collected client-side list as the agreement cross-check
        out = _serving_hist_pcts(h0, _serving_hist_snap(),
                                 float(np.percentile(ftl, 50)),
                                 "serving_load")
        out.update({
            "first_token_ms_p99_hand":
                round(float(np.percentile(ftl, 99)), 2),
            "per_token_ms_p50_hand":
                round(float(np.percentile(ptl, 50)), 3),
            "tokens_per_s": round(n_req * max_new / wall, 2),
        })
        return out

    out = {}
    for paged in (False, True):
        leg = "paged" if paged else "fixed"
        eng = make_engine(paged)
        try:
            for p in sorted(set(plens)):   # warm every prefill bucket
                eng.generate(prompts[plens.index(p) % n_req][:p],
                             max_new_tokens=2)
            out[leg] = {name: run_level(eng, gaps)
                        for name, gaps in levels.items()}
        finally:
            eng.shutdown(drain=False)

    # mesh leg (ISSUE 10): the paged engine sharded data=4 x model=2 over
    # the 8-device mesh (virtual on CPU runs — real win on a TPU slice);
    # pool sized to the same tokens, rounded to the per-shard layout
    import jax

    if len(jax.devices()) >= 8:
        from jax.sharding import Mesh

        from paddle_tpu.parallel.mesh import AXES
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 1, 1, 2), AXES)
        eng = InferenceEngine(
            cfg, params, n_slots=8, max_len=256, paged=True,
            block_size=block, n_blocks=4 + pool_tokens // block,
            prefill_chunk=64, queue_size=4 * n_req, mesh=mesh)
        try:
            for p in sorted(set(plens)):
                eng.generate(prompts[plens.index(p) % n_req][:p],
                             max_new_tokens=2)
            out["paged_mesh"] = {name: run_level(eng, gaps)
                                 for name, gaps in levels.items()}
        except Exception as e:  # noqa: BLE001 — record, don't sink the A/B
            out["paged_mesh"] = f"error: {type(e).__name__}: {e}"
        finally:
            eng.shutdown(drain=False)

    # shared-prefix leg (ISSUE 11): production traffic — every prompt =
    # one shared system prompt + few-shot header (208 tokens) plus a
    # short unique tail (16), Poisson arrivals, prefix cache ON vs OFF
    # on the SAME paged pool. >= 80% of prompt tokens should come from
    # the radix tree, and skipping their prefill is first-token latency
    # off the critical path.
    from paddle_tpu.monitor import stat_get as _sg

    shared_head = sched_rng.integers(0, cfg.vocab_size, 208).astype(np.int32)
    sp_prompts = [np.concatenate([
        shared_head,
        sched_rng.integers(0, cfg.vocab_size, 16).astype(np.int32)])
        for _ in range(n_req)]
    sp_gaps = sched_rng.exponential(1 / 16.0, n_req)

    def run_shared(prefix_on):
        eng = InferenceEngine(
            cfg, params, n_slots=8, paged=True, block_size=block,
            n_blocks=1 + pool_tokens // block, prefill_chunk=64,
            queue_size=4 * n_req, prefix_cache=prefix_on)
        try:
            # warm the programs AND (prefix leg) seed the radix tree —
            # steady-state behavior is what production traffic sees.
            # The second warm request HITS the freshly-seeded tree, so
            # the tail-prefill and CoW programs compile here, not under
            # the measured burst (a compile on the scheduler thread
            # would serialize every stream behind it)
            eng.generate(sp_prompts[0], max_new_tokens=2)
            eng.generate(sp_prompts[0], max_new_tokens=2)
            m0, l0 = _sg("prefix_matched_tokens"), _sg("prefix_lookup_tokens")
            first_t = [None] * n_req
            done_t = [None] * n_req
            sub_t = [None] * n_req

            def consume(i, req):
                it = req.stream(timeout=600)
                next(it)
                first_t[i] = time.perf_counter()
                for _ in it:
                    pass
                done_t[i] = time.perf_counter()

            threads = []
            t0 = time.perf_counter()
            for i in range(n_req):
                sub_t[i] = time.perf_counter()
                req = eng.submit(sp_prompts[i], max_new_tokens=max_new)
                th = threading.Thread(target=consume, args=(i, req))
                th.start()
                threads.append(th)
                if sp_gaps[i] > 0:
                    time.sleep(sp_gaps[i])
            for th in threads:
                th.join(timeout=600)
            wall = time.perf_counter() - t0
            ftl = np.asarray([f - s for f, s in zip(first_t, sub_t)]) * 1e3
            matched = _sg("prefix_matched_tokens") - m0
            looked = _sg("prefix_lookup_tokens") - l0
            return {
                "cache_hit_rate": round(matched / looked, 3) if looked
                else 0.0,
                "first_token_ms_p50":
                    round(float(np.percentile(ftl, 50)), 2),
                "first_token_ms_p99":
                    round(float(np.percentile(ftl, 99)), 2),
                "tokens_per_s": round(n_req * max_new / wall, 2),
            }
        finally:
            eng.shutdown(drain=False)

    sp_off = run_shared(False)
    sp_on = run_shared(True)
    out["shared_prefix"] = {
        "cache_off": sp_off, "cache_on": sp_on,
        "first_token_p50_speedup": round(
            sp_off["first_token_ms_p50"]
            / max(sp_on["first_token_ms_p50"], 1e-9), 3),
        "tokens_per_s_speedup": round(
            sp_on["tokens_per_s"] / max(sp_off["tokens_per_s"], 1e-9), 3)}

    hi = "burst"
    ab = out["paged"][hi]["tokens_per_s"] / out["fixed"][hi]["tokens_per_s"]
    result = {"levels": out, "value": round(ab, 3),
              "unit": "x tokens/s, paged/fixed @ burst",
              "ab_speedup_at_high_concurrency": round(ab, 3),
              "shared_prefix_hit_rate": out["shared_prefix"]["cache_on"][
                  "cache_hit_rate"],
              "shared_prefix_first_token_p50_speedup":
                  out["shared_prefix"]["first_token_p50_speedup"],
              "note": f"{n_req} req x {max_new} new tokens, prompts "
                      f"{plens}, same {pool_tokens}-token KV pool both "
                      "legs (fixed: 4 slots x 256; paged: 64x16 blocks, "
                      "8 slots, prefill_chunk 64); Poisson arrivals per "
                      "level; paged_mesh = same paged engine sharded "
                      "data=4 x model=2 over the 8-device mesh; "
                      "shared_prefix = 208-token shared system prompt + "
                      "16-token unique tail at 16rps Poisson, radix "
                      "prefix cache ON vs OFF on the same pool"}
    if ab < 1.2:
        result["skip_reason"] = (
            f"paged-vs-fixed tokens/s A/B measured {ab:.3f}x (< 1.2x "
            "gate) on this backend — recorded with full level numbers "
            "above; the win requires tick cost to stay sub-linear in "
            "batch width (true on TPU, dispatch-bound CPU varies)")
    return result


def _serving_chaos_lifecycle_leg(cfg, params, rng):
    """ISSUE 14: the lifecycle leg of serving_chaos — Poisson load over
    a 2-replica prefix-caching router WITH a ReplicaSupervisor, under
    ``replica_crash`` + ``spawn_fail``. Gates: identity 1.0, >= 1
    successful restart-rejoin (through the backoff ladder — the first
    respawn attempt is made to fail), >= 1 scale-up/scale-down cycle
    (a slow_tick storm steps the brownout rung, recovery steps it
    back), and the rejoined replica's first token served WARM (radix
    re-warm replay) vs a cold engine's."""
    import threading

    from paddle_tpu import monitor
    from paddle_tpu.resilience.faults import configure_faults
    from paddle_tpu.serving import (EngineRouter, InferenceEngine,
                                    OverloadController, ReplicaSupervisor)

    max_new = 12
    n_req = 16
    head = rng.integers(0, cfg.vocab_size, 64).astype(np.int32)
    tails = [np.concatenate(
        [head, rng.integers(0, cfg.vocab_size, 8).astype(np.int32)])
        for _ in range(n_req)]
    gaps = rng.exponential(1 / 24.0, n_req)

    ctl = OverloadController(queue_wait_budget_ms=150.0,
                             tick_budget_ms=60.0, step_up_after=2,
                             step_down_after=4)

    def make_engine():
        return InferenceEngine(cfg, params, n_slots=4, paged=True,
                               block_size=16, n_blocks=129,
                               prefill_chunk=64, queue_size=4 * n_req,
                               prefix_cache=True, overload=ctl, seed=0)

    # fault-free oracle + the COLD first-token sample (empty radix tree:
    # the full shared head prefills before the first token)
    ref = make_engine()
    try:
        t0 = time.perf_counter()
        it = ref.submit(tails[0], max_new_tokens=max_new).stream(timeout=120)
        next(it)
        cold_ms = (time.perf_counter() - t0) * 1e3
        for _ in it:
            pass
        expected = [ref.generate(t, max_new_tokens=max_new) for t in tails]
    finally:
        ref.shutdown(drain=False)

    rs0 = monitor.stat_get("serving_replica_restarts")
    sc0 = monitor.stat_get("serving_scale_events")
    warm0 = monitor.stat_get("prefix_warm_tokens")
    # replica 0 crashes early (first respawn attempt spawn-fails, the
    # ladder's backoff rung recovers it); replica 1 then eats a slow-tick
    # storm that steps the brownout rung and triggers scale-up
    configure_faults("replica_crash@step=12:replica=0,"
                     "spawn_fail@restart=1:times=1,"
                     "slow_tick@step=40:secs=0.12:repeat=3:replica=1")
    results: list = [None] * n_req
    try:
        router = EngineRouter([make_engine(), make_engine()])
        sup = ReplicaSupervisor(
            router, make_engine, min_replicas=2, max_replicas=3,
            poll_s=0.05, backoff_s=0.1, quarantine_s=1.0, stable_s=1.0,
            scale_up_rung=1, scale_up_after=2, scale_down_after=6,
            scale_down_occupancy=0.3, scale_cooldown_s=0.5,
            drain_timeout_s=2.0)

        def consume(i, req):
            try:
                results[i] = req.result(timeout=180)
            except RuntimeError:
                results[i] = None

        threads = []
        for i in range(n_req):
            req = router.submit(tails[i], max_new_tokens=max_new)
            th = threading.Thread(target=consume, args=(i, req))
            th.start()
            threads.append(th)
            if gaps[i] > 0:
                time.sleep(gaps[i])
        for th in threads:
            th.join(timeout=300)

        # wait out the rejoin (and any in-flight scale-up)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            snap = sup.snapshot()
            if snap["rejoins"] >= 1 and all(
                    r["state"] == "live"
                    for r in snap["replicas"].values()):
                break
            time.sleep(0.05)
        # recovery trickle: fast ticks walk the rung back to 0 (the
        # storm's queue-wait EWMA starts seconds over budget, and each
        # rung needs step_down_after consecutive cool samples), then an
        # idle fleet at rung 0 drains the scale-up replica back out
        for _ in range(120):
            router.generate(tails[0][:16], max_new_tokens=1)
            if ctl.rung == 0:
                break
        t0 = time.monotonic()
        while router.n_replicas > 2 and time.monotonic() - t0 < 60:
            time.sleep(0.05)

        # WARM first-token p50 on the re-warmed fleet (affinity routes
        # the shared head to a replica whose radix tree holds it)
        warm_samples = []
        for _ in range(5):
            t_new = np.concatenate(
                [head, rng.integers(0, cfg.vocab_size, 8).astype(np.int32)])
            t0 = time.perf_counter()
            it = router.submit(t_new, max_new_tokens=2).stream(timeout=120)
            next(it)
            warm_samples.append((time.perf_counter() - t0) * 1e3)
            for _ in it:
                pass
        snap = sup.snapshot()
        n_final = router.n_replicas
        router.shutdown(drain=True, timeout=120)
    finally:
        configure_faults("")

    completed = [i for i in range(n_req) if results[i] is not None]
    corrupt = [i for i in completed if results[i] != expected[i]]
    warm_p50 = float(np.percentile(np.asarray(warm_samples), 50))
    return {
        "identity": 1.0 if completed and not corrupt else 0.0,
        "completed": len(completed), "corrupt": len(corrupt),
        "restarts": monitor.stat_get("serving_replica_restarts") - rs0,
        "rejoins": snap["rejoins"],
        "scale_events": monitor.stat_get("serving_scale_events") - sc0,
        "scale_ups": snap["scale_ups"],
        "scale_downs_completed": snap["scale_downs"],
        "replicas_final": n_final,
        "warm_tokens_replayed":
            monitor.stat_get("prefix_warm_tokens") - warm0,
        "first_token_cold_ms": round(cold_ms, 2),
        "first_token_warm_p50_ms": round(warm_p50, 2),
        "warm_vs_cold": round(warm_p50 / cold_ms, 3) if cold_ms else None,
        "note": f"{n_req} shared-prefix req over 2 prefix-caching "
                "replicas + supervisor; replica 0 crashes at tick 12 "
                "(first respawn spawn-fails -> backoff rung), replica 1 "
                "eats a 3x120ms slow-tick storm (rung climbs -> scale-up "
                "to 3), recovery trickle walks the rung down (drain-"
                "shrink back to 2); identity = all completed streams "
                "token-equal to a fault-free engine; warm = first-token "
                "p50 after the radix re-warm vs the cold full-head "
                "prefill",
    }


def bench_serving_chaos(on_accel):
    """ISSUE 13: serving chaos leg — Poisson load through a 2-replica
    EngineRouter under injected faults (``replica_crash`` mid-run,
    ``slow_tick`` latency storms, ``conn_drop``-style abandoned
    streams) with a shared brownout controller. The acceptance gate:

    - zero healthy-stream token corruption: every stream that COMPLETES
      is token-identical to the same prompt on a fault-free engine;
    - no silent drops: every request ends with an explicit
      finish_reason (deadline sheds included — the 503 material);
    - bounded first-token tail: p99 first-token latency recorded.

    The ISSUE-14 lifecycle leg (``_serving_chaos_lifecycle_leg``) then
    adds a ReplicaSupervisor: restart-rejoin through the backoff ladder
    under ``spawn_fail``, a brownout-driven scale-up/scale-down cycle,
    and the warm-vs-cold first-token comparison for the re-warmed
    radix tree. The ISSUE-19 host-loss leg (``_fleet_burst``) kills a
    decode host of a small cross-host fleet abruptly mid-burst — the
    top-level ``value`` gates ALL three legs' identity.
    """
    import threading

    import jax.numpy as jnp

    from paddle_tpu import monitor
    from paddle_tpu.models import gpt_init, gpt_tiny
    from paddle_tpu.resilience.faults import configure_faults
    from paddle_tpu.serving import (EngineRouter, InferenceEngine,
                                    OverloadController)

    cfg = gpt_tiny(seq_len=256,
                   dtype=jnp.bfloat16 if on_accel else jnp.float32)
    params = gpt_init(cfg, seed=0)
    max_new = 16
    n_req = 20
    rng = np.random.default_rng(1301)
    plens = [12, 24, 40, 72]
    prompts = [rng.integers(0, cfg.vocab_size,
                            plens[i % len(plens)]).astype(np.int32)
               for i in range(n_req)]
    gaps = rng.exponential(1 / 24.0, n_req)    # ~24 rps Poisson
    # a slice of the offered load carries a tight deadline — under the
    # injected storm some of it MUST be shed (503 material), loudly
    tight = {i for i in range(n_req) if i % 5 == 4}

    def make_engine(ctl=None):
        return InferenceEngine(cfg, params, n_slots=4, paged=True,
                               block_size=16, n_blocks=65,
                               prefill_chunk=64, queue_size=4 * n_req,
                               overload=ctl, seed=0)

    # fault-free reference: the token-corruption oracle
    ref = make_engine()
    try:
        expected = [ref.generate(p, max_new_tokens=max_new)
                    for p in prompts]
    finally:
        ref.shutdown(drain=False)

    ctl = OverloadController(queue_wait_budget_ms=150.0,
                             tick_budget_ms=120.0, step_up_after=2,
                             step_down_after=6)
    shed0 = monitor.stat_get("serving_deadline_sheds")
    fo0 = monitor.stat_get("router_failovers")
    h0 = _serving_hist_snap()      # after the oracle run: chaos-leg only
    configure_faults("replica_crash@step=20:replica=0,"
                     "slow_tick@step=8:secs=0.15:repeat=3:replica=1,"
                     "conn_drop@step=3")
    try:
        router = EngineRouter([make_engine(ctl), make_engine(ctl)])
        first_t = [None] * n_req
        results: list = [None] * n_req
        finishes: list = [None] * n_req
        sub_t = [None] * n_req

        def consume(i, req):
            from paddle_tpu.resilience import faults as _f
            dropped = _f.FAULTS.take_conn(i + 1) is not None
            try:
                it = req.stream(timeout=120)
                toks = []
                for n, tok in enumerate(it):
                    if first_t[i] is None:
                        first_t[i] = time.perf_counter()
                    toks.append(tok)
                    if dropped and n >= 1:
                        # the abandoning client: stop consuming and
                        # cancel (the frontend's disconnect path does
                        # exactly this on reader EOF)
                        req.cancel()
                        try:
                            req.result(timeout=60)   # wait for eviction
                        except (TimeoutError, RuntimeError):
                            pass
                        break
                results[i] = toks if not dropped else None
            except (TimeoutError, RuntimeError):
                results[i] = None
            finishes[i] = req.finish_reason

        threads = []
        t0 = time.perf_counter()
        for i in range(n_req):
            sub_t[i] = time.perf_counter()
            req = router.submit(
                prompts[i], max_new_tokens=max_new,
                deadline_s=0.4 if i in tight else 60.0)
            th = threading.Thread(target=consume, args=(i, req))
            th.start()
            threads.append(th)
            if gaps[i] > 0:
                time.sleep(gaps[i])
        for th in threads:
            th.join(timeout=300)
        wall = time.perf_counter() - t0
        router.shutdown(drain=True, timeout=120)
    finally:
        configure_faults("")

    completed = [i for i in range(n_req)
                 if finishes[i] in ("length", "eos")
                 and results[i] is not None]
    corrupt = [i for i in completed if results[i] != expected[i]]
    shed = [i for i in range(n_req) if finishes[i] == "deadline"]
    silent = [i for i in range(n_req) if finishes[i] is None]
    ftl = np.asarray([(first_t[i] - sub_t[i]) * 1e3 for i in range(n_req)
                      if first_t[i] is not None])
    # source-recorded histogram percentiles (ISSUE 15) + agreement gate
    # vs the hand-collected list — under chaos, p50 only (failover
    # adoption restamps a not-yet-started request's submit clock, so the
    # tail definitions legitimately diverge)
    hist = _serving_hist_pcts(
        h0, _serving_hist_snap(),
        float(np.percentile(ftl, 50)) if ftl.size else 0.0,
        "serving_chaos")
    identity = 1.0 if completed and not corrupt else 0.0
    lifecycle = _serving_chaos_lifecycle_leg(cfg, params, rng)
    # ISSUE 19 chaos extension: host-loss injection — a small cross-host
    # fleet burst where a decode host dies abruptly mid-burst and every
    # rerouted stream must stay token-identical
    fleet_loss = _fleet_burst(cfg, params, rng, n_req=8, max_new=10,
                              lose_host=True, job="chaos_fleet")
    # ISSUE 20 network-chaos legs: a net_partition window between the
    # router and one decode host mid-burst (open streams reroute, new
    # submits re-place — token identity must hold), and a prefill host
    # blackholed mid-KV-stream (decode resumes with a local tail
    # prefill, greedy AND sampled identity)
    fleet_partition = _fleet_burst(
        cfg, params, rng, n_req=8, max_new=10, lose_host=False,
        job="chaos_partition",
        fault_spec="net_partition@step=6:secs=1.5:hosts=router|decode0")
    fleet_resume = _fleet_resume_leg(cfg, params, rng)
    return {
        "value": min(identity, lifecycle["identity"],
                     fleet_loss["identity"],
                     fleet_partition["identity"],
                     fleet_resume["identity"]),
        "overload_leg_identity": identity,
        "lifecycle": lifecycle,
        "fleet_host_loss": fleet_loss,
        "fleet_net_partition": fleet_partition,
        "fleet_kv_resume": fleet_resume,
        "unit": "healthy-stream token-identity under chaos (1.0 = exact)",
        "completed": len(completed), "corrupt": len(corrupt),
        "deadline_shed": len(shed), "silent_drops": len(silent),
        "failovers": monitor.stat_get("router_failovers") - fo0,
        "engine_deadline_sheds":
            monitor.stat_get("serving_deadline_sheds") - shed0,
        "brownout_rung_final": monitor.stat_get("brownout_rung"),
        "brownout_steps": monitor.stat_get("brownout_steps"),
        "first_token_ms_p50": hist["first_token_ms_p50"] or None,
        "first_token_ms_p99": hist["first_token_ms_p99"] or None,
        "first_token_ms_p50_hand": round(float(np.percentile(ftl, 50)), 2)
        if ftl.size else None,
        "histograms": hist,
        "wall_s": round(wall, 2),
        "note": f"{n_req} req x {max_new} tokens at ~24rps Poisson over "
                "2 paged replicas (shared 64-block pools), faults: "
                "replica 0 crashes at tick 40, replica 1 eats 3x150ms "
                "slow ticks, stream 3 abandoned mid-generation; every "
                "fifth request carries a 0.4s deadline; identity = all "
                "completed streams token-equal to a fault-free engine",
    }


def _fleet_burst(cfg, params, rng, *, n_req, max_new, lose_host, job,
                 fault_spec=None):
    """ISSUE 19 shared harness: an in-process 3-host fleet (one
    prefill-role + two decode-role HostAgents over real RPC sockets and
    a FileKVStore registry) serving a Poisson burst, optionally losing
    one decode host abruptly mid-burst. Greedy and sampled requests
    interleave; every completed stream is gated token-identical to a
    monolithic single-engine oracle — the disaggregated KV stream and
    the cross-host failover replay must both be invisible in tokens.
    ``fault_spec`` (ISSUE 20) arms deterministic network chaos — e.g. a
    ``net_partition`` window between the router and one decode host —
    for the duration of the burst."""
    import shutil
    import tempfile
    import threading

    from paddle_tpu import monitor
    from paddle_tpu.distributed.elastic import FileKVStore
    from paddle_tpu.monitor import get_histogram, hist_delta, hist_quantile
    from paddle_tpu.resilience.faults import configure_faults
    from paddle_tpu.serving import InferenceEngine
    from paddle_tpu.serving.pod import HostAgent, connect_fleet

    def factory():
        return InferenceEngine(cfg, params, n_slots=4, paged=True,
                               block_size=16, n_blocks=129,
                               prefill_chunk=64, queue_size=4 * n_req,
                               prefix_cache=True, seed=0)

    plens = [40, 72, 24, 56]        # 24 < disagg_min=32: stays direct
    prompts = [rng.integers(0, cfg.vocab_size,
                            plens[i % len(plens)]).astype(np.int32)
               for i in range(n_req)]
    # even requests greedy, odd sampled — identity must hold for both
    sample_kw = [{} if i % 2 == 0 else {"temperature": 0.7, "top_k": 5}
                 for i in range(n_req)]
    gaps = rng.exponential(1 / 24.0, n_req)

    # greedy oracles are rid-independent and precompute; sampled ones
    # are a pure function of (seed, rid), and each fleet engine assigns
    # its OWN rid sequence — so sampled requests verify post-run against
    # a monolithic engine replaying the fleet's actual rid (adoption
    # preserves rid: the same mechanism failover identity rides on)
    expected: dict = {}
    mono = factory()
    try:
        for i in range(n_req):
            if not sample_kw[i]:
                expected[i] = mono.generate(prompts[i],
                                            max_new_tokens=max_new)
    finally:
        mono.shutdown(drain=False)

    s0 = {k: monitor.stat_get(k) for k in
          ("fleet_prefill_routed", "fleet_direct_fallbacks",
           "fleet_kv_transfer_bytes", "fleet_reroutes", "rpc_calls",
           "fleet_kv_chunks_streamed", "fleet_kv_resume_tails",
           "rpc_retries")}
    kv0 = get_histogram("fleet_kv_transfer_ms").snapshot()
    root = tempfile.mkdtemp(prefix="fleet_bench_")
    agents: dict = {}
    router = None
    try:
        store = FileKVStore(root)
        for host, role in (("prefill0", "prefill"), ("decode0", "decode"),
                           ("decode1", "decode")):
            agents[host] = HostAgent(store, job, host, factory, role=role,
                                     heartbeat_s=0.1)
        router = connect_fleet(store, job, min_hosts=3, registry_ttl=0.9,
                               rpc_timeout=60.0, poll_s=0.2,
                               monitor_poll_s=0.1)
        if fault_spec:
            configure_faults(fault_spec)   # after connect: clean per-peer
                                           # RPC call-index spaces

        # role-utilization sampler: decode occupancy vs prefill busy
        util = {"decode": [], "prefill": []}
        stop = threading.Event()

        def sample():
            while not stop.wait(0.05):
                reps = router.healthy_replicas()
                occ = sum(router.engine_for(r).occupancy for r in reps)
                cap = sum(router.engine_for(r).n_slots for r in reps)
                util["decode"].append(occ / cap if cap else 0.0)
                util["prefill"].append(
                    float(any(p.busy for p in router._prefill_pool)))
        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()

        first_t = [None] * n_req
        sub_t = [None] * n_req
        results: list = [None] * n_req
        reqs: list = [None] * n_req

        def consume(i, req):
            try:
                toks = []
                for tok in req.stream(timeout=240):
                    if first_t[i] is None:
                        first_t[i] = time.perf_counter()
                    toks.append(tok)
                results[i] = toks
            except (TimeoutError, RuntimeError):
                results[i] = None

        threads = []
        lost_host = None
        t0 = time.perf_counter()
        for i in range(n_req):
            sub_t[i] = time.perf_counter()
            reqs[i] = router.submit(prompts[i], max_new_tokens=max_new,
                                    **sample_kw[i])
            th = threading.Thread(target=consume, args=(i, reqs[i]))
            th.start()
            threads.append(th)
            if lose_host and lost_host is None and i == n_req // 2:
                # kill the decode host serving an in-flight stream: its
                # open requests MUST reroute token-identically
                for r in reqs[:i + 1]:
                    rep = getattr(r, "_replica", None)
                    if r.finish_reason is None and rep is not None:
                        host = getattr(router.engine_for(rep), "host",
                                       None)
                        if host in agents:
                            lost_host = host
                            agents[host].close(abrupt=True)
                            break
            if gaps[i] > 0:
                time.sleep(gaps[i])
        for th in threads:
            th.join(timeout=300)
        wall = time.perf_counter() - t0
        stop.set()
        sampler.join(timeout=2.0)
        stream_stats = dict(router.last_stream_stats or {})
    finally:
        if fault_spec:
            configure_faults("")
        if router is not None:
            router.shutdown(drain=False)
        for a in agents.values():
            try:
                a.close()
            except Exception:  # noqa: BLE001 — the killed host is gone
                pass
        shutil.rmtree(root, ignore_errors=True)

    from paddle_tpu.serving.engine import GenerationRequest

    oracle = factory()
    try:
        for i in range(n_req):
            if not sample_kw[i] or results[i] is None:
                continue
            req = GenerationRequest(prompts[i], max_new,
                                    sample_kw[i]["temperature"],
                                    sample_kw[i]["top_k"], 1.0, None, None)
            req.rid = reqs[i].rid
            oracle.adopt_request(req)
            expected[i] = req.result(timeout=120)
    finally:
        oracle.shutdown(drain=False)

    completed = [i for i in range(n_req) if results[i] is not None]
    corrupt = [i for i in completed if results[i] != expected.get(i)]
    ftl = np.asarray([(first_t[i] - sub_t[i]) * 1e3 for i in range(n_req)
                      if first_t[i] is not None])
    kvd = hist_delta(kv0, get_histogram("fleet_kv_transfer_ms").snapshot())
    s1 = {k: monitor.stat_get(k) - s0[k] for k in s0}
    routed = s1["fleet_prefill_routed"]
    disagg_total = routed + s1["fleet_direct_fallbacks"]
    return {
        "identity": 1.0 if len(completed) == n_req and not corrupt
        else 0.0,
        "completed": len(completed), "corrupt": len(corrupt),
        "lost_host": lost_host,
        "rerouted_streams": s1["fleet_reroutes"],
        "prefill_routed": routed,
        "direct_fallbacks": s1["fleet_direct_fallbacks"],
        "disagg_frac": round(routed / disagg_total, 3)
        if disagg_total else 0.0,
        "kv_transfer_ms_p50": round(hist_quantile(kvd, 0.50), 3),
        "kv_transfer_ms_p99": round(hist_quantile(kvd, 0.99), 3),
        "kv_transfer_mib": round(
            s1["fleet_kv_transfer_bytes"] / (1 << 20), 3),
        "kv_chunks_streamed": s1["fleet_kv_chunks_streamed"],
        "kv_resume_tails": s1["fleet_kv_resume_tails"],
        "rpc_retries": s1["rpc_retries"],
        "last_stream_first_block_ms": None
        if stream_stats.get("first_block_ms") is None
        else round(stream_stats["first_block_ms"], 3),
        "last_stream_chunks": stream_stats.get("chunks"),
        "first_token_ms_p50": round(float(np.percentile(ftl, 50)), 2)
        if ftl.size else None,
        "first_token_ms_p99": round(float(np.percentile(ftl, 99)), 2)
        if ftl.size else None,
        "decode_occupancy_mean": round(
            float(np.mean(util["decode"])), 3) if util["decode"] else 0.0,
        "prefill_busy_frac": round(
            float(np.mean(util["prefill"])), 3) if util["prefill"] else 0.0,
        "rpc_calls": s1["rpc_calls"],
        "wall_s": round(wall, 2),
    }


def _fleet_resume_leg(cfg, params, rng):
    """ISSUE 20 chaos leg: prefill-host death MID-KV-stream. A 2-host
    fleet (prefill0 + decode0) streams a long prompt's KV blocks in
    2-block chunks; after the first chunk lands, every further
    ``export_range`` to the prefill host is blackholed (``rpc_drop``
    with an unspendable budget — the wire signature of the host dying
    mid-transfer). The decode replica must keep the received prefix and
    locally prefill only the missing tail (``fleet_kv_resume_tails``),
    token-identical to a monolithic oracle — greedy AND sampled."""
    import shutil
    import tempfile

    from paddle_tpu import monitor
    from paddle_tpu.distributed.elastic import FileKVStore
    from paddle_tpu.resilience.faults import configure_faults
    from paddle_tpu.serving import InferenceEngine
    from paddle_tpu.serving.engine import GenerationRequest
    from paddle_tpu.serving.pod import HostAgent, connect_fleet

    def factory():
        return InferenceEngine(cfg, params, n_slots=4, paged=True,
                               block_size=16, n_blocks=129,
                               prefill_chunk=64, prefix_cache=True,
                               seed=0)

    max_new = 12
    out = {}
    for mode, kw in (("greedy", {}),
                     ("sampled", {"temperature": 0.7, "top_k": 5})):
        prompt = rng.integers(0, cfg.vocab_size, 120).astype(np.int32)
        root = tempfile.mkdtemp(prefix="fleet_resume_")
        agents, router = {}, None
        r0 = c0 = 0
        try:
            store = FileKVStore(root)
            for host, role in (("prefill0", "prefill"),
                               ("decode0", "decode")):
                agents[host] = HostAgent(store, f"resume_{mode}", host,
                                         factory, role=role,
                                         heartbeat_s=0.1)
            router = connect_fleet(store, f"resume_{mode}", min_hosts=2,
                                   registry_ttl=0.9, rpc_timeout=60.0,
                                   poll_s=0.2, monitor_poll_s=0.1,
                                   kv_chunk_blocks=2)
            # warm the whole disagg path (prefill jit, export, splice)
            # faults-off, so the measured stream's FIRST export_range
            # returns a chunk instead of an empty compile-stalled poll
            # — the fault targets call indices, which must line up
            warm = rng.integers(0, cfg.vocab_size, 120).astype(np.int32)
            router.submit(warm, max_new_tokens=2).result(timeout=240)
            r0 = monitor.stat_get("fleet_kv_resume_tails")
            c0 = monitor.stat_get("fleet_kv_chunks_streamed")
            # router->prefill0 call-index space: 1 = prefill_start,
            # 2 = first export_range (ships chunk 1), 3+ = blackholed
            configure_faults("rpc_drop@call=3:method=export_range:"
                             "host=prefill0:repeat=1000")
            req = router.submit(prompt, max_new_tokens=max_new, **kw)
            toks = req.result(timeout=240)
            stream = dict(router.last_stream_stats or {})
        finally:
            configure_faults("")
            if router is not None:
                router.shutdown(drain=False)
            for a in agents.values():
                try:
                    a.close()
                except Exception:  # noqa: BLE001
                    pass
            shutil.rmtree(root, ignore_errors=True)
        # sampled output is a pure function of (seed, rid): replay the
        # fleet's actual rid on a monolithic oracle, as the identity
        # contract defines it
        oracle = factory()
        try:
            if kw:
                o = GenerationRequest(prompt, max_new, kw["temperature"],
                                      kw["top_k"], 1.0, None, None)
                o.rid = req.rid
                oracle.adopt_request(o)
                expected = o.result(timeout=120)
            else:
                expected = oracle.generate(prompt, max_new_tokens=max_new)
        finally:
            oracle.shutdown(drain=False)
        resumes = monitor.stat_get("fleet_kv_resume_tails") - r0
        out[mode] = {
            # the gate is identity AND an actual mid-stream resume — a
            # direct-fallback run would be identical but prove nothing
            "identity": 1.0 if toks == expected and resumes >= 1
            else 0.0,
            "token_identical": toks == expected,
            "resume_tails": resumes,
            "chunks_before_death":
                monitor.stat_get("fleet_kv_chunks_streamed") - c0,
            "acked_tokens": stream.get("acked_tokens"),
            "target_tokens": stream.get("target_tokens"),
        }
    return {
        "identity": min(out["greedy"]["identity"],
                        out["sampled"]["identity"]),
        "greedy": out["greedy"], "sampled": out["sampled"],
        "note": "prefill0 blackholed after the first 2-block KV chunk; "
                "decode keeps the received prefix and locally prefills "
                "the missing tail — gated token-identical vs a "
                "monolithic oracle, greedy and sampled (rid-replayed)",
    }


def bench_serving_fleet(on_accel):
    """ISSUE 19: cross-host fleet leg — one prefill-role + two
    decode-role HostAgents over real loopback RPC and a FileKVStore
    registry, serving a Poisson burst of mixed greedy/sampled requests
    with disaggregated prefill->decode KV-block streaming, then losing
    a decode host abruptly mid-burst. Gates: every stream completes
    token-identical to a monolithic engine (identity 1.0 — KV splice
    AND cross-host failover replay both invisible), plus first-token
    p50/p99, kv-transfer ms, and the prefill/decode utilization split
    the acceptance bar names."""
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_tiny

    from paddle_tpu.serving import InferenceEngine

    cfg = gpt_tiny(seq_len=256,
                   dtype=jnp.bfloat16 if on_accel else jnp.float32)
    params = gpt_init(cfg, seed=0)
    rng = np.random.default_rng(1901)
    leg = _fleet_burst(cfg, params, rng, n_req=12, max_new=16,
                       lose_host=True, job="bench_fleet")

    # ISSUE 20: streamed first-block latency vs whole-prefix
    # stop-and-copy, both measured from COLD prefill start on the same
    # 240-token prompt — chunks ship while the next chunk computes, so
    # the first spliceable block lands after ONE prefill chunk while a
    # stop-and-copy export waits for all 15 (prefill_chunk=16 keeps
    # the per-chunk cost well above timer noise on a warm engine)
    def eng():
        return InferenceEngine(cfg, params, n_slots=4, paged=True,
                               block_size=16, n_blocks=129,
                               prefill_chunk=16, prefix_cache=True,
                               seed=0)

    p_warm = rng.integers(0, cfg.vocab_size, 240).astype(np.int32)
    p = rng.integers(0, cfg.vocab_size, 240).astype(np.int32)
    src_a, dst_a, src_b, dst_b = eng(), eng(), eng(), eng()
    first_block_ms = stop_copy_ms = None
    try:
        # warmup round (p_warm): amortize per-engine jit compile of the
        # prefill / export / splice paths so the measured round compares
        # transfer strategies, not compile noise
        src_b.warm_prefix(p_warm).result(timeout=240)
        w = src_b.export_kv_range(p_warm, start_block=0, max_blocks=1)
        dst_b.import_kv_chunk(p_warm, w["kb"], w["vb"],
                              int(w["start_block"]),
                              int(w["covered_tokens"]))
        src_a.warm_prefix(p_warm).result(timeout=240)
        w = src_a.export_kv_prefix(p_warm)
        dst_a.import_kv_prefix(p_warm, w["kb"], w["vb"],
                               w["matched_len"])
        # measured round (p): both paths from COLD prefill start
        t0 = time.perf_counter()
        wreq = src_b.warm_prefix(p)    # NON-blocking: chunked prefill
        deadline = t0 + 240            # computes while we stream
        while time.perf_counter() < deadline:
            exp1 = src_b.export_kv_range(p, start_block=0, max_blocks=1)
            if exp1["n_blocks"] > 0:
                dst_b.import_kv_chunk(p, exp1["kb"], exp1["vb"],
                                      int(exp1["start_block"]),
                                      int(exp1["covered_tokens"]))
                first_block_ms = (time.perf_counter() - t0) * 1e3
                break
            time.sleep(0.002)
        wreq.result(timeout=240)       # quiesce: src_b's tail prefill
        t0 = time.perf_counter()       # must not tax the stop-copy leg
        src_a.warm_prefix(p).result(timeout=240)   # the WHOLE prefill
        exp = src_a.export_kv_prefix(p)
        dst_a.import_kv_prefix(p, exp["kb"], exp["vb"],
                               exp["matched_len"])
        stop_copy_ms = (time.perf_counter() - t0) * 1e3
    finally:
        for e in (src_a, dst_a, src_b, dst_b):
            e.shutdown(drain=False)
    leg["kv_first_block_ms"] = None if first_block_ms is None \
        else round(first_block_ms, 3)
    leg["kv_stop_copy_ms"] = None if stop_copy_ms is None \
        else round(stop_copy_ms, 3)
    leg["kv_first_block_lt_stop_copy"] = (
        first_block_ms is not None and stop_copy_ms is not None
        and first_block_ms < stop_copy_ms)

    leg["value"] = leg["identity"]
    leg["unit"] = "fleet token-identity under host loss (1.0 = exact)"
    leg["note"] = (
        "12 req (greedy/sampled interleaved, ~24rps Poisson) through a "
        "3-host fleet (prefill0 + decode0/decode1, real RPC sockets, "
        "FileKVStore registry heartbeats); long prompts prefill on the "
        "prefill host and stream KV blocks to the placed decode "
        "replica; one decode host is killed abruptly mid-burst — its "
        "open streams reroute via token-replay failover; identity = "
        "every stream token-equal to one monolithic engine; "
        "kv_first_block_ms (cold prefill start -> first streamed block "
        "spliced) vs kv_stop_copy_ms (cold start -> whole-prefix "
        "export+import) on the same 240-token prompt")
    return leg


def bench_serving_spec(on_accel):
    """ISSUE 10/11: speculative-decoding A/B — tokens/s spec vs non-spec
    at three temperatures on gpt_tiny, with the measured draft
    acceptance rate. The HEADLINE draft is a *distilled* 2-layer
    gpt_nano (tools/distill_draft — KL-matched to the teacher on CPU in
    seconds, embeddings seeded from the target), so the acceptance
    number measures a real draft, not shared-weights machinery; the
    PR-10 1-layer truncation (models.gpt_truncate) stays as the
    comparison row.

    The speculative tick is ONE compiled program (k draft steps + the
    k+1-position verify + acceptance), so per tick a stream costs one
    dispatch instead of one per token — on a dispatch-bound CPU host
    the verify pass amortizes exactly that, and on TPU it additionally
    turns k serial matmul-bound steps into one wider pass."""
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_tiny
    from paddle_tpu.models.gpt import gpt_truncate
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.serving import InferenceEngine
    from tools.distill_draft import distill_draft

    cfg = gpt_tiny(seq_len=256,
                   dtype=jnp.bfloat16 if on_accel else jnp.float32)
    params = gpt_init(cfg, seed=0)
    truncated = gpt_truncate(cfg, params, 1)
    distilled, distill_info = distill_draft(cfg, params, n_layers=1,
                                            steps=250, seq=32)
    rng = np.random.default_rng(0)
    n_req, max_new = 4, 48
    prompts = [rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
               for _ in range(n_req)]

    def run(draft_arg, temp):
        eng = InferenceEngine(cfg, params, n_slots=4, max_len=256,
                              draft=draft_arg, spec_k=6)
        try:
            # warm the prefill bucket + both decode programs
            eng.generate(prompts[0], max_new_tokens=4, temperature=temp)
            d0 = stat_get("serving_decode_ms")
            p0, a0 = stat_get("spec_proposed"), stat_get("spec_accepted")
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new_tokens=max_new, temperature=temp)
                    for p in prompts]
            toks = sum(len(r.result(timeout=600)) for r in reqs)
            wall = time.perf_counter() - t0
            dms = stat_get("serving_decode_ms") - d0
            tps = toks / (dms / 1e3) if dms > 0 else toks / wall
            prop = stat_get("spec_proposed") - p0
            acc = stat_get("spec_accepted") - a0
            return {"tokens_per_s": round(tps, 2),
                    "acceptance": round(acc / prop, 3) if prop else None}
        finally:
            eng.shutdown(drain=False)

    temps = {}
    for temp in (0.0, 0.7, 1.0):
        base = run(None, temp)
        spec = run(distilled, temp)
        trunc = run(truncated, temp)
        temps[f"t{temp}"] = {
            "nonspec_tokens_per_s": base["tokens_per_s"],
            "spec_tokens_per_s": spec["tokens_per_s"],
            "speedup": round(spec["tokens_per_s"] / base["tokens_per_s"], 3),
            "acceptance": spec["acceptance"],
            "truncated_tokens_per_s": trunc["tokens_per_s"],
            "truncated_acceptance": trunc["acceptance"]}
    g = temps["t0.0"]
    result = {"temps": temps, "value": g["speedup"],
              "unit": "x tokens/s, spec/nonspec @ greedy",
              "acceptance_at_greedy": g["acceptance"],
              "distill": {k: round(v, 4) if isinstance(v, float) else v
                          for k, v in distill_info.items()},
              "note": f"{n_req} req x {max_new} tokens, prompt 24, 4 "
                      "slots, spec_k 6; draft = DISTILLED 1-layer "
                      "gpt_nano (tools/distill_draft, KL-matched, "
                      "embeddings seeded from the target) — acceptance "
                      "measures a real draft; truncated_* rows keep the "
                      "PR-10 shared-weights 1-layer truncation for "
                      "comparison; tokens/s is decode-phase "
                      "(serving_decode_ms), greedy output pinned "
                      "token-identical by tests/test_serving_spec.py"}
    if g["speedup"] < 1.3 or (g["acceptance"] or 0.0) < 0.6:
        result["skip_reason"] = (
            f"spec A/B measured {g['speedup']}x at acceptance "
            f"{g['acceptance']} (< 1.3x @ >= 0.6 gate) on this backend — "
            "full per-temperature numbers recorded above")
    return result


def bench_gpt_tiny_fused(on_accel):
    """ISSUE 6: fused-vs-unfused A/B for the Pallas kernel library on
    gpt_tiny — runs on ANY backend (the CPU-CI-visible kernel number).

    Two legs, identical model/seed/data:
    - unfused: FLAGS_fused_optimizer=0 (AdamW.step() = one jit dispatch
      per parameter) + the composed jnp MLP math;
    - fused: FLAGS_fused_optimizer=1 (ONE flat-bucket dispatch) +
      cfg.fused_mlp (Pallas fused LN/MLP on TPU; identical math on CPU).

    Parameters are held UNSTACKED — one Parameter per layer weight, the
    nn.Layer surface an eager user actually trains through (the stacked
    (L, ...) layout exists only inside the jitted loss) — so the
    optimizer A/B measures the real per-parameter dispatch count the
    fused path collapses (8 layers x 12 block params + 5 = 101).

    Reported: the optimizer-update A/B and MLP fwd+bwd A/B separately
    (the components the flags actually change), their composite speedup,
    and end-to-end train-step sps + MFU for both legs."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Parameter
    from paddle_tpu.models import gpt_init, gpt_loss, gpt_tiny
    from paddle_tpu.ops.fused_kernels import fused_ln_mlp

    dtype = jnp.bfloat16 if on_accel else jnp.float32
    batch, seq = 8, 128
    n_layers = 8
    rng = np.random.default_rng(0)
    iters = 20 if on_accel else 8

    def one_leg(fused):
        paddle.set_flags({"FLAGS_fused_optimizer": int(fused)})
        cfg = gpt_tiny(seq_len=seq, n_layers=n_layers, dtype=dtype,
                       fused_mlp=bool(fused))
        tree = jax.device_put(gpt_init(cfg, seed=0))
        top_names = sorted(k for k in tree if k != "blocks")
        bnames = sorted(tree["blocks"])
        L = cfg.n_layers
        plist = [Parameter(tree[k]) for k in top_names]
        for k in bnames:
            plist.extend(Parameter(tree["blocks"][k][l])
                         for l in range(L))
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=plist,
                                     weight_decay=0.01)
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        labels = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda pt, b: gpt_loss(cfg, pt, b)))

        def rebuilt():
            vals = [p._data for p in plist]
            t = dict(zip(top_names, vals[:len(top_names)]))
            off = len(top_names)
            b = {}
            for k in bnames:
                b[k] = jnp.stack(vals[off:off + L])
                off += L
            t["blocks"] = b
            return t

        def flat_grads(grads):
            out = [grads[k] for k in top_names]
            for k in bnames:
                gk = grads["blocks"][k]
                out.extend(gk[l] for l in range(L))
            return out

        def step():
            loss, grads = grad_fn(rebuilt(), (tokens, labels))
            for p, g in zip(plist, flat_grads(grads)):
                p.grad = g
            opt.step()
            opt.clear_grad()
            return loss

        for _ in range(3):
            loss = step()
        jax.block_until_ready(plist[0]._data)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step()
        jax.block_until_ready(plist[0]._data)
        float(loss)
        step_s = (time.perf_counter() - t0) / iters

        # optimizer-update A/B: grads fixed, ONLY opt.step() timed —
        # isolates what FLAGS_fused_optimizer changes (N per-param
        # dispatches vs one flat-bucket dispatch). FLAGS_benchmark is on
        # for the timed window so the per-kernel rows (fused_adam@step)
        # land in the artifact.
        from paddle_tpu.monitor import benchmark as _mb

        _, grads = grad_fn(rebuilt(), (tokens, labels))
        flat_g = flat_grads(grads)
        for _ in range(3):
            for p, g in zip(plist, flat_g):
                p.grad = g
            opt.step()
        jax.block_until_ready(plist[0]._data)
        paddle.set_flags({"FLAGS_benchmark": 1})
        opt_s = float("inf")
        for _ in range(3):                       # best-of-3 rounds
            t0 = time.perf_counter()
            for _ in range(iters):
                for p, g in zip(plist, flat_g):
                    p.grad = g
                opt.step()
            jax.block_until_ready(plist[0]._data)
            opt_s = min(opt_s, (time.perf_counter() - t0) / iters)
        paddle.set_flags({"FLAGS_benchmark": 0})
        bench_rows = [
            {k: r[k] for k in ("op", "calls", "avg")}
            for r in _mb.benchmark_rows()
            if r["op"].startswith(("fused_", "grad_overlap@"))]
        _mb.benchmark_reset()

        # MLP fwd+bwd A/B at the block's shapes (what cfg.fused_mlp
        # changes; identical math off-TPU, Pallas kernels on)
        H, M = cfg.hidden, cfg.mlp_hidden
        x = jnp.asarray(rng.normal(size=(batch, seq, H)), dtype)
        mlp_p = {
            "s": jnp.ones((H,), jnp.float32),
            "b": jnp.zeros((H,), jnp.float32),
            "w1": jnp.asarray(rng.normal(size=(H, M)) * 0.05, dtype),
            "b1": jnp.zeros((M,), dtype),
            "w2": jnp.asarray(rng.normal(size=(M, H)) * 0.05, dtype),
            "b2": jnp.zeros((H,), dtype),
        }

        if fused:
            def mlp(pp, xx):
                return jnp.sum(fused_ln_mlp(
                    xx, pp["w1"], pp["b1"], pp["w2"], pp["b2"],
                    ln_scale=pp["s"], ln_bias=pp["b"]).astype(jnp.float32))
        else:
            def mlp(pp, xx):
                x32 = xx.astype(jnp.float32)
                mu = jnp.mean(x32, -1, keepdims=True)
                var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
                h = ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * pp["s"]
                     + pp["b"]).astype(xx.dtype)
                h = jax.nn.gelu(h @ pp["w1"] + pp["b1"])
                return jnp.sum((xx + h @ pp["w2"]
                                + pp["b2"]).astype(jnp.float32))

        mlp_g = jax.jit(jax.grad(mlp))
        out = mlp_g(mlp_p, x)
        jax.block_until_ready(out)
        mlp_s = float("inf")
        for _ in range(3):                       # best-of-3 rounds
            t0 = time.perf_counter()
            for _ in range(iters):
                out = mlp_g(mlp_p, x)
            jax.block_until_ready(out)
            mlp_s = min(mlp_s, (time.perf_counter() - t0) / iters)

        n_params = sum(int(np.prod(p._data.shape)) for p in plist)
        paddle.set_flags({"FLAGS_fused_optimizer": 0})
        return {"step_sps": batch / step_s, "opt_ms": opt_s * 1e3,
                "mlp_ms": mlp_s * 1e3, "n_params": n_params,
                "bench_rows": bench_rows}

    unf = one_leg(False)
    fus = one_leg(True)
    composite = ((unf["opt_ms"] + unf["mlp_ms"])
                 / max(fus["opt_ms"] + fus["mlp_ms"], 1e-9))
    return {
        "sps": round(fus["step_sps"], 2),
        "value": round(fus["step_sps"], 2),
        "unit": "samples/sec",
        "mfu": round(_mfu(fus["n_params"], seq, fus["step_sps"]), 4),
        "speedup": round(composite, 3),
        "opt_ab_ms": {"unfused": round(unf["opt_ms"], 3),
                      "fused": round(fus["opt_ms"], 3),
                      "speedup": round(unf["opt_ms"]
                                       / max(fus["opt_ms"], 1e-9), 2)},
        "mlp_ab_ms": {"unfused": round(unf["mlp_ms"], 3),
                      "fused": round(fus["mlp_ms"], 3)},
        "unfused_sps": round(unf["step_sps"], 2),
        "benchmark_rows": fus["bench_rows"],
        "note": "params held unstacked (101 Parameters, the eager "
                "nn.Layer surface); fused leg = FLAGS_fused_optimizer "
                "(ONE flat-bucket AdamW dispatch vs 101 per-param "
                "dispatches) + cfg.fused_mlp (Pallas LN/MLP on TPU, "
                "identical math on CPU); speedup is the composite over "
                "the components the flags change (opt update + MLP "
                "fwd/bwd), best-of-3 timing"}


def bench_flash_s2048(on_accel):
    """ISSUE 17: the real seq-2048 flash A/B — autotuned block config
    (FLAGS_autotune, shape-keyed trial cache) vs the hand-picked
    defaults, at BERT-base attention shapes, causal, fwd+bwd.

    vs_baseline here is autotuned-over-hand-picked: >1.0 means the
    measured trials beat the static block table for this shape. The
    first autotuned compile runs the 3-5 candidate trials and persists
    the winner (tools/autotune_cache.json or PADDLE_TPU_AUTOTUNE_CACHE);
    the timed window then re-jits and HITS the cache — autotune_hits
    moving is asserted alongside the timing."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.monitor import stats as _st
    from paddle_tpu.ops.flash_attention import flash_attention_arrays

    B, H, S, D = (4, 12, 2048, 64) if on_accel else (1, 2, 2048, 64)
    dtype = jnp.bfloat16 if on_accel else jnp.float32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)) * 0.05, dtype)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)) * 0.05, dtype)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)) * 0.05, dtype)

    if not on_accel:
        # CPU: Pallas only runs under interpret (minutes at S=2048), so
        # the recorded number is the composed-jnp fallback — the row
        # exists with provenance; the A/B itself needs an accelerator.
        fn = jax.jit(lambda a, b, c: flash_attention_arrays(
            a, b, c, causal=True))
        jax.block_until_ready(fn(q, k, v))
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 3
        return {"value": round(B * S / dt, 1), "unit": "tokens/sec",
                "vs_baseline": None, "mfu": None,
                "note": "cpu smoke: composed-jnp fallback, fwd only; "
                        "the autotuned-vs-hand-picked A/B runs the "
                        "Pallas kernel and needs an accelerator"}

    iters = 20

    def fwd_bwd(a, b, c):
        def f(aa, bb, cc):
            return jnp.sum(flash_attention_arrays(
                aa, bb, cc, causal=True).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(a, b, c)

    def one_leg(auto):
        paddle.set_flags({"FLAGS_autotune": int(auto)})
        try:
            fn = jax.jit(fwd_bwd)          # fresh wrapper => retrace
            jax.block_until_ready(fn(q, k, v))   # compile (+trials)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = fn(q, k, v)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) / iters)
        finally:
            paddle.set_flags({"FLAGS_autotune": 0})
        return best

    hand_s = one_leg(False)
    h0, m0 = _st.AUTOTUNE_HITS.get(), _st.AUTOTUNE_MISSES.get()
    auto_s = one_leg(True)
    hits, misses = _st.AUTOTUNE_HITS.get() - h0, _st.AUTOTUNE_MISSES.get() - m0
    # causal attention FLOPs: fwd = 0.5 * 4*B*H*S^2*D; bwd ~= 2.5x fwd
    # (the flash-attention repo's counting convention)
    flops = 3.5 * 0.5 * 4.0 * B * H * S * S * D
    best_s = min(hand_s, auto_s)
    return {"value": round(B * S / best_s, 1), "unit": "tokens/sec",
            "mfu": round(flops / best_s / 197e12, 4),
            "vs_baseline": round(hand_s / auto_s, 4),
            "hand_picked_ms": round(hand_s * 1e3, 3),
            "autotuned_ms": round(auto_s * 1e3, 3),
            "autotune_hits": hits, "autotune_misses": misses,
            "baseline": "the hand-picked block table (_auto_block) this "
                        "repo shipped before ISSUE 17 — vs_baseline is "
                        "hand_picked_ms/autotuned_ms at this shape",
            "note": "causal flash fwd+bwd at (%d,%d,%d,%d) bf16, "
                    "best-of-3x%d; mfu uses the 3.5x-causal-fwd FLOP "
                    "convention over the v5e 197e12 peak"
                    % (B, H, S, D, iters)}


def bench_gpt_tiny_fp8(on_accel):
    """ISSUE 17: fp8 (e4m3) MLP A/B on gpt_tiny — GPTConfig(fp8=True)
    routes both MLP matmuls through the fused-dequant fp8 kernel with
    just-in-time per-tensor scaling and STE gradients. Runs on any
    backend (off-TPU the kernel falls back to the identical-op-sequence
    reference, so CPU measures the quantize+bf16-dot math, not the MXU
    fp8 rate — the note says which one the row is)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_loss, gpt_tiny
    from paddle_tpu.monitor import stats as _st

    dtype = jnp.bfloat16 if on_accel else jnp.float32
    batch, seq, n_layers = 8, 128, 8
    iters = 20 if on_accel else 8
    rng = np.random.default_rng(0)
    tokens = None

    def one_leg(fp8):
        nonlocal tokens
        cfg = gpt_tiny(seq_len=seq, n_layers=n_layers, dtype=dtype,
                       fp8=fp8)
        tree = jax.device_put(gpt_init(cfg, seed=0))
        if tokens is None:
            tokens = (jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (batch, seq)), jnp.int32),
                      jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (batch, seq)), jnp.int32))
        grad_fn = jax.jit(jax.value_and_grad(
            lambda pt, b: gpt_loss(cfg, pt, b)))
        loss, g = grad_fn(tree, tokens)
        jax.block_until_ready(g)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, g = grad_fn(tree, tokens)
            jax.block_until_ready(g)
            best = min(best, (time.perf_counter() - t0) / iters)
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree_util.tree_leaves(tree))
        return batch / best, float(loss), n_params

    c0 = _st.FP8_MATMUL_CALLS.get()
    base_sps, base_loss, n_params = one_leg(False)
    fp8_sps, fp8_loss, _ = one_leg(True)
    return {"value": round(fp8_sps, 2), "unit": "samples/sec",
            "mfu": round(_mfu(n_params, seq, fp8_sps), 4),
            "vs_baseline": round(fp8_sps / base_sps, 4),
            "baseline_sps": round(base_sps, 2),
            "loss_drift": round(abs(fp8_loss - base_loss), 4),
            "fp8_matmul_calls": _st.FP8_MATMUL_CALLS.get() - c0,
            "baseline": "the same model/seed/data with the default "
                        "(unfused jnp) MLP — vs_baseline is "
                        "fp8_sps/default_sps",
            "note": ("fp8 Pallas kernel (fused dequant epilogue), "
                     "jit per-tensor scaling, grad fwd+bwd timed"
                     if on_accel else
                     "cpu: fp8 reference path (quantize + bf16 dots — "
                     "numerics identical to the kernel, no MXU fp8 "
                     "rate); loss_drift is the expected e4m3 "
                     "quantization error, NOT a bug"),
            }


def bench_gpt_moe(on_accel):
    """ISSUE 18: FLOPs-matched dense vs MoE A/B on the 8-device mesh.

    Dense leg: mlp_ratio=4 per-token FFN. MoE leg: E=8 experts of
    mlp_ratio=2 with top-2 routing and capacity factor 1.0 — each token
    still does 2 x 2H of FFN compute (exactly FLOPs-matched: cf=1.0
    means zero capacity padding), but the layer HOLDS 8 x (2/4) = 4x
    the dense MLP parameters. Both legs train on the same dp=2 x
    model=4 mesh (experts sharded over "model", ep=4); the row pins the
    MoE promise: >=4x MLP parameters at <=1.5x the dense step time,
    with the token->expert dispatch really lowering to an AllToAll pair
    and a finite aux load-balance loss."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import (GPTConfig, gpt_init, gpt_loss,
                                       gpt_param_specs)
    from paddle_tpu.parallel.mesh import create_mesh, set_mesh
    from paddle_tpu.parallel.train_step import DistributedTrainStep

    if len(jax.devices()) < 8:
        return {"value": None, "unit": "moe_step_time_ratio",
                "note": "skipped: needs 8 devices (dp=2 x ep=4)"}
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16 if on_accel else jnp.float32
    batch, seq, iters = 16, 64, (20 if on_accel else 3)
    base = dict(vocab_size=512, hidden=512, n_layers=4, n_heads=4,
                seq_len=seq, dtype=dtype)
    tokens = rng.integers(0, base["vocab_size"], (batch, seq + 1))
    data = (jnp.asarray(tokens[:, :-1], jnp.int32),
            jnp.asarray(tokens[:, 1:], jnp.int32))

    def mlp_params(cfg, params):
        if cfg.moe_experts:
            moe = params["moe"]
            return sum(int(np.prod(v.shape)) for k, v in moe.items()
                       if k != "router_w") \
                + sum(int(np.prod(params["blocks"][k].shape))
                      for k in ("fc_w", "fc_b", "out_w", "out_b")
                      if params["blocks"][k].size)
        return sum(int(np.prod(params["blocks"][k].shape))
                   for k in ("fc_w", "fc_b", "out_w", "out_b"))

    def one_leg(cfg):
        params = gpt_init(cfg, 0)
        st = DistributedTrainStep(
            lambda p, b: gpt_loss(cfg, p, b), params,
            gpt_param_specs(cfg), optimizer="adamw", lr=1e-3)
        hlo = st.lower(data).compile().as_text()
        loss = float(st(data))          # warm + compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                loss_dev = st(data)
            loss = float(loss_dev)      # sync
            best = min(best, (time.perf_counter() - t0) / iters)
        return best, loss, hlo, mlp_params(cfg, params)

    try:
        create_mesh(dp=2, sharding=1, pp=1, mp=4)
        dense_s, dense_loss, _, dense_mlp = one_leg(
            GPTConfig(mlp_ratio=4, **base))
        moe_cfg = GPTConfig(mlp_ratio=2, moe_experts=8, moe_top_k=2,
                            moe_every=1, moe_axis="model",
                            moe_capacity_factor=1.0, **base)
        moe_s, moe_loss, moe_hlo, moe_mlp = one_leg(moe_cfg)
    finally:
        set_mesh(None)
    ratio = moe_s / dense_s
    a2a = "all-to-all" in moe_hlo
    return {"value": round(ratio, 4), "unit": "moe_step_time_ratio",
            "mfu": None, "vs_baseline": None,
            "dense_step_ms": round(dense_s * 1e3, 2),
            "moe_step_ms": round(moe_s * 1e3, 2),
            "mlp_params_ratio": round(moe_mlp / dense_mlp, 2),
            "all_to_all_in_hlo": a2a,
            "dense_loss": round(dense_loss, 4),
            "moe_loss": round(moe_loss, 4),
            "loss_finite": bool(np.isfinite(moe_loss)),
            "holds_4x_at_1p5x": bool(moe_mlp / dense_mlp >= 4.0
                                     and ratio <= 1.5 and a2a),
            "baseline": "the FLOPs-matched dense leg (mlp_ratio=4) on "
                        "the same dp=2 x model=4 mesh — value is "
                        "moe_step/dense_step; the MoE leg carries "
                        "mlp_params_ratio x the MLP parameters",
            "note": "E=8 top-2 experts of mlp_ratio=2, capacity factor "
                    "1.0 (exact FLOPs match: zero padding), experts "
                    "sharded over \"model\" (ep=4); moe_loss folds the "
                    "aux+z router losses (finiteness pinned by "
                    "loss_finite)"}


def bench_overlap_zero2(on_accel):
    """ISSUE 17: MEASURED grad-collective overlap under ZeRO-2
    (FLAGS_overlap_zero2: the in-backward collective is a
    reduce-scatter, not a pmean) on the dp=2 x sharding=4 mesh, and the
    measured hidden_comm_frac fed back into the fleet.auto cost model —
    the row records both the measurement and how it moves the planner
    score vs the assumed-0.5 default."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.auto.cost_model import ModelStats
    from paddle_tpu.distributed.fleet.auto.planner import plan
    from paddle_tpu.models import gpt_init, gpt_loss, gpt_tiny
    from paddle_tpu.parallel.mesh import create_mesh, set_mesh
    from paddle_tpu.parallel.train_step import DistributedTrainStep, P

    if len(jax.devices()) < 8:
        return {"value": None, "unit": "hidden_comm_frac",
                "note": "skipped: needs 8 devices (dp=2 x sharding=4)"}
    rng = np.random.default_rng(0)
    paddle.set_flags({"FLAGS_overlap_grads": 1, "FLAGS_overlap_zero2": 1})
    try:
        create_mesh(dp=2, sharding=4, pp=1, mp=1)
        cfg = gpt_tiny(seq_len=64, n_layers=2, dtype=jnp.float32)
        params = gpt_init(cfg, seed=0)
        specs = jax.tree_util.tree_map(lambda _: P(), params)
        st = DistributedTrainStep(
            lambda p, b: gpt_loss(cfg, p, b), params, specs,
            optimizer="adamw", lr=1e-4, zero=2)
        batch = (jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)),
                             jnp.int32),
                 jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)),
                             jnp.int32))
        m = st.measure_overlap(batch, reps=3)
        hf = m.get("hidden_frac")
        rs2_active = bool(getattr(st, "_overlap_zero2", False))
    finally:
        set_mesh(None)
        paddle.set_flags({"FLAGS_overlap_grads": 0,
                          "FLAGS_overlap_zero2": 0})

    # feed the measurement into the planner: same model/topology scored
    # with the assumed 0.5 overlap vs the measured fraction
    stats = ModelStats.from_params(params, layers=cfg.n_layers,
                                   hidden=cfg.hidden, seq_len=64)
    p_assumed = plan(stats=stats, global_batch=64, n_devices=8,
                     constraints={"pp": 1, "mp": 1})
    p_meas = plan(stats=stats, global_batch=64, n_devices=8,
                  constraints={"pp": 1, "mp": 1},
                  hidden_comm_frac=hf)
    return {"value": None if hf is None else round(hf, 4),
            "unit": "hidden_comm_frac", "mfu": None,
            "vs_baseline": None,
            "step_ms": round(m["step_ms"], 3),
            "compute_ms": round(m["compute_ms"], 3),
            "comm_ms": round(m["comm_ms"], 3),
            "zero2_reduce_scatter": rs2_active,
            "plan_assumed": p_assumed.chosen.describe(),
            "plan_measured": p_meas.chosen.describe(),
            "plan_score_ratio": round(
                p_meas.chosen.score / max(p_assumed.chosen.score, 1e-12),
                4),
            "note": ("measured on the real ICI mesh" if on_accel else
                     "8-device CPU host mesh: collectives are memcpys, "
                     "so hidden_frac trends ~1.0 — the MEASUREMENT "
                     "machinery is what this row exercises; plan_* show "
                     "the measured fraction changing the cost-model "
                     "score vs the assumed 0.5")}


def bench_ring_attention(on_accel):
    """Long-context flagship: ring+flash attention (context parallelism
    whose per-hop block compute is the Pallas flash kernel,
    parallel/ring_flash.py) at seq 2048 on BERT-base shapes. One chip:
    ring degree 1, where the ring degenerates to exactly one flash block
    — the measured number IS the per-hop kernel throughput a multi-chip
    ring runs between ppermutes. The ring schedule itself (hop masking,
    lse merge, hand-written ring backward with dK/dV riding home) is
    pinned against full attention on the 8-device virtual mesh
    (tests/test_ring_moe.py TestRingFlash) and by dryrun_multichip."""
    from paddle_tpu.models import bert_base_config
    from paddle_tpu.parallel.mesh import create_mesh, set_mesh

    if not on_accel:
        return None
    try:
        create_mesh(dp=1, sharding=1, pp=1, mp=1)
        cfg = bert_base_config(remat=False, seq_len=2048, scan_unroll=1,
                               ring_attention=True)
        batch = 8
        dt, n = _device_step_seconds(cfg, batch, K=6, loss_chunk=256)
        sps = batch / dt
        return {"sps": round(sps, 2),
                "mfu": round(_mfu(n, 2048, sps), 4),
                "note": "ring+flash path (Pallas kernel per hop), ring "
                        "degree 1 on one chip = the per-hop kernel "
                        "throughput; multi-chip ring schedule pinned on "
                        "the virtual mesh and in dryrun_multichip; r5: "
                        "jnp blockwise 0.12 MFU -> flash-block design"}
    finally:
        set_mesh(None)


# -- eager-TrainStep configs (dispatch included: the eager user's view) ----

def _eager_and_device_sps(model, loss_fn, opt, batch_tensors, batch,
                          on_accel, K=10, eager_iters=15, eager_runs=1):
    """Measure BOTH views of a TrainStep config: per-call eager dispatch
    (what an eager user pays) and K steps inside one jit (pure device
    time — the steady-state number the A100 DeepLearningExamples
    baselines report). ``eager_runs`` repeats the eager measurement for a
    median + variance band. Returns (eager_sps_runs: list, device_sps)."""
    import functools as _ft

    import jax

    from paddle_tpu.jit import TrainStep

    step = TrainStep(model, loss_fn, opt)
    loss = None
    for _ in range(3):
        loss = step(*batch_tensors)
    float(loss._data)
    n = eager_iters if on_accel else 3
    eager_runs_sps = []
    for _ in range(max(1, eager_runs)):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step(*batch_tensors)
        float(loss._data)
        eager_runs_sps.append(batch / ((time.perf_counter() - t0) / n))

    impl = step._step_impl
    lr = float(opt.get_lr())
    arr_batch = tuple(t._data for t in batch_tensors)
    params = {k: p._data for k, p in model.named_parameters()}
    slots = dict(step._slot_values)
    buffers = {k: b._data for k, b in model.named_buffers()
               if b is not None}

    @_ft.partial(jax.jit, donate_argnums=(0, 1, 2))
    def k_steps(params, slots, buffers):
        def body(_, c):
            p, s, b = c
            np_, ns, nb, _ = impl(p, s, b, lr, arr_batch)
            return (np_, ns, nb)

        return jax.lax.fori_loop(0, K if on_accel else 2, body,
                                 (params, slots, buffers))

    out = k_steps(params, slots, buffers)
    jax.block_until_ready(out[0])
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = k_steps(*out)
        jax.block_until_ready(out[0])
        best = min(best, (time.perf_counter() - t0) / (K if on_accel else 2))
    return eager_runs_sps, batch / best


def _eager_tape_sps(model, opt, batch_tensors, batch, iters):
    """TRUE eager training: per-op apply_op dispatch + tape backward +
    optimizer step — the surface the grad-jit cache (framework/core.py
    ``_grad_jit_cache``) accelerates. Distinct from the TrainStep figure
    (one fused jit per step): here every op of forward AND backward is an
    individual dispatch, amortized only by the (fn, attrs, avals)-keyed
    jitted-VJP cache. Returns (sps, grad_jit counter deltas)."""
    import paddle_tpu as paddle
    from paddle_tpu import monitor

    images, labels = batch_tensors

    def step():
        loss = paddle.nn.functional.cross_entropy(model(images), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(3):
        loss = step()
    float(loss._data)
    marks = {n: monitor.stat_get(n) for n in
             ("grad_jit_hit", "grad_jit_miss", "grad_jit_compile")}
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step()
    float(loss._data)
    sps = batch * iters / (time.perf_counter() - t0)
    return sps, {n: monitor.stat_get(n) - m for n, m in marks.items()}


def bench_dlrm_ctr(on_accel):
    """Recommender config (ISSUE 16): DLRM CTR training with the table
    row-sharded over the mesh's "model" axis (paddle_tpu.sparse).

    Measures steady-state examples/s through SparseTrainStep — the
    all-to-all sharded lookup forward, unique+segment_sum SelectedRows
    backward, row-wise lazy Adam — with each batch round-tripped
    through the shm-ring slot encoding (io/shm_ring: the ragged
    multi-hot lists ride the offsets+values descriptor), so the
    transport the DataLoader workers use is on the measured path.
    Reports table bytes/device sharded vs replicated: row-sharding is
    THE point of the subsystem (an 8-shard table costs 0.125x the
    replicated HBM)."""
    import functools as _ft

    import jax as _jax
    from paddle_tpu.io.shm_ring import _decode, encode_into
    from paddle_tpu.models import (dlrm_init, dlrm_loss_from_emb,
                                   dlrm_tiny, synthetic_ctr_batches)
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.sparse import SparseTrainStep

    cfg = dlrm_tiny(n_dense=13, n_slots=26,
                    table_rows=2_000_000 if on_accel else 100_000,
                    table_dim=32 if on_accel else 16,
                    mlp_hidden=128 if on_accel else 32)
    batch = 4096 if on_accel else 512
    steps = 20 if on_accel else 8
    mesh = create_mesh(dp=1, mp=len(_jax.devices()))
    n_shards = int(mesh.shape["model"])

    params = dlrm_init(cfg, seed=0)
    step = SparseTrainStep(
        _ft.partial(dlrm_loss_from_emb, cfg), params["dense"],
        {"table": params["table"]},
        ids_fn=lambda b: {"table": b["slots"]}, mesh=mesh, lr=1e-3)

    # batches pre-generated, then shipped through a real shm slot per
    # step (worker-less: the encode/copy-out cost is the transport cost)
    batches = list(synthetic_ctr_batches(cfg, batch, steps + 2, seed=1,
                                         ragged=True))
    slot = bytearray(max(64 << 20, 2 * batch * (
        cfg.n_dense * 4 + cfg.n_slots * 4 + 8) + (1 << 20)))

    def ship(b):
        skel = encode_into(b, memoryview(slot), len(slot))
        got = _decode(skel, memoryview(slot)) if skel is not None else b
        got.pop("multi_hot", None)  # ragged ride-along, not model input
        return got

    float(step(ship(batches[0])))          # warmup / compile
    float(step(ship(batches[1])))
    t0 = time.perf_counter()
    losses = [float(step(ship(b))) for b in batches[2:]]
    dt = time.perf_counter() - t0
    sps = steps * batch / dt

    table_bytes = cfg.table_rows * cfg.table_dim * 4
    sharded = table_bytes // n_shards
    return {
        "sps": round(sps, 2),
        "unit": "examples/sec",
        "arch": f"dlrm slots={cfg.n_slots} rows={cfg.table_rows} "
                f"dim={cfg.table_dim} batch={batch}",
        "loss_first_last": [round(losses[0], 4), round(losses[-1], 4)],
        "table_bytes_per_device_replicated": table_bytes,
        "table_bytes_per_device_sharded": sharded,
        "sharded_over_replicated": round(sharded / table_bytes, 4),
        "shards": n_shards,
        "note": "SparseTrainStep over the row-sharded table: all-to-all "
                "exchange lookup, unique+segment_sum SelectedRows grads, "
                "row-wise lazy Adam; each batch round-trips a shm-ring "
                "slot (ragged multi-hot via offsets+values descriptor)"}


def bench_lenet(on_accel):
    """BASELINE config 1: MNIST LeNet train step (synthetic data).

    Returns (eager, device_sps, tape): the eager figure includes
    per-step dispatch; the device figure is K steps in one jit, dispatch
    excluded; tape is the
    per-op eager path through the grad-jit cache (steady state must show
    zero grad_jit_compile — a nonzero delta is a recompile storm)."""
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())

    def loss_fn(run_model, images, labels):
        out = run_model(images)
        return paddle.nn.functional.cross_entropy(out, labels)

    batch = 256 if on_accel else 32
    rng = np.random.default_rng(0)
    images = paddle.to_tensor(
        rng.normal(size=(batch, 1, 28, 28)).astype("float32"))
    labels = paddle.to_tensor(rng.integers(0, 10, (batch,)).astype("int64"))
    tape_sps, tape_stats = _eager_tape_sps(model, opt, (images, labels),
                                           batch, 10 if on_accel else 3)
    # >=5 eager runs for a median + band
    runs, device_sps = _eager_and_device_sps(
        model, loss_fn, opt, (images, labels), batch, on_accel, K=50,
        eager_iters=30, eager_runs=5 if on_accel else 2)
    eager = {
        "median_sps": round(float(np.median(runs)), 2),
        "band_sps": [round(min(runs), 2), round(max(runs), 2)],
        "runs": len(runs),
    }
    return eager, device_sps, {"sps": round(tape_sps, 2),
                               "grad_jit": tape_stats}


def bench_resnet50(on_accel):
    """BASELINE config 2: ResNet-50, AMP bf16 (synthetic ImageNet shapes).

    Returns (eager_sps, device_sps); device = K steps in one jit, the
    apples-to-apples number against the A100 DeepLearningExamples
    steady-state throughput."""
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    # r5 sweep (tools/exp_resnet.py): b256 + O2 (bf16 params, fp32 norms)
    # is the best of {b128,b256,b384} x {O1,O2,full-bf16}: 2203 vs 2141
    # img/s; full-bf16 BN bought nothing (XLA already fuses the BN
    # elementwise into conv epilogues)
    if on_accel:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(run_model, images, labels):
        with paddle.amp.auto_cast(enable=True, level="O2"):
            out = run_model(images)
        return paddle.nn.functional.cross_entropy(out, labels)

    batch = 256 if on_accel else 4
    size = 224 if on_accel else 64
    rng = np.random.default_rng(0)
    images = paddle.to_tensor(
        rng.normal(size=(batch, 3, size, size)).astype("float32"))
    labels = paddle.to_tensor(rng.integers(0, 1000, (batch,)).astype("int64"))
    runs, device_sps = _eager_and_device_sps(
        model, loss_fn, opt, (images, labels), batch, on_accel, K=10,
        eager_iters=15)
    return float(np.median(runs)), device_sps


def main():
    # an 8-device virtual mesh for the auto-parallel config on CPU runs —
    # must land in XLA_FLAGS before jax initializes (TPU runs, where
    # JAX_PLATFORMS is unset, are untouched)
    if os.environ.get("JAX_PLATFORMS", "") == "cpu" and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")

    import jax

    from paddle_tpu.device import enable_compile_cache

    # persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR, else
    # .jax_cache in the checkout): the full-unroll configs compile for
    # minutes cold
    enable_compile_cache()

    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    # Time budget (BENCH_TIME_BUDGET seconds, default 45 min): cold
    # compiles cost minutes per config, so an unbounded run risks the
    # driver's timeout killing the process before the ONE json line
    # prints. The phases run most-important-first (headline BERT-512,
    # then the real-optimizer configs, then the heavyweight seq-2048 A/B)
    # and later phases are skipped with a note once 80% of the budget is
    # spent — partial-but-printed beats complete-but-killed.
    t_start = time.perf_counter()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 2700))

    def over_budget():
        return time.perf_counter() - t_start > 0.8 * budget

    def _release():
        # Drop compiled executables + free device buffers between configs:
        # measured cross-config interference (gpt_760m_adamw 10.5 -> 4.4
        # sps when run after the b8 full-unroll flash A/B in the same
        # process — HBM fragmentation); the on-disk compile cache makes
        # re-lowering cheap.
        import gc

        gc.collect()
        try:
            jax.clear_caches()
        except Exception:  # noqa: BLE001
            pass

    configs = {}
    # Derived per-config baselines (VERDICT r4 item 3 — every config
    # carries vs_baseline + provenance; method = BASELINE.md's BERT
    # derivation applied to each config's own public record):
    # - ResNet-50: NVIDIA DeepLearningExamples ResNet-50 v1.5 PyTorch AMP,
    #   DGX A100 8xA100 ~18.85k img/s => 2,356 per GPU — the SAME 8-GPU
    #   table convention the BERT derivation uses (75 = 600/8).
    #   Single-GPU-tuned runs reach ~2.5k (larger per-GPU batch); against
    #   that figure our number reads ~0.88x — both stated for honesty.
    # - LeNet: NO public A100 LeNet number exists (nobody benchmarks it);
    #   eager LeNet is DISPATCH-bound, so the baseline is derived from
    #   the public per-op overhead record instead: ~50us CUDA-launch +
    #   framework dispatch per op x ~60 ops per fwd+bwd+opt step ~= 3ms
    #   per eager step on any 2021-era framework => batch 256 ~= 85k
    #   img/s. The device-loop figure (dispatch excluded) is reported
    #   alongside.
    RESNET_A100_BASELINE = 2356.0
    LENET_A100_BASELINE = 85000.0

    # phase 1: the headline metric (BERT-base 512 A/B)
    bert_sps, mfu, flash_ab = bench_bert(
        on_accel, which=("xla_512", "flash_512"))
    if not flash_ab:
        # never emit an empty {} — record WHY the A/B has no rows
        # (r1-r5 artifacts carried a bare "flash_ab": {} on CPU runs)
        flash_ab = {"skipped": "cpu backend: the flash-vs-XLA A/B needs "
                               "an accelerator (smoke config only)"}
    _release()

    # phase 2: real-optimizer + model-family configs, importance order
    for name, fn in (("gpt_760m_adamw", bench_gpt_760m_adamw),
                     ("ernie_large_bf16", bench_ernie_large),
                     ("gpt_1p3b", bench_gpt_1p3b),
                     ("gpt_1p3b_auto", bench_gpt_1p3b_auto),
                     ("ring_attention", bench_ring_attention),
                     ("gpt_tiny_fused", bench_gpt_tiny_fused),
                     ("flash_s2048", bench_flash_s2048),
                     ("gpt_tiny_fp8", bench_gpt_tiny_fp8),
                     ("gpt_moe", bench_gpt_moe),
                     ("overlap_zero2", bench_overlap_zero2),
                     ("gpt_tiny_serving", bench_gpt_tiny_serving),
                     ("serving_spec", bench_serving_spec),
                     ("serving_load", bench_serving_load),
                     ("serving_chaos", bench_serving_chaos),
                     ("serving_fleet", bench_serving_fleet),
                     ("dlrm_ctr", bench_dlrm_ctr),
                     ("resilience", bench_resilience)):
        if over_budget():
            configs[name] = "skipped: time budget (BENCH_TIME_BUDGET)"
            continue
        try:
            r = fn(on_accel)
            if r is not None:
                configs[name] = r
        except Exception as e:  # noqa: BLE001
            configs[name] = f"error: {type(e).__name__}: {e}"
        _release()

    # phase 2b: vision configs (heavy resnet compile)
    if over_budget():
        configs["mnist_lenet"] = configs["resnet50_amp"] = \
            "skipped: time budget (BENCH_TIME_BUDGET)"
    else:
        try:
            lenet_eager, lenet_dev, lenet_tape = bench_lenet(on_accel)
            configs["mnist_lenet"] = {
                "sps": lenet_eager["median_sps"],
                "eager": lenet_eager,  # median/band/runs
                "device_sps": round(lenet_dev, 2),
                "eager_tape": lenet_tape,
                # vs_baseline scores the plain eager median (the derived
                # baseline models per-op eager dispatch); the device-loop
                # ratio is published alongside
                "vs_baseline": round(
                    lenet_eager["median_sps"] / LENET_A100_BASELINE, 4),
                "vs_baseline_device": round(lenet_dev / LENET_A100_BASELINE, 4),
                "baseline": "derived: eager dispatch model ~50us/op x ~60 "
                            "ops => ~3ms/step, batch 256 => ~85k img/s on "
                            "A100-class eager frameworks (no published LeNet "
                            "benchmark exists)",
                "note": "eager = median + [min,max] band over >=5 runs of "
                        "the FLAGS_fast_step donated async TrainStep "
                        "(dispatch pipelined, loss read once per run), "
                        "which is what vs_baseline scores; device_sps is "
                        "50 steps in one "
                        "jit; eager_tape is the per-op tape path through "
                        "the grad-jit cache (steady state: "
                        "grad_jit_compile delta 0)"}
        except Exception as e:  # noqa: BLE001 — auxiliary config must not kill the bench
            configs["mnist_lenet"] = f"error: {type(e).__name__}: {e}"
        try:
            rn_eager, rn_dev = bench_resnet50(on_accel)
            configs["resnet50_amp"] = {
                "sps": round(rn_dev, 2),
                "eager_sps": round(rn_eager, 2),
                "vs_baseline": round(rn_dev / RESNET_A100_BASELINE, 4),
                "baseline": "derived: DeepLearningExamples ResNet-50 v1.5 "
                            "PyTorch AMP, DGX-A100 8-GPU ~18.85k img/s => "
                            "2,356/GPU (same 8-GPU-table convention as the "
                            "BERT derivation); single-GPU-tuned runs ~2.5k "
                            "=> ~0.88x against that figure"}
        except Exception as e:  # noqa: BLE001
            configs["resnet50_amp"] = f"error: {type(e).__name__}: {e}"

        _release()

    # phase 3 (heaviest compiles + largest HBM footprint, so LAST): the
    # seq-2048 flash-vs-XLA A/B
    if on_accel and not over_budget():
        try:
            bench_bert(on_accel, which=("xla_2048", "flash_2048"),
                       ab=flash_ab)
        except Exception as e:  # noqa: BLE001
            flash_ab["seq_2048"] = f"error: {type(e).__name__}: {e}"
        _release()
    elif on_accel:
        flash_ab["seq_2048"] = "skipped: time budget (BENCH_TIME_BUDGET)"

    out = {
        "metric": "bert_base_train_samples_per_sec_per_chip"
                  if on_accel else "bert_tiny_cpu_smoke_samples_per_sec",
        "value": round(bert_sps, 2),
        "unit": "samples/sec",
        "vs_baseline": round(bert_sps / A100_BASELINE_SAMPLES_PER_SEC, 4),
        "baseline": BASELINE_PROVENANCE,
        "mfu": round(mfu, 4) if mfu else None,
        "peak_flops_note": "MFU = 6NT / 197e12 (v5e bf16 peak; r2 used the "
                           "394e12 int8 figure, understating MFU 2x)",
        "flash_ab": flash_ab,
        "configs": configs,
    }
    # every completed config carries value + mfu keys in the artifact
    for cfg_ in configs.values():
        if isinstance(cfg_, dict):
            cfg_.setdefault("value", cfg_.get("sps"))
            cfg_.setdefault("mfu", None)

    # Truncation-proofing (r5 lost gpt_760m_adamw this way): the driver
    # keeps only the TAIL of stdout, so a single huge json line loses its
    # FRONT keys. Full results go to BENCH_OUT.json on disk; stdout ends
    # with a compact digest — headline + per-config value/mfu/vs_baseline
    # only, a few hundred bytes that always survive the tail capture.
    out_path = os.environ.get(
        "BENCH_OUT", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "BENCH_OUT.json"))
    try:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
    except OSError as e:
        out["bench_out_error"] = repr(e)

    def _digest(c):
        if not isinstance(c, dict):
            return str(c)[:60]
        return {k: c[k] for k in ("value", "mfu", "vs_baseline",
                                  "device_sps")
                if c.get(k) is not None}

    compact = {
        "metric": out["metric"], "value": out["value"], "unit": out["unit"],
        "vs_baseline": out["vs_baseline"], "mfu": out["mfu"],
        "configs": {k: _digest(v) for k, v in configs.items()},
        "flash_ab": {k: (v.get("sps") if isinstance(v, dict) else str(v)[:40])
                     for k, v in flash_ab.items()},
        "detail": "BENCH_OUT.json",
    }
    print(json.dumps(compact))


if __name__ == "__main__":
    main()
