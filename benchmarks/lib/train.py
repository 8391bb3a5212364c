"""Driver ``train``: the window drives ``DistributedTrainStep.__call__``
with a fresh host batch every step."""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, harness, program, traffic

PIPELINE = 2          # steps in flight before the host waits for a loss


def _leaf_norms_scaled(family, tree, scale):
    return {k: v * scale for k, v in family.leaf_norms(tree).items()}


@jax.jit
def _diff(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


def first_steps(step, ring, spec, seed, hyper, n=3):
    """Drive the step object through its first n steps, by the window's
    own call and feed, and take the readings ``correct`` compares."""
    family = spec.family
    losses, grad_norms = [], None
    for i in range(n):
        losses.append(float(step(ring[i % len(ring)])))
        if i == 0:
            # Adam's first moment after one step is (1 - beta1) * g
            grad_norms = _leaf_norms_scaled(
                family, step.opt_state["m"], 1.0 / (1.0 - hyper["beta1"]))
    start = family.make_params(spec.config["sizes"], seed)
    change = family.leaf_norms(_diff(step.params, start))
    del start
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def reference_readings(spec, seed, ring, hyper, rows, n=3, **kw):
    sizes = spec.config["sizes"]
    params0 = spec.family.make_params(sizes, seed)
    return spec.family.train_readings(
        params0, [ring[i % len(ring)] for i in range(n)],
        sizes, hyper, rows, **kw)


def run(spec, args, env):
    sizes, mix, wl = spec.config["sizes"], spec.traffic, spec.workload
    family = spec.family
    hyper = dict(wl["step"]["opt"], lr=wl["step"]["lr"])
    cfg = program.build_config(spec.config)
    ring = traffic.train_batches(mix, args.seed, sizes["vocab_size"])
    tokens_per_step = mix["batch"] * mix["seq"]

    step = program.build_train_step(
        cfg, family.make_params(sizes, args.seed), wl["step"], family)
    env["stage"]("weights made and step built")
    got = first_steps(step, ring, spec, args.seed, hyper)
    env["stage"]("first three steps done")
    harness.say("first losses", got["losses"])
    for i in range(3, 3 + PIPELINE + 1):          # the window's rhythm
        loss = step(ring[i % len(ring)])
    jax.block_until_ready(loss)
    n_done = 3 + PIPELINE + 1

    tw = harness.TraceWindow(env["out_dir"], wl["trace"]) \
        if args.trace else None
    stats0 = program.stats_snapshot()
    env["compiles"].mark()
    env["setup_done"]()

    dispatch_ms, inflight, n_steps = [], [], 0
    traced_steps = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= args.seconds:
            break
        todo = tw.due(now) if tw is not None else None
        if todo:
            jax.block_until_ready(inflight)
            tw.start() if todo == "start" else tw.stop()
        batch = ring[(n_done + n_steps) % len(ring)]
        d0 = time.perf_counter()
        if tw is not None and tw.running:
            with jax.profiler.TraceAnnotation("bench.train_step_dispatch"):
                loss = step(batch)
            traced_steps += 1
        else:
            loss = step(batch)
        dispatch_ms.append((time.perf_counter() - d0) * 1e3)
        n_steps += 1
        inflight.append(loss)
        if len(inflight) > PIPELINE:
            jax.block_until_ready(inflight.pop(0))
    jax.block_until_ready(inflight)
    last_loss = float(inflight[-1])
    elapsed = time.perf_counter() - t0
    if tw is not None:
        tw.stop()
    compiles = env["compiles"].since_mark()
    stats1 = program.stats_snapshot()
    device = harness.device_record(jax.devices()[:spec.chips])

    tokens_per_s = n_steps * tokens_per_step / elapsed
    harness.say(f"window: {n_steps} steps of {tokens_per_step} tokens in "
                f"{elapsed:.4f} s; last loss {last_loss:.4f}; "
                f"train_tokens_per_s from {n_steps} steps; {compiles} "
                "programs compiled inside it")
    e2e = {"train_tokens_per_s": tokens_per_s}

    # the program's state goes before the reference comes
    del step, inflight, loss
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(spec, args.seed, ring, hyper,
                             wl["check"]["reference_rows"])
    compared, notes = check.train(got, ref, wl["check"]["limits"])
    harness.say(f"reference took {time.perf_counter() - t_ref:.1f} s; "
                "losses", ref["losses"], "notes", notes)
    finite = all(np.isfinite(x) for x in got["losses"] + [last_loss])
    compared.update([harness.compare("non_finite_losses",
                                     0 if finite else 1, 0, "==")])

    ctx = None
    if args.trace:
        ctx = harness.trace_context(spec, env, tw, stats0, stats1, {
            "host_dispatch_ms": harness.median(dispatch_ms),
            "compiles_in_window": compiles,
            # the step's rate outside the traced sub-window: starting
            # and stopping the profiler stalls the host
            "model_flops_per_s":
                (n_steps - traced_steps) * tokens_per_step
                / (elapsed - (tw.t_exit - tw.t_enter))
                * family.train_flops_per_token(sizes, mix["seq"])})
        harness.say(f"traced {traced_steps} steps in {tw.window_s:.3f} s")
    return {"attempted": n_steps, "failed": 0, "e2e": e2e,
            "compared": compared, "device": device, "ctx": ctx}
