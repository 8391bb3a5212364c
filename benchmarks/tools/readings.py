#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, at a cell's own
size, several seeds in one process (set-up is long, a reading is short).

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --mode program|control|half_batch [--seconds 12] [--out file]

``program``: the cell as committed; its compared numbers are the lower
readings. ``control``: the program's own path in the nearest precision
below the configuration's (train: ``fp8=True`` MLP matmuls; serve:
``int8_weights=True`` decode), put in the program's place: its numbers
are the upper readings. ``half_batch`` (train): the reference with half
of the batch left out and the mean taken over the rest, put in the
program's place. Serve modes also print, per seed, the gap a float8
reference pass would give (``ref_fp8_gap``), read on the same prompts.

One JSON line per seed on standard output and in ``--out``. Needs a
TPU, like run.py; the benchmark's own runs never call this."""
import argparse
import dataclasses
import gc
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def train_reading(spec, seed, mode):
    from benchmarks.lib import check, program, traffic, train

    sizes, mix, wl = spec.config["sizes"], spec.traffic, spec.workload
    hyper = dict(wl["step"]["opt"], lr=wl["step"]["lr"])
    ring = traffic.train_batches(mix, seed, sizes["vocab_size"])
    rows = wl["check"]["reference_rows"]
    if mode == "half_batch":
        got = train.reference_readings(spec, seed, ring, hyper, rows,
                                       drop_half=True)
    else:
        cfg = program.build_config(spec.config)
        if mode == "control":
            cfg = dataclasses.replace(cfg, fp8=True)
        step = program.build_train_step(
            cfg, spec.family.make_params(sizes, seed), wl["step"],
            spec.family)
        got = train.first_steps(step, ring, spec, seed, hyper)
        del step
        gc.collect()
    ref = train.reference_readings(spec, seed, ring, hyper, rows)
    compared, notes = check.train(got, ref, wl["check"]["limits"])
    return {"values": {k: v["value"] for k, v in compared.items()},
            "notes": notes, "losses": got["losses"],
            "ref_losses": ref["losses"]}


def serve_reading(spec, seed, mode, seconds, env):
    from benchmarks.lib import serve

    if mode == "control":
        spec.workload["engine"]["int8_weights"] = True
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    got = serve.run(spec, args, env)
    sample = serve.pick_sample(got["clients"], seed,
                               spec.workload["check"]["sample_requests"])
    _, fp8_gap, _ = serve.compare_served(
        sample, spec, seed,
        serve.pad_length(spec.traffic, spec.config["sizes"]["seq_len"]),
        lowp="fp8")
    return {"values": {k: v["value"] for k, v in got["compared"].items()},
            "ref_fp8_gap": fp8_gap, "e2e": got["e2e"],
            "attempted": got["attempted"], "failed": got["failed"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", default="program",
                    choices=("program", "control", "half_batch"))
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out")
    a = ap.parse_args()

    import jax

    from benchmarks.lib import harness, program, spec as spec_mod

    if jax.devices()[0].platform != "tpu":
        sys.stderr.write("readings.py: needs a TPU\n")
        return 2
    program.enable_compile_cache()
    spec = spec_mod.Spec(a.workload)
    env = {"peak": spec.peak(jax.devices()[0].device_kind),
           "out_dir": os.path.join(spec.root, ".bench_out"),
           "compiles": harness.CompileCounter(), "setup_done": lambda: None,
           "stage": lambda what: None}
    for seed in (int(s) for s in a.seeds.split(",")):
        if spec.workload["driver"] == "train":
            r = train_reading(spec, seed, a.mode)
        else:
            r = serve_reading(spec, seed, a.mode, a.seconds, env)
        line = json.dumps(dict(r, workload=a.workload, seed=seed,
                               mode=a.mode))
        print("READING", line, flush=True)
        if a.out:
            with open(a.out, "a") as out:
                out.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
