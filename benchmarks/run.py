#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It makes its inputs and weights from --seed,
warms up every shape the cell's traffic uses (counted as set-up: the
clock of ``setup_s`` starts when jax has found the devices, and every
run prints what Python, jax and the TPU runtime took before that),
measures for --seconds, checks what the timed path produced against the
plain reference, and prints one JSON object as its last line. With no
TPU, fewer chips than the cell asks for, or a device kind that is not in
``benchmarks/lib/peaks.json``, it exits non-zero and prints no result.
"""
import argparse
import os
import sys
import time

T_PROCESS = time.perf_counter()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

DRIVERS = {"train": "train", "serve_open_loop": "serve"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, rehearsal=None):
    """``rehearsal`` is for the tests under ``benchmarks/tests`` alone:
    {"platform", "peak", "benchmark_file", "overlay"} lets the command
    run at a tiny size with no chip. The command line cannot set it, so
    no measurement path runs off the chip."""
    import importlib

    from benchmarks.lib import harness, readers, spec as spec_mod, xplane

    args = parse(argv)
    reh = rehearsal or {}
    spec = spec_mod.Spec(args.workload,
                         benchmark_file=reh.get("benchmark_file"),
                         overlay=reh.get("overlay"))

    def stage(what):
        """A line that says how far into set-up the run is, and how much
        CPU time the process has used: a stage that grows in wall time
        alone was waiting, not working."""
        harness.say(f"set-up: {what} at "
                    f"{time.perf_counter() - T_PROCESS:.1f} s "
                    f"(cpu {time.process_time():.1f} s)")

    stage("benchmark's files read")
    import jax

    stage("jax imported")
    devices = jax.devices()
    t_devices = time.perf_counter()
    want = reh.get("platform", "tpu")
    if devices[0].platform != want:
        sys.stderr.write(
            f"run.py: needs a TPU, but jax found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}); nothing "
            "was run\n")
        return 2
    if len(devices) < spec.chips:
        sys.stderr.write(f"run.py: cell {spec.name} needs {spec.chips} "
                         f"chip(s), jax found {len(devices)}\n")
        return 2
    peak = reh.get("peak") or spec.peak(devices[0].device_kind)

    from benchmarks.lib import program

    cache = program.enable_compile_cache()
    out_dir = os.path.join(spec.root, ".bench_out")
    os.makedirs(out_dir, exist_ok=True)
    harness.say(f"cell {spec.name} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}; device {devices[0].device_kind} x"
                f"{len(devices)}; jax {jax.__version__}; compile cache "
                f"{cache}")
    marks = {}
    stage("devices found")
    harness.say(f"set-up: Python, jax and the TPU runtime took "
                f"{t_devices - T_PROCESS:.3f} s to start; setup_s counts "
                "from here")
    env = {"peak": peak, "out_dir": out_dir, "stage": stage,
           "compiles": harness.CompileCounter(),
           "setup_done": lambda: marks.setdefault(
               "setup_s", time.perf_counter() - t_devices)}
    driver = importlib.import_module(
        "benchmarks.lib." + DRIVERS[spec.workload["driver"]])
    got = driver.run(spec, args, env)

    compared = got["compared"]
    correct = all(c["ok"] for c in compared.values())
    device = got["device"]
    result = {"correct": correct, "attempted": got["attempted"],
              "failed": got["failed"], "metrics": {}, "device": device}
    if not args.trace:
        values = dict(got["e2e"], setup_s=marks["setup_s"])
        for m in spec.end_to_end():
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        ctx = got["ctx"]
        for m in spec.per_layer():
            v = readers.read_metric(ctx, m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        device["busy_s"] = xplane.busy_seconds(ctx.trace)
        device["window_s"] = ctx.trace_window_s
        result["breakdown"] = {
            "device_ops": xplane.device_ops(ctx.trace),
            "idle_gaps": xplane.idle_by_span(ctx.trace, ctx.host_spans)}
        harness.say("device time by program",
                    xplane.module_seconds(ctx.trace))
        harness.say("end-to-end in this traced run (not the metric)",
                    got["e2e"], "setup_s", marks["setup_s"])
    harness.print_result(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
