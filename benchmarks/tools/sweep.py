#!/usr/bin/env python3
"""Find a serve cell's knee once, on the chip: one engine, several
arrival rates in turn, each for ``--seconds``; between rates the engine
drains. For each rate it prints whether the backlog grew: requests in
the system at a quarter, half, three quarters and the end of the
window, first-token time of the first and the second half, and the
tokens per second completed. The knee is the highest rate at which the
backlog does not grow; the cell's ``rate_rps`` is four fifths of it,
written into its workload file by hand.

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 2,3,4,5,6 \
        [--seconds 25] [--seed 7]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()

    import jax

    from benchmarks.lib import (harness, program, serve, spec as spec_mod,
                                traffic)

    if jax.devices()[0].platform != "tpu":
        sys.stderr.write("sweep.py: needs a TPU\n")
        return 2
    program.enable_compile_cache()
    spec = spec_mod.Spec(a.workload)
    sizes, mix, wl = spec.config["sizes"], spec.traffic, spec.workload
    cfg = program.build_config(spec.config)
    eng = program.build_engine(cfg, spec.family.make_params(sizes, a.seed),
                               wl["engine"], a.seed)
    try:
        serve.warm_up(eng, mix, wl["engine"], sizes, a.seed)
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            sched = traffic.open_loop(mix, rate, a.seconds, a.seed + i,
                                      sizes["vocab_size"])
            clients, t0, used = serve.drive(eng, sched, a.seconds, None)
            t_close = t0 + a.seconds

            def in_system(t):
                return sum(1 for c in clients if t0 + c.spec["due"] <= t
                           and (not c.arrivals or len(c.arrivals)
                                < c.spec["max_new_tokens"]
                                or c.arrivals[-1] > t))

            ttft, tpot = serve.latencies(clients, t0)
            half = len(ttft) // 2
            toks = sum(1 for c in clients for t in c.arrivals
                       if t <= t_close)
            last = max((c.arrivals[-1] for c in clients if c.arrivals),
                       default=t_close)
            print("SWEEP", json.dumps({
                "rate": rate, "requests": len(clients),
                "failed": sum(1 for c in clients if not c.ok),
                "in_system": [in_system(t0 + a.seconds * f)
                              for f in (0.25, 0.5, 0.75, 1.0)],
                "ttft_p50_halves": [harness.median(ttft[:half]),
                                    harness.median(ttft[half:])],
                "ttft_p95": harness.percentile(ttft, 95),
                "tpot_p50": harness.median(tpot),
                "tpot_p95": harness.percentile(tpot, 95),
                "tokens_per_s": toks / a.seconds,
                "drain_s": last - t_close,
                "blocks_used_peak": used}), flush=True)
            time.sleep(1.0)
    finally:
        eng.shutdown(drain=False, timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
