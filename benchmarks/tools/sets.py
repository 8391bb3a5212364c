#!/usr/bin/env python3
"""Sets of runs of one cell, in one call on the chip, as the contract
asks for a bound: the same seeds in every set, each run a new process
of ``benchmarks/run.py``. Writes one JSON line a run (its result line,
its ``set-up:`` stages, and what the machine said just before it:
load, free memory, CPU time stolen) and prints, for each metric, each
set's median and spread (interquartile distance over the median, by
``statistics.quantiles(n=4)``) and how far a later set's median lies
from the first's. This process never touches jax: the chip is the run's.

    python3 benchmarks/tools/sets.py --workload <cell> --seeds 11,12,13 \
        [--sets 2] [--seconds 50] [--trace 0] [--out chiprun_out/x.jsonl]
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def machine():
    """A few readings of the host, each left out where it cannot be read."""
    got = {}
    try:
        with open("/proc/loadavg") as f:
            got["load1"] = float(f.read().split()[0])
        with open("/proc/meminfo") as f:
            for line in f:
                k, v = line.split(":")
                if k in ("MemAvailable", "Cached"):
                    got[k + "_GiB"] = round(int(v.split()[0]) / 2 ** 20, 2)
        with open("/proc/stat") as f:
            cpu = f.readline().split()
            tck = os.sysconf("SC_CLK_TCK")
            got["cpu_busy_s"] = round(
                sum(int(x) for x in cpu[1:4] + cpu[6:9]) / tck, 1)
            got["cpu_steal_s"] = round(int(cpu[8]) / tck, 2)
        with open("/proc/pressure/cpu") as f:
            got["cpu_pressure_avg10"] = float(
                f.readline().split()[1].split("=")[1])
    except (OSError, IndexError, ValueError):
        pass
    return got


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    out = a.out or os.path.join(ROOT, "chiprun_out",
                                f"sets_{a.workload}.jsonl")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    t_call = time.time()
    per_set = {}
    with open(out, "a") as log:
        for k in range(1, a.sets + 1):
            for seed in seeds:
                before = machine()
                t = time.time()
                p = subprocess.run(
                    [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
                     "--workload", a.workload, "--seed", str(seed),
                     "--seconds", str(a.seconds), "--trace", str(a.trace)],
                    cwd=ROOT, capture_output=True, text=True)
                lines = p.stdout.strip().splitlines()
                try:
                    line = json.loads(lines[-1])
                except (IndexError, ValueError):
                    line = None
                rec = {"set": k, "seed": seed, "rc": p.returncode,
                       "started_s": round(t - t_call, 1),
                       "took_s": round(time.time() - t, 1),
                       "machine": before, "line": line,
                       "stages": [x for x in lines if "set-up:" in x
                                  or "warm-up:" in x or "host:" in x]}
                if line is None or not line.get("correct"):
                    rec["stdout_tail"] = p.stdout[-3000:]
                    rec["stderr_tail"] = p.stderr[-3000:]
                log.write(json.dumps(rec) + "\n")
                log.flush()
                print(json.dumps({x: rec[x] for x in rec if x != "line"}),
                      flush=True)
                if line is not None:
                    print("  ", {m: v["value"] for m, v
                                 in line["metrics"].items()},
                          "correct", line["correct"], flush=True)
                    for m, v in line["metrics"].items():
                        per_set.setdefault(m, {}).setdefault(
                            k, []).append(v["value"])
    for m, sets in per_set.items():
        first = None
        for k, vals in sorted(sets.items()):
            med = statistics.median(vals)
            first = med if first is None else first
            sp = spread(vals) if len(vals) >= 2 else float("nan")
            print(f"{m} set {k}: median {med!r} spread {sp:.4%} "
                  f"median against set 1 "
                  f"{med / first - 1 if first else float('nan'):+.3%} "
                  f"values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
