"""Find a cell's files by the names in BENCHMARK.json.

A cell is ``workloads[i]`` of BENCHMARK.json: a configuration under a
traffic mix. Everything that belongs to one of them is a file found by
its name, so a later PR adds a cell, a configuration or a metric by
adding files:

    benchmarks/configs/<config>.json      sizes, builder call, source
    benchmarks/traffic/<traffic>.json     the mix's parameters
    benchmarks/workloads/<cell>.json      driver kind, the system's
                                          parameters, rate, limits
    benchmarks/metrics/<metric>.json      a per-layer metric's reader
    benchmarks/readers/<metric>.py        (optional) a reader of its own
"""
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _load(path):
    with open(path) as f:
        return json.load(f)


class Spec:
    """BENCHMARK.json plus the files it names, for one cell."""

    def __init__(self, workload, benchmark_file=None, overlay=None):
        """``benchmark_file`` and ``overlay`` (a directory searched
        before ``benchmarks/``) are for the rehearsal tests alone."""
        self.root = REPO_ROOT
        self.bench_dir = BENCH_DIR
        self.overlay = overlay
        self.benchmark = _load(benchmark_file or os.path.join(
            self.root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in cells:
            raise SystemExit(f"run.py: no workload {workload!r} in "
                             f"BENCHMARK.json (have {sorted(cells)})")
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        self.config = _load(self.path("configs", self.cell["config"]))
        self.traffic = _load(self.path("traffic", self.cell["traffic"]))
        self.workload = _load(self.path("workloads", workload))
        self.peaks = _load(os.path.join(self.bench_dir, "lib", "peaks.json"))

    def path(self, kind, name, ext=".json"):
        if self.overlay:
            p = os.path.join(self.overlay, kind, name + ext)
            if os.path.exists(p):
                return p
        return os.path.join(self.bench_dir, kind, name + ext)

    def reports(self, metric):
        """Does this cell report ``metric`` (an entry of end_to_end or
        per_layer)? With no ``workloads`` key: every cell that reports
        the metric it moves (per-layer) or every cell (end-to-end)."""
        if "workloads" in metric:
            return self.name in metric["workloads"]
        moves = metric.get("moves")
        if moves is None:
            return True
        e2e = {m["name"]: m for m in self.benchmark["end_to_end"]}
        return self.reports(e2e[moves])

    def end_to_end(self):
        return [m for m in self.benchmark["end_to_end"] if self.reports(m)]

    def per_layer(self):
        return [m for m in self.benchmark["per_layer"] if self.reports(m)]

    def metric_file(self, name):
        return _load(self.path("metrics", name))

    def peak(self, device_kind):
        table = self.peaks["devices"]
        if device_kind not in table:
            raise SystemExit(
                f"run.py: device kind {device_kind!r} is not in "
                f"benchmarks/lib/peaks.json ({sorted(table)}); add it with "
                "its published peaks — there is no default")
        return table[device_kind]
