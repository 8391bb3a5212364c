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
    benchmarks/families/<family>/         what the harness knows of a
                                          model's insides

A configuration file names its ``family`` (there is no default), and
the package of that name gives, as ``FAMILY`` lists:

    make_params(sizes, seed)              the tree the program's builder
                                          takes, one jitted call
    served_gaps(params, prompt, served, sizes, pad_to, lowp=None)
    train_readings(params0, batches, sizes, hyper, rows, lowp=None,
                   drop_half=False)
    leaf_norms(tree)                      the plain reference's readings
    forward_flops_per_token(sizes, context, causal_mean=False)
    train_flops_per_token(sizes, seq)     the model's work, from shapes
    KERNEL_WORK                           {name: f(ctx, n_events) ->
                                          (flops, bytes) or None}: what a
                                          metric file's "work" names
    train_loss(cfg), param_specs(cfg)     for ``DistributedTrainStep``

What a family reads of ``sizes``, and how it blocks its reference to fit
the chip, is its own affair; the rest of ``benchmarks/lib`` reads of
them only ``vocab_size`` and ``seq_len``.
"""
import functools
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
FAMILY = ("make_params", "served_gaps", "train_readings", "leaf_norms",
          "forward_flops_per_token", "train_flops_per_token", "KERNEL_WORK",
          "train_loss", "param_specs")


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_family(name, overlay=None):
    """The package ``families/<name>/`` (``overlay``'s first), loaded
    once a process; refused where it lacks a name of ``FAMILY``."""
    dirs = [os.path.join(d, "families", name)
            for d in (overlay, BENCH_DIR) if d]
    init = next((os.path.join(d, "__init__.py") for d in dirs
                 if os.path.exists(os.path.join(d, "__init__.py"))), None)
    if init is None:
        raise SystemExit(f"run.py: no family {name!r}: none of {dirs} "
                         "holds an __init__.py")
    mod_name = "bench_family_" + name
    mod = sys.modules.get(mod_name)
    if mod is not None and mod.__file__ == init:
        return mod
    mod_spec = importlib.util.spec_from_file_location(
        mod_name, init, submodule_search_locations=[os.path.dirname(init)])
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_name] = mod          # the package's own imports need it
    try:
        mod_spec.loader.exec_module(mod)
        lacking = [n for n in FAMILY if not hasattr(mod, n)]
        if lacking:
            raise SystemExit(f"run.py: family {name!r} ({init}) lacks "
                             f"{lacking}; a family gives {list(FAMILY)}")
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


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
        if "family" not in self.config:
            raise SystemExit(
                f"run.py: configuration {self.cell['config']!r} "
                f"({self.path('configs', self.cell['config'])}) names no "
                '"family": say which package of benchmarks/families/ makes '
                "its weights, reference and work counts; there is no "
                "default")
        self.traffic = _load(self.path("traffic", self.cell["traffic"]))
        self.workload = _load(self.path("workloads", workload))
        self.peaks = _load(os.path.join(self.bench_dir, "lib", "peaks.json"))

    def path(self, kind, name, ext=".json"):
        if self.overlay:
            p = os.path.join(self.overlay, kind, name + ext)
            if os.path.exists(p):
                return p
        return os.path.join(self.bench_dir, kind, name + ext)

    @functools.cached_property
    def family(self):
        return load_family(self.config["family"], self.overlay)

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
