"""Per-layer metric readers. Each metric has a file
``benchmarks/metrics/<metric>.json`` whose ``reader`` names one of the
kinds below, or ``{"kind": "module"}`` for a reader of its own in
``benchmarks/readers/<metric>.py`` (a function ``read(ctx)``).

A reader returns a number, or None when it finds nothing to read; the
harness then leaves the metric out of the line. It never returns 0 for
a share of a roofline or of a peak.

The operations and bytes of a kernel come from the configuration's
family: a ``device_trace_kernel`` file's ``"work"`` names an entry of its
``KERNEL_WORK`` (``lib/spec.py``); no table is kept here.

``ctx`` (a ``types.SimpleNamespace``) carries: ``spec``, ``sizes``,
``mix``, ``peak``; from the traced sub-window ``trace`` (xplane.Trace),
``trace_window_s``, ``program_events`` (the program's own host spans);
``stat_delta`` and ``hist_delta`` (the program's counters and
histograms, window end minus window start); and ``values``, the
driver's own measurements by key.
"""
import importlib.util
import os
import re

from . import harness, work, xplane


def driver_value(ctx, r):
    return ctx.values.get(r["key"])


def device_idle(ctx, r):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - xplane.busy_seconds(ctx.trace)
                    / ctx.trace_window_s)


def device_trace_kernel(ctx, r):
    """The kernel's share of its roofline: least time the chip could
    take for the work, over the time its events took."""
    if ctx.trace is None:
        return None
    table = ctx.spec.family.KERNEL_WORK
    if r["work"] not in table:
        raise SystemExit(f"kernel {r['pattern']}: the family "
                         f"{ctx.spec.config['family']!r} counts no work "
                         f"{r['work']!r} (it has {sorted(table)})")
    evs = xplane.kernel_events(ctx.trace, r["pattern"])
    seconds = sum(e.dur for e in evs) / 1e9
    if not evs or seconds <= 0:
        return None
    got = table[r["work"]](ctx, len(evs))
    if got is None:
        return None
    least, bound = work.roofline_seconds(got[0], got[1], ctx.peak)
    harness.say(f"kernel {r['pattern']}: {len(evs)} events, "
                f"{seconds:.6f} s, least {least:.6f} s ({bound}-bound)")
    return 100.0 * least / seconds


def device_trace_module(ctx, r):
    """Median device time (ms) of one run of the programs whose name
    matches, from the trace's ``XLA Modules`` line: what a tick or a
    chunk costs the device, whatever the host was waiting for."""
    if ctx.trace is None:
        return None
    rx = re.compile(r["pattern"])
    durs = [e.dur / 1e6 for e in ctx.trace.modules.get(0, ())
            if rx.search(e.name)]
    return harness.median(durs) if durs else None


def share_of_peak(ctx, r):
    rate = ctx.values.get(r["key"])
    if not rate:
        return None
    return 100.0 * rate / ctx.peak[r["peak"]]


def stat_delta(ctx, r):
    return ctx.stat_delta.get(r["name"])


def histogram_quantile(ctx, r):
    """Quantile of a fixed-bucket histogram's window delta by linear
    interpolation inside the bucket the rank falls in (the arithmetic of
    the program's ``hist_quantile``, copied: the yardstick is here)."""
    h = ctx.hist_delta.get(r["name"])
    if not h or h["count"] <= 0:
        return None
    rank, cum, lo = r["q"] * h["count"], 0, 0.0
    for i, c in enumerate(h["counts"]):
        if cum + c >= rank and c > 0:
            if i >= len(h["bounds"]):
                return float(h["bounds"][-1])
            return lo + (h["bounds"][i] - lo) * (rank - cum) / c
        cum += c
        if i < len(h["bounds"]):
            lo = h["bounds"][i]
    return float(h["bounds"][-1])


def host_span(ctx, r):
    """Median duration (ms) of the program's spans of one name in the
    traced sub-window."""
    durs = [e["dur"] / 1e3 for e in (ctx.program_events or ())
            if e.get("ph") == "X" and e["name"] == r["name"]]
    return harness.median(durs) if durs else None


KINDS = {f.__name__: f for f in (
    driver_value, device_idle, device_trace_kernel, device_trace_module,
    share_of_peak,
    stat_delta, histogram_quantile, host_span)}


def read_metric(ctx, name):
    spec = ctx.spec.metric_file(name)
    reader = spec["reader"]
    if reader["kind"] == "module":
        path = ctx.spec.path("readers", name, ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_reader_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx)
    if reader["kind"] not in KINDS:
        raise SystemExit(f"metric {name}: unknown reader kind "
                         f"{reader['kind']!r} (have {sorted(KINDS)}, or "
                         f"'module' with {os.path.join('benchmarks', 'readers', name + '.py')})")
    return KINDS[reader["kind"]](ctx, reader)
