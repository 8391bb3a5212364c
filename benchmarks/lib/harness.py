"""What every driver shares: the compile listener, the device's record,
the traced sub-window and its clock, and the result line."""
import json
import os
import shutil
import statistics
import sys
import time

from . import xplane

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(*parts):
    """A line of the run's own log (standard output, before the result)."""
    print("[bench]", *parts, flush=True)


class CompileCounter:
    """Backend compilations (or cache reads) since the last mark(): jax
    reports one duration event for each program it compiles or loads,
    with the program's name."""

    def __init__(self):
        import jax.monitoring

        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_, **kw):
        if name == COMPILE_EVENT:
            self.names.append(str(kw.get("fun_name")))

    def mark(self):
        self._mark = len(self.names)

    def since_mark(self):
        """How many since mark(); a run that has any says which."""
        late = self.names[self._mark:]
        if late:
            say("compiled inside the window:", late)
        return len(late)


class HostWatch:
    """Was the host in the way? ``tick()`` from a loop that only sleeps:
    its longest iteration, the CPU time the process used in it (a thread
    that hogs the interpreter uses it, a paused machine does not), and
    the CPU time stolen from the machine over the whole watch."""

    def __init__(self):
        self.t0 = self.last = time.perf_counter()
        self.cpu = time.process_time()
        self.steal0 = self._steal()
        self.worst = (0.0, 0.0, 0.0)

    @staticmethod
    def _steal():
        try:
            with open("/proc/stat") as f:
                return int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return 0.0

    def tick(self, count=True):
        """``count=False`` after work the loop did itself (starting or
        stopping the profiler): that turn is not the host's doing."""
        now, cpu = time.perf_counter(), time.process_time()
        if count and now - self.last > self.worst[0]:
            self.worst = (now - self.last, cpu - self.cpu, self.last - self.t0)
        self.last, self.cpu = now, cpu

    def report(self):
        gap, cpu, at = self.worst
        return (f"the watching loop's longest turn took {gap * 1e3:.0f} ms "
                f"({cpu * 1e3:.0f} ms of the process's CPU time) "
                f"{at:.1f} s in; {self._steal() - self.steal0:.2f} s of "
                "CPU time were stolen from the machine")


def device_record(devices):
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def percentile(values, q):
    """The q-th percentile by linear interpolation; values non-empty."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = q / 100.0 * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


median = statistics.median


class TraceWindow:
    """A traced sub-window of the measured window, from ``start_s`` for
    ``seconds`` (the workload file's ``trace`` group). Device events
    come from jax's profiler; the program's own host spans
    (monitor.trace, on ``perf_counter``) are moved onto the profiler's
    clock through one annotation whose ``perf_counter`` time is known."""

    def __init__(self, out_dir, cfg):
        self.dir = os.path.join(out_dir, "trace")
        self.t_on = cfg["start_s"]
        self.t_off = self.t_on + cfg["seconds"]
        self.t_enter = self.t_start = self.t_stop = self.t_exit = None
        self._sync_perf_ns = None

    @property
    def running(self):
        return self.t_start is not None and self.t_stop is None

    def due(self, now):
        """What the driver's loop has to do ``now`` seconds into the
        window: "start", "stop" or nothing."""
        if self.t_start is None:
            return "start" if now >= self.t_on else None
        return "stop" if self.running and now >= self.t_off else None

    def start(self):
        import jax

        from paddle_tpu.monitor import trace as ptrace

        self.t_enter = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.SYNC_NAME):
            self._sync_perf_ns = time.perf_counter_ns()
        ptrace.start_tracing(clear=True)
        self.t_start = time.perf_counter()

    def stop(self):
        """Stop, if it runs. ``t_enter`` to ``t_exit`` is the time the
        profiler held the host, its start-up and shutdown included."""
        import jax

        from paddle_tpu.monitor import trace as ptrace

        if not self.running:
            return
        self.t_stop = time.perf_counter()
        self.program_events = ptrace.stop_tracing().events()
        jax.profiler.stop_trace()
        self.t_exit = time.perf_counter()

    @property
    def window_s(self):
        return self.t_stop - self.t_start

    def reduce(self):
        """-> (xplane.Trace, its host spans). The trace's files are
        deleted once read: a run writes little."""
        trace = xplane.Trace(xplane.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace, host_spans(trace, self.program_events,
                                 self._sync_perf_ns)


def host_spans(trace, program_events, sync_perf_ns):
    """[Event] on the session clock: the benchmark's annotations and,
    moved there through the sync annotation (entered at
    ``sync_perf_ns`` on ``perf_counter``), the program's spans of what
    the host was doing. A span of one request (it says whose: ``rid``)
    lasts that request's wait and covers every gap in it: left out."""
    spans = list(trace.annotations)
    sync = trace.sync_start()
    if sync is not None:
        off = sync - sync_perf_ns                  # perf ns -> session ns
        for ev in program_events:
            if ev.get("ph") == "X" and "rid" not in (ev.get("args") or {}):
                spans.append(xplane.Event(
                    ev["name"], ev["ts"] * 1e3 + off, ev["dur"] * 1e3))
    return spans


def trace_context(spec, env, tw, stats0, stats1, values):
    """What the per-layer readers get (see lib/readers.py)."""
    import types

    from . import program

    counters, hists = program.stats_delta(stats0, stats1)
    trace, spans = tw.reduce()
    return types.SimpleNamespace(
        spec=spec, sizes=spec.config["sizes"], mix=spec.traffic,
        peak=env["peak"], trace=trace, host_spans=spans,
        trace_window_s=tw.window_s, program_events=tw.program_events,
        stat_delta=counters, hist_delta=hists, values=values)


def print_result(result, compared):
    """The last lines: every number compared beside its limit on
    standard error, then the one JSON object on standard output, its
    ``compared`` key last."""
    for name, c in compared.items():
        sys.stderr.write(f"compared {name}: {c['value']!r} "
                         f"(limit {c['limit']!r}, {c['rule']}) "
                         f"{'ok' if c['ok'] else 'NOT OK'}\n")
    sys.stderr.flush()
    line = dict(result)
    line["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in compared.items()}
    print(json.dumps(line), flush=True)


def compare(name, value, limit, rule="<="):
    """One compared number. rule "<=": value must not pass limit;
    ">=": must reach it; "==": exact."""
    if value is None or value != value:
        ok = False
    elif rule == "<=":
        ok = value <= limit
    elif rule == ">=":
        ok = value >= limit
    else:
        ok = value == limit
    return name, {"value": value, "limit": limit, "rule": rule, "ok": ok}
