"""``idle_with_work_share.serve``: the share of the traced window in
which the device sat idle between program runs while the engine had
work.

The idle gaps between program runs on device 0 are found as
``xplane.idle_by_span`` finds them (20 us floor), clipped to the window
the program traced (from the harness's sync annotation, for
``trace_window_s``). What of them no ``serving.idle`` span covers counts:
there the engine's scheduler had a queue, an open slot, a tick in flight
or a host call. The engine that writes the span writes one
``serving_engine`` event a window too; without that event (the parent
of the PR that added both) the reader says nothing, whatever the spans:
an engine that never idles in the window writes no span at all.

Logged beside it, the clock check. A ``serving.device_wait`` waits for
the tick that the turn named by its ``reads`` dispatched, a tick whose
run was dispatched before the wait began; its end less the end of that
run is the wake-up and the read, never negative where the two clocks
agree. Decode runs pair in order with the ``serving.decode_step`` spans
that dispatched them, at the shift that puts most runs between their own
span's start and the next one's: a pairing a clock error under a tick
cannot move."""
from benchmarks.lib import harness
from benchmarks.readers import serve_scopes

IDLE, STEP, WAIT = "serving.idle", "serving.decode_step", "serving.device_wait"


def with_work_ns(runs, idle, lo, hi, floor_ns=20e3):
    """(ns of idle gaps between ``runs`` inside [lo, hi], ns of them no
    ``idle`` span covers)."""
    total = work = 0.0
    end = None
    for e in runs:
        if end is not None and e.start - end > floor_ns:
            a, b = max(end, lo), min(e.start, hi)
            if b > a:
                total += b - a
                work += (b - a) - sum(
                    min(b, s.end) - max(a, s.start) for s in idle
                    if s.start < b and s.end > a)
        end = e.end if end is None else max(end, e.end)
    return total, work


def offset_ns(ctx):
    """perf_counter us (the program's events) -> session ns, as the
    harness moved them, or None where it moved none."""
    first = next((e for e in ctx.program_events or ()
                  if e.get("ph") == "X"
                  and "rid" not in (e.get("args") or {})), None)
    if first is None:
        return None
    for s in ctx.host_spans:
        if s.name == first["name"] and s.dur == first["dur"] * 1e3:
            return s.start - first["ts"] * 1e3
    return None


def wait_lags_us(ctx, off):
    """For each ``serving.device_wait``: its end less the end of the run
    of the tick it read (us)."""
    evs = [e for e in ctx.program_events or () if e.get("ph") == "X"]
    steps = sorted((e for e in evs if e["name"] == STEP
                    and "spec_k" not in e["args"]), key=lambda e: e["ts"])
    runs = [m for m in ctx.trace.modules.get(0, ())
            if serve_scopes.TICK in m.name]
    at = [e["ts"] * 1e3 + off for e in steps] + [float("inf")]
    best, pairs = None, {}
    for shift in range(-3, 4):
        fit = [i for i in range(len(steps)) if 0 <= i + shift < len(runs)
               and at[i] <= runs[i + shift].start <= at[i + 1]]
        if best is None or len(fit) > best:
            best = len(fit)
            pairs = {steps[i]["args"]["tick"]: runs[i + shift] for i in fit}
    return [((e["ts"] + e["dur"]) * 1e3 + off
             - pairs[e["args"]["reads"]].end) / 1e3
            for e in evs if e["name"] == WAIT
            and e["args"].get("reads") in pairs]


def read(ctx):
    if serve_scopes.engine_event(ctx) is None or ctx.trace is None \
            or not ctx.trace.modules.get(0):
        return None
    lo, off = ctx.trace.sync_start(), offset_ns(ctx)
    if lo is None or off is None:
        return None
    hi = lo + ctx.trace_window_s * 1e9
    idle = [s for s in ctx.host_spans if s.name == IDLE]
    total, work = with_work_ns(ctx.trace.modules[0], idle, lo, hi)
    harness.say(f"idle between program runs in the window: "
                f"{total / 1e9:.4f} s, {(total - work) / 1e9:.4f} s of it "
                f"under {len(idle)} {IDLE} spans, {work / 1e9:.4f} s with "
                "work")
    lags = wait_lags_us(ctx, off)
    if lags:
        harness.say(f"clock check: {len(lags)} {WAIT} spans end "
                    f"{min(lags):.1f} us (least) and "
                    f"{harness.median(lags):.1f} us (median) after the "
                    f"run of the tick they read; "
                    f"{sum(x < 0 for x in lags)} end before it")
    return 100.0 * work / 1e9 / ctx.trace_window_s
