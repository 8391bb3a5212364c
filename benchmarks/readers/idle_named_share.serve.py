"""``idle_named_share.serve``: how much of the device's idle time has a
name.

``xplane.idle_gaps`` gives each idle gap between program runs to the
host span that covers most of it, or to "unattributed". The metric is
the share that falls under a span of the scheduler's turn tree. Logged
beside it: the same gaps shared out by overlap to the innermost span,
and how the engine's decode runs sit inside their
``serving.decode_step`` spans — a run that ends after its span does
says by how much the two clocks disagree.

The program's spans are on ``perf_counter``; the harness moves them to
the profiler's clock through its sync annotation. Where the trace holds
no such annotation, ``ctx.host_spans`` has none of the program's spans,
and this reader fits the offset itself: every decode run ends before
the host sees its tokens, so the offset is the least that lets no run
outlast its span. That is short of the truth by the quickest wake-up of
the window (tens of microseconds, against gaps of milliseconds), and
the log says which way the spans came."""
import collections

from benchmarks.lib import harness, xplane

# the scheduler's turn and its children: what the host was doing. The
# per-request chain (queue_wait, admit_to_first, request_done) is left
# out: a span that lasts a request's whole wait covers every gap in it
TREE = ("serving.turn", "serving.admit", "serving.prefill",
        "serving.prefill_chunk", "serving.first_token",
        "serving.decode_prep", "serving.decode_step",
        "serving.device_wait", "serving.emit")
STEP, RUN = "serving.decode_step", "_decode_paged_fn"


def shared_out(trace, spans, floor_ns=20e3):
    """{span name: seconds}: every idle gap between program runs (found
    as ``xplane.idle_gaps`` finds them) shared out by overlap, each
    stretch to the innermost span over it (the last to begin, the
    shorter of two that begin together)."""
    out = collections.Counter()
    end = None
    for e in trace.modules.get(0, ()):
        if end is not None and e.start - end > floor_ns:
            over = [s for s in spans if s.start < e.start and s.end > end]
            cuts = sorted({end, e.start}
                          | {t for s in over for t in (s.start, s.end)
                             if end < t < e.start})
            for a, b in zip(cuts, cuts[1:]):
                inner = max((s for s in over if s.start <= a and s.end >= b),
                            key=lambda s: (s.start, -s.dur), default=None)
                out[inner.name if inner else "unattributed"] += (b - a) / 1e9
        end = e.end if end is None else max(end, e.end)
    return out


def lags_us(trace, spans):
    """For each decode run, how long after it its span ends (us)."""
    steps = sorted((s for s in spans if s.name == STEP),
                   key=lambda s: s.start)
    lags = []
    for run in trace.modules.get(0, ()):
        if RUN not in run.name:
            continue
        best, cover = None, 0.0
        for s in steps:
            if s.start >= run.end:
                break
            ov = min(s.end, run.end) - max(s.start, run.start)
            if ov > cover:
                best, cover = s, ov
        if best is not None:
            lags.append((best.end - run.end) / 1e3)
    return lags


def fitted(ctx):
    """The program's spans moved onto the trace's clock by the decode
    runs themselves -> ([Event], pairs used) or None. Spans and runs
    are paired in order; which run the first span belongs to (a span or
    a run may be cut by the window's edge) is the pairing whose offsets
    agree best."""
    events = [e for e in ctx.program_events or ()
              if e.get("ph") == "X" and e["name"] in TREE]
    ends = sorted((e["ts"] + e["dur"]) * 1e3 for e in events
                  if e["name"] == STEP)
    runs = [m.end for m in ctx.trace.modules.get(0, ()) if RUN in m.name]
    best = None
    for shift in range(-3, 4):
        d = sorted(runs[i + shift] - end for i, end in enumerate(ends)
                   if 0 <= i + shift < len(runs))
        if len(d) < 4:
            continue
        spread = d[3 * len(d) // 4] - d[len(d) // 4]
        if best is None or spread < best[0]:
            best = (spread, d[-1], len(d))
    if best is None:
        return None
    off = best[1]
    return [xplane.Event(e["name"], e["ts"] * 1e3 + off, e["dur"] * 1e3)
            for e in events], best[2]


def read(ctx):
    if ctx.trace is None or not ctx.trace.modules.get(0):
        return None
    spans = [s for s in ctx.host_spans if s.name in TREE]
    how = "moved by the harness's sync annotation"
    if not spans:
        got = fitted(ctx)
        if got is None:
            return None
        spans, n = got
        how = (f"no sync annotation on the trace: offset fitted on {n} "
               f"{RUN} runs")
    gaps = xplane.idle_gaps(ctx.trace, spans, top=1 << 30)
    total = sum(v for _, v in gaps)
    if total <= 0:
        return None
    named = sum(v for k, v in gaps if k != "unattributed")
    harness.say(f"idle between program runs: {total:.4f} s ({how}); "
                "shared out by overlap to the innermost span:",
                [[k, round(v, 4)] for k, v in
                 shared_out(ctx.trace, spans).most_common()])
    lags = lags_us(ctx.trace, spans)
    if lags:
        over = [-x for x in lags if x < 0]
        harness.say(f"clock skew: {len(over)} of {len(lags)} {RUN} runs "
                    f"end after their {STEP} span, by at most "
                    f"{max(over, default=0.0):.1f} us; the span ends "
                    f"{min(lags):.1f} us (least) and "
                    f"{harness.median(lags):.1f} us (median) after the "
                    "run")
    return 100.0 * named / total
