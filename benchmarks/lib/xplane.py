"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` alone. On a TPU the device plane
is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO instruction (the event's name is the instruction's text,
``%name.N = ...``), ``XLA Modules`` one event per program run. The host
plane ``/host:CPU`` has one line a thread, named for the thread (the
main thread's for the command: ``python3`` under the benchmark's own),
and a ``TraceAnnotation`` span lies on the line of the thread that
entered it. All start times are nanoseconds on the session's clock.
"""
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SYNC_NAME = "bench.clock_sync"


class Event(collections.namedtuple("Event", "name start dur")):
    @property
    def end(self):
        return self.start + self.dur


def op_name(text):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def host_annotations(plane):
    """The benchmark's own ``bench.``-named spans, from every line of
    the host plane: whatever thread entered them."""
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for line in plane.lines for e in line.events
            if e.name.startswith("bench.")]


class Trace:
    """ops / modules: {device ordinal: [Event]} sorted by start (ns);
    annotations: [Event], the benchmark's own spans on the host."""

    def __init__(self, path):
        from jax.profiler import ProfileData

        self.ops, self.modules, self.annotations = {}, {}, []
        for plane in ProfileData.from_file(path).planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name in ("XLA Ops", "XLA Modules"):
                        evs = sorted(
                            (Event(e.name, float(e.start_ns),
                                   float(e.duration_ns))
                             for e in line.events), key=lambda e: e.start)
                        (self.ops if line.name == "XLA Ops"
                         else self.modules)[int(m.group(1))] = evs
            elif plane.name == "/host:CPU":
                self.annotations += host_annotations(plane)

    def sync_start(self):
        """Session-clock start (ns) of the clock-sync annotation."""
        for e in self.annotations:
            if e.name == SYNC_NAME:
                return e.start
        return None


def union_ns(events):
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in events:                      # sorted by start
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(trace):
    """Seconds an operation ran on the device, averaged over devices."""
    if not trace.ops:
        return 0.0
    return sum(union_ns(evs) for evs in trace.ops.values()) \
        / len(trace.ops) / 1e9


def self_seconds(events):
    """{op name: seconds} with a nested event's time taken out of the
    event that encloses it (a ``while`` holds its body's ops)."""
    out = collections.Counter()
    stack = []                            # [event, child time]
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            done, child = stack.pop()
            out[op_name(done.name)] += max(0.0, done.dur - child)
        if stack:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    while stack:
        done, child = stack.pop()
        out[op_name(done.name)] += max(0.0, done.dur - child)
    return {k: v / 1e9 for k, v in out.items()}


def kernel_events(trace, pattern):
    """Events of device 0's ops whose instruction name matches."""
    rx = re.compile(pattern)
    return [e for e in trace.ops.get(0, ())
            if rx.search(e.name.split(" = ", 1)[0])]


def device_ops(trace, top=10):
    """[[name, seconds]]: the operations that took most device time."""
    times = self_seconds(trace.ops.get(0, ()))
    return [[k, v] for k, v in sorted(times.items(),
                                      key=lambda kv: -kv[1])[:top]]


def module_seconds(trace):
    """{program name: seconds on device 0} from the modules line."""
    out = collections.Counter()
    for e in trace.modules.get(0, ()):
        out[re.sub(r"\(\d+\)$", "", e.name)] += e.dur / 1e9
    return dict(out)


def idle_gaps(trace, host_spans, top=10, floor_ns=20e3):
    """[[what the host was doing, seconds]]: idle time of device 0
    between program runs, by the host span that covers most of each gap.
    ``host_spans``: [Event] on the session clock (the program's spans
    and the benchmark's annotations)."""
    mods = trace.modules.get(0, ())
    out = collections.Counter()
    spans = sorted(host_spans, key=lambda s: s.start)
    end = None
    for e in mods:
        if end is not None and e.start - end > floor_ns:
            best, cover = "unattributed", 0.0
            for s in spans:
                if s.start >= e.start:
                    break
                ov = min(s.end, e.start) - max(s.start, end)
                if ov > cover:
                    best, cover = s.name, ov
            out[best] += (e.start - end) / 1e9
        end = e.end if end is None else max(end, e.end)
    return [[k, v] for k, v in sorted(out.items(),
                                      key=lambda kv: -kv[1])[:top]]


def idle_by_span(trace, host_spans, top=10, floor_ns=20e3):
    """[[what the host was doing, seconds]] for the result's breakdown:
    the same gaps as ``idle_gaps`` finds, each shared out by overlap,
    every stretch to the innermost span over it (the last to begin, the
    shorter of two that begin together) or to "unattributed". A span
    that holds others (a scheduler's turn) keeps what its children
    leave, as ``self_seconds`` does for ops. (The arithmetic of
    ``readers/idle_named_share.serve.py``'s log, copied.)"""
    out = collections.Counter()
    end = None
    for e in trace.modules.get(0, ()):
        if end is not None and e.start - end > floor_ns:
            over = [s for s in host_spans
                    if s.start < e.start and s.end > end]
            cuts = sorted({end, e.start}
                          | {t for s in over for t in (s.start, s.end)
                             if end < t < e.start})
            for a, b in zip(cuts, cuts[1:]):
                inner = max((s for s in over if s.start <= a and s.end >= b),
                            key=lambda s: (s.start, -s.dur), default=None)
                out[inner.name if inner else "unattributed"] += (b - a) / 1e9
        end = e.end if end is None else max(end, e.end)
    return [[k, v] for k, v in out.most_common(top)]
