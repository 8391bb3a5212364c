"""Device time of the train step by phase, for ``fwd_ms.train``,
``bwd_ms.train`` and ``opt_ms.train``.

The profiler's device events carry the bare instruction name; which
phase and ``named_scope`` an instruction came from is in the program's
own trace: one ``op_scopes`` metadata event (``monitor.trace``), made
from the compiled step's text when the traced window stops. A program
that emits none (the parent of the PR that added it) reads nothing.
"""
import bisect
import collections
import re

from benchmarks.lib import harness

PHASES = ("forward", "backward", "optimizer")


def instruction(text):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_ns(events):
    """{instruction: ns} with a nested event's time taken out of the
    event that encloses it: ``xplane.self_seconds``'s rule, keyed by the
    whole instruction name where that folds ``fusion.12`` to ``fusion``."""
    out = collections.Counter()
    stack = []                            # [event, child time]

    def close():
        done, child = stack.pop()
        out[instruction(done.name)] += max(0.0, done.dur - child)

    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            close()
        if stack:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    while stack:
        close()
    return out


def op_scopes_event(ctx):
    for e in ctx.program_events or ():
        if e.get("ph") == "M" and e.get("name") == "op_scopes":
            return e["args"]
    return None


def phase_table(ctx):
    """{"runs", "phase_ms": {phase: ms a step}, "scope_ms", "unplaced_ms",
    "busy_ms"} or None; computed once a context, and logged once."""
    if hasattr(ctx, "_phase_table"):
        return ctx._phase_table
    ctx._phase_table = table = _phase_table(ctx)
    if table is not None:
        placed = sum(table["phase_ms"].values())
        harness.say(
            f"train step by phase, ms a step over {table['runs']} runs: "
            f"{ {k: round(v, 3) for k, v in table['phase_ms'].items()} }; "
            f"unplaced {table['unplaced_ms']:.3f} ms "
            f"({100 * table['unplaced_ms'] / table['busy_ms']:.2f}% of the "
            f"step's {table['busy_ms']:.3f} ms of device self time; phases "
            f"+ unplaced = {placed + table['unplaced_ms']:.3f})")
        harness.say("train step by scope, ms a step:",
                    [[k, round(v, 3)] for k, v in sorted(
                        table["scope_ms"].items(), key=lambda kv: -kv[1])])
        harness.say("train step by operation and scope, ms a step:",
                    [[op, label, round(v, 3)]
                     for (op, label), v in table["op_scope_top"]])
        harness.say("unplaced instructions, ms a step:",
                    [[k, round(v, 3)] for k, v in table["unplaced_top"]])
    return table


def _phase_table(ctx):
    args = op_scopes_event(ctx)
    if args is None or ctx.trace is None:
        return None
    runs = [m for m in ctx.trace.modules.get(0, ())
            if re.sub(r"\(\d+\)$", "", m.name) == args["program"]]
    if not runs:
        return None
    starts = [m.start for m in runs]

    def in_a_run(e):
        i = bisect.bisect_right(starts, e.start) - 1
        return i >= 0 and e.start < runs[i].end

    times = self_ns([e for e in ctx.trace.ops.get(0, ()) if in_a_run(e)])
    scopes = args["scopes"]
    per = 1e6 * len(runs)                 # ns in all -> ms a step
    phase_ms = dict.fromkeys(PHASES, 0.0)
    scope_ms, unplaced = collections.Counter(), collections.Counter()
    op_scope = collections.Counter()
    for name, ns in times.items():
        label = scopes.get(name)
        family = re.sub(r"(\.\d+)+$", "", name)      # as device_ops has it
        if label is None:
            unplaced[family] += ns / per
            continue
        phase_ms[label.split("/", 1)[0]] += ns / per
        scope_ms[label] += ns / per
        op_scope[family, label] += ns / per
    return {"runs": len(runs), "phase_ms": phase_ms,
            "scope_ms": dict(scope_ms),
            "op_scope_top": op_scope.most_common(24),
            "unplaced_ms": sum(unplaced.values()),
            "unplaced_top": unplaced.most_common(8),
            "busy_ms": sum(times.values()) / per}


def read_phase(ctx, phase):
    table = phase_table(ctx)
    return None if table is None else table["phase_ms"][phase]
