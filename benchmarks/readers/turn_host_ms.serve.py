"""``turn_host_ms.serve``: the host's work in one scheduler turn.

Median over the engine's ``serving.turn`` spans of the turn's duration
minus the ``serving.device_wait`` spans that carry its ``tick``. A
program without the span tree reads nothing."""
import collections

from benchmarks.lib import harness

TURN, WAIT = "serving.turn", "serving.device_wait"


def read(ctx):
    turns, by_tick = [], collections.defaultdict(collections.Counter)
    for e in ctx.program_events or ():
        if e.get("ph") != "X" or not e["name"].startswith("serving."):
            continue
        tick = (e.get("args") or {}).get("tick")
        if tick is None:
            continue
        if e["name"] == TURN:
            turns.append((tick, e["dur"] / 1e3))
        else:
            by_tick[tick][e["name"]] += e["dur"] / 1e3
    if not turns:
        return None
    host = [dur - by_tick[tick][WAIT] for tick, dur in turns]
    names = sorted({n for c in by_tick.values() for n in c})
    harness.say(f"scheduler turns: {len(turns)}; median ms a turn: whole "
                f"{harness.median([d for _, d in turns]):.3f}, by child",
                {n: round(harness.median(
                    [by_tick[t][n] for t, _ in turns]), 3) for n in names})
    return harness.median(host)
