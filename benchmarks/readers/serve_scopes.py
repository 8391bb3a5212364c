"""Device time of the serving engine's programs by ``named_scope``, for
``tick_attn_ms.serve``, ``tick_ffn_ms.serve``, ``tick_head_ms.serve``,
``tick_other_ms.serve``, ``chunk_attn_ms.serve`` and
``chunk_ffn_ms.serve``.

The engine writes one ``op_scopes`` metadata event for each (program,
signature) it dispatched in the traced window (``monitor.trace``): its
``program`` is the name the trace's ``XLA Modules`` line gives its runs,
less the ``(N)``. The tables of one program (its width buckets, its
chunk lengths) are merged; an instruction whose label differs between
them counts as unplaced. Every run of the program on device 0 gives its
ops' self time (``phases.self_ns``) to their labels. The engine names
each signature a program of its own (``jit__decode_paged_fn_w64``,
``jit__chunk_fn_c128_w16``), so a run finds its own table; a metric sums
its scopes over the runs of every program its kind's pattern finds
(``_decode_paged_fn``, ``_chunk_fn``) and divides by their number. A
program that emits no table (the parent of the PR that added it) reads
nothing, nor does one with no run on the trace.
"""
import bisect
import collections
import re

from benchmarks.lib import harness
from benchmarks.readers import phases

TICK, CHUNK = "_decode_paged_fn", "_chunk_fn"
GROUPS = {"attn": ("attn", "kv_pool", "retention"),
          "ffn": ("mlp", "router", "experts"),
          "head": ("embed", "head", "sampling")}
UNPLACED = "(unplaced)"


def strip(module):
    """``jit__decode_paged_fn(12)`` -> ``jit__decode_paged_fn``."""
    return re.sub(r"\(\d+\)$", "", module)


def engine_event(ctx):
    """The args of the engine's ``serving_engine`` event, or None."""
    for e in ctx.program_events or ():
        if e.get("ph") == "M" and e.get("name") == "serving_engine":
            return e["args"]
    return None


def merged_tables(ctx):
    """{program: (labels, conflicting instructions, signatures)}."""
    out = {}
    for e in ctx.program_events or ():
        if e.get("ph") != "M" or e.get("name") != "op_scopes":
            continue
        a = e["args"]
        labels, clash, sigs = out.setdefault(a["program"], ({}, set(), []))
        sigs.append(a.get("signature"))
        for name, label in a["scopes"].items():
            if labels.setdefault(name, label) != label:
                clash.add(name)
    return out


def scope_of(label):
    """``forward/attn`` -> ``attn``; a bare phase has no scope: ``""``."""
    return label.split("/", 1)[1] if "/" in label else ""


def _by_program(ctx, programs):
    """{program: [ops of its runs]}, {program: [runs]} on device 0."""
    runs = [m for m in ctx.trace.modules.get(0, ())
            if strip(m.name) in programs]
    starts = [m.start for m in runs]
    ops = collections.defaultdict(list)
    for e in ctx.trace.ops.get(0, ()):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < runs[i].end:
            ops[strip(runs[i].name)].append(e)
    by = collections.defaultdict(list)
    for m in runs:
        by[strip(m.name)].append(m)
    return ops, by


def _tables(ctx):
    """{program: {"runs", "run_ns", "signatures", "scope_ns", "op_scope_ns",
    "unplaced_ns"}}: device totals of every run of each program with a
    table."""
    merged = merged_tables(ctx)
    if not merged or ctx.trace is None:
        return {}
    ops, runs = _by_program(ctx, merged)
    out = {}
    for prog, (labels, clash, sigs) in merged.items():
        if not runs.get(prog):
            continue
        scope_ns, op_scope = collections.Counter(), collections.Counter()
        unplaced = collections.Counter()
        for name, ns in phases.self_ns(ops[prog]).items():
            label = None if name in clash else labels.get(name)
            family = re.sub(r"(\.\d+)+$", "", name)
            if label is None:
                scope_ns[UNPLACED] += ns
                unplaced[family] += ns
            else:
                scope_ns[scope_of(label)] += ns
                op_scope[family, scope_of(label)] += ns
        out[prog] = {"runs": len(runs[prog]),
                     "run_ns": sum(m.dur for m in runs[prog]),
                     "signatures": [str(s) for s in sigs],
                     "scope_ns": scope_ns, "op_scope_ns": op_scope,
                     "unplaced_ns": unplaced}
    return out


def _kind(tables, pattern):
    """The programs whose name ``pattern`` finds, as one: {"programs",
    "runs", "scope_ms": {scope: ms a run}, "busy_ms", "run_ms" (mean
    run), "op_scope_top", "unplaced_top"}, or None."""
    mine = {p: t for p, t in tables.items() if re.search(pattern, p)}
    if not mine:
        return None
    runs = sum(t["runs"] for t in mine.values())
    per = 1e6 * runs                       # ns in all -> ms a run

    def add(key):
        return sum((t[key] for t in mine.values()), collections.Counter())

    scope_ms = {k: v / per for k, v in add("scope_ns").items()}
    return {"programs": sorted(mine), "runs": runs, "scope_ms": scope_ms,
            "busy_ms": sum(scope_ms.values()),
            "run_ms": sum(t["run_ns"] for t in mine.values()) / per,
            "op_scope_top": [(k, v / per) for k, v in
                             add("op_scope_ns").most_common(12)],
            "unplaced_top": [(k, v / per) for k, v in
                             add("unplaced_ns").most_common(6)]}


def tables(ctx):
    """{TICK: the decode programs as one, CHUNK: the chunk programs} (a
    kind with no table or no run left out); computed once a context,
    and logged once."""
    if hasattr(ctx, "_serve_scopes"):
        return ctx._serve_scopes
    every = _tables(ctx)
    ctx._serve_scopes = got = {k: t for k in (TICK, CHUNK)
                               if (t := _kind(every, k)) is not None}
    for kind, t in got.items():
        unpl = t["scope_ms"].get(UNPLACED, 0.0)
        scopes = sorted(t["scope_ms"].items(), key=lambda kv: -kv[1])
        harness.say(
            f"{kind} by scope, ms a run over {t['runs']} runs of "
            f"{len(t['programs'])} programs ({', '.join(t['programs'])}): "
            f"{ {k or '(none)': round(v, 4) for k, v in scopes} }; self "
            f"time {t['busy_ms']:.4f} ms against a mean run of "
            f"{t['run_ms']:.4f} ms; unplaced {unpl:.4f} ms "
            f"({100 * unpl / t['busy_ms'] if t['busy_ms'] else 0:.2f}%)")
        harness.say(f"{kind} by operation and scope, ms a run:",
                    [[op, s or "(none)", round(v, 4)]
                     for (op, s), v in t["op_scope_top"]])
        harness.say(f"{kind} unplaced, ms a run:",
                    [[k, round(v, 4)] for k, v in t["unplaced_top"]])
    if got:
        eng = engine_event(ctx) or {}
        harness.say(f"the engine's on_stop callback wrote "
                    f"{eng.get('tables')} tables in {eng.get('seconds')} s")
    return got


def group_ms(ctx, kind, group):
    """Ms a run of ``kind``'s scopes in ``group`` (``other``: what the
    three groups leave, unplaced included), or None."""
    t = tables(ctx).get(kind)
    if t is None:
        return None
    ms = t["scope_ms"]
    if group == "other":
        named = {s for g in GROUPS.values() for s in g}
        return sum(v for k, v in ms.items() if k not in named)
    return sum(ms.get(s, 0.0) for s in GROUPS[group])
