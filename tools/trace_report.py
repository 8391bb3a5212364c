"""Chrome-trace analysis reports: load one or more trace-event JSON
files (as written by paddle_tpu.profiler / monitor.trace.TraceWriter /
the crash flight recorder, or any chrome://tracing export) and print the
hot-span table plus every section report the events support — so CI and
chip runs can diff hot paths without TensorBoard.

    python -m tools.trace_report trace.json [more.json ...]
        [--top 20] [--json] [--section NAME]

One CLI fronts every report (ISSUE 15 satellite — previously ~10
per-subsystem entry points): ``--section NAME`` prints just that
section (``--list-sections`` enumerates them), ``--json`` emits one
machine-readable object ``{section: result, ...}`` for CI consumption,
and MULTIPLE trace files merge into one timeline — flight-recorder
dumps from different hosts get distinct synthetic pids (named per host)
so a pod-wide failure reads as one chrome-loadable merged trace.

Handles both "X" (complete) events and matched "B"/"E" pairs; events come
either as a bare list or under the {"traceEvents": [...]} envelope
(flight dumps additionally carry their summary under a "flight" key).
"""
from __future__ import annotations

import argparse
import io
import json
import sys


def load_trace(path: str) -> dict:
    """One file -> {"path", "events", "flight" (summary dict or None)}."""
    with open(path) as f:
        data = json.load(f)
    events = data.get("traceEvents", []) if isinstance(data, dict) else data
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a chrome-trace file "
                         "(expected a list or a traceEvents envelope)")
    flight = data.get("flight") if isinstance(data, dict) else None
    return {"path": path, "events": events, "flight": flight}


def load_events(path: str) -> list:
    return load_trace(path)["events"]


def merge_traces(traces: list) -> list:
    """Merge several loaded traces into one event list. Every (file,
    pid) pair gets a DISTINCT synthetic pid — two hosts' flight dumps
    (or two simulated hosts in one process, sharing a real pid) land in
    separate process lanes — and a process_name metadata row names each
    lane after the dump's host id. Timestamps share the perf_counter
    timeline per host and are left untouched."""
    if len(traces) == 1 and traces[0]["flight"] is None:
        return list(traces[0]["events"])
    out = []
    next_pid = 1
    for tr in traces:
        host = (tr["flight"] or {}).get("host")
        pid_map: dict = {}
        for ev in tr["events"]:
            if ev.get("ph") == "M":
                continue        # re-emitted below with the merged pid
            pid = ev.get("pid", 0)
            if pid not in pid_map:
                pid_map[pid] = next_pid
                next_pid += 1
            ev = dict(ev)
            ev["pid"] = pid_map[pid]
            out.append(ev)
        for pid, mapped in pid_map.items():
            label = f"{host} pid={pid}" if host else f"pid={pid}"
            out.append({"name": "process_name", "ph": "M", "pid": mapped,
                        "args": {"name": label}})
    return out


def aggregate(events: list) -> list:
    """Per-name rows {name, calls, total_us, avg_us, max_us} sorted by
    total, descending. B/E pairs are matched per (pid, tid) as a stack —
    the format guarantees nesting within a thread."""
    acc: dict = {}  # name -> [calls, total_us, max_us]
    open_marks: dict = {}  # (pid, tid) -> [(name, ts)]

    def feed(name, dur):
        r = acc.get(name)
        if r is None:
            acc[name] = [1, dur, dur]
        else:
            r[0] += 1
            r[1] += dur
            if dur > r[2]:
                r[2] = dur

    for ev in events:
        ph = ev.get("ph")
        name = ev.get("name", "?")
        if ph == "X":
            feed(name, float(ev.get("dur", 0)))
        elif ph == "B":
            open_marks.setdefault((ev.get("pid"), ev.get("tid")), []).append(
                (name, float(ev.get("ts", 0))))
        elif ph == "E":
            stack = open_marks.get((ev.get("pid"), ev.get("tid")))
            if stack:
                bname, bts = stack.pop()
                feed(bname, float(ev.get("ts", 0)) - bts)
    rows = [{"name": n, "calls": r[0], "total_us": r[1],
             "avg_us": r[1] / r[0], "max_us": r[2]}
            for n, r in acc.items()]
    rows.sort(key=lambda r: -r["total_us"])
    return rows


def input_pipeline_report(rows: list, file=None) -> dict:
    """Input-vs-compute verdict from the prefetch/H2D spans (ISSUE 3).

    The DevicePrefetcher emits ``prefetch.h2d_copy`` (host->device copy of
    each staged batch) and ``prefetch.wait`` (consumer blocked on an empty
    prefetch queue) spans; step-level spans land under names containing
    "step"/"train_batch". Comparing them answers the question a slow
    trace always raises: is the step starving on INPUT (wait time rivals
    step time) or is input fully hidden behind COMPUTE?"""
    def total(pred):
        return sum(r["total_us"] for r in rows if pred(r["name"]))

    h2d = total(lambda n: n == "prefetch.h2d_copy")
    wait = total(lambda n: n == "prefetch.wait")
    step = total(lambda n: "step" in n.lower() or "train_batch" in n.lower())
    if h2d == 0 and wait == 0:
        return {}
    out = {"h2d_copy_ms": h2d / 1e3, "prefetch_wait_ms": wait / 1e3,
           "step_ms": step / 1e3}
    if step > 0:
        out["wait_frac_of_step"] = wait / step
        out["verdict"] = ("input-bound: the consumer waited on the "
                          "prefetch queue for a significant share of "
                          "step time — add workers / enable shared "
                          "memory / deepen prefetch"
                          if wait > 0.1 * step else
                          "compute-bound: H2D copies are hidden behind "
                          "the step")
    print("\nInput pipeline:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<22}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def overlap_report(rows: list, file=None) -> dict:
    """Comm-vs-compute overlap verdict from the overlap spans (ISSUE 6).

    ``DistributedTrainStep.measure_overlap`` emits ``overlap.step`` (full
    loss+grads including the dp all-reduce), ``overlap.compute``
    (backward compute only) and ``overlap.comm`` (the grad all-reduce
    alone). The share of comm hidden inside the step —
    ``(compute + comm - step) / comm`` — answers whether the gradient
    all-reduce overlaps the backward (FLAGS_overlap_grads working) or
    serializes after it, mirroring the input-vs-compute verdict."""
    def total(name):
        return sum(r["total_us"] for r in rows if r["name"] == name)

    step = total("overlap.step")
    compute = total("overlap.compute")
    comm = total("overlap.comm")
    if step == 0 and comm == 0:
        return {}
    out = {"step_ms": step / 1e3, "compute_ms": compute / 1e3,
           "comm_ms": comm / 1e3}
    if comm > 0:
        hidden = max(0.0, min(1.0, (compute + comm - step) / comm))
        out["hidden_comm_frac"] = hidden
        out["verdict"] = (
            "overlapped: the gradient all-reduce is mostly hidden behind "
            "backward compute" if hidden >= 0.5 else
            "serialized: the gradient all-reduce adds mostly un-hidden "
            "time after the backward — enable FLAGS_overlap_grads / "
            "check bucket sizes")
    print("\nComm/compute overlap:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<22}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def kernels_report(events: list, file=None) -> dict:
    """Kernel-library health from the autotune/fallback events (ISSUE 17).

    ``paddle_tpu.ops.autotune`` emits one ``autotune.tune`` span per
    trial sweep (args: cache key, winner, per-candidate ms) and a
    zero-duration ``kernel.fallback`` event every time a Pallas entry
    drops to composed jnp (args: kernel, shape, why). The section answers
    two questions a quiet run hides: where did FLAGS_autotune's one-time
    trial cost go, and is the model silently running WITHOUT its fused
    kernels."""
    tunes = [e for e in events if e.get("name") == "autotune.tune"]
    falls = [e for e in events if e.get("name") == "kernel.fallback"]
    if not tunes and not falls:
        return {}
    out: dict = {}
    if tunes:
        out["tune_sweeps"] = len(tunes)
        out["tune_total_ms"] = sum(e.get("dur", 0) for e in tunes) / 1e3
        out["winners"] = {
            e.get("args", {}).get("key", "?"):
                e.get("args", {}).get("winner", "?")
            for e in tunes}
    if falls:
        by_kernel: dict = {}
        for e in falls:
            a = e.get("args", {})
            k = a.get("kernel", "?")
            ent = by_kernel.setdefault(
                k, {"count": 0, "detail": a.get("detail", "")})
            ent["count"] += 1
        out["fallbacks"] = by_kernel
        out["verdict"] = (
            "DEGRADED: %d Pallas entr%s fell back to composed jnp — the "
            "run is not using the fused kernels at those shapes"
            % (len(falls), "y" if len(falls) == 1 else "ies"))
    else:
        out["verdict"] = "all Pallas entries ran their kernels (no " \
                         "composed-jnp fallbacks in the trace window)"
    print("\nKernel library (autotune/fallbacks):", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<22}{v:>12.3f}", file=file)
        elif isinstance(v, dict):
            print(f"  {k}:", file=file)
            for kk, vv in sorted(v.items()):
                print(f"    {kk}: {vv}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def pipeline_report(events: list, file=None) -> dict:
    """Pipeline-bubble verdict from the ``pipeline.tick`` spans (ISSUE 9).

    The FleetEngine emits one span per schedule tick with ``{t, busy,
    slots, stages, n_micro, schedule}`` — the stage occupancy of the
    STATIC schedule the step compiled (the in-jit scan never returns to
    the host mid-step, so occupancy comes from the schedule's closed
    form). The measured bubble fraction ``1 - Σbusy/Σslots`` is diffed
    against the cost model's prediction — ``(S-1)/T`` with
    ``T = n_micro + S - 1`` per pass (fill/drain), or the 1F1B
    equivalent ``2(S-1)/(n_micro + 2(S-1))`` — answering whether the
    schedule that actually ran matches what the fleet.auto planner
    budgeted for."""
    ticks = [e for e in events if e.get("name") == "pipeline.tick"]
    if not ticks:
        return {}
    busy = slots = 0
    a0 = ticks[0].get("args") or {}
    for e in ticks:
        a = e.get("args") or {}
        busy += int(a.get("busy", 0))
        slots += int(a.get("slots", 0))
    measured = 1.0 - busy / slots if slots else 0.0
    S = int(a0.get("stages", 1))
    n = int(a0.get("n_micro", 1))
    sched = str(a0.get("schedule", "fthenb"))
    if sched == "1f1b" and S > 1:
        predicted = 2.0 * (S - 1) / (n + 2 * (S - 1))
    else:
        predicted = (S - 1) / (n + S - 1) if S > 1 else 0.0
    out = {"schedule": sched, "stages": S, "n_micro": n,
           "ticks": len(ticks), "measured_bubble_frac": measured,
           "predicted_bubble_frac": predicted}
    delta = abs(measured - predicted)
    out["verdict"] = (
        f"pipeline schedule matches the cost model (bubble "
        f"{measured:.3f} vs predicted {predicted:.3f})" if delta <= 0.02
        else f"bubble deviates from the cost model by {delta:.3f} "
             f"(measured {measured:.3f} vs predicted {predicted:.3f}) — "
             "the compiled schedule is not the one the planner budgeted; "
             "check accumulate_steps/pipeline_configs overrides")
    print("\nPipeline schedule:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<24}{v:>12.4f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def recompile_report(events: list, file=None, top: int = 5) -> dict:
    """Recompile-causes verdict from the ``sanitize.recompile`` spans
    (ISSUE 8, FLAGS_sanitize).

    Each span names the cache group (grad_jit:<op> / TrainStep /
    DistributedTrainStep) and the LEAF whose (shape, dtype, weak-type)
    signature differed from the nearest already-compiled entry. Grouped
    by (group, leaf) they answer the question GRAD_JIT_MISS alone
    cannot: WHICH input keeps churning — a shape-unstable data loader, a
    dtype flip, a python-scalar arg retraced per value."""
    recs = [e for e in events if e.get("name") == "sanitize.recompile"]
    if not recs:
        return {}
    agg: dict = {}   # (group, leaf) -> [count, kinds, example]
    for e in recs:
        a = e.get("args") or {}
        key = (a.get("group", "?"), a.get("leaf", "?"))
        r = agg.setdefault(key, [0, set(), ""])
        r[0] += 1
        r[1].add(a.get("kind", "?"))
        r[2] = f"{a.get('had', '?')} -> {a.get('got', '?')}"
    causes = sorted(
        ({"group": g, "leaf": leaf, "count": c, "kinds": sorted(k),
          "example": ex} for (g, leaf), (c, k, ex) in agg.items()),
        key=lambda r: -r["count"])[:top]
    worst = causes[0]
    out = {"recompiles": len(recs), "causes": causes,
           "verdict": (f"recompile churn: {len(recs)} explained "
                       f"recompile(s); top cause is {worst['group']} "
                       f"{worst['leaf']} ({'/'.join(worst['kinds'])}: "
                       f"{worst['example']}) — stabilize that input "
                       "(pad/bucket shapes, pin dtypes, pass scalars as "
                       "arrays)")}
    print("\nRecompile causes:", file=file)
    for r in causes:
        print(f"  {r['group']:<28}{r['leaf']:<12}{r['count']:>6}x  "
              f"{'/'.join(r['kinds'])}: {r['example']}", file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    return out


def _prefill_starvation(events: list) -> dict:
    """Max consecutive scheduler ticks in which chunked prefill ran while
    open decode streams got no decode step (ISSUE 7).

    The paged engine tags ``serving.prefill_chunk`` spans with
    ``{tick, open_streams}`` and ``serving.decode_step`` spans with
    ``{tick}``. A tick that did chunk work with ``open_streams > 0`` but
    no decode step starved every open stream for that tick; the maximum
    RUN of such ticks is how long any stream waited. With the chunk loop
    interleaved correctly this is 0 — a nonzero value means prefill is
    monopolizing the scheduler (serial-prefill regression)."""
    chunk_ticks: dict = {}   # tick -> had open streams waiting
    decode_ticks = set()
    for e in events:
        name = e.get("name")
        args = e.get("args") or {}
        if "tick" not in args:
            continue
        if name == "serving.prefill_chunk":
            t = int(args["tick"])
            chunk_ticks[t] = chunk_ticks.get(t, False) \
                or int(args.get("open_streams", 0)) > 0
        elif name == "serving.decode_step":
            decode_ticks.add(int(args["tick"]))
    if not chunk_ticks:
        return {}
    starved = sorted(t for t, waiting in chunk_ticks.items()
                     if waiting and t not in decode_ticks)
    worst = run = 0
    prev = None
    for t in starved:
        run = run + 1 if prev is not None and t == prev + 1 else 1
        worst = max(worst, run)
        prev = t
    return {"prefill_chunk_ticks": len(chunk_ticks),
            "starved_ticks": len(starved),
            "max_consecutive_starved_ticks": worst}


def serving_report(rows: list, file=None, events: list | None = None) -> dict:
    """Prefill-vs-decode verdict from the serving spans (ISSUE 4/7).

    The serving engine emits ``serving.prefill_chunk`` (one per
    chunked-prefill tick slice) and ``serving.decode_step`` (one per
    batched decode tick) spans; a trace file from before PR 32 may also
    hold ``serving.prefill`` (one per whole-prompt admission of the
    fixed-slot engine) and is still read. Their split answers the first
    question about a slow serving trace: is admission or steady-state
    decode eating the time budget? When raw ``events`` are passed, the
    run also gets a PREFILL STARVATION verdict — the max consecutive ticks any open
    stream waited behind chunked prefill work — and the share of the
    decode ticks' tabled blocks that were live (``decode_blocks_live``
    over ``decode_blocks_tabled`` of the ``serving.decode_step`` spans),
    the rows a tick's writer put into the paged pool beside the rows a
    grid over every lane would put (``kv_rows_written``: active lanes x
    layers; ``kv_rows_grid``: slots x layers),
    for a model whose cache is a recurrent state the lanes whose state a
    tick moved (``state_slots_live``: the tick's state traffic is that
    times one state's bytes, twice), and the share of the ticks whose
    sampling went each way (the spans'
    ``sample_path``: ``greedy`` argmax, ``select`` among a row's largest
    entries, ``sort`` of the vocabulary; the engine counts the same
    ticks in ``serving_sample_ticks_<path>``), how many ticks left with
    the tick before still in flight (the spans' ``ahead``: the engine's
    ``serving_decode_ticks_ahead``, the rest
    ``serving_decode_ticks_synced``) and how many lane results were
    never pushed (``lanes_discarded``, the engine's
    ``serving_decode_lanes_discarded``), and for a model with an expert
    layer that holds a share of its experts, by kind of step (``decode``
    ticks, prefill ``chunk``s), the held experts read
    (``moe_expert_reads``), the grouped expert kernel's row tiles
    (``moe_kernel_tiles``, 0 where it did not run) and their ratio, how
    often a held expert's weights were read (``moe_reread``: 1.0 at
    best)."""
    pre = [r for r in rows if r["name"] == "serving.prefill"]
    chk = [r for r in rows if r["name"] == "serving.prefill_chunk"]
    dec = [r for r in rows if r["name"] == "serving.decode_step"]
    if not pre and not chk and not dec:
        return {}
    pre_us = sum(r["total_us"] for r in pre + chk)
    dec_us = sum(r["total_us"] for r in dec)
    out = {"prefill_ms": pre_us / 1e3, "decode_ms": dec_us / 1e3,
           "prefills": sum(r["calls"] for r in pre),
           "prefill_chunks": sum(r["calls"] for r in chk),
           "decode_steps": sum(r["calls"] for r in dec)}
    total = pre_us + dec_us
    if total > 0:
        out["prefill_frac"] = pre_us / total
        out["verdict"] = (
            "prefill-bound: prompt prefills stall the decode batch for a "
            "significant share of engine time — admit fewer requests per "
            "tick or shrink the chunks (InferenceEngine(prefill_chunk=))"
            if pre_us > 0.5 * total else
            "decode-bound: steady-state batched decode dominates — "
            "throughput scales with slot occupancy; raise n_slots or "
            "batch more traffic")
    if events is not None:
        # paged decode ticks say how much of their tabled width is live
        # (what the decode kernel's live-block walk has to read)
        ticks = [e.get("args") or {} for e in events
                 if e.get("name") == "serving.decode_step"]
        tabled = sum(int(a.get("decode_blocks_tabled", 0)) for a in ticks)
        if tabled:
            live = sum(int(a.get("decode_blocks_live", 0)) for a in ticks)
            out.update(decode_blocks_live=live, decode_blocks_tabled=tabled,
                       decode_blocks_live_share=live / tabled)
        rows = [(int(a["kv_rows_written"]), int(a.get("kv_rows_grid", 0)))
                for a in ticks if "kv_rows_written" in a]
        if rows:
            out.update(
                kv_rows_written_a_tick=sum(r for r, _ in rows) / len(rows),
                kv_rows_grid_a_tick=sum(g for _, g in rows) / len(rows))
        moved = [int(a["state_slots_live"]) for a in ticks
                 if "state_slots_live" in a]
        if moved:
            out.update(state_slots_live=sum(moved),
                       state_slots_live_a_tick=sum(moved) / len(moved))
        ahead = [int(a["ahead"]) for a in ticks if "ahead" in a]
        if ahead:
            out.update(
                decode_ticks_ahead=sum(ahead),
                decode_ticks_synced=len(ahead) - sum(ahead),
                decode_lanes_discarded=sum(
                    int(a.get("lanes_discarded", 0)) for a in ticks))
        for kind, name in (("decode", "serving.decode_step"),
                           ("chunk", "serving.prefill_chunk")):
            moe = [e["args"] for e in events if e.get("name") == name
                   and "moe_expert_reads" in (e.get("args") or {})]
            reads = sum(int(a["moe_expert_reads"]) for a in moe)
            if moe:
                out[f"{kind}_moe_expert_reads"] = reads
                out[f"{kind}_moe_kernel_tiles"] = sum(
                    int(a.get("moe_kernel_tiles", 0)) for a in moe)
            if reads:
                out[f"{kind}_moe_reread"] = (
                    out[f"{kind}_moe_kernel_tiles"] / reads)
        paths = [a["sample_path"] for a in ticks if "sample_path" in a]
        for path in ("greedy", "select", "sort") if paths else ():
            out[f"sample_ticks_{path}"] = paths.count(path)
            out[f"sample_{path}_share"] = paths.count(path) / len(paths)
        starve = _prefill_starvation(events)
        if starve:
            out.update(starve)
            worst = starve["max_consecutive_starved_ticks"]
            out["starvation_verdict"] = (
                "no prefill starvation: decode ran every tick that did "
                "chunked prefill work" if worst == 0 else
                f"prefill starvation: some stream waited {worst} "
                "consecutive tick(s) with no decode step — shrink "
                "prefill_chunk or admit fewer prompts per tick")
    print("\nServing engine:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<22}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def spec_report(events: list, file=None) -> dict:
    """Speculative-decoding verdict from the decode spans (ISSUE 10).

    A speculative tick tags its ``serving.decode_step`` span with
    ``{spec_k, proposed, accepted}``. Aggregated they answer the first
    question about a spec-enabled engine: is the draft EARNING its k
    extra forward passes? Each tick emits ``accepted + batch`` tokens
    for one target dispatch, so the acceptance rate directly sets the
    speedup ceiling — a rate near 0 means the engine is doing strictly
    more work than plain decode."""
    ticks = [e for e in events
             if e.get("name") == "serving.decode_step"
             and "proposed" in (e.get("args") or {})]
    if not ticks:
        return {}
    proposed = sum(int(e["args"]["proposed"]) for e in ticks)
    accepted = sum(int(e["args"]["accepted"]) for e in ticks)
    batch = sum(int(e["args"].get("batch", 0)) for e in ticks)
    rate = accepted / proposed if proposed else 0.0
    # every active stream runs one target pass per tick and emits its
    # accepted proposals + one target token, so tokens-per-pass is the
    # dispatch amortization the speculation buys
    out = {"spec_ticks": len(ticks), "proposed": proposed,
           "accepted": accepted, "acceptance_rate": rate,
           "tokens_per_target_pass":
               (accepted + batch) / batch if batch else 0.0}
    out["verdict"] = (
        f"speculation effective: {rate:.2f} of draft proposals accepted "
        f"({out['tokens_per_target_pass']:.2f} tokens per target pass)"
        if rate >= 0.5 else
        f"draft poorly matched: only {rate:.2f} of proposals accepted — "
        "use a closer draft model or lower spec_k (below ~0.3 the spec "
        "engine does more work than plain decode)")
    print("\nSpeculative decoding:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<24}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def shard_balance_report(events: list, file=None) -> dict:
    """Shard-balance verdict for multi-chip decode (ISSUE 10).

    Mesh-mode ``serving.decode_step`` spans carry ``{shards,
    shard_load: [...]}`` — the live slots per "data" shard that tick.
    SPMD decode runs at the pace of the busiest shard while every shard
    pays the full program, so sustained imbalance is pure wasted
    capacity; the verdict compares the busiest shard's share against
    the ideal 1/shards."""
    ticks = [e for e in events
             if e.get("name") == "serving.decode_step"
             and "shard_load" in (e.get("args") or {})]
    if not ticks:
        return {}
    shards = int(ticks[0]["args"].get("shards", 1))
    totals = [0] * shards
    for e in ticks:
        for d, n in enumerate(e["args"]["shard_load"]):
            totals[d] += int(n)
    grand = sum(totals)
    out = {"shards": shards, "ticks": len(ticks),
           "slot_ticks_per_shard": totals}
    if grand > 0:
        worst = max(totals) / grand
        out["busiest_shard_frac"] = worst
        ideal = 1.0 / shards
        out["verdict"] = (
            f"balanced: busiest shard carried {worst:.2f} of slot-ticks "
            f"(ideal {ideal:.2f})" if worst <= 1.5 * ideal else
            f"imbalanced: busiest shard carried {worst:.2f} of slot-ticks "
            f"(ideal {ideal:.2f}) — admission is clumping requests; check "
            "per-shard free blocks and n_slots % shards")
    print("\nShard balance:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<24}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def frontend_report(events: list, file=None) -> dict:
    """Multi-tenant front-end verdict from the frontend spans (ISSUE 11).

    The HTTP front end emits one ``frontend.request`` span per
    generation request (args: tenant, lane, status, ms, and the
    prefix_hit_rate gauge at completion) and one ``frontend.queue_wait``
    span per ADMITTED request (args: tenant, lane, wait_ms — the time
    spent in the weighted-fair-queuing lane before engine submission).
    Aggregated per tenant they answer the SLO questions: who is waiting,
    who is being throttled (429s), and whether the radix prefix cache is
    actually absorbing the prompt traffic."""
    reqs = [e for e in events if e.get("name") == "frontend.request"]
    waits = [e for e in events if e.get("name") == "frontend.queue_wait"]
    if not reqs and not waits:
        return {}
    tenants: dict = {}
    for e in reqs:
        a = e.get("args") or {}
        t = tenants.setdefault(str(a.get("tenant", "?")), {
            "lane": a.get("lane", "?"), "requests": 0, "throttled_429": 0,
            "queue_wait_ms": [], "ok": 0})
        t["requests"] += 1
        status = int(a.get("status", 0))
        if status == 429:
            t["throttled_429"] += 1
        elif status == 200:
            t["ok"] += 1
    for e in waits:
        a = e.get("args") or {}
        t = tenants.setdefault(str(a.get("tenant", "?")), {
            "lane": a.get("lane", "?"), "requests": 0, "throttled_429": 0,
            "queue_wait_ms": [], "ok": 0})
        t["queue_wait_ms"].append(float(a.get("wait_ms", 0.0)))
    rows_out = []
    for name, t in sorted(tenants.items()):
        ws = t.pop("queue_wait_ms")
        t["tenant"] = name
        t["queue_wait_ms_avg"] = round(sum(ws) / len(ws), 3) if ws else 0.0
        t["queue_wait_ms_max"] = round(max(ws), 3) if ws else 0.0
        rows_out.append(t)
    hit = next((float((e.get("args") or {}).get("prefix_hit_rate", 0))
                for e in reversed(reqs)
                if (e.get("args") or {}).get("prefix_hit_rate")
                is not None), 0.0)
    total_429 = sum(t["throttled_429"] for t in rows_out)
    worst = max(rows_out, key=lambda t: t["queue_wait_ms_max"],
                default=None)
    out = {"tenants": rows_out, "throttled_429_total": total_429,
           "prefix_hit_rate_pct": hit}
    healthy = worst is None or worst["queue_wait_ms_max"] < 1000.0
    out["verdict"] = (
        f"lanes healthy: worst queue wait "
        f"{0.0 if worst is None else worst['queue_wait_ms_max']:.1f}ms"
        + (f", {total_429} request(s) throttled per tenant contract"
           if total_429 else "")
        + f"; prefix cache serving {hit:.0f}% of prompt tokens"
        if healthy else
        f"SLO pressure: tenant {worst['tenant']} ({worst['lane']}) waited "
        f"up to {worst['queue_wait_ms_max']:.0f}ms in its lane — raise its "
        "weight, shed load (lower rate/burst), or grow the engine pool")
    print("\nServing front end:", file=file)
    for t in rows_out:
        print(f"  {t['tenant']:<16}{t['lane']:<8}req={t['requests']:<6}"
              f"429={t['throttled_429']:<5}"
              f"wait avg/max={t['queue_wait_ms_avg']:.1f}/"
              f"{t['queue_wait_ms_max']:.1f}ms", file=file)
    print(f"  prefix_hit_rate: {hit:.0f}%", file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    return out


def overload_report(events: list, file=None) -> dict:
    """Overload/brownout verdict (ISSUE 13).

    Three sources: ``serving.brownout_step`` zero-duration spans from
    the OverloadController (args: rung, rung_name, from, pressure) give
    the RUNG TIMELINE; ``frontend.request`` spans with status 503 plus
    the shed counters give the LOAD SHED view; ``serving.decode_step``
    spans carrying a ``replica`` arg plus ``router.replica_down`` spans
    give the PER-REPLICA health verdict (ticks served, died-or-healthy,
    streams failed over). An on-call human reads one question off it:
    did the ladder absorb the storm, and did anything get dropped
    silently (it must never be — sheds are 503s, deaths are failovers)."""
    steps = [e for e in events if e.get("name") == "serving.brownout_step"]
    downs = [e for e in events if e.get("name") == "router.replica_down"]
    decodes = [e for e in events if e.get("name") == "serving.decode_step"
               and (e.get("args") or {}).get("replica") is not None]
    sheds_503 = sum(1 for e in events
                    if e.get("name") == "frontend.request"
                    and int((e.get("args") or {}).get("status", 0)) == 503)
    if not steps and not downs and not decodes and not sheds_503:
        return {}
    timeline = []
    max_rung = 0
    for e in sorted(steps, key=lambda e: float(e.get("ts", 0))):
        a = e.get("args") or {}
        rung = int(a.get("rung", 0))
        max_rung = max(max_rung, rung)
        timeline.append({"t_ms": float(e.get("ts", 0)) / 1e3,
                         "rung": rung,
                         "rung_name": a.get("rung_name", "?"),
                         "from": a.get("from"),
                         "pressure": a.get("pressure")})
    final_rung = timeline[-1]["rung"] if timeline else 0
    replicas: dict = {}
    for e in decodes:
        rep = int(e["args"]["replica"])
        replicas.setdefault(rep, {"ticks": 0, "died": False,
                                  "failed_over_streams": 0})
        replicas[rep]["ticks"] += 1
    for e in downs:
        a = e.get("args") or {}
        rep = int(a.get("replica", -1))
        replicas.setdefault(rep, {"ticks": 0, "died": False,
                                  "failed_over_streams": 0})
        replicas[rep]["died"] = True
    out = {"rung_timeline": timeline, "max_rung": max_rung,
           "final_rung": final_rung, "sheds_503": sheds_503,
           "replicas": {str(k): v for k, v in sorted(replicas.items())},
           "replica_deaths": len(downs)}
    bits = []
    if timeline:
        tail = "still there" if final_rung == max_rung \
            else f"recovered to {final_rung}"
        bits.append(f"ladder climbed to rung {max_rung}, {tail}")
    else:
        bits.append("ladder never stepped")
    bits.append(f"{sheds_503} request(s) shed with 503+Retry-After"
                if sheds_503 else "no load shed")
    if replicas:
        dead = sorted(r for r, v in replicas.items() if v["died"])
        if dead:
            bits.append(f"replica(s) {dead} died — open streams failed "
                        "over to survivors")
        else:
            bits.append(f"{len(replicas)} replica(s) healthy")
    out["verdict"] = "; ".join(bits)
    print("\nOverload:", file=file)
    for row in timeline:
        print(f"  t={row['t_ms']:>12.3f}ms  rung {row['from']}->"
              f"{row['rung']} ({row['rung_name']}) "
              f"pressure={row['pressure']}", file=file)
    for rep, v in sorted(replicas.items()):
        state = "DIED" if v["died"] else "healthy"
        print(f"  replica {rep:<4}{state:<10}ticks={v['ticks']}", file=file)
    print(f"  sheds_503: {sheds_503}", file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    return out


def lifecycle_report(events: list, file=None) -> dict:
    """Replica-lifecycle verdict (ISSUE 14).

    Reads the ReplicaSupervisor's spans: ``lifecycle.restart`` (one per
    spawn attempt, with the death cause), ``lifecycle.rejoin`` (warm
    stats + orphan adoptions), ``lifecycle.quarantine`` /
    ``lifecycle.give_up`` (the ladder's upper rungs), and
    ``lifecycle.scale_up`` / ``lifecycle.scale_down`` (the autoscale
    timeline). Prints the restart-cause table, the scale-event
    timeline, and a warm verdict: did rejoined replicas come back with
    their prefix trees re-warmed, or cold?"""
    restarts = [e for e in events if e.get("name") == "lifecycle.restart"]
    rejoins = [e for e in events if e.get("name") == "lifecycle.rejoin"]
    quarantines = [e for e in events
                   if e.get("name") == "lifecycle.quarantine"]
    give_ups = [e for e in events if e.get("name") == "lifecycle.give_up"]
    scales = [e for e in events
              if e.get("name") in ("lifecycle.scale_up",
                                   "lifecycle.scale_down")]
    if not restarts and not rejoins and not scales and not give_ups:
        return {}
    causes: dict = {}
    for e in restarts:
        c = (e.get("args") or {}).get("cause", "?")
        causes[c] = causes.get(c, 0) + 1
    timeline = []
    for e in sorted(scales, key=lambda e: float(e.get("ts", 0))):
        a = e.get("args") or {}
        row = {"t_ms": float(e.get("ts", 0)) / 1e3,
               "event": e["name"].split(".", 1)[1]}
        row.update(a)
        timeline.append(row)
    warm_tokens = sum(int((e.get("args") or {}).get("warm_tokens", 0))
                      for e in rejoins)
    warm_rejoins = sum(1 for e in rejoins
                       if int((e.get("args") or {}).get("warm_tokens", 0)))
    adopted = sum(int((e.get("args") or {}).get("adopted", 0))
                  for e in rejoins)
    out = {"restarts": len(restarts), "rejoins": len(rejoins),
           "restart_causes": causes, "quarantines": len(quarantines),
           "give_ups": len(give_ups), "scale_timeline": timeline,
           "warm_tokens": warm_tokens, "adopted_streams": adopted}
    bits = []
    if restarts:
        top = max(causes.items(), key=lambda kv: kv[1])
        bits.append(f"{len(rejoins)}/{len(restarts)} restart(s) rejoined "
                    f"(top cause: {top[0]} x{top[1]})")
    if give_ups:
        bits.append(f"{len(give_ups)} replica(s) GAVE UP after exhausting "
                    "the ladder — capacity is down, page someone")
    elif quarantines:
        bits.append(f"{len(quarantines)} quarantine hold(s): a replica "
                    "is flapping")
    if timeline:
        ups = sum(1 for r in timeline if r["event"] == "scale_up")
        downs = sum(1 for r in timeline
                    if r["event"] == "scale_down"
                    and r.get("phase") == "done")
        bits.append(f"autoscale: {ups} up / {downs} down")
    if rejoins:
        bits.append(f"rejoins warm: {warm_rejoins}/{len(rejoins)} replayed "
                    f"{warm_tokens} prefix token(s)"
                    if warm_rejoins else
                    "rejoins came back COLD (no routed prefixes to replay"
                    " — expect a first-token latency dip)")
    out["verdict"] = "; ".join(bits) if bits else "no lifecycle events"
    print("\nReplica lifecycle:", file=file)
    for c, n in sorted(causes.items(), key=lambda kv: -kv[1]):
        print(f"  restart cause {c:<24}{n:>6}", file=file)
    for row in timeline:
        extra = {k: v for k, v in row.items() if k not in ("t_ms", "event")}
        print(f"  t={row['t_ms']:>12.3f}ms  {row['event']}"
              + (f"  {extra}" if extra else ""), file=file)
    if give_ups:
        for e in give_ups:
            print(f"  GAVE UP: {e.get('args')}", file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    return out


def resilience_report(events: list, rows: list, file=None,
                      gauges: dict | None = None) -> dict:
    """Self-healing verdict from the resilience spans (ISSUE 5).

    TrainGuardian emits ``resilience.snapshot`` / ``resilience.rollback``
    / ``resilience.preempt_save`` spans and ``resilience.trip`` instants.
    This prints the trip/rollback/preemption timeline and a one-line
    verdict: a healthy run snapshots and nothing else; trips without
    rollbacks mean the in-jit gate absorbed them; rollbacks/preemption
    are the events an on-call human wants timestamped. ``gauges`` (a
    stat_snapshot dict) adds the counter view when provided."""
    res = [e for e in events
           if str(e.get("name", "")).startswith("resilience.")]
    if not res and not gauges:
        return {}
    counts: dict = {}
    timeline = []
    for e in sorted(res, key=lambda e: float(e.get("ts", 0))):
        name = e["name"].split(".", 1)[1]
        counts[name] = counts.get(name, 0) + 1
        if name != "snapshot":  # snapshots are cadence noise on the timeline
            entry = {"t_ms": float(e.get("ts", 0)) / 1e3, "event": name}
            entry.update(e.get("args") or {})
            timeline.append(entry)
    out = {"counts": counts, "timeline": timeline}
    if gauges:
        out["gauges"] = {k: gauges[k] for k in
                         ("faults_injected", "sentinel_trips", "rollbacks",
                          "preempt_saves", "watchdog_stalls",
                          "elastic_resizes", "pod_hosts_alive",
                          "serving_watchdog_trips",
                          "serving_watchdog_restarts")
                         if k in gauges}
    # pod timeline (ISSUE 12): pod-attached guardians tag their spans
    # with a host arg — merge them into a per-host event matrix plus an
    # elastic-resize verdict, so an on-call human sees which host
    # snapshotted/rolled back/resized when, in ONE view
    hosts = sorted({(e.get("args") or {}).get("host") for e in res
                    if (e.get("args") or {}).get("host") is not None})
    resizes = [e for e in res if e.get("name") == "resilience.resize"]
    if hosts or resizes:
        per_host: dict = {h: {} for h in hosts}
        merged = []
        for e in sorted(res, key=lambda e: float(e.get("ts", 0))):
            name = e["name"].split(".", 1)[1]
            a = e.get("args") or {}
            h = a.get("host")
            if h is not None:
                per_host.setdefault(h, {})
                per_host[h][name] = per_host[h].get(name, 0) + 1
            if name in ("rollback", "resize", "pod_agree", "preempt_save"):
                row = {"t_ms": float(e.get("ts", 0)) / 1e3, "event": name}
                row.update(a)
                merged.append(row)
        if resizes:
            a = resizes[-1].get("args") or {}
            rv = (f"resized: lost {a.get('lost')} -> replanned over "
                  f"{a.get('devices')} device(s), resumed from step "
                  f"{a.get('step')}")
        else:
            rv = "no resize: pod membership stable"
        out["pod"] = {"hosts": hosts, "per_host": per_host,
                      "timeline": merged, "resize_verdict": rv}
    # spans are authoritative (scoped to this trace); gauges are process-
    # cumulative, so they only speak when the trace has no spans at all
    src = counts if res else {
        "trip": (gauges or {}).get("sentinel_trips", 0),
        "rollback": (gauges or {}).get("rollbacks", 0),
        "preempt_save": (gauges or {}).get("preempt_saves", 0)}
    trips = src.get("trip", 0)
    rollbacks = src.get("rollback", 0)
    preempts = src.get("preempt_save", 0)
    if preempts:
        out["verdict"] = ("preempted: a priority checkpoint was forced — "
                         "expect a relaunch resuming from it")
    elif rollbacks:
        out["verdict"] = (f"unhealthy: {trips} sentinel trip(s) escalated "
                          f"to {rollbacks} rollback(s) — inspect the data/"
                          "lr around the rollback timestamps")
    elif trips:
        out["verdict"] = (f"recovered: {trips} sentinel trip(s) absorbed "
                          "by the in-jit skip gate, no rollback needed")
    else:
        out["verdict"] = "healthy: snapshots only, no trips"
    print("\nResilience:", file=file)
    for k, v in counts.items():
        print(f"  {k:<22}{v:>12}", file=file)
    for g, v in out.get("gauges", {}).items():
        print(f"  gauge {g:<16}{v:>12}", file=file)
    for entry in timeline:
        extra = {k: v for k, v in entry.items() if k not in ("t_ms", "event")}
        print(f"  t={entry['t_ms']:>12.3f}ms  {entry['event']}"
              + (f"  {extra}" if extra else ""), file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    if "pod" in out:
        pod = out["pod"]
        print("  Pod timeline:", file=file)
        for h in pod["hosts"]:
            ev = ", ".join(f"{k}x{v}" for k, v in
                           sorted(pod["per_host"][h].items()))
            print(f"    {h:<10}{ev}", file=file)
        for row in pod["timeline"]:
            extra = {k: v for k, v in row.items()
                     if k not in ("t_ms", "event")}
            print(f"    t={row['t_ms']:>12.3f}ms  {row['event']}"
                  + (f"  {extra}" if extra else ""), file=file)
        print(f"    resize verdict: {pod['resize_verdict']}", file=file)
    return out


_RID_CHAIN = ("serving.queue_wait", "serving.admit_to_first",
              "serving.request_done")


def request_report(events: list, file=None, top: int = 5) -> dict:
    """Per-request critical path from the causal trace context
    (ISSUE 15).

    Every span a request touches is stamped with its ``trace`` id:
    ``frontend.admission`` (the clock start), ``frontend.queue_wait``
    (WFQ lane wait in ``wait_ms``), ``serving.prefill`` /
    ``serving.prefill_chunk`` (prompt work), ``serving.decode_tick``
    (this request's share of each batched decode tick),
    ``serving.failover_hop`` (replica hops survived) and
    ``serving.request_done`` (the clock stop + finish reason). Grouped
    by trace id they answer THE latency question — where did this
    request's time go: lane wait, prefill, decode, or unattributed
    STALL (scheduler queueing between ticks, failover gaps) — and the
    slowest-N breakdown says whether the tail is an admission problem
    or a decode problem. The engine's ``serving.queue_wait`` (submit →
    admit) counts as lane wait; a request no front end traced is still
    reported, from its rid-keyed chain (``serving.queue_wait`` →
    ``serving.admit_to_first`` → ``serving.request_done``)."""
    traces: dict = {}
    for e in events:
        a = e.get("args") or {}
        tid = a.get("trace")
        if tid is None and e.get("name") in _RID_CHAIN \
                and a.get("rid") is not None:
            # no front end minted a context: the engine's own chain
            # (queue_wait -> admit_to_first -> request_done) is keyed
            # by request id; negative keys cannot meet a trace id
            tid = -1 - int(a["rid"])
        if tid is not None:
            traces.setdefault(tid, []).append(e)
    if not traces:
        return {}
    rows = []
    for tid, evs in traces.items():
        evs.sort(key=lambda e: float(e.get("ts", 0)))
        a_of = lambda e: e.get("args") or {}      # noqa: E731
        done = [e for e in evs if e["name"] == "serving.request_done"]
        t0 = float(evs[0]["ts"])
        t1 = float(done[-1]["ts"]) if done else max(
            float(e.get("ts", 0)) + float(e.get("dur", 0)) for e in evs)
        lane_ms = sum(float(a_of(e).get("wait_ms", 0.0)) for e in evs
                      if e["name"] == "frontend.queue_wait")
        prefill_ms = sum(float(e.get("dur", 0)) for e in evs
                         if e["name"] in ("serving.prefill",
                                          "serving.prefill_chunk")) / 1e3
        decode_ms = sum(float(e.get("dur", 0)) for e in evs
                        if e["name"] == "serving.decode_tick") / 1e3
        # the engine's own queue (submit -> admit) and admit -> first
        # token: where a paged prompt waits behind other requests' work
        queue_ms = sum(float(e.get("dur", 0)) for e in evs
                       if e["name"] == "serving.queue_wait") / 1e3
        first = [e for e in evs if e["name"] == "serving.admit_to_first"]
        first_ms = sum(float(e.get("dur", 0)) for e in first) / 1e3
        hops = [e for e in evs if e["name"] == "serving.failover_hop"]
        total_ms = (t1 - t0) / 1e3
        if tid < 0:
            # rid-keyed chain: no per-request chunk or tick spans, so
            # admit -> first token stands for prefill, the rest is decode
            prefill_ms = first_ms
            decode_ms = max(0.0, total_ms - queue_ms - first_ms)
        stall_ms = max(0.0, total_ms - lane_ms - queue_ms - prefill_ms
                       - decode_ms)
        phases = {"lane_wait": lane_ms + queue_ms, "prefill": prefill_ms,
                  "decode": decode_ms, "stall": stall_ms}
        replicas = sorted({a_of(e)["replica"] for e in evs
                           if a_of(e).get("replica") is not None})
        rows.append({
            "trace": tid if tid >= 0 else None,
            "rid": a_of(evs[0]).get("rid") if tid < 0 else None,
            "total_ms": round(total_ms, 3),
            "lane_wait_ms": round(lane_ms + queue_ms, 3),
            "queue_ms": round(queue_ms, 3),
            "admit_to_first_ms": round(first_ms, 3),
            "chunks": a_of(first[-1]).get("chunks") if first else None,
            "prefill_ms": round(prefill_ms, 3),
            "decode_ms": round(decode_ms, 3),
            "stall_ms": round(stall_ms, 3),
            "decode_ticks": sum(1 for e in evs
                                if e["name"] == "serving.decode_tick"),
            "prefill_chunks": sum(1 for e in evs
                                  if e["name"] == "serving.prefill_chunk"),
            "hops": len(hops),
            "hop_path": [(a_of(e).get("hop_from"), a_of(e).get("hop_to"))
                         for e in hops],
            "replicas": replicas,
            "tokens": a_of(done[-1]).get("tokens") if done else None,
            "finish": a_of(done[-1]).get("reason") if done else None,
            "critical_phase": max(phases, key=phases.get),
        })
    rows.sort(key=lambda r: -r["total_ms"])
    n = len(rows)
    agg = {k: sum(r[k] for r in rows)
           for k in ("lane_wait_ms", "prefill_ms", "decode_ms", "stall_ms")}
    total = sum(agg.values()) or 1.0
    worst = rows[0]
    out = {"requests": n, "completed": sum(1 for r in rows if r["finish"]),
           "failovers_survived": sum(r["hops"] for r in rows),
           "phase_fractions": {k: round(v / total, 4)
                               for k, v in agg.items()},
           "slowest": rows[:top]}
    out["verdict"] = (
        f"{n} traced request(s); slowest spent "
        f"{worst['total_ms']:.1f}ms, dominated by {worst['critical_phase']}"
        + (f", surviving {worst['hops']} failover hop(s) across replicas "
           f"{worst['replicas']}" if worst["hops"] else "")
        + "; fleet-wide split "
        + ", ".join(f"{k} {v:.0%}"
                    for k, v in out["phase_fractions"].items()))
    print("\nRequest critical paths (slowest first):", file=file)
    print(f"  {'trace':<16}{'total':>9}{'lane':>8}{'prefill':>9}"
          f"{'decode':>8}{'stall':>8}{'hops':>6}  finish", file=file)
    for r in rows[:top]:
        who = f"rid:{r['rid']}" if r["trace"] is None \
            else f"{r['trace']:x}"
        print(f"  {who:<16}{r['total_ms']:>9.1f}"
              f"{r['lane_wait_ms']:>8.1f}{r['prefill_ms']:>9.1f}"
              f"{r['decode_ms']:>8.1f}{r['stall_ms']:>8.1f}"
              f"{r['hops']:>6}  {r['finish']}", file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    return out


def flight_report(flights: list, file=None) -> dict:
    """Flight-recorder dump summaries (ISSUE 15): one row per dump —
    host, reason, event count, the gauge highlights an on-call human
    triages by — plus a merged verdict when dumps from several hosts
    were loaded together."""
    flights = [f for f in flights if f]
    if not flights:
        return {}
    rows = []
    for fl in flights:
        g = fl.get("gauges", {})
        rows.append({
            "host": fl.get("host", "?"), "pid": fl.get("pid"),
            "reason": fl.get("reason", "?"), "events": fl.get("events", 0),
            "watchdog_trips": g.get("serving_watchdog_trips", 0),
            "restarts": g.get("serving_replica_restarts", 0),
            "failovers": g.get("router_failovers", 0),
            "rollbacks": g.get("rollbacks", 0),
        })
    hosts = sorted({r["host"] for r in rows})
    out = {"dumps": rows, "hosts": hosts}
    out["verdict"] = (
        f"{len(rows)} flight dump(s) from host(s) {hosts}: "
        + "; ".join(f"{r['host']} dumped on '{r['reason']}' with "
                    f"{r['events']} ring event(s)" for r in rows))
    print("\nFlight recorder:", file=file)
    for r in rows:
        print(f"  {r['host']:<8}pid={r['pid']:<8}{r['reason']:<36}"
              f"events={r['events']:<6}failovers={r['failovers']} "
              f"restarts={r['restarts']}", file=file)
    print(f"  verdict: {out['verdict']}", file=file)
    return out


def embedding_report(events: list, file=None) -> dict:
    """Sparse embedding verdict (ISSUE 16).

    ``sparse.step`` spans (SparseTrainStep) carry ``{lookup_ids,
    unique_ids, exchange_bytes, shards}``; ``sparse.lookup`` spans
    (ShardedEmbedding.lookup / serving EmbeddingRanker) carry ``{ids,
    exchange_bytes, shards}``. Together they answer the two questions
    that decide a recommender run's health: how much wire the all-to-all
    id exchange is moving, and whether the batches are duplicate-heavy
    enough (low unique ratio) for the SelectedRows merge + lazy rows to
    be paying off."""
    steps = [e for e in events if e.get("name") == "sparse.step"
             and "lookup_ids" in (e.get("args") or {})]
    lookups = [e for e in events if e.get("name") == "sparse.lookup"
               and "ids" in (e.get("args") or {})]
    if not steps and not lookups:
        return {}
    out: dict = {}
    total_ids = sum(int(e["args"]["lookup_ids"]) for e in steps) + \
        sum(int(e["args"]["ids"]) for e in lookups)
    xbytes = sum(int(e["args"].get("exchange_bytes", 0))
                 for e in steps + lookups)
    shards = max([int(e["args"].get("shards", 1))
                  for e in steps + lookups], default=1)
    out["train_steps"] = len(steps)
    out["serve_lookups"] = len(lookups)
    out["lookup_ids"] = total_ids
    out["exchange_bytes"] = xbytes
    out["shards"] = shards
    if steps:
        uniq = sum(int(e["args"]["unique_ids"]) for e in steps)
        ids = sum(int(e["args"]["lookup_ids"]) for e in steps)
        ratio = uniq / ids if ids else 1.0
        out["unique_ratio"] = ratio
        out["rows_touched_per_step"] = uniq / len(steps)
        out["verdict"] = (
            f"duplicate-heavy batches ({ratio:.2f} unique): the "
            "unique+segment_sum merge and lazy rows are earning their "
            "keep" if ratio < 0.7 else
            f"mostly-unique ids ({ratio:.2f}): sparse path is "
            "correctness-only here — wins come from the row-sharded "
            "table HBM, not gradient dedup")
    else:
        out["verdict"] = (
            f"serving-only lookups over {shards} shard(s), "
            f"{xbytes} exchange bytes")
    print("\nSparse embeddings:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<24}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def moe_report(events: list, file=None) -> dict:
    """Mixture-of-experts routing verdict (ISSUE 18).

    ``serving.decode_step`` spans from an MoE engine carry
    ``{moe_busiest_pct, moe_dropped}`` per tick (engine._note_moe).
    The report answers the one question that decides MoE serving
    health: is the router balanced?  A uniform router puts 100/E % on
    the busiest expert; a collapsed router puts ~100 % there, which
    serialises every token through one expert's FFN and wastes the
    other E-1 shards."""
    ticks = [e for e in events if e.get("name") == "serving.decode_step"
             and "moe_busiest_pct" in (e.get("args") or {})]
    if not ticks:
        return {}
    busiest = [float(e["args"]["moe_busiest_pct"]) for e in ticks]
    dropped = sum(int(e["args"].get("moe_dropped", 0)) for e in ticks)
    out: dict = {
        "ticks": len(ticks),
        "busiest_expert_pct_avg": sum(busiest) / len(busiest),
        "busiest_expert_pct_max": max(busiest),
        "tokens_dropped": dropped,
    }
    avg = out["busiest_expert_pct_avg"]
    # uniform-router baseline is 100/E, but E isn't in the span; grade
    # on absolute share — >50 % means one expert owns the majority of
    # every tick regardless of E
    out["verdict"] = (
        f"router collapse: busiest expert averages {avg:.1f}% of routed "
        "tokens — raise moe_aux_weight or re-init the router"
        if avg > 50.0 else
        f"imbalanced but working ({avg:.1f}% busiest): aux loss is "
        "holding the router short of collapse" if avg > 25.0 else
        f"balanced router ({avg:.1f}% busiest expert)")
    if dropped:
        out["verdict"] += f"; {dropped} routed assignments dropped"
    print("\nMixture of experts:", file=file)
    for k, v in out.items():
        if isinstance(v, float):
            print(f"  {k:<24}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def fleet_report(events: list, file=None) -> dict:
    """Cross-host serving fleet verdict (ISSUE 19).

    Reads the spans ``serving/pod.py`` emits: ``fleet.members``
    (membership snapshot per change), ``fleet.kv_stream`` (one per
    disaggregated prefill->decode KV transfer, with bytes/ms/matched),
    ``fleet.direct`` (disagg fallback, with reason), ``fleet.host_lost``
    (rerouted stream count) and ``fleet.prewarm``. When the trace is a
    ``merge_traces`` stitch of per-host flight dumps, the process-name
    lanes also split prefill vs decode wall time per host."""
    def _args(e):
        return e.get("args") or {}

    members = [e for e in events if e.get("name") == "fleet.members"]
    streams = [e for e in events if e.get("name") == "fleet.kv_stream"]
    directs = [e for e in events if e.get("name") == "fleet.direct"]
    lost = [e for e in events if e.get("name") == "fleet.host_lost"]
    prewarms = [e for e in events if e.get("name") == "fleet.prewarm"]
    breakers = [e for e in events if e.get("name") == "rpc.breaker_open"]
    collects = [e for e in events if e.get("name") == "fleet.collect"]
    if not (members or streams or directs or lost or prewarms
            or breakers or collects):
        return {}
    out: dict = {}

    # -- per-host replica table (last membership snapshot wins) -----------
    hosts = dict(_args(members[-1]).get("hosts") or {}) if members else {}
    lost_hosts = sorted({str(_args(e).get("host")) for e in lost})
    # per-host prefill/decode wall time: merge_traces names each process
    # lane "<host> pid=N", so pid -> host recovers the split
    pid_host = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            label = str(_args(e).get("name", ""))
            if " pid=" in label:
                pid_host[e.get("pid")] = label.split(" pid=")[0]
    _PREFILL = ("serving.prefill", "serving.prefill_chunk")
    util: dict = {}     # host -> [prefill_us, decode_us]
    marks: dict = {}    # (pid, tid) -> [(name, ts)]
    for e in events:
        name, ph = e.get("name", ""), e.get("ph")
        if name not in _PREFILL and name != "serving.decode_step":
            continue
        host = pid_host.get(e.get("pid"), "?")
        if ph == "X":
            util.setdefault(host, [0.0, 0.0])[
                0 if name in _PREFILL else 1] += float(e.get("dur", 0))
        elif ph == "B":
            marks.setdefault((e.get("pid"), e.get("tid")), []).append(
                (name, float(e.get("ts", 0))))
        elif ph == "E":
            stack = marks.get((e.get("pid"), e.get("tid")))
            if stack:
                bname, bts = stack.pop()
                util.setdefault(host, [0.0, 0.0])[
                    0 if bname in _PREFILL else 1] += \
                    float(e.get("ts", 0)) - bts
    table = []
    for h in sorted(set(hosts) | set(util) | set(lost_hosts)):
        rec = hosts.get(h, {})
        pf_us, dec_us = util.get(h, (0.0, 0.0))
        table.append({"host": h, "role": rec.get("role", "?"),
                      "replicas": rec.get("replicas", "?"),
                      "lost": h in lost_hosts,
                      "prefill_ms": pf_us / 1e3, "decode_ms": dec_us / 1e3})
    out["hosts"] = table

    # -- KV streaming ------------------------------------------------------
    n_direct = len(directs)
    if streams:
        ms = sorted(float(_args(e).get("ms", 0.0)) for e in streams)
        nbytes = sum(int(_args(e).get("bytes", 0)) for e in streams)
        out["kv_transfers"] = len(streams)
        out["kv_bytes"] = nbytes
        out["kv_tokens_streamed"] = sum(int(_args(e).get("matched", 0))
                                        for e in streams)
        out["kv_ms_p50"] = ms[len(ms) // 2]
        out["kv_ms_max"] = ms[-1]
        secs = sum(ms) / 1e3
        out["kv_mib_per_s"] = (nbytes / (1 << 20)) / secs if secs else 0.0
        # ISSUE 20: resumable chunked streaming telemetry
        out["kv_chunks"] = sum(int(_args(e).get("chunks", 0))
                               for e in streams)
        out["kv_resumed_streams"] = sum(
            1 for e in streams if _args(e).get("resumed"))
        fb = [float(_args(e)["first_block_ms"]) for e in streams
              if _args(e).get("first_block_ms") is not None]
        if fb:
            fb.sort()
            out["kv_first_block_ms_p50"] = fb[len(fb) // 2]
    out["direct_fallbacks"] = n_direct
    if n_direct:
        reasons: dict = {}
        for e in directs:
            r = str(_args(e).get("reason", "?"))
            reasons[r] = reasons.get(r, 0) + 1
        out["fallback_reasons"] = dict(sorted(reasons.items()))
    total = len(streams) + n_direct
    out["disagg_frac"] = len(streams) / total if total else 0.0
    out["hosts_lost"] = len(lost)
    out["streams_rerouted"] = sum(int(_args(e).get("rerouted", 0))
                                  for e in lost)
    out["replicas_prewarmed"] = sum(int(_args(e).get("added", 0))
                                    for e in prewarms)

    # -- network incidents + fleet postmortem (ISSUE 20) -------------------
    if breakers:
        by_peer: dict = {}
        for e in breakers:
            p = str(_args(e).get("peer", "?"))
            by_peer[p] = by_peer.get(p, 0) + 1
        out["breaker_opens"] = dict(sorted(by_peer.items()))
    if collects:
        out["flight_collections"] = []
        for e in collects:
            a = _args(e)
            out["flight_collections"].append(
                {"reason": str(a.get("reason", "?")),
                 "hosts_ok": list(a.get("hosts_ok") or ()),
                 "gaps": list(a.get("gaps") or ()),
                 "unarmed": list(a.get("unarmed") or ())})

    # -- verdict -----------------------------------------------------------
    if streams:
        out["verdict"] = (
            f"{len(streams)}/{total} long prompts prefilled remotely "
            f"({out['kv_bytes'] / (1 << 20):.1f} MiB of KV streamed at "
            f"{out['kv_mib_per_s']:.0f} MiB/s, p50 {out['kv_ms_p50']:.1f} "
            "ms): disaggregation is carrying prefill off the decode "
            "hosts" if out["disagg_frac"] >= 0.5 else
            f"only {len(streams)}/{total} disagg submissions landed — "
            "check fallback_reasons; decode hosts are still running "
            "most prefills")
    elif n_direct:
        out["verdict"] = (f"no KV stream completed ({n_direct} "
                          "fallback(s)) — disagg path is configured but "
                          "never succeeding; see fallback_reasons")
    else:
        out["verdict"] = "fleet registered; no disaggregated traffic seen"
    if lost:
        out["verdict"] += (f"; {len(lost)} host-loss event(s) rerouted "
                           f"{out['streams_rerouted']} stream(s)")
    if out.get("kv_resumed_streams"):
        out["verdict"] += (f"; {out['kv_resumed_streams']} stream(s) "
                           "resumed from received blocks after a "
                           "mid-transfer prefill loss")
    if breakers:
        out["verdict"] += (f"; circuit breakers opened "
                           f"{len(breakers)} time(s) on "
                           f"{len(out['breaker_opens'])} peer(s)")
    if collects:
        gaps = sorted({h for c in out["flight_collections"]
                       for h in c["gaps"]})
        out["verdict"] += (
            f"; {len(collects)} fleet flight collection(s)"
            + (f" with unreachable host(s) {gaps} recorded as gaps"
               if gaps else " covered every host"))

    print("\nServing fleet:", file=file)
    for r in table:
        flag = "LOST" if r["lost"] else ""
        print(f"  {str(r['host']):<12}{str(r['role']):<9}"
              f"replicas={str(r['replicas']):<4}"
              f"prefill_ms={r['prefill_ms']:<10.1f}"
              f"decode_ms={r['decode_ms']:<10.1f}{flag}", file=file)
    for k, v in out.items():
        if k == "hosts":
            continue
        if isinstance(v, float):
            print(f"  {k:<24}{v:>12.3f}", file=file)
        else:
            print(f"  {k}: {v}", file=file)
    return out


def report(rows: list, top: int = 20, file=None) -> list:
    rows = rows[:top]
    if not rows:
        print("no span events found", file=file)
        return rows
    print(f"{'Span':<48}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"
          f"{'Max(ms)':>12}", file=file)
    for r in rows:
        print(f"{r['name'][:47]:<48}{r['calls']:>8}"
              f"{r['total_us'] / 1e3:>12.3f}{r['avg_us'] / 1e3:>12.3f}"
              f"{r['max_us'] / 1e3:>12.3f}", file=file)
    return rows


# the one CLI's section registry (ISSUE 15 satellite): name ->
# callable(ctx, file) -> result. ``ctx`` carries events/rows/top/flights
# so each section keeps its historical function signature for direct
# callers (tests) while the CLI drives them uniformly.
SECTIONS = {
    "spans": lambda c, f: report(c["rows"], c["top"], file=f),
    "input_pipeline": lambda c, f: input_pipeline_report(c["rows"], file=f),
    "overlap": lambda c, f: overlap_report(c["rows"], file=f),
    "kernels": lambda c, f: kernels_report(c["events"], file=f),
    "serving": lambda c, f: serving_report(c["rows"], file=f,
                                           events=c["events"]),
    "spec": lambda c, f: spec_report(c["events"], file=f),
    "shard_balance": lambda c, f: shard_balance_report(c["events"], file=f),
    "frontend": lambda c, f: frontend_report(c["events"], file=f),
    "overload": lambda c, f: overload_report(c["events"], file=f),
    "lifecycle": lambda c, f: lifecycle_report(c["events"], file=f),
    "resilience": lambda c, f: resilience_report(c["events"], c["rows"],
                                                 file=f),
    "recompile": lambda c, f: recompile_report(c["events"], file=f),
    "pipeline": lambda c, f: pipeline_report(c["events"], file=f),
    "request": lambda c, f: request_report(c["events"], file=f,
                                           top=c["top"]),
    "flight": lambda c, f: flight_report(c["flights"], file=f),
    "embedding": lambda c, f: embedding_report(c["events"], file=f),
    "moe": lambda c, f: moe_report(c["events"], file=f),
    "fleet": lambda c, f: fleet_report(c["events"], file=f),
}


def run_sections(events: list, top: int = 20, flights: list | None = None,
                 sections=None, file=None) -> dict:
    """Run the requested (default: all) sections over one merged event
    list; returns {section: result} with empty sections dropped."""
    ctx = {"events": events, "rows": aggregate(events), "top": top,
           "flights": flights or []}
    out = {}
    for name in (sections or SECTIONS):
        if name not in SECTIONS:
            raise KeyError(f"unknown section {name!r} "
                           f"(choose from {sorted(SECTIONS)})")
        result = SECTIONS[name](ctx, file)
        if result:
            out[name] = result
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="*",
                    help="chrome-trace JSON file(s); several (e.g. "
                         "per-host flight dumps) merge into one timeline")
    ap.add_argument("--top", type=int, default=20,
                    help="number of spans/requests to print (by total "
                         "time)")
    ap.add_argument("--section", action="append", default=None,
                    metavar="NAME",
                    help="print only this section (repeatable; default "
                         "all) — see --list-sections")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable {section: result} on stdout "
                         "(for CI consumption)")
    ap.add_argument("--list-sections", action="store_true")
    args = ap.parse_args(argv)
    if args.list_sections:
        for name in SECTIONS:
            print(name)
        return {}
    if not args.trace:
        ap.error("at least one trace file is required")
    traces = [load_trace(p) for p in args.trace]
    events = merge_traces(traces)
    flights = [t["flight"] for t in traces if t["flight"]]
    sink = io.StringIO() if args.as_json else None
    out = run_sections(events, top=args.top, flights=flights,
                       sections=args.section, file=sink)
    if args.as_json:
        print(json.dumps(out, indent=2, default=str))
    return out


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
