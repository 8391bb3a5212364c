"""The serve engine's scope readers and ``idle_with_work_share.serve``,
with no chip: over contexts built by hand (device events as the profiler
names them, program events as the engine writes them)."""
import importlib.util
import os
import types

import pytest

from benchmarks.lib import spec as spec_mod, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
E = xplane.Event
SCOPE_METRICS = ["tick_attn_ms.serve", "tick_ffn_ms.serve",
                 "tick_head_ms.serve", "tick_other_ms.serve",
                 "chunk_attn_ms.serve", "chunk_ffn_ms.serve"]
NEW = SCOPE_METRICS + ["idle_with_work_share.serve"]
SERVE = ["serve.gpt_1p3b.chat", "serve.gpt_1p3b.longprompt",
         "serve.sarvam_105b_ep4.docqa", "serve.brumby_14b_l8.digest"]


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, ts_us, dur_us, **args):
    return {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
            "args": args}


def table(program, signature, scopes):
    return {"name": "op_scopes", "ph": "M", "pid": 1, "tid": 0,
            "args": {"program": program, "signature": signature,
                     "scopes": scopes}}


ENGINE = {"name": "serving_engine", "ph": "M", "pid": 1, "tid": 0,
          "args": {"spans": ["serving.idle"], "tables": 3, "seconds": 0.5}}

# two width buckets of the tick agree on all but fusion.6; the chunk's
# fusion.1 is another program's instruction and clashes with nothing
TICK_W4 = {"fusion.1": "forward/attn", "pallas_paged_decode.2":
           "forward/attn", "fusion.2": "forward/mlp",
           "fusion.3": "forward/sampling", "fusion.4": "forward/ln",
           "fusion.5": "forward", "fusion.6": "forward/attn"}
TICK_W8 = dict(TICK_W4, **{"fusion.6": "forward/mlp"})
CHUNK = {"fusion.1": "forward/mlp", "fusion.7": "forward/kv_pool",
         "fusion.8": "forward/experts"}


def tick_ops(t0):
    """One tick's ops (ns): attn 100 + 50, mlp 200, sampling 40, ln 30,
    no scope 20, clashing 10, no label 5."""
    return [E("%fusion.1 = bf16[] fusion()", t0, 100.0),
            E("%pallas_paged_decode.2 = bf16[] custom-call()", t0 + 100,
              50.0),
            E("%fusion.2 = bf16[] fusion()", t0 + 150, 200.0),
            E("%fusion.3 = s32[] fusion()", t0 + 350, 40.0),
            E("%fusion.4 = f32[] fusion()", t0 + 390, 30.0),
            E("%fusion.5 = f32[] fusion()", t0 + 420, 20.0),
            E("%fusion.6 = f32[] fusion()", t0 + 440, 10.0),
            E("%copy-done.9 = f32[] copy-done()", t0 + 450, 5.0)]


def scope_ctx(events=None, ticks=("jit__decode_paged_fn(3)",
                                  "jit__decode_paged_fn(4)",
                                  "jit__decode_paged_fn(3)")):
    ops, mods = [], []
    for name, t0 in zip(ticks, (0.0, 1000.0, 2000.0)):
        mods.append(E(name, t0, 500.0))
        ops += tick_ops(t0)
    mods.append(E("jit__chunk_fn(7)", 3000.0, 900.0))
    ops += [E("%fusion.1 = bf16[] fusion()", 3000.0, 300.0),
            E("%fusion.7 = bf16[] fusion()", 3300.0, 200.0),
            E("%fusion.8 = bf16[] fusion()", 3500.0, 100.0),
            E("%while.3 = () while()", 3600.0, 250.0)]
    # an op outside every run is no program's
    ops.append(E("%fusion.2 = bf16[] fusion()", 9000.0, 999.0))
    if events is None:
        events = [table("jit__decode_paged_fn", "width 4", TICK_W4),
                  table("jit__decode_paged_fn", "width 8", TICK_W8),
                  table("jit__chunk_fn", "chunk 128 width 8", CHUNK),
                  ENGINE]
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(ops={0: ops}, modules={0: mods}),
        program_events=events)


@pytest.mark.parametrize("name, ns", [
    ("tick_attn_ms.serve", 150.0), ("tick_ffn_ms.serve", 200.0),
    ("tick_head_ms.serve", 40.0),
    ("tick_other_ms.serve", 30.0 + 20.0 + 10.0 + 5.0),
    ("chunk_attn_ms.serve", 200.0), ("chunk_ffn_ms.serve", 400.0)])
def test_scope_groups_a_run(name, ns):
    assert reader(name).read(scope_ctx()) == pytest.approx(ns / 1e6)


def test_the_groups_add_up_and_a_clash_is_unplaced(capsys):
    ctx = scope_ctx()
    got = [reader(n).read(ctx) for n in SCOPE_METRICS[:4]]
    mod = reader("tick_attn_ms.serve").serve_scopes
    tick = mod.tables(ctx)[mod.TICK]
    assert tick["runs"] == 3 and tick["programs"] == ["jit__decode_paged_fn"]
    assert sum(got) == pytest.approx(tick["busy_ms"])
    # fusion.6 is attn in one bucket, mlp in the other: unplaced, as is
    # copy-done, which no table names
    assert tick["scope_ms"][mod.UNPLACED] == pytest.approx(15e-6)
    assert dict(tick["unplaced_top"]) == pytest.approx(
        {"fusion": 10e-6, "copy-done": 5e-6})
    assert tick["run_ms"] == pytest.approx(500e-6)
    out = capsys.readouterr().out
    # logged once a run of the benchmark, whatever reads it
    assert out.count("_decode_paged_fn by scope") == 1
    assert out.count("_chunk_fn by scope") == 1
    assert "['pallas_paged_decode', 'attn'," in out
    assert "wrote 3 tables in 0.5 s" in out


def test_a_program_a_signature_finds_its_own_table():
    """The engine names each signature a program of its own: the clash
    of one name across two tables is then no clash, and the kind's
    metrics sum over its programs' runs."""
    events = [table("jit__decode_paged_fn_w4", "w4", TICK_W4),
              table("jit__decode_paged_fn_w8", "w8", TICK_W8),
              table("jit__chunk_fn", "c128_w8", CHUNK), ENGINE]
    ctx = scope_ctx(events, ticks=("jit__decode_paged_fn_w4(3)",
                                   "jit__decode_paged_fn_w8(4)",
                                   "jit__decode_paged_fn_w4(3)"))
    mod = reader("tick_attn_ms.serve").serve_scopes
    # fusion.6: attn in the two runs of w4, mlp in the one of w8
    assert reader("tick_attn_ms.serve").read(ctx) \
        == pytest.approx((150.0 + 2 * 10.0 / 3) / 1e6)
    assert reader("tick_ffn_ms.serve").read(ctx) \
        == pytest.approx((200.0 + 10.0 / 3) / 1e6)
    tick = mod.tables(ctx)[mod.TICK]
    assert tick["programs"] == ["jit__decode_paged_fn_w4",
                                "jit__decode_paged_fn_w8"]
    assert tick["scope_ms"][mod.UNPLACED] == pytest.approx(5e-6)
    assert reader("chunk_ffn_ms.serve").read(ctx) == pytest.approx(400e-6)


@pytest.mark.parametrize("name", SCOPE_METRICS)
@pytest.mark.parametrize("ctx", [
    scope_ctx(events=[ENGINE]),                           # the parent
    scope_ctx(events=[table("jit__other_fn", "x", CHUNK), ENGINE]),
    types.SimpleNamespace(trace=None, program_events=[
        table("jit__decode_paged_fn", "width 4", TICK_W4),
        table("jit__chunk_fn", "chunk 128 width 8", CHUNK)]),
    types.SimpleNamespace(
        trace=types.SimpleNamespace(ops={0: []}, modules={0: []}),
        program_events=[table("jit__decode_paged_fn", "width 4", TICK_W4),
                        table("jit__chunk_fn", "c", CHUNK)])],
    ids=["no_tables", "program_never_traced", "no_trace", "no_run"])
def test_scope_readers_say_nothing_without_a_table_and_a_run(name, ctx):
    assert reader(name).read(ctx) is None


# -- idle with work, and the clock check -----------------------------------

SYNC = 1e9                      # the session's clock, ns


def idle_ctx(lags_us=(300.0, 300.0, -200.0), engine=True, idle=True):
    """Three decode runs (2 ms each, from 10 ms, every 10 ms; session
    clock = the program's + SYNC); each read by the next turn's
    device_wait, ``lag`` after the run's end; the engine idle from 0.5
    ms after the first wait to 1 ms before the next run."""
    runs, evs = [], []
    for i in range(3):
        t0 = 10_000 + 10_000 * i                              # us
        runs.append(E("jit__decode_paged_fn(3)", (t0 * 1e3) + SYNC,
                      2000e3))
        evs.append(span("serving.decode_step", t0 - 200, 100, tick=i,
                        batch=1))
        end = t0 + 2000 + lags_us[i]
        evs.append(span("serving.device_wait", end - 100, 100,
                        tick=i + 1, reads=i))
        if idle and i < 2:
            evs.append(span("serving.idle", end + 500,
                            t0 + 10_000 - 1000 - end - 500))
    evs.append(span("serving.queue_wait", 0, 40_000, rid=1, tick=0))
    if engine:
        evs.append(ENGINE)
    host = [E(e["name"], e["ts"] * 1e3 + SYNC, e["dur"] * 1e3) for e in evs
            if e.get("ph") == "X" and "rid" not in e["args"]]
    trace = types.SimpleNamespace(ops={0: runs}, modules={0: runs},
                                  sync_start=lambda: SYNC)
    return types.SimpleNamespace(trace=trace, host_spans=host,
                                 program_events=evs, trace_window_s=0.04)


def test_idle_with_work_is_what_no_idle_span_covers(capsys):
    ctx = idle_ctx()
    got = reader("idle_with_work_share.serve").read(ctx)
    # two gaps of 8 ms between the three runs; serving.idle covers each
    # from 0.5 ms after the wait's end (0.3 ms after the run's) to 1 ms
    # before the next run: 6.2 ms of it, 1.8 ms with work
    assert got == pytest.approx(100.0 * 2 * 1.8e-3 / 0.04)
    out = capsys.readouterr().out
    assert "under 2 serving.idle spans" in out
    # the clock check pairs each wait with the run of the tick it read
    assert "3 serving.device_wait spans end -200.0 us (least) and " \
        "300.0 us (median)" in out
    assert "1 end before it" in out


def test_idle_with_work_counts_only_the_window():
    ctx = idle_ctx()
    ctx.trace_window_s = 0.02
    got = reader("idle_with_work_share.serve").read(ctx)
    # the second gap (from 22 ms) lies past the window's end at 20 ms
    assert got == pytest.approx(100.0 * 1.8e-3 / 0.02)


@pytest.mark.parametrize("kw", [{"engine": False},
                                {"engine": False, "idle": False}],
                         ids=["parent_with_spans", "parent"])
def test_idle_with_work_needs_the_engines_event(kw):
    assert reader("idle_with_work_share.serve").read(idle_ctx(**kw)) is None


def test_an_engine_that_never_idles_reads_all_its_gaps():
    got = reader("idle_with_work_share.serve").read(idle_ctx(idle=False))
    assert got == pytest.approx(100.0 * 16e-3 / 0.04)


# -- the files ---------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_metric_file_matches_its_benchmark_entry(name):
    sp = spec_mod.Spec("serve.gpt_1p3b.chat")
    entries = sp.benchmark["per_layer"]
    entry = {m["name"]: m for m in entries}[name]
    f = sp.metric_file(name)
    assert (f["name"], f["layer"], f["unit"], f["moves"]) == (
        name, entry["layer"], entry["unit"], entry["moves"])
    assert f["reader"] == {"kind": "module"}
    assert os.path.exists(sp.path("readers", name, ".py"))
    assert entry["workloads"] == SERVE
    assert (entry["source"], entry["better"]) == ("device_trace", "lower")
    assert [m["name"] for m in entries][-len(NEW):] == NEW
