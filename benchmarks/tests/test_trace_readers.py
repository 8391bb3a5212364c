"""The readers PR 26 added, with no chip: over a context built by hand
(device events as the profiler names them, program events as
``monitor.trace`` writes them), and through the whole command at a tiny
size, where the device's readers find no TPU plane and say nothing."""
import importlib.util
import json
import os
import types

import pytest

from benchmarks import run
from benchmarks.lib import harness, readers, spec as spec_mod, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
FX = os.path.join(HERE, "fixtures")
E = xplane.Event
NEW = ["step_dispatch_ms.train", "fwd_ms.train", "bwd_ms.train",
       "opt_ms.train", "turn_host_ms.serve", "idle_named_share.serve",
       "prefill_wait_share.serve"]


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("r_" + name[:3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fake_trace(ops=(), modules=()):
    return types.SimpleNamespace(ops={0: list(ops)},
                                 modules={0: list(modules)})


def span(name, ts_us, dur_us, **args):
    return {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
            "args": args}


# -- the train step's phases -------------------------------------------------

SCOPES = {"fusion.1": "forward/mlp", "fusion.2": "backward/mlp",
          "flash_forward.1": "forward/flash_forward",
          "while.3": "backward/attn", "fusion.9": "backward/attn",
          "fusion.4": "optimizer"}


def train_ctx(scopes=SCOPES, program="jit_step"):
    ops, modules = [], []
    for t0 in (0.0, 1000.0):                 # two runs of the step
        modules.append(E("jit_step(77)", t0, 900.0))
        ops += [E("%fusion.1 = f32[] fusion()", t0 + 0, 100.0),
                E("%flash_forward.1 = f32[] custom-call()", t0 + 100, 50.0),
                E("%while.3 = () while()", t0 + 200, 300.0),
                E("%fusion.9 = f32[] fusion()", t0 + 250, 100.0),  # nested
                E("%fusion.2 = f32[] fusion()", t0 + 500, 200.0),
                E("%fusion.4 = f32[] fusion()", t0 + 700, 80.0),
                E("%copy-done.5 = f32[] copy-done()", t0 + 800, 20.0)]
    # another program's op of the same name must not be counted
    modules.append(E("jit_other(5)", 5000.0, 100.0))
    ops.append(E("%fusion.1 = f32[] fusion()", 5000.0, 100.0))
    events = [span("DistributedTrainStep.step", 0, 1100, step=0),
              span("DistributedTrainStep.step", 5000, 900, step=1),
              span("DistributedTrainStep.step", 9000, 1300, step=2)]
    if scopes is not None:
        events.append({"name": "op_scopes", "ph": "M", "pid": 1, "tid": 0,
                       "args": {"program": program, "scopes": scopes}})
    return types.SimpleNamespace(trace=fake_trace(ops, modules),
                                 program_events=events)


def test_phase_times_a_step_and_what_is_left_over(capsys):
    ctx = train_ctx()
    fwd = reader("fwd_ms.train").read(ctx)
    bwd = reader("bwd_ms.train").read(ctx)
    opt = reader("opt_ms.train").read(ctx)
    # per step: forward 100 + 50, backward (300 - 100) + 100 + 200,
    # optimizer 80 ns; copy-done's 20 ns has no label
    assert fwd == pytest.approx(150e-6)
    assert bwd == pytest.approx(500e-6)
    assert opt == pytest.approx(80e-6)
    table = ctx._phase_table
    assert table["runs"] == 2
    assert table["unplaced_ms"] == pytest.approx(20e-6)
    assert table["unplaced_top"][0][0] == "copy-done"
    assert fwd + bwd + opt + table["unplaced_ms"] \
        == pytest.approx(table["busy_ms"])
    assert table["scope_ms"]["backward/attn"] == pytest.approx(300e-6)
    out = capsys.readouterr().out
    # the tables are logged once, not once a metric
    assert out.count("train step by phase") == 1
    assert out.count("train step by scope") == 1


@pytest.mark.parametrize("name", ["fwd_ms.train", "bwd_ms.train",
                                  "opt_ms.train"])
@pytest.mark.parametrize("ctx", [
    train_ctx(scopes=None),                         # the parent: no event
    train_ctx(program="jit_never_ran"),
    types.SimpleNamespace(trace=None, program_events=[])],
    ids=["no_op_scopes_event", "program_not_on_trace", "no_trace"])
def test_phase_readers_say_nothing_without_their_table(name, ctx):
    assert reader(name).read(ctx) is None


def test_step_dispatch_is_the_programs_own_span():
    ctx = train_ctx()
    got = readers.host_span(ctx, {"name": "DistributedTrainStep.step"})
    assert got == pytest.approx(1.1)
    assert readers.host_span(train_ctx(scopes=None),
                             {"name": "nothing"}) is None


# -- the serve turn ----------------------------------------------------------

TURNS = [(0, 90_000), (100_000, 91_000), (200_000, 150_000),
         (400_000, 92_000), (500_000, 90_000)]      # (start, device wait) us


def serve_events():
    evs = []
    for tick, (t0, wait) in enumerate(TURNS):
        evs += [span("serving.turn", t0, wait + 5000, tick=tick),
                span("serving.admit", t0, 500, tick=tick),
                span("serving.decode_prep", t0 + 500, 1500, tick=tick),
                span("serving.decode_step", t0 + 2000, wait + 1000,
                     tick=tick, batch=4),
                span("serving.device_wait", t0 + 2900, wait, tick=tick),
                span("serving.emit", t0 + 3000 + wait, 2000, tick=tick)]
    evs.append(span("serving.request_done", 5, 0, rid=1))     # no tick
    return evs


def test_turn_host_time_takes_the_device_wait_out(capsys):
    ctx = types.SimpleNamespace(program_events=serve_events())
    assert reader("turn_host_ms.serve").read(ctx) == pytest.approx(5.0)
    out = capsys.readouterr().out
    assert "scheduler turns: 5" in out and "serving.emit" in out
    assert reader("turn_host_ms.serve").read(
        types.SimpleNamespace(program_events=[
            span("serving.decode_step", 0, 10, tick=1, batch=1)])) is None
    assert reader("turn_host_ms.serve").read(
        types.SimpleNamespace(program_events=None)) is None


def on_session_clock(events, off_ns=0.0):
    return [E(e["name"], e["ts"] * 1e3 + off_ns, e["dur"] * 1e3)
            for e in events if e["name"] != "serving.request_done"]


def serve_trace(off_ns=0.0, lags_us=(900,) * 5, first=0):
    """One decode run a turn, ending ``lag`` before its span does."""
    return fake_trace(modules=[
        E("jit__decode_paged_fn(1)",
          (t0 + 3000 - lag) * 1e3 + off_ns, wait * 1e3)
        for (t0, wait), lag in list(zip(TURNS, lags_us))[first:]])


def test_idle_named_share_and_the_shared_out_table(capsys):
    chain = [span("serving.admit_to_first", 0, 600_000, rid=1, tick=0)]
    spans = on_session_clock(serve_events() + chain) \
        + [E("bench.clock_sync", 0.0, 10.0)]
    ctx = types.SimpleNamespace(trace=serve_trace(), host_spans=spans)
    mod = reader("idle_named_share.serve")
    # every gap has a span of the turn tree over part of it, and
    # idle_gaps gives the whole gap to the one that covers most
    assert mod.read(ctx) == pytest.approx(100.0)
    out = capsys.readouterr().out
    assert "moved by the harness's sync annotation" in out
    # a request's chain spans every gap of its wait: it is not the
    # host's doing and takes no part
    assert "admit_to_first" not in out
    assert "0 of 5 _decode_paged_fn runs end after" in out
    assert "900.0 us (least)" in out
    # shared out exactly: after each run 0.8 ms of waking up, 0.1 of
    # the step, 2.0 of emit, then nothing until the next turn's 0.5 of
    # admit, 1.5 of prep and 0.1 of the step's dispatch
    tree = [s for s in spans if s.name in mod.TREE]
    got = mod.shared_out(ctx.trace, tree)
    assert got["serving.device_wait"] == pytest.approx(4 * 0.8e-3)
    assert got["serving.decode_step"] == pytest.approx(4 * 0.2e-3)
    assert got["serving.emit"] == pytest.approx(4 * 2.0e-3)
    assert got["serving.admit"] == pytest.approx(4 * 0.5e-3)
    assert got["serving.decode_prep"] == pytest.approx(4 * 1.5e-3)
    assert got["unattributed"] == pytest.approx(57e-3)
    assert "serving.turn" not in got          # it has no time of its own
    assert sum(got.values()) == pytest.approx(
        sum(v for _, v in xplane.idle_gaps(ctx.trace, tree)))


def test_idle_with_no_program_span_over_it_is_unattributed():
    spans = on_session_clock([span("serving.decode_step", 2000, 90_000,
                                   tick=0, batch=1)])
    ctx = types.SimpleNamespace(trace=serve_trace(), host_spans=spans)
    assert reader("idle_named_share.serve").read(ctx) == pytest.approx(0.0)
    ctx = types.SimpleNamespace(trace=None, host_spans=spans)
    assert reader("idle_named_share.serve").read(ctx) is None


def test_overhang_puts_a_number_on_the_skew(capsys):
    # the program's clock 1.5 ms early: every run now ends 0.6 ms AFTER
    # its span
    spans = on_session_clock(serve_events(), off_ns=-1.5e6)
    ctx = types.SimpleNamespace(trace=serve_trace(), host_spans=spans)
    reader("idle_named_share.serve").read(ctx)
    out = capsys.readouterr().out
    assert "5 of 5 _decode_paged_fn runs end after" in out
    assert "by at most 600.0 us" in out


@pytest.mark.parametrize("first", [0, 1], ids=["whole", "first_run_cut"])
def test_with_no_sync_annotation_the_offset_is_fitted(capsys, first):
    # the trace's clock 7 s ahead of the program's, no annotation found:
    # host_spans is empty, and the reader pairs spans with runs itself
    trace = serve_trace(off_ns=7e9, lags_us=(900, 500, 900, 700, 900),
                        first=first)
    ctx = types.SimpleNamespace(trace=trace, host_spans=[],
                                program_events=serve_events())
    assert reader("idle_named_share.serve").read(ctx) \
        == pytest.approx(100.0)
    out = capsys.readouterr().out
    assert f"offset fitted on {5 - first} _decode_paged_fn runs" in out
    # the quickest wake-up (500 us) is what the fit cannot see
    assert f"0 of {5 - first} _decode_paged_fn runs end after" in out
    assert "0.0 us (least)" in out
    assert ("400.0" if not first else "300.0") + " us (median)" in out
    # a program with no spans at all, or too few runs to pair, reads
    # nothing
    bare = types.SimpleNamespace(trace=trace, host_spans=[],
                                 program_events=[])
    assert reader("idle_named_share.serve").read(bare) is None


# -- the two clocks ----------------------------------------------------------

def host_event(name, start_ns, dur_ns):
    return types.SimpleNamespace(name=name, start_ns=start_ns,
                                 duration_ns=dur_ns)


def test_annotations_come_from_every_line_of_the_host_plane():
    # a line is named for its thread: under ``python3 benchmarks/run.py``
    # the main thread's is ``python3``, not ``python`` (PR 26's chip runs)
    plane = types.SimpleNamespace(lines=[
        types.SimpleNamespace(name="python3", events=[
            host_event("bench.clock_sync", 5000, 10),
            host_event("PjitFunction(step)", 6000, 50)]),
        types.SimpleNamespace(name="load-generator/77", events=[
            host_event("bench.train_step_dispatch", 7000, 20),
            host_event("serving.turn", 7000, 20)])])
    assert xplane.host_annotations(plane) == [
        E("bench.clock_sync", 5000.0, 10.0),
        E("bench.train_step_dispatch", 7000.0, 20.0)]
    # the recorded trace's line is ``python``: it reads as it did
    trace = xplane.Trace(os.path.join(HERE, "data", "small.xplane.pb"))
    assert sorted(e.name for e in trace.annotations) \
        == ["bench.paged"] * 3 + ["bench.step"] * 3
    assert trace.sync_start() is None


def test_program_spans_move_to_the_trace_clock_through_the_sync():
    events = serve_events() + [
        span("serving.queue_wait", 0, 600_000, rid=1, tick=0),
        span("serving.admit_to_first", 0, 600_000, rid=1, tick=0),
        {"name": "op_scopes", "ph": "M", "args": {}}]
    found = types.SimpleNamespace(
        annotations=[E("bench.clock_sync", 9e9, 10.0)],
        sync_start=lambda: 9e9)
    # the annotation was entered at 2 s on perf_counter and lies at 9 s
    # on the session's clock: every span moves by 7 s
    spans = harness.host_spans(found, events, 2e9)
    assert spans[0].name == "bench.clock_sync"
    moved = spans[1:]
    assert len(moved) == 6 * len(TURNS)
    assert moved[0] == E("serving.turn", 7e9, 95_000e3)
    # a request's chain is not what the host was doing
    assert not {"serving.queue_wait", "serving.admit_to_first",
                "serving.request_done"} & {s.name for s in moved}
    gaps = dict(xplane.idle_gaps(serve_trace(off_ns=7e9), spans))
    # idle_gaps gives a whole gap to the span that covers most of it, the
    # turn; the breakdown shares it out to the innermost, as the reader's
    # log does
    assert set(gaps) == {"serving.turn"}
    trace, mod = serve_trace(off_ns=7e9), reader("idle_named_share.serve")
    shared = xplane.idle_by_span(trace, spans)
    assert dict(shared) == pytest.approx(dict(mod.shared_out(trace, spans)))
    assert [k for k, _ in shared][:2] == ["unattributed", "serving.emit"]
    assert sum(v for _, v in shared) == pytest.approx(sum(gaps.values()))
    assert len(xplane.idle_by_span(trace, spans, top=2)) == 2
    # no annotation on the trace: the program's spans have no clock
    lost = types.SimpleNamespace(annotations=[], sync_start=lambda: None)
    assert harness.host_spans(lost, events, 2e9) == []


def hist(total, count):
    return {"bounds": [1.0], "counts": [0, count], "count": count,
            "sum": total}


def test_prefill_wait_share():
    trace = fake_trace(modules=[E("jit__chunk_fn(3)", 0.0, 60e6),
                                E("jit__chunk_fn(3)", 1e9, 64e6),
                                E("jit__chunk_fn(4)", 2e9, 62e6)])
    ctx = types.SimpleNamespace(
        trace=trace, stat_delta={"serving_prefill_chunks": 20},
        hist_delta={"serving_first_token_ms": hist(7000.0, 4),
                    "serving_queue_wait_ms": hist(800.0, 4)})
    # own work 20 x 62 ms = 1240 of 6200 ms from admission to first token
    assert reader("prefill_wait_share.serve").read(ctx) \
        == pytest.approx(80.0)
    for lacking in ({"stat_delta": {}},                     # the parent
                    {"trace": fake_trace()}, {"trace": None},
                    {"hist_delta": {}}):
        less = types.SimpleNamespace(**{**vars(ctx), **lacking})
        assert reader("prefill_wait_share.serve").read(less) is None


# -- the files and the whole command -----------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_metric_file_matches_its_benchmark_entry(name):
    sp = spec_mod.Spec("train.bert_base.b32")
    entry = {m["name"]: m for m in sp.benchmark["per_layer"]}[name]
    f = sp.metric_file(name)
    assert (f["name"], f["layer"], f["unit"], f["moves"]) == (
        name, entry["layer"], entry["unit"], entry["moves"])
    kind = f["reader"]["kind"]
    assert kind in readers.KINDS or (
        kind == "module" and os.path.exists(sp.path("readers", name, ".py")))
    cells = [w["name"] for w in sp.benchmark["workloads"]
             if w["name"].split(".")[0] == name.rsplit(".", 1)[1]]
    assert entry["workloads"] == cells
    # new entries stand at the end of the list, after the sixteen
    names = [m["name"] for m in sp.benchmark["per_layer"]]
    assert names[16:] == NEW


REH = {"platform": "cpu",
       "peak": {"bf16_flops": 1e12, "int8_ops": 2e12,
                "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
       "benchmark_file": os.path.join(FX, "BENCHMARK.rehearsal26.json"),
       "overlay": FX}


def drive(capsys, workload, seconds):
    code = run.main(["--workload", workload, "--seed", str(2 ** 31 + 26),
                     "--seconds", str(seconds), "--trace", "1"],
                    rehearsal=REH)
    out = capsys.readouterr()
    assert code == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.out


def test_traced_train_cell_reads_its_own_span(capsys):
    line, log = drive(capsys, "train.tiny.b4", 1.5)
    assert line["correct"]
    got = line["metrics"]
    # no TPU plane here: the device's readers say nothing, and the
    # table's on_stop lookup compiles nothing inside the window
    assert "fwd_ms.train" not in got and "opt_ms.train" not in got
    assert got["compiles_in_window.train"]["value"] == 0
    assert 0 < got["step_dispatch_ms.train"]["value"] \
        <= got["host_dispatch_ms.train"]["value"] * 1.5


def test_traced_serve_cell_reads_its_turns(capsys):
    line, log = drive(capsys, "serve.tiny.chat", 2.5)
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    assert got["turn_host_ms.serve"]["value"] > 0
    assert "scheduler turns:" in log
    assert "idle_named_share.serve" not in got
    assert "prefill_wait_share.serve" not in got
    assert got["compiles_in_window.serve"]["value"] == 0
