"""The ``brumby`` family rehearsed with no chip: a tiny configuration of
the family and the ``digest`` mix cut small go through ``run.py`` with
``--trace 0`` and ``1`` as ``test_families.py`` runs the fixture family;
every metric file the cell adds loads, and its reader returns a number
or ``None`` on the rehearsal's trace, never 0; the benchmark's own files
hold the cell as ISSUE 34 names it."""
import json
import os
import types

import pytest

from benchmarks import run
from benchmarks.lib import readers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
FX = os.path.join(HERE, "fixtures")
REH = {"platform": "cpu",
       "peak": {"bf16_flops": 1e12, "int8_ops": 2e12,
                "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
       "benchmark_file": os.path.join(FX, "BENCHMARK.rehearsal34.json"),
       "overlay": FX}
CELL, REAL = "serve.tiny_brumby.digest", "serve.brumby_14b_l8.digest"
NEW = ("retention_decode_roofline.serve", "retention_chunk_roofline.serve",
       "retention_tick_share.serve")


def drive(capsys, trace):
    code = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 34),
                     "--seconds", "3.0", "--trace", str(trace)],
                    rehearsal=REH)
    out = capsys.readouterr()
    assert code == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.out


def test_untraced_run_reports_the_end_to_end_metrics(capsys):
    line, log = drive(capsys, 0)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 18
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                    "tpot_p95_ms", "setup_s"}
    assert line["compared"]["compared_tokens"]["value"] >= 8
    assert "reference normalisers over" in log


def test_traced_run_leaves_the_device_metrics_out(capsys):
    line, _ = drive(capsys, 1)
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    # no TPU plane on a CPU trace: the three find nothing to read
    assert not set(NEW) & set(got)
    assert got["compiles_in_window.serve"]["value"] == 0
    assert got["preemptions.serve"]["value"] == 0
    assert 0 < got["kv_blocks_used_peak_share.serve"]["value"] <= 100.0
    for name, m in got.items():
        assert m["value"] is not None and m["value"] == m["value"], name


def _ctx(spec, **kw):
    base = dict(spec=spec, sizes=spec.config["sizes"], mix=spec.traffic,
                peak=REH["peak"], trace=None, trace_window_s=4.0,
                program_events=[], stat_delta={}, hist_delta={}, values={})
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_nothing_without_a_trace(name):
    spec = spec_mod.Spec(REAL)
    assert spec.metric_file(name)["name"] == name
    assert readers.read_metric(_ctx(spec), name) is None


def _trace():
    from benchmarks.lib import xplane

    E = xplane.Event
    return types.SimpleNamespace(
        ops={0: [E("%power_retention_decode.3 = (f32[17,8,8,136,9216]) "
                   "custom-call()", 0, 2e6),
                 E("%power_retention_decode.3 = (f32[17,8,8,136,9216]) "
                   "custom-call()", 5e6, 2e6),
                 E("%power_retention_chunk.5 = (f32[17,8,8,136,9216]) "
                   "custom-call()", 1e7, 8e6),
                 E("%fusion.7 = bf16[16,5120] fusion()", 2e7, 1e6)]},
        modules={0: [E("jit__decode_paged_fn(123)", 0, 5e6),
                     E("jit__decode_paged_fn(123)", 5e6, 3e6),
                     E("jit__chunk_fn(9)", 1e7, 4e7)]}, annotations=[])


EVENTS = [
    {"ph": "X", "name": "serving.decode_step", "ts": 0, "dur": 9,
     "args": {"batch": 3, "state_slots_live": 3}},
    {"ph": "X", "name": "serving.decode_step", "ts": 0, "dur": 9,
     "args": {"batch": 2, "state_slots_live": 2}},
    {"ph": "X", "name": "serving.prefill_chunk", "ts": 0, "dur": 9,
     "args": {"chunk": 512, "start": 0}},
    {"ph": "X", "name": "serving.prefill_chunk", "ts": 0, "dur": 9,
     "args": {"chunk": 200, "start": 512}},
    {"ph": "X", "name": "serving.turn", "ts": 0, "dur": 9}]


def test_the_rooflines_sum_the_span_arguments():
    spec = spec_mod.Spec(REAL)
    ctx = _ctx(spec, trace=_trace(), program_events=EVENTS)
    D = 8256
    per = 8 * 3 * D * 128 + 40 * 2 * D * 129          # FLOPs a token-layer
    state = 8 * (D * 128 + D) * 4                      # bytes a lane-layer
    got = readers.read_metric(ctx, "retention_decode_roofline.serve")
    least = max(5 * 8 * 2 * state / REH["peak"]["hbm_bytes_per_s"],
                5 * 8 * per / REH["peak"]["bf16_flops"])
    assert got == pytest.approx(100.0 * least / 4e-3)
    got = readers.read_metric(ctx, "retention_chunk_roofline.serve")
    io = (40 + 16) * 128 * 2 + 40 * 128 * 4
    least = max(8 * (2 * 2 * state + 712 * io)
                / REH["peak"]["hbm_bytes_per_s"],
                712 * 8 * per / REH["peak"]["bf16_flops"])
    assert got == pytest.approx(100.0 * least / 8e-3)
    assert readers.read_metric(ctx, "retention_tick_share.serve") == \
        pytest.approx(100.0 * 4e-3 / 8e-3)
    # a program without the span argument, or a trace without the kernel
    bare = [dict(e, args={}) for e in EVENTS]
    assert readers.read_metric(_ctx(spec, trace=_trace(),
                                    program_events=bare),
                               "retention_decode_roofline.serve") is None
    none = types.SimpleNamespace(ops={0: []}, modules={0: []},
                                 annotations=[])
    for name in NEW:
        assert readers.read_metric(
            _ctx(spec, trace=none, program_events=EVENTS), name) is None


def test_the_family_counts_what_a_token_needs():
    spec = spec_mod.Spec(REAL)
    s, fam = spec.config["sizes"], spec.family
    layer = 330_352_896 - 2 * 5120 - 256               # matmul weights
    want = 2.0 * (8 * layer + 5120 * 151936) \
        + 8 * (8 * 3 * 8256 * 128 + 40 * 2 * 8256 * 129)
    assert fam.forward_flops_per_token(s, 512) == pytest.approx(want)
    assert fam.forward_flops_per_token(s, 16384) == pytest.approx(want)
    for name in ("train_loss", "param_specs", "train_flops_per_token",
                 "leaf_norms", "train_readings"):
        with pytest.raises(NotImplementedError, match="29.5 GB"):
            getattr(fam, name)(*([None] * {"train_flops_per_token": 2,
                                           "train_readings": 5}.get(name, 1)))


def test_the_cell_is_the_one_the_issue_names():
    spec = spec_mod.Spec(REAL)
    assert spec.chips == 1 and spec.config["family"] == "brumby"
    eng = spec.workload["engine"]
    assert eng["n_blocks"] == eng["n_slots"] + 1 and eng["n_slots"] >= 12
    assert eng["prefill_chunk"] == 512 and eng["queue_size"] == 256
    from paddle_tpu.models.retention import STATE_PAD
    assert eng["block_size"] == STATE_PAD    # the warm-up's shape model
    mix = spec.traffic
    assert mix["prompt"] == {"dist": "lognormal", "median": 3072,
                             "sigma": 0.8, "min": 512, "max": 16384}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.6, "min": 64, "max": 768}
    assert mix["arrivals"] == "poisson" and mix["greedy_share"] == 0.5
    assert 0.6 * spec.workload["knee_rps"] <= spec.workload["rate_rps"] \
        <= 0.75 * spec.workload["knee_rps"] + 1e-9
    names = {m["name"] for m in spec.per_layer()}
    assert set(NEW) <= names
    assert not names & {"paged_attn_roofline.serve",
                        "mla_decode_roofline.serve",
                        "moe_expert_roofline.serve", "moe_held_share.serve"}
    assert {m["name"] for m in spec.end_to_end()} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    c = spec.config
    assert c["reduced"] == ["num_hidden_layers"]
    for k, v in c["published"].items():
        if k not in c["reduced"]:
            assert c[k] == v, k
    assert c["num_hidden_layers"] == c["sizes"]["n_layers"] == 8
