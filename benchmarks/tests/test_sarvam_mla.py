"""The ``sarvam_mla`` family rehearsed with no chip: a tiny
configuration of the family and the ``docqa`` mix cut small go through
``run.py`` with ``--trace 0`` and ``1`` as ``test_families.py`` runs the
fixture family; every metric file the cell adds loads, and its reader
returns a number or ``None`` on the rehearsal's trace, never 0; the
benchmark's own files hold the cell as ISSUE 30 names it."""
import json
import os
import types

import pytest

from benchmarks import run
from benchmarks.lib import readers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FX = os.path.join(HERE, "fixtures")
REH = {"platform": "cpu",
       "peak": {"bf16_flops": 1e12, "int8_ops": 2e12,
                "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
       "benchmark_file": os.path.join(FX, "BENCHMARK.rehearsal30.json"),
       "overlay": FX}
CELL, REAL = "serve.tiny_mla.docqa", "serve.sarvam_105b_ep4.docqa"
NEW = ("mla_decode_roofline.serve", "moe_expert_roofline.serve",
       "moe_held_share.serve")


def drive(capsys, trace):
    code = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 30),
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
    assert "reference routing over" in log       # the near-tie line


def test_traced_run_reads_the_counters_and_leaves_the_device_metrics_out(
        capsys):
    line, _ = drive(capsys, 1)
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    # no TPU plane on a CPU trace: the two rooflines find nothing to
    # read and are left out; the counter's share is a number
    assert "mla_decode_roofline.serve" not in got
    assert "moe_expert_roofline.serve" not in got
    assert 0.0 < got["moe_held_share.serve"]["value"] < 100.0
    assert got["compiles_in_window.serve"]["value"] == 0
    assert got["preemptions.serve"]["value"] == 0
    for name, m in got.items():
        assert m["value"] is not None and m["value"] == m["value"], name


def _ctx(spec, **kw):
    base = dict(spec=spec, sizes=spec.config["sizes"], mix=spec.traffic,
                peak=REH["peak"], trace=None, trace_window_s=4.0,
                program_events=[], stat_delta={}, hist_delta={}, values={})
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_nothing_from_a_program_that_lacks_it(name):
    """The parent has no such counter, span argument or kernel: None, not
    0 and no exception."""
    spec = spec_mod.Spec(REAL)
    assert spec.metric_file(name)["name"] == name
    assert readers.read_metric(_ctx(spec), name) is None


def test_the_expert_roofline_sums_the_span_arguments():
    from benchmarks.lib import xplane

    spec = spec_mod.Spec(REAL)
    E = xplane.Event
    trace = types.SimpleNamespace(
        ops={0: [E("%ragged-dot-metadata.1 = (s32[33]) custom-call()", 0, 5e3),
                 E("%ragged-dot-none.3 = bf16[256,2048] custom-call()",
                   1e4, 2e6),
                 E("%ragged-dot-none.4 = bf16[256,4096] custom-call()",
                   3e6, 1e6)]}, modules={}, annotations=[])
    events = [{"ph": "X", "name": "serving.decode_step", "ts": 0, "dur": 9,
               "args": {"moe_assignments_routed": 1280,
                        "moe_assignments_held": 300,
                        "moe_expert_reads": 100}},
              {"ph": "X", "name": "serving.prefill_chunk", "ts": 0, "dur": 9,
               "args": {"moe_assignments_held": 724, "moe_expert_reads": 60,
                        "moe_assignments_routed": 4096 * 5}},
              {"ph": "X", "name": "serving.turn", "ts": 0, "dur": 9}]
    ctx = _ctx(spec, trace=trace, program_events=events)
    got = readers.read_metric(ctx, "moe_expert_roofline.serve")
    s = spec.config["sizes"]
    one = 3 * s["hidden"] * s["expert_ffn"]
    byts = (160 * one + 2 * 1024 * s["hidden"]) * 2
    flops = 2.0 * one * 1024
    least = max(byts / REH["peak"]["hbm_bytes_per_s"],
                flops / REH["peak"]["bf16_flops"])
    assert got == pytest.approx(100.0 * least / 3e-3)
    # a skipped expert lowers the bytes counted
    events[0]["args"]["moe_expert_reads"] = 90
    assert readers.read_metric(ctx, "moe_expert_roofline.serve") < got


def test_the_family_counts_what_a_token_needs_here():
    spec = spec_mod.Spec(REAL)
    s, fam = spec.config["sizes"], spec.family
    attn = (4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 8192 * 4096)
    expert = 3 * 4096 * 2048
    params = (6 * attn + 3 * 4096 * 16384
              + 5 * (4096 * 128 + expert + 2 * expert) + 4096 * 65536)
    assert fam.forward_flops_per_token(s, 1000) == pytest.approx(
        2.0 * params + 2.0 * 1000 * 64 * (192 + 128) * 6)
    flops, byts = fam.KERNEL_WORK["mla_decode"](
        types.SimpleNamespace(values={"traced_decode_contexts": 1000},
                              sizes=s), 3)
    assert byts == 1000 * 6 * 1152
    assert flops == 1000 * 6 * 64 * (576 + 512) * 2
    for name in ("train_loss", "param_specs", "train_flops_per_token",
                 "leaf_norms", "train_readings"):
        with pytest.raises(NotImplementedError, match="29.6 GB"):
            getattr(fam, name)(*([None] * {"train_flops_per_token": 2,
                                           "train_readings": 5}.get(name, 1)))


def test_the_cell_is_the_one_the_issue_names():
    spec = spec_mod.Spec(REAL)
    assert spec.chips == 1 and spec.config["family"] == "sarvam_mla"
    assert spec.workload["engine"] == {
        "paged": True, "block_size": 64, "n_slots": 32, "n_blocks": 6801,
        "prefill_chunk": 512, "queue_size": 256}
    mix = spec.traffic
    assert mix["prompt"] == {"dist": "lognormal", "median": 6000,
                             "sigma": 0.5, "min": 2048, "max": 12288}
    assert mix["output"] == {"dist": "lognormal", "median": 128,
                             "sigma": 0.6, "min": 32, "max": 384}
    assert 0.6 * spec.workload["knee_rps"] <= spec.workload["rate_rps"] \
        <= 0.8 * spec.workload["knee_rps"] + 1e-9
    names = {m["name"] for m in spec.per_layer()}
    assert set(NEW) <= names and "paged_attn_roofline.serve" not in names
    assert {m["name"] for m in spec.end_to_end()} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    # every published width is in the file as published
    c = spec.config
    for k, v in c["published"].items():
        if k not in c["reduced"]:
            assert c[k] == v, k
    assert c["sizes"]["n_experts"] == c["published"]["num_experts"]
