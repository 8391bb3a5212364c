"""The whole command at a tiny size with no chip, through the test-only
override of ``run.main`` (the command line cannot take it), and the
faults and the control that ``correct`` has to catch."""
import dataclasses
import json
import os

import pytest

from benchmarks import run

FX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
REH = {"platform": "cpu",
       "peak": {"bf16_flops": 1e12, "int8_ops": 2e12,
                "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
       "benchmark_file": os.path.join(FX, "BENCHMARK.rehearsal.json"),
       "overlay": FX}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def drive(capsys, workload, seed=2 ** 31 + 5, seconds=1.5, trace=0):
    code = run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    rehearsal=REH)
    out = capsys.readouterr()
    assert code == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[:5] == KEYS and list(line)[-1] == "compared"
    assert out.err.strip().splitlines()[-1].startswith("compared ")
    return line


def test_no_chip_no_result(capsys):
    # the command line has no override: with the CPU it refuses
    code = run.main(["--workload", "train.bert_base.b32", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == 2 and "needs a TPU" in out.err and "{" not in out.out


@pytest.mark.parametrize("trace", [0, 1])
def test_train_cell(capsys, trace):
    line = drive(capsys, "train.tiny.b4", trace=trace)
    assert line["correct"] and line["failed"] == 0
    want = ({"train_tokens_per_s", "setup_s"} if not trace else
            {"host_dispatch_ms.train", "compiles_in_window.train",
             "step_mfu.train"})     # the device's readers find no TPU plane
    assert set(line["metrics"]) == want
    if trace:
        assert line["metrics"]["compiles_in_window.train"]["value"] == 0
        assert {"busy_s", "window_s"} <= set(line["device"])


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell(capsys, trace):
    line = drive(capsys, "serve.tiny.chat", seconds=2.5, trace=trace)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 20
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                        "tpot_p95_ms", "setup_s"}
    else:
        assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert "queue_wait_p50_ms.serve" in line["metrics"]


# -- faults planted under the timed path -----------------------------------

def test_fault_state_unchanged(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import DistributedTrainStep

    real = DistributedTrainStep.__call__

    def frozen(self, batch):
        keep = jax.tree_util.tree_map(jnp.copy,
                                      (self.params, self.opt_state))
        loss = real(self, batch)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(DistributedTrainStep, "__call__", frozen)
    line = drive(capsys, "train.tiny.b4")
    assert not line["correct"]
    assert line["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(capsys, monkeypatch):
    from paddle_tpu.parallel import DistributedTrainStep

    real = DistributedTrainStep.__call__
    monkeypatch.setattr(
        DistributedTrainStep, "__call__",
        lambda self, batch: real(self, tuple(x[:len(x) // 2]
                                             for x in batch)))
    line = drive(capsys, "train.tiny.b4")
    c = line["compared"]
    assert not line["correct"]
    assert c["grad_norm_gap"]["value"] > c["grad_norm_gap"]["limit"]


def test_fault_token_altered_where_it_is_produced(capsys, monkeypatch):
    from paddle_tpu.serving.engine import GenerationRequest

    real = GenerationRequest._push

    def altered(self, tok):
        if self.temperature == 0.0 and len(self.tokens) == 2:
            tok = (tok + 7) % 512
        real(self, tok)

    monkeypatch.setattr(GenerationRequest, "_push", altered)
    line = drive(capsys, "serve.tiny.chat", seconds=2.5)
    c = line["compared"]["served_logit_gap"]
    assert not line["correct"] and c["value"] > c["limit"]


# -- the control: the nearest precision below the configuration's ----------

@pytest.mark.parametrize("seed", [3, 5, 6])
def test_control_train_fp8_is_not_correct(capsys, monkeypatch, seed):
    # the program's own fp8 MLP path in the place of its bf16 one. At
    # this width the two read alike on some seeds (PERF.md): these three
    # are seeds on which the tiny fixture's limits tell them apart
    from benchmarks.lib import program

    assert drive(capsys, "train.tiny.b4", seed=seed, seconds=0.3)["correct"]
    real = program.build_config
    monkeypatch.setattr(program, "build_config",
                        lambda c: dataclasses.replace(real(c), fp8=True))
    line = drive(capsys, "train.tiny.b4", seed=seed, seconds=0.3)
    assert not line["correct"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_serve_reference_in_fp8_is_not_correct(capsys, monkeypatch,
                                                       seed):
    # the reference computed in float8 (e4m3 operands in every matmul),
    # put in the program's place: at each position of the served prompts
    # and tokens, the gap of the token that pass puts first
    from benchmarks.lib import serve

    assert drive(capsys, "serve.tiny.chat", seed=seed,
                 seconds=2.5)["correct"]
    real = serve.compare_served

    def control(sample, sizes, seed, pad_to):
        _, low, n = real(sample, sizes, seed, pad_to, lowp="fp8")
        return low, low, n

    monkeypatch.setattr(serve, "compare_served", control)
    line = drive(capsys, "serve.tiny.chat", seed=seed, seconds=2.5)
    c = line["compared"]["served_logit_gap"]
    assert not line["correct"] and c["value"] > 3 * c["limit"]
