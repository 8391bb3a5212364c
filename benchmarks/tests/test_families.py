"""A model's insides are reached through its family alone: a second
family is added with files (a), ``benchmarks/lib`` and the shared tools
hold nothing of a model (b), the move out of ``benchmarks/lib`` kept
every bit (c), and a configuration or a family that lacks what the
harness needs is refused with a line that says what (d)."""
import hashlib
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import readers, spec as spec_mod, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FX = os.path.join(HERE, "fixtures")
REH = {"platform": "cpu",
       "peak": {"bf16_flops": 1e12, "int8_ops": 2e12,
                "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
       "benchmark_file": os.path.join(FX, "BENCHMARK.rehearsal29.json"),
       "overlay": FX}
SHARED = [os.path.join(BENCH, "run.py")] \
    + [os.path.join(BENCH, "tools", f)
       for f in ("sweep.py", "readings.py", "sets.py")] \
    + sorted(os.path.join(BENCH, "lib", f)
             for f in os.listdir(os.path.join(BENCH, "lib"))
             if f.endswith((".py", ".json")))


# -- (a) a family of files alone ---------------------------------------------

CALLED = {"train.tally.b4": {"make_params", "train_readings", "leaf_norms",
                               "train_flops_per_token", "train_loss",
                               "param_specs", "KERNEL_WORK"},
          "serve.tally.chat": {"make_params", "served_gaps",
                                 "forward_flops_per_token", "KERNEL_WORK"}}


@pytest.mark.parametrize("workload,seconds", [("train.tally.b4", 1.5),
                                              ("serve.tally.chat", 2.5)])
def test_a_second_family_runs_both_drivers_from_files_alone(
        capsys, workload, seconds):
    tally = spec_mod.load_family("tally", FX)
    tally.CALLS.clear()
    for trace in (0, 1):
        code = run.main(["--workload", workload, "--seed", str(2 ** 31 + 29),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        rehearsal=REH)
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert code == 0 and line["correct"] and line["failed"] == 0
        assert line["metrics"]
    assert {k for k, n in tally.CALLS.items() if n} == CALLED[workload]
    # between them the two drivers call every name a family gives
    assert set().union(*CALLED.values()) == set(spec_mod.FAMILY)
    for path in SHARED + [os.path.join(BENCH, "tools", "compile_check.py")]:
        with open(path) as f:
            assert "tally" not in f.read(), path


# -- (b) nothing of a model left behind --------------------------------------

@pytest.mark.parametrize("word", ['"hidden"', '"n_heads"', '"n_layers"',
                                  '"mlp_ratio"', "qkv", "wte", "gpt_loss",
                                  "gpt_param_specs"])
def test_the_shared_harness_holds_nothing_of_a_model(word):
    for path in SHARED:
        with open(path) as f:
            assert word not in f.read(), f"{word} in {path}"


# -- (c) the same bits as before the move ------------------------------------

SEED = 2 ** 31 + 5
HYPER = {"lr": 0.0002, "beta1": 0.9, "beta2": 0.999, "eps": 1e-08,
         "weight_decay": 0.01}
B4 = {"kind": "train_batches", "batch": 4, "seq": 64, "ring": 8}


def digest(tree):
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def sizes_of(config):
    with open(os.path.join(FX, "configs", config + ".json")) as f:
        return json.load(f)["sizes"]


@pytest.fixture(scope="module")
def parent():
    """Recorded at the parent (7c5d793, before the move) on this
    sandbox's CPU, by this file's calls made on ``lib/weights.py``'s
    ``make_params`` and ``lib/reference.py``'s ``served_gaps`` and
    ``train_readings`` (which took ``sizes["n_heads"]``)."""
    with open(os.path.join(HERE, "data", "parent_bits.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gpt():
    return spec_mod.load_family("gpt")


@pytest.mark.parametrize("config", ["tiny_serve", "tiny_train"])
def test_a_seeds_weights_are_the_same_bits(gpt, parent, config):
    assert digest(gpt.make_params(sizes_of(config), SEED)) \
        == parent["params"][config]


@pytest.mark.parametrize("lowp", [None, "fp8"])
def test_served_gaps_reads_the_same(gpt, parent, lowp):
    sizes = sizes_of("tiny_serve")
    rng = np.random.default_rng(29)
    prompt = rng.integers(0, 512, 37).astype(np.int32)
    served = rng.integers(0, 512, 11).astype(np.int32)
    gap, low = gpt.served_gaps(gpt.make_params(sizes, SEED), prompt, served,
                               sizes, 128, lowp)
    assert {"gap": [float(x) for x in gap], "low": [float(x) for x in low]} \
        == parent[f"served_gaps.{lowp}"]


@pytest.mark.parametrize("tag,kw", [
    ("plain", {}), ("bf16_half", {"lowp": "bf16", "drop_half": True})])
def test_train_readings_read_the_same(gpt, parent, tag, kw):
    sizes = sizes_of("tiny_train")
    ring = traffic.train_batches(B4, SEED, 512)
    got = gpt.train_readings(gpt.make_params(sizes, SEED), ring[:3], sizes,
                             HYPER, 2, **kw)
    assert got == parent[f"train_readings.{tag}"]


def test_a_kernels_work_comes_from_the_family(gpt):
    # the recorded v5e trace of test_yardstick: three events a kernel.
    # What the reader counts is what the family's functions give by hand
    from bench_family_gpt import work as gpt_work

    trace = xplane.Trace(os.path.join(HERE, "data", "small.xplane.pb"))
    sizes = {"hidden": 768, "n_layers": 12, "n_heads": 12}
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(
        spec=types.SimpleNamespace(family=gpt, config={"family": "gpt"}),
        trace=trace, sizes=sizes, mix={"batch": 32, "seq": 512}, peak=peak,
        values={})

    def share(pattern, work, flops, byts):
        evs = xplane.kernel_events(trace, pattern)
        least = max(flops / peak["bf16_flops"],
                    byts / peak["hbm_bytes_per_s"])
        got = readers.device_trace_kernel(
            ctx, {"pattern": pattern, "work": work})
        assert got == pytest.approx(
            100.0 * least / (sum(e.dur for e in evs) / 1e9), rel=1e-12)
        return got

    f, b = gpt_work.flash_forward(32, 12, 512, 64)
    assert 0 < share("flash_forward", "flash_forward", 3 * f, 3 * b) < 100
    f, b = gpt_work.flash_backward(32, 12, 512, 64)
    assert 0 < share("flash_backward", "flash_backward", 3 * f, 3 * b) < 100
    # the recorded paged kernel ran one layer of 16 heads of 128
    ctx.sizes = {"hidden": 2048, "n_layers": 1, "n_heads": 16}
    ctx.values = {"traced_decode_contexts": 3 * 32 * 300}
    f, b = gpt_work.paged_decode(3 * 32 * 300, 16, 128, 1)
    assert 0 < share("_paged_decode", "paged_decode", f, b) < 100
    # nothing to count, nothing to read; a count the family lacks is
    # refused by name
    ctx.values = {}
    assert readers.device_trace_kernel(
        ctx, {"pattern": "_paged_decode", "work": "paged_decode"}) is None
    with pytest.raises(SystemExit, match="counts no work 'latent_decode'"):
        readers.device_trace_kernel(
            ctx, {"pattern": "_paged_decode", "work": "latent_decode"})


# -- (d) refusals ------------------------------------------------------------

def test_a_configuration_without_a_family_is_refused(tmp_path):
    with open(os.path.join(FX, "configs", "tiny_train.json")) as f:
        config = json.load(f)
    del config["family"]
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "orphan.json", "w") as f:
        json.dump(config, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump({"workloads": [{"name": "train.orphan.b4", "chips": 1,
                                  "config": "orphan", "traffic": "b4"}]}, f)
    with pytest.raises(SystemExit, match='names no "family".*no default'):
        spec_mod.Spec("train.orphan.b4", str(tmp_path / "BENCHMARK.json"),
                      str(tmp_path))


def test_a_family_that_lacks_a_name_is_refused_at_load(tmp_path):
    os.makedirs(tmp_path / "families" / "partial")
    with open(tmp_path / "families" / "partial" / "__init__.py", "w") as f:
        f.write("def make_params(sizes, seed):\n    return {}\n"
                "KERNEL_WORK = {}\n")
    with pytest.raises(SystemExit) as e:
        spec_mod.load_family("partial", str(tmp_path))
    said = str(e.value)
    assert "lacks ['served_gaps', 'train_readings', 'leaf_norms'" in said
    assert all(name in said.split("a family gives")[1]
               for name in spec_mod.FAMILY)
    assert "bench_family_partial" not in sys.modules
    with pytest.raises(SystemExit, match="no family 'nowhere'"):
        spec_mod.load_family("nowhere", str(tmp_path))


def test_a_family_is_loaded_once_and_the_overlay_comes_first():
    sp = spec_mod.Spec("train.tally.b4", REH["benchmark_file"], FX)
    assert sp.family is spec_mod.load_family("tally", FX)
    assert sp.family.__file__.startswith(FX)
    tiny = spec_mod.Spec("train.bert_base.b32")
    assert tiny.family is spec_mod.load_family("gpt")
    assert tiny.family.__file__ == os.path.join(BENCH, "families", "gpt",
                                                "__init__.py")
