"""The yardstick's own arithmetic: traffic, work functions, the trace
reduction on a small recorded trace, the comparisons of ``correct``."""
import os

import numpy as np
import pytest

from benchmarks.lib import check, spec as spec_mod, traffic, work, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = {"arrivals": "poisson",
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 32, "max": 1024},
        "output": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                   "min": 16, "max": 256},
        "greedy_share": 0.5,
        "sampling": {"temperature": 0.8, "top_k": 40, "top_p": 0.95}}
BERT = {"hidden": 768, "n_layers": 12, "n_heads": 12, "mlp_ratio": 4,
        "vocab_size": 30592}


def _key(s):
    return (s["due"], s["prompt"].tobytes(), s["max_new_tokens"],
            s["greedy"], s["temperature"], s["top_k"], s["top_p"])


def test_same_seed_same_schedule():
    a = traffic.open_loop(CHAT, 4.0, 30, 2 ** 31 + 77, 50304)
    b = traffic.open_loop(CHAT, 4.0, 30, 2 ** 31 + 77, 50304)
    assert [_key(s) for s in a] == [_key(s) for s in b]


def test_seeds_share_the_schedule_and_differ_in_tokens():
    a = traffic.open_loop(CHAT, 4.0, 30, 1, 50304)
    b = traffic.open_loop(CHAT, 4.0, 30, 2, 50304)
    c = traffic.open_loop(dict(CHAT, pattern_seed=2), 4.0, 30, 1, 50304)
    assert len(a) == len(b) == len(c) == 120
    fields = (lambda s: len(s["prompt"]), lambda s: s["max_new_tokens"],
              lambda s: s["greedy"], lambda s: s["due"])
    for f in fields:
        # the seed leaves the schedule alone; another pattern is the
        # same sizes and gaps in another order
        assert list(map(f, a)) == list(map(f, b))
        if f is not fields[-1]:
            assert sorted(map(f, a)) == sorted(map(f, c))
    assert [len(s["prompt"]) for s in a] != [len(s["prompt"]) for s in c]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    due = [s["due"] for s in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30
    assert all(32 <= len(s["prompt"]) <= 1024 for s in a)
    assert all(16 <= s["max_new_tokens"] <= 256 for s in a)
    assert sum(s["greedy"] for s in a) == 60


def test_train_ring_rows_all_differ():
    ring = traffic.train_batches({"batch": 4, "seq": 16, "ring": 3}, 5, 512)
    rows = {r.tobytes() for t, _ in ring for r in t}
    assert len(rows) == 12
    t, l = ring[0]
    assert (t[:, 1:] == l[:, :-1]).all()


def test_bert_base_flops_by_hand():
    # the counts are the gpt family's; the roofline is everyone's
    spec_mod.load_family("gpt")
    from bench_family_gpt import work as gpt

    # four projections a layer: 768*2304 + 768*768 + 2*768*3072 = 7,077,888
    assert gpt.matmul_params(BERT) == 12 * 7077888 + 30592 * 768
    # forward: 2 FLOPs a weight a token, plus causal attention
    # 4 * (512 / 2) * 768 * 12 = 9,437,184; training is three forwards
    fwd = 2 * 108429312 + 9437184
    assert gpt.train_flops_per_token(BERT, 512) == 3 * fwd == 678887424
    f, b = gpt.flash_forward(32, 12, 512, 64)
    assert f == 4 * 384 * 512 * 512 * 64 / 2 and b == 4 * 384 * 512 * 64 * 2
    fb, bb = gpt.flash_backward(32, 12, 512, 64)
    assert fb == 2.5 * f and bb == 2 * b
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # 128 FLOPs a byte at head size 64 and seq 512: under the v5e's ridge
    assert work.roofline_seconds(f, b, peak)[1] == "memory"
    assert work.roofline_seconds(*gpt.flash_forward(1, 16, 2048, 128),
                                 peak)[1] == "compute"
    pf, pb = gpt.paged_decode(1000, 16, 128, 24)
    assert pb == 2 * 1000 * 16 * 128 * 2 * 24 and pf == pb
    assert work.roofline_seconds(pf, pb, peak)[1] == "memory"


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace(os.path.join(HERE, "data", "small.xplane.pb"))


def test_recorded_trace_reduction(trace):
    # recorded on a v5e (PR 25): three rounds of flash fwd+bwd, the paged
    # kernel and a matmul
    assert len(trace.ops[0]) == 174 and len(trace.modules[0]) == 9
    assert xplane.busy_seconds(trace) == pytest.approx(0.012495337)
    for pat, us in (("flash_forward", 1833.52), ("flash_backward", 2476.665),
                    ("_paged_decode", 2776.67)):
        evs = xplane.kernel_events(trace, pat)
        assert len(evs) == 3
        assert sum(e.dur for e in evs) / 1e3 == pytest.approx(us)
    top = dict(xplane.device_ops(trace))
    assert top["_paged_decode"] == pytest.approx(0.00277667)
    assert sum(xplane.self_seconds(trace.ops[0]).values()) \
        <= xplane.busy_seconds(trace) * 1.0001
    import types

    from benchmarks.lib import readers
    ctx = types.SimpleNamespace(trace=trace)
    assert readers.device_trace_module(ctx, {"pattern": "^jit_fb"}) \
        == pytest.approx(2.4856, rel=1e-3)
    assert readers.device_trace_module(ctx, {"pattern": "nothing"}) is None
    gaps = dict(xplane.idle_gaps(trace, trace.annotations))
    assert "bench.step" in gaps and all(v > 0 for v in gaps.values())


def test_union_and_self_time_of_nested_events():
    E = xplane.Event
    evs = [E("%while.1 = x", 0.0, 100.0), E("%a.2 = x", 10.0, 30.0),
           E("%b = x", 50.0, 20.0), E("%c = x", 150.0, 10.0)]
    assert xplane.union_ns(evs) == 110.0
    assert xplane.self_seconds(evs) == {"while": 50e-9, "a": 30e-9,
                                        "b": 20e-9, "c": 10e-9}


LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_gap": 0.05,
          "change_norm_gap": 0.05}


def _readings(scale=1.0, k_bias=1e-9):
    g = {"a": 1.0 * scale, "b": 2.0 * scale, "c": 3.0, "kb": k_bias}
    return {"losses": [10.0, 9.9, 9.8], "grad_norms": dict(g),
            "change_norms": dict(g)}


def test_train_comparison_takes_norm_gaps_by_the_worst_leaf():
    ok, notes = check.train(_readings(), _readings(), LIMITS)
    assert all(c["ok"] for c in ok.values())
    assert notes["left_out_of_change"] == ["kb"]
    off, _ = check.train(_readings(scale=1.2), _readings(), LIMITS)
    assert not off["grad_norm_gap"]["ok"]
    assert off["grad_norm_gap"]["value"] == pytest.approx(0.2)
    # a leaf with no gradient to speak of moves by round-off alone: it is
    # left out of the change, but a wrong gradient there still shows
    noisy, _ = check.train(_readings(k_bias=1e-6), _readings(), LIMITS)
    assert noisy["change_norm_gap"]["ok"]
    # an unchanged state reads 1
    still = dict(_readings(), grad_norms={k: 0.0 for k in "a b c kb".split()},
                 change_norms={k: 0.0 for k in "a b c kb".split()})
    got, _ = check.train(still, _readings(), LIMITS)
    assert got["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert got["change_norm_gap"]["value"] == pytest.approx(1.0)
