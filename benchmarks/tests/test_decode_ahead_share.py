"""``decode_ahead_share.serve`` with no chip: its reader over counter
deltas built by hand, over the deltas of a parent program that lacks
the counters, and over those of a tiny engine's run; its metric file
against its entry in ``BENCHMARK.json``."""
import importlib.util
import os
import types

import numpy as np
import pytest

from benchmarks.lib import program, readers, spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "decode_ahead_share.serve"


def reader():
    path = os.path.join(os.path.dirname(HERE), "readers", NAME + ".py")
    spec = importlib.util.spec_from_file_location("r_ahead", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ctx(**counters):
    return types.SimpleNamespace(stat_delta=counters)


def test_share_of_the_ticks_that_left_with_one_in_flight():
    got = reader().read(ctx(serving_decode_ticks_ahead=180,
                            serving_decode_ticks_synced=20))
    assert got == pytest.approx(90.0)
    assert reader().read(ctx(serving_decode_ticks_ahead=0,
                             serving_decode_ticks_synced=7)) == 0.0


@pytest.mark.parametrize("counters", [
    {},                                            # the parent: no counter
    {"serving_kv_rows_written": 960, "serving_prefill_chunks": 12},
    {"serving_decode_ticks_ahead": 5},
    {"serving_decode_ticks_ahead": 0, "serving_decode_ticks_synced": 0},
], ids=["empty", "parent_counters", "one_of_two", "no_tick"])
def test_reads_nothing_without_its_counters(counters):
    assert reader().read(ctx(**counters)) is None


def test_metric_file_matches_its_benchmark_entry():
    sp = spec_mod.Spec("serve.gpt_1p3b.chat")
    entries = sp.benchmark["per_layer"]
    assert entries[-1]["name"] == NAME
    entry = entries[-1]
    f = sp.metric_file(NAME)
    assert (f["name"], f["layer"], f["unit"], f["moves"]) == (
        NAME, entry["layer"], entry["unit"], entry["moves"])
    assert f["reader"]["kind"] == "module"
    assert os.path.exists(sp.path("readers", NAME, ".py"))
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "serve entry (serving/engine.py)"
    assert entry["moves"] == "tpot_p95_ms"
    assert entry["workloads"] == [
        w["name"] for w in sp.benchmark["workloads"]
        if w["name"].startswith("serve.")]
    assert NAME in [m["name"] for m in sp.per_layer()]
    assert readers.read_metric(types.SimpleNamespace(
        spec=sp, stat_delta={}), NAME) is None


def test_a_tiny_engine_reads_most_ticks_ahead():
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_init, gpt_tiny
    from paddle_tpu.serving import InferenceEngine

    cfg = gpt_tiny(dtype=jnp.float32, seq_len=64)
    eng = InferenceEngine(cfg, gpt_init(cfg, 38), n_slots=4, block_size=8,
                          prefill_chunk=16)
    rng = np.random.default_rng(38)
    try:
        before = program.stats_snapshot()
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, 6 + 4 * i)
                           .astype(np.int32), max_new_tokens=12)
                for i in range(3)]
        for r in reqs:
            assert len(r.result(timeout=300)) == 12
        counters, _ = program.stats_delta(before, program.stats_snapshot())
    finally:
        eng.shutdown()
    got = reader().read(ctx(**counters))
    assert 50.0 < got < 100.0
