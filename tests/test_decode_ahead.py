"""One decode tick in flight: a turn of the serving engine dispatches tick
n+1 before it reads tick n's tokens. Greedy streams are pinned token for
token to a full recompute, sampled streams to the same requests on an
engine with the watchdog armed (which reads every tick in the turn that
dispatched it). Lanes leave on the tick the host foresees; an EOS found
at the read discards one lane-tick and pushes nothing after it; a cancel
and a deadline land while a tick is in flight; preemption under a pool of
a few blocks, a prefix-cache hit, and the tiny latent-attention and
retention models keep their streams. Constrained, speculative and
watchdog engines read every tick at once; ``run_on_scheduler`` never
runs with a tick in flight; a plain run sends every tick but the first of
a busy stretch with one in flight."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.spec import load_family
from paddle_tpu import monitor
from paddle_tpu.models import (gpt_forward, gpt_init, gpt_tiny, gpt_truncate,
                               mla_forward, mla_tiny, retention_tiny)
from paddle_tpu.serving import InferenceEngine
from paddle_tpu.serving.constrained import compile_constraint
from paddle_tpu.serving.tokenizer import ByteTokenizer

CFG = gpt_tiny(dtype=jnp.float32, seq_len=64)
PARAMS = gpt_init(CFG, seed=38)
COUNTERS = ("serving_decode_ticks_ahead", "serving_decode_ticks_synced",
            "serving_decode_lanes_discarded")
_FULL = jax.jit(lambda p, t: gpt_forward(CFG, p, t))


def _prompt(n, seed, vocab=CFG.vocab_size):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _ref_greedy(prompt, n, params=PARAMS):
    """Full-recompute greedy decode: the tokens every engine must give."""
    toks, out = list(prompt), []
    for _ in range(n):
        buf = np.zeros((1, CFG.seq_len), np.int32)
        buf[0, :len(toks)] = toks
        t = int(np.argmax(np.asarray(
            _FULL(params, jnp.asarray(buf))[0, len(toks) - 1])))
        out.append(t)
        toks.append(t)
    return out


class _Counted:
    """How far the three counters moved over a block."""

    def __enter__(self):
        self._before = [monitor.stat_get(n) for n in COUNTERS]
        return self

    def __exit__(self, *exc):
        self.ahead, self.synced, self.discarded = [
            monitor.stat_get(n) - b for n, b in zip(COUNTERS, self._before)]


@pytest.fixture
def engine():
    engines = []

    def make(cfg=CFG, params=PARAMS, **kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("block_size", 8)
        kw.setdefault("prefill_chunk", 16)
        eng = InferenceEngine(cfg, params, **kw)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        eng.shutdown(drain=False, timeout=30)


def _idle(eng):
    """(blocks used, slots occupied) once nothing is in flight."""
    return eng.run_on_scheduler(
        lambda e: (e.cache.used_blocks_count, e.occupancy), timeout=60)


class TestStreams:
    def test_greedy_with_staggered_arrivals_and_mixed_lengths(self, engine):
        """Requests arrive while others decode and leave on the tick the
        host foresees (their ``max_new_tokens``, or the positional cap):
        every stream is the full recompute's, no lane-tick is wasted."""
        specs = [(9, 14), (5, 3), (17, 9), (12, 1), (50, 30), (7, 20)]
        eng = engine(n_slots=3)
        with _Counted() as c:
            reqs = []
            for i, (n, m) in enumerate(specs):
                reqs.append(eng.submit(_prompt(n, i), max_new_tokens=m))
                time.sleep(0.02)
            got = [r.result(timeout=120) for r in reqs]
        for i, ((n, m), out) in enumerate(zip(specs, got)):
            assert len(out) == min(m, CFG.seq_len - n + 1)
            assert out == _ref_greedy(_prompt(n, i), len(out))
            assert reqs[i].finish_reason == "length"
        assert c.discarded == 0
        assert c.ahead > c.synced > 0
        assert _idle(eng) == (0, 0)

    def test_sampled_streams_equal_a_watchdog_engines(self, engine):
        """The draw of a lane is keyed by (rid, steps) with the projected
        steps: a sampled stream is the one an engine that reads every
        tick at once gives, token for token."""
        def run(**kw):
            eng = engine(n_slots=3, seed=11, **kw)
            with _Counted() as c:
                reqs = [eng.submit(_prompt(6 + 5 * i, 20 + i),
                                   max_new_tokens=8 + 3 * i,
                                   temperature=0.9, top_k=(0, 20, 40)[i],
                                   top_p=(0.9, 1.0, 0.95)[i])
                        for i in range(3)]
                out = [r.result(timeout=120) for r in reqs]
            eng.shutdown()
            return out, c

        ahead, c_ahead = run()
        synced, c_synced = run(watchdog=True)
        assert ahead == synced
        assert [len(o) for o in ahead] == [8, 11, 14]
        assert c_ahead.ahead > 0
        assert c_synced.ahead == 0 and c_synced.synced > 0

    def test_eos_found_at_the_read_discards_one_lane_tick(self, engine):
        """The EOS token is read while the lane's next tick is in flight:
        that tick's result for the lane is thrown away and nothing after
        the EOS reaches the stream; the lane's blocks are free at once and
        the next request in the slot is exact."""
        # the block matmuls x8 make seed 4's continuation of this prompt
        # new at its third token (tests/test_serving.py: test_eos_eviction)
        params = gpt_init(CFG, seed=4)
        params = dict(params, blocks={
            k: w * 8.0 if w.ndim == 3 else w
            for k, w in params["blocks"].items()})
        prompt = np.random.default_rng(1).integers(
            0, CFG.vocab_size, 7).astype(np.int32)
        ref = _ref_greedy(prompt, 6, params)
        assert ref.index(ref[2]) == 2, "fixture assumption broke"
        eng = engine(params=params, eos_id=ref[2], n_slots=1)
        with _Counted() as c:
            req = eng.submit(prompt, max_new_tokens=12)
            assert req.result(timeout=120) == ref[:3]
            assert _idle(eng) == (0, 0)
        assert req.finish_reason == "eos"
        assert req.tokens == ref[:3]
        assert c.discarded == 1
        other = _prompt(10, 5)
        assert eng.submit(other, max_new_tokens=6).result(timeout=120) \
            == _ref_greedy(other, 6, params)

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_cancel_and_deadline_with_a_tick_in_flight(self, engine, how):
        eng = engine()
        prompt = _prompt(4, 3)
        req = eng.submit(prompt, max_new_tokens=58)
        stream = req.stream(timeout=120)
        for _ in range(4):
            next(stream)
        if how == "cancel":
            req.cancel()
        else:
            req.deadline = time.monotonic() - 1.0
        got = req.result(timeout=120)
        assert req.finish_reason == {"cancel": "cancelled",
                                     "deadline": "deadline"}[how]
        assert 4 <= len(got) < 58
        assert got == _ref_greedy(prompt, len(got))
        assert _idle(eng) == (0, 0)

    def test_preemption_under_a_pool_of_a_few_blocks(self, engine):
        """Two streams outgrow six blocks: a projected table that cannot
        grow reads the tick in flight first, the youngest is preempted
        and resumes exactly."""
        pa, pb = _prompt(9, 6), _prompt(11, 7)
        pre0 = monitor.stat_get("serving_preemptions")
        eng = engine(n_blocks=7)
        with _Counted() as c:
            ra = eng.submit(pa, max_new_tokens=20)
            rb = eng.submit(pb, max_new_tokens=20)
            assert ra.result(timeout=120) == _ref_greedy(pa, 20)
            assert rb.result(timeout=120) == _ref_greedy(pb, 20)
        assert monitor.stat_get("serving_preemptions") - pre0 >= 1
        assert c.ahead > 0 and c.discarded == 0
        assert _idle(eng) == (0, 0)

    def test_prefix_cache_hit(self, engine):
        shared = _prompt(21, 8)
        p1 = np.concatenate([shared, _prompt(5, 9)])
        p2 = np.concatenate([shared, _prompt(7, 10)])
        eng = engine(prefix_cache=True)
        matched0 = monitor.stat_get("prefix_matched_tokens")
        with _Counted() as c:
            assert eng.submit(p1, max_new_tokens=12).result(timeout=120) \
                == _ref_greedy(p1, 12)
            assert eng.submit(p2, max_new_tokens=12).result(timeout=120) \
                == _ref_greedy(p2, 12)
        assert monitor.stat_get("prefix_matched_tokens") - matched0 >= 16
        assert c.ahead > 0


# -- the other two model families ---------------------------------------------

def _family_sizes(cfg):
    s = {k: v for k, v in dataclasses.asdict(cfg).items()
         if k not in ("dtype", "param_dtype")}
    s.update(dtype="float32", param_dtype="float32", init_std=0.2)
    return s


def _both_ways(make, cfg, params, prompts, **kw):
    """The same requests (greedy, then sampled) on a plain engine and on
    a watchdog engine: (streams, counters) each."""
    got = []
    for extra in ({}, {"watchdog": True}):
        eng = make(cfg=cfg, params=params, seed=5, **kw, **extra)
        with _Counted() as c:
            reqs = [eng.submit(p, max_new_tokens=m, temperature=t, top_k=20)
                    for p, m, t in prompts]
            got.append(([r.result(timeout=300) for r in reqs], c))
        eng.shutdown()
    return got


def test_latent_attention_model(engine):
    cfg = mla_tiny(experts_held=4, expert_offset=2)
    params = load_family("sarvam_mla").make_params(_family_sizes(cfg),
                                                   2 ** 31 + 38)
    prompts = [(_prompt(16, 30, 256), 6, 0.0), (_prompt(9, 31, 256), 9, 0.8),
               (_prompt(21, 32, 256), 4, 0.0)]
    (ahead, c), (synced, c_synced) = _both_ways(
        engine, cfg, params, prompts, n_blocks=24, prefix_cache=False)
    assert ahead == synced
    for (p, _, t), out in zip(prompts, ahead):
        if t == 0.0:
            seq = np.concatenate([p, np.asarray(out, np.int32)])
            lg, _ = mla_forward(cfg, params, jnp.asarray(seq[None]))
            assert out == np.asarray(jnp.argmax(lg[0], -1))[
                len(p) - 1:len(seq) - 1].tolist()
    assert c.ahead > 0 and c_synced.ahead == 0


def test_retention_model(engine):
    cfg = retention_tiny()
    family = load_family("brumby")
    sizes = _family_sizes(cfg)
    params = family.make_params(sizes, 2 ** 31 + 38)
    prompts = [(_prompt(37, 40, 256), 6, 0.0), (_prompt(12, 41, 256), 8, 0.8),
               (_prompt(20, 42, 256), 5, 0.0)]
    (ahead, c), (synced, c_synced) = _both_ways(
        engine, cfg, params, prompts, n_blocks=3, prefill_chunk=128)
    assert ahead == synced
    prompt, n, _ = prompts[0]
    seq = list(prompt)
    for _ in range(n):
        buf = np.zeros(-(-len(seq) // 64) * 64, np.int32)
        buf[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(family.reference.logits_at(
                params, buf, sizes, len(seq) - 1, len(seq))[0])
        seq.append(int(np.argmax(lg[0])))
    assert ahead[0] == seq[len(prompt):]
    assert c.ahead > 0 and c_synced.ahead == 0


# -- when the engine reads at once ----------------------------------------------

class TestSynchronousRead:
    def test_watchdog_engine(self, engine):
        eng = engine(watchdog=True)
        prompt = _prompt(10, 50)
        with _Counted() as c:
            got = eng.submit(prompt, max_new_tokens=10).result(timeout=120)
        assert got == _ref_greedy(prompt, 10)
        assert (c.ahead, c.synced, c.discarded) == (0, 9, 0)

    def test_speculative_engine(self, engine):
        eng = engine(draft=gpt_truncate(CFG, PARAMS, 2), spec_k=3)
        prompt = _prompt(10, 51)
        with _Counted() as c:
            got = eng.submit(prompt, max_new_tokens=12).result(timeout=120)
        assert got == _ref_greedy(prompt, 12)
        assert c.ahead == 0 and c.synced > 0

    def test_constrained_row(self, engine):
        """A live constrained row needs each token for its next mask: the
        plain engine reads every tick at once while it lives, and both
        streams are the watchdog engine's."""
        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=38)
        con = compile_constraint(tokenizer=tok, regex="[a-z]{12}",
                                 vocab_size=cfg.vocab_size)
        outs = []
        for extra in ({}, {"watchdog": True}):
            eng = engine(cfg=cfg, params=params, tokenizer=tok, seed=3,
                         **extra)
            with _Counted() as c:
                rc = eng.submit(text="word: ", max_new_tokens=16,
                                temperature=0.9, constraint=con)
                rp = eng.submit(_prompt(8, 52, tok.vocab_size),
                                max_new_tokens=6)
                outs.append((rc.result(timeout=120), rp.result(timeout=120)))
            assert rc.finish_reason == "stop" and len(outs[-1][0]) == 12
            assert c.ahead == 0
            eng.shutdown()
        assert outs[0] == outs[1]

    def test_run_on_scheduler_never_sees_a_tick_in_flight(self, engine):
        eng = engine()
        prompt = _prompt(6, 53)
        req = eng.submit(prompt, max_new_tokens=50)
        stream = req.stream(timeout=120)
        next(stream)
        seen = []
        while not req.done and len(seen) < 20:
            seen.append(eng.run_on_scheduler(
                lambda e: e._inflight is None, timeout=60))
        assert seen and all(seen)
        assert req.result(timeout=120) == _ref_greedy(prompt, 50)


def test_a_plain_run_sends_all_but_a_stretchs_first_tick_ahead(engine):
    """One request alone: its first token comes from its prompt's last
    chunk, then nine ticks, the first with nothing in flight. Twice."""
    eng = engine()
    for stretch in range(2):
        prompt = _prompt(8, 60 + stretch)
        with _Counted() as c:
            got = eng.submit(prompt, max_new_tokens=10).result(timeout=120)
        assert got == _ref_greedy(prompt, 10)
        assert (c.synced, c.ahead, c.discarded) == (1, 8, 0)
