"""paddle_tpu.serving continuous-batching engine (ISSUE 4): KV-cache
decode numerics vs full recompute, per-token speedup, continuous-batching
admission, eviction (eos/max_tokens), deadline/cancellation, queue
backpressure, and gauge/span emission feeding tools/trace_report.py's
serving verdict.

The paged cache (ISSUE 7), the engine's only one since PR 32: greedy
token-identity vs the full-recompute oracle, long-prompt admission up to
cfg.seq_len, the ``paged`` argument's last duty (False raises), chunked
prefill interleaving with open decode streams (no-starvation pin),
block-pool accounting/gauges/double-free, eviction→reuse of recycled
blocks, pool-exhaustion preemption with exact resume, and the
queue-until-blocks-free backpressure path."""
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.models import (gpt_decode_step, gpt_forward, gpt_init,
                               gpt_prefill, gpt_tiny)
from paddle_tpu.serving import (InferenceEngine, KVCache, PagedKVCache,
                                QueueFull, cache_insert, sample_tokens)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fp32 so the cache path and the full-recompute path agree to fp tolerance
# (bf16 would make argmax ties an accident of reduction order)
CFG = gpt_tiny(dtype=jnp.float32, seq_len=64)
PARAMS = gpt_init(CFG, seed=3)
RNG = np.random.default_rng(7)


def _prompt(n):
    return RNG.integers(0, CFG.vocab_size, n).astype(np.int32)


# ONE jitted full-sequence forward at the padded length serves every
# reference-decode step: causality makes end-padding exact (position i's
# logits never see positions > i), so logits[0, len-1] of the padded
# buffer equals the unpadded full recompute — and the test file pays one
# compile instead of an eager dispatch storm per token.
_FULL_PAD = jax.jit(lambda p, t: gpt_forward(CFG, p, t))


def _ref_step_logits(toks):
    buf = np.zeros((1, CFG.seq_len), np.int32)
    buf[0, :len(toks)] = toks
    return np.asarray(_FULL_PAD(PARAMS, jnp.asarray(buf))[0, len(toks) - 1])


def _ref_greedy(prompt, n):
    """Full-recompute greedy decode — the ground truth the cache path must
    reproduce token-for-token."""
    toks = list(np.asarray(prompt))
    out = []
    for _ in range(n):
        t = int(np.argmax(_ref_step_logits(toks)))
        out.append(t)
        toks.append(t)
    return out


@pytest.fixture
def engine(request):
    engines = []

    def make(params=PARAMS, **kw):
        kw.setdefault("n_slots", 2)
        eng = InferenceEngine(CFG, params, **kw)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        eng.shutdown(drain=False, timeout=10)


class TestKVCacheDecode:
    def test_prefill_matches_forward_logits(self):
        tokens = jnp.asarray(_prompt(12)[None])
        want = gpt_forward(CFG, PARAMS, tokens)
        got, (k, v) = gpt_prefill(CFG, PARAMS, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert k.shape == (1, CFG.n_layers, CFG.n_heads, 12, CFG.head_dim)
        assert v.shape == k.shape

    def test_cached_greedy_matches_full_recompute(self):
        """Acceptance: token-identical greedy across 20 steps, and the
        per-step decode logits match the recompute logits."""
        prompt = _prompt(9)
        n = 20
        ref = _ref_greedy(prompt, n)

        logits, (ke, ve) = gpt_prefill(CFG, PARAMS, jnp.asarray(prompt[None]))
        cache = KVCache(CFG, n_slots=2)
        k, v = cache_insert(cache.k, cache.v, 0, ke[0], ve[0])
        tok = int(jnp.argmax(logits[0, len(prompt) - 1]))
        got = [tok]
        pos = len(prompt)
        seq = list(prompt)
        for _ in range(n - 1):
            seq.append(tok)
            lg, (k, v) = gpt_decode_step(
                CFG, PARAMS, (k, v), jnp.asarray([pos, 0], jnp.int32),
                jnp.asarray([tok, 0], jnp.int32))
            np.testing.assert_allclose(np.asarray(lg[0]),
                                       _ref_step_logits(seq),
                                       rtol=2e-4, atol=2e-4)
            tok = int(jnp.argmax(lg[0]))
            got.append(tok)
            pos += 1
        assert got == ref

    def test_decode_step_faster_than_recompute(self):
        """Acceptance: one cached decode step beats one full-sequence
        recompute per token at seq_len >= 128."""
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=192)
        params = gpt_init(cfg, seed=1)
        S = 128
        prompt = jnp.asarray(
            RNG.integers(0, cfg.vocab_size, (1, S)), jnp.int32)

        full = jax.jit(lambda p, t: gpt_forward(cfg, p, t))
        jax.block_until_ready(full(params, prompt))

        _, (ke, ve) = gpt_prefill(cfg, params, prompt)
        cache = KVCache(cfg, n_slots=1)
        k, v = cache_insert(cache.k, cache.v, 0, ke[0], ve[0])
        dec = jax.jit(lambda p, kk, vv, pos, t: gpt_decode_step(
            cfg, p, (kk, vv), pos, t))
        pos = jnp.asarray([S], jnp.int32)
        tok = jnp.asarray([5], jnp.int32)
        jax.block_until_ready(dec(params, k, v, pos, tok)[0])

        def best(f, reps=20):
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f())
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t_full = best(lambda: full(params, prompt))
        t_dec = best(lambda: dec(params, k, v, pos, tok)[0])
        assert t_dec < t_full, (
            f"cached decode {t_dec * 1e3:.3f}ms/token is not faster than "
            f"full recompute {t_full * 1e3:.3f}ms/token at S={S}")

    def test_kv_cache_slot_accounting(self):
        cache = KVCache(CFG, n_slots=3, max_len=32)
        assert cache.free_count == 3 and cache.occupancy == 0
        a, b = cache.alloc(), cache.alloc()
        assert {a, b} == {0, 1} and cache.occupancy == 2
        cache.release(a)
        with pytest.raises(ValueError):
            cache.release(a)
        assert cache.alloc() == 2 and cache.alloc() == a
        assert cache.alloc() is None           # full
        with pytest.raises(ValueError):
            KVCache(CFG, n_slots=1, max_len=CFG.seq_len + 1)


class TestSampling:
    def test_greedy_and_top_k1_agree_with_argmax(self):
        logits = jnp.asarray(RNG.normal(size=(3, 32)), jnp.float32)
        am = np.asarray(jnp.argmax(logits, axis=-1))
        key = jax.random.key(0)
        zeros, ones = jnp.zeros(3), jnp.ones(3)
        greedy = sample_tokens(logits, key, zeros, jnp.zeros(3, jnp.int32),
                               ones)
        topk1 = sample_tokens(logits, key, ones,
                              jnp.ones(3, jnp.int32), ones)
        np.testing.assert_array_equal(np.asarray(greedy), am)
        np.testing.assert_array_equal(np.asarray(topk1), am)

    def test_top_k_and_top_p_restrict_support(self):
        # row distribution heavily peaked on the last two ids
        logits = jnp.asarray(np.tile([0.0, 1.0, 8.0, 9.0], (2, 1)),
                             jnp.float32)
        temps = jnp.ones(2)
        for i in range(50):
            key = jax.random.key(i)
            tk = sample_tokens(logits, key, temps,
                               jnp.full(2, 2, jnp.int32), jnp.ones(2))
            assert set(np.asarray(tk).tolist()) <= {2, 3}
            tp = sample_tokens(logits, key, temps,
                               jnp.zeros(2, jnp.int32), jnp.full(2, 0.6))
            assert set(np.asarray(tp).tolist()) <= {3}

    def test_per_slot_params_mix(self):
        """One batch can mix greedy and sampled slots (continuous batching
        serves heterogeneous requests through one program)."""
        logits = jnp.asarray(RNG.normal(size=(2, 64)), jnp.float32)
        out = sample_tokens(logits, jax.random.key(1),
                            jnp.asarray([0.0, 1.0], jnp.float32),
                            jnp.zeros(2, jnp.int32), jnp.ones(2))
        assert int(out[0]) == int(jnp.argmax(logits[0]))
        assert 0 <= int(out[1]) < 64


class TestEngine:
    def test_engine_matches_reference_greedy(self, engine):
        eng = engine()
        p1, p2 = _prompt(6), _prompt(11)
        r1 = eng.submit(p1, max_new_tokens=10)
        r2 = eng.submit(p2, max_new_tokens=8)
        assert r1.result(timeout=120) == _ref_greedy(p1, 10)
        assert r2.result(timeout=120) == _ref_greedy(p2, 8)
        assert r1.finish_reason == "length"
        assert eng.occupancy == 0

    def test_late_request_admitted_mid_decode(self, engine):
        """Acceptance: a late arrival lands in a free slot and completes
        while an earlier request is still mid-generation — no global
        drain — with occupancy and tokens/s gauges populated."""
        eng = engine(n_slots=2)
        pa, pb = _prompt(4), _prompt(5)
        ra = eng.submit(pa, max_new_tokens=58)
        stream = ra.stream(timeout=120)
        for _ in range(3):            # A is warmed up and mid-decode
            next(stream)
        rb = eng.submit(pb, max_new_tokens=3)
        saw_both = 0
        deadline = time.monotonic() + 30
        while not rb.done and time.monotonic() < deadline:
            saw_both = max(saw_both,
                           monitor.stat_get("serving_slot_occupancy"))
            time.sleep(0.0005)
        got_b = rb.result(timeout=120)
        assert not ra.done, "late request should finish first, without " \
                            "draining the earlier one"
        assert saw_both == 2, "both slots should have been generating at once"
        assert got_b == _ref_greedy(pb, 3)
        assert ra.result(timeout=120) == _ref_greedy(pa, 58)
        assert monitor.stat_get("serving_tokens_per_s") > 0

    def test_eos_eviction(self, engine):
        # gpt_init's small weights leave the tied head echoing the last
        # prompt token for ever (every seed continues [t, t, t, ...]), so
        # eos would fire on the prefill token. Block matmuls scaled x8
        # make the blocks outweigh the residual: seed 4 / prompt seed 1
        # continues [494, 178, 307, ...] — the third token is NEW, so eos
        # fires mid-generation, on the decode-tick eviction path
        params = gpt_init(CFG, seed=4)
        params = dict(params, blocks={
            k: w * 8.0 if w.ndim == 3 else w
            for k, w in params["blocks"].items()})
        prompt = np.random.default_rng(1).integers(
            0, CFG.vocab_size, 7).astype(np.int32)
        full = jax.jit(lambda p, t: gpt_forward(CFG, p, t))
        toks, ref = list(prompt), []
        for _ in range(6):
            buf = np.zeros((1, CFG.seq_len), np.int32)
            buf[0, :len(toks)] = toks
            t = int(np.argmax(np.asarray(
                full(params, jnp.asarray(buf))[0, len(toks) - 1])))
            ref.append(t)
            toks.append(t)
        assert ref.index(ref[2]) == 2, "fixture assumption broke"
        eng = engine(params=params, eos_id=ref[2])
        req = eng.submit(prompt, max_new_tokens=12)
        assert req.result(timeout=120) == ref[:3]   # eos token included
        assert req.finish_reason == "eos"

    def test_max_tokens_eviction_counts(self, engine):
        eng = engine(n_slots=1)
        ev0 = monitor.stat_get("serving_evictions")
        reqs = [eng.submit(_prompt(4), max_new_tokens=4) for _ in range(3)]
        for r in reqs:
            assert len(r.result(timeout=120)) == 4
            assert r.finish_reason == "length"
        assert monitor.stat_get("serving_evictions") - ev0 == 3

    def test_cancellation_mid_generation(self, engine):
        eng = engine()
        req = eng.submit(_prompt(4), max_new_tokens=58)
        stream = req.stream(timeout=120)
        next(stream)
        next(stream)
        req.cancel()
        got = req.result(timeout=120)
        assert req.finish_reason == "cancelled"
        assert 2 <= len(got) < 58
        assert eng.occupancy == 0

    def test_deadline_expired_in_queue(self, engine):
        eng = engine()
        req = eng.submit(_prompt(4), max_new_tokens=8, deadline_s=0.0)
        assert req.result(timeout=120) == []
        assert req.finish_reason == "deadline"

    def test_deadline_mid_generation(self, engine):
        eng = engine()
        req = eng.submit(_prompt(4), max_new_tokens=58)
        stream = req.stream(timeout=120)
        next(stream)
        next(stream)
        req.deadline = time.monotonic() - 1.0   # force expiry next tick
        got = req.result(timeout=120)
        assert req.finish_reason == "deadline"
        assert 2 <= len(got) < 58

    def test_queue_backpressure(self, engine):
        eng = engine(n_slots=1, queue_size=1)
        blocker = eng.submit(_prompt(4), max_new_tokens=40)
        # wait until the blocker owns the slot so the next submit queues
        bstream = blocker.stream(timeout=120)
        next(bstream)
        queued = eng.submit(_prompt(4), max_new_tokens=2)
        with pytest.raises(QueueFull):
            eng.submit(_prompt(4), max_new_tokens=2, block=False)
        with pytest.raises(QueueFull):
            eng.submit(_prompt(4), max_new_tokens=2, timeout=0.05)
        assert len(blocker.result(timeout=120)) == 40
        assert len(queued.result(timeout=120)) == 2

    def test_submit_validation_and_shutdown(self, engine):
        eng = engine()
        with pytest.raises(ValueError):
            eng.submit([], max_new_tokens=2)
        with pytest.raises(ValueError):
            eng.submit(_prompt(CFG.seq_len), max_new_tokens=2)
        req = eng.submit(_prompt(4), max_new_tokens=3)
        eng.shutdown(drain=True, timeout=120)
        assert req.finish_reason == "length"       # drained, not dropped
        assert len(req.result(timeout=1)) == 3
        with pytest.raises(RuntimeError):
            eng.submit(_prompt(4))

    def test_submit_after_scheduler_crash_fails_fast(self, engine):
        """ISSUE 5 satellite: a dead scheduler must not let submit()
        enqueue requests that hang forever — it fails fast with the
        stored crash cause."""
        eng = engine()
        boom = RuntimeError("device wedged")

        def crash(*a, **kw):
            raise boom

        eng._prefill_one_chunk = crash
        victim = eng.submit(_prompt(4), max_new_tokens=4)
        with pytest.raises(RuntimeError):
            victim.result(timeout=120)
        assert victim.finish_reason == "error"
        eng._thread.join(timeout=120)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="device wedged"):
            eng.submit(_prompt(4), max_new_tokens=4)
        assert time.monotonic() - t0 < 1.0  # fail-fast, not a queue hang

    def test_shutdown_without_drain_evicts(self, engine):
        eng = engine(n_slots=1)
        a = eng.submit(_prompt(4), max_new_tokens=58)
        b = eng.submit(_prompt(4), max_new_tokens=58)  # queued behind a
        astream = a.stream(timeout=120)
        next(astream)
        eng.shutdown(drain=False, timeout=120)
        assert a.result(timeout=1) is not None
        assert a.finish_reason == "shutdown"
        assert b.finish_reason == "shutdown"


class TestPagedKVCache:
    def test_block_pool_accounting_gauges_and_double_free(self):
        """Satellite: kv_blocks_free/used + kv_fragmentation gauges, and
        a loud AssertionError on free-list double-free."""
        cache = PagedKVCache(CFG, n_slots=2, block_size=8, n_blocks=9)
        assert cache.free_blocks_count == 8          # block 0 = sink
        assert monitor.stat_get("kv_blocks_used") == 0
        s = cache.alloc()
        assert cache.grow(s, 17)                     # 3 blocks
        assert cache.used_blocks_count == 3
        assert monitor.stat_get("kv_blocks_used") == 3
        assert monitor.stat_get("kv_blocks_free") == 5
        cache.lengths[s] = 17
        cache.update_gauges()
        # 3 blocks x 8 = 24 capacity, 17 live -> 29% internal fragmentation
        assert monitor.stat_get("kv_fragmentation") == 29
        assert 0 not in cache.block_tables[s]        # sink never allocated
        blocks = list(cache.block_tables[s])
        cache.release(s)
        assert cache.free_blocks_count == 8
        assert monitor.stat_get("kv_fragmentation") == 0
        with pytest.raises(AssertionError):
            cache.free_blocks(blocks[:1])            # double-free trips
        with pytest.raises(ValueError):
            cache.release(s)                         # slot double-free too
        s2 = cache.alloc()
        assert not cache.grow(s2, 8 * 9)   # needs 9 > 8 free: all-or-nothing
        assert cache.block_tables[s2] == []

    def test_table_rows_are_sink_padded(self):
        cache = PagedKVCache(CFG, n_slots=2, block_size=8)
        s = cache.alloc()
        cache.grow(s, 20)
        row = cache.table_row(s)
        assert row.shape == (cache.table_width,)
        assert list(row[:3]) == cache.block_tables[s]
        assert (row[3:] == 0).all()
        tables = cache.tables_array([s])
        assert (tables[1 - s] == 0).all()            # inactive row -> sink


class TestPagedEngine:
    def _make(self, engine, **kw):
        kw.setdefault("block_size", 8)
        kw.setdefault("prefill_chunk", 16)
        return engine(**kw)

    def test_admits_prompt_longer_than_fixed_budget(self, engine):
        """Acceptance: no per-slot max_len budget — a 40-token prompt
        (past the 32 the fixed engine was capped at here) admits
        whenever free blocks suffice, up to cfg.seq_len."""
        prompt = _prompt(40)
        paged = self._make(engine)
        assert paged.max_len == CFG.seq_len
        got = paged.submit(prompt, max_new_tokens=6).result(timeout=120)
        assert got == _ref_greedy(prompt, 6)

    def test_paged_argument_selects_nothing(self, engine):
        """``paged`` stays in the signature for the benchmark's workload
        files only: False raises, and True and no argument build the
        same engine (the same decode program)."""
        with pytest.raises(ValueError, match="fixed-slot target path is "
                                             "gone"):
            InferenceEngine(CFG, PARAMS, n_slots=2, paged=False)
        plain, asked = engine(), engine(paged=True)
        assert not hasattr(plain, "paged")
        assert isinstance(plain.cache, PagedKVCache)
        assert plain.lower_decode().as_text() \
            == asked.lower_decode().as_text()
        with pytest.raises(TypeError):
            InferenceEngine(CFG, PARAMS, max_len=32)

    def test_chunked_prefill_interleaves_with_decode(self, engine):
        """Acceptance: a long-prompt admission advances at most
        prefill_chunk tokens per tick, and every tick that did chunk
        work while a stream was open also ran a decode step — open
        streams never wait more than one chunk's work."""
        eng = self._make(engine, n_slots=2)
        pa, pb = _prompt(4), _prompt(48)             # pb = 3 chunks of 16
        writer = monitor.start_tracing()
        try:
            ra = eng.submit(pa, max_new_tokens=40)
            sa = ra.stream(timeout=120)
            for _ in range(3):                       # A is mid-decode
                next(sa)
            rb = eng.submit(pb, max_new_tokens=4)
            got_b = rb.result(timeout=120)
            got_a = ra.result(timeout=120)
        finally:
            monitor.stop_tracing()
        assert got_a == _ref_greedy(pa, 40)
        assert got_b == _ref_greedy(pb, 4)
        evs = writer.events()
        chunks = [e for e in evs if e["name"] == "serving.prefill_chunk"]
        b_chunks = [e for e in chunks if e["args"]["start"] > 0]
        assert len(b_chunks) >= 2                    # really chunked
        assert all(e["args"]["chunk"] <= 16 for e in chunks)
        decode_ticks = {e["args"]["tick"] for e in evs
                        if e["name"] == "serving.decode_step"}
        waited = [e["args"]["tick"] for e in chunks
                  if e["args"]["open_streams"] > 0]
        assert waited and all(t in decode_ticks for t in waited)

    def test_eviction_recycles_blocks_identically(self, engine):
        """Satellite: eviction returns every block to the pool, and a
        queued request admitted into recycled blocks generates exactly
        what a fresh engine would."""
        p1, p2 = _prompt(7), _prompt(11)
        want1, want2 = _ref_greedy(p1, 6), _ref_greedy(p2, 8)
        eng = self._make(engine, n_slots=1, n_blocks=9)
        r1 = eng.submit(p1, max_new_tokens=6)
        r2 = eng.submit(p2, max_new_tokens=8)        # queued behind r1
        assert r1.result(timeout=120) == want1
        assert r2.result(timeout=120) == want2       # recycled blocks
        assert eng.cache.used_blocks_count == 0
        assert eng.cache.free_blocks_count == 8
        assert monitor.stat_get("kv_blocks_used") == 0

    def test_pool_exhaustion_preempts_and_resumes_exactly(self, engine):
        """Two streams outgrow a 6-block pool: the youngest is preempted
        back to the queue and resumes by re-prefilling — both outputs
        stay token-identical to the reference."""
        pa, pb = _prompt(9), _prompt(11)
        ra_ref, rb_ref = _ref_greedy(pa, 20), _ref_greedy(pb, 20)
        pre0 = monitor.stat_get("serving_preemptions")
        eng = self._make(engine, n_slots=2, n_blocks=7)
        ra = eng.submit(pa, max_new_tokens=20)
        rb = eng.submit(pb, max_new_tokens=20)
        assert ra.result(timeout=120) == ra_ref
        assert rb.result(timeout=120) == rb_ref
        assert monitor.stat_get("serving_preemptions") - pre0 >= 1

    def test_queue_until_blocks_free(self, engine):
        """Acceptance: the former hard reject is now backpressure — a
        prompt that does not fit the free pool waits at the head of the
        queue until evictions free blocks, then completes correctly."""
        p1, p2 = _prompt(30), _prompt(30)
        eng = self._make(engine, n_slots=2, n_blocks=7)  # one at a time
        r1 = eng.submit(p1, max_new_tokens=10)
        r2 = eng.submit(p2, max_new_tokens=10)
        assert r1.result(timeout=120) == _ref_greedy(p1, 10)
        assert r2.result(timeout=120) == _ref_greedy(p2, 10)

    def test_lone_slot_pool_exhaustion_truncates(self, engine):
        """A lone stream that outgrows the whole pool is evicted with
        finish_reason='length' (cache capacity), not hung."""
        p = _prompt(9)
        eng = self._make(engine, n_slots=1, n_blocks=3)  # 16-token pool
        r = eng.submit(p, max_new_tokens=30)
        out = r.result(timeout=120)
        assert r.finish_reason == "length"
        assert out == _ref_greedy(p, len(out))
        assert 0 < len(out) < 30

    def test_tokens_per_s_window_is_tick_scoped(self, engine):
        """Satellite: tokens/s is a sliding window over the last N ticks
        (deque maxlen), not a lifetime average."""
        eng = engine(tps_window_ticks=8)
        assert eng._window.maxlen == 8
        eng.submit(_prompt(5), max_new_tokens=12).result(timeout=120)
        assert monitor.stat_get("serving_tokens_per_s") > 0
        eng.shutdown(drain=True, timeout=120)
        for _ in range(20):
            eng._note_tokens(3)
        assert len(eng._window) == 8                 # old ticks fell out


class TestObservability:
    def test_lower_decode_exposes_the_tick_program(self, engine):
        """Assert-on-HLO surface (chip_smoke.py reads it for the Mosaic
        call): the lowered decode program, at the widest table by
        default and at the width bucket asked for."""
        eng = engine(block_size=8, prefill_chunk=16)
        text = eng.lower_decode().as_text()
        assert "func.func public @main" in text
        assert "tensor<2x8xi32>" in text      # 64 positions / 8 a block
        text = eng.lower_decode(table_width=3).as_text()
        assert "tensor<2x4xi32>" in text      # 3 blocks -> width bucket 4

    def _trace_report(self):
        spec = importlib.util.spec_from_file_location(
            "trace_report", os.path.join(_ROOT, "tools", "trace_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gauges_and_spans(self, engine):
        writer = monitor.start_tracing()
        try:
            eng = engine()
            eng.submit(_prompt(5), max_new_tokens=6).result(timeout=120)
            eng.submit(_prompt(6), max_new_tokens=4).result(timeout=120)
        finally:
            monitor.stop_tracing()
        names = {e["name"] for e in writer.events()}
        assert "serving.prefill_chunk" in names
        assert "serving.prefill" not in names
        assert "serving.decode_step" in names
        assert monitor.stat_get("serving_prefill_ms") >= 0
        assert monitor.stat_get("serving_decode_ms") > 0
        assert monitor.stat_get("serving_tokens_per_s") > 0
        assert monitor.stat_get("serving_queue_depth") == 0

        tr = self._trace_report()
        rows = tr.aggregate(writer.events())
        verdict = tr.serving_report(rows, file=open(os.devnull, "w"))
        assert verdict["prefills"] == 0
        assert verdict["prefill_chunks"] >= 2
        assert verdict["decode_steps"] >= 1
        assert "verdict" in verdict

    def test_paged_report_learns_chunks_and_starvation(self, engine):
        """Satellite: serving_report counts serving.prefill_chunk spans
        and prints the prefill-starvation verdict (max consecutive ticks
        any open stream waited without a decode step — 0 when chunked
        prefill interleaves correctly)."""
        writer = monitor.start_tracing()
        try:
            eng = engine(block_size=8, prefill_chunk=16)
            ra = eng.submit(_prompt(4), max_new_tokens=30)
            next(ra.stream(timeout=120))
            eng.submit(_prompt(40), max_new_tokens=4).result(timeout=120)
            ra.result(timeout=120)
        finally:
            monitor.stop_tracing()
        evs = writer.events()
        tr = self._trace_report()
        rows = tr.aggregate(evs)
        verdict = tr.serving_report(rows, file=open(os.devnull, "w"),
                                    events=evs)
        assert verdict["prefill_chunks"] >= 3       # 40-token prompt
        assert verdict["decode_steps"] >= 1
        assert verdict["max_consecutive_starved_ticks"] == 0
        assert "no prefill starvation" in verdict["starvation_verdict"]
        assert monitor.stat_get("kv_blocks_free") >= 0
