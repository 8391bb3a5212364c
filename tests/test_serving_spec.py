"""ISSUE 10 — speculative decoding + multi-chip sharded decode for the
serving engine, and their satellites: per-request RNG streams (batch
composition cannot perturb a sampled stream), the byte-level tokenizer
front end, spec × paged preemption-resume token identity, the
spec/shard trace-report verdicts, and the FLAGS_serving_mesh=0 /
draft=None pins."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import gpt_forward, gpt_init, gpt_tiny
from paddle_tpu.models.gpt import (gpt_decode_step, gpt_prefill,
                                   gpt_truncate, gpt_verify_step)
from paddle_tpu.serving import (ByteTokenizer, InferenceEngine, KVCache,
                                cache_insert, spec_accept, stream_keys)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fp32 so cache/verify/full-recompute argmaxes agree exactly
CFG = gpt_tiny(dtype=jnp.float32, seq_len=64)
PARAMS = gpt_init(CFG, seed=3)
DRAFT = gpt_truncate(CFG, PARAMS, 2)
RNG = np.random.default_rng(11)


def _prompt(n):
    return RNG.integers(0, CFG.vocab_size, n).astype(np.int32)


_FULL_PAD = jax.jit(lambda p, t: gpt_forward(CFG, p, t))


def _ref_greedy(prompt, n):
    toks = list(np.asarray(prompt))
    out = []
    for _ in range(n):
        buf = np.zeros((1, CFG.seq_len), np.int32)
        buf[0, :len(toks)] = toks
        t = int(np.argmax(np.asarray(
            _FULL_PAD(PARAMS, jnp.asarray(buf))[0, len(toks) - 1])))
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


def _mesh42():
    from jax.sharding import Mesh

    from paddle_tpu.parallel.mesh import AXES
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual mesh (conftest XLA_FLAGS)")
    return Mesh(np.array(devs[:8]).reshape(4, 1, 1, 2), AXES)


class TestVerifyStep:
    def test_verify_matches_sequential_decode(self):
        """The k+1-position verify pass is gpt_decode_step run
        token-by-token, in one program (logits AND cache writes)."""
        prompt = _prompt(9)
        _, (ke, ve) = gpt_prefill(CFG, PARAMS, jnp.asarray(prompt[None]))
        cache = KVCache(CFG, n_slots=2)
        k, v = cache_insert(cache.k, cache.v, 0, ke[0], ve[0])
        k2, v2 = k, v
        toks = _prompt(4)
        pos0 = len(prompt)
        seq = []
        for j, t in enumerate(toks):
            lg, (k, v) = gpt_decode_step(
                CFG, PARAMS, (k, v), jnp.asarray([pos0 + j, 0], jnp.int32),
                jnp.asarray([t, 0], jnp.int32))
            seq.append(np.asarray(lg[0]))
        vlg, (k2, v2) = gpt_verify_step(
            CFG, PARAMS, (k2, v2), jnp.asarray([pos0, 0], jnp.int32),
            jnp.asarray([toks, np.zeros(4, np.int32)], jnp.int32))
        for j in range(4):
            np.testing.assert_allclose(np.asarray(vlg[0, j]), seq[j],
                                       rtol=2e-4, atol=2e-4)
            assert int(np.argmax(vlg[0, j])) == int(np.argmax(seq[j]))
        np.testing.assert_allclose(np.asarray(k2[0]), np.asarray(k[0]),
                                   rtol=1e-5, atol=1e-5)


class TestSpecAccept:
    def test_greedy_rule_counts_and_correction(self):
        B, K, V = 3, 4, 50
        rng = np.random.default_rng(0)
        tl = jnp.asarray(rng.normal(size=(B, K + 1, V)).astype(np.float32))
        dl = jnp.asarray(rng.normal(size=(B, K, V)).astype(np.float32))
        tgt = np.asarray(jnp.argmax(tl, axis=-1))
        d = tgt[:, :K].copy()
        d[0, 2] = (d[0, 2] + 1) % V          # row 0 misses at j=2
        d[2, 0] = (d[2, 0] + 1) % V          # row 2 misses immediately
        keys = stream_keys(jax.random.key(0),
                           jnp.arange(B, dtype=jnp.int32),
                           jnp.zeros(B, jnp.int32))
        toks, n = spec_accept(tl, dl, jnp.asarray(d), keys,
                              jnp.zeros(B, jnp.float32),
                              jnp.zeros(B, jnp.int32),
                              jnp.ones(B, jnp.float32))
        toks, n = np.asarray(toks), np.asarray(n)
        assert list(n) == [3, K + 1, 1]
        assert list(toks[0, :3]) == [d[0, 0], d[0, 1], tgt[0, 2]]
        assert list(toks[1, :K + 1]) == list(tgt[1])   # all accepted + bonus
        assert toks[2, 0] == tgt[2, 0]                 # immediate correction

    def test_sampled_first_token_keeps_target_distribution(self):
        """Acceptance rule correctness: over many independent streams the
        FIRST emitted token's histogram matches the target softmax —
        speculation must not bias sampled output."""
        B, K, V = 4000, 2, 8
        rng = np.random.default_rng(1)
        tl = jnp.broadcast_to(jnp.asarray(
            rng.normal(size=(1, K + 1, V)).astype(np.float32)), (B, K + 1, V))
        ql = jnp.broadcast_to(jnp.asarray(
            rng.normal(size=(1, K, V)).astype(np.float32)), (B, K, V))
        keys = stream_keys(jax.random.key(5),
                           jnp.arange(B, dtype=jnp.int32),
                           jnp.zeros(B, jnp.int32))
        from paddle_tpu.serving.sampling import (DRAFT_SALT,
                                                 sample_tokens_streams)
        ones = jnp.ones(B, jnp.float32)
        zeros = jnp.zeros(B, jnp.int32)
        draw = jax.jit(lambda lg, ks: sample_tokens_streams(
            lg, ks, ones, zeros, ones))
        dk = jax.vmap(lambda k: jax.random.fold_in(k, DRAFT_SALT))(keys)
        d0 = draw(ql[:, 0], dk)
        d1 = draw(ql[:, 1],
                  jax.vmap(lambda k: jax.random.fold_in(k, 99))(keys))
        toks, _ = jax.jit(spec_accept)(tl, ql, jnp.stack([d0, d1], axis=1),
                                       keys, ones, zeros, ones)
        hist = np.bincount(np.asarray(toks[:, 0]), minlength=V) / B
        want = np.asarray(jax.nn.softmax(tl[0, 0]))
        assert np.abs(hist - want).max() < 0.03


class TestSpeculativeEngine:
    @pytest.mark.slow  # the identity leg below again, at the default
    #                    block and chunk sizes, with the spec gauges
    def test_spec_greedy_token_identity_and_gauges(self, engine):
        """Acceptance: speculative greedy == non-speculative greedy ==
        full-recompute reference, spec gauges move, report verdict."""
        prompt = _prompt(9)
        ref = _ref_greedy(prompt, 20)
        base = engine()
        assert base.submit(prompt, max_new_tokens=20).result(
            timeout=120) == ref
        p0 = monitor.stat_get("spec_proposed")
        spec = engine(draft=DRAFT, spec_k=4)
        assert spec.submit(prompt, max_new_tokens=20).result(
            timeout=120) == ref
        assert monitor.stat_get("spec_proposed") > p0
        assert 0 <= monitor.stat_get("spec_acceptance_rate") <= 100

    def test_spec_greedy_token_identity_paged(self, engine):
        prompt = _prompt(9)
        ref = _ref_greedy(prompt, 20)
        eng = engine(block_size=8, prefill_chunk=16,
                     draft=DRAFT, spec_k=4)
        assert eng.submit(prompt, max_new_tokens=20).result(
            timeout=120) == ref

    def test_spec_paged_preemption_resume_identity(self, engine):
        """Satellite: spec × paged preemption — two streams outgrow a
        tiny pool; the preempted stream resumes (draft cache re-seeded
        by the chunked re-prefill) and both outputs stay
        token-identical."""
        pa, pb = _prompt(9), _prompt(11)
        ra_ref, rb_ref = _ref_greedy(pa, 20), _ref_greedy(pb, 20)
        pre0 = monitor.stat_get("serving_preemptions")
        eng = engine(block_size=8, prefill_chunk=16,
                     n_blocks=7, draft=DRAFT, spec_k=3)
        ra = eng.submit(pa, max_new_tokens=20)
        rb = eng.submit(pb, max_new_tokens=20)
        assert ra.result(timeout=120) == ra_ref
        assert rb.result(timeout=120) == rb_ref
        assert monitor.stat_get("serving_preemptions") - pre0 >= 1

    def test_spec_eos_truncates_mid_burst(self, engine):
        """A burst that includes eos stops exactly there — extra
        accepted tokens past eos are discarded like the plain engine."""
        prompt = _prompt(7)
        ref = _ref_greedy(prompt, 20)
        eos = ref[8]
        want = ref[:ref.index(eos) + 1]   # first occurrence wins
        eng = engine(draft=DRAFT, spec_k=4)
        req = eng.submit(prompt, max_new_tokens=20, eos_id=eos)
        assert req.result(timeout=120) == want
        assert req.finish_reason == "eos"

    def test_spec_near_cap_falls_back_not_crashes(self, engine):
        """Slots without k+1 positions of headroom drop the tick to the
        plain program: output still reference-exact up to the cap."""
        prompt = _prompt(CFG.seq_len - 6)     # 5 tokens of headroom < k+1
        eng = engine(draft=DRAFT, spec_k=6)
        req = eng.submit(prompt, max_new_tokens=30)
        out = req.result(timeout=120)
        assert req.finish_reason == "length"
        assert out == _ref_greedy(prompt, len(out))
        assert 0 < len(out) <= 7      # prefill + (max_len - S) decode steps

    def test_draft_contract_validation(self, engine):
        import dataclasses
        bad_vocab = dataclasses.replace(DRAFT[0], vocab_size=17)
        with pytest.raises(ValueError, match="vocab"):
            engine(draft=(bad_vocab, DRAFT[1]))
        short = dataclasses.replace(DRAFT[0], seq_len=8)
        with pytest.raises(ValueError, match="seq_len"):
            engine(draft=(short, DRAFT[1]))
        with pytest.raises(ValueError, match="spec_k"):
            engine(draft=DRAFT, spec_k=0)
        with pytest.raises(ValueError, match="outside"):
            gpt_truncate(CFG, PARAMS, 99)

    def test_spec_sampled_is_deterministic_per_seed(self, engine):
        """Sampled speculative output is a pure function of
        (seed, rid): two fresh engines replay the same stream."""
        prompt = _prompt(8)
        outs = []
        for _ in range(2):
            eng = engine(draft=DRAFT, spec_k=3, seed=123)
            outs.append(eng.submit(prompt, max_new_tokens=12,
                                   temperature=0.8).result(timeout=120))
            eng.shutdown(drain=True, timeout=30)
        assert outs[0] == outs[1]


class TestPerRequestRNGStreams:
    def test_stream_unperturbed_by_batch_neighbors(self, engine):
        """Satellite pin: a sampled stream depends only on (seed, rid) —
        a neighbor admitted into the batch (and evicted mid-run) does
        not change a single token of it."""
        pa = _prompt(8)
        solo = engine(seed=7)
        want = solo.submit(pa, max_new_tokens=16,
                           temperature=0.9).result(timeout=120)
        solo.shutdown(drain=True, timeout=30)

        crowd = engine(seed=7)
        ra = crowd.submit(pa, max_new_tokens=16, temperature=0.9)
        # neighbor with a different sampling config, evicted early (eos
        # impossible: max_new small) — admission AND eviction both
        # perturb the batch composition mid-stream
        rb = crowd.submit(_prompt(5), max_new_tokens=3, temperature=0.3,
                          top_k=7)
        assert rb.result(timeout=120)
        assert ra.result(timeout=120) == want

    def test_stream_keys_fold_rid_and_draw(self):
        base = jax.random.key(0)
        k1 = stream_keys(base, jnp.asarray([1, 1, 2], jnp.int32),
                         jnp.asarray([0, 1, 0], jnp.int32))
        raw = jax.random.key_data(k1)
        assert not np.array_equal(raw[0], raw[1])   # draw index matters
        assert not np.array_equal(raw[0], raw[2])   # rid matters
        k2 = stream_keys(base, jnp.asarray([1], jnp.int32),
                         jnp.asarray([0], jnp.int32))
        assert np.array_equal(raw[0], jax.random.key_data(k2)[0])


class TestTokenizer:
    def test_roundtrip_and_merges(self):
        tok = ByteTokenizer()
        for s in ["hello", "naïve café 拼音 🚀", "", "a\nb\t"]:
            assert tok.decode(tok.encode(s)) == s
        m = ByteTokenizer(merges=["the ", "ing", "拼音"])
        s = "the king sing ing 拼音"
        ids = m.encode(s)
        assert m.decode(ids) == s
        assert len(ids) < len(s.encode("utf-8"))     # merges compress
        assert any(int(i) >= 256 for i in ids)
        with pytest.raises(ValueError):
            ByteTokenizer(merges=["x"])              # under the byte floor

    def test_vocab_file_roundtrip(self, tmp_path):
        m = ByteTokenizer(merges=["the ", "ing"])
        path = str(tmp_path / "vocab.json")
        m.save(path)
        m2 = ByteTokenizer.load(path)
        s = "the thing"
        assert list(m2.encode(s)) == list(m.encode(s))
        assert m2.eos_id == m.eos_id
        lines = str(tmp_path / "vocab.txt")
        with open(lines, "w") as f:
            f.write("the \ning\n")
        m3 = ByteTokenizer.load(lines)
        assert m3.decode(m3.encode(s)) == s
        with pytest.raises(FileNotFoundError):
            ByteTokenizer.load(str(tmp_path / "missing.json"))

    def test_stream_detokenizer_holds_split_utf8(self):
        tok = ByteTokenizer()
        det = tok.stream_detokenizer()
        raw = "é🚀x".encode("utf-8")
        pieces = [det.push(b) for b in raw] + [det.flush()]
        assert "".join(pieces) == "é🚀x"
        assert pieces[0] == ""            # lead byte of é held back
        assert det.push(tok.eos_id) == ""  # specials skipped

    def test_engine_text_front_end(self, engine):
        tok = ByteTokenizer()
        eng = engine(tokenizer=tok)
        req = eng.submit(text="hi", max_new_tokens=8)
        assert req.eos_id == tok.eos_id   # tokenizer eos wired in
        pieces = list(req.stream_text(timeout=120))
        assert "".join(pieces) == req.text()
        assert req.text() == tok.decode(req.result(), skip_special=True)
        with pytest.raises(ValueError, match="not both"):
            eng.submit(prompt=[1], text="x")
        with pytest.raises(ValueError, match="provide a prompt"):
            eng.submit()
        plain = engine()
        with pytest.raises(ValueError, match="tokenizer"):
            plain.submit(text="x")


class TestMultiChipDecode:
    def test_sharded_decode_token_identity_and_hlo(self, engine):
        """Acceptance: slots sharded over "data", weights over "model",
        output token-identical to single-chip, collectives in the
        compiled decode HLO, serving_shards gauge set."""
        from jax.sharding import PartitionSpec as P

        mesh = _mesh42()
        prompt = _prompt(9)
        ref = _ref_greedy(prompt, 12)
        eng = engine(n_slots=8, mesh=mesh)
        assert eng._shards == 4
        assert monitor.stat_get("serving_shards") == 4
        assert eng.cache.kb.sharding.spec == P("data", None, "model",
                                               None, None)
        assert eng._params["blocks"]["qkv_w"].sharding.spec == \
            P(None, None, "model")
        assert eng.submit(prompt, max_new_tokens=12).result(
            timeout=300) == ref

        hlo = eng.lower_decode().compile().as_text()
        assert "all-reduce" in hlo or "all-gather" in hlo

    def test_paged_mesh_per_shard_block_accounting(self, engine):
        """Per-data-shard pool layout: every slot's blocks stay inside
        its shard's range, padding points at the shard's own sink, and
        admission lands in a shard with free blocks + a free slot."""
        mesh = _mesh42()
        prompt = _prompt(9)
        ref = _ref_greedy(prompt, 10)
        eng = engine(n_slots=8, block_size=8, prefill_chunk=16,
                     mesh=mesh)
        cache = eng.cache
        assert cache.shards == 4
        assert cache.n_blocks % 4 == 0
        reqs = [eng.submit(_prompt(9), max_new_tokens=6) for _ in range(4)]
        for r in reqs:
            assert r.result(timeout=300)
        got = eng.submit(prompt, max_new_tokens=10).result(timeout=300)
        assert got == ref
        for s, table in enumerate(cache.block_tables):
            d = cache.shard_of(s)
            lo, hi = d * cache.blocks_per_shard, (d + 1) * cache.blocks_per_shard
            assert all(lo < b < hi for b in table), (s, d, table)
            row = cache.table_row(s)
            assert row[-1] == cache.sink_of(d) or len(table) == len(row)

    def test_serving_mesh_flag_and_pin(self, engine):
        """FLAGS_serving_mesh=4 builds the mesh; =0 (default) + draft=None
        is the single-chip non-speculative engine."""
        _mesh42()   # skip without 8 devices
        prompt = _prompt(6)
        ref = _ref_greedy(prompt, 6)
        paddle.set_flags({"FLAGS_serving_mesh": 4})
        try:
            eng = engine(n_slots=8)
            assert eng._shards == 4
            assert eng.submit(prompt, max_new_tokens=6).result(
                timeout=300) == ref
        finally:
            paddle.set_flags({"FLAGS_serving_mesh": 0})
        pinned = engine()
        assert pinned._mesh is None and pinned._shards == 1
        assert pinned.draft is None and pinned.spec_k == 0
        assert pinned.submit(prompt, max_new_tokens=6).result(
            timeout=120) == ref

    def test_mesh_validation_errors(self, engine):
        mesh = _mesh42()
        with pytest.raises(ValueError, match="divisible"):
            engine(n_slots=3, mesh=mesh)
        with pytest.raises(ValueError, match="int8"):
            engine(n_slots=8, mesh=mesh, int8_weights=True)

    def test_mesh_spec_compose(self, engine):
        """Speculation per shard: mesh + draft together stay greedy
        token-identical."""
        mesh = _mesh42()
        prompt = _prompt(9)
        ref = _ref_greedy(prompt, 10)
        eng = engine(n_slots=8, mesh=mesh, draft=DRAFT, spec_k=3)
        assert eng.submit(prompt, max_new_tokens=10).result(
            timeout=300) == ref


class TestObservability:
    def _trace_report(self):
        spec = importlib.util.spec_from_file_location(
            "trace_report", os.path.join(_ROOT, "tools", "trace_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_spec_report_verdict(self, engine):
        writer = monitor.start_tracing()
        try:
            eng = engine(draft=DRAFT, spec_k=4)
            eng.submit(_prompt(7), max_new_tokens=12).result(timeout=120)
        finally:
            monitor.stop_tracing()
        evs = writer.events()
        spans = [e for e in evs if e["name"] == "serving.decode_step"]
        assert any("proposed" in (e.get("args") or {}) for e in spans)
        tr = self._trace_report()
        out = tr.spec_report(evs, file=open(os.devnull, "w"))
        assert out["proposed"] > 0
        assert 0.0 <= out["acceptance_rate"] <= 1.0
        assert out["tokens_per_target_pass"] > 1.0
        assert "verdict" in out
        assert monitor.stat_get("spec_proposed") >= out["proposed"]

    def test_shard_balance_report_verdict(self, engine):
        mesh = _mesh42()
        writer = monitor.start_tracing()
        try:
            eng = engine(n_slots=8, mesh=mesh)
            reqs = [eng.submit(_prompt(5), max_new_tokens=5)
                    for _ in range(4)]
            for r in reqs:
                r.result(timeout=300)
        finally:
            monitor.stop_tracing()
        evs = writer.events()
        tr = self._trace_report()
        out = tr.shard_balance_report(evs, file=open(os.devnull, "w"))
        assert out["shards"] == 4
        assert len(out["slot_ticks_per_shard"]) == 4
        assert "verdict" in out
