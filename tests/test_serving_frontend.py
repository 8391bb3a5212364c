"""ISSUE 11: radix-tree prefix cache, constrained decoding, and the
multi-tenant OpenAI-style HTTP front end.

Pins, per the acceptance criteria:
- prefix cache ON is greedy token-identical to the cache-cold engine,
  with refcount/CoW edge cases covered (double-admit, evict-while-
  shared, LRU-leaf eviction into the right shard's free list,
  preemption-resume replay, fragmentation/hit-rate gauges);
- JSON-schema/regex constrained decoding emits automaton-legal output
  that json.loads-parses, composing with temperature sampling;
- ``python -m paddle_tpu.serving.frontend`` serves real HTTP end to
  end (completions + streamed chat SSE + schema-constrained JSON),
  with per-tenant 429s under overload while other tenants stay served;
- trace_report grows the frontend_report verdict; graftlint stays
  clean and owns a known-bad fixture for an unguarded radix-tree write.
"""
import http.client
import importlib.util
import json
import os
import re
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import gpt_init, gpt_tiny
from paddle_tpu.serving import InferenceEngine, PagedKVCache
from paddle_tpu.serving.constrained import (compile_constraint,
                                            compile_regex, schema_to_regex)
from paddle_tpu.serving.prefix_cache import RadixPrefixCache
from paddle_tpu.serving.tokenizer import ByteTokenizer

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt_tiny(dtype=jnp.float32, seq_len=64)
PARAMS = gpt_init(CFG, seed=3)
RNG = np.random.default_rng(11)


def _prompt(n, rng=RNG):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def engine():
    engines = []

    def make(params=PARAMS, cfg=CFG, **kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("block_size", 8)
        kw.setdefault("prefill_chunk", 16)
        eng = InferenceEngine(cfg, params, **kw)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        eng.shutdown(drain=False, timeout=30)


# ==========================================================================
# refcounts + copy-on-write in the pool
# ==========================================================================

class TestRefcountedPool:
    def test_refcount_pins_blocks_until_last_unref(self):
        pool = PagedKVCache(CFG, n_slots=2, n_blocks=9, block_size=8)
        s = pool.alloc()
        assert pool.grow(s, 16)
        blocks = list(pool.block_tables[s])
        free0 = pool.free_blocks_count
        pool.ref_block(blocks[0])          # a second owner (the tree)
        pool.release(s)                    # slot lets go of everything
        # the doubly-owned block did NOT return to the free list
        assert pool.free_blocks_count == free0 + 1
        assert pool.ref_count(blocks[0]) == 1
        pool.unref_block(blocks[0])        # last reference drops
        assert pool.free_blocks_count == free0 + 2
        assert pool.ref_count(blocks[0]) == 0

    def test_double_free_and_bad_refs_raise(self):
        pool = PagedKVCache(CFG, n_slots=2, n_blocks=9, block_size=8)
        s = pool.alloc()
        assert pool.grow(s, 8)
        b = pool.block_tables[s][0]
        pool.release(s)
        with pytest.raises(AssertionError):
            pool.unref_block(b)            # already free
        with pytest.raises(AssertionError):
            pool.ref_block(b)              # ref of a free block
        with pytest.raises(AssertionError):
            pool.unref_block(pool.sink_of(0))   # the reserved sink

    def test_splice_refs_and_replace_block_swaps(self):
        pool = PagedKVCache(CFG, n_slots=2, n_blocks=9, block_size=8)
        a = pool.alloc()
        assert pool.grow(a, 16)
        shared = list(pool.block_tables[a])
        b = pool.alloc()
        pool.splice(b, shared)
        assert pool.block_tables[b] == shared
        assert all(pool.ref_count(x) == 2 for x in shared)
        nb = pool.alloc_block(0)
        old = pool.replace_block(b, 1, nb)  # the CoW commit
        assert old == shared[1]
        assert pool.ref_count(shared[1]) == 1    # only slot a now
        assert pool.block_tables[b] == [shared[0], nb]
        pool.release(a)
        pool.release(b)
        assert pool.free_blocks_count == pool.n_blocks - pool.shards

    def test_splice_rejects_cross_shard_blocks(self):
        pool = PagedKVCache(CFG, n_slots=4, n_blocks=16, block_size=8,
                            shards=2)
        a = pool.alloc(prefer_shard=0)
        assert pool.grow(a, 8)
        b = pool.alloc(prefer_shard=1)
        with pytest.raises(AssertionError):
            pool.splice(b, list(pool.block_tables[a]))


# ==========================================================================
# radix tree
# ==========================================================================

class TestRadixTree:
    def _pool_tree(self, shards=1, n_blocks=17, n_slots=2):
        pool = PagedKVCache(CFG, n_slots=n_slots, n_blocks=n_blocks,
                            block_size=8, shards=shards)
        return pool, RadixPrefixCache(pool)

    def _fill(self, pool, slot, n_tokens):
        pool.grow(slot, n_tokens)
        pool.lengths[slot] = n_tokens

    def test_insert_then_match_with_len_minus_one_cap(self):
        pool, tree = self._pool_tree()
        toks = _prompt(20)                  # 2 full blocks + 4 tail
        s = pool.alloc()
        self._fill(pool, s, 20)
        tree.insert(0, toks, pool.block_tables[s])
        assert tree.block_count == 3
        # identical prompt: match stops at len-1 = 19 (one token must
        # remain for the tail prefill), inside the partial block → the
        # engine will CoW it
        m, blocks = tree.match(0, toks)
        assert m == 19
        assert blocks == pool.block_tables[s][:3]
        # shared-prefix prompt diverging in the tail: full blocks only
        other = np.concatenate([toks[:16], _prompt(8)])
        m2, blocks2 = tree.match(0, other)
        assert m2 == 16
        assert blocks2 == pool.block_tables[s][:2]
        # divergent from token 0: no match
        assert tree.match(0, _prompt(12))[0] == 0

    def test_partial_use_of_a_block_matches_any_prefix(self):
        pool, tree = self._pool_tree()
        toks = _prompt(16)
        s = pool.alloc()
        self._fill(pool, s, 16)
        tree.insert(0, toks, pool.block_tables[s])
        probe = np.concatenate([toks[:5], _prompt(10)])
        m, blocks = tree.match(0, probe)
        assert m == 5                       # mid-block: masking makes it legal
        assert blocks == pool.block_tables[s][:1]

    def test_evict_while_shared_refcount_pins(self):
        pool, tree = self._pool_tree()
        toks = _prompt(16)
        s = pool.alloc()
        self._fill(pool, s, 16)
        tree.insert(0, toks, pool.block_tables[s])   # refcount 2 each
        assert tree.evictable_count(0) == 0          # slot still reads them
        assert tree.evict(0, 4) == 0
        pool.release(s)                              # tree is the last owner
        assert tree.evictable_count(0) == 1          # the leaf, then cascades
        assert tree.evict(0, 4) == 2
        assert tree.block_count == 0
        assert pool.free_blocks_count == pool.n_blocks - pool.shards

    def test_lru_leaf_eviction_returns_to_right_shard(self):
        pool, tree = self._pool_tree(shards=2, n_blocks=18, n_slots=2)
        s0 = pool.alloc(prefer_shard=0)
        s1 = pool.alloc(prefer_shard=1)
        t0, t1 = _prompt(8), _prompt(8)
        self._fill(pool, s0, 8)
        self._fill(pool, s1, 8)
        tree.insert(0, t0, pool.block_tables[s0])
        tree.insert(1, t1, pool.block_tables[s1])
        b1 = pool.block_tables[s1][0]
        pool.release(s0)
        pool.release(s1)
        free0, free1 = pool.free_blocks_of(0), pool.free_blocks_of(1)
        assert tree.evict(1, 1) == 1                 # shard 1's tree only
        assert pool.free_blocks_of(1) == free1 + 1
        assert pool.free_blocks_of(0) == free0
        assert b1 in pool._free[1]
        # LRU order within a shard: older (never re-matched) goes first
        tree.match(0, t0)                            # touch shard 0's path
        probe = _prompt(8)
        s2 = pool.alloc(prefer_shard=0)
        self._fill(pool, s2, 8)
        tree.insert(0, probe, pool.block_tables[s2])
        pool.release(s2)
        tree.match(0, t0)                            # t0 most recent again
        assert tree.evict(0, 1) == 1
        assert tree.match(0, probe)[0] == 0          # the stale leaf died
        assert tree.match(0, t0)[0] == 7             # the touched one lives


# ==========================================================================
# engine integration: token identity, double admit, preemption, gauges
# ==========================================================================

class TestPrefixEngine:
    def _shared_prompts(self, n=4):
        rng = np.random.default_rng(5)
        head = rng.integers(0, CFG.vocab_size, 30).astype(np.int32)
        return [np.concatenate([
            head, rng.integers(0, CFG.vocab_size, 6).astype(np.int32)])
            for _ in range(n)]

    def test_greedy_token_identity_vs_cache_cold(self, engine):
        """Acceptance pin: prefix cache ON is token-identical (greedy)
        to the cache-cold engine — including two CONCURRENT streams
        served from the same spliced blocks (the reader's masked
        attention must not see the writer's extensions)."""
        prompts = self._shared_prompts(3)
        cold = engine(n_slots=2, n_blocks=33, prefix_cache=False)
        ref = [cold.generate(p, max_new_tokens=8) for p in prompts] \
            + [cold.generate(p, max_new_tokens=8) for p in prompts]
        warm = engine(n_slots=2, n_blocks=33, prefix_cache=True)
        out = [warm.generate(p, max_new_tokens=8) for p in prompts] \
            + [warm.generate(p, max_new_tokens=8) for p in prompts]
        assert out == ref
        assert warm._prefix.hit_rate > 0.4       # repeats + shared heads
        reqs = [warm.submit(prompts[0], max_new_tokens=8)
                for _ in range(2)]
        assert [r.result(timeout=120) for r in reqs] == [ref[0], ref[0]]

    def test_double_admit_cow_and_gauges(self, engine):
        """Refcount/CoW edge cases on one engine: double-admit of the
        same prompt hits the tree, the partially-used last block is
        CoW-duplicated before the second stream extends it, and the
        hit-rate/fragmentation gauges move."""
        p = _prompt(21)                      # 2 full blocks + 5 in the tail
        eng = engine(n_slots=2, n_blocks=33, prefix_cache=True)
        m0 = monitor.stat_get("prefix_matched_tokens")
        c0 = monitor.stat_get("prefix_cow_copies")
        first = eng.generate(p, max_new_tokens=8)
        assert monitor.stat_get("prefix_matched_tokens") == m0  # cold
        second = eng.generate(p, max_new_tokens=8)
        assert second == first
        # identical re-admit matches 20 of 21 tokens (cap len-1): the
        # 16-token full-block prefix plus 4 of the partial leaf → CoW
        assert monitor.stat_get("prefix_matched_tokens") - m0 >= 16
        assert monitor.stat_get("prefix_cow_copies") > c0
        assert monitor.stat_get("prefix_hit_rate") > 0
        assert monitor.stat_get("prefix_cache_blocks") > 0
        assert 0 <= monitor.stat_get("kv_fragmentation") <= 100
        assert monitor.stat_get("kv_blocks_free") \
            + monitor.stat_get("kv_blocks_used") == 32

    def test_preemption_resume_prefix_replays_identically(self, engine):
        """Pool pressure preempts the youngest prefix-cached stream;
        resume re-admits THROUGH the radix tree and must replay
        token-identically. The sequential seeding generates run without
        pool pressure, so they double as the unpressured reference."""
        prompts = self._shared_prompts(3)
        monitor.stat_reset("serving_preemptions")
        tight = engine(n_slots=3, n_blocks=13, prefix_cache=True)
        ref = [tight.generate(p, max_new_tokens=16) for p in prompts]
        reqs = [tight.submit(p, max_new_tokens=16) for p in prompts]
        assert [r.result(timeout=120) for r in reqs] == ref
        assert monitor.stat_get("serving_preemptions") > 0

    def test_tree_reclaim_before_preemption(self, engine):
        """A full pool whose blocks are only pinned by the TREE is
        reclaimed leaf-by-leaf instead of preempting live work."""
        eng = engine(n_slots=2, n_blocks=17, prefix_cache=True)
        monitor.stat_reset("serving_preemptions")
        e0 = monitor.stat_get("prefix_evictions")
        for i in range(7):                  # distinct prompts fill the tree
            eng.generate(_prompt(24, np.random.default_rng(100 + i)),
                         max_new_tokens=4)
        assert monitor.stat_get("prefix_evictions") > e0
        assert monitor.stat_get("serving_preemptions") == 0

    def test_validation(self, engine):
        from paddle_tpu.models.gpt import gpt_truncate
        with pytest.raises(ValueError, match="draft"):
            engine(prefix_cache=True, n_blocks=33,
                   draft=gpt_truncate(CFG, PARAMS, 1))


# ==========================================================================
# constrained decoding
# ==========================================================================

class TestConstrained:
    def test_regex_dfa_prefix_liveness(self):
        dfa = compile_regex(r"-?(0|[1-9][0-9]*)")
        assert dfa.matches(b"-42") and dfa.matches(b"0")
        assert not dfa.matches(b"01") and not dfa.matches(b"-")
        # prefix-liveness: "-" must be extendable even though it does
        # not match, and "01" must be DEAD (pruned transition)
        s = dfa.trans[dfa.start].get(ord("-"))
        assert s is not None and dfa.trans[s]
        z = dfa.trans[dfa.start][ord("0")]
        assert ord("1") not in dfa.trans[z]

    def test_schema_regex_shapes(self):
        schema = {"type": "object", "properties": {
            "ok": {"type": "boolean"},
            "n": {"type": "integer"},
            "tag": {"enum": ["a", "b"]},
            "xs": {"type": "array", "items": {"type": "integer"},
                   "minItems": 1, "maxItems": 2}}}
        dfa = compile_regex(schema_to_regex(schema))
        assert dfa.matches(b'{"ok":true,"n":-3,"tag":"b","xs":[1,2]}')
        assert not dfa.matches(b'{"ok":true}')
        assert not dfa.matches(b'{"ok":true,"n":3,"tag":"c","xs":[1]}')

    def test_token_masks_and_eos_gating(self):
        tok = ByteTokenizer()
        con = compile_constraint(tokenizer=tok, regex="ab?")
        cur = con.cursor()
        m = cur.mask()
        assert m[ord("a")] and not m[ord("b")] and not m[ord("c")]
        assert not m[tok.eos_id]            # nothing matched yet
        assert cur.advance(ord("a"))
        m = cur.mask()
        assert m[ord("b")] and m[tok.eos_id]     # "a" accepts; "ab" possible
        assert cur.accepting and not cur.finished
        assert cur.advance(ord("b"))
        assert cur.finished                 # no live continuation

    def test_engine_constrained_json_valid_and_stops(self, frontend):
        # rides the module-scoped frontend engine: same submit surface,
        # one set of compiled programs for the whole HTTP/engine class
        eng = frontend.engine
        tok = eng.tokenizer
        # gpt_init's weights leave the tied head echoing its input: a
        # greedy run repeats a digit for ever inside an unbounded integer
        # and ends on `length`, so the greedy case takes a bounded id
        for temp, id_schema in ((0.0, {"enum": [3, 17, 404]}),
                                (0.9, {"type": "integer"})):
            schema = {"type": "object", "properties": {
                "name": {"type": "string", "pattern": "[a-z]{1,6}"},
                "id": id_schema,
                "live": {"type": "boolean"}}}
            con = compile_constraint(tokenizer=tok, json_schema=schema,
                                     vocab_size=eng.cfg.vocab_size)
            req = eng.submit(text=f"json at t={temp}: ",
                             max_new_tokens=96, temperature=temp,
                             constraint=con)
            out = req.text()
            assert req.finish_reason == "stop"
            obj = json.loads(out)
            assert re.fullmatch("[a-z]{1,6}", obj["name"])
            assert isinstance(obj["id"], int)
            assert isinstance(obj["live"], bool)
        assert monitor.stat_get("constrained_requests") >= 2

    def test_constrained_regex_on_a_plain_engine(self, engine):
        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=3)
        con = compile_constraint(tokenizer=tok, regex="(yes|no)",
                                 vocab_size=cfg.vocab_size)
        eng = engine(params=params, cfg=cfg, n_slots=2, tokenizer=tok)
        req = eng.submit(text="answer: ", max_new_tokens=8, constraint=con)
        assert req.text() in ("yes", "no")
        assert req.finish_reason == "stop"


# ==========================================================================
# HTTP front end
# ==========================================================================

@pytest.fixture(scope="module")
def frontend():
    from paddle_tpu.serving.frontend import ServingFrontend, Tenant

    tok = ByteTokenizer()
    cfg = gpt_tiny(dtype=jnp.float32, seq_len=256,
                   vocab_size=tok.vocab_size)
    params = gpt_init(cfg, seed=3)
    eng = InferenceEngine(cfg, params, n_slots=4, block_size=16,
                          prefill_chunk=64, prefix_cache=True,
                          tokenizer=tok)
    fe = ServingFrontend(eng, tenants=[
        Tenant("gold-co", "sk-gold", rate=1000, burst=1000, lane="gold"),
        Tenant("tiny-co", "sk-tiny", rate=0.5, burst=2, lane="bronze",
               max_streams=1),
    ]).start()
    yield fe
    fe.close()
    eng.shutdown(drain=False, timeout=30)


def _call(fe, method, path, body=None, key="sk-gold", timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=timeout)
    try:
        conn.request(method, path,
                     json.dumps(body) if body is not None else None,
                     {"Authorization": f"Bearer {key}"})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


class TestFrontendHttp:
    def test_models_and_auth(self, frontend):
        status, _, data = _call(frontend, "GET", "/v1/models")
        assert status == 200
        assert json.loads(data)["data"][0]["object"] == "model"
        status, _, data = _call(frontend, "POST", "/v1/completions",
                                {"prompt": "x"}, key="wrong")
        assert status == 401
        assert "error" in json.loads(data)
        assert _call(frontend, "GET", "/nope")[0] == 404

    def test_completions_end_to_end(self, frontend):
        status, _, data = _call(frontend, "POST", "/v1/completions",
                                {"prompt": "hello world",
                                 "max_tokens": 8})
        assert status == 200
        obj = json.loads(data)
        assert obj["object"] == "text_completion"
        choice = obj["choices"][0]
        assert choice["finish_reason"] in ("length", "eos", "stop")
        assert obj["usage"]["completion_tokens"] >= 1
        assert obj["usage"]["prompt_tokens"] == 11

    def test_chat_sse_stream(self, frontend):
        conn = http.client.HTTPConnection("127.0.0.1", frontend.port,
                                          timeout=120)
        try:
            conn.request(
                "POST", "/v1/chat/completions",
                json.dumps({"messages": [
                    {"role": "system", "content": "be brief"},
                    {"role": "user", "content": "hi"}],
                    "max_tokens": 6, "stream": True}),
                {"Authorization": "Bearer sk-gold"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith(
                "text/event-stream")
            raw = resp.read().decode("utf-8", errors="replace")
        finally:
            conn.close()
        events = [e for e in raw.strip().split("\n\n") if e]
        assert events[-1] == "data: [DONE]"
        deltas = [json.loads(e[len("data: "):]) for e in events[:-1]]
        assert all(d["object"] == "chat.completion.chunk" for d in deltas)
        assert deltas[-1]["choices"][0]["finish_reason"] is not None
        assert any(d["choices"][0].get("delta", {}).get("content")
                   for d in deltas[:-1])

    def test_constrained_response_validates(self, frontend):
        schema = {"type": "object", "properties": {
            "tag": {"type": "string", "pattern": "[a-z]{1,5}"},
            "on": {"type": "boolean"}}}
        status, _, data = _call(
            frontend, "POST", "/v1/completions",
            {"prompt": "emit json: ", "max_tokens": 80,
             "temperature": 0.8,
             "response_format": {"type": "json_schema",
                                 "json_schema": {"schema": schema}}})
        assert status == 200
        choice = json.loads(data)["choices"][0]
        assert choice["finish_reason"] == "stop"
        obj = json.loads(choice["text"])
        assert re.fullmatch("[a-z]{1,5}", obj["tag"])
        assert isinstance(obj["on"], bool)

    def test_rate_limit_429_isolated_per_tenant(self, frontend):
        codes = [
            _call(frontend, "POST", "/v1/completions",
                  {"prompt": "x", "max_tokens": 2}, key="sk-tiny")[0]
            for _ in range(4)]
        assert codes.count(429) >= 2 and 200 in codes
        status, headers, data = _call(
            frontend, "POST", "/v1/completions", {"prompt": "x"},
            key="sk-tiny")
        assert status == 429
        assert int(headers.get("Retry-After", "0")) >= 1
        assert json.loads(data)["error"]["type"] == "invalid_request_error"
        # the other tenant's lane is untouched by tiny-co's throttling
        status, _, _ = _call(frontend, "POST", "/v1/completions",
                             {"prompt": "still here", "max_tokens": 2})
        assert status == 200
        assert monitor.stat_get("frontend_429s") >= 3

    def test_metrics_dump(self, frontend):
        status, headers, data = _call(frontend, "GET", "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        # Prometheus text exposition (ISSUE 15): samples carry HELP/TYPE
        # metadata lines and histograms ride along — parse accordingly
        samples = [line for line in data.decode().splitlines()
                   if line and not line.startswith("#")]
        names = {line.split()[0] for line in samples}
        for gauge in ("paddle_tpu_frontend_requests",
                      "paddle_tpu_prefix_hit_rate",
                      "paddle_tpu_serving_tokens_per_s",
                      "paddle_tpu_frontend_429s"):
            assert gauge in names
            assert f"# TYPE {gauge} gauge" in data.decode()
        got = {line.split()[0]: float(line.split()[1]) for line in samples}
        assert got["paddle_tpu_frontend_requests"] >= 1
        # the source-recorded histograms are scrapeable series now
        assert got["paddle_tpu_serving_first_token_ms_count"] >= 1
        assert any(n.startswith(
            "paddle_tpu_serving_first_token_ms_bucket") for n in names)

    def test_wfq_prefers_gold_under_contention(self, frontend):
        """Weighted fair queuing: with both lanes loaded, gold's higher
        weight buys a shorter average queue wait than bronze's."""
        writer = monitor.start_tracing()
        try:
            threads = []
            results = []

            def one(key):
                results.append(_call(
                    frontend, "POST", "/v1/completions",
                    {"prompt": "load " * 8, "max_tokens": 4},
                    key=key)[0])

            for _ in range(3):
                for key in ("sk-gold", "sk-gold"):
                    th = threading.Thread(target=one, args=(key,))
                    th.start()
                    threads.append(th)
            for th in threads:
                th.join(timeout=120)
        finally:
            monitor.stop_tracing()
        assert results.count(200) >= 4
        waits = [e for e in writer.events()
                 if e["name"] == "frontend.queue_wait"]
        assert waits and all(
            e["args"]["lane"] == "gold" for e in waits
            if e["args"]["tenant"] == "gold-co")

    def test_frontend_report_verdict(self, frontend):
        writer = monitor.start_tracing()
        try:
            _call(frontend, "POST", "/v1/completions",
                  {"prompt": "report me", "max_tokens": 4})
            for _ in range(4):
                _call(frontend, "POST", "/v1/completions",
                      {"prompt": "x", "max_tokens": 2}, key="sk-tiny")
        finally:
            monitor.stop_tracing()
        tr = _trace_report()
        out = tr.frontend_report(writer.events(),
                                 file=open(os.devnull, "w"))
        tenants = {t["tenant"]: t for t in out["tenants"]}
        assert tenants["gold-co"]["requests"] >= 1
        assert tenants["tiny-co"]["throttled_429"] >= 1
        assert out["throttled_429_total"] >= 1
        assert out["prefix_hit_rate_pct"] >= 0
        assert "verdict" in out
        # and main() wires it in without crashing
        rows = tr.aggregate(writer.events())
        tr.serving_report(rows, file=open(os.devnull, "w"),
                          events=writer.events())


class TestModuleMain:
    def test_python_dash_m_serves_http(self):
        """Acceptance: ``python -m paddle_tpu.serving.frontend`` answers
        a real completion request end to end."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.frontend",
             "--port", "0", "--api-key", "test-key"],
            cwd=_ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        try:
            line = ""
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if "http://" in line:
                    break
                assert proc.poll() is None, f"frontend died: {line}"
            m = re.search(r"http://([\d.]+):(\d+)", line)
            assert m, f"no address line: {line!r}"
            host, port = m.group(1), int(m.group(2))
            conn = http.client.HTTPConnection(host, port, timeout=120)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": "hello", "max_tokens": 4}),
                         {"Authorization": "Bearer test-key"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 200
            assert body["choices"][0]["text"] is not None
            conn.close()
        finally:
            proc.terminate()
            proc.wait(timeout=30)


# ==========================================================================
# graftlint: the shipped front end stays clean; a known-bad radix fixture
# ==========================================================================

class TestLintCoverage:
    def test_unguarded_radix_write_fixture_flags(self):
        """Known-bad fixture (ISSUE 11 satellite): a scheduler thread
        mutating the radix tree while the submit path also writes it,
        with no shared lock — GL003 must see the server's threads."""
        from paddle_tpu.analysis import lint_source

        bad = (
            "import threading\n"
            "class Frontend:\n"
            "    def __init__(self):\n"
            "        self._roots = {}\n"
            "        self._lock = threading.Lock()\n"
            "        t = threading.Thread(target=self._run)\n"
            "        t.start()\n"
            "    def _run(self):\n"
            "        while True:\n"
            "            self._roots['chunk'] = object()\n"
            "    def submit(self):\n"
            "        self._roots.clear()\n")
        findings = [f for f in lint_source(bad) if f.rule == "GL003"]
        assert findings and any("_roots" in f.message for f in findings)
        good = bad.replace(
            "            self._roots['chunk'] = object()\n",
            "            with self._lock:\n"
            "                self._roots['chunk'] = object()\n").replace(
            "        self._roots.clear()\n",
            "        with self._lock:\n"
            "            self._roots.clear()\n")
        assert [f for f in lint_source(good) if f.rule == "GL003"] == []

    def test_new_serving_modules_lint_clean(self):
        from paddle_tpu.analysis import run_lint

        findings = run_lint(
            [os.path.join(_ROOT, "paddle_tpu", "serving"),
             os.path.join(_ROOT, "paddle_tpu", "monitor")], root=_ROOT)
        assert findings == [], \
            "\n".join(f.format() for f in findings)
