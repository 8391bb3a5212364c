"""ISSUE 13 — overload-hardened serving: deadline propagation, the
brownout degradation ladder, the replicated-engine router with
failover, and the serving chaos harness."""
import http.client
import importlib.util
import json
import os
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import gpt_init, gpt_tiny
from paddle_tpu.resilience.faults import FAULTS, configure_faults, parse_spec
from paddle_tpu.serving import (EngineRouter, InferenceEngine,
                                OverloadController)
from paddle_tpu.serving.overload import (RUNG_CAPPED_TOKENS, RUNG_HEALTHY,
                                         RUNG_NO_SPEC, RUNG_SHED_BRONZE,
                                         RUNG_SHED_SILVER,
                                         RUNG_SMALL_CHUNKS)
from paddle_tpu.serving.tokenizer import ByteTokenizer

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt_tiny(dtype=jnp.float32, seq_len=64)
PARAMS = gpt_init(CFG, seed=3)
RNG = np.random.default_rng(13)


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
        try:
            eng.shutdown(drain=False, timeout=30)
        except Exception:  # noqa: BLE001 — crashed engines already stopped
            pass


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    configure_faults("")


# ==========================================================================
# the brownout ladder controller
# ==========================================================================

class TestOverloadController:
    def test_steps_up_only_after_hysteresis(self):
        ctl = OverloadController(tick_budget_ms=100, step_up_after=3)
        ctl.observe_tick(500)
        ctl.observe_tick(500)
        assert ctl.rung == RUNG_HEALTHY          # 2 hot samples < 3
        ctl.observe_tick(500)
        assert ctl.rung == RUNG_NO_SPEC          # 3rd consecutive steps
        assert ctl.rung_name == "no_spec"

    def test_band_holds_and_resets_streaks(self):
        ctl = OverloadController(tick_budget_ms=100, step_up_after=2,
                                 low_water=0.5, alpha=1.0)
        ctl.observe_tick(500)
        ctl.observe_tick(80)     # inside the band: the hot streak resets
        ctl.observe_tick(500)
        assert ctl.rung == RUNG_HEALTHY

    def test_recovery_needs_sustained_cool(self):
        ctl = OverloadController(tick_budget_ms=100, alpha=1.0,
                                 step_up_after=1, step_down_after=3)
        ctl.observe_tick(500)
        assert ctl.rung == RUNG_NO_SPEC
        ctl.observe_tick(10)
        ctl.observe_tick(10)
        assert ctl.rung == RUNG_NO_SPEC          # 2 cool samples < 3
        ctl.observe_tick(10)
        assert ctl.rung == RUNG_HEALTHY

    def test_full_ladder_and_gauges(self):
        ctl = OverloadController(tick_budget_ms=100, alpha=1.0,
                                 step_up_after=1, step_down_after=1)
        for expect in (RUNG_NO_SPEC, RUNG_SMALL_CHUNKS, RUNG_CAPPED_TOKENS,
                       RUNG_SHED_BRONZE, RUNG_SHED_SILVER):
            ctl.observe_tick(1000)
            assert ctl.rung == expect
        ctl.observe_tick(1000)
        assert ctl.rung == RUNG_SHED_SILVER      # top rung saturates
        assert monitor.stat_get("brownout_rung") == RUNG_SHED_SILVER
        for _ in range(5):
            ctl.observe_tick(0)
        assert ctl.rung == RUNG_HEALTHY
        assert monitor.stat_get("brownout_rung") == 0

    def test_knobs_per_rung(self):
        ctl = OverloadController(token_cap=8, chunk_shrink=4)
        assert ctl.spec_allowed()
        assert ctl.prefill_chunk(64) == 64
        assert ctl.cap_max_tokens("bronze", 100) == 100
        assert not ctl.sheds("bronze")
        ctl.force_rung(RUNG_NO_SPEC)
        assert not ctl.spec_allowed()
        assert ctl.prefill_chunk(64) == 64
        ctl.force_rung(RUNG_SMALL_CHUNKS)
        assert ctl.prefill_chunk(64) == 16
        ctl.force_rung(RUNG_CAPPED_TOKENS)
        assert ctl.cap_max_tokens("silver", 100) == 8
        assert ctl.cap_max_tokens("gold", 100) == 100
        assert not ctl.sheds("bronze")
        ctl.force_rung(RUNG_SHED_BRONZE)
        assert ctl.sheds("bronze") and not ctl.sheds("silver")
        ctl.force_rung(RUNG_SHED_SILVER)
        assert ctl.sheds("silver") and ctl.sheds("bronze")
        assert not ctl.sheds("gold")             # gold is never shed
        snap = ctl.snapshot()
        assert snap["rung_name"] == "shed_silver"

    def test_brownout_spans_emitted(self):
        writer = monitor.start_tracing()
        try:
            ctl = OverloadController(tick_budget_ms=100, alpha=1.0,
                                     step_up_after=1)
            ctl.observe_tick(1000)
        finally:
            monitor.stop_tracing()
        steps = [e for e in writer.events()
                 if e["name"] == "serving.brownout_step"]
        assert steps and steps[0]["args"]["rung"] == 1
        assert steps[0]["args"]["from"] == 0
        assert any(e["name"] == "serving.brownout"
                   for e in writer.events())

    def test_validation(self):
        with pytest.raises(ValueError):
            OverloadController(alpha=0.0)
        with pytest.raises(ValueError):
            OverloadController(low_water=1.0, high_water=1.0)
        with pytest.raises(ValueError):
            OverloadController().force_rung(9)


# ==========================================================================
# chaos fault specs
# ==========================================================================

class TestChaosFaultSpecs:
    def test_parse_serving_kinds(self):
        specs = parse_spec("replica_crash@step=30:replica=0,"
                           "slow_tick@step=5:secs=0.2:repeat=3,"
                           "conn_drop@step=2")
        kinds = {f.kind: f for f in specs}
        assert kinds["replica_crash"].replica == 0
        assert kinds["slow_tick"].replica is None
        assert kinds["slow_tick"].secs == 0.2
        assert kinds["conn_drop"].step == 2

    def test_take_tick_replica_filter_and_budget(self):
        configure_faults("replica_crash@step=10:replica=1")
        assert FAULTS.take_tick("replica_crash", 0, 50) is None
        assert FAULTS.take_tick("replica_crash", 1, 9) is None
        assert FAULTS.take_tick("replica_crash", 1, 10) is not None
        assert FAULTS.take_tick("replica_crash", 1, 11) is None  # spent

    def test_take_conn_index_space(self):
        configure_faults("conn_drop@step=3")
        assert FAULTS.take_conn(1) is None
        assert FAULTS.take_conn(2) is None
        assert FAULTS.take_conn(3) is not None
        assert FAULTS.take_conn(4) is None       # budget of one


# ==========================================================================
# deadline propagation in the engine
# ==========================================================================

class TestEngineDeadlineShed:
    def test_expired_in_queue_sheds_before_prefill(self, engine):
        """A queued request whose deadline passes is shed WITHOUT any
        prefill work: no serving.prefill_chunk span carries its tokens,
        and serving_deadline_sheds counts it."""
        eng = engine(n_slots=1, queue_size=8)
        shed0 = monitor.stat_get("serving_deadline_sheds")
        blocker = eng.submit(_prompt(8), max_new_tokens=48)
        doomed = eng.submit(_prompt(8), max_new_tokens=8, deadline_s=0.05)
        writer = monitor.start_tracing()
        try:
            assert doomed.result(timeout=60) == []
        finally:
            monitor.stop_tracing()
        assert doomed.finish_reason == "deadline"
        assert monitor.stat_get("serving_deadline_sheds") == shed0 + 1
        # the shed burned zero prefill: every chunk span belongs to the
        # slot the blocker holds (slot 0 of a 1-slot engine)
        chunks = [e for e in writer.events()
                  if e["name"] == "serving.prefill_chunk"]
        assert all(e["args"]["slot"] == 0 for e in chunks)
        blocker.result(timeout=120)

    def test_shed_mid_queue_not_just_head(self, engine):
        """The sweep sheds expired work anywhere in line, so a live
        request BEHIND a dead one is not blocked by it."""
        eng = engine(n_slots=1, queue_size=8)
        blocker = eng.submit(_prompt(8), max_new_tokens=32)
        doomed = eng.submit(_prompt(8), max_new_tokens=8, deadline_s=0.02)
        live = eng.submit(_prompt(8), max_new_tokens=4)
        assert live.result(timeout=120) != []
        assert doomed.finish_reason == "deadline"
        assert doomed.tokens == []
        blocker.result(timeout=120)

    def test_overload_none_pins_identical_tokens(self, engine):
        """The ladder fully off (overload=None) and a rung-0 controller
        produce identical greedy streams — attaching the controller
        changes nothing until pressure steps it."""
        p = _prompt(12)
        plain = engine(seed=0).generate(p, max_new_tokens=12)
        ctl = OverloadController(queue_wait_budget_ms=1e9,
                                 tick_budget_ms=1e9)
        guarded = engine(seed=0, overload=ctl)
        assert guarded.generate(p, max_new_tokens=12) == plain
        assert ctl.rung == RUNG_HEALTHY

    def test_rung2_shrinks_prefill_chunks(self, engine):
        ctl = OverloadController()
        ctl.force_rung(RUNG_SMALL_CHUNKS)
        eng = engine(overload=ctl, prefill_chunk=32, block_size=8)
        writer = monitor.start_tracing()
        try:
            eng.generate(_prompt(32), max_new_tokens=2)
        finally:
            monitor.stop_tracing()
        chunks = [e for e in writer.events()
                  if e["name"] == "serving.prefill_chunk"]
        # 32-token chunks shrink to 8 (32 // chunk_shrink=4, block-
        # rounded): the prompt takes several small chunks, never one big
        assert chunks and all(e["args"]["chunk"] <= 8 for e in chunks)

    def test_queue_wait_feeds_controller(self, engine):
        ctl = OverloadController(queue_wait_budget_ms=1.0, alpha=1.0,
                                 step_up_after=1, tick_budget_ms=1e9)
        eng = engine(n_slots=1, overload=ctl)
        blocker = eng.submit(_prompt(8), max_new_tokens=32)
        waiter = eng.submit(_prompt(8), max_new_tokens=2)
        waiter.result(timeout=120)
        blocker.result(timeout=120)
        # the waiter sat behind the blocker >> 1ms: pressure stepped it
        assert ctl.rung >= RUNG_NO_SPEC


# ==========================================================================
# the replicated-engine router
# ==========================================================================

class TestEngineRouter:
    def _mk(self, engine, n=2, **kw):
        kw.setdefault("seed", 0)
        return EngineRouter([engine(**kw) for _ in range(n)])

    def test_single_replica_passthrough_identity(self, engine):
        p = _prompt(12)
        ref = engine(seed=0).generate(p, max_new_tokens=10)
        router = self._mk(engine, n=1)
        assert router.generate(p, max_new_tokens=10) == ref

    def test_least_loaded_spread(self, engine):
        router = self._mk(engine, n=2, n_slots=2)
        reqs = [router.submit(_prompt(8), max_new_tokens=8)
                for _ in range(4)]
        for r in reqs:
            r.result(timeout=120)
        # both replicas served work (ticks advanced on each)
        assert all(e._ticks > 0 for e in router.engines)

    def test_prefix_affinity_routes_to_matching_replica(self, engine):
        router = self._mk(engine, n=2, prefix_cache=True, n_slots=2,
                          n_blocks=33)
        head = _prompt(24)
        tails = [np.concatenate([head, _prompt(8)]) for _ in range(3)]
        first = router.submit(tails[0], max_new_tokens=2)
        first.result(timeout=120)
        # the shared head is now affine to that replica: every later
        # prompt sharing it routes there, idle neighbors notwithstanding
        for t in tails[1:]:
            assert router.place(t) == first._replica
            req = router.submit(t, max_new_tokens=2)
            req.result(timeout=120)
            assert req._replica == first._replica

    def test_failover_greedy_token_identity(self, engine):
        prompts = [_prompt(9) for _ in range(4)]
        ref_eng = engine(seed=0, n_slots=4)
        expected = [ref_eng.generate(p, max_new_tokens=12) for p in prompts]
        fo0 = monitor.stat_get("router_failovers")
        configure_faults("replica_crash@step=4:replica=0")
        router = self._mk(engine, n=2, n_slots=2)
        reqs = [router.submit(p, max_new_tokens=12) for p in prompts]
        outs = [r.result(timeout=120) for r in reqs]
        assert outs == expected
        assert all(r.finish_reason == "length" for r in reqs)
        assert monitor.stat_get("router_failovers") > fo0
        assert router.healthy_replicas() == [1]
        assert router.health()[0]["failed_over"]

    @pytest.mark.slow  # same failover machinery as the greedy leg above
    def test_failover_sampled_token_identity(self, engine):
        """Sampled streams survive failover bit-exactly too: the rid
        rides along and replicas share the seed, so the per-request RNG
        stream continues unbroken on the survivor."""
        prompts = [_prompt(9) for _ in range(4)]
        ref_eng = engine(seed=0, n_slots=4)
        expected = [ref_eng.generate(p, max_new_tokens=12, temperature=0.9,
                                     top_k=7) for p in prompts]
        configure_faults("replica_crash@step=4:replica=0")
        router = self._mk(engine, n=2, n_slots=2)
        outs = [router.submit(p, max_new_tokens=12, temperature=0.9,
                              top_k=7).result(timeout=120)
                for p in prompts]
        assert outs == expected

    def test_all_replicas_dead_fails_loudly(self, engine):
        configure_faults("replica_crash@step=2:replica=0,"
                         "replica_crash@step=2:replica=1")
        router = self._mk(engine, n=2, n_slots=2)
        reqs = [router.submit(_prompt(8), max_new_tokens=16)
                for _ in range(2)]
        failed = 0
        for r in reqs:
            try:
                r.result(timeout=120)
            except RuntimeError:
                failed += 1
        assert failed >= 1                      # never a silent hang
        assert router.healthy_replicas() == []
        with pytest.raises(RuntimeError, match="no healthy replica"):
            router.submit(_prompt(4), max_new_tokens=2)

    def test_replica_down_span_and_gauge(self, engine):
        configure_faults("replica_crash@step=3:replica=0")
        writer = monitor.start_tracing()
        try:
            router = self._mk(engine, n=2, n_slots=2)
            reqs = [router.submit(_prompt(8), max_new_tokens=10)
                    for _ in range(3)]
            for r in reqs:
                r.result(timeout=120)
        finally:
            monitor.stop_tracing()
        downs = [e for e in writer.events()
                 if e["name"] == "router.replica_down"]
        assert len(downs) == 1 and downs[0]["args"]["replica"] == 0
        decs = [e for e in writer.events()
                if e["name"] == "serving.decode_step"]
        assert {e["args"].get("replica") for e in decs} <= {0, 1}
        assert monitor.stat_get("serving_replicas_healthy") == 1

    def test_validation(self, engine):
        with pytest.raises(ValueError, match="at least one"):
            EngineRouter([])
        tok = ByteTokenizer()
        cfg2 = gpt_tiny(dtype=jnp.float32, seq_len=64,
                        vocab_size=tok.vocab_size)
        with pytest.raises(ValueError, match="diverge"):
            EngineRouter([engine(), engine(cfg=cfg2,
                                           params=gpt_init(cfg2, seed=3))])


# ==========================================================================
# the HTTP front end: 429-vs-503, deadlines, probes, disconnects
# ==========================================================================

def _frontend(engine_or_router, tenants=None):
    from paddle_tpu.serving.frontend import ServingFrontend, Tenant

    tenants = tenants or [
        Tenant("gold-co", "sk-gold", rate=1000, burst=1000, lane="gold"),
        Tenant("silver-co", "sk-silver", rate=1000, burst=1000,
               lane="silver"),
        Tenant("bronze-co", "sk-bronze", rate=1000, burst=1000,
               lane="bronze"),
    ]
    return ServingFrontend(engine_or_router, tenants=tenants).start()


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


def _text_engine(engine, **kw):
    tok = ByteTokenizer()
    cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                   vocab_size=tok.vocab_size)
    params = gpt_init(cfg, seed=3)
    kw.setdefault("tokenizer", tok)
    return engine(params=params, cfg=cfg, **kw)


class TestFrontendOverload:
    def test_healthz_and_readyz_ok(self, engine):
        fe = _frontend(_text_engine(engine))
        try:
            status, _, data = _call(fe, "GET", "/healthz")
            assert status == 200 and json.loads(data)["status"] == "ok"
            status, _, data = _call(fe, "GET", "/readyz")
            obj = json.loads(data)
            assert status == 200 and obj["status"] == "ok"
            assert obj["checks"]["engine_alive"]
            assert obj["checks"]["pool_headroom"] > 0
        finally:
            fe.close()

    def test_readyz_503_on_shed_rung_and_dead_engine(self, engine):
        ctl = OverloadController()
        eng = _text_engine(engine, overload=ctl)
        fe = _frontend(eng)
        try:
            ctl.force_rung(RUNG_SHED_BRONZE)
            status, headers, data = _call(fe, "GET", "/readyz")
            assert status == 503
            obj = json.loads(data)
            assert obj["status"] == "unready"
            assert obj["checks"]["brownout"]["rung_name"] == "shed_bronze"
            assert headers.get("Retry-After")
            ctl.force_rung(RUNG_HEALTHY)
            assert _call(fe, "GET", "/readyz")[0] == 200
            eng.shutdown(drain=False, timeout=30)
            assert _call(fe, "GET", "/readyz")[0] == 503
            assert _call(fe, "GET", "/healthz")[0] == 200  # loop lives
        finally:
            fe.close()

    def test_brownout_shed_503_per_lane_vs_429(self, engine):
        """The status contract: brownout sheds are 503 (server-side,
        Retry-After, frontend_load_sheds), tenant-budget rejections stay
        429 — and gold is never shed."""
        from paddle_tpu.serving.frontend import Tenant

        ctl = OverloadController()
        eng = _text_engine(engine, overload=ctl)
        fe = _frontend(eng, tenants=[
            Tenant("gold-co", "sk-gold", rate=1000, burst=1000,
                   lane="gold"),
            Tenant("bronze-co", "sk-bronze", rate=1000, burst=1000,
                   lane="bronze"),
            Tenant("tiny-co", "sk-tiny", rate=0.01, burst=1,
                   lane="gold"),
        ])
        try:
            ctl.force_rung(RUNG_SHED_BRONZE)
            shed0 = monitor.stat_get("frontend_load_sheds")
            status, headers, data = _call(
                fe, "POST", "/v1/completions",
                {"prompt": "hi", "max_tokens": 2}, key="sk-bronze")
            assert status == 503
            assert int(headers.get("Retry-After", "0")) >= 1
            assert json.loads(data)["error"]["type"] == "server_error"
            assert monitor.stat_get("frontend_load_sheds") == shed0 + 1
            # gold sails through the same rung
            assert _call(fe, "POST", "/v1/completions",
                         {"prompt": "hi", "max_tokens": 2})[0] == 200
            # tenant-budget violations remain 429 even during brownout
            _call(fe, "POST", "/v1/completions",
                  {"prompt": "x", "max_tokens": 2}, key="sk-tiny")
            status, _, _ = _call(fe, "POST", "/v1/completions",
                                 {"prompt": "x", "max_tokens": 2},
                                 key="sk-tiny")
            assert status == 429
        finally:
            fe.close()

    def test_rung3_caps_non_gold_max_tokens(self, engine):
        ctl = OverloadController(token_cap=3)
        eng = _text_engine(engine, overload=ctl)
        fe = _frontend(eng)
        try:
            ctl.force_rung(RUNG_CAPPED_TOKENS)
            status, _, data = _call(
                fe, "POST", "/v1/completions",
                {"prompt": "hello", "max_tokens": 40}, key="sk-silver")
            assert status == 200
            obj = json.loads(data)
            assert obj["usage"]["completion_tokens"] <= 3
            status, _, data = _call(
                fe, "POST", "/v1/completions",
                {"prompt": "hello", "max_tokens": 40}, key="sk-gold")
            assert json.loads(data)["usage"]["completion_tokens"] > 3
        finally:
            fe.close()

    def test_deadline_expired_in_queue_is_503_retry_after(self, engine):
        """deadline_s propagates into the engine queue: a request that
        expires there (behind a slot hog) answers 503 + Retry-After with
        the shed gauges bumped — not an empty 200, not a hang."""
        eng = _text_engine(engine, n_slots=1)
        fe = _frontend(eng)
        try:
            hog = eng.submit(_prompt(8, np.random.default_rng(5)) %
                             eng.cfg.vocab_size, max_new_tokens=64)
            shed0 = monitor.stat_get("frontend_load_sheds")
            status, headers, data = _call(
                fe, "POST", "/v1/completions",
                {"prompt": "too late", "max_tokens": 8,
                 "deadline_s": 0.05})
            assert status == 503
            assert int(headers.get("Retry-After", "0")) >= 1
            assert monitor.stat_get("frontend_load_sheds") == shed0 + 1
            hog.result(timeout=120)
        finally:
            fe.close()

    def test_deadline_partial_returns_200_with_reason(self, engine):
        """A request that got tokens out before its deadline returns
        them with a clean deadline/timeout finish_reason (the old path
        hung on a hardcoded 600s wait)."""
        eng = _text_engine(engine)
        fe = _frontend(eng)
        try:
            eng.generate(eng.tokenizer.encode("warm"), max_new_tokens=2)
            status, _, data = _call(
                fe, "POST", "/v1/completions",
                {"prompt": "go", "max_tokens": 4000, "deadline_s": 0.4})
            assert status == 200
            choice = json.loads(data)["choices"][0]
            assert choice["finish_reason"] in ("deadline", "timeout")
            assert json.loads(data)["usage"]["completion_tokens"] >= 1
        finally:
            fe.close()

    def test_engine_queue_full_is_503(self, engine):
        eng = _text_engine(engine, n_slots=1, queue_size=1)
        fe = _frontend(eng)
        try:
            hogs = [eng.submit(np.asarray([7, 8, 9], np.int32),
                               max_new_tokens=64) for _ in range(2)]
            codes = []
            threads = []

            def one():
                codes.append(_call(
                    fe, "POST", "/v1/completions",
                    {"prompt": "x", "max_tokens": 2,
                     "deadline_s": 0.2})[0])

            for _ in range(3):
                th = threading.Thread(target=one)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=120)
            # every rejection is a 503 (server overload), never silent
            assert codes and set(codes) <= {200, 503}
            for h in hogs:
                h.result(timeout=120)
        finally:
            fe.close()


class TestClientDisconnect:
    def _raw_stream(self, fe, body):
        s = socket.create_connection(("127.0.0.1", fe.port), timeout=60)
        payload = json.dumps(body).encode()
        s.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                  b"Authorization: Bearer sk-gold\r\n"
                  b"Content-Length: " + str(len(payload)).encode()
                  + b"\r\n\r\n" + payload)
        return s

    def test_disconnect_cancels_and_returns_blocks(self, engine):
        """The ISSUE-13 leak fix: an SSE client that vanishes
        mid-generation must CANCEL its engine request — slot freed,
        paged blocks returned, nothing decoding to nobody."""
        eng = _text_engine(engine, n_slots=2, n_blocks=17)
        free0 = eng.cache.free_blocks_count
        fe = _frontend(eng)
        try:
            s = self._raw_stream(fe, {"prompt": "stream me",
                                      "max_tokens": 4000, "stream": True})
            # read until the first SSE data chunk proves decoding started
            buf = b""
            while b"data:" not in buf:
                buf += s.recv(4096)
            s.close()                    # the client vanishes
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and \
                    (eng.occupancy or eng.cache.free_blocks_count != free0):
                time.sleep(0.05)
            assert eng.occupancy == 0
            # pool fully returned (no prefix cache on this engine: every
            # block the stream held must be back on the free list)
            assert eng.cache.free_blocks_count == free0
        finally:
            fe.close()

    def test_disconnect_with_prefix_cache_releases_refs(self, engine):
        """With the radix tree on, the dead stream's blocks are either
        free or tree-owned (refcount 1, reclaimable) — never pinned by
        the vanished slot."""
        eng = _text_engine(engine, n_slots=2, n_blocks=33,
                           prefix_cache=True)
        fe = _frontend(eng)
        try:
            s = self._raw_stream(fe, {"prompt": "cache me please",
                                      "max_tokens": 4000, "stream": True})
            buf = b""
            while b"data:" not in buf:
                buf += s.recv(4096)
            s.close()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and eng.occupancy:
                time.sleep(0.05)
            assert eng.occupancy == 0
            pool = eng.cache
            free = pool.free_blocks_count
            tree = eng._prefix.block_count
            assert free + tree == pool.n_blocks - pool.shards
        finally:
            fe.close()

    @pytest.mark.chaos
    def test_conn_drop_fault_exercises_the_path(self, engine):
        """conn_drop@step=1: the front end aborts the FIRST streaming
        connection after a piece — the deterministic client-vanish."""
        eng = _text_engine(engine, n_slots=2, n_blocks=17)
        free0 = eng.cache.free_blocks_count
        fe = _frontend(eng)
        try:
            configure_faults("conn_drop@step=1")
            s = self._raw_stream(fe, {"prompt": "doomed stream",
                                      "max_tokens": 4000, "stream": True})
            # server aborts mid-stream: recv eventually returns b'' or
            # resets — both prove the injected drop
            try:
                while s.recv(4096):
                    pass
            except ConnectionError:
                pass
            s.close()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and \
                    (eng.occupancy or eng.cache.free_blocks_count != free0):
                time.sleep(0.05)
            assert eng.occupancy == 0
            assert eng.cache.free_blocks_count == free0
        finally:
            fe.close()


# ==========================================================================
# chaos harness: router + faults + ladder end to end, plus the report
# ==========================================================================

class TestChaosHarness:
    @pytest.mark.chaos
    def test_crash_under_load_healthy_streams_exact(self, engine):
        """The bench gate in miniature: replica crash + slow ticks under
        Poisson-ish load — completed streams token-identical to the
        fault-free oracle, sheds explicit, nothing silent."""
        prompts = [_prompt(10) for _ in range(6)]
        ref = engine(seed=0, n_slots=4)
        expected = [ref.generate(p, max_new_tokens=10) for p in prompts]
        configure_faults("replica_crash@step=6:replica=0,"
                         "slow_tick@step=3:secs=0.05:repeat=2:replica=1")
        ctl = OverloadController(queue_wait_budget_ms=50.0,
                                 tick_budget_ms=40.0, step_up_after=2,
                                 step_down_after=6)
        router = EngineRouter([engine(seed=0, n_slots=2, overload=ctl),
                               engine(seed=0, n_slots=2, overload=ctl)])
        reqs = [router.submit(p, max_new_tokens=10) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        assert outs == expected
        assert all(r.finish_reason is not None for r in reqs)
        assert router.healthy_replicas() == [1]

    def test_overload_report_rungs_replicas_and_sheds(self, engine):
        tr = _trace_report()
        writer = monitor.start_tracing()
        try:
            ctl = OverloadController(tick_budget_ms=100, alpha=1.0,
                                     step_up_after=1, step_down_after=1)
            ctl.observe_tick(1000)
            ctl.observe_tick(1000)
            ctl.observe_tick(0)
            configure_faults("replica_crash@step=3:replica=0")
            router = EngineRouter([engine(seed=0, n_slots=2),
                                   engine(seed=0, n_slots=2)])
            reqs = [router.submit(_prompt(8), max_new_tokens=8)
                    for _ in range(3)]
            for r in reqs:
                r.result(timeout=120)
        finally:
            monitor.stop_tracing()
        out = tr.overload_report(writer.events(),
                                 file=open(os.devnull, "w"))
        assert out["max_rung"] == 2
        assert out["final_rung"] == 1
        assert len(out["rung_timeline"]) == 3
        assert out["replica_deaths"] == 1
        assert out["replicas"]["0"]["died"]
        assert not out["replicas"]["1"]["died"]
        assert out["replicas"]["1"]["ticks"] > 0
        assert "verdict" in out
        # and main() wiring survives an event list with no overload rows
        assert tr.overload_report([], file=open(os.devnull, "w")) == {}

    def test_trace_report_main_includes_overload(self, tmp_path, engine):
        tr = _trace_report()
        writer = monitor.start_tracing()
        try:
            ctl = OverloadController(tick_budget_ms=100, alpha=1.0,
                                     step_up_after=1)
            ctl.observe_tick(500)
        finally:
            monitor.stop_tracing()
        path = writer.write(str(tmp_path / "trace.json"))
        rows = tr.main([path])
        assert rows is not None
