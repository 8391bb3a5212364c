"""ISSUE 14 — elastic replica lifecycle: restart/rejoin with prefix
re-warm, brownout-driven autoscaling, the backoff/quarantine ladder,
and the spec-aware watchdog (watchdog= x draft= composition)."""
import http.client
import importlib.util
import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — jax/mesh bootstrap
from paddle_tpu import monitor
from paddle_tpu.models import gpt_init, gpt_tiny, gpt_truncate
from paddle_tpu.resilience.faults import (FAULTS, configure_faults,
                                          parse_spec)
from paddle_tpu.serving import (EngineRouter, InferenceEngine,
                                OverloadController, ReplicaSupervisor)
from paddle_tpu.serving.lifecycle import ReplicaFailed
from paddle_tpu.serving.overload import (RUNG_HEALTHY, RUNG_NO_SPEC,
                                         RUNG_SMALL_CHUNKS)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt_tiny(dtype=jnp.float32, seq_len=128)
PARAMS = gpt_init(CFG, seed=3)
DRAFT = gpt_truncate(CFG, PARAMS, 2)
RNG = np.random.default_rng(14)


def _prompt(n, rng=RNG):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _wait(pred, timeout=60.0, poll=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


@pytest.fixture
def engine():
    engines = []

    def make(params=PARAMS, cfg=CFG, **kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("block_size", 8)
        kw.setdefault("prefill_chunk", 16)
        kw.setdefault("seed", 0)
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


def _supervised(engine, n=1, factory_kw=None, **sup_kw):
    """Router of n replicas + a fast-polling supervisor over the SAME
    factory (the identical-build contract)."""
    factory_kw = dict(factory_kw or {})

    def factory():
        return engine(**factory_kw)

    router = EngineRouter([factory() for _ in range(n)])
    sup_kw.setdefault("poll_s", 0.02)
    sup_kw.setdefault("backoff_s", 0.02)
    sup_kw.setdefault("backoff_cap_s", 0.1)
    sup_kw.setdefault("quarantine_s", 0.1)
    sup_kw.setdefault("stable_s", 0.3)
    sup = ReplicaSupervisor(router, factory, **sup_kw)
    return router, sup


# ==========================================================================
# lifecycle fault specs
# ==========================================================================

class TestLifecycleFaultSpecs:
    def test_parse_restart_kinds(self):
        specs = parse_spec("spawn_fail@restart=2:times=3,"
                           "replica_flap@restart=1")
        kinds = {f.kind: f for f in specs}
        assert kinds["spawn_fail"].restart == 2
        assert kinds["spawn_fail"].repeat == 3
        assert kinds["replica_flap"].restart == 1
        assert kinds["replica_flap"].repeat == 1

    def test_restart_trigger_validation(self):
        with pytest.raises(ValueError, match="exactly one trigger"):
            parse_spec("spawn_fail@restart=1:step=2")
        with pytest.raises(ValueError, match="restart="):
            parse_spec("crash@restart=1")           # non-lifecycle kind
        with pytest.raises(ValueError, match="restart=N"):
            parse_spec("spawn_fail@step=1")         # wrong trigger key

    def test_take_restart_own_index_space(self):
        """A restart-keyed budget is invisible to step/tick/conn hooks —
        training fault replay and serving tick faults stay clean."""
        configure_faults("spawn_fail@restart=2:times=2")
        assert FAULTS.take("crash", 5) is None
        assert FAULTS.take_tick("replica_crash", 0, 5) is None
        assert FAULTS.take_conn(5) is None
        assert FAULTS.take_restart("spawn_fail", 1) is None
        assert FAULTS.take_restart("spawn_fail", 2) is not None
        assert FAULTS.take_restart("spawn_fail", 3) is not None
        assert FAULTS.take_restart("spawn_fail", 4) is None   # spent
        assert FAULTS.take_restart("replica_flap", 9) is None


# ==========================================================================
# the dynamic replica set (router surface)
# ==========================================================================

class TestDynamicReplicaSet:
    def test_add_remove_and_gauge(self, engine):
        router = EngineRouter([engine()])
        assert router.healthy_replicas() == [0]
        rid = router.add_replica(engine())
        assert rid == 1
        assert sorted(router.healthy_replicas()) == [0, 1]
        assert monitor.stat_get("serving_replicas_healthy") == 2
        gone = router.remove_replica(1)
        assert gone is not None
        assert router.healthy_replicas() == [0]
        with pytest.raises(ValueError, match="already live"):
            router.add_replica(engine(), replica_id=0)

    def test_warming_replica_not_routable(self, engine):
        router = EngineRouter([engine()])
        rid = router.add_replica(engine(), warming=True)
        assert rid not in router.healthy_replicas()
        assert router.health()[rid]["warming"]
        assert not router.health()[rid]["routable"]
        router.mark_ready(rid)
        assert rid in router.healthy_replicas()
        assert not router.health()[rid]["warming"]

    def test_draining_replica_places_nothing(self, engine):
        router = EngineRouter([engine(), engine()])
        router.begin_drain(1)
        assert router.healthy_replicas() == [0]
        assert router.health()[1]["draining"]
        for _ in range(3):
            assert router.place(_prompt(8)) == 0

    def test_reused_id_stale_incarnation_cannot_unroute(self, engine):
        """The failover hook is keyed by (id, engine): after a
        replacement reuses id 0, the OLD engine's late death must not
        mark the new one dead."""
        old = engine()
        router = EngineRouter([old])
        hook = old.failover
        router.remove_replica(0)
        router.add_replica(engine(), replica_id=0)
        # simulate the stale incarnation failing a request now
        req = router.submit(_prompt(8), max_new_tokens=2)
        req.result(timeout=120)
        assert hook(req, RuntimeError("stale death")) in (True, False)
        assert router.healthy_replicas() == [0]     # successor unharmed

    def test_hot_prefixes_maximal_and_stashed(self, engine):
        router = EngineRouter([engine(prefix_cache=True, n_blocks=65)])
        head = _prompt(32)
        long = np.concatenate([head, _prompt(16)])
        router.submit(long, max_new_tokens=2).result(timeout=120)
        hot = router.hot_prefixes(4)
        # one maximal entry: the longest block-aligned routed prefix
        assert len(hot) == 1 and hot[0].size == 48
        assert np.array_equal(hot[0][:32], head)
        # a death stashes them for the replacement's re-warm
        router.remove_replica(0)
        hot2 = router.hot_prefixes(4)
        assert len(hot2) == 1 and np.array_equal(hot2[0], hot[0])


# ==========================================================================
# restart / rejoin
# ==========================================================================

class TestRestartRejoin:
    def test_greedy_identity(self, engine):
        prompts = [_prompt(9) for _ in range(3)]
        ref = engine(n_slots=4)
        expected = [ref.generate(p, max_new_tokens=12) for p in prompts]
        rs0 = monitor.stat_get("serving_replica_restarts")
        configure_faults("replica_crash@step=4:replica=0")
        router, sup = _supervised(engine, n=1)
        reqs = [router.submit(p, max_new_tokens=12) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        assert outs == expected
        assert all(r.finish_reason == "length" for r in reqs)
        assert monitor.stat_get("serving_replica_restarts") == rs0 + 1
        assert _wait(lambda: sup.snapshot()["rejoins"] == 1)
        assert sup.snapshot()["replicas"]["0"]["state"] == "live"
        configure_faults("")
        router.shutdown(drain=True, timeout=60)

    def test_sampled_identity_and_rid_space(self, engine):
        """Sampled streams survive a full-fleet death bit-exactly (rid +
        seed ride into the replacement), and a request submitted AFTER
        the rejoin continues the rid numbering — its stream matches the
        fault-free run's."""
        prompts = [_prompt(9) for _ in range(4)]
        ref = engine(n_slots=4)
        expected = [ref.generate(p, max_new_tokens=10, temperature=0.9,
                                 top_k=7) for p in prompts]
        configure_faults("replica_crash@step=4:replica=0")
        router, sup = _supervised(engine, n=1)
        reqs = [router.submit(p, max_new_tokens=10, temperature=0.9,
                              top_k=7) for p in prompts[:3]]
        outs = [r.result(timeout=180) for r in reqs]
        assert outs == expected[:3]
        assert _wait(lambda: sup.snapshot()["rejoins"] == 1)
        # rid space carried past the dead engine's: the 4th request gets
        # rid 3, exactly as on the fault-free engine
        late = router.submit(prompts[3], max_new_tokens=10,
                             temperature=0.9, top_k=7)
        assert late.result(timeout=120) == expected[3]
        assert late.rid == 3
        configure_faults("")
        router.shutdown(drain=True, timeout=60)

    def test_rejoin_rewarms_prefix_tree(self, engine):
        """The rejoined replica's radix tree holds the hottest routed
        prefix again (re-warm replay), so its tail-only prefill does
        strictly less chunk work than a cold engine — the warm
        first-token contract."""
        head = _prompt(48)
        tails = [np.concatenate([head, _prompt(6)]) for _ in range(3)]
        kw = {"prefix_cache": True, "n_blocks": 65}
        warm0 = monitor.stat_get("prefix_warm_tokens")
        configure_faults("replica_crash@step=60:replica=0")
        router, sup = _supervised(engine, n=1, factory_kw=kw)
        for t in tails[:2]:
            router.submit(t, max_new_tokens=2).result(timeout=120)
        # burn ticks past the crash point, then wait out the rejoin
        doomed = router.submit(tails[2], max_new_tokens=80)
        doomed.result(timeout=180)
        assert _wait(lambda: sup.snapshot()["rejoins"] == 1
                     and sup.snapshot()["replicas"]["0"]["state"] == "live")
        warmed = monitor.stat_get("prefix_warm_tokens") - warm0
        assert warmed >= 48
        eng = router.engine_for(0)
        assert eng._prefix.peek(0, head) == 48   # tree is warm again
        # warm vs cold prefill work for the same prompt: the rejoined
        # replica only chunk-prefills the uncached tail
        writer = monitor.start_tracing()
        try:
            fresh = np.concatenate([head, _prompt(6)])
            router.submit(fresh, max_new_tokens=2).result(timeout=120)
        finally:
            monitor.stop_tracing()
        warm_work = sum(e["args"]["chunk"] for e in writer.events()
                        if e["name"] == "serving.prefill_chunk")
        cold = engine(**kw)
        writer2 = monitor.start_tracing()
        try:
            cold.generate(fresh, max_new_tokens=2)
        finally:
            monitor.stop_tracing()
        cold_work = sum(e["args"]["chunk"] for e in writer2.events()
                        if e["name"] == "serving.prefill_chunk")
        assert warm_work < cold_work
        configure_faults("")
        router.shutdown(drain=True, timeout=60)

    def test_supervisor_off_pins_pr13_behavior(self, engine):
        """No supervisor: a full-fleet death fails the stream loudly
        (no parking, no respawn) — bit-identical PR-13 semantics."""
        configure_faults("replica_crash@step=3:replica=0")
        router = EngineRouter([engine()])
        req = router.submit(_prompt(8), max_new_tokens=16)
        with pytest.raises(RuntimeError):
            req.result(timeout=120)
        assert router.healthy_replicas() == []
        assert router.supervisor is None

    def test_supervisor_attached_identical_tokens_no_faults(self, engine):
        p = _prompt(12)
        plain = EngineRouter([engine()])
        expected = plain.generate(p, max_new_tokens=12)
        router, sup = _supervised(engine, n=1)
        assert router.generate(p, max_new_tokens=12) == expected
        assert sup.snapshot()["spawns"] == 0        # healer never woke
        router.shutdown(drain=True, timeout=60)

    def test_supervisor_validation(self, engine):
        router = EngineRouter([engine()])
        with pytest.raises(ValueError, match="min_replicas"):
            ReplicaSupervisor(router, engine, min_replicas=0)
        with pytest.raises(ValueError, match="quarantine_after"):
            ReplicaSupervisor(router, engine, quarantine_after=9,
                              max_restarts=3)
        sup = ReplicaSupervisor(router, engine, poll_s=0.02)
        with pytest.raises(ValueError, match="already has a supervisor"):
            ReplicaSupervisor(router, engine)
        sup.close()


# ==========================================================================
# the backoff / quarantine ladder
# ==========================================================================

class TestRestartLadder:
    def test_quarantine_then_give_up_loudly(self, engine):
        """spawn_fail on every respawn: immediate -> backoff ->
        quarantined -> failed, with the orphaned stream erroring with
        ReplicaFailed (never a silent hang)."""
        writer = monitor.start_tracing()
        configure_faults("replica_crash@step=3:replica=0,"
                         "spawn_fail@restart=1:times=10")
        try:
            router, sup = _supervised(engine, n=1, max_restarts=3,
                                      quarantine_after=2)
            req = router.submit(_prompt(8), max_new_tokens=16)
            with pytest.raises(RuntimeError) as ei:
                req.result(timeout=120)
            assert isinstance(ei.value.__cause__, ReplicaFailed)
            assert _wait(lambda: sup.snapshot()["replicas"]["0"]["state"]
                         == "failed")
            assert sup.snapshot()["spawns"] == 3
        finally:
            monitor.stop_tracing()
            configure_faults("")
        names = [e["name"] for e in writer.events()]
        assert names.count("lifecycle.restart") == 3
        assert "lifecycle.quarantine" in names
        assert "lifecycle.give_up" in names
        router.shutdown(drain=False, timeout=30)

    def test_flapping_replica_climbs_the_ladder(self, engine):
        """replica_flap: the first two rejoins crash at their next busy
        tick, the third sticks — streams still finish token-identically
        (every crash replays through adoption/orphans)."""
        p = _prompt(9)
        ref = engine(n_slots=4)
        expected = ref.generate(p, max_new_tokens=24)
        configure_faults("replica_crash@step=4:replica=0,"
                         "replica_flap@restart=1:times=2")
        router, sup = _supervised(engine, n=1, max_restarts=5)
        req = router.submit(p, max_new_tokens=24)
        assert req.result(timeout=240) == expected
        assert _wait(lambda: sup.snapshot()["replicas"]["0"]["state"]
                     == "live" and sup.snapshot()["rejoins"] >= 3)
        assert sup.snapshot()["rejoins"] >= 3
        configure_faults("")
        router.shutdown(drain=True, timeout=60)


# ==========================================================================
# brownout-driven autoscaling
# ==========================================================================

class TestAutoscale:
    def _ctl(self):
        return OverloadController(queue_wait_budget_ms=1e9,
                                  tick_budget_ms=1e9)

    def test_scale_up_on_sustained_rung(self, engine):
        ctl = self._ctl()
        ev0 = monitor.stat_get("serving_scale_events")
        router, sup = _supervised(
            engine, n=1, factory_kw={"overload": ctl}, max_replicas=2,
            scale_up_rung=RUNG_NO_SPEC, scale_up_after=3,
            scale_down_after=1000, scale_cooldown_s=0.05)
        ctl.force_rung(RUNG_SMALL_CHUNKS)
        assert _wait(lambda: router.n_replicas == 2)
        assert sorted(router.healthy_replicas()) == [0, 1]
        assert monitor.stat_get("serving_replicas_target") == 2
        assert monitor.stat_get("serving_scale_events") == ev0 + 1
        # saturation: at max_replicas the set holds
        time.sleep(0.3)
        assert router.n_replicas == 2
        router.shutdown(drain=True, timeout=60)

    def test_hysteresis_no_scale_on_blip(self, engine):
        """One hot poll is not a trend: the set must not grow until the
        rung SUSTAINS for scale_up_after polls (mirroring the brownout
        ladder's asymmetric hysteresis)."""
        ctl = self._ctl()
        router, sup = _supervised(
            engine, n=1, factory_kw={"overload": ctl}, max_replicas=2,
            scale_up_rung=RUNG_NO_SPEC, scale_up_after=200,
            scale_down_after=1000, poll_s=0.01)
        ctl.force_rung(RUNG_SMALL_CHUNKS)
        time.sleep(0.2)       # ~20 hot polls << 200
        ctl.force_rung(RUNG_HEALTHY)
        assert router.n_replicas == 1
        assert sup.snapshot()["scale_events"] == 0
        router.shutdown(drain=True, timeout=60)

    def test_scale_down_drains_and_shrinks(self, engine):
        ctl = self._ctl()
        router, sup = _supervised(
            engine, n=2, factory_kw={"overload": ctl}, min_replicas=1,
            max_replicas=2, scale_up_after=1000, scale_down_after=3,
            scale_down_occupancy=0.5, scale_cooldown_s=0.05)
        assert _wait(lambda: router.n_replicas == 1)
        assert monitor.stat_get("serving_replicas_target") == 1
        # min_replicas floor: the last replica never drains
        time.sleep(0.3)
        assert router.n_replicas == 1
        router.shutdown(drain=True, timeout=60)

    def test_drain_shrink_migrates_open_streams(self, engine):
        """A scale-down victim holding an open stream past
        drain_timeout_s EVACUATES: the stream migrates to a survivor
        through adopt_request and finishes token-identically."""
        ctl = self._ctl()
        ref = engine(n_slots=4)
        pm = _prompt(10)
        expected = ref.generate(pm, max_new_tokens=48)
        router, sup = _supervised(
            engine, n=2, factory_kw={"overload": ctl}, min_replicas=1,
            scale_up_after=1000, scale_down_after=3,
            scale_down_occupancy=1.1, scale_cooldown_s=0.05,
            drain_timeout_s=0.1)
        # load replica 0 harder so the least-loaded victim is replica 1
        hogs = [router.submit(_prompt(8), max_new_tokens=40)
                for _ in range(3)]
        mig = router.submit(pm, max_new_tokens=48)
        assert mig._replica == 1
        assert _wait(lambda: router.n_replicas == 1)
        assert mig.result(timeout=180) == expected
        assert mig._replica == 0                    # adopted by survivor
        for h in hogs:
            h.result(timeout=180)
        router.shutdown(drain=True, timeout=60)


# ==========================================================================
# spec-aware watchdog (watchdog= x draft=)
# ==========================================================================

class TestWatchdogDraftCompose:
    def test_healthy_compose_token_identity(self, engine):
        p = _prompt(9)
        expected = engine().generate(p, max_new_tokens=12)
        eng = engine(draft=DRAFT, spec_k=3, watchdog=True)
        assert eng.generate(p, max_new_tokens=12) == expected

    def test_nan_spec_tick_fails_only_poisoned_slot(self, engine):
        """serving_nan inside a SPECULATIVE tick: the verify program's
        in-jit verdict fingers the poisoned slot, only its stream fails
        (finish_reason watchdog), the healthy neighbor replays
        token-identically, and the draft cache is rebuilt alongside the
        target's."""
        p1, p2 = _prompt(9), _prompt(9)
        ref = engine(n_slots=2)
        e1 = ref.generate(p1, max_new_tokens=12)
        e2 = ref.generate(p2, max_new_tokens=12)
        eng = engine(n_slots=2, draft=DRAFT, spec_k=3, watchdog=True)
        old_draft_cache = eng.draft_cache
        trips0 = monitor.stat_get("serving_watchdog_trips")
        configure_faults("serving_nan@step=2")      # rid 2 on THIS engine
        eng.generate(p1, max_new_tokens=2)          # rid 0 warms programs
        r1 = eng.submit(p1, max_new_tokens=12)      # rid 1: healthy
        r2 = eng.submit(p2, max_new_tokens=12)      # rid 2: poisoned
        assert r1.result(timeout=180) == e1
        with pytest.raises(RuntimeError):
            r2.result(timeout=180)
        assert r1.finish_reason == "length"
        assert r2.finish_reason == "watchdog"
        assert monitor.stat_get("serving_watchdog_trips") > trips0
        assert eng.draft_cache is not old_draft_cache   # rebuilt
        configure_faults("")
        # the restarted engine still speculates correctly
        assert eng.generate(p2, max_new_tokens=12) == e2

    def test_watchdog_off_spec_engine_unchanged(self, engine):
        """watchdog=None spec programs return no health output — the
        historical PR-10 tick shape (pinned by running the spec engine
        with faults armed for a DIFFERENT rid: nothing trips)."""
        p = _prompt(9)
        ref = engine(n_slots=2)
        expected = ref.generate(p, max_new_tokens=12)
        eng = engine(n_slots=2, draft=DRAFT, spec_k=3)
        configure_faults("serving_nan@step=99")
        assert eng.generate(p, max_new_tokens=12) == expected
        configure_faults("")


# ==========================================================================
# observability: readyz, gauges, lifecycle_report
# ==========================================================================

class TestLifecycleObservability:
    def test_rung_held_s_tracks_transitions(self):
        ctl = OverloadController(tick_budget_ms=100, alpha=1.0,
                                 step_up_after=1)
        time.sleep(0.05)
        held = ctl.rung_held_s()
        assert held >= 0.05
        assert ctl.snapshot()["rung_held_s"] >= 0.05
        ctl.observe_tick(1000)          # steps to rung 1: dwell resets
        assert ctl.rung_held_s() < held


    def test_readyz_excludes_warming_replica(self, engine):
        from paddle_tpu.serving.frontend import ServingFrontend, Tenant
        from paddle_tpu.serving.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=3)

        def mk():
            return engine(params=params, cfg=cfg, tokenizer=tok)

        router = EngineRouter([mk()])
        sup = ReplicaSupervisor(router, mk, poll_s=0.02)
        fe = ServingFrontend(router, tenants=[
            Tenant("t", "sk-t", rate=1000, burst=1000)]).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=60)
            conn.request("GET", "/readyz")
            resp = conn.getresponse()
            obj = json.loads(resp.read())
            conn.close()
            assert resp.status == 200
            assert obj["checks"]["lifecycle"]["target"] == 1
            # flip the only replica to warming: not ready, and the
            # replica row says why
            router._warming.add(0)
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=60)
            conn.request("GET", "/readyz")
            resp = conn.getresponse()
            obj = json.loads(resp.read())
            conn.close()
            assert resp.status == 503
            assert obj["checks"]["replicas"]["0"]["warming"]
            router.mark_ready(0)
        finally:
            fe.close()
            router.shutdown(drain=False, timeout=30)

    def test_metrics_expose_lifecycle_gauges(self, engine):
        from paddle_tpu.serving.frontend import ServingFrontend, Tenant
        from paddle_tpu.serving.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                       vocab_size=tok.vocab_size)
        eng = engine(params=gpt_init(cfg, seed=3), cfg=cfg, tokenizer=tok)
        fe = ServingFrontend(eng, tenants=[Tenant("t", "sk-t")]).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=60)
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
            conn.close()
            for g in ("serving_replicas_target", "serving_replica_restarts",
                      "serving_scale_events", "prefix_warm_tokens"):
                assert f"paddle_tpu_{g} " in text
        finally:
            fe.close()

    def test_lifecycle_report_causes_scales_and_warm(self, engine):
        tr = _trace_report()
        ctl = OverloadController(queue_wait_budget_ms=1e9,
                                 tick_budget_ms=1e9)
        writer = monitor.start_tracing()
        configure_faults("replica_crash@step=4:replica=0")
        try:
            router, sup = _supervised(
                engine, n=1,
                factory_kw={"overload": ctl, "prefix_cache": True,
                            "n_blocks": 65},
                max_replicas=2, scale_up_rung=RUNG_NO_SPEC,
                scale_up_after=2, scale_down_after=1000,
                scale_cooldown_s=0.05)
            head = _prompt(24)
            router.submit(np.concatenate([head, _prompt(6)]),
                          max_new_tokens=2).result(timeout=120)
            router.submit(np.concatenate([head, _prompt(6)]),
                          max_new_tokens=12).result(timeout=180)
            assert _wait(lambda: sup.snapshot()["rejoins"] == 1)
            ctl.force_rung(RUNG_SMALL_CHUNKS)
            # the scale_events counter moves AFTER the scale_up span is
            # written, so waiting on it guarantees the trace row exists
            assert _wait(lambda: sup.snapshot()["scale_events"] >= 1)
        finally:
            monitor.stop_tracing()
            configure_faults("")
        out = tr.lifecycle_report(writer.events(),
                                  file=open(os.devnull, "w"))
        assert out["restarts"] >= 2          # respawn + scale-up spawn
        assert out["rejoins"] >= 1
        assert "InjectedCrash" in out["restart_causes"]
        assert any(r["event"] == "scale_up" for r in out["scale_timeline"])
        assert out["warm_tokens"] >= 24
        assert "verdict" in out
        # empty-event robustness (main() wiring)
        assert tr.lifecycle_report([], file=open(os.devnull, "w")) == {}
        router.shutdown(drain=True, timeout=60)

    def test_trace_report_main_includes_lifecycle(self, tmp_path, engine):
        tr = _trace_report()
        writer = monitor.start_tracing()
        configure_faults("replica_crash@step=3:replica=0")
        try:
            router, sup = _supervised(engine, n=1)
            router.submit(_prompt(8), max_new_tokens=10).result(timeout=180)
            assert _wait(lambda: sup.snapshot()["rejoins"] == 1)
        finally:
            monitor.stop_tracing()
            configure_faults("")
        path = writer.write(str(tmp_path / "trace.json"))
        assert tr.main([path]) is not None
        router.shutdown(drain=True, timeout=60)
