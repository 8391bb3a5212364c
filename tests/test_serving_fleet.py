"""ISSUE 19 — cross-host serving fleet: stdlib RPC transport, registry
heartbeats over FileKVStore, remote replica proxies with token-replay
failover, disaggregated prefill->decode KV-block streaming, the
(host, replica)-keyed supervisor ladder, and the fleet trace section."""
import http.client
import importlib.util
import json
import multiprocessing
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — jax/mesh bootstrap
from paddle_tpu import monitor
from paddle_tpu.distributed.elastic import FileKVStore
from paddle_tpu.models import gpt_init, gpt_tiny
from paddle_tpu.resilience.faults import configure_faults
from paddle_tpu.serving import (EngineRouter, InferenceEngine,
                                ReplicaSupervisor)
from paddle_tpu.serving.pod import (ArrivalRateForecaster, FleetRegistry,
                                    FleetScheduler, HostAgent,
                                    RemoteReplica, connect_fleet)
from paddle_tpu.serving.rpc import (RpcClient, RpcError, RpcRemoteError,
                                    RpcServer, decode_arrays, encode_arrays)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt_tiny(dtype=jnp.float32, seq_len=128)
PARAMS = gpt_init(CFG, seed=3)
RNG = np.random.default_rng(19)


def _prompt(n, rng=RNG):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


def _wait(pred, timeout=60.0, poll=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


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
        kw.setdefault("seed", 0)
        kw.setdefault("prefix_cache", True)
        kw.setdefault("n_blocks", 129)
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


def _factory():
    return InferenceEngine(CFG, PARAMS, n_slots=2,
                           block_size=8, prefill_chunk=16, seed=0,
                           prefix_cache=True, n_blocks=129)


@pytest.fixture
def fleet(tmp_path):
    """Build in-process HostAgents over real loopback RPC + a FileKVStore
    registry; yields (make_fleet, store) and tears everything down."""
    made = {"agents": [], "routers": []}
    store = FileKVStore(str(tmp_path / "kv"))

    def make(roles, job="j", factory=_factory, **connect_kw):
        agents = {}
        for host, role in roles.items():
            agents[host] = HostAgent(store, job, host, factory,
                                     role=role, heartbeat_s=0.1)
            made["agents"].append(agents[host])
        connect_kw.setdefault("min_hosts", len(roles))
        connect_kw.setdefault("registry_ttl", 0.8)
        connect_kw.setdefault("poll_s", 0.2)
        connect_kw.setdefault("monitor_poll_s", 0.1)
        router = connect_fleet(store, job, **connect_kw)
        made["routers"].append(router)
        return agents, router

    yield make, store
    for router in made["routers"]:
        try:
            router.shutdown(drain=False)
        except Exception:  # noqa: BLE001
            pass
    for a in made["agents"]:
        try:
            a.close()
        except Exception:  # noqa: BLE001 — abruptly-killed hosts are gone
            pass


# ==========================================================================
# RPC transport
# ==========================================================================

class TestRpcTransport:
    def test_roundtrip_scalars_and_arrays(self):
        def echo(params, arrays):
            # double the numeric payloads; pass bf16 through untouched
            # (numpy would silently promote bf16 * int to float32)
            return {"got": params}, {
                k: v if k == "c" else v * 2 for k, v in arrays.items()}

        srv = RpcServer({"echo": echo})
        client = RpcClient(srv.addr)
        try:
            arrs = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
                    "b": np.asarray([1, -2], np.int32),
                    "c": np.ones((3,), jnp.bfloat16)}
            res, out = client.call("echo", {"x": 1, "s": "ok"}, arrs)
            assert res["got"] == {"x": 1, "s": "ok"}
            assert out["a"].dtype == np.float32
            np.testing.assert_array_equal(out["a"],
                                          np.asarray(arrs["a"]) * 2)
            np.testing.assert_array_equal(out["b"], [2, -4])
            assert str(out["c"].dtype) == "bfloat16"   # ml_dtypes name
        finally:
            client.close()
            srv.close()

    def test_remote_error_carries_type(self):
        def boom(params, arrays):
            raise ValueError("bad widget")

        srv = RpcServer({"boom": boom})
        client = RpcClient(srv.addr)
        try:
            with pytest.raises(RpcRemoteError, match="bad widget") as ei:
                client.call("boom")
            assert ei.value.etype == "ValueError"
            with pytest.raises(RpcRemoteError) as ei:
                client.call("no_such_method")
            assert ei.value.etype == "KeyError"
            # the server survives handler errors: next call still works
            with pytest.raises(RpcRemoteError):
                client.call("boom")
        finally:
            client.close()
            srv.close()

    def test_concurrent_calls_do_not_serialize(self):
        """A parked long-poll must not delay a health probe — the client
        pool hands each concurrent caller its own socket."""
        def slow(params, arrays):
            time.sleep(0.5)
            return {"ok": "slow"}

        def fast(params, arrays):
            return {"ok": "fast"}

        srv = RpcServer({"slow": slow, "fast": fast})
        client = RpcClient(srv.addr)
        try:
            done = {}
            th = threading.Thread(
                target=lambda: done.setdefault(
                    "slow", client.call("slow")[0]))
            th.start()
            time.sleep(0.05)               # the slow call is parked
            t0 = time.monotonic()
            res, _ = client.call("fast")
            assert time.monotonic() - t0 < 0.4
            assert res["ok"] == "fast"
            th.join(timeout=5)
            assert done["slow"]["ok"] == "slow"
        finally:
            client.close()
            srv.close()

    def test_dead_server_raises_transport_error(self):
        srv = RpcServer({"ping": lambda p, a: {"ok": True}})
        addr = srv.addr
        srv.close()
        client = RpcClient(addr, timeout=2.0)
        try:
            with pytest.raises(RpcError):
                client.call("ping")
        finally:
            client.close()

    def test_torn_blob_rejected(self):
        manifest, blob = encode_arrays(
            {"a": np.arange(4, dtype=np.float32)})
        assert decode_arrays(manifest, blob)["a"].shape == (4,)
        with pytest.raises(RpcError, match="torn blob"):
            decode_arrays(manifest, blob[:-1])
        with pytest.raises(RpcError, match="trailing"):
            decode_arrays(manifest, blob + b"x")


# ==========================================================================
# registry: announce / heartbeat / staleness
# ==========================================================================

class TestFleetRegistry:
    def test_announce_alive_retire(self, tmp_path):
        store = FileKVStore(str(tmp_path))
        reg = FleetRegistry(store, "job", ttl=5.0)
        reg.announce("h0", {"host": "h0", "role": "decode", "seq": 1})
        reg.announce("h1", {"host": "h1", "role": "prefill", "seq": 1})
        alive = reg.alive()
        assert set(alive) == {"h0", "h1"}
        assert alive["h1"]["role"] == "prefill"
        reg.retire("h1")
        assert set(reg.alive()) == {"h0"}

    def test_unchanged_record_goes_stale(self, tmp_path):
        """Liveness is payload CHANGE under a monotonic clock — a host
        that stops bumping its seq ages out, no wall-clock skew games."""
        store = FileKVStore(str(tmp_path))
        reg = FleetRegistry(store, "job", ttl=0.2)
        reg.announce("h0", {"host": "h0", "seq": 1})
        assert set(reg.alive()) == {"h0"}
        assert _wait(lambda: "h0" not in reg.alive(), timeout=5.0)
        # heartbeat resumes (payload changes): alive again
        reg.announce("h0", {"host": "h0", "seq": 2})
        assert set(reg.alive()) == {"h0"}

    def test_corrupt_record_skipped_not_fatal(self, tmp_path):
        store = FileKVStore(str(tmp_path))
        reg = FleetRegistry(store, "job", ttl=5.0)
        reg.announce("h0", {"host": "h0", "seq": 1})
        # a torn write: raw garbage where a framed record should be
        store.put("fleet/job/hosts/evil", b"garbage-not-a-frame")
        assert set(reg.alive()) == {"h0"}


# ==========================================================================
# KV-block streaming: export on one engine, splice into another
# ==========================================================================

class TestKVStreaming:
    def test_greedy_identity_through_export_import(self, engine):
        p = _prompt(33)
        src, dst, mono = engine(), engine(), engine()
        expected = mono.generate(p, max_new_tokens=16)
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_prefix(p)
        assert exp is not None and exp["matched_len"] == 32  # len-1 cap
        assert exp["kb"].shape == exp["vb"].shape
        cached = dst.import_kv_prefix(p, exp["kb"], exp["vb"],
                                      exp["matched_len"])
        assert cached >= 32
        assert dst.generate(p, max_new_tokens=16) == expected

    def test_sampled_identity_through_export_import(self, engine):
        p = _prompt(25)
        src, dst, mono = engine(), engine(), engine()
        expected = mono.generate(p, max_new_tokens=16, temperature=0.8,
                                 top_k=7)
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_prefix(p)
        dst.import_kv_prefix(p, exp["kb"], exp["vb"], exp["matched_len"])
        # both engines assign rid 0 to their first submit: same (seed,
        # rid) -> the spliced blocks must be invisible in sampled tokens
        got = dst.generate(p, max_new_tokens=16, temperature=0.8, top_k=7)
        assert got == expected

    def test_import_is_idempotent(self, engine):
        p = _prompt(33)
        src, dst = engine(), engine()
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_prefix(p)
        c1 = dst.import_kv_prefix(p, exp["kb"], exp["vb"],
                                  exp["matched_len"])
        c2 = dst.import_kv_prefix(p, exp["kb"], exp["vb"],
                                  exp["matched_len"])
        assert c2 >= c1 >= 32

    def test_import_validates_geometry(self, engine):
        p = _prompt(33)
        src, dst = engine(), engine()
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_prefix(p)
        with pytest.raises(ValueError):
            dst.import_kv_prefix(p, exp["kb"][:-1], exp["vb"][:-1],
                                 exp["matched_len"])


# ==========================================================================
# fleet end-to-end (threaded hosts, real RPC sockets)
# ==========================================================================

class TestFleetEndToEnd:
    def test_disagg_token_identity_greedy_and_sampled(self, fleet, engine):
        make, _ = fleet
        agents, router = make({"pf": "prefill", "dec": "decode"})
        assert router.n_replicas == 1          # prefill pool ≠ replica
        mono = engine()
        long_p, sampled_p = _prompt(40), _prompt(33)
        exp_greedy = mono.generate(long_p, max_new_tokens=16)
        exp_sampled = mono.generate(sampled_p, max_new_tokens=16,
                                    temperature=0.7, top_k=5)
        routed0 = monitor.stat_get("fleet_prefill_routed")
        # sequential submits: rid order on the single decode engine
        # matches the monolithic oracle's
        got = router.submit(long_p, max_new_tokens=16).result(timeout=120)
        assert got == exp_greedy
        got = router.submit(sampled_p, max_new_tokens=16, temperature=0.7,
                            top_k=5).result(timeout=120)
        assert got == exp_sampled
        assert monitor.stat_get("fleet_prefill_routed") - routed0 == 2

    def test_short_prompt_stays_direct(self, fleet):
        make, _ = fleet
        agents, router = make({"pf": "prefill", "dec": "decode"})
        routed0 = monitor.stat_get("fleet_prefill_routed")
        req = router.submit(_prompt(9), max_new_tokens=8)  # < disagg_min
        assert len(req.result(timeout=120)) == 8
        assert monitor.stat_get("fleet_prefill_routed") == routed0

    def test_fleet_members_and_readyz(self, fleet):
        from paddle_tpu.serving.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=3)

        def factory():
            return InferenceEngine(cfg, params, n_slots=2,
                                   block_size=8, prefill_chunk=16, seed=0,
                                   prefix_cache=True, n_blocks=129,
                                   tokenizer=tok)

        make, _ = fleet
        agents, router = make({"pf": "prefill", "dec": "decode"},
                              factory=factory)
        # a health probe stamps each proxy's last-heard time; before the
        # first one the age is rightly infinite
        for e in list(router.engines) + list(router._prefill_pool):
            assert e.alive
        members = router.fleet_members()
        # the ISSUE-20 registry-reachability entry rides alongside the
        # per-replica rows
        assert members.pop("registry")["reachable"] is True
        by_host = {v["host"]: v for v in members.values()}
        assert by_host["dec"]["role"] == "decode"
        assert by_host["pf"]["role"] == "prefill"
        assert all(v["heartbeat_age_s"] < 60 for v in members.values())
        assert all(v["status"] == "ok" for v in members.values())
        from paddle_tpu.serving.frontend import ServingFrontend, Tenant

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
            fleet_checks = obj["checks"]["fleet"]
            assert fleet_checks.pop("registry")["reachable"] is True
            hosts = {v["host"] for v in fleet_checks.values()}
            assert hosts == {"pf", "dec"}
        finally:
            fe.close()

    def test_prefill_host_loss_falls_back_to_direct(self, fleet):
        make, _ = fleet
        agents, router = make({"pf": "prefill", "dec": "decode"})
        fb0 = monitor.stat_get("fleet_direct_fallbacks")
        agents["pf"].close(abrupt=True)     # no retire: heartbeat stops
        assert _wait(lambda: all(p._lost for p in router._prefill_pool),
                     timeout=20.0)
        req = router.submit(_prompt(40), max_new_tokens=12)
        assert len(req.result(timeout=120)) == 12
        assert monitor.stat_get("fleet_direct_fallbacks") > fb0

    def test_decode_host_loss_reroutes_token_identically(self, fleet,
                                                         engine):
        make, _ = fleet
        agents, router = make({"pf": "prefill", "d0": "decode",
                               "d1": "decode"})
        assert router.n_replicas == 2
        mono = engine()
        p = _prompt(40)
        expected = mono.generate(p, max_new_tokens=24)
        rr0 = monitor.stat_get("fleet_reroutes")
        req = router.submit(p, max_new_tokens=24)
        assert _wait(lambda: len(req.tokens) >= 4, timeout=60.0)
        victim = router.engine_for(req._replica)
        agents[victim.host].close(abrupt=True)
        assert req.result(timeout=120) == expected
        assert monitor.stat_get("fleet_reroutes") > rr0

    def test_remote_tokenizer_reconstructs_for_text_surface(self, tmp_path):
        from paddle_tpu.serving.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=3)

        def factory():
            return InferenceEngine(cfg, params, n_slots=2,
                                   block_size=8, prefill_chunk=16, seed=0,
                                   prefix_cache=True, n_blocks=129,
                                   tokenizer=tok)

        store = FileKVStore(str(tmp_path / "kv"))
        agent = HostAgent(store, "jt", "h0", factory, role="decode",
                          heartbeat_s=0.1)
        router = None
        try:
            router = connect_fleet(store, "jt", min_hosts=1,
                                   registry_ttl=5.0)
            assert type(router.tokenizer).__name__ == "ByteTokenizer"
            req = router.submit(text="hello fleet", max_new_tokens=8)
            assert isinstance(req.text(timeout=120), str)
        finally:
            if router is not None:
                router.shutdown(drain=False)
            agent.close()


# ==========================================================================
# forecaster + scheduler planning
# ==========================================================================

class TestFleetScheduling:
    def test_forecaster_windowed_rps(self):
        f = ArrivalRateForecaster(window_s=0.5)
        assert f.rps() == 0.0
        for _ in range(10):
            f.note_arrival()
        assert f.rps() > 0.0
        assert _wait(lambda: f.rps() == 0.0, timeout=5.0)

    def test_plan_roles_and_pool_plan(self):
        assert FleetScheduler.plan_roles(["a"]) == {"a": "mixed"}
        roles = FleetScheduler.plan_roles(["c", "a", "b"])
        assert roles["a"] == "prefill"
        assert roles["b"] == roles["c"] == "decode"
        pf = FleetScheduler.pool_plan("prefill", n_slots=4, block_size=16,
                                      n_blocks=65, prefill_chunk=32)
        dec = FleetScheduler.pool_plan("decode", n_slots=4, block_size=16,
                                       n_blocks=65, prefill_chunk=32)
        # prefill phase: fewer concurrent slots, more blocks, bigger
        # chunks; decode keeps the caller's shape
        assert pf["n_slots"] < dec["n_slots"]
        assert pf["n_blocks"] > dec["n_blocks"]
        assert pf["prefill_chunk"] >= 4 * 16
        assert dec == {"n_slots": 4, "block_size": 16, "n_blocks": 65,
                       "prefill_chunk": 32}

    def test_desired_replicas_ceils(self):
        s = FleetScheduler.__new__(FleetScheduler)
        s.rps_per_replica = 8.0
        s.max_replicas = 4
        assert s.desired_replicas(0.0) == 1
        assert s.desired_replicas(8.1) == 2
        assert s.desired_replicas(1e9) == 4


# ==========================================================================
# satellite 3: the (host, replica)-keyed ladder
# ==========================================================================

class TestHostKeyedLadder:
    def _hosted_supervised(self, engine, host="hostA", **sup_kw):
        def factory():
            eng = engine()
            eng.host = host
            return eng

        router = EngineRouter([factory()])
        sup_kw.setdefault("poll_s", 0.02)
        sup_kw.setdefault("backoff_s", 0.02)
        sup_kw.setdefault("backoff_cap_s", 0.1)
        sup_kw.setdefault("stable_s", 10.0)
        sup = ReplicaSupervisor(router, factory, **sup_kw)
        return router, sup

    def test_host_offer_springs_quarantine(self, engine):
        """A quarantined slot offered a DIFFERENT host becomes
        immediately due on that host's own (clean) ladder — the dead
        host's sentence doesn't transfer."""
        p = _prompt(8)
        expected = engine().generate(p, max_new_tokens=12)
        configure_faults("replica_crash@step=3:replica=0,"
                         "spawn_fail@restart=1:times=2")
        router, sup = self._hosted_supervised(
            engine, max_restarts=6, quarantine_after=2,
            quarantine_s=600.0)
        req = router.submit(p, max_new_tokens=12)
        # two spawn failures climb hostA's ladder into a 600s quarantine
        # — the slot is parked, nothing mutates it until the offer
        assert _wait(lambda: sup.snapshot()["replicas"]["0"]["state"]
                     == "quarantined", timeout=60.0)
        snap = sup.snapshot()["replicas"]["0"]
        assert snap["attempts"] == 2
        assert snap["host"] == "hostA"
        assert sup.note_host_offer(0, "hostA") is False  # same host: no-op
        assert sup.snapshot()["replicas"]["0"]["state"] == "quarantined"
        configure_faults("")               # spawns succeed from here on
        assert sup.note_host_offer(0, "hostB") is True
        # hostA's sentence was banked, not erased
        assert sup._ladders[("hostA", 0)] == 2
        # immediately due on hostB's clean ladder: the slot rejoins in
        # seconds (not 600), and the parked stream replays identically
        assert _wait(lambda: sup.snapshot()["replicas"]["0"]["state"]
                     == "live", timeout=60.0)
        assert req.result(timeout=120) == expected
        assert sup.note_host_offer(0, "hostC") is False  # live: no-op
        sup.close(timeout=30)
        router.shutdown(drain=False, timeout=30)

    def test_ladder_memory_per_host(self, engine):
        """Each host carries its OWN attempt count: quarantine on hostA,
        offer hostB (fresh ladder, climbs to its own quarantine), offer
        hostA back — both sentences are banked independently."""
        p = _prompt(8)
        configure_faults("replica_crash@step=3:replica=0,"
                         "spawn_fail@restart=1:times=2")
        router, sup = self._hosted_supervised(
            engine, max_restarts=20, quarantine_after=2,
            quarantine_s=600.0)
        router.submit(p, max_new_tokens=12)
        assert _wait(lambda: sup.snapshot()["replicas"]["0"]["state"]
                     == "quarantined", timeout=60.0)
        assert sup.snapshot()["replicas"]["0"]["host"] == "hostA"
        # re-arm two more spawn failures, then offer hostB: its ladder
        # starts at 0 and climbs to its own quarantine
        configure_faults("spawn_fail@restart=1:times=2")
        assert sup.note_host_offer(0, "hostB") is True
        assert _wait(
            lambda: (lambda s: s["state"] == "quarantined"
                     and s["host"] == "hostB")(
                         sup.snapshot()["replicas"]["0"]), timeout=60.0)
        assert sup._ladders[("hostA", 0)] == 2
        assert sup._ladders[("hostB", 0)] == 2
        # back to hostA with spawns healthy: resumes hostA's count (2,
        # still under max_restarts) and recovers
        configure_faults("")
        assert sup.note_host_offer(0, "hostA") is True
        assert _wait(lambda: sup.snapshot()["replicas"]["0"]["state"]
                     == "live", timeout=60.0)
        sup.close(timeout=30)
        router.shutdown(drain=False, timeout=30)


# ==========================================================================
# observability: fleet trace section
# ==========================================================================

class TestFleetTraceSection:
    def test_fleet_section_listed(self):
        tr = _trace_report()
        assert "fleet" in tr.SECTIONS
        assert tr.main(["--list-sections"]) == {}

    def test_fleet_report_from_live_spans(self, fleet):
        tr = _trace_report()
        make, _ = fleet
        writer = monitor.start_tracing()
        try:
            agents, router = make({"pf": "prefill", "dec": "decode"},
                                  job="jtrace")
            router.fleet_scan()            # membership snapshot span
            req = router.submit(_prompt(40), max_new_tokens=8)
            req.result(timeout=120)
            router.submit(_prompt(9), max_new_tokens=4).result(timeout=120)
        finally:
            monitor.stop_tracing()
        import io
        out = tr.fleet_report(writer.events(), file=io.StringIO())
        assert out["kv_transfers"] >= 1
        assert out["kv_bytes"] > 0
        hosts = {r["host"]: r for r in out["hosts"]}
        assert hosts["pf"]["role"] == "prefill"
        assert hosts["dec"]["role"] == "decode"
        assert "verdict" in out

    def test_empty_events_empty_report(self):
        tr = _trace_report()
        import io
        assert tr.fleet_report([], file=io.StringIO()) == {}


# ==========================================================================
# 2-process end-to-end (outside tier-1: `pytest -m pod`)
# ==========================================================================

@pytest.mark.pod
@pytest.mark.slow
class TestFleetMultiProcess:
    """One prefill-role + one decode-role host, each a REAL process,
    serving a Poisson burst through the HTTP frontend — the deployment
    shape of the acceptance bar."""

    @staticmethod
    def _host_proc(root, job, host, role, stop_file):
        import os as _os
        import time as _time

        import jax.numpy as _jnp

        from paddle_tpu.distributed.elastic import FileKVStore as _Store
        from paddle_tpu.models import gpt_init as _init, gpt_tiny as _tiny
        from paddle_tpu.serving import InferenceEngine as _Engine
        from paddle_tpu.serving.pod import HostAgent as _Agent
        from paddle_tpu.serving.tokenizer import ByteTokenizer as _Tok

        tok = _Tok()
        cfg = _tiny(dtype=_jnp.float32, seq_len=128,
                    vocab_size=tok.vocab_size)
        params = _init(cfg, seed=3)

        def factory():
            return _Engine(cfg, params, n_slots=2,
                           block_size=8, prefill_chunk=16, seed=0,
                           prefix_cache=True, n_blocks=129, tokenizer=tok)

        agent = _Agent(_Store(root), job, host, factory, role=role,
                       heartbeat_s=0.2)
        try:
            while not _os.path.exists(stop_file):
                _time.sleep(0.1)
        finally:
            agent.close()

    def test_two_process_fleet_burst_through_frontend(self, tmp_path):
        from paddle_tpu.serving.frontend import ServingFrontend, Tenant
        from paddle_tpu.serving.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=128,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=3)
        mono = InferenceEngine(cfg, params, n_slots=2,
                               block_size=8, prefill_chunk=16, seed=0,
                               prefix_cache=True, n_blocks=129,
                               tokenizer=tok)
        prompts = [f"request {i}: the quick brown fox number {i} "
                   f"jumps over the lazy dog" for i in range(6)]
        expected = [mono.submit(text=p, max_new_tokens=8).text(timeout=120)
                    for p in prompts]
        mono.shutdown(drain=False)

        root = str(tmp_path / "kv")
        stop_file = str(tmp_path / "stop")
        ctx = multiprocessing.get_context("spawn")
        procs = [ctx.Process(target=self._host_proc,
                             args=(root, "e2e", h, r, stop_file))
                 for h, r in (("pf", "prefill"), ("dec", "decode"))]
        for p in procs:
            p.start()
        router = fe = None
        try:
            router = connect_fleet(FileKVStore(root), "e2e", min_hosts=2,
                                   timeout=300.0, registry_ttl=2.0,
                                   poll_s=0.2)
            fe = ServingFrontend(router, tenants=[
                Tenant("t", "sk-t", rate=1000, burst=1000)]).start()
            rng = np.random.default_rng(7)
            gaps = rng.exponential(1 / 20.0, len(prompts))
            results: list = [None] * len(prompts)

            def post(i):
                conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                                  timeout=180)
                conn.request(
                    "POST", "/v1/completions",
                    json.dumps({"model": "m", "prompt": prompts[i],
                                "max_tokens": 8, "temperature": 0.0}),
                    {"Authorization": "Bearer sk-t",
                     "Content-Type": "application/json"})
                resp = conn.getresponse()
                body = json.loads(resp.read())
                conn.close()
                results[i] = (resp.status, body)

            threads = []
            for i in range(len(prompts)):
                th = threading.Thread(target=post, args=(i,))
                th.start()
                threads.append(th)
                time.sleep(float(gaps[i]))
            for th in threads:
                th.join(timeout=300)
            for i, (status, body) in enumerate(results):
                assert status == 200, body
                assert body["choices"][0]["text"] == expected[i]
            # the long text prompts ran disaggregated at least once
            assert monitor.stat_get("fleet_prefill_routed") > 0
        finally:
            with open(stop_file, "w") as f:
                f.write("stop")
            if fe is not None:
                fe.close()
            if router is not None:
                router.shutdown(drain=False)
            for p in procs:
                p.join(timeout=60)
                if p.is_alive():
                    p.terminate()
