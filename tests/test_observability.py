"""ISSUE 15 — observability v2: causal request tracing, histogram
metrics with real Prometheus exposition, and the crash flight recorder.

Covers the acceptance gates:
- a chaos request that survives a replica crash renders as ONE connected
  trace (admission -> lane -> prefill -> decode ticks -> failover hop ->
  completion on the survivor) and request_report attributes its latency;
- tracing disabled is pinned bit-identical on the token stream;
- GET /metrics parses under a STRICT Prometheus text-format parser while
  a burst of streaming requests is in flight, histogram buckets are
  monotone, _count/_sum are consistent, and the scrape never blocks the
  scheduler tick;
- watchdog/give-up paths dump flight recordings that trace_report loads
  and MERGES across >= 2 simulated hosts;
- the README observability catalog cannot drift from the registry;
- graftlint GL011 span hygiene.
"""
import http.client
import importlib.util
import json
import math
import os
import re
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — jax/mesh bootstrap
from paddle_tpu import monitor
from paddle_tpu.models import gpt_init, gpt_tiny
from paddle_tpu.monitor.stats import (DEFAULT_BUCKETS_MS,
                                      DEFAULT_HISTOGRAMS, Histogram,
                                      _prom_name, hist_delta,
                                      hist_quantile, prometheus_text)
from paddle_tpu.resilience.faults import configure_faults
from paddle_tpu.serving import EngineRouter, InferenceEngine
from paddle_tpu.serving.tokenizer import ByteTokenizer

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt_tiny(dtype=jnp.float32, seq_len=128)
PARAMS = gpt_init(CFG, seed=5)
RNG = np.random.default_rng(15)


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


@pytest.fixture(autouse=True)
def _clean():
    yield
    configure_faults("")
    monitor.stop_tracing()
    monitor.disarm_flight_recorder()
    monitor.set_host_id("h0")


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


# ==========================================================================
# Histogram type + quantile math
# ==========================================================================

class TestHistogram:
    def test_observe_buckets_count_sum(self):
        h = Histogram("t", bounds=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.7, 3.0, 100.0):
            h.observe(v)
        s = h.snapshot()
        assert s["counts"] == [1, 2, 1, 1]      # last = +Inf overflow
        assert s["count"] == 5
        assert abs(s["sum"] - 106.7) < 1e-9

    def test_quantile_within_bucket_resolution(self):
        h = Histogram("t")
        rng = np.random.default_rng(0)
        vals = np.exp(rng.normal(3.0, 1.0, size=2000))
        for v in vals:
            h.observe(float(v))
        for q in (0.5, 0.9, 0.99):
            est = h.quantile(q)
            true = float(np.percentile(vals, q * 100))
            assert abs(math.log2(est / true)) <= 1.0, (q, est, true)

    def test_delta_scopes_a_run(self):
        h = Histogram("t")
        h.observe(1.0)
        before = h.snapshot()
        h.observe(3.0)
        h.observe(5.0)
        d = hist_delta(before, h.snapshot())
        assert d["count"] == 2 and abs(d["sum"] - 8.0) < 1e-9

    def test_empty_quantile_is_zero(self):
        assert hist_quantile(Histogram("t").snapshot(), 0.5) == 0.0

    def test_bad_bounds_rejected(self):
        with pytest.raises(ValueError):
            Histogram("t", bounds=(2.0, 1.0))

    def test_registry_reset_covers_histograms(self):
        h = monitor.get_histogram("serving_first_token_ms")
        h.observe(1.0)
        monitor.reset_all_stats()
        assert h.snapshot()["count"] == 0

    def test_default_histograms_registered(self):
        snap = monitor.histogram_snapshot()
        for name, _ in DEFAULT_HISTOGRAMS:
            assert name in snap
            assert snap[name]["bounds"] == list(DEFAULT_BUCKETS_MS)


# ==========================================================================
# Prometheus exposition — strict parser
# ==========================================================================

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$")
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*"$')


def parse_prometheus(text):
    """STRICT text-format 0.0.4 parser: rejects invalid metric/label
    names, HELP/TYPE-less samples, non-numeric values, non-monotone
    histogram buckets and _count/_sum inconsistencies. Returns
    {family: {"type", "samples": [(name, labels, value)]}}."""
    families = {}
    helped, typed = set(), set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert _NAME_RE.match(name), f"bad HELP name {name!r}"
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            name, kind = parts[2], parts[3]
            assert _NAME_RE.match(name), f"bad TYPE name {name!r}"
            assert kind in ("gauge", "counter", "histogram", "summary",
                            "untyped")
            typed.add(name)
            families[name] = {"type": kind, "samples": []}
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line {line!r}"
        name = m.group("name")
        labels = {}
        if m.group("labels"):
            for pair in m.group("labels").split(","):
                assert _LABEL_RE.match(pair), f"bad label {pair!r}"
                k, v = pair.split("=", 1)
                labels[k] = v.strip('"')
        value = float(m.group("value"))
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in families:
                base = name[:-len(suffix)]
                break
        assert base in families, f"sample {name!r} without # TYPE"
        assert base in helped, f"sample {name!r} without # HELP"
        families[base]["samples"].append((name, labels, value))
    # histogram invariants: monotone buckets, +Inf == _count,
    # _sum present and non-negative for latency series
    for fam, data in families.items():
        if data["type"] != "histogram":
            continue
        buckets = [(s[1]["le"], s[2]) for s in data["samples"]
                   if s[0] == fam + "_bucket"]
        assert buckets, f"{fam}: histogram without buckets"
        nums = []
        for le, v in buckets:
            nums.append((math.inf if le == "+Inf" else float(le), v))
        assert nums == sorted(nums, key=lambda t: t[0]), \
            f"{fam}: bucket les out of order"
        counts = [v for _, v in nums]
        assert counts == sorted(counts), f"{fam}: non-monotone buckets"
        assert nums[-1][0] == math.inf, f"{fam}: missing +Inf bucket"
        count = [s[2] for s in data["samples"] if s[0] == fam + "_count"]
        total = [s[2] for s in data["samples"] if s[0] == fam + "_sum"]
        assert len(count) == 1 and len(total) == 1
        assert counts[-1] == count[0], f"{fam}: +Inf != _count"
        assert total[0] >= 0.0
    return families


class TestPrometheusExposition:
    def test_sanitize_names(self):
        assert _prom_name("device_memory_bytes.data") \
            == "paddle_tpu_device_memory_bytes_data"
        assert _prom_name("op@grad_jit") == "paddle_tpu_op_grad_jit"
        assert _NAME_RE.match(_prom_name("9starts_with_digit"))

    def test_exposition_parses_strict(self):
        monitor.stat_add("device_memory_bytes.data", 0)  # dotted gauge
        monitor.get_histogram("serving_first_token_ms").observe(3.0)
        fams = parse_prometheus(prometheus_text())
        assert "paddle_tpu_serving_first_token_ms" in fams
        assert fams["paddle_tpu_serving_first_token_ms"]["type"] \
            == "histogram"
        assert "paddle_tpu_device_memory_bytes_data" in fams
        # every registered gauge made it out with metadata
        for name in monitor.stat_names():
            assert _prom_name(name) in fams


# ==========================================================================
# /metrics under live load (frontend) + scrape-never-blocks
# ==========================================================================

@pytest.fixture(scope="module")
def frontend():
    from paddle_tpu.serving.frontend import ServingFrontend, Tenant

    tok = ByteTokenizer()
    cfg = gpt_tiny(dtype=jnp.float32, seq_len=256,
                   vocab_size=tok.vocab_size)
    params = gpt_init(cfg, seed=5)
    eng = InferenceEngine(cfg, params, n_slots=4,
                          block_size=16, prefill_chunk=64, tokenizer=tok)
    fe = ServingFrontend(eng, tenants=[
        Tenant("load-co", "sk-load", rate=1000, burst=1000,
               max_streams=64, lane="gold")]).start()
    yield fe
    fe.close()
    eng.shutdown(drain=False, timeout=30)


def _call(fe, method, path, body=None, key="sk-load", timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=timeout)
    try:
        conn.request(method, path,
                     json.dumps(body) if body is not None else None,
                     {"Authorization": f"Bearer {key}"})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


class TestMetricsUnderLoad:
    def test_scrape_during_streaming_burst(self, frontend):
        """Scrape /metrics while streaming generations are in flight:
        strict-parse every scrape, pin histogram monotonicity and
        count/sum consistency, and require the scheduler to keep
        ticking (token counts grow BETWEEN scrapes — the scrape cannot
        have blocked the tick loop)."""
        results = []

        def fire():
            results.append(_call(
                frontend, "POST", "/v1/completions",
                {"prompt": "observability " * 4, "max_tokens": 24,
                 "stream": False}))

        threads = [threading.Thread(target=fire) for _ in range(6)]
        for t in threads:
            t.start()
        eng = frontend.engine
        fams_seen = []
        progress = []
        deadline = time.monotonic() + 120
        while any(t.is_alive() for t in threads) \
                and time.monotonic() < deadline:
            t0 = time.perf_counter()
            status, headers, data = _call(frontend, "GET", "/metrics")
            scrape_s = time.perf_counter() - t0
            assert status == 200
            assert headers.get("Content-Type", "").startswith("text/plain")
            fams = parse_prometheus(data.decode())
            fams_seen.append(fams)
            progress.append(monitor.stat_get("serving_decode_ms"))
            assert scrape_s < 5.0, "scrape stalled"
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=120)
        assert len(fams_seen) >= 3, "burst finished before any scrape"
        assert all(s == 200 for s, _, _ in results)
        # live histogram series moved during the burst
        fam = "paddle_tpu_serving_first_token_ms"
        count_of = lambda f: [s[2] for s in f[fam]["samples"]   # noqa: E731
                              if s[0] == fam + "_count"][0]
        assert count_of(fams_seen[-1]) >= count_of(fams_seen[0])
        # the tick loop made progress while scrapes were happening
        assert progress[-1] > progress[0] or len(set(progress)) > 1

    def test_queue_wait_histogram_fed_by_lane(self, frontend):
        before = monitor.get_histogram("serving_queue_wait_ms").snapshot()
        status, _, _ = _call(frontend, "POST", "/v1/completions",
                             {"prompt": "q", "max_tokens": 2})
        assert status == 200
        after = monitor.get_histogram("serving_queue_wait_ms").snapshot()
        assert hist_delta(before, after)["count"] >= 1


# ==========================================================================
# Causal request tracing
# ==========================================================================

class TestRequestTracing:
    def test_tracing_disabled_token_stream_bit_identical(self, engine):
        """The pin the ISSUE names: minting/propagating a trace context
        (and full tracing itself) must not perturb one sampled or greedy
        token."""
        p = _prompt(12)
        for temp in (0.0, 0.9):
            base = engine(seed=7).submit(
                p, max_new_tokens=12, temperature=temp).result(timeout=120)
            eng = engine(seed=7)
            monitor.start_tracing()
            try:
                traced = eng.submit(p, max_new_tokens=12, temperature=temp,
                                    trace=monitor.mint_trace()
                                    ).result(timeout=120)
            finally:
                monitor.stop_tracing()
            assert traced == base

    def test_engine_spans_share_one_trace_id(self, engine):
        eng = engine()
        ctx = monitor.mint_trace()
        writer = monitor.start_tracing()
        try:
            eng.submit(_prompt(20), max_new_tokens=6,
                       trace=ctx).result(timeout=120)
        finally:
            monitor.stop_tracing()
        evs = [e for e in writer.events()
               if (e.get("args") or {}).get("trace") == ctx.trace_id]
        names = {e["name"] for e in evs}
        assert {"serving.prefill_chunk", "serving.decode_tick",
                "serving.request_done"} <= names
        # flow chain: steps plus exactly one finish, all on the ctx id
        flows = [e for e in writer.events()
                 if e.get("id") == ctx.trace_id]
        assert sum(1 for e in flows if e["ph"] == "f") == 1
        assert any(e["ph"] == "t" for e in flows)
        # span ids are unique within the trace, parents resolve
        sids = [e["args"]["span"] for e in evs]
        assert len(sids) == len(set(sids))

    def test_chaos_crash_renders_one_connected_timeline(self, engine):
        """THE acceptance gate: a request surviving a replica crash is
        one connected timeline — admission, lane wait, prefill, decode
        ticks on the dead replica, the failover hop, decode ticks on
        the survivor, completion — under a single trace id, and
        request_report attributes its latency across those phases."""
        from paddle_tpu.serving.frontend import ServingFrontend, Tenant

        tok = ByteTokenizer()
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=256,
                       vocab_size=tok.vocab_size)
        params = gpt_init(cfg, seed=5)

        def make():
            return InferenceEngine(cfg, params, n_slots=2,
                                   block_size=8, prefill_chunk=16,
                                   seed=0, tokenizer=tok)

        writer = monitor.start_tracing()
        configure_faults("replica_crash@step=4:replica=0")
        router = EngineRouter([make(), make()])
        fe = ServingFrontend(router, tenants=[
            Tenant("t", "sk-t", rate=1000, burst=1000)]).start()
        try:
            status, _, data = _call(
                fe, "POST", "/v1/completions",
                {"prompt": "failover me " * 3, "max_tokens": 24},
                key="sk-t")
            assert status == 200
            body = json.loads(data)
            assert body["choices"][0]["finish_reason"] in ("length", "eos")
        finally:
            monitor.stop_tracing()
            configure_faults("")
            fe.close()
            router.shutdown(drain=False, timeout=30)
        events = writer.events()
        hops = [e for e in events
                if e["name"] == "serving.failover_hop"]
        assert hops, "the crash never produced a failover hop"
        tid = hops[0]["args"]["trace"]
        mine = [e for e in events
                if (e.get("args") or {}).get("trace") == tid]
        names = [e["name"] for e in mine]
        for expected in ("frontend.admission", "frontend.queue_wait",
                         "serving.prefill_chunk", "serving.decode_tick",
                         "serving.failover_hop", "serving.request_done"):
            assert expected in names, f"timeline missing {expected}"
        # decode ticks ran on BOTH replicas of the hop
        replicas = {e["args"].get("replica") for e in mine
                    if e["name"] == "serving.decode_tick"}
        assert len(replicas) >= 2, f"no cross-replica ticks: {replicas}"
        # ONE connected flow chain: a start, steps, one finish
        flows = [e for e in events if e.get("id") == tid]
        phs = [e["ph"] for e in flows]
        assert "s" in phs and phs.count("f") == 1
        # request_report attributes the phases
        tr = _trace_report()
        out = tr.request_report(events, file=open(os.devnull, "w"))
        row = next(r for r in out["slowest"] if r["trace"] == tid)
        assert row["hops"] == 1
        assert row["decode_ms"] > 0 and row["prefill_ms"] > 0
        assert row["finish"] in ("length", "eos")
        assert len(row["replicas"]) >= 2
        assert out["failovers_survived"] >= 1

    def test_request_report_synthetic_phases(self):
        tr = _trace_report()
        evs = [
            {"name": "frontend.admission", "ph": "X", "ts": 0, "dur": 0,
             "args": {"trace": 9, "span": 1, "parent": 0}},
            {"name": "frontend.queue_wait", "ph": "X", "ts": 5000,
             "dur": 0, "args": {"trace": 9, "span": 2, "parent": 1,
                                "wait_ms": 5.0}},
            {"name": "serving.prefill_chunk", "ph": "X", "ts": 6000,
             "dur": 4000, "args": {"trace": 9, "span": 3, "parent": 2}},
            {"name": "serving.decode_tick", "ph": "X", "ts": 11000,
             "dur": 8000, "args": {"trace": 9, "span": 4, "parent": 3,
                                   "replica": 0, "tokens": 4}},
            {"name": "serving.request_done", "ph": "X", "ts": 20000,
             "dur": 0, "args": {"trace": 9, "span": 5, "parent": 4,
                                "reason": "length", "tokens": 4}},
        ]
        out = tr.request_report(evs, file=open(os.devnull, "w"))
        row = out["slowest"][0]
        assert row["total_ms"] == 20.0
        assert row["lane_wait_ms"] == 5.0
        assert row["prefill_ms"] == 4.0
        assert row["decode_ms"] == 8.0
        assert abs(row["stall_ms"] - 3.0) < 1e-6
        assert row["critical_phase"] == "decode"

    def test_report_empty_without_traces(self):
        tr = _trace_report()
        assert tr.request_report([], file=open(os.devnull, "w")) == {}


# ==========================================================================
# Flight recorder
# ==========================================================================

class TestFlightRecorder:
    def test_ring_is_bounded_and_gauge_deltas_interleave(self):
        rec = monitor.arm_flight_recorder("/tmp/unused", capacity=32,
                                          gauge_every=8)
        from paddle_tpu.monitor.trace import span
        for i in range(200):
            monitor.stat_add("serving_evictions", 1)   # gauges keep moving
            with span("flight.test", args={"i": i}):
                pass
        assert len(rec) <= 32
        deltas = [e for e in rec.events() if e["ph"] == "C"]
        assert deltas, "moving gauges must interleave as counter deltas"
        # only the gauges that MOVED ride in each delta
        assert all("serving_evictions" in e["args"] for e in deltas)

    def test_span_events_recorded_without_tracing(self, tmp_path):
        assert not monitor.is_tracing()
        rec = monitor.arm_flight_recorder(str(tmp_path))
        from paddle_tpu.monitor.trace import span
        with span("flight.untraced"):
            pass
        assert any(e["name"] == "flight.untraced" for e in rec.events())

    def test_watchdog_dump_and_two_host_merge(self, tmp_path, engine):
        """Acceptance: watchdog/give-up dumps load and MERGE across >=2
        simulated hosts into one timeline with per-host lanes."""
        d = str(tmp_path)
        # host A: serving watchdog restart (serving_nan poisons rid 0)
        monitor.set_host_id("hA")
        monitor.arm_flight_recorder(d)
        configure_faults("serving_nan@step=0")
        eng = engine(watchdog=True, flight_dir=d)
        try:
            req = eng.submit(_prompt(8), max_new_tokens=8)
            with pytest.raises(RuntimeError):
                req.result(timeout=120)
        finally:
            configure_faults("")
        assert _wait(lambda: any(
            f.startswith("flight_hA") for f in os.listdir(d)))
        # host B: supervisor give-up (fresh recorder = fresh "host")
        monitor.disarm_flight_recorder()
        monitor.set_host_id("hB")
        monitor.arm_flight_recorder(d)
        monitor.dump_flight("lifecycle_give_up_r0",
                            extra={"replica": 0, "cause": "test"})
        files = sorted(os.path.join(d, f) for f in os.listdir(d)
                       if f.startswith("flight_"))
        hosts = {f.split("_")[1] for f in map(os.path.basename, files)}
        assert {"hA", "hB"} <= hosts
        tr = _trace_report()
        traces = [tr.load_trace(p) for p in files]
        assert all(t["flight"] for t in traces)
        merged = tr.merge_traces(traces)
        pids = {e["pid"] for e in merged}
        assert len(pids) >= 2, "hosts must land in distinct lanes"
        labels = {e["args"]["name"] for e in merged
                  if e.get("ph") == "M"}
        assert any("hA" in l for l in labels)
        assert any("hB" in l for l in labels)
        out = tr.flight_report([t["flight"] for t in traces],
                               file=open(os.devnull, "w"))
        assert set(out["hosts"]) >= {"hA", "hB"}
        assert any("serving_watchdog_restart" in r["reason"]
                   for r in out["dumps"])

    def test_give_up_path_dumps(self, tmp_path, engine):
        """The ReplicaSupervisor's loud last rung writes a flight dump."""
        from paddle_tpu.serving import ReplicaSupervisor

        d = str(tmp_path)
        monitor.set_host_id("hG")
        monitor.arm_flight_recorder(d)
        configure_faults("replica_crash@step=3:replica=0,"
                         "spawn_fail@restart=1:times=10")
        router = EngineRouter([engine()])
        ReplicaSupervisor(
            router, engine, poll_s=0.02, backoff_s=0.02,
            backoff_cap_s=0.1, quarantine_s=0.1, stable_s=0.3,
            max_restarts=2, quarantine_after=1)
        try:
            req = router.submit(_prompt(8), max_new_tokens=16)
            with pytest.raises(RuntimeError):
                req.result(timeout=120)
            assert _wait(lambda: any(
                "give_up" in f for f in os.listdir(d)))
        finally:
            configure_faults("")
            router.shutdown(drain=False, timeout=30)
        path = next(os.path.join(d, f) for f in os.listdir(d)
                    if "give_up" in f)
        fl = _trace_report().load_trace(path)["flight"]
        assert fl["host"] == "hG" and "give_up" in fl["reason"]

    def test_trace_report_cli_json_and_merge(self, tmp_path):
        """python -m tools.trace_report --json --section over merged
        multi-file input (the satellite's CI surface)."""
        monitor.set_host_id("hX")
        rec = monitor.arm_flight_recorder(str(tmp_path))
        from paddle_tpu.monitor.trace import span
        with span("cli.test"):
            pass
        p1 = rec.dump("first")
        monitor.disarm_flight_recorder()
        monitor.set_host_id("hY")
        rec2 = monitor.arm_flight_recorder(str(tmp_path))
        with span("cli.test"):
            pass
        p2 = rec2.dump("second")
        out = subprocess.run(
            [sys.executable, "-m", "tools.trace_report", p1, p2,
             "--json", "--section", "flight", "--section", "spans"],
            cwd=_ROOT, capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        data = json.loads(out.stdout)
        assert set(data["flight"]["hosts"]) == {"hX", "hY"}
        assert any(r["name"] == "cli.test" for r in data["spans"])


# ==========================================================================
# GL011 span hygiene
# ==========================================================================

class TestSpanHygieneLint:
    def _run(self, src):
        from paddle_tpu.analysis import spans
        from paddle_tpu.analysis.lint import lint_source

        return [f for f in lint_source(src, rules=[spans.check])
                if f.rule == "GL011"]

    def test_known_bad_straight_line_pair(self):
        src = ("def f(w):\n"
               "    w.add_begin('x', 0.0)\n"
               "    work()\n"
               "    w.add_end('x', 1.0)\n")
        found = self._run(src)
        assert len(found) == 1 and found[0].detail == "span:x"

    def test_known_bad_no_closer(self):
        src = ("def f(w):\n"
               "    w.begin()\n")
        assert len(self._run(src)) == 1

    def test_known_good_finally(self):
        src = ("def f(w):\n"
               "    w.add_begin('x', 0.0)\n"
               "    try:\n"
               "        work()\n"
               "    finally:\n"
               "        w.add_end('x', 1.0)\n")
        assert self._run(src) == []

    def test_known_good_opener_inside_try(self):
        src = ("def f(w):\n"
               "    try:\n"
               "        w.add_begin('x', 0.0)\n"
               "        work()\n"
               "    finally:\n"
               "        w.add_end('x', 1.0)\n")
        assert self._run(src) == []

    def test_rule_registered_and_tree_clean(self):
        from paddle_tpu.analysis import RULE_DOCS, run_lint

        assert "GL011" in RULE_DOCS
        findings = [f for f in run_lint(
            [os.path.join(_ROOT, "paddle_tpu", "monitor"),
             os.path.join(_ROOT, "paddle_tpu", "serving")])
            if f.rule == "GL011"]
        assert findings == [], [f.format() for f in findings]


# ==========================================================================
# README catalog drift guard
# ==========================================================================

class TestCatalogDrift:
    def test_readme_lists_every_gauge_and_histogram(self):
        """The README observability catalog is CHECKED, not trusted:
        every registered gauge and histogram name must appear in the
        README, so adding a metric without documenting it fails CI."""
        from paddle_tpu.monitor.stats import DEFAULT_STATS

        with open(os.path.join(_ROOT, "README.md")) as f:
            readme = f.read()
        missing = [n for n in DEFAULT_STATS if n not in readme]
        missing += [n for n, _ in DEFAULT_HISTOGRAMS if n not in readme]
        assert not missing, f"README catalog missing: {missing}"

    def test_readme_documents_flight_and_tracing(self):
        with open(os.path.join(_ROOT, "README.md")) as f:
            readme = f.read()
        for needle in ("flight recorder", "trace_report", "request_report",
                       "Prometheus"):
            assert needle in readme, f"README missing {needle!r}"
