"""ISSUE 20 — fleet network fault tolerance: RPC frame fuzzing, pool
hygiene, retry/backoff/circuit-breaking, the deterministic network fault
family (rpc_drop / rpc_delay / rpc_corrupt / net_partition), resumable
chunked KV streaming with mid-transfer resume, fleet-wide flight
collection, and the GL012 network-hygiene lint rule."""
import json
import os
import socket
import struct
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — jax/mesh bootstrap
from paddle_tpu import monitor
from paddle_tpu.analysis import lint_source
from paddle_tpu.distributed.elastic import FileKVStore
from paddle_tpu.models import gpt_init, gpt_tiny
from paddle_tpu.monitor.flight import (arm_flight_recorder,
                                       disarm_flight_recorder)
from paddle_tpu.resilience.faults import configure_faults, parse_spec
from paddle_tpu.serving import InferenceEngine
from paddle_tpu.serving.pod import HostAgent, connect_fleet
from paddle_tpu.serving.rpc import (BREAKER_CLOSED, BREAKER_OPEN,
                                    CircuitBreaker, RetryPolicy, RpcClient,
                                    RpcError, RpcRemoteError, RpcServer,
                                    _pack_frame, _recv_frame, decode_arrays,
                                    encode_arrays)

CFG = gpt_tiny(dtype=jnp.float32, seq_len=128)
PARAMS = gpt_init(CFG, seed=3)
RNG = np.random.default_rng(20)


def _prompt(n, rng=RNG):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


def _wait(pred, timeout=60.0, poll=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    configure_faults("")


@pytest.fixture
def echo_server():
    def echo(params, arrays):
        return {"got": params}, dict(arrays)

    def boom(params, arrays):
        raise ValueError("kapow")

    def slow(params, arrays):
        time.sleep(float(params.get("s", 0.2)))
        return {"ok": 1}

    srv = RpcServer({"echo": echo, "boom": boom, "slow": slow,
                     "submit": echo, "health": echo})
    yield srv
    srv.close()


def _feed(payload: bytes):
    """Push raw bytes at _recv_frame through a socketpair, closing the
    writer (so truncation is observable), with a timeout so a decoder
    bug can never hang the test."""
    a, b = socket.socketpair()
    a.sendall(payload)
    a.close()
    b.settimeout(5.0)
    try:
        return _recv_frame(b)
    finally:
        b.close()


# ==========================================================================
# frame fuzzing: every corruption raises, nothing hangs or half-decodes
# ==========================================================================

class TestFrameFuzz:
    def _frame(self):
        manifest, blob = encode_arrays(
            {"v": np.arange(12, dtype=np.float32)})
        return _pack_frame({"id": 7, "method": "echo", "params": {"x": 1},
                            "blobs": manifest}, blob)

    def test_valid_frame_roundtrips(self):
        header, blob = _feed(self._frame())
        assert header["id"] == 7
        assert decode_arrays(header["blobs"], blob)["v"].shape == (12,)

    def test_bad_magic_rejected(self):
        frame = bytearray(self._frame())
        frame[:4] = b"XXXX"
        with pytest.raises(RpcError, match="magic"):
            _feed(bytes(frame))

    def test_oversized_lengths_rejected_before_allocation(self):
        for jlen, blen in ((1 << 30, 0), (16, 1 << 62)):
            head = b"PRPC" + struct.pack("<IQ", jlen, blen)
            with pytest.raises(RpcError, match="oversized"):
                _feed(head + b"{}")

    def test_truncation_at_every_region_raises(self):
        """Cut the frame at a sample of offsets spanning head / header /
        blob; every cut must raise (RpcError for mid-frame death), never
        hang, never return partial data."""
        frame = self._frame()
        cuts = {1, 8, 15, 16, 20, len(frame) // 2, len(frame) - 1}
        for cut in sorted(cuts):
            with pytest.raises((RpcError, ConnectionError)):
                _feed(frame[:cut])

    def test_bitflip_fuzz_never_partially_decodes(self):
        """XOR one byte at a spread of positions. Outcomes allowed:
        clean RpcError, or a fully-valid decode whose arrays still parse
        (flips inside the float payload change values, not structure) —
        never an exception besides RpcError, never a hang."""
        frame = self._frame()
        jlen = struct.unpack("<IQ", frame[4:16])[0]
        rng = np.random.default_rng(0)
        positions = sorted(set(
            rng.integers(4, len(frame), 40).tolist()))
        for pos in positions:
            mutated = bytearray(frame)
            mutated[pos] ^= 0xFF
            try:
                header, blob = _feed(bytes(mutated))
            except (RpcError, ConnectionError):
                continue
            # decoded: manifest/blob must still be self-consistent
            try:
                arrs = decode_arrays(header.get("blobs"), blob)
            except RpcError:
                continue
            for a in arrs.values():
                assert a.size == 12
        assert jlen > 0   # sanity: the header region existed to fuzz

    def test_torn_blob_decode(self):
        manifest, blob = encode_arrays({"a": np.ones(5, np.float32)})
        with pytest.raises(RpcError, match="torn blob"):
            decode_arrays(manifest, blob[:-2])
        with pytest.raises(RpcError, match="trailing"):
            decode_arrays(manifest, blob + b"\0\0")
        # manifest claiming more than the frame carries
        lie = [dict(manifest[0], nbytes=999)]
        with pytest.raises(RpcError, match="torn blob"):
            decode_arrays(lie, blob)


# ==========================================================================
# pool hygiene: a poisoned socket is never re-pooled
# ==========================================================================

class _RogueServer:
    """Raw-socket server: per-connection scripts of misbehavior, then
    (optionally) correct echo service — for proving client pool hygiene
    without any cooperation from RpcServer."""

    def __init__(self, script):
        self.script = list(script)   # one entry per accepted connection
        self._listener = socket.create_server(("127.0.0.1", 0))
        self.addr = self._listener.getsockname()[:2]
        self._accepted = 0
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            mode = (self.script[self._accepted]
                    if self._accepted < len(self.script) else "echo")
            self._accepted += 1
            threading.Thread(target=self._serve, args=(conn, mode),
                             daemon=True).start()

    def _serve(self, conn, mode):
        conn.settimeout(10.0)
        try:
            while True:
                header, blob = _recv_frame(conn)
                if mode == "wrong_id":
                    reply = _pack_frame({"id": 999999, "ok": True,
                                         "result": {}, "blobs": []})
                    conn.sendall(reply)
                    mode = "echo"      # later requests on this conn: fine
                elif mode == "torn":
                    reply = _pack_frame({"id": header["id"], "ok": True,
                                         "result": {}, "blobs": []})
                    conn.sendall(reply[:len(reply) - 3])
                    conn.close()
                    return
                else:
                    reply = _pack_frame(
                        {"id": header["id"], "ok": True,
                         "result": {"echo": header.get("params")},
                         "blobs": []})
                    conn.sendall(reply)
        except (RpcError, ConnectionError, OSError):
            try:
                conn.close()
            except OSError:
                pass

    def close(self):
        self._stop = True
        try:
            self._listener.close()
        except OSError:
            pass


class TestPoolHygiene:
    def test_desynced_reply_never_corrupts_next_call(self):
        srv = _RogueServer(["wrong_id"])
        client = RpcClient(srv.addr, timeout=5.0)
        try:
            with pytest.raises(RpcError, match="desynced"):
                client.call("echo", {"n": 1})
            # the poisoned socket must have been destroyed, not pooled
            assert client._pool == []
            res, _ = client.call("echo", {"n": 2})
            assert res["echo"] == {"n": 2}
        finally:
            client.close()
            srv.close()

    def test_torn_reply_never_corrupts_next_call(self):
        srv = _RogueServer(["torn"])
        client = RpcClient(srv.addr, timeout=5.0)
        try:
            with pytest.raises(RpcError):
                client.call("echo", {"n": 1})
            assert client._pool == []
            res, _ = client.call("echo", {"n": 2})
            assert res["echo"] == {"n": 2}
        finally:
            client.close()
            srv.close()

    def test_healthy_socket_is_reused(self, echo_server):
        client = RpcClient(echo_server.addr, timeout=5.0)
        try:
            client.call("echo", {"n": 1})
            assert len(client._pool) == 1
            sock = client._pool[0]
            client.call("echo", {"n": 2})
            assert client._pool == [sock]   # same socket came back
        finally:
            client.close()

    def test_remote_error_keeps_socket(self, echo_server):
        """A handler exception is a HEALTHY round trip — the stream is
        aligned, so the socket must return to the pool."""
        client = RpcClient(echo_server.addr, timeout=5.0)
        try:
            with pytest.raises(RpcRemoteError):
                client.call("boom")
            assert len(client._pool) == 1
        finally:
            client.close()


# ==========================================================================
# retry policy + circuit breaker
# ==========================================================================

class TestRetryBreaker:
    def test_backoff_is_deterministic_and_capped(self):
        pol = RetryPolicy(max_attempts=5, backoff_s=0.05, backoff_max_s=0.3)
        assert [pol.backoff(i) for i in range(5)] == \
            [0.05, 0.1, 0.2, 0.3, 0.3]

    def test_idempotent_only(self):
        pol = RetryPolicy()
        assert pol.retryable("health") and pol.retryable("export_range")
        assert not pol.retryable("submit")
        assert not pol.retryable("adopt")

    def test_retry_rides_through_transient_drops(self, echo_server):
        configure_faults("rpc_drop@call=1:repeat=2:host=h0")
        client = RpcClient(echo_server.addr, timeout=5.0,
                           retry=RetryPolicy(max_attempts=3,
                                             backoff_s=0.01),
                           peer_host="h0")
        r0 = monitor.stat_get("rpc_retries")
        try:
            res, _ = client.call("health", {"n": 1})
            assert res["got"] == {"n": 1}
        finally:
            client.close()
        assert monitor.stat_get("rpc_retries") - r0 == 2

    def test_non_idempotent_never_retries(self, echo_server):
        configure_faults("rpc_drop@call=1:host=h1")
        client = RpcClient(echo_server.addr, timeout=5.0,
                           retry=RetryPolicy(max_attempts=3,
                                             backoff_s=0.01),
                           peer_host="h1")
        try:
            with pytest.raises(RpcError):
                client.call("submit", {"n": 1})
        finally:
            client.close()

    def test_retry_respects_deadline_budget(self, echo_server):
        configure_faults("rpc_drop@call=1:repeat=99:host=h2")
        client = RpcClient(echo_server.addr, timeout=5.0,
                           retry=RetryPolicy(max_attempts=50,
                                             backoff_s=0.2),
                           peer_host="h2")
        t0 = time.monotonic()
        try:
            with pytest.raises(RpcError):
                client.call("health", deadline_s=0.3)
        finally:
            client.close()
        assert time.monotonic() - t0 < 2.0

    def test_breaker_opens_fast_fails_and_recovers(self, echo_server):
        """3 consecutive injected transport errors open the breaker
        (gauge counts it); while open, calls fast-fail without touching
        the network; after cooldown the half-open probe (fault budget
        now spent) succeeds and closes it."""
        configure_faults("rpc_drop@call=1:repeat=3:host=h3")
        br = CircuitBreaker(threshold=3, cooldown_s=0.15, peer="h3")
        client = RpcClient(echo_server.addr, timeout=5.0, breaker=br,
                           peer_host="h3")
        try:
            for _ in range(3):
                with pytest.raises(RpcError):
                    client.call("health")
            assert br.state == BREAKER_OPEN
            assert monitor.stat_get("rpc_breaker_state") >= 1
            t0 = time.monotonic()
            with pytest.raises(RpcError, match="breaker open"):
                client.call("health")
            assert time.monotonic() - t0 < 0.05   # no dial, no timeout
            time.sleep(0.2)
            res, _ = client.call("health", {"ok": 1})   # half-open probe
            assert res["got"] == {"ok": 1}
            assert br.state == BREAKER_CLOSED
        finally:
            client.close()

    def test_breaker_failed_probe_reopens(self):
        br = CircuitBreaker(threshold=1, cooldown_s=0.05, peer="dead")
        client = RpcClient(("127.0.0.1", 1), timeout=0.2, breaker=br)
        try:
            with pytest.raises(RpcError):
                client.call("health")
            assert br.state == BREAKER_OPEN
            time.sleep(0.08)
            with pytest.raises(RpcError):
                client.call("health")       # the probe, still dead
            assert br.state == BREAKER_OPEN
        finally:
            client.close()


# ==========================================================================
# the network fault family
# ==========================================================================

class TestNetworkFaults:
    def test_specs_parse(self):
        specs = parse_spec("rpc_drop@call=3:method=export_range:host=h0,"
                           "rpc_delay@call=1:secs=0.5,"
                           "rpc_corrupt@call=2,"
                           "net_partition@step=1:secs=2:hosts=router|h2")
        kinds = [s.kind for s in specs]
        assert kinds == ["rpc_drop", "rpc_delay", "rpc_corrupt",
                         "net_partition"]
        assert specs[0].call == 3 and specs[0].method == "export_range"
        assert specs[3].hosts == (frozenset({"router"}), frozenset({"h2"}))

    def test_bad_specs_rejected(self):
        for bad in ("rpc_drop@step=1",            # wrong trigger space
                    "net_partition@step=1:secs=1",        # missing hosts
                    "net_partition@call=1:secs=1:hosts=a|b",
                    "crash@step=1:hosts=a|b"):    # hosts on wrong kind
            with pytest.raises(ValueError):
                parse_spec(bad)

    def test_drop_is_scoped_by_method_and_host(self, echo_server):
        configure_faults("rpc_drop@call=1:method=slow:host=h0")
        cli = RpcClient(echo_server.addr, timeout=5.0, peer_host="h0")
        other = RpcClient(echo_server.addr, timeout=5.0, peer_host="h1")
        try:
            cli.call("echo", {})               # method mismatch: untouched
            other.call("slow", {"s": 0.0})     # host mismatch: untouched
            with pytest.raises(RpcError):
                cli.call("slow", {"s": 0.0})   # claims the fault
            cli.call("slow", {"s": 0.0})       # budget spent
        finally:
            cli.close()
            other.close()

    def test_delay_plus_deadline_sheds_remotely(self, echo_server):
        """The caller's remaining budget rides the frame header: with a
        0.3s injected delay and a 0.1s deadline the CLIENT gives up at
        its deadline (transport timeout, never a longer wait) and the
        SERVER sheds the expired work instead of computing a result
        nobody will read (``rpc_deadline_sheds``)."""
        configure_faults("rpc_delay@call=1:secs=0.3:host=h0")
        cli = RpcClient(echo_server.addr, timeout=5.0, peer_host="h0")
        d0 = monitor.stat_get("rpc_deadline_sheds")
        t0 = time.monotonic()
        try:
            with pytest.raises(RpcError) as ei:
                cli.call("echo", {}, deadline_s=0.1)
            assert not isinstance(ei.value, RpcRemoteError)
            assert time.monotonic() - t0 < 0.3    # gave up AT the deadline
        finally:
            cli.close()
        assert _wait(lambda: monitor.stat_get("rpc_deadline_sheds") > d0,
                     timeout=5.0)

    def test_corrupt_blob_caught_by_crc(self, echo_server):
        configure_faults("rpc_corrupt@call=1:host=h0")
        cli = RpcClient(echo_server.addr, timeout=5.0, peer_host="h0")
        try:
            with pytest.raises(RpcRemoteError) as ei:
                cli.call("echo", {}, {"v": np.ones(16, np.float32)},
                         crc=True)
            assert ei.value.etype == "RpcCorruptFrame"
            res, arrs = cli.call("echo", {"n": 2},
                                 {"v": np.ones(4, np.float32)}, crc=True)
            assert np.array_equal(arrs["v"], np.ones(4, np.float32))
        finally:
            cli.close()

    def test_corrupt_header_is_torn_frame(self, echo_server):
        configure_faults("rpc_corrupt@call=1:host=h0")
        cli = RpcClient(echo_server.addr, timeout=2.0, peer_host="h0")
        try:
            with pytest.raises(RpcError) as ei:
                cli.call("echo", {})
            assert not isinstance(ei.value, RpcRemoteError)
            cli.call("echo", {})
        finally:
            cli.close()

    def test_net_partition_blocks_both_directions_then_heals(
            self, echo_server):
        configure_faults("net_partition@step=1:secs=0.25:hosts=router|h4")
        c_r4 = RpcClient(echo_server.addr, timeout=5.0, peer_host="h4",
                         local_host="router")
        c_4r = RpcClient(echo_server.addr, timeout=5.0, peer_host="router",
                         local_host="h4")
        c_other = RpcClient(echo_server.addr, timeout=5.0, peer_host="h5",
                            local_host="router")
        try:
            with pytest.raises(RpcError, match="partition"):
                c_r4.call("echo", {})
            with pytest.raises(RpcError, match="partition"):
                c_4r.call("echo", {})          # reverse direction too
            c_other.call("echo", {})           # unrelated pair untouched
            time.sleep(0.3)
            c_r4.call("echo", {})              # window expired: healed
        finally:
            c_r4.close()
            c_4r.close()
            c_other.close()

    def test_flag_unset_is_pinned_off_path(self, echo_server):
        """No faults configured: the call index is never bumped (the one
        integer check per call) and the wire header carries EXACTLY the
        ISSUE-19 keys — no deadline, no crc, no injection fields."""
        cli = RpcClient(echo_server.addr, timeout=5.0, peer_host="h0")
        try:
            cli.call("echo", {"x": 1})
            assert cli._call_idx == 0
        finally:
            cli.close()
        manifest, blob = encode_arrays({})
        frame = _pack_frame({"id": 1, "method": "echo",
                             "params": {"x": 1}, "blobs": manifest}, blob)
        header = json.loads(frame[16:16 + struct.unpack(
            "<IQ", frame[4:16])[0]])
        assert set(header) == {"id", "method", "params", "blobs"}


# ==========================================================================
# resumable chunked KV streaming (engine level)
# ==========================================================================

@pytest.fixture
def engine():
    engines = []

    def make(**kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("block_size", 8)
        kw.setdefault("prefill_chunk", 16)
        kw.setdefault("seed", 0)
        kw.setdefault("prefix_cache", True)
        kw.setdefault("n_blocks", 129)
        eng = InferenceEngine(CFG, PARAMS, **kw)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        try:
            eng.shutdown(drain=False, timeout=30)
        except Exception:  # noqa: BLE001
            pass


def _stream(src, dst, p, chunk_blocks=None, stop_after_tokens=None):
    """Drive export_kv_range -> import_kv_chunk until done (or until
    ``stop_after_tokens`` acked — the mid-transfer-death simulation).
    Returns (acked_tokens, chunks)."""
    ack, chunks = 0, 0
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        exp = src.export_kv_range(p, start_block=ack // 8,
                                  max_blocks=chunk_blocks)
        if exp["n_blocks"] > 0:
            got = dst.import_kv_chunk(p, exp["kb"], exp["vb"],
                                      exp["start_block"],
                                      exp["covered_tokens"])
            chunks += 1
            if got <= ack:
                break
            ack = got
            if stop_after_tokens is not None and ack >= stop_after_tokens:
                break
        if exp["done"] and ack >= exp["matched_len"]:
            break
        time.sleep(0.005)
    return ack, chunks


class TestChunkStreaming:
    def test_greedy_and_sampled_identity(self, engine):
        p = _prompt(41)
        src, dst, mono = engine(), engine(), engine()
        exp_greedy = mono.generate(p, max_new_tokens=12)
        src.warm_prefix(p).result(timeout=120)
        ack, chunks = _stream(src, dst, p)
        assert ack == 40 and chunks >= 1     # len-1 cap
        assert dst.generate(p, max_new_tokens=12) == exp_greedy
        # sampled identity on fresh engines (same rid space: first
        # submit each side)
        src2, dst2, mono2 = engine(), engine(), engine()
        exp_sampled = mono2.generate(p, max_new_tokens=12,
                                     temperature=0.8, top_k=7)
        src2.warm_prefix(p).result(timeout=120)
        _stream(src2, dst2, p)
        got = dst2.generate(p, max_new_tokens=12, temperature=0.8,
                            top_k=7)
        assert got == exp_sampled

    def test_resume_tail_identity_after_partial_stream(self, engine):
        """Only part of the prefix arrives (prefill host 'dies'): decode
        keeps the received blocks and its own prefill covers the tail —
        output still token-identical, greedy AND sampled."""
        p = _prompt(41)
        src, mono_g, mono_s = engine(), engine(), engine()
        # one oracle per mode: sampling keys fold in (seed, rid), so
        # every engine's generate must be its FIRST submit
        exp_greedy = mono_g.generate(p, max_new_tokens=12)
        exp_sampled = mono_s.generate(p, max_new_tokens=12,
                                      temperature=0.8, top_k=7)
        src.warm_prefix(p).result(timeout=120)
        dst_g, dst_s = engine(), engine()
        ack, _ = _stream(src, dst_g, p, chunk_blocks=2,
                         stop_after_tokens=16)
        assert 16 <= ack < 40                # genuinely partial
        assert dst_g.generate(p, max_new_tokens=12) == exp_greedy
        ack, _ = _stream(src, dst_s, p, chunk_blocks=2,
                         stop_after_tokens=16)
        assert 16 <= ack < 40
        got = dst_s.generate(p, max_new_tokens=12, temperature=0.8,
                             top_k=7)
        assert got == exp_sampled

    def test_export_visible_mid_prefill(self, engine):
        """The overlap contract: finished FULL blocks are exportable
        while the prefill is still computing later chunks (the radix
        insert only lands at completion, so this is the live-slot
        scan). ``slow_tick`` stretches each prefill tick so the
        mid-prefill window is deterministic, not a CPU-speed race."""
        p = _prompt(96)                      # 6 prefill chunks of 16
        src, dst, mono = engine(), engine(), engine()
        exp_greedy = mono.generate(p, max_new_tokens=10)
        configure_faults("slow_tick@step=1:secs=0.05:repeat=500")
        req = src.warm_prefix(p)
        partial = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            exp = src.export_kv_range(p, start_block=0)
            if exp["done"]:
                break                        # missed the window
            if exp["n_blocks"] > 0:
                partial = exp
                break
            time.sleep(0.003)
        assert partial is not None, "no mid-prefill export observed"
        assert not partial["done"]
        assert partial["covered_tokens"] % 8 == 0    # FULL blocks only
        assert 0 < partial["covered_tokens"] < 95
        got = dst.import_kv_chunk(p, partial["kb"], partial["vb"],
                                  partial["start_block"],
                                  partial["covered_tokens"])
        assert got == partial["covered_tokens"]
        configure_faults("")                 # let the prefill finish fast
        req.result(timeout=120)
        ack, _ = _stream(src, dst, p)        # tail, incl. partial block
        assert ack == 95
        assert dst.generate(p, max_new_tokens=10) == exp_greedy

    def test_out_of_order_chunk_rewinds_not_corrupts(self, engine):
        """A chunk whose start_block is past the receiver's high-water
        mark is dropped and the current mark returned — the sender's
        resume discipline."""
        p = _prompt(41)
        src, dst = engine(), engine()
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_range(p, start_block=2)   # skip ahead
        assert exp["n_blocks"] > 0
        have = dst.import_kv_chunk(p, exp["kb"], exp["vb"],
                                   exp["start_block"],
                                   exp["covered_tokens"])
        assert have == 0                     # gap: rewound, not spliced
        ack, _ = _stream(src, dst, p)        # clean restart from 0 works
        assert ack == 40

    def test_chunk_import_is_idempotent(self, engine):
        p = _prompt(33)
        src, dst = engine(), engine()
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_range(p, start_block=0)
        a1 = dst.import_kv_chunk(p, exp["kb"], exp["vb"], 0,
                                 exp["covered_tokens"])
        a2 = dst.import_kv_chunk(p, exp["kb"], exp["vb"], 0,
                                 exp["covered_tokens"])
        assert a2 >= a1 >= 32

    def test_chunk_geometry_validated(self, engine):
        p = _prompt(33)
        src, dst = engine(), engine()
        src.warm_prefix(p).result(timeout=120)
        exp = src.export_kv_range(p, start_block=0)
        with pytest.raises(ValueError):
            dst.import_kv_chunk(p, exp["kb"][:-1], exp["vb"][:-1], 0,
                                exp["covered_tokens"])


# ==========================================================================
# fleet-level: readyz distinction + flight collection
# ==========================================================================

def _factory():
    return InferenceEngine(CFG, PARAMS, n_slots=2,
                           block_size=8, prefill_chunk=16, seed=0,
                           prefix_cache=True, n_blocks=129)


@pytest.fixture
def fleet(tmp_path):
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
        except Exception:  # noqa: BLE001
            pass


class TestFleetStatus:
    def test_host_dead_vs_registry_unreachable(self, fleet):
        make, _ = fleet
        agents, router = make({"d0": "decode", "d1": "decode"})
        router.fleet_scan()
        members = router.fleet_members()
        assert members["registry"]["reachable"] is True
        assert all(v["status"] == "ok" for k, v in members.items()
                   if k != "registry")
        # host death: heartbeat goes stale while the registry answers
        agents["d1"].close(abrupt=True)
        assert _wait(lambda: any(
            v.get("status") == "dead"
            for v in router.fleet_members().values()), timeout=20.0)
        members = router.fleet_members()
        assert members["registry"]["reachable"] is True
        dead = {v["host"] for k, v in members.items()
                if k != "registry" and v["status"] == "dead"}
        assert dead == {"d1"}
        # registry partition: nothing is knowable — and hosts must NOT
        # be marked dead on no evidence
        orig = router.registry.alive
        router.registry.alive = lambda: (_ for _ in ()).throw(
            OSError("partition"))
        try:
            router.fleet_scan()
            members = router.fleet_members()
            assert members["registry"]["reachable"] is False
            assert members["registry"]["unreachable_for_s"] >= 0.0
            assert all(v["status"] == "unknowable"
                       for k, v in members.items() if k != "registry"
                       and v["host"] is not None)
        finally:
            router.registry.alive = orig
        router.fleet_scan()
        assert router.fleet_members()["registry"]["reachable"] is True


class TestFlightCollection:
    def test_collect_writes_per_host_dumps_and_records_gaps(
            self, fleet, tmp_path):
        make, _ = fleet
        agents, router = make({"d0": "decode", "d1": "decode"})
        trace_dir = str(tmp_path / "flight")
        arm_flight_recorder(trace_dir=trace_dir)
        try:
            res = router.collect_flight("unit_test", trace_dir=trace_dir)
            assert sorted(res["hosts"]) == ["d0", "d1"]
            assert res["gaps"] == []
            names = sorted(os.listdir(trace_dir))
            # local dump + one collected dump per host
            assert any("fleet_unit_test" in n for n in names)
            assert any(n.startswith("flight_d0_") for n in names)
            assert any(n.startswith("flight_d1_") for n in names)
            # collected dumps are valid flight files (merge_traces
            # needs traceEvents + flight.host)
            path = os.path.join(trace_dir, next(
                n for n in names if n.startswith("flight_d0_")))
            with open(path) as f:
                payload = json.load(f)
            assert payload["flight"]["host"] == "d0"
            assert any(e.get("name") == "process_name"
                       for e in payload["traceEvents"])
            # kill one host: its ring becomes a recorded gap, bounded
            agents["d1"].close(abrupt=True)
            t0 = time.monotonic()
            res = router.collect_flight("after_loss",
                                        trace_dir=trace_dir,
                                        timeout=1.0)
            assert time.monotonic() - t0 < 10.0    # never a hang
            assert res["hosts"] == ["d0"]
            assert res["gaps"] == ["d1"]
            assert monitor.stat_get("flight_collects") >= 2
        finally:
            disarm_flight_recorder()

    def test_unarmed_host_reports_honestly(self, fleet):
        make, _ = fleet
        agents, router = make({"d0": "decode"})
        disarm_flight_recorder()
        res = router.collect_flight("unarmed_probe")
        assert res["unarmed"] == ["d0"]
        assert res["gaps"] == []


# ==========================================================================
# GL012 fixtures
# ==========================================================================

class TestGL012:
    def test_known_bad_fixtures_fire(self):
        src = '''
import socket

def dial(addr):
    return socket.create_connection(addr)

def pump(addr):
    s = socket.socket()
    s.connect(addr)
    return s.recv(1024)

class Router:
    def probe(self):
        with self._lock:
            res, _ = self.client.call("health", {})
        return res

class Supervisor:
    def scan(self):
        with self._cv:
            return _recv_frame(self.sock)
'''
        fs = [f for f in lint_source(src) if f.rule == "GL012"]
        details = {f.detail for f in fs}
        assert "untimed:create_connection" in details
        assert "untimed:s.connect" in details and "untimed:s.recv" in details
        assert any(d.startswith("rpc_under_lock:_lock:call")
                   for d in details)
        assert any(d.startswith("rpc_under_lock:_cv:_recv_frame")
                   for d in details)

    def test_known_good_fixtures_clean(self):
        src = '''
import socket

def dial(addr):
    return socket.create_connection(addr, timeout=5.0)

def pump(addr):
    s = socket.socket()
    s.settimeout(5.0)
    s.connect(addr)
    return s.recv(1024)

class Router:
    def probe(self):
        with self._lock:
            client = self.client
        res, _ = client.call("health", {})
        return res
'''
        assert [f for f in lint_source(src) if f.rule == "GL012"] == []

    def test_shipped_serving_tree_clean(self):
        from paddle_tpu.analysis import run_lint

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fs = [f for f in run_lint(
            [os.path.join(root, "paddle_tpu", "serving")], root=root)
            if f.rule == "GL012"]
        assert fs == [], [f.format() for f in fs]
