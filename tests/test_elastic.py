"""Elastic manager (distributed/elastic.py + launch.elastic_launch) —
reference fleet/elastic/manager.py:103,176-225,247-292,317.

VERDICT r3 item 5: membership registry, scale-in/out within
[min_np, max_np], rank-map regeneration preserving survivors, and a
relaunch that resumes training from the latest checkpoint.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.distributed.elastic import (ElasticManager, ElasticStatus,
                                            FileKVStore)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFileKVStore:
    def test_put_get_delete_prefix(self, tmp_path):
        kv = FileKVStore(str(tmp_path / "kv"))
        kv.put("jobs/j/nodes/n0", b"a")
        kv.put("jobs/j/nodes/n1", "b")
        assert kv.get("jobs/j/nodes/n0") == b"a"
        assert kv.get("missing") is None
        got = kv.get_prefix("jobs/j/nodes")
        assert sorted(got) == ["jobs/j/nodes/n0", "jobs/j/nodes/n1"]
        kv.delete("jobs/j/nodes/n0")
        assert kv.get("jobs/j/nodes/n0") is None
        with pytest.raises(ValueError):
            kv.put("../escape", b"x")

    def test_bytes_roundtrip_and_missing(self, tmp_path):
        kv = FileKVStore(str(tmp_path / "kv"))
        payload = bytes(range(256)) * 3        # every byte value rides
        kv.put_bytes("blobs/b0", payload)
        assert kv.get_bytes("blobs/b0") == payload
        kv.put_bytes("blobs/empty", b"")
        assert kv.get_bytes("blobs/empty") == b""
        assert kv.get_bytes("blobs/missing") is None

    def test_bytes_size_guard(self, tmp_path):
        kv = FileKVStore(str(tmp_path / "kv"))
        with pytest.raises(ValueError, match="size guard"):
            kv.put_bytes("blobs/big", b"x" * 17, max_bytes=16)
        kv.put_bytes("blobs/ok", b"x" * 16, max_bytes=16)
        assert kv.get_bytes("blobs/ok") == b"x" * 16

    def test_bytes_corruption_detected(self, tmp_path):
        """A reader must never consume garbage: bit-flips, truncation
        and unframed text values all raise instead of returning."""
        kv = FileKVStore(str(tmp_path / "kv"))
        kv.put_bytes("blobs/b0", b"framed payload bytes")
        path = tmp_path / "kv" / "blobs" / "b0"
        raw = bytearray(path.read_bytes())
        raw[-1] ^= 0xFF                        # flip a payload byte
        path.write_bytes(bytes(raw))
        with pytest.raises(ValueError, match="checksum mismatch"):
            kv.get_bytes("blobs/b0")
        kv.put_bytes("blobs/b1", b"will be truncated mid-flush")
        p1 = tmp_path / "kv" / "blobs" / "b1"
        p1.write_bytes(p1.read_bytes()[:-5])   # torn write
        with pytest.raises(ValueError, match="torn frame"):
            kv.get_bytes("blobs/b1")
        kv.put("blobs/text", "plain text value")
        with pytest.raises(ValueError, match="bad magic"):
            kv.get_bytes("blobs/text")


class TestMembership:
    def test_alive_dead_and_ttl(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        mgr = ElasticManager(kv, "job", min_np=2, max_np=4,
                             heartbeat_ttl=0.3)
        for h in ("n0", "n1", "n2", "n3"):
            mgr.register(h)
        assert mgr.alive_hosts() == ["n0", "n1", "n2", "n3"]
        mgr.mark_dead("n3")
        assert mgr.alive_hosts() == ["n0", "n1", "n2"]
        ok, hosts = mgr.match()
        assert ok and hosts == ["n0", "n1", "n2"]
        # heartbeat expiry drops a silent node
        time.sleep(0.4)
        mgr.heartbeat("n0")
        mgr.heartbeat("n1")
        assert mgr.alive_hosts() == ["n0", "n1"]

    def test_quorum_bounds(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        mgr = ElasticManager(kv, "job", min_np=2, max_np=3)
        mgr.register("n0")
        ok, _ = mgr.match()
        assert not ok  # below min
        for h in ("n1", "n2", "n3"):
            mgr.register(h)
        ok, _ = mgr.match()
        assert not ok  # above max
        mgr.mark_dead("n3")
        ok, hosts = mgr.match()
        assert ok and len(hosts) == 3

    def test_rank_map_preserves_survivors(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        mgr = ElasticManager(kv, "job", min_np=2, max_np=4)
        first = mgr.rank_map(["n0", "n1", "n2", "n3"])
        assert sorted(first.values()) == [0, 1, 2, 3]
        # n1 dies: n0/n2/n3 keep their ranks when still in range, the
        # vacated rank is refilled
        prev = dict(first)
        second = mgr.rank_map(["n0", "n2", "n3"], prev)
        assert sorted(second.values()) == [0, 1, 2]
        assert second["n0"] == first["n0"]
        for h in ("n2", "n3"):
            if first[h] < 3:
                assert second[h] == first[h]
        # scale out: existing ranks stable, new host takes the free rank
        third = mgr.rank_map(["n0", "n2", "n3", "n9"], second)
        for h in ("n0", "n2", "n3"):
            assert third[h] == second[h]
        assert sorted(third.values()) == [0, 1, 2, 3]
        assert mgr.last_rank_map() == third


WORKER = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, {repo!r})
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from paddle_tpu.framework.checkpoint import CheckpointManager
    from paddle_tpu.distributed.elastic import ElasticManager, FileKVStore

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    nproc = int(os.environ["PADDLE_TRAINERS_NUM"])
    node = os.environ["PADDLE_ELASTIC_NODE"]
    kv = FileKVStore(os.environ["PADDLE_ELASTIC_KV_DIR"])
    mgr = ElasticManager(kv, os.environ["PADDLE_ELASTIC_JOB_ID"],
                         min_np=2, max_np=4)
    workdir = sys.argv[1]

    class Step:  # minimal train-step-like object CheckpointManager installs into
        def __init__(self):
            self.params = {{"w": jnp.zeros((2,), jnp.float32)}}
            self.opt_state = {{"count": jnp.zeros((), jnp.int32)}}
            self._step_count = 0

    step_obj = Step()
    ckpt = CheckpointManager(os.path.join(workdir, "ckpt"),
                             save_interval_steps=1, async_save=False)
    start = ckpt.restore_latest(step_obj) or 0

    # record this incarnation (world size + start step + rank)
    with open(os.path.join(workdir, f"trace_{{node}}.jsonl"), "a") as f:
        f.write(json.dumps({{"node": node, "rank": rank, "nproc": nproc,
                             "start": start}}) + "\\n")

    # a data-parallel step is a collective: every rank of a pod enters
    # step i together, so all have restored before rank 0 saves, and
    # rank 0 has saved step i - 1 before any rank finishes step i. The
    # workers here share no collective, so files in a directory of the
    # pod's own stand in for it.
    pod_dir = os.path.join(workdir, "barrier_" + "".join(
        c if c.isalnum() else "_"
        for c in os.environ["PADDLE_TRAINER_ENDPOINTS"]))
    os.makedirs(pod_dir, exist_ok=True)

    def barrier(step):
        open(os.path.join(pod_dir, f"{{step}}_{{rank}}"), "w").close()
        deadline = time.monotonic() + 60
        while not all(os.path.exists(os.path.join(pod_dir, f"{{step}}_{{r}}"))
                      for r in range(nproc)):
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"rank {{rank}}: the pod's other ranks did not reach "
                    f"step {{step}} within 60 s")
            time.sleep(0.02)

    poison = os.path.join(workdir, "poison_" + node)
    for i in range(start, 4):
        barrier(i)
        step_obj.params = {{"w": step_obj.params["w"] + 1.0}}
        step_obj._step_count = i
        if rank == 0:
            ckpt.save(i, step_obj)
            ckpt.wait_until_finished()
        if os.path.exists(poison) and i >= 1:
            mgr.mark_dead(node)   # permanent failure: scale me in
            sys.exit(17)
    ckpt.close()
    sys.exit(0)
""")


class TestElasticRelaunch:
    def test_kill_one_of_four_relaunch_np3_resume(self, tmp_path):
        """Worker n3 dies permanently at step>=1 of incarnation 0; the pod
        must relaunch with np=3 (ranks remapped onto survivors) and resume
        from the newest checkpoint, then complete."""
        from paddle_tpu.distributed.launch import elastic_launch

        workdir = str(tmp_path / "work")
        os.makedirs(workdir)
        script = str(tmp_path / "worker.py")
        with open(script, "w") as f:
            f.write(WORKER.format(repo=REPO))
        open(os.path.join(workdir, "poison_n3"), "w").close()

        kv_dir = str(tmp_path / "kv")
        code = elastic_launch([script, workdir], kv_dir=kv_dir,
                              job_id="t1", min_np=2, max_np=4,
                              initial_np=4, max_restarts=3,
                              quorum_timeout=30.0)
        assert code == 0

        kv = FileKVStore(kv_dir)
        mgr = ElasticManager(kv, "t1", min_np=2, max_np=4)
        assert mgr.completed()
        # final incarnation ran with np=3 and ranks 0..2 on survivors
        final_map = mgr.last_rank_map()
        assert sorted(final_map) == ["n0", "n1", "n2"]
        assert sorted(final_map.values()) == [0, 1, 2]

        # n3 saw exactly one incarnation (np=4); survivors saw two, the
        # second resuming from a checkpointed step > 0
        def trace(node):
            with open(os.path.join(workdir, f"trace_{node}.jsonl")) as f:
                return [json.loads(l) for l in f]

        assert len(trace("n3")) == 1 and trace("n3")[0]["nproc"] == 4
        for node in ("n0", "n1", "n2"):
            t = trace(node)
            assert [e["nproc"] for e in t] == [4, 3]
            assert t[0]["start"] == 0
            assert t[1]["start"] > 0, "did not resume from checkpoint"


SLOW_WORKER = WORKER.replace(
    "    for i in range(start, 4):",
    "    import time as _t\n    for i in range(start, 6):\n        _t.sleep(0.25)")


class TestElasticScaleOut:
    def test_external_node_joins_and_pod_grows(self, tmp_path):
        """A node registered externally mid-run scales the pod out at the
        next membership check (reference np watch, manager.py:205)."""
        import threading

        from paddle_tpu.distributed.launch import elastic_launch

        workdir = str(tmp_path / "work")
        os.makedirs(workdir)
        script = str(tmp_path / "worker.py")
        with open(script, "w") as f:
            f.write(SLOW_WORKER.format(repo=REPO))

        kv_dir = str(tmp_path / "kv")
        kv = FileKVStore(kv_dir)
        mgr = ElasticManager(kv, "t2", min_np=2, max_np=3,
                             heartbeat_ttl=30.0)

        def join_later():
            # wait for the first incarnation to be visibly running
            while not os.path.exists(os.path.join(workdir,
                                                  "trace_n0.jsonl")):
                time.sleep(0.1)
            time.sleep(0.3)
            mgr.register("n9")

        t = threading.Thread(target=join_later, daemon=True)
        t.start()
        code = elastic_launch([script, workdir], kv_dir=kv_dir,
                              job_id="t2", min_np=2, max_np=3,
                              initial_np=2, max_restarts=3,
                              quorum_timeout=30.0)
        t.join(timeout=5)
        assert code == 0
        final_map = ElasticManager(kv, "t2", 2, 3).last_rank_map()
        assert sorted(final_map) == ["n0", "n1", "n9"]
        with open(os.path.join(workdir, "trace_n0.jsonl")) as f:
            sizes = [json.loads(l)["nproc"] for l in f]
        assert sizes[0] == 2 and sizes[-1] == 3, sizes


class TestElasticRelaunchReuse:
    def test_reused_kv_dir_clears_tombstones(self, tmp_path):
        """A second launch with the same job_id must not inherit the first
        run's dead-marks or completion flag."""
        from paddle_tpu.distributed.launch import elastic_launch

        kv = FileKVStore(str(tmp_path / "kv"))
        mgr = ElasticManager(kv, "t3", min_np=2, max_np=4)
        mgr.mark_dead("n3")
        mgr.set_completed()

        workdir = str(tmp_path / "work")
        os.makedirs(workdir)
        script = str(tmp_path / "worker.py")
        with open(script, "w") as f:
            f.write(WORKER.format(repo=REPO))
        code = elastic_launch([script, workdir], kv_dir=str(tmp_path / "kv"),
                              job_id="t3", min_np=2, max_np=4,
                              initial_np=4, max_restarts=1,
                              quorum_timeout=30.0)
        assert code == 0
        final_map = ElasticManager(kv, "t3", 2, 4).last_rank_map()
        assert sorted(final_map) == ["n0", "n1", "n2", "n3"]
