"""Pod-level resilience (ISSUE 12): coordinated rollback agreement over
the elastic FileKVStore, async double-buffered snapshots, LR backoff,
elastic resize (replan + reshard + resume on host loss), the pod-level
fault specs (host_loss / kv_partition / serving_nan), checkpoint
retention GC, and the serving watchdog's NaN-sentinel auto-restart.

Multi-host runs are simulated in ONE process: threads for the 4-"host"
agreement protocol (each with its own guardian + PodCoordinator over a
shared tmpdir FileKVStore), and the 8-device virtual CPU mesh grouped
into 4 device-hosts for the resize path. True multi-PROCESS contention
is `-m pod` (also slow, outside the tier-1 budget).
"""
import multiprocessing
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.distributed.elastic import ElasticManager, FileKVStore
from paddle_tpu.jit import TrainStep
from paddle_tpu.resilience import configure_faults, faults
from paddle_tpu.resilience.guardian import TrainGuardian, TrainingAborted
from paddle_tpu.resilience.pod import PodAgreementError, PodCoordinator

HOSTS = ["h0", "h1", "h2", "h3"]


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    configure_faults("")
    paddle.set_flags({"FLAGS_fast_step": 1})


def _build_mlp(seed=0, sentinel_cfg=True):
    paddle.seed(seed)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                               paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())

    def loss_fn(run_model, x, y):
        return paddle.nn.functional.cross_entropy(run_model(x), y)

    return net, TrainStep(net, loss_fn, opt, sentinel=sentinel_cfg)


def _mlp_batch(i, poison=False, n=16):
    rng = np.random.default_rng(100 + i)
    x = rng.normal(size=(n, 8)).astype("float32")
    if poison:
        x = x * np.float32("nan")
    y = rng.integers(0, 4, (n,)).astype("int64")
    return paddle.to_tensor(x), paddle.to_tensor(y)


def _params_np(net):
    return {k: np.asarray(p._data).copy() for k, p in net.named_parameters()}


# ---------------------------------------------------------------------------
# fault-spec surface
# ---------------------------------------------------------------------------
class TestPodFaultSpecs:
    def test_parse_pod_kinds(self):
        specs = faults.parse_spec(
            "host_loss@step=40:host=h2, kv_partition@step=10:secs=0.5,"
            "serving_nan@step=3")
        assert [s.kind for s in specs] == ["host_loss", "kv_partition",
                                          "serving_nan"]
        assert specs[0].host == "h2"
        assert specs[1].secs == 0.5

    def test_host_loss_requires_host(self):
        with pytest.raises(ValueError, match="host"):
            faults.parse_spec("host_loss@step=5")

    def test_request_keyed_faults_have_own_index_space(self):
        """A serving_nan budget must not be consumed by train-step
        indices, and vice versa."""
        reg = faults.FaultRegistry()
        reg.configure("serving_nan@step=2,nan_grad@step=2")
        # train-step hook walks steps 0..5: nan_grad fires, serving_nan
        # budget untouched
        fired = [reg.take("nan_grad", i) is not None for i in range(6)]
        assert fired == [False, False, True, False, False, False]
        assert reg.take("serving_nan", 1) is None
        assert reg.take_request("serving_nan", 1) is None   # rid 1 < 2
        assert reg.take_request("serving_nan", 2) is not None
        assert reg.take_request("serving_nan", 3) is None   # budget spent
        reg.configure("")

    def test_kv_partition_window_closes_with_flag(self):
        configure_faults("kv_partition@step=0:secs=30")
        faults.begin_kv_partition(30)
        assert faults.kv_partition_active()
        configure_faults("")     # clearing the flag closes the window
        assert not faults.kv_partition_active()


# ---------------------------------------------------------------------------
# FileKVStore under concurrent writers + the agreement protocol
# ---------------------------------------------------------------------------
class TestKVContention:
    def test_concurrent_writers_last_value_wins_no_torn_reads(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        errors = []

        def writer(i):
            try:
                for r in range(40):
                    kv.put(f"jobs/j/nodes/h{i}", f"{i}:{r}".encode())
                    kv.put("jobs/j/shared", f"{i}:{r}".encode())
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            try:
                for _ in range(80):
                    vals = kv.get_prefix("jobs/j/nodes")
                    for v in vals.values():
                        # atomic rename => never a torn/partial value
                        i, r = v.decode().split(":")
                        int(i), int(r)
                    s = kv.get("jobs/j/shared")
                    if s is not None:
                        int(s.decode().split(":")[1])
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
        ts += [threading.Thread(target=reader) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert errors == []
        for i in range(4):
            assert kv.get(f"jobs/j/nodes/h{i}") == f"{i}:39".encode()
        # no tmp leftovers from the contention
        leftovers = [n for _, _, fs in os.walk(str(tmp_path))
                     for n in fs if ".tmp." in n]
        assert leftovers == []

    def test_four_host_propose_commit_contention(self, tmp_path):
        """All four coordinators racing the SAME round converge on one
        committed step (the highest step every proposal holds)."""
        kv = FileKVStore(str(tmp_path))
        pods = [PodCoordinator(kv, "job", h, HOSTS, timeout=20.0)
                for h in HOSTS]
        held = {0: [4, 10], 1: [4, 10], 2: [2, 4, 10], 3: [2, 4]}
        results = {}

        def run(i):
            results[i] = pods[i].agree_rollback(held[i])

        ts = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert set(results.values()) == {4}   # 10 missing from h3's set

    def test_laggard_adopts_existing_commit(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        pods = [PodCoordinator(kv, "job", h, HOSTS, timeout=20.0)
                for h in HOSTS]
        results = {}

        def run(i, delay):
            time.sleep(delay)
            results[i] = pods[i].agree_rollback([6, 8])

        ts = [threading.Thread(target=run, args=(i, 0.0)) for i in range(3)]
        ts.append(threading.Thread(target=run, args=(3, 0.3)))  # laggard
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert set(results.values()) == {8}

    def test_no_common_step_raises(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        pods = [PodCoordinator(kv, "job", h, HOSTS, timeout=20.0)
                for h in HOSTS]
        errs = {}

        def run(i):
            try:
                pods[i].agree_rollback([i])   # disjoint snapshot sets
            except PodAgreementError as e:
                errs[i] = e

        ts = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(errs) == 4

    def test_timeout_when_pod_incomplete(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        lone = PodCoordinator(kv, "job", "h0", HOSTS, timeout=0.4,
                              poll=0.02)
        with pytest.raises(PodAgreementError, match="no commit"):
            lone.agree_rollback([5])


@pytest.mark.pod
@pytest.mark.slow
class TestKVContentionMultiProcess:
    """True multi-PROCESS propose/commit over a shared directory —
    the deployment shape (one agent per real host). Outside tier-1."""

    @staticmethod
    def _agent(root, host, out_q):
        from paddle_tpu.distributed.elastic import FileKVStore
        from paddle_tpu.resilience.pod import PodCoordinator

        kv = FileKVStore(root)
        pod = PodCoordinator(kv, "job", host, ["h0", "h1", "h2", "h3"],
                             timeout=30.0)
        out_q.put((host, pod.agree_rollback([3, 9])))

    def test_four_process_agreement(self, tmp_path):
        ctx = multiprocessing.get_context("spawn")
        q = ctx.Queue()
        procs = [ctx.Process(target=self._agent,
                             args=(str(tmp_path), h, q)) for h in HOSTS]
        for p in procs:
            p.start()
        got = dict(q.get(timeout=120) for _ in range(4))
        for p in procs:
            p.join(timeout=30)
        assert set(got.values()) == {9}


# ---------------------------------------------------------------------------
# coordinated rollback on a simulated 4-host pod
# ---------------------------------------------------------------------------
class TestCoordinatedRollback:
    def _run_pod(self, tmp_path, n_steps=8, laggard_drops=None):
        kv = FileKVStore(str(tmp_path / "kv"))
        guards, nets, committed = [], [], {}
        for h in HOSTS:
            pod = PodCoordinator(kv, "job", h, HOSTS, timeout=30.0)
            net, step = _build_mlp(0)     # replicas: same init everywhere
            g = TrainGuardian(step, snapshot_every=2, skip_limit=0,
                              max_rollbacks=2, keep_snapshots=2, pod=pod)
            guards.append(g)
            nets.append(net)

        def drive(j):
            g = guards[j]
            i, n_rb = 0, 0
            while i < n_steps:
                loss = g.step(*_mlp_batch(i, poison=(i == 5 and n_rb == 0)))
                if i == 5 and n_rb == 0 and laggard_drops and j == 3:
                    # the laggard's newest snapshot never landed
                    for s in laggard_drops:
                        g._snaps.pop(s, None)
                a = g.after_step(i, loss)
                if a == "rollback":
                    n_rb += 1
                    committed[j] = g.resume_step - 1
                    i = g.resume_step
                    continue
                i += 1

        ts = [threading.Thread(target=drive, args=(j,)) for j in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for g in guards:
            g.close()
        return nets, committed

    def test_pod_agrees_one_step_and_replay_is_bit_exact(self, tmp_path):
        n_steps = 8
        netc, stepc = _build_mlp(0)
        for i in range(n_steps):
            float(stepc(*_mlp_batch(i)))
        clean = _params_np(netc)

        nets, committed = self._run_pod(tmp_path)
        # every host rolled back to the SAME committed step
        assert len(set(committed.values())) == 1
        assert set(committed) == {0, 1, 2, 3}
        for j, net in enumerate(nets):
            got = _params_np(net)
            for k in clean:
                np.testing.assert_array_equal(got[k], clean[k],
                                              err_msg=f"host{j}:{k}")

    def test_laggard_host_adopts_committed_step(self, tmp_path):
        """h3 lost its newest snapshot (step 4); the pod must commit the
        OLDER step every host still holds (2) — and the replay from
        there is still bit-exact vs the fault-free run."""
        n_steps = 8
        netc, stepc = _build_mlp(0)
        for i in range(n_steps):
            float(stepc(*_mlp_batch(i)))
        clean = _params_np(netc)

        nets, committed = self._run_pod(tmp_path, laggard_drops=[4])
        assert set(committed.values()) == {2}
        for j, net in enumerate(nets):
            got = _params_np(net)
            for k in clean:
                np.testing.assert_array_equal(got[k], clean[k],
                                              err_msg=f"host{j}:{k}")


# ---------------------------------------------------------------------------
# async double-buffered snapshots + LR backoff
# ---------------------------------------------------------------------------
class TestAsyncSnapshots:
    def test_async_matches_sync_and_keeps_syncs_flat(self, tmp_path):
        n = 8
        net1, s1 = _build_mlp(0)
        g1 = TrainGuardian(s1, snapshot_every=2)
        for i in range(n):
            g1.after_step(i, s1(*_mlp_batch(i)))
        g1.close()

        d = str(tmp_path / "ck")
        net2, s2 = _build_mlp(0)
        g2 = TrainGuardian(s2, ckpt_dir=d, snapshot_every=2,
                           async_snapshot=True, save_interval_steps=2)
        monitor.start_tracing()
        mark = monitor.stat_get("step_async_syncs")
        for i in range(n):
            g2.after_step(i, s2(*_mlp_batch(i)))
        # the snapshot thread reads host arrays, never the AsyncLoss
        assert monitor.stat_get("step_async_syncs") == mark
        g2.drain_snapshots()
        writer = monitor.stop_tracing()
        spans = [e for e in writer.events()
                 if e.get("name") == "resilience.snapshot_async"]
        assert spans, "no snapshot_async spans emitted"
        writer.clear()
        # background disk checkpoints landed and are restorable
        saved = sorted(int(x) for x in os.listdir(d) if x.isdigit())
        assert saved, "no async checkpoints on disk"
        g2.close()
        # trajectory identical to the synchronous guardian
        p1, p2 = _params_np(net1), _params_np(net2)
        for k in p1:
            np.testing.assert_array_equal(p1[k], p2[k], err_msg=k)

    def test_async_checkpoint_restorable_after_crash(self, tmp_path):
        d = str(tmp_path / "ck")
        net, s = _build_mlp(0)
        g = TrainGuardian(s, ckpt_dir=d, snapshot_every=2,
                          async_snapshot=True, save_interval_steps=2)
        for i in range(6):
            g.after_step(i, s(*_mlp_batch(i)))
        g.drain_snapshots()
        g.close()
        net2, s2 = _build_mlp(1)   # different init — must be overwritten
        g2 = TrainGuardian(s2, ckpt_dir=d, snapshot_every=2)
        start = g2.restore_latest()
        assert start is not None and start >= 1
        g2.close()

    def test_rollback_applies_lr_backoff(self):
        net, s = _build_mlp(0)
        g = TrainGuardian(s, snapshot_every=1, skip_limit=0,
                          max_rollbacks=4, lr_backoff=0.5)
        configure_faults("nan_grad@step=3:repeat=1,nan_grad@step=6:repeat=1")
        i = 0
        while i < 9:
            loss = s(*_mlp_batch(i))
            a = g.after_step(i, loss)
            if a == "rollback":
                i = g.resume_step
                continue
            i += 1
        # two rollbacks -> cumulative 0.25 on the step's lr multiplier
        assert g._lr_scale == 0.25
        assert s._lr_scale == 0.25
        g.close()

    def test_default_backoff_keeps_replay_bit_exact(self):
        """lr_backoff=1.0 (default): the rollback replay still matches a
        fault-free run exactly — the PR-5 pin survives the ring/backoff
        refactor."""
        n_steps = 8
        netc, stepc = _build_mlp(0)
        for i in range(n_steps):
            float(stepc(*_mlp_batch(i)))
        clean = _params_np(netc)
        net, s = _build_mlp(0)
        g = TrainGuardian(s, snapshot_every=2, skip_limit=0, max_rollbacks=2)
        configure_faults("nan_grad@step=5:repeat=1")
        i = 0
        while i < n_steps:
            loss = s(*_mlp_batch(i))
            a = g.after_step(i, loss)
            if a == "rollback":
                i = g.resume_step
                continue
            i += 1
        g.close()
        got = _params_np(net)
        for k in clean:
            np.testing.assert_array_equal(got[k], clean[k], err_msg=k)


# ---------------------------------------------------------------------------
# elastic resize on the 8-device virtual mesh
# ---------------------------------------------------------------------------
class TestElasticResize:
    def _setup(self, tmp_path, rebuild=None, hosts_alive=True):
        import jax

        from paddle_tpu.parallel import DistributedTrainStep, create_mesh

        devs = jax.devices()
        assert len(devs) == 8
        template = {"w": np.ones((8, 4), np.float32) * 0.1}
        from jax.sharding import PartitionSpec as P
        specs = {"w": P()}
        import jax.numpy as jnp

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        kv = FileKVStore(str(tmp_path / "kv"))
        pod = PodCoordinator(
            kv, "job", "h0", ["h0"],
            device_map={"h0": devs[0:2], "h1": devs[2:4],
                        "h2": devs[4:6], "h3": devs[6:8]}, timeout=20.0)
        mesh = create_mesh(dp=8, devices=devs)
        step = DistributedTrainStep(loss_fn, template, specs,
                                    optimizer="sgd", lr=0.1, mesh=mesh,
                                    sentinel=True)
        return template, specs, loss_fn, pod, step

    @staticmethod
    def _batch(i):
        rng = np.random.default_rng(7 + i)
        return (rng.normal(size=(24, 8)).astype(np.float32),
                rng.normal(size=(24, 4)).astype(np.float32))

    def test_host_loss_triggers_replan_reshard_resume(self, tmp_path):
        import jax.numpy as jnp

        from paddle_tpu.distributed.fleet.auto import replan_for_devices
        from paddle_tpu.parallel import (DistributedTrainStep, create_mesh,
                                         set_mesh)

        try:
            template, specs, loss_fn, pod, step = self._setup(tmp_path)
            plans = []

            def rebuild(devices):
                plan, mesh = replan_for_devices(devices, global_batch=24,
                                                params=template)
                plans.append((len(devices), plan))
                return DistributedTrainStep(loss_fn, template, specs,
                                            optimizer="sgd", lr=0.1,
                                            mesh=mesh, sentinel=True,
                                            zero=plan.zero)

            g = TrainGuardian(step, snapshot_every=2, keep_snapshots=2,
                              pod=pod, rebuild=rebuild)
            rz0 = monitor.stat_get("elastic_resizes")
            configure_faults("host_loss@step=4:host=h2")
            losses, actions = {}, []
            i = 0
            while i < 10:
                loss = g.step(self._batch(i))
                a = g.after_step(i, loss)
                actions.append((i, a))
                if a in ("rollback", "resize"):
                    i = g.resume_step
                    continue
                losses[i] = float(loss)
                i += 1
            final_w = np.asarray(g.step.params["w"]).copy()
            g.close()
            configure_faults("")
            assert ("resize" in [a for _, a in actions])
            assert monitor.stat_get("elastic_resizes") - rz0 == 1
            # the replan saw exactly the 6 surviving devices
            assert plans and plans[0][0] == 6
            dims = plans[0][1].mesh_dims
            assert (dims["data"] * dims["sharding"] * dims["pipe"]
                    * dims["model"]) == 6
            # the lost host left the pod's watch set — no resize loop
            assert "h2" not in pod.device_map

            # reference: fault-free 8-device run; the resumed trajectory
            # (restored from the same snapshot under the new plan) must
            # match it — replicated SPMD math is mesh-width independent
            set_mesh(None)
            mesh2 = create_mesh(dp=8)
            step2 = DistributedTrainStep(loss_fn, template, specs,
                                         optimizer="sgd", lr=0.1,
                                         mesh=mesh2, sentinel=True)
            g2 = TrainGuardian(step2, snapshot_every=2, keep_snapshots=2)
            ref = {}
            for i in range(10):
                loss = g2.step(self._batch(i))
                g2.after_step(i, loss)
                ref[i] = float(loss)
            ref_w = np.asarray(g2.step.params["w"]).copy()
            g2.close()
            np.testing.assert_allclose(final_w, ref_w, rtol=1e-6,
                                       atol=1e-7)
            for k in losses:
                assert abs(losses[k] - ref[k]) < 1e-6, (k, losses[k],
                                                        ref[k])
        finally:
            from paddle_tpu.parallel import set_mesh
            set_mesh(None)

    def test_host_loss_without_rebuild_aborts(self, tmp_path):
        from paddle_tpu.parallel import set_mesh

        try:
            template, specs, loss_fn, pod, step = self._setup(tmp_path)
            g = TrainGuardian(step, snapshot_every=1, pod=pod)
            configure_faults("host_loss@step=2:host=h1")
            with pytest.raises(TrainingAborted, match="no rebuild"):
                for i in range(5):
                    loss = g.step(self._batch(i))
                    g.after_step(i, loss)
            g.close()
        finally:
            set_mesh(None)

    def test_kv_partition_does_not_kill_the_pod(self, tmp_path):
        """A transient store partition: liveness is unknowable (no hosts
        reported lost), heartbeats ride the put retry budget, and the
        host re-registers cleanly after the window."""
        kv = FileKVStore(str(tmp_path / "kv"))
        em = ElasticManager(kv, "job", min_np=1, heartbeat_ttl=5.0)
        pod = PodCoordinator(kv, "job", "h0", ["h0"], elastic=em,
                             device_map={"h0": [0], "h1": [1]})
        em.register("h0")
        em.register("h1")
        assert pod.lost_hosts() == []
        configure_faults("kv_partition@step=3:secs=0.05")
        assert pod.lost_hosts(2) == []       # before the window
        lost = pod.lost_hosts(3)             # fault fires -> window opens
        assert lost == []                    # partition => unknowable
        time.sleep(0.08)                     # window closes
        em.heartbeat("h0")                   # re-register succeeds
        assert "h0" in em.alive_hosts()
        assert pod.lost_hosts() == []
        configure_faults("")


# ---------------------------------------------------------------------------
# elastic manager hardening (satellite)
# ---------------------------------------------------------------------------
class TestElasticAges:
    def test_last_seen_age_and_gauge(self, tmp_path):
        kv = FileKVStore(str(tmp_path))
        em = ElasticManager(kv, "j", min_np=1, heartbeat_ttl=5.0)
        assert em.last_seen_age("nope") is None
        em.register("a")
        em.register("b")
        assert em.alive_hosts() == ["a", "b"]
        assert monitor.stat_get("pod_hosts_alive") == 2
        ages = em.host_ages()
        assert set(ages) == {"a", "b"}
        assert all(0 <= v < 1.0 for v in ages.values())

    def test_reregister_after_partition_not_stale(self, tmp_path):
        """A host whose record vanished (partition wiped the lease) and
        then re-registered with an IDENTICAL payload must be alive —
        the stale bookkeeping row is pruned, not double-counted."""
        import json

        kv = FileKVStore(str(tmp_path))
        em = ElasticManager(kv, "j", min_np=1, heartbeat_ttl=0.1)
        rec = json.dumps({"host": "a", "status": "alive", "ts": 123.0})
        kv.put("jobs/j/nodes/a", rec)
        assert em.alive_hosts() == ["a"]
        time.sleep(0.15)
        assert em.alive_hosts() == []        # same payload, ttl elapsed
        kv.delete("jobs/j/nodes/a")          # the partition wiped it
        assert em.alive_hosts() == []        # prunes the bookkeeping row
        kv.put("jobs/j/nodes/a", rec)        # re-register, SAME payload
        assert em.alive_hosts() == ["a"]     # fresh observation, alive
        assert monitor.stat_get("pod_hosts_alive") == 1


# ---------------------------------------------------------------------------
# checkpoint retention GC (satellite)
# ---------------------------------------------------------------------------
class TestCheckpointGC:
    class _Obj:
        def __init__(self, val):
            import jax.numpy as jnp

            self.params = {"w": jnp.full((4,), float(val))}
            self.opt_state = {"count": jnp.zeros((), "int32")}
            self._step_count = 0

    def test_keep_last_bounds_step_dirs(self, tmp_path):
        from paddle_tpu.framework.checkpoint import CheckpointManager

        d = str(tmp_path / "ck")
        mgr = CheckpointManager(d, save_interval_steps=1, max_to_keep=None,
                                async_save=False, keep_last=2)
        for s in range(5):
            mgr.save(s, self._Obj(s))
        dirs = sorted(n for n in os.listdir(d) if n.isdigit())
        assert dirs == ["3", "4"]
        mgr.close()

    def test_gc_sweeps_corrupt_and_tmp_leftovers(self, tmp_path):
        from paddle_tpu.framework.checkpoint import CheckpointManager

        d = str(tmp_path / "ck")
        os.makedirs(d)
        # debris a crash mid-save would leave behind
        os.makedirs(os.path.join(d, "latest.tmp-123-456"))
        os.makedirs(os.path.join(d, "0"))
        with open(os.path.join(d, "0", "junk"), "wb") as f:
            f.write(b"garbage")
        mgr = CheckpointManager(d, save_interval_steps=1, max_to_keep=None,
                                async_save=False, keep_last=2)
        for s in range(1, 4):
            mgr.save(s, self._Obj(s))
        names = sorted(os.listdir(d))
        assert "latest.tmp-123-456" not in names
        assert "0" not in names              # old corrupt dir swept
        assert {"2", "3"} <= set(names)
        mgr.close()

    def test_corrupt_newest_still_skipped_after_gc(self, tmp_path):
        from paddle_tpu.framework.checkpoint import CheckpointManager

        d = str(tmp_path / "ck")
        mgr = CheckpointManager(d, save_interval_steps=1, max_to_keep=None,
                                async_save=False, keep_last=2)
        for s in range(4):
            mgr.save(s, self._Obj(s))
        for root, _, files in os.walk(os.path.join(d, "3")):
            for f in files:
                with open(os.path.join(root, f), "wb") as fh:
                    fh.write(b"garbage")
        obj = self._Obj(0.0)
        with pytest.warns(UserWarning, match="skipping unreadable"):
            start = mgr.restore_latest(obj)
        assert start == 3                    # fell back to intact step 2
        np.testing.assert_allclose(np.asarray(obj.params["w"]), 2.0)
        mgr.close()


# ---------------------------------------------------------------------------
# serving watchdog
# ---------------------------------------------------------------------------
class TestServingWatchdog:
    # nano-scale target + class-cached watchdog-OFF baselines: the
    # token-identity pins need the SAME params everywhere, not a big
    # model, and each engine build costs a fresh set of jit traces
    _baseline_outs = None

    @classmethod
    def _cfg_params(cls):
        import jax.numpy as jnp

        from paddle_tpu.models import gpt_init, gpt_nano

        if not hasattr(cls, "_cached"):
            cfg = gpt_nano(seq_len=64, param_dtype=jnp.float32)
            cls._cached = (cfg, gpt_init(cfg, seed=0))
        return cls._cached

    def _run(self, watchdog, nan_rid=None, n_new=10):
        from paddle_tpu.serving.engine import InferenceEngine

        cfg, params = self._cfg_params()
        configure_faults(f"serving_nan@step={nan_rid}"
                         if nan_rid is not None else "")
        eng = InferenceEngine(cfg, params, n_slots=4, watchdog=watchdog)
        prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14],
                   [3, 1, 4, 1, 5]]
        reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        outs = []
        for r in reqs:
            try:
                outs.append(r.result(timeout=180))
            except RuntimeError:
                outs.append(("FAILED", r.finish_reason))
        eng.shutdown()
        configure_faults("")
        return outs

    def _baseline(self):
        cls = type(self)
        if cls._baseline_outs is None:
            cls._baseline_outs = self._run(None)
        return cls._baseline_outs

    @pytest.mark.parametrize("nan_rid", [1, 2])
    def test_restart_token_identical(self, nan_rid):
        base = self._baseline()
        trips0 = monitor.stat_get("serving_watchdog_trips")
        rest0 = monitor.stat_get("serving_watchdog_restarts")
        wd = self._run(True, nan_rid=nan_rid)
        assert wd[nan_rid] == ("FAILED", "watchdog")
        for i in set(range(4)) - {nan_rid}:
            assert wd[i] == base[i], i
        assert monitor.stat_get("serving_watchdog_trips") - trips0 >= 1
        assert monitor.stat_get("serving_watchdog_restarts") - rest0 == 1

    def test_watchdog_off_is_inert(self):
        """Watchdog off: no health output, no restart, gauges flat —
        a poisoned slot simply streams garbage (the historical
        behavior), pinning that all new behavior is opt-in."""
        trips0 = monitor.stat_get("serving_watchdog_trips")
        rest0 = monitor.stat_get("serving_watchdog_restarts")
        outs = self._run(None, nan_rid=1)
        assert all(not (isinstance(o, tuple) and o[0] == "FAILED")
                   for o in outs)
        assert monitor.stat_get("serving_watchdog_trips") == trips0
        assert monitor.stat_get("serving_watchdog_restarts") == rest0

    def test_watchdog_composes_with_draft(self):
        # PR 12 rejected this combination; ISSUE 14 made the verify
        # program carry the per-slot health verdict, so it now builds
        # (full compose coverage lives in test_serving_lifecycle.py)
        from paddle_tpu.serving.engine import InferenceEngine

        cfg, params = self._cfg_params()
        eng = InferenceEngine(cfg, params, watchdog=True,
                              draft=(cfg, params))
        try:
            assert eng._watchdog is not None and eng.draft is not None
        finally:
            eng.shutdown(drain=False, timeout=30)

    def test_unknown_watchdog_option_rejected(self):
        from paddle_tpu.serving.engine import InferenceEngine

        cfg, params = self._cfg_params()
        with pytest.raises(ValueError, match="unknown watchdog"):
            InferenceEngine(cfg, params, watchdog={"bogus": 1})

    def test_latency_sentinel_counts_stalls(self):
        from paddle_tpu.serving.engine import InferenceEngine

        cfg, params = self._cfg_params()
        eng = InferenceEngine(
            cfg, params, n_slots=2,
            watchdog={"latency_budget_ms": 0.0001, "latency_trips": 2})
        trips0 = monitor.stat_get("serving_watchdog_trips")
        req = eng.submit([1, 2, 3], max_new_tokens=8)
        req.result(timeout=180)
        eng.shutdown()
        # every CPU tick blows a 0.1us budget: >= 8 ticks / 2 per trip
        assert monitor.stat_get("serving_watchdog_trips") - trips0 >= 2

    def test_restart_budget_exhaustion_fails_open_requests(self):
        from paddle_tpu.serving.engine import InferenceEngine, WatchdogTripped

        cfg, params = self._cfg_params()
        # two sequentially-poisoned requests against a one-restart budget
        configure_faults("serving_nan@step=0:repeat=2")
        eng = InferenceEngine(cfg, params, n_slots=2,
                              watchdog={"max_restarts": 1})
        r0 = eng.submit([1, 2, 3], max_new_tokens=6)
        with pytest.raises(RuntimeError) as ei:
            r0.result(timeout=180)           # restart 1: r0 fails alone
        assert r0.finish_reason == "watchdog"
        assert isinstance(ei.value.__cause__, WatchdogTripped)
        r1 = eng.submit([4, 5, 6], max_new_tokens=6)
        with pytest.raises(RuntimeError):
            r1.result(timeout=180)           # restart 2 > budget: abort
        # the engine died loudly: further submits fail fast with the cause
        with pytest.raises(RuntimeError, match="watchdog|crashed"):
            eng.submit([7, 8], max_new_tokens=2)
        eng.shutdown()
        configure_faults("")


# ---------------------------------------------------------------------------
# pod timeline in the trace report
# ---------------------------------------------------------------------------
class TestPodTimelineReport:
    def test_report_merges_per_host_events(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import trace_report

        kv = FileKVStore(str(tmp_path / "kv"))
        monitor.start_tracing()
        guards = []
        for h in HOSTS:
            pod = PodCoordinator(kv, "job", h, HOSTS, timeout=30.0)
            _, step = _build_mlp(0)
            guards.append(TrainGuardian(step, snapshot_every=2,
                                        skip_limit=0, max_rollbacks=2,
                                        keep_snapshots=2, pod=pod))

        def drive(j):
            g = guards[j]
            i, n_rb = 0, 0
            while i < 6:
                loss = g.step(*_mlp_batch(i, poison=(i == 3 and n_rb == 0)))
                a = g.after_step(i, loss)
                if a == "rollback":
                    n_rb += 1
                    i = g.resume_step
                    continue
                i += 1

        ts = [threading.Thread(target=drive, args=(j,)) for j in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for g in guards:
            g.close()
        writer = monitor.stop_tracing()
        events = writer.events()
        rows = trace_report.aggregate(events)
        out = trace_report.resilience_report(
            events, rows, gauges=monitor.stat_snapshot())
        assert "pod" in out
        assert out["pod"]["hosts"] == HOSTS
        for h in HOSTS:
            assert out["pod"]["per_host"][h].get("rollback", 0) == 1
            assert out["pod"]["per_host"][h].get("snapshot", 0) >= 1
        assert "no resize" in out["pod"]["resize_verdict"]
        rb_rows = [r for r in out["pod"]["timeline"]
                   if r["event"] == "rollback"]
        assert len(rb_rows) == 4
        assert len({r["to_step"] for r in rb_rows}) == 1
        writer.clear()

    def test_resize_verdict(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import trace_report

        events = [
            {"name": "resilience.snapshot", "ph": "X", "ts": 5, "dur": 2,
             "args": {"step": 2, "host": "h0"}},
            {"name": "resilience.resize", "ph": "X", "ts": 10, "dur": 5,
             "args": {"step": 2, "lost": ["h2"], "devices": 6,
                      "host": "h0"}},
        ]
        out = trace_report.resilience_report(events, [])
        assert "resized: lost ['h2']" in out["pod"]["resize_verdict"]
