"""Distributed launcher — ``python -m paddle_tpu.distributed.launch``.

Parity: reference fleet launcher (python/paddle/distributed/fleet/
launch.py:250 launch_collective — builds a Cluster/Pod, spawns one worker
process per device with PADDLE_* env, watches children, aborts the pod on
failure) and the elastic relaunch loop (fleet/elastic/manager.py:103).

TPU-native process model: ONE worker process per HOST drives all local
chips (the reference's one-proc-per-GPU maps to jax's one-proc-per-host);
``--nproc_per_node`` exists for CPU rehearsal and multi-host emulation.
A chip belongs to one process at a time, so on a host with TPU chips more
than one worker is refused unless the workers are pinned off the TPU
(``JAX_PLATFORMS=cpu``). The launcher itself never touches jax: a parent
that initialized a backend would hold the chips its worker needs.
Workers get the jax.distributed coordinator env (the TCP bootstrap that
replaces the reference's gen_comm_id_helper NCCL-id rendezvous) plus the
PADDLE_* variables reference role-makers read. ``--elastic`` enables
supervised restarts: a failed worker pod is relaunched up to
``--max_restarts`` times, picking up from the newest checkpoint (see
framework/checkpoint.py CheckpointManager.restore_latest).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "elastic_launch", "main", "get_cluster_env", "wait_pod"]


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_cluster_env(rank: int, nproc: int, coordinator: str,
                    endpoints: List[str]) -> dict:
    """Env block for one worker (reference launch_utils.py pod env)."""
    env = dict(os.environ)
    env.update({
        # reference PaddleCloudRoleMaker reads these (role_maker.py:692)
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nproc),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        # jax.distributed bootstrap (replaces NCCL-id TCP rendezvous)
        "JAX_COORDINATOR_ADDRESS": coordinator,
        "JAX_NUM_PROCESSES": str(nproc),
        "JAX_PROCESS_ID": str(rank),
    })
    return env


class Pod:
    """Local worker group (reference launch_utils.py:144 Pod)."""

    def __init__(self, procs: List[subprocess.Popen], log_files: List[str]):
        self.procs = procs
        self.log_files = log_files

    def poll(self) -> Optional[int]:
        """None while all alive; else the first non-zero exit code (0 when
        all exited cleanly)."""
        codes = [p.poll() for p in self.procs]
        if any(c is None for c in codes):
            for c in codes:
                if c not in (None, 0):
                    return c  # fail fast while others still run
            return None
        bad = [c for c in codes if c != 0]
        return bad[0] if bad else 0

    def terminate(self):
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        # monotonic: a wall-clock step here would stretch/starve the
        # shared kill budget across workers (graftlint GL008)
        deadline = time.monotonic() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()


def _check_one_process_per_chip(nproc: int) -> None:
    """Refuse several workers on a TPU host: they would all open the same
    chips, and all but one fail or hang. Chips are found by their device
    nodes, so this parent stays off jax."""
    if nproc <= 1:
        return
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return  # workers inherit a pin to another platform (CPU rehearsal)
    if glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"):
        raise RuntimeError(
            f"--nproc_per_node={nproc} on a host with TPU chips: every "
            "worker would open the same chips, and a chip belongs to one "
            "process. Run one worker (it drives all local chips), or set "
            "JAX_PLATFORMS=cpu for a CPU rehearsal.")


def start_pod(script: List[str], nproc: int, log_dir: Optional[str] = None,
              extra_env_of_rank=None) -> Pod:
    """Spawn nproc workers with cluster env (reference
    start_local_trainers)."""
    _check_one_process_per_chip(nproc)
    coordinator = f"127.0.0.1:{_free_port()}"
    endpoints = [f"127.0.0.1:{_free_port()}" for _ in range(nproc)]
    procs, logs = [], []
    for rank in range(nproc):
        env = get_cluster_env(rank, nproc, coordinator, endpoints)
        if extra_env_of_rank is not None:
            env.update(extra_env_of_rank(rank))
        stdout = None
        log_path = ""
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            log_path = os.path.join(log_dir, f"workerlog.{rank}")
            stdout = open(log_path, "w")
        p = subprocess.Popen([sys.executable] + script, env=env,
                             stdout=stdout,
                             stderr=subprocess.STDOUT if stdout else None)
        procs.append(p)
        logs.append(log_path)
    return Pod(procs, logs)


def wait_pod(pod: Pod, poll_interval: float = 0.5) -> int:
    """Watch children; abort the pod when any worker fails (reference
    launch_utils.py watch_local_trainers)."""
    while True:
        code = pod.poll()
        if code is None:
            time.sleep(poll_interval)
            continue
        if code != 0:
            pod.terminate()
        return code


def launch(script: List[str], nproc: int = 1, log_dir: Optional[str] = None,
           elastic: bool = False, max_restarts: int = 3,
           poll_interval: float = 0.5) -> int:
    """Run the pod (optionally under elastic supervision). Returns the
    final exit code."""
    restarts = 0
    while True:
        pod = start_pod(script, nproc, log_dir)
        code = wait_pod(pod, poll_interval)
        if code == 0:
            return 0
        if not elastic or restarts >= max_restarts:
            return code
        restarts += 1
        sys.stderr.write(
            f"[paddle_tpu.launch] pod failed (exit {code}); elastic restart "
            f"{restarts}/{max_restarts}\n")


def elastic_launch(script: List[str], kv_dir: str, job_id: str,
                   min_np: int, max_np: Optional[int] = None,
                   initial_np: Optional[int] = None,
                   log_dir: Optional[str] = None, max_restarts: int = 10,
                   quorum_timeout: float = 60.0,
                   poll_interval: float = 0.2) -> int:
    """Elastic supervision (reference fleet/elastic/manager.py:317 watch
    loop): maintain a pod matching the job's live membership.

    - Membership lives in a FileKVStore; logical node ``n{i}``'s liveness
      is heartbeated by this agent while worker i runs. A worker that
      fails transiently keeps its node (same-np restart); a worker whose
      script marks its node dead (``ElasticManager.mark_dead``) is scaled
      IN — the pod relaunches with np-1 (down to min_np) and ranks
      remapped, surviving workers keeping theirs. Externally registered
      nodes scale the pod OUT (up to max_np) at the next membership check.
    - Every relaunch starts workers that auto-resume from the newest
      checkpoint (CheckpointManager.restore_latest) — the reference pairs
      its relaunch with --auto_checkpoint the same way.

    Returns the final exit code (0 = pod completed).
    """
    from .elastic import ElasticManager, FileKVStore

    kv = FileKVStore(kv_dir)
    mgr = ElasticManager(kv, job_id, min_np, max_np)
    # a fresh launch is a new incarnation of the job: clear the previous
    # run's completion flag and tombstones, else a reused job_id/kv_dir
    # silently starts scaled-in
    kv.delete(f"{mgr.prefix}/completed")
    for h in mgr.dead_hosts():
        mgr.readmit(h)
    n0 = initial_np or mgr.max_np
    for i in range(n0):
        mgr.register(f"n{i}")

    prev_map = None
    restarts = 0
    while True:
        hosts = mgr.wait_for_quorum(quorum_timeout, poll=poll_interval)
        rank_of = mgr.rank_map(hosts, prev_map)
        prev_map = rank_of
        node_of_rank = {r: h for h, r in rank_of.items()}

        def extra_env(rank):
            return {
                "PADDLE_ELASTIC_NODE": node_of_rank[rank],
                "PADDLE_ELASTIC_KV_DIR": kv_dir,
                "PADDLE_ELASTIC_JOB_ID": job_id,
            }

        pod = start_pod(script, nproc=len(hosts), log_dir=log_dir,
                        extra_env_of_rank=extra_env)
        sys.stderr.write(
            f"[paddle_tpu.elastic] pod up np={len(hosts)} "
            f"ranks={rank_of}\n")
        code = None
        scale_event = False
        while code is None:
            code = pod.poll()
            # heartbeat nodes whose worker is alive
            for rank, proc in enumerate(pod.procs):
                if proc.poll() is None:
                    mgr.heartbeat(node_of_rank[rank])
            if code is None:
                # scale-out/in watch: membership vs running pod
                ok, now = mgr.match()
                if ok and set(now) - set(hosts):
                    sys.stderr.write(
                        f"[paddle_tpu.elastic] membership grew to {now}; "
                        "relaunching\n")
                    scale_event = True
                    break
                time.sleep(poll_interval)
        # stop every surviving worker before relaunching: a half-dead pod
        # left running would race the new one on checkpoints and linger on
        # a dead coordinator
        pod.terminate()
        if code == 0:
            mgr.set_completed()
            return 0
        if scale_event:
            # voluntary resize, not a failure — doesn't consume the budget
            continue
        restarts += 1
        if restarts > max_restarts:
            sys.stderr.write(
                f"[paddle_tpu.elastic] giving up after {max_restarts} "
                "restarts\n")
            return code if code else 1
        sys.stderr.write(
            f"[paddle_tpu.elastic] pod exited {code}; restart "
            f"{restarts}/{max_restarts}\n")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="paddle_tpu distributed launcher")
    ap.add_argument("--nproc_per_node", type=int, default=1,
                    help="worker processes on this host (TPU: usually 1 — "
                         "one process drives all local chips)")
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("--elastic", action="store_true",
                    help="supervised restarts on worker failure")
    ap.add_argument("--max_restarts", type=int, default=3)
    ap.add_argument("--np", default=None,
                    help="elastic size or range 'min:max' (enables the "
                         "membership manager; reference --elastic_server "
                         "np syntax)")
    ap.add_argument("--elastic_kv_dir", default=None,
                    help="shared directory backing the membership store")
    ap.add_argument("--job_id", default="default")
    ap.add_argument("script", help="training script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.np:
        lo, _, hi = args.np.partition(":")
        min_np, max_np = int(lo), int(hi or lo)
        kv_dir = args.elastic_kv_dir or os.path.join(
            args.log_dir or ".", f"elastic_{args.job_id}")
        return elastic_launch([args.script] + args.script_args,
                              kv_dir=kv_dir, job_id=args.job_id,
                              min_np=min_np, max_np=max_np,
                              log_dir=args.log_dir,
                              max_restarts=args.max_restarts)
    return launch([args.script] + args.script_args,
                  nproc=args.nproc_per_node, log_dir=args.log_dir,
                  elastic=args.elastic, max_restarts=args.max_restarts)


if __name__ == "__main__":
    sys.exit(main())
