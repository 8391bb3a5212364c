"""Launcher + elastic supervision (distributed/launch.py).

Reference pattern: test_parallel_dygraph_dataparallel.py:146 TestMultipleGpus
— run a target script through the real launcher machinery and check exit
codes + env wiring; test_fleet_elastic_* for the restart loop.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.distributed.launch import (Pod, get_cluster_env, launch,
                                           start_pod, wait_pod)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


class TestClusterEnv:
    def test_env_block(self):
        eps = ["127.0.0.1:9100", "127.0.0.1:9101"]
        env = get_cluster_env(1, 2, "127.0.0.1:9000", eps)
        assert env["PADDLE_TRAINER_ID"] == "1"
        assert env["PADDLE_TRAINERS_NUM"] == "2"
        assert env["PADDLE_CURRENT_ENDPOINT"] == "127.0.0.1:9101"
        assert env["PADDLE_TRAINER_ENDPOINTS"] == ",".join(eps)
        assert env["JAX_PROCESS_ID"] == "1"
        assert env["JAX_NUM_PROCESSES"] == "2"
        assert env["JAX_COORDINATOR_ADDRESS"] == "127.0.0.1:9000"


class TestOneProcessPerChip:
    """A chip belongs to one process: several workers on a TPU host are
    refused, and the launcher parent never opens a backend itself."""

    def test_refuses_many_workers_on_tpu_host(self, monkeypatch):
        from paddle_tpu.distributed import launch as L

        monkeypatch.setattr(L.glob, "glob", lambda pat: ["/dev/accel0"])
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match=r"one\s+process"):
            L.start_pod(["-c", "pass"], nproc=2)
        L._check_one_process_per_chip(1)           # one worker: fine
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        L._check_one_process_per_chip(4)           # CPU rehearsal: fine

    @pytest.mark.slow  # a fresh interpreter importing the package
    def test_launcher_parent_opens_no_backend(self):
        code = ("import paddle_tpu.distributed.launch\n"
                "from jax._src import xla_bridge\n"
                "assert not xla_bridge._backends, xla_bridge._backends\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]


class TestLauncher:
    def test_two_workers_env_wiring(self, tmp_path):
        script = _write(tmp_path, "worker.py", """
            import json, os, sys
            rank = os.environ["PADDLE_TRAINER_ID"]
            keys = ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                    "PADDLE_CURRENT_ENDPOINT", "JAX_PROCESS_ID")
            info = dict((k, os.environ[k]) for k in keys)
            out = os.path.dirname(os.path.abspath(__file__))
            with open(os.path.join(out, "rank%s.json" % rank), "w") as f:
                json.dump(info, f)
            """)
        code = launch([script], nproc=2)
        assert code == 0
        import json

        r0 = json.load(open(tmp_path / "rank0.json"))
        r1 = json.load(open(tmp_path / "rank1.json"))
        assert r0["PADDLE_TRAINER_ID"] == "0" and r1["PADDLE_TRAINER_ID"] == "1"
        assert r0["PADDLE_TRAINERS_NUM"] == "2"
        assert r0["PADDLE_CURRENT_ENDPOINT"] != r1["PADDLE_CURRENT_ENDPOINT"]
        assert r0["JAX_PROCESS_ID"] == "0" and r1["JAX_PROCESS_ID"] == "1"

    def test_failing_worker_aborts_pod(self, tmp_path):
        script = _write(tmp_path, "bad.py", """
            import os, sys, time
            if os.environ["PADDLE_TRAINER_ID"] == "1":
                sys.exit(7)
            time.sleep(30)  # rank 0 would hang: the pod must kill it
            """)
        code = launch([script], nproc=2)
        assert code == 7

    def test_log_dir_captures_worker_output(self, tmp_path):
        script = _write(tmp_path, "noisy.py", """
            import os
            print("hello from", os.environ["PADDLE_TRAINER_ID"])
            """)
        log_dir = str(tmp_path / "logs")
        code = launch([script], nproc=2, log_dir=log_dir)
        assert code == 0
        logs = sorted(os.listdir(log_dir))
        assert logs == ["workerlog.0", "workerlog.1"]
        assert "hello from 0" in open(os.path.join(log_dir, "workerlog.0")).read()


class TestElastic:
    def test_elastic_relaunches_until_success(self, tmp_path):
        marker = tmp_path / "attempts"
        script = _write(tmp_path, "flaky.py", """
            import os, sys
            if os.environ["PADDLE_TRAINER_ID"] != "0":
                sys.exit(0)
            marker = {m!r}
            n = int(open(marker).read()) if os.path.exists(marker) else 0
            tmp = marker + ".tmp"
            open(tmp, "w").write(str(n + 1))
            os.replace(tmp, marker)
            if n < 2:
                sys.exit(1)  # fail the first two pods
            """.format(m=str(marker)))
        code = launch([script], nproc=2, elastic=True, max_restarts=3,
                      poll_interval=0.1)
        assert code == 0
        assert int(open(marker).read()) == 3  # two failures + one success

    def test_elastic_gives_up_after_max_restarts(self, tmp_path):
        script = _write(tmp_path, "always_bad.py", "import sys; sys.exit(3)\n")
        code = launch([script], nproc=1, elastic=True, max_restarts=2,
                      poll_interval=0.1)
        assert code == 3

    def test_killed_worker_triggers_relaunch(self, tmp_path):
        """Kill a live worker; elastic supervision restarts the pod."""
        marker = tmp_path / "pids"
        script = _write(tmp_path, "victim.py", """
            import os, time
            with open({m!r}, "a") as f:
                f.write(str(os.getpid()) + chr(10))
            # first pod: wait to be killed; relaunched pod: exit clean
            if len(open({m!r}).read().split()) > 1:
                raise SystemExit(0)
            time.sleep(60)
            """.format(m=str(marker)))

        import signal
        import threading
        import time

        def killer():
            deadline = time.time() + 30
            while time.time() < deadline:
                if marker.exists() and marker.read_text().strip():
                    pid = int(marker.read_text().split()[0])
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except ProcessLookupError:
                        pass
                    return
                time.sleep(0.2)

        t = threading.Thread(target=killer)
        t.start()
        code = launch([script], nproc=1, elastic=True, max_restarts=2,
                      poll_interval=0.1)
        t.join()
        assert code == 0
        assert len(marker.read_text().split()) == 2  # original + relaunch
