"""Test config: force an 8-device CPU mesh (the TPU-sharding test rig).

Mirrors SURVEY.md §4's translation: the reference's single-host
multi-process cluster tests become single-process multi-device tests over
a virtual device mesh.

Must run before jax backends initialize. The environment is what the
subprocesses the tests spawn inherit; the config update also covers a
jax that a pytest plugin imported before this file.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 `-m 'not slow'` "
        "budget (full fault matrices, big-model benches)")
    config.addinivalue_line(
        "markers",
        "kernels: Pallas kernel parity suite (interpret mode on CPU) — "
        "select with `pytest -m kernels` after touching ops/ kernels")
    config.addinivalue_line(
        "markers",
        "pod: multi-PROCESS elastic/pod tests (select with `pytest -m "
        "pod`); tier-1 keeps the threaded single-process simulations")
    config.addinivalue_line(
        "markers",
        "chaos: serving chaos-harness tests (fault-injected router/"
        "brownout runs; select with `pytest -m chaos` after touching "
        "serving overload paths — tier-1 keeps the fast deterministic "
        "ones)")
    config.addinivalue_line(
        "markers",
        "recsys: recommender-stack tests (paddle_tpu.sparse sharded "
        "embeddings, DLRM, serving rank path) — select with `pytest -m "
        "recsys` after touching sparse/ or models/dlrm.py")
    config.addinivalue_line(
        "markers",
        "tuning: shape-keyed autotuner tests (trial sweeps, cache "
        "round-trips, `tools/autotune --check` staleness) — select with "
        "`pytest -m tuning` after touching ops/autotune.py or a kernel "
        "family registration")
    config.addinivalue_line(
        "markers",
        "moe: mixture-of-experts tests (nn/moe router+dispatch, MoE GPT "
        "blocks, ep planner, sparse serving decode) — select with "
        "`pytest -m moe` after touching nn/moe.py, ops/moe_dispatch.py "
        "or the gpt MoE paths")


@pytest.fixture(autouse=True)
def _seed():
    import numpy as np

    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
