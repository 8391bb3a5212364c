"""Device management — the Place/DeviceContext analog.

Reference: paddle/fluid/platform/place.h:150 (Place variant) and
device_context.h:818 (DeviceContextPool). On TPU the PJRT client owns
streams and contexts, so this reduces to device selection + queries;
the multi-device story is the jax.sharding Mesh (see paddle_tpu.distributed).
"""
from __future__ import annotations

import os

import jax

__all__ = [
    "set_device", "get_device", "get_all_devices", "device_count",
    "enable_compile_cache",
    "TPUPlace", "CPUPlace", "CUDAPlace", "XPUPlace", "NPUPlace",
    "CUDAPinnedPlace", "is_compiled_with_cuda", "is_compiled_with_xpu",
    "is_compiled_with_npu", "is_compiled_with_tpu", "synchronize",
]


class _Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"{self.device_type}:{self.device_id}"

    def __eq__(self, other):
        return (
            isinstance(other, _Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def get_device_id(self):
        return self.device_id

    @property
    def jax_device(self):
        # raises when jax has no backend of this type: a TPUPlace never
        # stands for whatever other device happens to exist
        devs = jax.devices(self.device_type)
        return devs[self.device_id % len(devs)]


class TPUPlace(_Place):
    device_type = "tpu"


class CPUPlace(_Place):
    device_type = "cpu"


class CUDAPlace(TPUPlace):
    """Accepted for API parity; maps to the accelerator (TPU) device."""

    device_type = "tpu"


class XPUPlace(TPUPlace):
    device_type = "tpu"


class NPUPlace(TPUPlace):
    device_type = "tpu"


class CUDAPinnedPlace(CPUPlace):
    device_type = "cpu"


_current_device = [None]


def _default_place():
    d = jax.devices()[0]
    return TPUPlace(0) if d.platform == "tpu" else CPUPlace(0)


def set_device(device):
    """paddle.set_device parity: 'tpu', 'tpu:0', 'cpu', 'gpu:0' (→ tpu)."""
    if isinstance(device, _Place):
        _current_device[0] = device
        return device
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    if name in ("gpu", "cuda", "tpu", "xpu", "npu"):
        place = TPUPlace(idx)
    else:
        place = CPUPlace(idx)
    jax.config.update("jax_default_device", place.jax_device)
    _current_device[0] = place
    return place


def get_device() -> str:
    p = _current_device[0] or _default_place()
    return f"{p.device_type}:{p.device_id}"


def current_place():
    return _current_device[0] or _default_place()


def get_all_devices():
    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def device_count():
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def synchronize(device=None):
    """Block until all dispatched work on the device completes."""
    (jax.device_put(0) + 0).block_until_ready()


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is touched. Otherwise the cache goes to ``.jax_cache`` beside
    the package (the checkout root; gitignored). The path is part of the
    cache key's surroundings, so it is fixed: never a temp name, a pid or
    a time. Entry points call this once (chip_smoke.py, benchmarks/run.py);
    the package itself sets no cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
