"""ctypes bindings for the native runtime core (csrc/ptpu_core.cc).

The reference binds its C++ core with pybind11 (paddle/fluid/pybind/
pybind.cc); this environment has no pybind11, so the native library exports
a C ABI consumed here via ctypes. The .so is built with the Makefile at
import whenever it is missing or older than its source; if the toolchain
is unavailable the pure-Python fallbacks below keep the API working
(slower, same semantics) and a RuntimeWarning says so.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import time
import warnings
from contextlib import contextmanager
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "lib", "libptpu_core.so")
_SRC_PATH = os.path.join(_DIR, "csrc", "ptpu_core.cc")

_lib: Optional[ctypes.CDLL] = None


def _lib_is_stale() -> bool:
    """True when the .so is missing or older than its source. The .so is
    not tracked by git, so a checkout has none and a copied working tree
    may carry one built from an older csrc."""
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True


def _build_and_load() -> Optional[ctypes.CDLL]:
    if _lib_is_stale():
        try:
            subprocess.run(["make", "-C", _DIR, "-s"], check=True,
                           capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as e:
            # the pure-Python fallbacks keep the API working; a stale .so
            # is never loaded in place of a failed rebuild
            stderr = (getattr(e, "stderr", None) or b"").decode(
                errors="replace").strip()
            warnings.warn("paddle_tpu native core not built (%s); using "
                          "the pure-Python fallbacks" % (stderr or e),
                          RuntimeWarning, stacklevel=2)
            return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        warnings.warn("paddle_tpu native core not loaded (%s); using the "
                      "pure-Python fallbacks" % e, RuntimeWarning,
                      stacklevel=2)
        return None
    # signatures
    lib.ptpu_last_error.restype = ctypes.c_char_p
    lib.ptpu_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.ptpu_flag_get.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
    lib.ptpu_flag_get.restype = ctypes.c_int
    lib.ptpu_stat_add.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.ptpu_stat_get.argtypes = [ctypes.c_char_p]
    lib.ptpu_stat_get.restype = ctypes.c_int64
    lib.ptpu_stat_reset.argtypes = [ctypes.c_char_p]
    lib.ptpu_profiler_enable.argtypes = [ctypes.c_int]
    lib.ptpu_event_begin.restype = ctypes.c_int64
    lib.ptpu_event_end.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.ptpu_profiler_dump.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.ptpu_profiler_dump.restype = ctypes.c_int64
    lib.ptpu_profiler_event_count.restype = ctypes.c_int
    lib.ptpu_queue_create.argtypes = [ctypes.c_int]
    lib.ptpu_queue_create.restype = ctypes.c_void_p
    lib.ptpu_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_int64, ctypes.c_int]
    lib.ptpu_queue_push.restype = ctypes.c_int
    lib.ptpu_queue_pop.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
                                   ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.ptpu_queue_pop.restype = ctypes.c_int
    lib.ptpu_buffer_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
    lib.ptpu_queue_size.argtypes = [ctypes.c_void_p]
    lib.ptpu_queue_size.restype = ctypes.c_int
    lib.ptpu_queue_close.argtypes = [ctypes.c_void_p]
    lib.ptpu_queue_destroy.argtypes = [ctypes.c_void_p]
    lib.ptpu_arena_create.argtypes = [ctypes.c_int64]
    lib.ptpu_arena_create.restype = ctypes.c_void_p
    lib.ptpu_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.ptpu_arena_alloc.restype = ctypes.c_void_p
    lib.ptpu_arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.ptpu_arena_free.restype = ctypes.c_int
    lib.ptpu_arena_stat.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptpu_arena_stat.restype = ctypes.c_int64
    lib.ptpu_arena_destroy.argtypes = [ctypes.c_void_p]
    return lib


_lib = _build_and_load()
NATIVE_AVAILABLE = _lib is not None


# -- flags ------------------------------------------------------------------

_py_flags = {}
_py_flags_lock = threading.Lock()


# Fast-path mirror of FLAGS_check_nan_inf, read per-op by apply_op (the
# analog of the reference's post-kernel CheckOpHasNanOrInf gate,
# operator.cc:1199); a list so importers share the mutable cell.
check_nan_inf = [False]

# Fast-path mirror of FLAGS_benchmark (reference imperative/flags.cc):
# while on, apply_op accumulates per-op wall time into
# paddle_tpu.monitor.benchmark.
benchmark = [False]

# Fast-path mirror of FLAGS_eager_grad_jit (ISSUE 2): gates the cached
# jitted-VJP fast path on grad-enabled eager dispatch (the training-side
# PreparedOp-cache analog in framework.core). Default ON; flip with
# `paddle.set_flags({"FLAGS_eager_grad_jit": 0})` to fall back to raw
# per-call jax.vjp closures.
eager_grad_jit = [True]


def _truthy(value) -> bool:
    return str(value).lower() in ("1", "true", "yes", "on")


# Fast-path mirror of FLAGS_use_shared_memory (ISSUE 3 — the reference's
# fluid/dataloader flags.use_shared_memory): multiprocess DataLoader
# workers ship batches through a shared-memory ring instead of pickling
# them over pipes. Default ON; the pipe path stays the automatic fallback
# for non-numpy payloads and platform errors.
use_shared_memory = [_truthy(os.environ.get("FLAGS_use_shared_memory", "1"))]

# Fast-path mirror of FLAGS_fast_step (ISSUE 3): donated async train-step
# fast path — params/opt-state stay device-resident across steps with
# buffer donation, the step is dispatched without blocking, and reading
# the loss is the only sync point (counted by the step_async_syncs gauge).
# `paddle.set_flags({"FLAGS_fast_step": 0})` restores the per-step
# writeback + per-step host scalar paths.
fast_step = [_truthy(os.environ.get("FLAGS_fast_step", "1"))]

# Fast-path mirror of FLAGS_fused_optimizer (ISSUE 6 — the reference's
# operators/fused/ fused Adam/LAMB kernels): flatten the param/moment/grad
# pytrees into a few contiguous dtype-homogeneous buffers and run the
# whole optimizer update as ONE pass (a Pallas kernel on TPU, a single
# fused XLA program elsewhere) instead of a per-leaf tree_map. Opt-in on
# Adam/AdamW/Lamb eager ``step()`` and on jit.TrainStep /
# DistributedTrainStep. Default OFF; the unfused path is pinned
# bit-for-bit while unset.
fused_optimizer = [_truthy(os.environ.get("FLAGS_fused_optimizer", "0"))]

# Fast-path mirror of FLAGS_fused_kernels (ISSUE 6): fused
# residual+layernorm and GeLU/SwiGLU-MLP Pallas kernels in the
# transformer block hot path (ops/fused_kernels.py, wired through
# ops/fused.py and models/gpt.py). Off-TPU the "fused" entry points fall
# back to the identical composed jnp math, so flipping the flag on CPU
# changes nothing; interpret-mode parity tests cover the kernels
# themselves. Default OFF.
fused_kernels = [_truthy(os.environ.get("FLAGS_fused_kernels", "0"))]

# Fast-path mirror of FLAGS_overlap_grads (ISSUE 6): latency-hiding
# gradient collectives — DistributedTrainStep computes grads under
# shard_map with a per-bucket pmean issued INSIDE the backward (a
# custom-vjp identity on each param bucket), so the dp-grad all-reduce
# for layer N overlaps the backward compute of layers < N instead of
# serializing after the full backward. Default OFF; requires a pure
# data/sharding mesh (model/pipe degree 1) and replicated params — other
# topologies keep the GSPMD path.
overlap_grads = [_truthy(os.environ.get("FLAGS_overlap_grads", "0"))]

# FLAGS_fault_inject (ISSUE 5): deterministic fault-injection spec string
# (e.g. "nan_grad@step=50:repeat=3,crash@step=120"); empty = no faults.
# The resilience.faults registry registers a watcher here so set_flags
# reconfigures it immediately; the cell holds the raw spec text.
fault_inject = [os.environ.get("FLAGS_fault_inject", "")]
fault_inject_watchers: list = []

# FLAGS_sanitize (ISSUE 8): opt-in runtime sanitizers
# (paddle_tpu.analysis.sanitizers) — the jit-boundary recompile explainer
# (a cache miss diffs its aval signature against the nearest cached entry
# and emits a `sanitize.recompile` span naming the differing leaf) and
# the donation-after-use guard (buffers donated to a compiled step are
# tombstoned; a later host read raises with the donating call site).
# Default OFF; the unset path is pinned bit-for-bit — each hook is one
# list-index check.
sanitize = [_truthy(os.environ.get("FLAGS_sanitize", "0"))]


# FLAGS_shardy (ISSUE 9): lower shardings through the Shardy (sdy)
# partitioner dialect instead of legacy GSPMD mhlo.sharding strings —
# axis NAMES survive into the lowered module (`sdy.sharding_constraint
# <@mesh, [{"data"}, {"model"}]>`), which is what fleet.auto.explain
# debugging and the assert-on-HLO tests read. Default ON; flip to 0 to
# fall back to the legacy partitioner (the compiled HLO is equivalent —
# partitioning happens at compile time either way).
shardy = [_truthy(os.environ.get("FLAGS_shardy", "1"))]


def apply_shardy_flag() -> None:
    """Push the cell value into jax's global lowering config (called at
    paddle_tpu import and from set_flags)."""
    import jax

    jax.config.update("jax_use_shardy_partitioner", bool(shardy[0]))


def _int_or_zero(value) -> int:
    try:
        return int(str(value))
    except (TypeError, ValueError):
        return 0


# FLAGS_shm_slot_bytes (ISSUE 3 transport, cell added by ISSUE 8's
# env-flag lint): manual override of the shared-memory ring's per-slot
# byte size; 0 = size from the probed sample. Going through a cell keeps
# `paddle.set_flags({"FLAGS_shm_slot_bytes": n})` working — the env var
# alone would be unreachable after import.
shm_slot_bytes = [_int_or_zero(os.environ.get("FLAGS_shm_slot_bytes", "0"))]


# FLAGS_serving_mesh (ISSUE 10): multi-chip sharded decode for the
# serving engine — an integer DATA degree: decode slots shard over the
# mesh "data" axis, the remaining devices become the "model" axis over
# which weights shard Megatron-style via gpt_param_specs (GSPMD derives
# the collectives). 0 (default) keeps the single-chip engine bit-for-bit;
# an explicit ``InferenceEngine(mesh=...)`` overrides the flag either way.
serving_mesh = [_int_or_zero(os.environ.get("FLAGS_serving_mesh", "0"))]


# FLAGS_prefix_cache (ISSUE 11): radix-tree prefix sharing over the
# paged KV block pool — admission walks a host-side radix tree of
# cached prompt prefixes, splices matched (refcounted, copy-on-write)
# blocks into the new slot's table and only prefills the uncached tail,
# so a shared system prompt prefills ONCE and fans out across streams.
# Default OFF; the cache-cold engine is pinned token-identical while
# unset, and greedy output with the cache ON is pinned token-identical
# to cold.
prefix_cache = [_truthy(os.environ.get("FLAGS_prefix_cache", "0"))]


# FLAGS_autotune (ISSUE 17): shape-keyed Pallas block autotuning — at the
# first compile of a kernel family for a concrete (kernel, shape, dtype,
# backend) key, time a handful of legal block configs and persist the
# winner to tools/autotune_cache.json (ops/autotune.py); later compiles
# consult the cache. Default OFF; unset, every kernel keeps its
# hand-picked `_auto_block` defaults bit-for-bit. Kernel modules mirror
# the cell via `autotune_watchers` so no jit-reachable code reads it.
autotune = [_truthy(os.environ.get("FLAGS_autotune", "0"))]
autotune_watchers: list = []

# FLAGS_fp8_matmul (ISSUE 17): fp8 (e4m3) matmul path for the block
# projections — delayed-scaling amax history through paddle_tpu.amp.fp8,
# dequant fused into the kernel epilogue (ops/fp8_matmul.py, the int8
# epilogue pattern). Default OFF; the bf16 path is pinned bit-for-bit
# while unset. `GPTConfig(fp8=True)` opts a model in explicitly.
fp8_matmul = [_truthy(os.environ.get("FLAGS_fp8_matmul", "0"))]
fp8_matmul_watchers: list = []

# FLAGS_overlap_zero2 (ISSUE 17): extend FLAGS_overlap_grads' in-backward
# gradient collective from pmean to the ZeRO-2 reduce-scatter — sharded
# grad buckets issue psum_scatter INSIDE the backward so the scatter of
# layer N overlaps the backward compute of layers < N, and each device
# only ever materializes its grad shard. Requires FLAGS_overlap_grads=1
# and zero level >= 2. Default OFF; the post-backward GSPMD
# reduce-scatter path is pinned bit-for-bit while unset.
overlap_zero2 = [_truthy(os.environ.get("FLAGS_overlap_zero2", "0"))]


def set_flag(name: str, value) -> None:
    if name.endswith("check_nan_inf"):
        check_nan_inf[0] = _truthy(value)
    elif name.endswith("benchmark"):
        benchmark[0] = _truthy(value)
    elif name.endswith("eager_grad_jit"):
        eager_grad_jit[0] = _truthy(value)
    elif name.endswith("use_shared_memory"):
        use_shared_memory[0] = _truthy(value)
    elif name.endswith("fast_step"):
        fast_step[0] = _truthy(value)
    elif name.endswith("fused_optimizer"):
        fused_optimizer[0] = _truthy(value)
    elif name.endswith("fused_kernels"):
        fused_kernels[0] = _truthy(value)
    elif name.endswith("overlap_grads"):
        overlap_grads[0] = _truthy(value)
    elif name.endswith("fault_inject"):
        fault_inject[0] = str(value)
        for watcher in fault_inject_watchers:
            watcher(fault_inject[0])
    elif name.endswith("sanitize"):
        sanitize[0] = _truthy(value)
    elif name.endswith("shardy"):
        shardy[0] = _truthy(value)
        apply_shardy_flag()
    elif name.endswith("shm_slot_bytes"):
        shm_slot_bytes[0] = _int_or_zero(value)
    elif name.endswith("serving_mesh"):
        serving_mesh[0] = _int_or_zero(value)
    elif name.endswith("prefix_cache"):
        prefix_cache[0] = _truthy(value)
    elif name.endswith("autotune"):
        autotune[0] = _truthy(value)
        for watcher in autotune_watchers:
            watcher(autotune[0])
    elif name.endswith("fp8_matmul"):
        fp8_matmul[0] = _truthy(value)
        for watcher in fp8_matmul_watchers:
            watcher(fp8_matmul[0])
    elif name.endswith("overlap_zero2"):
        overlap_zero2[0] = _truthy(value)
    if _lib is not None:
        _lib.ptpu_flag_set(name.encode(), str(value).encode())
    else:
        with _py_flags_lock:
            _py_flags[name] = str(value)


def get_flag(name: str, default=None):
    if _lib is not None:
        buf = ctypes.create_string_buffer(4096)
        if _lib.ptpu_flag_get(name.encode(), buf, 4096):
            return buf.value.decode()
        return default
    with _py_flags_lock:
        if name in _py_flags:
            return _py_flags[name]
    return os.environ.get(name, default)


# -- stats ------------------------------------------------------------------

_py_stats = {}


def stat_add(name: str, delta: int = 1) -> None:
    if _lib is not None:
        _lib.ptpu_stat_add(name.encode(), int(delta))
    else:
        with _py_flags_lock:
            _py_stats[name] = _py_stats.get(name, 0) + int(delta)


def stat_get(name: str) -> int:
    if _lib is not None:
        return int(_lib.ptpu_stat_get(name.encode()))
    with _py_flags_lock:
        return _py_stats.get(name, 0)


def stat_reset(name: str) -> None:
    if _lib is not None:
        _lib.ptpu_stat_reset(name.encode())
    else:
        with _py_flags_lock:
            _py_stats[name] = 0


# -- profiler ---------------------------------------------------------------

_py_events = []
_py_prof_enabled = [False]


def profiler_enable(on: bool = True) -> None:
    if _lib is not None:
        _lib.ptpu_profiler_enable(1 if on else 0)
    else:
        _py_prof_enabled[0] = bool(on)


def profiler_clear() -> None:
    if _lib is not None:
        _lib.ptpu_profiler_clear()
    else:
        _py_events.clear()


def profiler_dump() -> str:
    """Chrome-trace JSON of recorded events."""
    if _lib is not None:
        n = _lib.ptpu_profiler_dump(None, 0)
        buf = ctypes.create_string_buffer(int(n) + 1)
        _lib.ptpu_profiler_dump(buf, n)
        return buf.raw[:n].decode()
    import json
    return json.dumps({"traceEvents": [
        {"name": name, "ph": "X", "pid": 0, "tid": 0,
         "ts": int(ts * 1e6), "dur": int(dur * 1e6)}
        for name, ts, dur in _py_events]})


@contextmanager
def record_event(name: str):
    """RAII event scope (reference platform/profiler.h:130 RecordEvent)."""
    if _lib is not None:
        t0 = _lib.ptpu_event_begin()
        try:
            yield
        finally:
            _lib.ptpu_event_end(name.encode(), t0)
    else:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if _py_prof_enabled[0]:
                _py_events.append((name, t0, time.perf_counter() - t0))


# -- blocking queue ---------------------------------------------------------

class BlockingQueue:
    """Bounded byte-buffer queue backed by the native impl (pure-Python
    fallback uses queue.Queue). Payloads are bytes; producers block when
    full, consumers when empty; close() releases both sides."""

    def __init__(self, capacity: int = 8):
        self._native = _lib is not None
        if self._native:
            self._h = _lib.ptpu_queue_create(int(capacity))
        else:
            import queue
            self._q = queue.Queue(maxsize=capacity)
            self._closed = threading.Event()

    def push(self, data: bytes, timeout_ms: int = -1) -> bool:
        if self._native:
            r = _lib.ptpu_queue_push(self._h, data, len(data), timeout_ms)
            if r == -1:
                raise TimeoutError("queue push timed out")
            return r == 1
        # fallback: poll in short slices so close() wakes blocked pushers
        # (matching the native close semantics)
        import queue as _q
        deadline = None if timeout_ms < 0 else time.monotonic() + timeout_ms / 1e3
        while True:
            if self._closed.is_set():
                return False
            try:
                self._q.put(data, timeout=0.05)
                return True
            except _q.Full:
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("queue push timed out")

    def pop(self, timeout_ms: int = -1) -> Optional[bytes]:
        """None means closed-and-drained."""
        if self._native:
            pdata = ctypes.POINTER(ctypes.c_char)()
            plen = ctypes.c_int64()
            r = _lib.ptpu_queue_pop(self._h, ctypes.byref(pdata),
                                    ctypes.byref(plen), timeout_ms)
            if r == -1:
                raise TimeoutError("queue pop timed out")
            if r == 0:
                return None
            out = ctypes.string_at(pdata, plen.value)
            _lib.ptpu_buffer_free(pdata)
            return out
        import queue as _q
        deadline = None if timeout_ms < 0 else time.monotonic() + timeout_ms / 1e3
        while True:
            try:
                return self._q.get(timeout=0.05)
            except _q.Empty:
                if self._closed.is_set() and self._q.empty():
                    return None
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("queue pop timed out")

    def __len__(self):
        if self._native:
            return _lib.ptpu_queue_size(self._h)
        return self._q.qsize()

    def close(self):
        if self._native:
            _lib.ptpu_queue_close(self._h)
        else:
            self._closed.set()

    def __del__(self):
        try:
            if self._native and _lib is not None:
                _lib.ptpu_queue_destroy(self._h)
        except Exception:
            pass


# -- arena allocator --------------------------------------------------------

class ArenaAllocator:
    """Host staging arena with best-fit + coalescing and stats.

    Stats indices: 0=allocated bytes, 1=peak bytes, 2=alloc count,
    3=free-block count (fragmentation signal).
    """

    def __init__(self, nbytes: int):
        if _lib is None:
            raise RuntimeError("native core unavailable — ArenaAllocator "
                               "requires the compiled runtime")
        self._h = _lib.ptpu_arena_create(int(nbytes))
        if not self._h:
            raise MemoryError(_lib.ptpu_last_error().decode())

    def alloc(self, nbytes: int) -> int:
        p = _lib.ptpu_arena_alloc(self._h, int(nbytes))
        if not p:
            raise MemoryError(_lib.ptpu_last_error().decode())
        return p

    def free(self, ptr: int) -> None:
        if not _lib.ptpu_arena_free(self._h, ptr):
            raise ValueError(_lib.ptpu_last_error().decode())

    def stat(self, which: int) -> int:
        return int(_lib.ptpu_arena_stat(self._h, which))

    @property
    def allocated(self):
        return self.stat(0)

    @property
    def peak(self):
        return self.stat(1)

    def __del__(self):
        try:
            if _lib is not None and getattr(self, "_h", None):
                _lib.ptpu_arena_destroy(self._h)
        except Exception:
            pass
