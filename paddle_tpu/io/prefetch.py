"""Double-buffered device prefetcher.

The DataLoader produces HOST batches; a training step consumes DEVICE
buffers. Without prefetch the host→device copy of batch N serializes
with step N-1's compute. :class:`DevicePrefetcher` wraps any batch
iterator and keeps ``size`` batches (default 2 — double buffering)
``jax.device_put`` ahead of the consumer, so the copy of batch N+1
overlaps step N: this is the framework-level version of the reference's
C++ BufferedReader async H2D stage.

When a parallel mesh is active (parallel.create_mesh) each array leaf is
placed with the mesh's batch sharding (leading dim over
``("data", "sharding")`` by default — the same default layout
DistributedTrainStep consumes), so the prefetcher also hides the
per-device scatter. Leaves whose leading dim doesn't divide the mesh (or
scalar leaves) fall back to single-device placement.

Gauges (paddle_tpu.monitor): ``prefetch_queue_depth`` tracks how many
batches are staged ahead (a persistently empty queue = input-bound),
``h2d_copy_ms`` accumulates host-side copy dispatch time. While tracing
is on, ``prefetch.h2d_copy`` and ``prefetch.wait`` spans land in the
chrome trace — ``tools/trace_report.py --top`` surfaces them in its
input-pipeline section.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Iterable, Optional

import numpy as np

from ..framework.core import Tensor
from ..monitor import stats as _mstats
from ..monitor.trace import TRACING as _TRACING
from ..monitor.trace import get_writer as _trace_writer
from ..resilience import faults as _faults

__all__ = ["DevicePrefetcher", "prefetch_to_device"]


def _batch_sharding(mesh, batch_spec):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        from ..parallel.mesh import get_mesh

        mesh = get_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, batch_spec if batch_spec is not None
                         else P(("data", "sharding")))


class DevicePrefetcher:
    """Iterator wrapper: ``device_put`` batch N+1 while step N runs.

    Args:
      it: iterable of batches — pytrees whose leaves are Tensors, numpy
        arrays, jax arrays, or scalars. Structure is preserved; Tensor
        leaves come back as Tensors over committed device buffers.
      size: prefetch depth (2 = classic double buffering).
      mesh / batch_spec: device placement; default picks up the active
        mesh (parallel.get_mesh()) and shards the leading dim over
        ``("data", "sharding")``. No mesh → plain device_put.
    """

    def __init__(self, it: Iterable, size: int = 2, mesh=None,
                 batch_spec=None):
        self._it = it
        self.size = max(1, int(size))
        self._mesh = mesh
        self._batch_spec = batch_spec
        self._h2d_ms = 0.0

    def _put_leaf(self, x, sharding):
        import jax

        is_tensor = isinstance(x, Tensor)
        arr = x._data if is_tensor else x
        if sharding is not None and getattr(arr, "ndim", 0) >= 1:
            try:
                arr = jax.device_put(arr, sharding)
            except Exception:  # e.g. leading dim not divisible by the mesh
                arr = jax.device_put(arr)
        else:
            try:
                arr = jax.device_put(arr)
            except TypeError:  # non-array leaf (str, None, ...)
                return x
        if is_tensor:
            t = Tensor(arr, stop_gradient=x.stop_gradient, name=x.name)
            return t
        return arr

    def _put_batch(self, batch, sharding):
        if isinstance(batch, (list, tuple)):
            return type(batch)(self._put_batch(v, sharding) for v in batch)
        if isinstance(batch, dict):
            return {k: self._put_batch(v, sharding) for k, v in batch.items()}
        return self._put_leaf(batch, sharding)

    def __iter__(self):
        sharding = _batch_sharding(self._mesh, self._batch_spec)
        q: queue.Queue = queue.Queue(maxsize=self.size)
        sentinel = object()
        err: list = []

        def producer():
            try:
                for idx, batch in enumerate(self._it):
                    if _faults.ENABLED[0]:
                        # input_stall@step=N fault hook (resilience.faults):
                        # a sleeping producer starves the consumer exactly
                        # like a wedged storage read would
                        _faults.FAULTS.on_input(idx)
                    t0 = time.perf_counter()
                    staged = self._put_batch(batch, sharding)
                    dt = time.perf_counter() - t0
                    new_total = self._h2d_ms + dt * 1e3
                    _mstats.H2D_COPY_MS.add(int(new_total) - int(self._h2d_ms))
                    self._h2d_ms = new_total
                    if _TRACING[0]:
                        _trace_writer().add_complete(
                            "prefetch.h2d_copy", t0, dt, cat="input")
                    q.put(staged)
                    _mstats.PREFETCH_QUEUE_DEPTH.set(q.qsize())
            except BaseException as e:  # noqa: BLE001
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            if _TRACING[0] and q.empty():
                t0 = time.perf_counter()
                item = q.get()
                _trace_writer().add_complete(
                    "prefetch.wait", t0, time.perf_counter() - t0,
                    cat="input")
            else:
                item = q.get()
            if item is sentinel:
                break
            _mstats.PREFETCH_QUEUE_DEPTH.set(q.qsize())
            yield item
        t.join()
        _mstats.PREFETCH_QUEUE_DEPTH.set(0)
        if err:
            raise err[0]

    def __len__(self):
        return len(self._it)


def prefetch_to_device(it: Iterable, size: int = 2, mesh=None,
                       batch_spec=None):
    """Functional form of :class:`DevicePrefetcher` (returns a fresh
    iterator each call)."""
    return iter(DevicePrefetcher(it, size=size, mesh=mesh,
                                 batch_spec=batch_spec))
