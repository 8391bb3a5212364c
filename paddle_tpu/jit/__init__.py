"""paddle_tpu.jit — eager→compiled bridge.

This is the TPU-native replacement for BOTH reference worlds:
- ``paddle.jit.to_static`` (dygraph_to_static ProgramTranslator,
  reference python/paddle/fluid/dygraph/dygraph_to_static/) — here there is
  no AST rewriting: jax traces the eager code directly, so ``to_static`` is
  "functionalize + jax.jit".
- the static Program+Executor pipeline — a traced function IS the program.

Key primitives:
- ``state(layer)`` → (params, buffers) dicts of raw jax arrays.
- ``functional_call(layer, params, buffers, *args)`` → (out, new_buffers):
  runs ``layer.forward`` with the given arrays bound in place of its
  Parameters/buffers. Buffer mutation (BatchNorm running stats) is captured
  and returned instead of leaking tracers.
- ``TrainStep(model, loss_fn, optimizer)`` → one fused XLA program per
  (shape-set): forward + backward + optimizer update, the analog of the
  reference executor running the whole ProgramDesc in one go.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..analysis import sanitizers as _san
from ..core.native import fast_step as _fast_step
from ..core.native import sanitize as _sanitize
from ..framework.core import AsyncLoss, Parameter, Tensor
from ..nn.layer.layers import Layer
from ..resilience import faults as _faults
from ..resilience import sentinel as _sentinel

__all__ = ["state", "functional_call", "to_static", "TrainStep", "not_to_static",
           "ProgramTranslator", "TracedLayer", "TranslatedLayer",
           "set_code_level", "set_verbosity",
           "InputSpec", "save", "load"]


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


def state(layer: Layer):
    params = {k: p._data for k, p in layer.named_parameters()}
    buffers = {k: b._data for k, b in layer.named_buffers() if b is not None}
    return params, buffers


def _named_state_tensors(layer: Layer):
    out = {}
    for k, p in layer.named_parameters():
        out[k] = p
    for k, b in layer.named_buffers():
        if b is not None:
            out[k] = b
    return out


def functional_call(layer: Layer, params: Dict[str, Any], buffers: Dict[str, Any],
                    *args, training: Optional[bool] = None, **kwargs):
    """Run layer.forward with arrays bound into its Parameters/buffers.

    Thread-unsafe by design (same as the reference's global tracer state);
    call within one trace at a time.
    """
    tensors = _named_state_tensors(layer)
    saved = {}
    saved_training = None
    try:
        for name, arr in {**params, **buffers}.items():
            t = tensors.get(name)
            if t is None:
                raise KeyError(f"no parameter/buffer named {name}")
            saved[name] = t._data
            t._data = arr if not isinstance(arr, Tensor) else arr._data
        if training is not None:
            saved_training = [(l, l.training) for l in layer.sublayers(include_self=True)]
            for l, _ in saved_training:
                l.training = training
        out = layer(*args, **kwargs)
        new_buffers = {name: tensors[name]._data for name in buffers}
        return out, new_buffers
    finally:
        for name, arr in saved.items():
            tensors[name]._data = arr
        if saved_training:
            for l, was in saved_training:
                l.training = was


def _tree_tensor_to_array(x):
    return jax.tree_util.tree_map(
        lambda v: v._data if isinstance(v, Tensor) else v, x,
        is_leaf=lambda v: isinstance(v, Tensor))


def _tree_array_to_tensor(x):
    return jax.tree_util.tree_map(
        lambda v: Tensor(v) if isinstance(v, (jax.Array,)) or hasattr(v, "dtype") else v, x)


class StaticFunction:
    """Result of to_static: jit-compiled callable with .forward parity.

    Data-dependent Python control flow in the wrapped code is AST-converted
    (dy2static.convert_to_static) to lax.cond/lax.while_loop before
    tracing — the reference ProgramTranslator's role
    (dygraph_to_static/program_translator.py:768). Conversion is best
    effort per function: code without retrievable source traces as-is.
    """

    def __init__(self, fn_or_layer, input_spec=None, build_strategy=None):
        from .dy2static import convert_to_static

        self._input_spec = input_spec
        if isinstance(fn_or_layer, Layer):
            self._layer = fn_or_layer
            self._fn = None
            self._orig_call = fn_or_layer.forward  # pre-conversion, bound
            try:
                converted = convert_to_static(fn_or_layer.forward)
                if converted is not type(fn_or_layer).forward:
                    # bind converted forward on the instance (shadows the
                    # class method for this layer only)
                    object.__setattr__(fn_or_layer, "forward", converted)
            except Exception:
                pass  # conversion is best-effort; plain trace still works
        else:
            self._layer = None
            self._orig_call = fn_or_layer
            try:
                self._fn = convert_to_static(fn_or_layer)
            except Exception:
                self._fn = fn_or_layer
        self._compiled = None

    def _make_compiled(self):
        if self._layer is not None:
            layer = self._layer

            def pure(params, buffers, training, args, kwargs):
                out, new_buf = functional_call(layer, params, buffers, *args,
                                               training=training, **kwargs)
                return _tree_tensor_to_array(out), new_buf

            self._compiled = jax.jit(pure, static_argnums=(2,))
        else:
            fn = self._fn

            def pure_fn(args, kwargs):
                args = _tree_array_to_tensor(args)
                kwargs = _tree_array_to_tensor(kwargs)
                return _tree_tensor_to_array(fn(*args, **kwargs))

            self._compiled = jax.jit(pure_fn)

    def __call__(self, *args, **kwargs):
        if not ProgramTranslator._enabled:
            # ProgramTranslator().enable(False): run the ORIGINAL python
            # eagerly (no AST conversion, no jit) so breakpoints/prints in
            # user code fire — reference program_translator.py semantics.
            return self._orig_call(*args, **kwargs)
        if self._compiled is None:
            self._make_compiled()
        arr_args = _tree_tensor_to_array(args)
        arr_kwargs = _tree_tensor_to_array(kwargs)
        if self._layer is not None:
            params, buffers = state(self._layer)
            out, new_buf = self._compiled(params, buffers, self._layer.training,
                                          arr_args, arr_kwargs)
            # write back mutated buffers eagerly
            tensors = _named_state_tensors(self._layer)
            for name, arr in new_buf.items():
                tensors[name]._data = arr
            return _tree_array_to_tensor(out)
        return _tree_array_to_tensor(self._compiled(arr_args, arr_kwargs))

    # Layer-protocol passthrough
    def __getattr__(self, item):
        if self._layer is not None:
            return getattr(self._layer, item)
        return getattr(self._fn, item)


def to_static(function=None, input_spec=None, build_strategy=None, **kwargs):
    """paddle.jit.to_static parity (decorator or call)."""
    if function is None:
        return functools.partial(to_static, input_spec=input_spec,
                                 build_strategy=build_strategy)
    return StaticFunction(function, input_spec, build_strategy)


def not_to_static(fn):
    return fn


class TrainStep:
    """Fused forward+backward+update as one compiled XLA program.

    ``step(*batch)`` runs the whole training step on device and writes the
    updated params/slots back into the eager model. This is the performance
    path — the analog of ParallelExecutor running the rewritten program
    (reference executor.py:998) — while plain eager backward mirrors dygraph.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate: bool = True, grad_postprocess: Optional[Callable] = None,
                 sentinel=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.grad_postprocess = grad_postprocess
        # optional in-jit health sentinel (paddle_tpu.resilience): verdict
        # + trip counter carried as device state, update gated on it
        self._sentinel_cfg = (_sentinel.normalize_config(sentinel)
                              if sentinel else None)
        self.sentinel_state = (_sentinel.init_state()
                               if self._sentinel_cfg is not None else None)
        self._step_count = 0
        self._param_names = [k for k, _ in model.named_parameters()]
        self._params = {k: p for k, p in model.named_parameters()}
        # materialize slots eagerly in deterministic order
        self._slot_values = {}
        for k in self._param_names:
            p = self._params[k]
            self._slot_values[k] = list(self.optimizer._get_slots(p))
        self._hyper = {k: tuple(sorted(self.optimizer._hyper(self._params[k]).items()))
                       for k in self._param_names}
        self._compiled = None
        # fast-step (FLAGS_fast_step) state: donated-buffers jit, cached
        # buffer-tensor refs, cached device lr scalar, lazy optimizer-slot
        # sync marker
        self._compiled_fast = None
        self._buffer_tensors: Dict[str, Tensor] = {}
        self._lr_cache = (None, None)
        # guardian lr_backoff multiplier (scale_lr); 1.0 = untouched
        self._lr_scale = 1.0
        self._slots_dirty = False
        # FLAGS_sanitize: batch aval signatures already compiled — a new
        # one is a recompile; the explainer names the differing leaf
        self._batch_sigs: list = []

    def _build(self):
        model = self.model
        loss_fn = self.loss_fn
        opt = self.optimizer
        param_names = self._param_names
        hyper = self._hyper
        pure_update = type(opt)._pure_update
        grad_post = self.grad_postprocess

        sentinel_cfg = self._sentinel_cfg

        # FLAGS_fused_optimizer (read at build time): run the whole
        # Adam/AdamW update as one flat-buffer pass per dtype bucket
        # (ops/fused_optimizer.py) instead of the per-param loop below —
        # same slot layout, same checkpoint shape, fused execution.
        from ..core.native import fused_optimizer as _fused_opt_flag
        from ..monitor.stats import FUSED_OPTIMIZER_STEPS as _fused_gauge

        use_fused = (_fused_opt_flag[0]
                     and type(opt).__name__ in ("Adam", "AdamW")
                     and opt._slot_names() == ["moment1", "moment2",
                                               "beta1_pow", "beta2_pow"])
        self._use_fused = use_fused
        self._fused_gauge = _fused_gauge
        if use_fused:
            from ..ops.fused_optimizer import fused_update_from_slots

        # loss_fn contract: loss_fn(run_model, *batch_tensors) -> loss Tensor,
        # where run_model(*model_inputs) executes the params-bound model.
        def step_impl(params, slots, buffers, lr, batch, sent_state):
            def loss_of(params):
                args = _tree_array_to_tensor(batch)
                captured = dict(buffers)

                def run_model(*xs, **kw):
                    out, new_buf = functional_call(model, params, captured, *xs,
                                                   training=True, **kw)
                    captured.update(new_buf)
                    return out

                loss = loss_fn(run_model, *args)
                return (loss._data if isinstance(loss, Tensor) else loss), captured

            (loss, new_buffers), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            if grad_post is not None:
                grads = grad_post(grads)
            if use_fused:
                new_params, new_slots = fused_update_from_slots(
                    opt, param_names, params, grads, slots, lr, hyper)
            else:
                new_params = {}
                new_slots = {}
                for k in param_names:
                    h = dict(hyper[k])
                    out = pure_update(params[k],
                                      grads[k].astype(params[k].dtype),
                                      jnp.asarray(lr, jnp.float32),
                                      *slots[k], **h)
                    if not isinstance(out, tuple):
                        out = (out,)
                    new_params[k] = out[0]
                    new_slots[k] = list(out[1:])
            if sent_state is not None:
                # in-jit health verdict + GradScaler-style skip gate
                # (resilience.sentinel): a tripped step is a no-op
                gnorm = _sentinel.global_grad_norm(grads)
                sent_state = _sentinel.update(sent_state, loss, gnorm,
                                              sentinel_cfg)
                trip = sent_state["last_trip"]
                new_params = _sentinel.gate(trip, new_params, params)
                new_slots = _sentinel.gate(trip, new_slots, slots)
                new_buffers = _sentinel.gate(trip, new_buffers, buffers)
            return new_params, new_slots, new_buffers, loss, sent_state

        # pure step exposed for K-steps-in-one-jit timing and custom
        # outer loops — keeps the historical 5-arg/4-output
        # contract (no sentinel state); _compiled is the per-call dispatch
        # path, _compiled_fast additionally donates the buffer tree
        # (FLAGS_fast_step)
        self._step_impl = (
            lambda p, s, b, lr, batch: step_impl(p, s, b, lr, batch,
                                                 None)[:4])
        self._compiled = jax.jit(step_impl, donate_argnums=(0, 1))
        self._compiled_fast = jax.jit(step_impl, donate_argnums=(0, 1, 2))
        self._buffer_tensors = {k: b for k, b in self.model.named_buffers()
                                if b is not None}

    def __call__(self, *batch):
        if self._compiled is None:
            self._build()
        if _faults.ENABLED[0]:
            # fault-injection hook (FLAGS_fault_inject) — see
            # resilience.faults; one list-index check when idle
            batch = _faults.FAULTS.on_train_step(self._step_count, batch)
        self._step_count += 1
        if getattr(self, "_use_fused", False):
            self._fused_gauge.add()
        if _fast_step[0]:
            return self._call_fast(batch)
        params = {k: self._params[k]._data for k in self._param_names}
        buffers = {k: b._data for k, b in self.model.named_buffers() if b is not None}
        lr = self.optimizer.get_lr() * self._lr_scale
        arr_batch = _tree_tensor_to_array(batch)
        donated = None
        if _sanitize[0]:
            self._note_batch_sig(arr_batch)
            donated = (params, {k: list(v)
                                for k, v in self._slot_values.items()})
        new_params, new_slots, new_buffers, loss, self.sentinel_state = \
            self._compiled(params, self._slot_values, buffers, lr, arr_batch,
                           self.sentinel_state)
        if donated is not None:
            _san.tombstone_tree(donated)
        for k in self._param_names:
            self._params[k]._data = new_params[k]
            self._slot_values[k] = new_slots[k]
            self.optimizer._set_slots(self._params[k], new_slots[k])
        tensors = _named_state_tensors(self.model)
        for name, arr in new_buffers.items():
            tensors[name]._data = arr
        return Tensor(loss)

    def _call_fast(self, batch):
        """FLAGS_fast_step path: the bench device loop as framework code.

        Per step: pointer-read the device state (no module-tree walks),
        dispatch the donated step (params AND slots AND buffers — nothing
        is double-buffered), pointer-write the new arrays back into the
        same eager tensors, and return the loss WITHOUT blocking — the
        AsyncLoss handle syncs (and bumps step_async_syncs) only when the
        user reads it. Optimizer slot mirrors are synced lazily
        (:meth:`sync`), since ``_set_slots`` walks per-param dicts the
        step itself never reads."""
        params = {k: self._params[k]._data for k in self._param_names}
        buffers = {k: t._data for k, t in self._buffer_tensors.items()}
        lr = self.optimizer.get_lr() * self._lr_scale
        if self._lr_cache[0] != lr:
            # device-cache the lr scalar: a python-float jit arg is a
            # fresh host->device transfer every step
            self._lr_cache = (lr, jnp.float32(lr))
        arr_batch = _tree_tensor_to_array(batch)
        donated = None
        if _sanitize[0]:
            self._note_batch_sig(arr_batch)
            donated = (params, {k: list(v)
                                for k, v in self._slot_values.items()},
                       buffers)
        new_params, new_slots, new_buffers, loss, self.sentinel_state = \
            self._compiled_fast(params, self._slot_values, buffers,
                                self._lr_cache[1], arr_batch,
                                self.sentinel_state)
        if donated is not None:
            _san.tombstone_tree(donated)
        for k in self._param_names:
            self._params[k]._data = new_params[k]
            self._slot_values[k] = new_slots[k]
        for name, arr in new_buffers.items():
            self._buffer_tensors[name]._data = arr
        self._slots_dirty = True
        out = AsyncLoss(loss)
        if self.sentinel_state is not None:
            out.health = {"trip": self.sentinel_state["last_trip"],
                          "trips": self.sentinel_state["trips"]}
        return out

    def scale_lr(self, scale: float) -> None:
        """Set the ABSOLUTE learning-rate multiplier (TrainGuardian's
        post-rollback backoff). The lr enters the compiled step as a
        traced scalar, so rescaling never recompiles; optimizer
        schedules keep their shape, scaled."""
        self._lr_scale = float(scale)

    def _note_batch_sig(self, arr_batch):
        """FLAGS_sanitize recompile explainer: a batch aval signature not
        seen before means jax recompiles the step — diff it against the
        nearest compiled one and emit a sanitize.recompile span."""
        sig = _san.aval_signature(arr_batch)
        if sig in self._batch_sigs:
            return
        if self._batch_sigs:
            _san.note_recompile("TrainStep", sig, self._batch_sigs)
        self._batch_sigs.append(sig)

    def sync(self):
        """Flush lazily-deferred state mirrors (optimizer slot dicts) so
        host-side readers — optimizer.state_dict(), checkpoint save — see
        the current device state. Called automatically by hapi Model.fit
        at epoch boundaries and by Model.save."""
        if self._slots_dirty:
            for k in self._param_names:
                self.optimizer._set_slots(self._params[k],
                                          self._slot_values[k])
            self._slots_dirty = False


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save parity (reference jit/api.py save): persists
    - ``path.pdparams`` — the state_dict (eager reload), and, when
      ``input_spec`` is given,
    - ``path.pdmodel`` / ``path.pdiparams`` / ``path.pdmeta.json`` — a
      versioned StableHLO inference artifact (static/export.py) servable
      by paddle_tpu.inference.Predictor with no model code."""
    from ..framework.io import save as _save

    if isinstance(layer, StaticFunction):
        layer = layer._layer
    _save(layer.state_dict(), path + ".pdparams")

    if input_spec:
        import numpy as np

        from ..static.export import export_callable, write_artifacts

        params, buffers = state(layer)
        keys = sorted(params) + sorted(buffers)
        n_params = len(params)
        arrays = [params[k] for k in sorted(params)] + \
                 [buffers[k] for k in sorted(buffers)]

        def pure(state_list, *feeds):
            p = dict(zip(sorted(params), state_list[:n_params]))
            b = dict(zip(sorted(buffers), state_list[n_params:]))
            out, _ = functional_call(layer, p, b, *[Tensor(f) for f in feeds],
                                     training=False)
            return _tree_tensor_to_array(out)

        examples = [np.zeros(tuple(1 if (s is None or int(s) < 0) else int(s)
                                   for s in spec.shape),
                             dtype=spec.dtype)
                    for spec in input_spec]
        data, st, meta = export_callable(
            pure, arrays, examples,
            feed_names=[spec.name or f"x{i}"
                        for i, spec in enumerate(input_spec)])
        write_artifacts(path, data, st, meta)


def load(path, **configs):
    """Reference jit.load: returns a TranslatedLayer when jit.save
    artifacts exist at ``path``; falls back to the raw state dict."""
    import os

    if os.path.exists(path + ".pdmodel"):
        return TranslatedLayer(path)
    from ..framework.io import load as _load

    return _load(path + ".pdparams")


class ProgramTranslator:
    """Singleton switch for dy2static (reference
    dygraph_to_static/program_translator.py:768). enable(False) makes
    to_static functions run eagerly."""

    _instance = None
    _enabled = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static=True):
        type(self)._enabled = bool(enable_to_static)

    @property
    def enable_to_static(self):
        return type(self)._enabled


def set_code_level(level=100, also_to_stdout=False):
    """Log transformed code at the given level (reference jit API); the
    AST translator logs through the standard logging module here."""
    import logging

    logging.getLogger("paddle_tpu.dy2static").setLevel(
        logging.DEBUG if level else logging.WARNING)


def set_verbosity(level=0, also_to_stdout=False):
    import logging

    logging.getLogger("paddle_tpu.dy2static").setLevel(
        logging.DEBUG if level else logging.WARNING)


class TranslatedLayer(Layer):
    """Layer reconstructed from jit.save artifacts, served through the
    compiled-program Predictor (reference dygraph/io.py TranslatedLayer)."""

    def __init__(self, path):
        super().__init__()
        from ..inference import Predictor

        self._predictor = Predictor(path)

    def forward(self, *inputs):
        arrs = [x.numpy() if isinstance(x, Tensor) else x for x in inputs]
        outs = [Tensor(jnp.asarray(o)) for o in self._predictor.run(arrs)]
        return outs[0] if len(outs) == 1 else tuple(outs)


class TracedLayer:
    """Trace a dygraph layer into a servable program (reference
    dygraph/jit.py TracedLayer): TracedLayer.trace -> (out, traced);
    traced(x) replays; save_inference_model exports."""

    def __init__(self, layer, input_spec):
        self._layer = layer
        self._input_spec = input_spec

    @staticmethod
    def trace(layer, inputs):
        out = layer(*inputs)
        spec = [InputSpec(list(x.shape), str(x.dtype)) for x in inputs]
        return out, TracedLayer(layer, spec)

    def __call__(self, *inputs):
        return self._layer(*inputs)

    def save_inference_model(self, path, feed=None, fetch=None):
        save(self._layer, path, input_spec=self._input_spec)
