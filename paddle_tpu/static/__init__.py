"""paddle_tpu.static — the static-graph world.

Parity: the reference's Program/Executor stack (framework.py:4393
``Program``, executor.py:1065 ``Executor.run``, backward.py:1406
``append_backward``, optimizer minimize on programs). TPU-native design:
user code builds the graph by calling ordinary ops on symbolic placeholders
(static/graph.py records through the SAME apply_op funnel eager mode uses),
and ``Executor.run`` replays the recorded DAG as ONE jitted XLA program per
(fetch set, feed shapes) — the ProgramDesc interpreter loop (reference
executor.cc:490 op-by-op hot loop) collapses into a single compiled module.

Typical reference workflow that runs unchanged::

    paddle.enable_static()
    x = paddle.static.data("x", [-1, 784])
    y = paddle.static.data("y", [-1, 1], dtype="int64")
    logits = my_layer(x)                    # any eager layers/ops
    loss = F.cross_entropy(logits, y)
    opt = paddle.optimizer.SGD(0.01, parameters=my_layer.parameters())
    opt.minimize(loss)
    exe = paddle.static.Executor()
    exe.run(paddle.static.default_startup_program())
    loss_val, = exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Parameter, Tensor
from ..framework.param_attr import ParamAttr
from ..jit import InputSpec  # noqa: F401
from .control_flow import case, cond, switch_case, while_loop  # noqa: F401
from . import nn  # noqa: F401
from .graph import (  # noqa: F401
    OpRecord, SymbolicTensor, SymExpr, collect_leaves, evaluate_exprs,
)

__all__ = [
    "InputSpec", "Program", "program_guard", "default_main_program",
    "default_startup_program", "data", "Executor", "CompiledProgram",
    "name_scope", "device_guard", "py_func", "save_inference_model",
    "load_inference_model", "gradients", "append_backward", "nn",
    "cond", "while_loop", "BuildStrategy", "ExecutionStrategy", "ParallelEnv",
    "Block", "Operator", "Variable", "ExponentialMovingAverage",
    "ParallelExecutor", "Print", "WeightNormParamAttr", "accuracy", "auc",
    "cpu_places", "cuda_places", "xpu_places", "npu_places", "Scope",
    "create_global_var", "create_parameter", "global_scope", "scope_guard",
    "load", "save", "load_from_file", "save_to_file", "load_program_state",
    "set_program_state", "normalize_program", "serialize_program",
    "serialize_persistables", "deserialize_program",
    "deserialize_persistables",
]

_static_mode = [False]


class Operator:
    """Introspection view over one recorded op (reference framework.py
    Operator: .type, .input_arg_names, .output_arg_names, .attr)."""

    def __init__(self, block: "Block", rec: OpRecord, idx: int):
        self._block = block
        self._rec = rec
        self.idx = idx

    @property
    def type(self):  # noqa: A003
        return self._rec.name

    @property
    def input_arg_names(self) -> List[str]:
        names = []
        for a in self._rec.args:
            if isinstance(a, SymExpr):
                names.append(self._block._name_of_expr(a))
            elif isinstance(a, Tensor):
                names.append(a.name or f"tensor_{id(a)}")
        return names

    @property
    def output_arg_names(self) -> List[str]:
        return [self._block._op_output_name(self._rec, k)
                for k in range(self._rec.n_outputs)]

    def attr(self, name: str):
        return self._rec.attrs.get(name)

    def all_attrs(self) -> Dict[str, object]:
        return dict(self._rec.attrs)

    @property
    def attr_names(self) -> List[str]:
        return list(self._rec.attrs)

    def __repr__(self):
        ins = ", ".join(self.input_arg_names)
        outs = ", ".join(self.output_arg_names)
        return f"{{{outs}}} = {self.type}(inputs=[{ins}], **{self.all_attrs()})"


class Variable:
    """Introspection view over a program value (reference framework.py
    Variable: .name/.shape/.dtype/.persistable)."""

    def __init__(self, name, shape, dtype, persistable=False, tensor=None):
        self.name = name
        self.shape = list(shape)
        self.dtype = dtype
        self.persistable = persistable
        self._tensor = tensor

    def __repr__(self):
        kind = "persist " if self.persistable else ""
        return f"var {self.name} : {kind}{self.shape} {self.dtype}"


class Block:
    """Introspection view over a Program's op list (reference framework.py
    Block). The TPU program is a flat DAG — control flow lives inside
    traced lax.cond/while bodies, not nested blocks — so there is exactly
    one block, matching the reference's global block for the same code."""

    def __init__(self, program: "Program", idx: int = 0):
        self.program = program
        self.idx = idx

    # -- naming --------------------------------------------------------------
    def _op_output_name(self, rec: OpRecord, index: int) -> str:
        i = self.program.ops.index(rec)
        suffix = f".{index}" if rec.n_outputs > 1 else ""
        return f"{rec.name}_{i}.tmp_0{suffix}"

    def _name_of_expr(self, e: SymExpr) -> str:
        if e.kind == "feed":
            return e.name
        if e.kind == "tensor":
            return e.tensor.name or f"tensor_{id(e.tensor)}"
        return self._op_output_name(e.op, e.index)

    # -- reference surface ---------------------------------------------------
    @property
    def ops(self) -> List[Operator]:
        return [Operator(self, rec, i)
                for i, rec in enumerate(self.program.ops)]

    @property
    def vars(self) -> Dict[str, Variable]:
        out = {}
        for name, t in self.program.feed_vars.items():
            out[name] = Variable(name, t._data.shape, str(t._data.dtype))
        for p in self.program.all_parameters():
            n = p.name or f"tensor_{id(p)}"
            out[n] = Variable(n, p._data.shape, str(p._data.dtype),
                              persistable=True, tensor=p)
        for rec in self.program.ops:
            for k in range(rec.n_outputs):
                n = self._op_output_name(rec, k)
                out[n] = Variable(n, (), "unknown")
        return out

    def var(self, name: str) -> Variable:
        v = self.vars.get(name)
        if v is None:
            from ..framework.enforce import NotFoundError

            raise NotFoundError(f"Variable {name!r} is not found in block "
                                f"{self.idx}.")
        return v

    def __repr__(self):
        lines = [f"block {self.idx} {{"]
        for v in self.vars.values():
            lines.append(f"  {v!r}")
        for op in self.ops:
            lines.append(f"  {op!r}")
        lines.append("}")
        return "\n".join(lines)


class Program:
    """A recorded op DAG + feed placeholders + training directives."""

    def __init__(self):
        self.feed_vars: Dict[str, SymbolicTensor] = {}
        self.feed_dynamic: Dict[str, List[int]] = {}  # name -> -1 dim indices
        self.ops: List[OpRecord] = []
        self.train_specs: List[tuple] = []   # (optimizer, loss SymbolicTensor)
        self.random_seed = None

    def global_block(self) -> Block:
        return Block(self, 0)

    def block(self, index: int) -> Block:
        if index != 0:
            from ..framework.enforce import OutOfRangeError

            raise OutOfRangeError(
                f"Program has 1 block (the flat DAG; control flow is traced "
                f"into op bodies), block({index}) does not exist.")
        return Block(self, 0)

    def current_block(self) -> Block:
        return Block(self, 0)

    @property
    def num_blocks(self) -> int:
        return 1

    @property
    def blocks(self) -> List[Block]:
        return [Block(self, 0)]

    def list_vars(self) -> List["Variable"]:
        return list(self.global_block().vars.values())

    def all_parameters(self):
        exprs = [t._expr for t in self.feed_vars.values()]
        exprs += [loss._expr for _, loss in self.train_specs]
        _, tensors = collect_leaves(
            [SymExpr("op", op=op, index=0) for op in self.ops] + exprs)
        return [t for t in tensors if isinstance(t, Parameter)]

    def clone(self, for_test=False):
        p = Program()
        p.feed_vars = dict(self.feed_vars)
        p.ops = list(self.ops)
        p.train_specs = [] if for_test else list(self.train_specs)
        p.random_seed = self.random_seed
        return p

    def to_string(self, throw_on_error=False, with_details=False) -> str:
        return repr(self.global_block())

    def __str__(self):
        return self.to_string()

    def __repr__(self):
        return (f"Program(feeds={list(self.feed_vars)}, ops={len(self.ops)}, "
                f"train_specs={len(self.train_specs)})")


_default_main = [Program()]
_default_startup = [Program()]


def default_main_program():
    return _default_main[0]


def default_startup_program():
    return _default_startup[0]


def _on_op_recorded(rec: OpRecord):
    rec.program = _default_main[0]
    _default_main[0].ops.append(rec)


@contextmanager
def program_guard(main_program, startup_program=None):
    pm, ps = _default_main[0], _default_startup[0]
    _default_main[0] = main_program
    if startup_program is not None:
        _default_startup[0] = startup_program
    try:
        yield
    finally:
        _default_main[0], _default_startup[0] = pm, ps


class FeedTensor(SymbolicTensor):
    """Feed placeholder: ``.shape`` reports -1 for runtime-determined dims
    (reference Variable semantics) instead of a baked build-time constant;
    internal shape inference uses 1 and the executor retraces per concrete
    feed shape."""

    __slots__ = ("_orig_shape",)

    def __init__(self, expr, aval, orig_shape):
        super().__init__(expr, aval)
        self._orig_shape = tuple(orig_shape)

    @property
    def shape(self):
        return list(self._orig_shape)


def data(name, shape, dtype="float32", lod_level=0):
    """Feed placeholder (reference paddle.static.data). dim -1/None means
    runtime-determined: reported as -1 in ``.shape``, exported as a
    symbolic dimension by save_inference_model."""
    from ..framework import dtype as dtypes

    dt = dtypes.convert_dtype(dtype)
    orig = tuple(-1 if (s is None or int(s) < 0) else int(s) for s in shape)
    build = tuple(1 if s == -1 else s for s in orig)
    aval = jax.ShapeDtypeStruct(build, dt)
    t = FeedTensor(SymExpr("feed", name=name, aval=aval), aval, orig)
    t.name = name
    prog = default_main_program()
    prog.feed_vars[name] = t
    prog.feed_dynamic[name] = [i for i, s in enumerate(orig) if s == -1]
    return t


@contextmanager
def name_scope(prefix):
    yield


@contextmanager
def device_guard(device=None):
    """Pipeline-stage placement hint (reference framework.py device_guard);
    stage placement in the TPU build is declared via mesh shardings, so
    this is accepted and ignored."""
    yield


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-python op; see static.nn.py_func (jax.pure_callback)."""
    from .nn import py_func as _py_func

    return _py_func(func, x, out, backward_func, skip_vars_in_backward_input)


def append_backward(loss, parameter_list=None, no_grad_set=None):
    """Static autodiff (reference backward.py:1406). Returns
    [(param, grad_symbol)] — grads become fetchable symbols."""
    if not isinstance(loss, SymbolicTensor):
        raise TypeError("append_backward expects a symbolic loss")
    params = parameter_list or _params_for(loss)
    grad_op = OpRecord(_GradFn(loss, params), [loss._expr], {}, "grad")
    grad_op.n_outputs = len(params)
    out = []
    for i, p in enumerate(params):
        aval = jax.ShapeDtypeStruct(tuple(p._data.shape), p._data.dtype)
        g = SymbolicTensor(SymExpr("op", op=grad_op, index=i, aval=aval), aval)
        g.name = (p.name or f"param{i}") + "@GRAD"
        out.append((p, g))
    return out


class _GradFn:
    """Env-aware op body: dloss/dparams by replaying the loss subgraph
    under jax.grad with the params as traced inputs (XLA CSEs the
    duplicated forward away inside the one jitted replay)."""

    __name__ = "grad"

    def __init__(self, loss, params):
        self.loss_expr = loss._expr
        self.params = params

    def evaluate_with_env(self, feed_env, tensor_env):
        from .graph import grad_of_loss

        return grad_of_loss(self.loss_expr, self.params, feed_env, tensor_env)


def _params_for(loss: SymbolicTensor):
    _, tensors = collect_leaves([loss._expr])
    return [t for t in tensors
            if isinstance(t, Parameter) and getattr(t, "trainable", True)
            and not t.stop_gradient]


class BuildStrategy:
    """reference details/build_strategy.h surface; knobs that map to XLA
    decisions are accepted and recorded (fusion/memory-optimize happen in
    the compiler), the rest are inert parity fields."""

    def __init__(self):
        self.reduce_strategy = "AllReduce"
        self.gradient_scale_strategy = "CoeffNumDevice"
        self.memory_optimize = None
        self.enable_inplace = None
        self.fuse_all_optimizer_ops = False
        self.fuse_elewise_add_act_ops = False
        self.fuse_bn_act_ops = False
        self.enable_auto_fusion = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 100
        self.num_iteration_per_run = 1


class CompiledProgram:
    """reference compiler.py CompiledProgram: program + build/exec strategy.

    ``with_data_parallel`` marks the program for batch-dim sharding over
    the "data" axis of the active mesh — the GSPMD replacement for the
    reference's per-device graph replication (multi_devices_graph_pass);
    Executor.run shards feeds accordingly when a mesh is active.
    """

    def __init__(self, program, build_strategy=None):
        self.program = program
        self.build_strategy = build_strategy or BuildStrategy()
        self.exec_strategy = None
        self._data_parallel = False
        self._loss_name = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, places=None):
        self._data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self.build_strategy = build_strategy
        self.exec_strategy = exec_strategy
        return self


class ParallelEnv:
    """reference dygraph ParallelEnv: rank / world-size / device info from
    the distributed environment (fleet.init or the launcher's env)."""

    def __init__(self):
        from ..distributed import env as _env

        self._rank = _env.get_rank()
        st = _env.get_state()
        topo = st.get("topology")
        self._world_size = topo.world_size() if topo else int(
            __import__("os").environ.get("PADDLE_TRAINERS_NUM", "1"))

    @property
    def rank(self):
        return self._rank

    local_rank = rank

    @property
    def world_size(self):
        return self._world_size

    nranks = world_size

    @property
    def device_id(self):
        return self._rank

    @property
    def current_endpoint(self):
        import os

        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:0")

    @property
    def trainer_endpoints(self):
        import os

        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []


class Executor:
    """Replays recorded programs as jitted XLA modules
    (reference executor.py:607 Executor / :1065 run)."""

    def __init__(self, place=None):
        self.place = place
        self._cache: Dict[tuple, Any] = {}

    # -- internals ----------------------------------------------------------

    def _exec_fetches(self, fetch_exprs, feed_arrays, grads_of=None):
        """One jitted call: fetch values (+ optional grads wrt params).

        Returns (fetch_values, grads, params) where grads aligns with
        params (None when grads_of is None)."""
        from .graph import grad_of_loss

        feeds_needed, tensors = collect_leaves(fetch_exprs)
        # differentiate only trainable, unfrozen Parameters; frozen ones
        # ride along as plain captured tensors
        params = [t for t in tensors
                  if isinstance(t, Parameter) and not t.stop_gradient
                  and getattr(t, "trainable", True)]
        param_ids = {id(p) for p in params}
        other = [t for t in tensors if id(t) not in param_ids]
        key = (tuple((id(e.op), e.index) if e.kind == "op"
                     else (e.kind, e.name, id(e.tensor))
                     for e in fetch_exprs),
               tuple((k, tuple(np.shape(v))) for k, v in sorted(feed_arrays.items())),
               grads_of is not None)
        fn = self._cache.get(key)
        if fn is None:
            loss_expr = grads_of

            def pure(param_arrays, other_arrays, feed_env):
                tensor_env = {id(t): a for t, a in zip(params, param_arrays)}
                tensor_env.update({id(t): a for t, a in zip(other, other_arrays)})
                if loss_expr is not None:
                    grads = grad_of_loss(loss_expr, params, feed_env, tensor_env)
                else:
                    grads = None
                vals = evaluate_exprs(fetch_exprs, feed_env, tensor_env)
                return vals, grads

            fn = jax.jit(pure)
            self._cache[key] = fn
        param_arrays = [p._data for p in params]
        other_arrays = [t._data for t in other]
        vals, grads = fn(param_arrays, other_arrays, feed_arrays)
        return vals, grads, params

    # -- public -------------------------------------------------------------

    def run(self, program=None, feed=None, fetch_list=None, return_numpy=True):
        program = program if program is not None else default_main_program()
        shard_feeds = False
        if isinstance(program, CompiledProgram):
            shard_feeds = program._data_parallel
            program = program.program
        if isinstance(program, InferenceProgram):
            vals = program.exported.run(feed or {})
            want = fetch_list or []
            out = [vals[f.index] if isinstance(f, _FetchHandle) else vals[int(f)]
                   for f in want] if want else vals
            if return_numpy:
                return [np.asarray(v) for v in out]
            return [Tensor(v) for v in out]
        if not isinstance(program, Program):
            raise TypeError(f"cannot run {type(program)}")
        if not program.ops and not program.train_specs and not fetch_list:
            return []  # startup program: params initialize eagerly

        feed = feed or {}
        feed_arrays = {
            k: (v._data if isinstance(v, Tensor) else np.asarray(v))
            for k, v in feed.items()
        }
        if shard_feeds:
            from ..parallel.mesh import get_mesh

            mesh = get_mesh()
            if mesh is not None and "data" in mesh.axis_names:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as PSpec

                sh = NamedSharding(mesh, PSpec("data"))
                feed_arrays = {
                    k: jax.device_put(v, sh)
                    if getattr(v, "ndim", 0) >= 1
                    and v.shape[0] % mesh.shape["data"] == 0 else v
                    for k, v in feed_arrays.items()
                }
        fetch_list = fetch_list or []
        fetch_exprs = []
        for f in fetch_list:
            if isinstance(f, SymbolicTensor):
                fetch_exprs.append(f._expr)
            elif isinstance(f, str) and f in program.feed_vars:
                fetch_exprs.append(program.feed_vars[f]._expr)
            else:
                raise TypeError(f"cannot fetch {f!r}")

        # training directives run like the reference's optimizer ops at the
        # end of the program: grads of pre-update params, then update.
        # Multiple minimize() calls (e.g. GAN d/g) run sequentially, each
        # seeing the previous spec's updates; fetches evaluate with the
        # FIRST spec (pre-any-update), matching op order in the reference.
        fetch_vals = None
        for optimizer, loss in program.train_specs:
            want = fetch_exprs if fetch_vals is None else []
            vals, grads, params = self._exec_fetches(
                want + [loss._expr], feed_arrays, grads_of=loss._expr)
            if fetch_vals is None:
                fetch_vals = vals[:-1]
            grad_of = {id(p): g for p, g in zip(params, grads)}
            if optimizer._parameter_list is None:
                optimizer._parameter_list = list(params)
            for p in optimizer._parameter_list:
                if id(p) in grad_of:
                    p.grad = Tensor(grad_of[id(p)])
            optimizer.step()
            optimizer.clear_grad()
        if program.train_specs:
            if return_numpy:
                return [np.asarray(v) for v in fetch_vals]
            return [Tensor(v) for v in fetch_vals]

        if not fetch_exprs:
            return []
        vals, _, _ = self._exec_fetches(fetch_exprs, feed_arrays)
        if return_numpy:
            return [np.asarray(v) for v in vals]
        return [Tensor(v) for v in vals]

    def close(self):
        pass


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor,
                         legacy_format=False, program=None, **kwargs):
    """Serialize the inference graph (reference fluid/io.py
    save_inference_model).

    Default: versioned StableHLO artifact via jax.export (static/export.py
    — the TPU analog of the reference's ProgramDesc proto,
    framework.proto:234), loadable with zero model-building Python.
    ``legacy_format=True`` writes the round-2 cloudpickle closure instead
    (version-fragile; kept for migration)."""
    if not isinstance(fetch_vars, (list, tuple)):
        fetch_vars = [fetch_vars]
    if not isinstance(feed_vars, (list, tuple)):
        feed_vars = [feed_vars]

    if not legacy_format:
        from .export import export_fetches, write_artifacts

        prog = program or default_main_program()
        data_bytes, state, meta = export_fetches(
            feed_vars, fetch_vars, dynamic_dims=prog.feed_dynamic)
        write_artifacts(path_prefix, data_bytes, state, meta)
        return

    import pickle

    exprs = [t._expr for t in fetch_vars]
    feeds, tensors = collect_leaves(exprs)
    state = {f"__t{i}": np.asarray(t._data) for i, t in enumerate(tensors)}
    meta = {
        "feed_names": [t.name for t in feed_vars],
        "state": state,
    }
    from ..framework.io import save as _save

    _save(meta, path_prefix + ".pdiparams")
    # cloudpickle: op bodies are often closures/partials a plain pickle
    # cannot carry (the reference serializes a ProgramDesc proto instead;
    # our "program" IS the python closure DAG)
    import cloudpickle

    with open(path_prefix + ".pdmodel", "wb") as f:
        cloudpickle.dump(_ExportedProgram(exprs, tensors), f)


class _ExportedProgram:
    """Pickled closure of the fetch DAG; tensors are re-bound on load."""

    def __init__(self, exprs, tensors):
        # replace tensor leaves with indices for pickling
        self.n_tensors = len(tensors)
        idx = {id(t): i for i, t in enumerate(tensors)}
        self.exprs = [_strip(e, idx) for e in exprs]

    def bind(self, arrays):
        return [_rebind(e, arrays) for e in self.exprs]


def _strip(e, idx, memo=None):
    # memo keyed by id(OpRecord): sibling outputs of a multi-output op must
    # reference the SAME op tuple so pickling (and _rebind's dedup)
    # preserves the sharing and the op executes once after load
    memo = memo if memo is not None else {}
    if not isinstance(e, SymExpr):
        return e
    if e.kind == "tensor":
        return ("__tensor__", idx[id(e.tensor)])
    if e.kind == "feed":
        return ("__feed__", e.name)
    if id(e.op) not in memo:
        memo[id(e.op)] = ("__op__", e.op.fn,
                          tuple(_strip(a, idx, memo) for a in e.op.args),
                          tuple(sorted(e.op.attrs.items())), e.op.n_outputs)
    return ("__out__", memo[id(e.op)], e.index)


def _rebind(e, arrays, memo=None, op_memo=None):
    memo = memo if memo is not None else {}
    op_memo = op_memo if op_memo is not None else {}
    if not isinstance(e, tuple) or not e or not isinstance(e[0], str):
        return e
    if e[0] == "__tensor__":
        return SymExpr("tensor", tensor=Tensor(arrays[e[1]]))
    if e[0] == "__feed__":
        return SymExpr("feed", name=e[1])
    if e[0] == "__out__":
        _, op_t, index = e
        key = id(op_t)
        if key not in op_memo:
            _, fn, args, attrs, n_out = op_t
            rec = OpRecord(fn, [ _rebind(a, arrays, memo, op_memo) for a in args],
                           dict(attrs), getattr(fn, "__name__", "op"))
            rec.n_outputs = n_out
            op_memo[key] = rec
        return SymExpr("op", op=op_memo[key], index=index)
    return e


class InferenceProgram(Program):
    """Loaded StableHLO inference artifact; Executor.run executes it
    directly (no symbolic replay — the program is already compiled IR)."""

    def __init__(self, exported):
        super().__init__()
        self.exported = exported


class _FetchHandle:
    """Fetch placeholder for a loaded inference program output index."""

    __slots__ = ("index", "name")

    def __init__(self, index):
        self.index = index
        self.name = f"fetch_{index}"


def load_inference_model(path_prefix, executor, **kwargs):
    """Returns (program, feed_names, fetch_symbols) runnable via
    Executor.run. Understands both the versioned StableHLO format and the
    legacy cloudpickle one."""
    from .export import ExportedInference, is_stablehlo_model, read_artifacts

    if is_stablehlo_model(path_prefix):
        data_bytes, state, meta = read_artifacts(path_prefix)
        exported = ExportedInference(data_bytes, state, meta)
        prog = InferenceProgram(exported)
        fetches = [_FetchHandle(i) for i in range(meta["fetch_count"])]
        return prog, exported.feed_names, fetches

    import pickle

    from ..framework.io import load as _load

    meta = _load(path_prefix + ".pdiparams")
    with open(path_prefix + ".pdmodel", "rb") as f:
        exported = pickle.load(f)
    arrays = [np.asarray(meta["state"][f"__t{i}"])
              for i in range(exported.n_tensors)]
    exprs = exported.bind(arrays)
    prog = Program()
    fetches = [SymbolicTensor(e, None) for e in exprs]
    return prog, meta["feed_names"], fetches


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    from ..framework.core import grad as _grad

    return _grad(targets, inputs, target_gradients, allow_unused=True)


# ---------------------------------------------------------------------------
# places / scope / program-state / serialization surface
# (reference python/paddle/static/__init__.py remaining exports)
# ---------------------------------------------------------------------------

from ..tensor.creation import create_parameter  # noqa: F401,E402
from ..optimizer.optimizer import ExponentialMovingAverage  # noqa: F401,E402
from ..metric import accuracy  # noqa: F401,E402


def cpu_places(device_count=None):
    """List of CPUPlaces (reference framework.py cpu_places); count
    defaults to CPU_NUM=1 like the reference under a TPU runtime."""
    from ..device import CPUPlace

    return [CPUPlace() for _ in range(device_count or 1)]


def cuda_places(device_ids=None):
    """Accelerator places. On this runtime the accelerators are TPU chips:
    returns one place per visible jax device (reference cuda_places
    semantics transposed to the TPU fleet)."""
    import jax

    from ..device import TPUPlace

    devs = jax.devices()
    ids = device_ids if device_ids is not None else range(len(devs))
    return [TPUPlace(int(i)) for i in ids]


def xpu_places(device_ids=None):
    return cuda_places(device_ids)


def npu_places(device_ids=None):
    return cuda_places(device_ids)


class Scope:
    """name → Tensor registry (reference framework/scope.h:52). The traced
    program captures tensors directly, so the scope is bookkeeping for
    save/load parity, not the execution store."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        from ..framework.core import Tensor

        if name not in self._vars:
            self._vars[name] = Tensor(jnp.zeros((), jnp.float32), name=name)
        return self._vars[name]

    def find_var(self, name):
        return self._vars.get(name)

    def set_var(self, name, t):
        self._vars[name] = t


_global_scope = [Scope()]


def global_scope():
    return _global_scope[-1]


@contextmanager
def scope_guard(scope):
    _global_scope.append(scope)
    try:
        yield
    finally:
        _global_scope.pop()


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    """Persistable global variable (reference layers/tensor.py
    create_global_var); registered in the global scope by name."""
    from ..framework import dtype as dtypes
    from ..framework.core import Tensor

    t = Tensor(jnp.full(tuple(int(s) for s in shape), value,
                        dtypes.convert_dtype(dtype)), name=name)
    t.persistable = persistable
    if name:
        global_scope().set_var(name, t)
    return t


def _print_impl(x, message, summarize):
    jax.debug.print((message + " {}") if message else "{}", x)
    return x + 0 if jnp.issubdtype(x.dtype, jnp.number) else x


def Print(input, first_n=-1, message=None, summarize=20,  # noqa: A002,N802
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase="both"):
    """Debug print op (reference controlflow/print_op.cc): prints the
    tensor when the op executes (jax.debug.print inside jit) and passes
    the value through."""
    from ..framework.core import apply_op

    return apply_op(_print_impl, input, message=message or "",
                    summarize=int(summarize), op_name="Print")


def auc(input, label, curve="ROC", num_thresholds=4095,  # noqa: A002
        topk=1, slide_steps=1, ins_tag_weight=None):
    """Batch AUC by threshold histogram (reference metrics/auc_op.cc —
    same bucketed trapezoid estimate). Returns (auc, batch_auc, states)
    with states = (tp, fp, tn, fn) histograms, like the reference's
    stat outputs."""
    from ..framework.core import apply_op

    def _auc(scores, lab, num_thresholds, curve):
        pos_score = scores[:, 1] if scores.ndim == 2 else scores.reshape(-1)
        lab = lab.reshape(-1)
        bins = jnp.clip((pos_score * num_thresholds).astype(jnp.int32), 0,
                        num_thresholds)
        pos = jnp.zeros(num_thresholds + 1).at[bins].add(lab == 1)
        neg = jnp.zeros(num_thresholds + 1).at[bins].add(lab == 0)
        # cumulative from the highest threshold down
        tp = jnp.cumsum(pos[::-1])[::-1]
        fp = jnp.cumsum(neg[::-1])[::-1]
        tot_pos, tot_neg = tp[0], fp[0]
        tpr = tp / jnp.maximum(tot_pos, 1)
        if curve == "PR":
            precision = tp / jnp.maximum(tp + fp, 1)
            a = jnp.trapezoid(precision[::-1], tpr[::-1])
        else:
            fpr = fp / jnp.maximum(tot_neg, 1)
            a = jnp.trapezoid(tpr[::-1], fpr[::-1])
        return a, a, tp, fp, tot_neg - fp, tot_pos - tp

    if curve not in ("ROC", "PR"):
        raise ValueError("curve must be 'ROC' or 'PR'")
    out = apply_op(_auc, input, label, num_thresholds=int(num_thresholds),
                   curve=curve, op_name="auc")
    return out[0], out[1], tuple(out[2:])


def save(program, model_path, protocol=4, **configs):
    """Persist a program's parameters + buffers to <path>.pdparams AND the
    optimizer state of any minimize()'d optimizers to <path>.pdopt
    (reference static/io.py save writes the same pair; the .pdopt file is
    an empty dict when the program has no train_specs)."""
    from ..framework.io import save as _save

    params = program.all_parameters()
    names = [t.name or f"param_{i}" for i, t in enumerate(params)]
    dup = {n for n in names if names.count(n) > 1}
    if dup:
        raise ValueError(
            "static.save: duplicate parameter names %s — give layers "
            "unique name= arguments" % sorted(dup))
    state = {n: np.asarray(t._data) for n, t in zip(names, params)}
    _save(state, model_path + ".pdparams")
    def _np(v):
        return np.asarray(v._data) if isinstance(v, Tensor) else v

    opt_state = {}
    # ALWAYS prefix with the spec index (previously single-spec programs
    # wrote bare keys): a checkpoint then round-trips into a program with
    # a different optimizer-spec count — load matches by prefix and warns
    # about the specs it cannot fill
    for i, (optimizer, _loss) in enumerate(getattr(program, "train_specs",
                                                   [])):
        sd = optimizer.state_dict()
        opt_state.update({f"opt{i}.{k}": _np(v) for k, v in sd.items()})
    _save(opt_state, model_path + ".pdopt")


def load(program, model_path, executor=None, var_list=None):
    """Restore parameters saved by static.save into the program's
    captured tensors, matched by name; optimizer state is restored from
    the .pdopt companion when present."""
    import os

    from ..framework.io import load as _load

    state = _load(model_path + ".pdparams")
    params = program.all_parameters()
    by_name = {(t.name or f"param_{i}"): t for i, t in enumerate(params)}
    for name, arr in state.items():
        if var_list is not None and name not in {
                getattr(v, "name", v) for v in var_list}:
            continue
        if name in by_name:
            by_name[name].set_value(np.asarray(arr))
    if var_list is None and os.path.exists(model_path + ".pdopt"):
        import re
        import warnings

        opt_state = _load(model_path + ".pdopt")
        specs = getattr(program, "train_specs", [])
        # legacy checkpoints from single-spec programs wrote bare keys
        # (no opt0. prefix) — detect and accept them for spec 0
        has_prefixed = any(re.match(r"opt\d+\.", k) for k in opt_state)
        for i, (optimizer, _loss) in enumerate(specs):
            prefix = f"opt{i}."
            sd = {k[len(prefix):]: v for k, v in opt_state.items()
                  if k.startswith(prefix)}
            if not sd and i == 0 and opt_state and not has_prefixed:
                sd = dict(opt_state)
            if sd:
                optimizer.set_state_dict(sd)
            elif opt_state:
                warnings.warn(
                    f"static.load: no optimizer-state entries under prefix "
                    f"'{prefix}' in {model_path}.pdopt (checkpoint has "
                    f"{len(opt_state)} entries) — optimizer spec {i} keeps "
                    "its current state")


def load_program_state(model_path, var_list=None):
    from ..framework.io import load as _load

    return {k: np.asarray(v)
            for k, v in _load(model_path + ".pdparams").items()}


def set_program_state(program, state_dict):
    params = program.all_parameters()
    by_name = {(t.name or f"param_{i}"): t for i, t in enumerate(params)}
    for name, arr in state_dict.items():
        if name in by_name:
            by_name[name].set_value(np.asarray(arr))


def save_to_file(path, content):
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path):
    with open(path, "rb") as f:
        return f.read()


def normalize_program(program, feed_vars, fetch_vars):
    """Prune to the inference graph (reference static/io.py
    normalize_program). The traced Program already contains only reached
    ops; returns the program annotated with the feed/fetch interface."""
    program._normalized_feeds = [getattr(v, "name", v) for v in feed_vars]
    program._normalized_fetches = list(fetch_vars)
    return program


def _export_cached(feed_vars, fetch_vars, program):
    """One export shared by the serialize pair: tracing + StableHLO
    lowering runs once per (program, feeds, fetches)."""
    from .export import export_fetches

    prog = program or default_main_program()
    if not isinstance(fetch_vars, (list, tuple)):
        fetch_vars = [fetch_vars]
    if not isinstance(feed_vars, (list, tuple)):
        feed_vars = [feed_vars]
    # identity-compared cache with no id() keys: feed/fetch var objects
    # are held strongly (tiny wrappers, prevents address-recycling false
    # hits) and parameter buffers via weakref (set_value rebinds t._data,
    # so updates invalidate the cache, and a dead ref is a miss instead
    # of pinning a stale model copy in device memory)
    import weakref

    bufs = [t._data for t in prog.all_parameters()]
    cached = getattr(prog, "_export_cache", None)
    if cached is not None:
        c_feeds, c_fetches, c_refs, c_result = cached
        c_bufs = [r() for r in c_refs]
        if (len(c_feeds) == len(feed_vars) and len(c_fetches) == len(fetch_vars)
                and all(a is b for a, b in zip(c_feeds, feed_vars))
                and all(a is b for a, b in zip(c_fetches, fetch_vars))
                and len(c_bufs) == len(bufs)
                and all(a is not None and a is b
                        for a, b in zip(c_bufs, bufs))):
            return c_result
    result = export_fetches(feed_vars, fetch_vars,
                            dynamic_dims=prog.feed_dynamic)
    try:
        refs = [weakref.ref(b) for b in bufs]
    except TypeError:
        refs = [(lambda v: (lambda: v))(b) for b in bufs]  # non-weakrefable
    prog._export_cache = (list(feed_vars), list(fetch_vars), refs, result)
    return result


def serialize_program(feed_vars, fetch_vars, program=None, **kwargs):
    """Program → bytes (reference static/io.py serialize_program): the
    versioned StableHLO export WITHOUT weights."""
    import pickle

    data, state, meta = _export_cached(feed_vars, fetch_vars, program)
    return pickle.dumps({"data": data, "meta": meta})


def serialize_persistables(feed_vars, fetch_vars, executor=None,
                           program=None, **kwargs):
    """Weights → bytes, companion of serialize_program."""
    import pickle

    data, state, meta = _export_cached(feed_vars, fetch_vars, program)
    return pickle.dumps([np.asarray(a) for a in state])


def deserialize_program(data):
    """bytes → runnable program shell; weights arrive via
    deserialize_persistables (reference static/io.py pairing)."""
    import pickle

    blob = pickle.loads(data)
    prog = InferenceProgram(None)
    prog._pending = blob
    return prog


def deserialize_persistables(program, data, executor=None):
    """Attach serialized weights to a deserialize_program shell, making it
    runnable by Executor (fetches via program.fetch_handles())."""
    import pickle

    from .export import ExportedInference

    state = pickle.loads(data)
    blob = getattr(program, "_pending", None)
    if blob is None:
        raise ValueError("program was not produced by deserialize_program")
    blob["meta"]["n_state"] = len(state)
    program.exported = ExportedInference(blob["data"], state, blob["meta"])
    program._pending = None
    return program


class ParallelExecutor:
    """reference parallel_executor.py shim: multi-device execution is
    GSPMD batch sharding (CompiledProgram.with_data_parallel); this class
    keeps the constructor/run surface."""

    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None):
        self._program = main_program or default_main_program()
        self._compiled = CompiledProgram(
            self._program, build_strategy).with_data_parallel(
                loss_name=loss_name, exec_strategy=exec_strategy)
        self._exe = Executor()

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        return self._exe.run(self._compiled, feed=feed or feed_dict,
                             fetch_list=fetch_list, return_numpy=return_numpy)


from ..framework.param_attr import WeightNormParamAttr  # noqa: F401,E402
