"""GL001/GL002 — trace hazards inside jit-compiled functions.

Seeds are every function the codebase hands to a tracing transform —
``jax.jit`` (call or decorator, incl. ``functools.partial(jax.jit, …)``),
``pl.pallas_call``, ``jax.custom_vjp``/``defvjp``, ``jax.grad``/
``value_and_grad``/``vjp``, ``shard_map``, the
``lax`` control-flow combinators — and the walk follows local calls,
``self.method`` calls, and imports resolvable inside the linted tree
(``serving/engine.py → models/gpt.py`` etc.). Inside a reachable body:

- **GL001 host sync**: ``.item()``/``.numpy()``/``.tolist()``/
  ``np.asarray``/``float()``/``int()`` applied to a *traced* value (taint
  = function parameters propagated through simple assignments; ``.shape``
  /``len()``-derived values are static under trace and exempt), plus
  ``print`` and ``time.*`` calls, which always run at trace time — the
  compiled program silently bakes in one observation of them.
- **GL002 flag capture**: subscripting a ``core.native`` flag cell
  (``native.fast_step[0]``, or an imported-cell alias) — the branch is
  resolved once at trace time; the flag must be read at dispatch and
  passed in (or used to pick the program) instead.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .lint import Finding, FuncInfo, Project

__all__ = ["check", "find_seeds"]

# attribute tails that mark a tracing transform; bare-name forms accepted
# only for the unambiguous ones
_TRACE_ATTRS = {
    "jit", "pallas_call", "custom_vjp", "grad", "value_and_grad", "vjp",
    "checkpoint", "remat", "shard_map", "scan", "while_loop", "fori_loop",
    "cond", "custom_jvp",
}
_TRACE_BARE = {"jit", "pallas_call", "custom_vjp", "shard_map",
               "value_and_grad", "checkpoint", "remat"}
# which positional args of each transform are traced functions
_FN_ARG_POS = {
    "cond": (1, 2), "fori_loop": (2,), "while_loop": (0, 1),
}

_SYNC_METHODS = {"item", "numpy", "tolist", "block_until_ready"}
_MUT_SAFE_ATTRS = {"shape", "ndim", "dtype", "size", "at"}


def _attr_tail(func) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_trace_call(call: ast.Call) -> Optional[str]:
    """Return the transform tail name when this Call is a tracing
    transform (jax.jit(...), pl.pallas_call(...), ...)."""
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr in _TRACE_ATTRS:
        return f.attr
    if isinstance(f, ast.Name) and f.id in _TRACE_BARE:
        return f.id
    return None


def _partial_trace_decorator(dec: ast.Call) -> bool:
    """@functools.partial(jax.jit, ...) / @partial(jax.jit, ...)"""
    tail = _attr_tail(dec.func)
    if tail != "partial" or not dec.args:
        return False
    first = dec.args[0]
    t = _attr_tail(first)
    return t in _TRACE_ATTRS or t in _TRACE_BARE


class _Resolver:
    """Resolution helper usable both inside a function and at module
    level (decorators / module-level defvjp calls)."""

    def __init__(self, proj: Project, module_relpath: str):
        self.proj = proj
        self.relpath = module_relpath

    def resolve(self, caller: Optional[FuncInfo], expr) -> Optional[FuncInfo]:
        if caller is not None:
            return self.proj.resolve_name(caller, expr)
        if isinstance(expr, ast.Name):
            hit = self.proj.by_module_name.get(self.relpath, {}).get(expr.id)
            if hit is not None and hit.cls is None:
                return hit
        return None


def _static_exempt(call_or_dec: Optional[ast.Call], fi: FuncInfo,
                   bwd_nondiff: int = 0) -> Set[str]:
    """Param names NOT traced: jit static_argnames/static_argnums,
    custom_vjp nondiff_argnums; for a defvjp bwd rule the first
    ``bwd_nondiff`` params are the nondiff args."""
    out: Set[str] = set()
    params = fi.params
    if bwd_nondiff:
        out.update(params[:bwd_nondiff])
    if call_or_dec is None:
        return out
    for kw in call_or_dec.keywords:
        if kw.arg == "static_argnames":
            vals = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    out.add(v.value)
        elif kw.arg in ("static_argnums", "nondiff_argnums"):
            vals = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int) \
                        and v.value < len(params):
                    out.add(params[v.value])
    return out


def _primal_nondiff(primal: Optional[FuncInfo]) -> List[int]:
    """nondiff_argnums positions from the primal's @custom_vjp
    decorator (fwd rule shares the primal signature; the bwd rule
    receives the nondiff args FIRST)."""
    if primal is None:
        return []
    for dec in primal.node.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        tail = _attr_tail(dec.func)
        if tail == "partial" and dec.args:
            if _attr_tail(dec.args[0]) != "custom_vjp":
                continue
        elif tail != "custom_vjp":
            continue
        for kw in dec.keywords:
            if kw.arg == "nondiff_argnums":
                vals = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                return [v.value for v in vals
                        if isinstance(v, ast.Constant)
                        and isinstance(v.value, int)]
        return []
    return []


def find_seeds(proj: Project) -> List[Tuple[FuncInfo, str, Set[str]]]:
    """(function, why, static-param-names) for every statically-visible
    trace root."""
    seeds: List[Tuple[FuncInfo, str, Set[str]]] = []
    seen: Set[Tuple[str, str]] = set()

    def add(fi: Optional[FuncInfo], why: str, static: Set[str]):
        if fi is not None and fi.key not in seen:
            seen.add(fi.key)
            seeds.append((fi, why, static))

    for relpath, mod in proj.modules.items():
        # decorators
        for key, fi in list(proj.functions.items()):
            if key[0] != relpath:
                continue
            for dec in fi.node.decorator_list:
                if isinstance(dec, ast.Call):
                    tail = _attr_tail(dec.func)
                    if tail in _TRACE_ATTRS or tail in _TRACE_BARE:
                        add(fi, f"@{tail}", _static_exempt(dec, fi))
                    elif _partial_trace_decorator(dec):
                        add(fi, "@partial(jit)", _static_exempt(dec, fi))
                else:
                    tail = _attr_tail(dec)
                    if tail in _TRACE_ATTRS or tail in _TRACE_BARE:
                        add(fi, f"@{tail}", set())
        # calls: jax.jit(fn), X.defvjp(fwd, bwd), lax.scan(f, ...), ...
        # attribute the call to its enclosing function for name resolution
        encl: Dict[int, FuncInfo] = {}
        for key, fi in proj.functions.items():
            if key[0] != relpath:
                continue
            for sub in ast.walk(fi.node):
                if sub is not fi.node:
                    encl.setdefault(id(sub), fi)
        res = _Resolver(proj, relpath)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            caller = encl.get(id(node))
            tail = _is_trace_call(node)
            if tail is not None:
                for pos in _FN_ARG_POS.get(tail, (0,)):
                    if pos < len(node.args):
                        tgt = res.resolve(caller, node.args[pos])
                        if tgt is not None:
                            add(tgt, f"{tail}()",
                                _static_exempt(node, tgt))
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "defvjp":
                primal = res.resolve(caller, f.value)
                nondiff = _primal_nondiff(primal)
                if node.args:
                    fwd = res.resolve(caller, node.args[0])
                    if fwd is not None:
                        add(fwd, "defvjp",
                            {fwd.params[i] for i in nondiff
                             if i < len(fwd.params)})
                if len(node.args) > 1:
                    bwd = res.resolve(caller, node.args[1])
                    if bwd is not None:
                        add(bwd, "defvjp",
                            _static_exempt(None, bwd,
                                           bwd_nondiff=len(nondiff)))
    return seeds


def _local_nodes(fn_node):
    """Statements of one function body, NOT descending into nested defs
    (they are separate FuncInfos reached through call edges)."""
    out = []
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        out.append(n)
        stack.extend(ast.iter_child_nodes(n))
    return out


def _names_in(expr) -> Set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def _is_static_expr(expr) -> bool:
    """Expressions whose value is static under trace even when built from
    traced inputs: .shape / .ndim / .dtype chains and len()."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and n.attr in _MUT_SAFE_ATTRS:
            return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id == "len":
            return True
    return False


def _numpy_aliases(mod_tree) -> Set[str]:
    out = set()
    for node in ast.walk(mod_tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def _flag_cell_name(proj: Project, fi: FuncInfo, sub: ast.Subscript
                    ) -> Optional[str]:
    """'fast_step' when ``sub`` reads a core.native flag cell."""
    v = sub.value
    relpath = fi.module.relpath
    if isinstance(v, ast.Name):
        return proj.flag_cells.get(relpath, {}).get(v.id)
    if isinstance(v, ast.Attribute) and isinstance(v.value, ast.Name):
        target = proj.imported_mods.get(relpath, {}).get(v.value.id)
        if target is not None and target.endswith("core/native.py"):
            return v.attr
    return None


def _local_taint(fi: FuncInfo, entry_taint: Set[str]) -> Set[str]:
    """entry taint (params known traced) propagated through simple
    assignments, in line order."""
    tainted = set(entry_taint)
    nodes = [n for n in _local_nodes(fi.node) if isinstance(n, ast.Assign)]
    nodes.sort(key=lambda n: (getattr(n, "lineno", 0),
                              getattr(n, "col_offset", 0)))
    for _ in range(2):               # two passes catch simple reorderings
        for n in nodes:
            if not _is_static_expr(n.value) \
                    and (_names_in(n.value) & tainted):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)
    return tainted


def _callee_taint(fi: FuncInfo, call: ast.Call, target: FuncInfo,
                  tainted: Set[str], is_self_call: bool) -> Set[str]:
    """Which of ``target``'s params receive a tainted value at this call
    site."""
    out: Set[str] = set()
    params = list(target.params)
    if params and params[0] in ("self", "cls") and is_self_call:
        params = params[1:]
    pos = 0
    for a in call.args:
        if isinstance(a, ast.Starred):
            # *args: conservatively taint the remaining params when the
            # starred expr is tainted
            if _names_in(a.value) & tainted:
                out.update(params[pos:])
            break
        if pos < len(params):
            if (_names_in(a) & tainted) and not _is_static_expr(a):
                out.add(params[pos])
        pos += 1
    for kw in call.keywords:
        if kw.arg is None:
            continue                  # **kwargs: unknown mapping
        if kw.arg in target.params \
                and (_names_in(kw.value) & tainted) \
                and not _is_static_expr(kw.value):
            out.add(kw.arg)
    return out


def _iter_calls_and_edges(proj: Project, fi: FuncInfo):
    """Yield (call_node, resolved_target_or_None, is_self_call,
    traced_fn_targets) over one body."""
    for n in _local_nodes(fi.node):
        if not isinstance(n, ast.Call):
            continue
        target = proj.resolve_call(fi, n)
        is_self = isinstance(n.func, ast.Attribute) \
            and isinstance(n.func.value, ast.Name) \
            and n.func.value.id in ("self", "cls")
        traced = []
        t2 = _is_trace_call(n)
        if t2 is not None:
            for pos in _FN_ARG_POS.get(t2, (0,)):
                if pos < len(n.args):
                    tgt = proj.resolve_name(fi, n.args[pos])
                    if tgt is not None:
                        traced.append((tgt, n))
        yield n, target, is_self, traced


def _scan_findings(proj: Project, fi: FuncInfo, why: str,
                   entry_taint: Set[str], findings: List[Finding]) -> None:
    relpath = fi.module.relpath
    np_alias = _numpy_aliases(fi.module.tree)
    tainted = _local_taint(fi, entry_taint)

    def emit(rule, node, detail, msg):
        findings.append(Finding(
            rule, relpath, getattr(node, "lineno", fi.node.lineno),
            fi.qualname, detail, msg))

    for n in _local_nodes(fi.node):
        if isinstance(n, ast.Subscript) and isinstance(n.ctx, ast.Load):
            cell = _flag_cell_name(proj, fi, n)
            if cell is not None:
                emit("GL002", n, f"flag:{cell}",
                     f"native flag cell '{cell}' read inside jit-traced "
                     f"'{fi.qualname}' (reached via {why}): the value is "
                     "baked in at trace time — read it at dispatch and "
                     "pass it in, or select the program on it")
        elif isinstance(n, ast.Call):
            f = n.func
            if isinstance(f, ast.Attribute) and f.attr in _SYNC_METHODS \
                    and not n.args:
                if _names_in(f.value) & tainted:
                    emit("GL001", n, f"sync:.{f.attr}",
                         f".{f.attr}() on a traced value inside "
                         f"jit-traced '{fi.qualname}' (reached via {why}) "
                         "— forces a host round-trip / trace-time "
                         "constant")
            elif isinstance(f, ast.Attribute) \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in np_alias \
                    and f.attr in ("asarray", "array"):
                if any((_names_in(a) & tainted) and not _is_static_expr(a)
                       for a in n.args):
                    emit("GL001", n, f"sync:np.{f.attr}",
                         f"np.{f.attr} on a traced value inside jit-traced "
                         f"'{fi.qualname}' (reached via {why}) — "
                         "materializes the tracer on host")
            elif isinstance(f, ast.Name) and f.id in ("float", "int", "bool") \
                    and len(n.args) == 1:
                a = n.args[0]
                if (_names_in(a) & tainted) and not _is_static_expr(a):
                    emit("GL001", n, f"sync:{f.id}()",
                         f"{f.id}() on a traced value inside jit-traced "
                         f"'{fi.qualname}' (reached via {why}) — host sync "
                         "(use jnp casts / keep it on device)")
            elif isinstance(f, ast.Name) and f.id == "print":
                emit("GL001", n, "sync:print",
                     f"print() inside jit-traced '{fi.qualname}' (reached "
                     f"via {why}) runs at trace time only — use "
                     "jax.debug.print")
            elif isinstance(f, ast.Attribute) \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id == "time" \
                    and f.attr in ("time", "perf_counter", "monotonic",
                                   "sleep", "monotonic_ns", "time_ns"):
                emit("GL001", n, f"sync:time.{f.attr}",
                     f"time.{f.attr}() inside jit-traced '{fi.qualname}' "
                     f"(reached via {why}) observes the clock once at "
                     "trace time")


def check(proj: Project) -> List[Finding]:
    findings: List[Finding] = []
    seeds = find_seeds(proj)

    # phase 1: fixed-point taint propagation over the call graph, with
    # per-call-site argument mapping so static config args stay clean
    taint: Dict[Tuple[str, str], Set[str]] = {}
    why_of: Dict[Tuple[str, str], str] = {}
    queue: List[FuncInfo] = []
    for fi, why, static in seeds:
        t = set(fi.params) - {"self", "cls"} - static
        taint[fi.key] = t
        why_of[fi.key] = why
        queue.append(fi)
    guard = 0
    while queue and guard < 50000:
        guard += 1
        fi = queue.pop()
        entry = taint.get(fi.key, set())
        local = _local_taint(fi, entry)
        for call, target, is_self, traced in _iter_calls_and_edges(proj, fi):
            for tgt in ([(target, call)] if target is not None else []) \
                    + traced:
                t_fi, t_call = tgt
                if t_fi.key == fi.key:
                    continue
                if t_call is call and t_fi is target:
                    add = _callee_taint(fi, call, t_fi, local, is_self)
                else:
                    # a function passed INTO a trace transform here: its
                    # params are traced (minus declared statics)
                    add = set(t_fi.params) - {"self", "cls"} \
                        - _static_exempt(call, t_fi)
                cur = taint.get(t_fi.key)
                if cur is None:
                    taint[t_fi.key] = set(add)
                    why_of[t_fi.key] = (
                        f"{why_of[fi.key]}->{fi.qualname}"
                        if "->" not in why_of[fi.key] else why_of[fi.key])
                    queue.append(t_fi)
                elif not add <= cur:
                    cur |= add
                    queue.append(t_fi)

    # phase 2: one findings scan per reachable function with final taint
    for key in sorted(taint):
        fi = proj.functions[key]
        _scan_findings(proj, fi, why_of.get(key, "jit"), taint[key],
                       findings)
    return findings
