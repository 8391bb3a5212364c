"""Chrome-trace-event JSON exporter (reference platform/profiler.cc
GenEventKernelCudaElapsedTime / DeviceTracer dump → chrome://tracing).

`TraceWriter` accumulates trace events host-side and serializes the
chrome trace-event format (the `{"traceEvents": [...]}` envelope) that
Perfetto / chrome://tracing / `tools/trace_report.py` load directly —
independent of jax.profiler's TensorBoard plugin, so it works on any
backend.

The module-level writer plus the `TRACING` gate are the recording
switch the hot paths check: `apply_op` and `RecordEvent` test
``TRACING[0]`` (one list index) before paying for any span bookkeeping,
so an idle process records nothing and allocates nothing.

Timestamps are `time.perf_counter()` seconds converted to the format's
microseconds — one monotonic clock for every producer keeps spans from
different layers aligned on the same timeline.

While tracing is on (and jax is already imported) every `span` also
enters a ``jax.profiler.TraceAnnotation`` of the same name, so when a
jax profiler session runs beside it the program's spans sit in the same
``.xplane.pb`` as the device ops — the operator's view. `on_stop`
callbacks let a program add what it can only know about itself when the
window closes (``op_scopes``: which phase and ``named_scope`` each
compiled instruction came from — the profiler's device events carry the
bare instruction name and nothing else); a component that runs several
programs keeps the ones a window saw in a `ProgramLog`.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import sys
import threading
import time

__all__ = ["TraceWriter", "TRACING", "FLIGHT", "is_tracing",
           "start_tracing", "stop_tracing", "get_writer", "span",
           "recording", "emit_complete", "emit_instant", "emit_flow",
           "on_stop", "op_scopes", "emit_op_scopes", "ProgramLog"]

# shared mutable gate — hot paths read TRACING[0] directly
TRACING = [False]

# the armed flight recorder (monitor/flight.py) or None — a second
# consumer of span/instant events that stays on across a failure so the
# last seconds before a crash are dumpable even when full tracing is off.
# Kept here (not in flight.py) so span() pays ONE extra list index when
# nothing is armed and flight.py can import without a cycle.
FLIGHT = [None]


class TraceWriter:
    """Thread-safe collector of chrome trace events."""

    def __init__(self, pid: int | None = None):
        self.pid = os.getpid() if pid is None else pid
        self._events: list[dict] = []
        self._lock = threading.Lock()

    # -- event constructors -------------------------------------------------
    def add_complete(self, name: str, ts: float, dur: float,
                     tid: int | None = None, cat: str = "op",
                     args: dict | None = None) -> None:
        """One "X" (complete) event; ts/dur in seconds on the perf_counter
        timeline."""
        ev = {
            "name": name, "ph": "X", "cat": cat, "pid": self.pid,
            "tid": threading.get_ident() & 0x7FFFFFFF if tid is None else tid,
            "ts": int(ts * 1e6), "dur": int(dur * 1e6),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def add_begin(self, name: str, ts: float, tid: int | None = None,
                  cat: str = "op") -> None:
        self._add_mark("B", name, ts, tid, cat)

    def add_end(self, name: str, ts: float, tid: int | None = None,
                cat: str = "op") -> None:
        self._add_mark("E", name, ts, tid, cat)

    def add_instant(self, name: str, ts: float, cat: str = "instant") -> None:
        self._add_mark("i", name, ts, None, cat)

    def _add_mark(self, ph, name, ts, tid, cat):
        with self._lock:
            self._events.append({
                "name": name, "ph": ph, "cat": cat, "pid": self.pid,
                "tid": threading.get_ident() & 0x7FFFFFFF if tid is None
                else tid,
                "ts": int(ts * 1e6),
            })

    def add_metadata(self, name: str, args: dict) -> None:
        """One "M" (metadata) event: a table about the trace itself,
        with no place on the timeline (``op_scopes``)."""
        with self._lock:
            self._events.append({"name": name, "ph": "M", "pid": self.pid,
                                 "tid": 0, "args": args})

    def add_counter(self, name: str, ts: float, values: dict) -> None:
        """One "C" (counter) event — e.g. the stat gauges over time."""
        with self._lock:
            self._events.append({
                "name": name, "ph": "C", "pid": self.pid, "tid": 0,
                "ts": int(ts * 1e6), "args": dict(values),
            })

    def add_flow(self, ph: str, flow_id: int, ts: float,
                 name: str = "request", cat: str = "trace") -> None:
        """One flow event ("s" start / "t" step / "f" finish) with
        ``id=flow_id``. Chrome/Perfetto draw an arrow chain through every
        flow event sharing an id, binding each to the enclosing slice on
        its thread — that chain is what turns per-layer spans into ONE
        connected per-request timeline (ISSUE 15 causal tracing)."""
        ev = {
            "name": name, "ph": ph, "cat": cat, "pid": self.pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "ts": int(ts * 1e6), "id": int(flow_id),
        }
        if ph == "f":
            ev["bp"] = "e"      # bind the finish to the enclosing slice
        with self._lock:
            self._events.append(ev)

    def extend(self, events) -> None:
        with self._lock:
            self._events.extend(events)

    # -- access / export ----------------------------------------------------
    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def __len__(self):
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_json(self) -> str:
        return json.dumps({"traceEvents": self.events(),
                           "displayTimeUnit": "ms"})

    def write(self, path: str) -> str:
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_json())
        return path


_writer = TraceWriter()


def get_writer() -> TraceWriter:
    return _writer


def is_tracing() -> bool:
    return TRACING[0]


def start_tracing(clear: bool = True) -> TraceWriter:
    if clear:
        _writer.clear()
    TRACING[0] = True
    return _writer


def stop_tracing() -> TraceWriter:
    TRACING[0] = False
    with _stop_lock:
        fns, _on_stop[:] = list(_on_stop), []
    for fn in fns:
        try:
            fn(_writer)
        except Exception as e:  # noqa: BLE001 — a table that cannot be
            # made must not cost the caller its trace
            _writer.add_instant(
                "on_stop_failed: %s: %s" % (type(e).__name__, e),
                time.perf_counter())
    return _writer


_on_stop: list = []
_stop_lock = threading.Lock()


def on_stop(fn) -> None:
    """Run ``fn(writer)`` once, at the next ``stop_tracing()``, after the
    gate is off: it writes straight to the writer. An exception becomes
    one instant event naming it and never reaches the caller."""
    with _stop_lock:
        _on_stop.append(fn)


def recording() -> bool:
    """True when anything consumes events: full tracing OR an armed
    flight recorder. Hot paths that pre-compute span args should gate on
    this rather than ``TRACING[0]`` alone."""
    return TRACING[0] or FLIGHT[0] is not None


def emit_complete(name: str, ts: float, dur: float, cat: str = "op",
                  args: dict | None = None, tid: int | None = None) -> None:
    """One complete event to every live consumer (trace writer when
    tracing, flight-recorder ring when armed); ``tid``: the thread whose
    line it lies on, where not the caller's."""
    if TRACING[0]:
        _writer.add_complete(name, ts, dur, tid=tid, cat=cat, args=args)
    rec = FLIGHT[0]
    if rec is not None:
        rec.add_complete(name, ts, dur, tid=tid, cat=cat, args=args)


def emit_instant(name: str, ts: float, cat: str = "instant") -> None:
    if TRACING[0]:
        _writer.add_instant(name, ts, cat=cat)
    rec = FLIGHT[0]
    if rec is not None:
        rec.add_instant(name, ts, cat=cat)


def emit_flow(ph: str, flow_id: int, ts: float,
              name: str = "request") -> None:
    if TRACING[0]:
        _writer.add_flow(ph, flow_id, ts, name=name)
    rec = FLIGHT[0]
    if rec is not None:
        rec.add_flow(ph, flow_id, ts, name=name)


@contextlib.contextmanager
def span(name: str, cat: str = "op", args: dict | None = None,
         flow: int | None = None):
    """Record a span around a block — free when tracing is off (one list
    index) and the flight recorder is unarmed (a second list index).

    ``flow``: a trace/flow id to stamp a flow STEP event at span start,
    chaining this span into its request's causal timeline."""
    if not TRACING[0] and FLIGHT[0] is None:
        yield
        return
    # the same span on the jax profiler's own timeline (a no-op TraceMe
    # unless a profiler session runs); never imports jax itself
    jax = sys.modules.get("jax") if TRACING[0] else None
    note = jax.profiler.TraceAnnotation(name) if jax is not None \
        else contextlib.nullcontext()
    t0 = time.perf_counter()
    if flow is not None:
        # flow events keep the constant "request" name: name-based event
        # filters (reports, tests) must see only the real span under the
        # span's name
        emit_flow("t", flow, t0)
    try:
        with note:
            yield
    finally:
        emit_complete(name, t0, time.perf_counter() - t0,
                      cat=cat, args=args)


# -- instruction -> phase/scope table ---------------------------------------

_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# a scope is named like an identifier: not ``jit(f)``, not an einsum's
# ``bsh,vh->bsv``, not ``branch_0_fun``
_SCOPE = re.compile(r"^(?!branch_\d)[A-Za-z_][\w.\-]*$")
# name-stack entries jax pushes itself: never a user's named_scope
_JAX_STACK = frozenset((
    "while", "body", "cond", "body_pred", "scan", "checkpoint", "remat",
    "rematted_computation", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin", "shard_map",
    "pallas_call", "xla_pmap", "named_call"))


def _scopes_of(op_name: str) -> list:
    """The ``jax.named_scope`` names in an op_name, outermost first:
    ``jit(step)/transpose(jvp(mlp))/ln/mul`` -> [mlp, ln]. A transform
    wraps the scope it traced through (``jvp(mlp)``); ``jit(f)`` is a
    function, the last entry the primitive."""
    out = []
    for part in op_name.split("/")[:-1]:
        m = _WRAPPED.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAPPED.match(part)
        if _SCOPE.match(part) and part not in _JAX_STACK:
            out.append(part)
    return out


def op_scopes(hlo_text: str, known=None) -> dict:
    """{instruction name: "phase/scope"} from ``compiled.as_text()``.

    Phase is ``optimizer`` if a scope of that name is in the
    instruction's ``op_name``, else ``backward`` if ``transpose(`` is
    (forward work recomputed for the backward counts there), else
    ``forward``; scope is the innermost ``named_scope`` (``backward/attn``,
    or the bare phase where there is none). ``known``: the scope names
    that count, where a caller gives them; any other entry is passed
    over, so a Pallas kernel (whose ``name=`` sits on the stack inside
    the scope that called it) takes its caller's scope. An instruction
    the compiler made without metadata (the async copies and slices that
    prefetch an operand), or with an ``op_name`` of its own that is no
    path (``ragged-dot-none``: a TPU's grouped matmul), takes the label
    of the first instruction that uses it; one nothing labelled uses is
    left out, and a reader counts it unplaced."""
    instrs = _HLO_INSTR.findall(hlo_text)
    table = {}
    for name, rest in instrs:
        op = _OP_NAME.search(rest)
        if op is None or "/" not in op.group(1):
            continue
        scopes = _scopes_of(op.group(1))
        if "optimizer" in scopes:
            phase = "optimizer"
            scopes = [s for s in scopes if s != "optimizer"]
        elif "transpose(" in op.group(1):
            phase = "backward"
        else:
            phase = "forward"
        if known is not None:
            scopes = [s for s in scopes if s in known]
        table[name] = phase + "/" + scopes[-1] if scopes else phase
    # users come after definitions: walking back, each labelled user
    # hands its label to the operands that have none (the earliest wins)
    inherited = {}
    for name, rest in reversed(instrs):
        label = table.get(name) or inherited.get(name)
        if label is None:
            continue
        for operand in _OPERAND.findall(rest):
            if operand not in table:
                inherited[operand] = label
    inherited.update(table)
    return inherited


def emit_op_scopes(writer: TraceWriter, program: str, hlo_text: str,
                   known=None, signature: str | None = None) -> None:
    """The ``op_scopes`` metadata event of one compiled program, with the
    ``signature`` it was compiled for where a caller names one."""
    args = {"program": program, "scopes": op_scopes(hlo_text, known)}
    if signature is not None:
        args["signature"] = signature
    writer.add_metadata("op_scopes", args)


_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")


def _aval(x):
    """An argument as a lowering must see it again to find the same
    executable: a jax array as its shape, dtype and weak type, with its
    sharding only where it is committed (an uncommitted array lowered
    with one is another program, and a compile); anything else (numpy
    arrays, scalars) as it is."""
    import jax

    if not isinstance(x, jax.Array):
        return x
    a = jax.typeof(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type,
                                sharding=x.sharding if x.committed else None)


class ProgramLog:
    """The jitted programs a component dispatched in a traced window, each
    kept once, by the avals it was called with, so that the window's
    ``on_stop`` can look its executable up again and write its
    ``op_scopes`` table (the ``DistributedTrainStep`` idiom for a
    component that runs more than one program)."""

    def __init__(self):
        self._kept = {}
        self._lock = threading.Lock()

    def note(self, fn, args, signature) -> bool:
        """For a caller that found ``TRACING[0]`` set: one dict lookup on
        the jitted ``fn`` (one executable: a program at one signature).
        The first time a window sees it, its arguments are kept as avals;
        returns True then."""
        if fn in self._kept:
            return False
        import jax

        avals = jax.tree_util.tree_map(_aval, args)
        with self._lock:
            self._kept.setdefault(fn, (avals, signature))
        return True

    def emit(self, writer: TraceWriter, known=None) -> int:
        """One ``op_scopes`` event for each program kept, named as the
        profiler's ``XLA Modules`` line names its runs (less the ``(N)``),
        with its ``signature``; empties the log. Lowering the kept avals
        again finds jax's own executable: no compiler runs. Returns how
        many tables it wrote."""
        with self._lock:
            kept, self._kept = self._kept, {}
        for fn, (avals, signature) in kept.items():
            text = fn.lower(*avals).compile().as_text()
            emit_op_scopes(writer, _HLO_MODULE.match(text).group(1), text,
                           known, signature=signature)
        return len(kept)
