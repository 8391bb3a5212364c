"""Continuous-batching inference engine (Orca-style) over the paged KV cache.

Request lifecycle::

    submit() ──► bounded queue ──► [admit: a free slot + blocks for the prompt]
                                        │   chunked prefill, a chunk a turn
        stream()/result() ◄── tokens ◄──┤  one jitted decode step per tick,
                                        │  batched over ALL occupied slots
                  [evict: eos / max_tokens / deadline / cancel / capacity]

A single scheduler thread owns the device state (params, cache buffers,
jit calls); ``submit`` may be called from any thread and only touches the
queue. Each turn the scheduler (1) admits waiting requests into free
slots, (2) advances every admitted-but-unprefilled slot by at most
``prefill_chunk`` tokens (``serving.prefill_chunk`` spans), streaming the
first token when a prompt's last chunk lands, and (3) runs ONE compiled
decode step over the whole slot batch, so a late arrival starts
generating next tick without draining anyone and a long prompt delays
open streams by one chunk's work a turn, not its full length (the
reference's AnalysisPredictor has no such path; batching there is
caller-side). Finished sequences release their slot between ticks; the
batch never stalls on the longest request.

One decode tick stays in flight: step (3) dispatches tick n+1 from the
host's projected state (each lane of tick n one token on, its input
tick n's sampled tokens on the device) and only then reads tick n's
tokens and emits them, so the read, the emit and the next turn's host
work run while the device runs tick n+1. A lane whose token in flight
is its last sits tick n+1 out; one found finished only at the read has
its tick n+1 result discarded. The turn reads synchronously instead
when it observes what needs the tokens first (``_may_go_ahead``).

One cache, one set of programs. The target model is served from a
:class:`~paddle_tpu.serving.kv_cache.PagedKVCache` block pool and
reached through ``cfg.serving_model()`` (models/serving_api.py). Jit
surface in steady state: a decode step at the fixed ``(n_slots,)`` batch
shape, one per block-table width bucket (``_decode_paged_fn``), and a
prefill chunk per block-padded chunk length and table width
(``_chunk_fn``). The pool's arrays are donated through both, so serving
allocates nothing per token. What a block of the pool is the model
says: a run of ``block_size`` tokens' cached rows, or (a model whose
layers keep a recurrent state) one sequence's whole state, of which a
slot owns exactly one: then there is one decode program, a chunk
program per padded chunk length, and admission is by free states.

Admission is by block capacity: a prompt up to ``cfg.seq_len - 1``
tokens is admitted whenever enough free blocks exist, and otherwise
waits at the head of the queue until evictions free blocks
(queue-until-available backpressure). If generation outruns the pool,
the youngest slot is preempted back to the queue
(``serving_preemptions`` gauge) and later resumes by re-prefilling its
prompt + generated prefix — output streams are unaffected.

Speculative decoding (ISSUE 10, ``InferenceEngine(draft=(draft_cfg,
draft_params), spec_k=k)``): a small draft model (its OWN fixed-slot
:class:`~paddle_tpu.serving.kv_cache.KVCache`, filled chunk by chunk
alongside the target's pool) proposes k tokens per slot per tick, and
the target model scores all k+1 positions in ONE batched verify pass
(``gpt_verify_step_paged``). Acceptance follows the standard
rejection-sampling rule (serving.sampling.spec_accept), so
temperature/top-k/top-p sampling keeps the target distribution exactly
and greedy output is token-identical to ``draft=None`` — the whole
propose+verify+accept tick is one compiled program, so a tick emits up
to k+1 tokens per stream for one dispatch. Draft contract: same
vocabulary, gpt_init-layout params (``models.gpt_truncate`` builds a
layer-truncated draft from the target for free). Rejected positions
leave stale K/V past the accepted length, which the position masks hide
until the next step overwrites them; the accepted length drives the
same block accounting as the plain path, with tables grown
(non-preemptively) to k+1 tokens of headroom — when a slot cannot get
spec headroom the tick falls back to the plain one-token program.

Multi-chip decode (ISSUE 10, ``FLAGS_serving_mesh=D`` or
``InferenceEngine(mesh=...)``): decode slots shard over the mesh "data"
axis and weights shard Megatron-style over "model"
(models.gpt_param_specs transfers directly — the decode step is a pure
function over the param pytree), so one jitted tick runs over the whole
mesh with GSPMD deriving the collectives. The pool partitions its
blocks into per-shard ranges (per-shard free lists + garbage sinks; see
PagedKVCache(shards=D)), the draft's fixed cache shards its slot dim,
and admission places each request in the shard with the most free blocks.
``FLAGS_serving_mesh=0`` (default) with no explicit mesh keeps the
single-chip engine unchanged.

Prefix sharing (ISSUE 11, ``FLAGS_prefix_cache=1`` or
``InferenceEngine(prefix_cache=True)``): admission
walks a host-side radix tree of cached prompt prefixes
(serving.prefix_cache.RadixPrefixCache). A hit splices the matched
(refcounted) pool blocks straight into the new slot's block table and
only the uncached TAIL is prefilled — chunked, through
``gpt_prefill_prefix``, which continues from an arbitrary (not
necessarily block-aligned) cached length; a partially-used last block
is copy-on-write duplicated first (one compiled ``_cow_jit`` pool-row
copy), since tree blocks are read-only to everyone but their original
writer. Releasing a slot unrefs its blocks instead of freeing them, a
fully-prefilled prompt is inserted back into the tree, and when the
pool runs dry the scheduler reclaims LRU tree leaves BEFORE falling
back to youngest-first preemption. Greedy output is pinned
token-identical to the cache-cold engine. Not combinable with
``draft=`` (the draft's fixed cache has no K/V for a skipped prefix —
sharing would force a full draft prefill and erase the win).

Constrained decoding (ISSUE 11, ``submit(constraint=...)`` with a
serving.constrained.TokenConstraint): each constrained request carries
a byte-DFA cursor; its per-state token mask rides into the SAME jitted
sampling program as a (slots, vocab) bool input, composing with
temperature/top-k/top-p, and the cursor advances host-side per emitted
token. A completed match stops the stream (finish_reason ``"stop"``).
Ticks whose batch holds a constrained row drop from the speculative to
the plain one-token program (counted by ``constrained_fallback_ticks``)
— a draft proposing through an automaton would otherwise get
unconstrained tokens accepted.

Overload hardening (ISSUE 13): deadlines propagate end to end —
``submit(deadline_s=...)`` stamps an absolute monotonic deadline, and a
request that expires while QUEUED is shed at the next tick before any
prefill is spent on it (``serving_deadline_sheds``; the front end turns
an empty-handed deadline finish into 503 + Retry-After). An attached
:class:`~paddle_tpu.serving.overload.OverloadController` (``overload=``)
gets queue-wait and tick-latency observations and steps the brownout
ladder: rung 1 drops speculative decode, rung 2 shrinks prefill chunks;
lane-aware rungs (token caps, sheds) are applied by the front end.
``overload=None`` (default) is pinned bit-identical. A
:class:`~paddle_tpu.serving.router.EngineRouter` fronts N replicas:
``replica_id`` tags this engine's spans and fault specs, ``failover``
holds the router's adoption hook (stamped onto every request), and
``adopt_request`` replays another replica's stream here through the
preemption-resume contract, token-identical because replicas share the
seed and the request keeps its rid. The replica lifecycle (ISSUE 14,
serving/lifecycle.py) adds three supervisor-facing hooks:
``warm_prefix`` (prefill-only radix re-warm in a dedicated rid space),
``evacuate`` (fail every open stream with :class:`ReplicaEvacuated` so
the router migrates them — the drain-shrink terminal step), and
``fail_at_tick`` (deterministic crash for replica_flap chaos / manual
replica kills).

Observability: gauges serving_queue_depth / serving_slot_occupancy /
serving_prefill_ms / serving_decode_ms / serving_tokens_per_s (sliding
window over the last N ticks) / serving_evictions /
serving_preemptions, kv_blocks_free / kv_blocks_used /
kv_fragmentation from the block pool, spec_proposed / spec_accepted /
spec_acceptance_rate from the speculative path and serving_shards for
the mesh, plus ``serving.prefill_chunk`` /
``serving.decode_step`` trace spans (decode spans carry
proposed/accepted and per-shard load args) that ``tools/trace_report.py``
turns into prefill-vs-decode, prefill-starvation, speculation and
shard-balance verdicts.

Observability v2 (ISSUE 15): latency HISTOGRAMS recorded at the source
(serving_first_token_ms / serving_per_token_ms / serving_queue_wait_ms
/ serving_decode_tick_ms / serving_prefill_chunk_ms — live under the
front end's Prometheus ``GET /metrics``).
serving_prefill_chunk_ms times only the asynchronous DISPATCH of a chunk
(about a millisecond whatever the chunk costs the device: nothing in the
span waits for it), and serving_decode_tick_ms (like the ``tick_ms`` the
overload controller and the watchdog see) runs from the tick's dispatch
to its tokens on the host, so it INCLUDES the device time of a chunk or
a tick queued ahead of it, and the turn between a tick left in flight
and its read; the device's own times are on the
profiler's trace (the benchmark's ``decode_tick_ms.serve`` /
``prefill_chunk_ms.serve``). CAUSAL TRACING — a request
submitted with ``trace=TraceContext`` stamps every span it touches
(each prefill chunk, each decode tick via per-request
``serving.decode_tick`` events, the ``serving.failover_hop`` of an
adoption, ``serving.request_done``) with its trace id + flow events,
so one request renders as one connected chrome-trace timeline across
replicas (``tools/trace_report.py --section request``); and the CRASH
FLIGHT RECORDER — ``flight_dir=`` arms a process-wide bounded ring of
recent spans/gauge deltas that ``_abort`` and the watchdog-restart
path dump as self-contained chrome-trace files at the moment of
failure (pod-aware naming, multi-host merge in trace_report).

The program read off its own trace (ISSUE 26): while anything records,
every scheduler turn is one span tree — ``serving.turn`` (admission to
the end of the decode tick) ⊃ ``serving.admit``, ``serving.prefill_chunk``,
``serving.first_token`` (the eager slice + sample + ``int()`` of a
prompt's first token: where the host waits for the chunk),
``serving.decode_prep`` (sweep, grow, host arrays, block tables),
``serving.decode_step`` ⊃ ``serving.device_wait`` (the blocking read of
the tokens of the tick in flight, dispatched a turn before, or of this
turn's tick when it is read at once; a turn that dispatches no tick
reads in the turn itself), ``serving.emit`` (push / finish / evict /
gauges of the tick read) — each carrying the turn's id as ``tick``.
``serving.decode_step``'s ``ahead`` is 1 where the tick left with
another unread (``serving_decode_ticks_ahead``; else
``serving_decode_ticks_synced``); ``serving_decode_lanes_discarded``
counts lane results never pushed. Every request, with or without
a front-end TraceContext, leaves one chain keyed by ``rid``:
``serving.queue_wait`` (submit → admit), ``serving.admit_to_first``
(admit → first token, with its ``chunks``), ``serving.request_done``.
Span args are built only under ``recording()``; ``serving_prefill_chunks``
counts prefill chunks, always. ``serving.decode_step``
carries ``decode_blocks_live`` (the active slots' table entries) and
``decode_blocks_tabled`` (``n_slots`` x the tick's table width), which
``serving_decode_blocks_live`` / ``_tabled`` sum, ``kv_rows_written``
(active lanes x layers: the rows the tick's writer puts into the paged
pool, summed by ``serving_kv_rows_written``) beside ``kv_rows_grid``
(``n_slots`` x layers: what a writer with a step for every lane would
put), and ``sample_path``
(``greedy`` / ``select`` / ``sort``: which way the tick's sampling goes,
by its rows' parameters; ``serving_sample_ticks_<path>`` count the ticks
of each). ``serving.device_wait`` names the turn whose tick it reads
(``reads``). The scheduler's stretch with no work (no queue, no open
slot, no tick in flight, no host call) is one ``serving.idle`` span, no
``rid``, emitted when the stretch ends. The jitted programs carry
``jax.named_scope``s (``kv_pool``, ``sampling``, and the model's
``embed`` / ``ln`` / ``attn`` / ``mlp`` / ``router`` / ``experts`` /
``retention`` / ``head``). Each signature of a step program (a table
width; a chunk's padded length and width) is a jit of its own, named for
it by ``_program`` (``jit__decode_paged_fn_w64`` on the profiler's
``XLA Modules`` line), so a run on a trace names its executable. While
tracing, each dispatch looks its jit up in the window's
``monitor.trace.ProgramLog`` (the first sighting keeps the avals and
registers one ``on_stop`` callback a window); when the window stops, the
callback writes the idle stretch still open, one ``op_scopes`` table for
each program kept (with its ``signature``), a ``serving.op_scopes`` span
over its own time and one ``serving_engine`` event (the spans the engine
records, the callback's ``seconds``). It runs on the thread that stops
the trace while the scheduler serves on; the lookups find jax's
executables, so no compiler runs. Tracing off, a dispatch pays one list
index.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import native
from ..monitor.stats import (CONSTRAINED_FALLBACK_TICKS,
                             CONSTRAINED_REQUESTS, FAULTS_INJECTED,
                             MOE_ASSIGNMENTS_HELD, MOE_ASSIGNMENTS_ROUTED,
                             MOE_EXPERT_LOAD, MOE_EXPERT_READS,
                             MOE_KERNEL_TILES,
                             MOE_EXPERT_SHARE_PCT, MOE_TOKENS_DROPPED,
                             PREFIX_COW_COPIES, SERVING_DEADLINE_SHEDS,
                             SERVING_DECODE_BLOCKS_LIVE,
                             SERVING_STATE_SLOTS_LIVE,
                             SERVING_DECODE_BLOCKS_TABLED,
                             SERVING_DECODE_LANES_DISCARDED,
                             SERVING_DECODE_MS, SERVING_DECODE_TICK_MS,
                             SERVING_DECODE_TICKS_AHEAD,
                             SERVING_DECODE_TICKS_SYNCED,
                             SERVING_EVICTIONS, SERVING_FIRST_TOKEN_MS,
                             SERVING_KV_ROWS_WRITTEN,
                             SERVING_PER_TOKEN_MS, SERVING_PREEMPTIONS,
                             SERVING_PREFILL_CHUNK_MS, SERVING_PREFILL_CHUNKS,
                             SERVING_PREFILL_MS,
                             SERVING_QUEUE_DEPTH, SERVING_QUEUE_WAIT_MS,
                             SERVING_SAMPLE_TICKS_GREEDY,
                             SERVING_SAMPLE_TICKS_SELECT,
                             SERVING_SAMPLE_TICKS_SORT,
                             SERVING_SHARDS, SERVING_SLOT_OCCUPANCY,
                             SERVING_TOKENS_PER_S,
                             SERVING_WATCHDOG_RESTARTS,
                             SERVING_WATCHDOG_TRIPS,
                             SPEC_ACCEPTANCE_RATE, SPEC_ACCEPTED,
                             SPEC_PROPOSED)
from ..resilience import faults as _faults
from ..resilience.sentinel import logits_finite
from ..monitor.flight import arm_flight_recorder, dump_flight
from ..monitor.trace import (TRACING, ProgramLog, emit_complete, emit_flow,
                             on_stop, recording, span)
from .kv_cache import KVCache, PagedKVCache
from .prefix_cache import RadixPrefixCache
from .sampling import (DRAFT_SALT, sample_one, sample_path,
                       sample_tokens_streams, spec_accept, stream_keys)

__all__ = ["InferenceEngine", "GenerationRequest", "QueueFull",
           "WatchdogTripped", "ReplicaEvacuated"]

_CACHE_SPEC = P("data", None, "model", None, None)

# rid floor of the prefix re-warm request space (lifecycle.py): warm
# prefills draw RNG streams that can never collide with, or shift the
# numbering of, live request ids — rejoined replicas stay token-identical
_WARM_RID_BASE = 2**30

# _prep_decode: a projected table cannot grow from free blocks alone
_NO_ROOM = object()

# the named_scopes of the engine's programs that their op_scopes tables
# keep: the models' layers and the sampler (a Pallas kernel's own name,
# which jax puts on the stack, passes to the scope that called it)
_SCOPES = frozenset(("embed", "ln", "attn", "kv_pool", "retention", "mlp",
                     "router", "experts", "head", "sampling"))
# every span the engine records, as its serving_engine event lists them
_SPANS = ("serving.turn", "serving.admit", "serving.prefill_chunk",
          "serving.first_token", "serving.decode_prep",
          "serving.decode_step", "serving.device_wait", "serving.emit",
          "serving.idle", "serving.queue_wait", "serving.admit_to_first",
          "serving.request_done", "serving.decode_tick",
          "serving.failover_hop", "serving.op_scopes")


class QueueFull(RuntimeError):
    """submit() backpressure: the bounded request queue is at capacity."""


# finish reasons
EOS = "eos"
LENGTH = "length"
DEADLINE = "deadline"
CANCELLED = "cancelled"
SHUTDOWN = "shutdown"
ERROR = "error"
STOP = "stop"        # constrained decoding: the token-mask automaton
#                      reached a complete match — nothing more to emit
WATCHDOG = "watchdog"  # the per-tick NaN sentinel found this stream's
#                        logits poisoned; the engine restarted around it


class WatchdogTripped(RuntimeError):
    """Carried as the ``error`` of a request the serving watchdog failed:
    its decode logits went non-finite (poisoned KV/weights/activations).
    Healthy streams in the same batch are resumed, token-identical."""


class ReplicaEvacuated(RuntimeError):
    """Raised by the scheduler when :meth:`InferenceEngine.evacuate` asks
    it to stop: every open stream fails with this cause, which a router
    failover hook turns into survivor adoption (token-identical replay) —
    the drain-shrink terminal step of the replica lifecycle (ISSUE 14)."""


class GenerationRequest:
    """Per-request future returned by :meth:`InferenceEngine.submit`.

    Tokens stream in as the scheduler generates them: ``stream()`` yields
    them live, ``result()`` blocks for the full list, ``finish_reason``
    says why generation stopped (eos/length/deadline/cancelled/shutdown).
    Engines built with a tokenizer also offer ``stream_text()`` /
    ``text()`` — live detokenized text (specials skipped, split utf-8
    sequences held until complete).
    """

    def __init__(self, prompt, max_new_tokens: int, temperature: float,
                 top_k: int, top_p: float, eos_id: Optional[int],
                 deadline: Optional[float], constraint=None):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = eos_id
        self.deadline = deadline          # absolute time.monotonic() or None
        self.constraint = constraint      # ConstraintCursor (scheduler-owned)
        self.rid = 0                      # engine-assigned request id: the
        #                                   RNG stream identity (sampling.py)
        self.trace = None                 # TraceContext (ISSUE 15) or None:
        #                                   the request's causal identity,
        #                                   surviving failover/rejoin hops
        self.tokens: List[int] = []       # generated ids (includes eos)
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self._cancelled = False
        self._t_first = None              # monotonic time of the first token
        self._tokenizer = None            # set by engines with a text front end
        # preemption: (cached-prefix tokens, last token) to re-prefill
        # from when the request is re-admitted
        self._resume = None
        # EngineRouter failover hook: called (req, err) when the OWNING
        # replica dies; True = a survivor adopted this request and the
        # error must NOT finish it (see router.py)
        self._failover = None
        self._t_submit = 0.0              # monotonic enqueue time (queue-wait)
        self._cv = threading.Condition()

    # -- scheduler side ------------------------------------------------------
    def _push(self, tok: int) -> None:
        with self._cv:
            self.tokens.append(tok)
            if self._t_first is None:
                self._t_first = time.monotonic()
                if self._t_submit:
                    SERVING_FIRST_TOKEN_MS.observe(
                        (self._t_first - self._t_submit) * 1e3)
            self._cv.notify_all()

    def _finish(self, reason: str, error: Optional[BaseException] = None):
        if reason == ERROR and self._failover is not None:
            # replica-level death (never a per-request verdict like
            # watchdog/deadline): offer the stream to the router before
            # failing it — adoption replays it on a survivor
            handler, self._failover = self._failover, None
            try:
                if handler(self, error):
                    return          # adopted: a survivor owns this now
            except BaseException:  # noqa: BLE001 — failover must never mask
                pass               # the original error; fall through to it
        finished = False
        with self._cv:
            if self.finish_reason is None:
                self.finish_reason = reason
                self.error = error
                finished = True
            self._cv.notify_all()
        if not finished:
            return
        if self._t_first is not None and len(self.tokens) >= 2:
            # the steady-state inter-token rate the client saw, stalls
            # and failover hops included
            SERVING_PER_TOKEN_MS.observe(
                (time.monotonic() - self._t_first) * 1e3
                / (len(self.tokens) - 1))
        if recording():
            # the last link of the request's chain (queue_wait ->
            # admit_to_first -> request_done), keyed by rid whether or
            # not a front end minted a TraceContext
            t = time.perf_counter()
            args = {"rid": self.rid, "reason": reason,
                    "tokens": len(self.tokens)}
            if self.trace is not None:
                args = self.trace.args(**args)
            emit_complete("serving.request_done", t, 0.0, cat="serving",
                          args=args)
            if self.trace is not None:
                emit_flow("f", self.trace.trace_id, t)

    # -- user side -----------------------------------------------------------
    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    def cancel(self) -> None:
        """Ask the scheduler to drop this request at its next tick (or at
        admission, if still queued)."""
        self._cancelled = True

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until generation stops; returns the generated ids (the
        tokens produced before an eviction are kept — a deadline/cancel
        result is the partial sequence)."""
        with self._cv:
            if not self._cv.wait_for(lambda: self.finish_reason is not None,
                                     timeout):
                raise TimeoutError("generation still in progress")
        if self.error is not None:
            raise RuntimeError("generation failed") from self.error
        return list(self.tokens)

    def stream(self, timeout: Optional[float] = None):
        """Yield token ids as they are generated; returns when finished."""
        i = 0
        while True:
            with self._cv:
                if not self._cv.wait_for(
                        lambda: len(self.tokens) > i
                        or self.finish_reason is not None, timeout):
                    raise TimeoutError("generation still in progress")
                fresh = self.tokens[i:]
                finished = self.finish_reason is not None
            for t in fresh:
                yield t
            i += len(fresh)
            if finished and i >= len(self.tokens):
                if self.error is not None:
                    raise RuntimeError("generation failed") from self.error
                return

    def stream_text(self, timeout: Optional[float] = None):
        """Yield decoded text pieces as tokens arrive (engine must have a
        tokenizer). Special ids are skipped; a token that ends mid-utf-8
        is held until its sequence completes."""
        if self._tokenizer is None:
            raise RuntimeError("engine has no tokenizer — pass "
                               "InferenceEngine(tokenizer=...)")
        detok = self._tokenizer.stream_detokenizer()
        for tok in self.stream(timeout):
            piece = detok.push(tok)
            if piece:
                yield piece
        tail = detok.flush()
        if tail:
            yield tail

    def text(self, timeout: Optional[float] = None) -> str:
        """Block until generation stops; returns the decoded text."""
        if self._tokenizer is None:
            raise RuntimeError("engine has no tokenizer — pass "
                               "InferenceEngine(tokenizer=...)")
        return self._tokenizer.decode(self.result(timeout),
                                      skip_special=True)


class _HostCall:
    """One cross-thread closure parked for the scheduler
    (:meth:`InferenceEngine.run_on_scheduler`): the result/error slot
    plus a completion event the submitting thread blocks on."""

    __slots__ = ("fn", "result", "error", "done")

    def __init__(self, fn):
        self.fn = fn
        self.result = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()

    def run(self, eng) -> None:
        try:
            self.result = self.fn(eng)
        except BaseException as e:  # noqa: BLE001 — a host-call error is
            self.error = e          # the caller's, never a scheduler crash
        self.done.set()

    def fail(self, err: BaseException) -> None:
        self.error = err
        self.done.set()


class _Slot:
    """Host-side state of one occupied cache slot."""

    __slots__ = ("req", "length", "last_token", "generated", "pending",
                 "resume_last", "admit_order", "tail_mode", "t_admit",
                 "chunks")

    def __init__(self, req: GenerationRequest):
        self.req = req
        self.length = 0               # tokens whose K/V are in the cache
        self.last_token = -1          # input of the next decode step
        self.generated = len(req.tokens)  # nonzero on resume
        self.pending = None           # prompt tokens not yet prefilled
        self.resume_last = None       # last token of a preempted run
        self.admit_order = 0          # preemption picks the youngest
        self.tail_mode = False        # prefix hit: chunks continue from an
        #                               unaligned cached length (_tail_fn)
        self.t_admit = time.perf_counter()  # serving.admit_to_first starts
        self.chunks = 0               # prefill chunks run so far


class _Tick:
    """One dispatched decode tick as the host holds it until its tokens
    are read: the program's outputs (device arrays until the read), the
    lanes it ran (slot -> the _Slot that held the slot at dispatch),
    when it left the host and its ``serving.decode_step`` span's args."""

    __slots__ = ("out", "n_emit", "health", "moe", "lanes", "t0", "args",
                 "ms")

    def __init__(self, out, n_emit, health, moe, lanes, t0, args):
        self.out = out                # sampled tokens: (B,), (B, k+1) spec
        self.n_emit = n_emit          # spec: tokens accepted a lane
        self.health = health          # watchdog: per-lane all-finite
        self.moe = moe                # router stats, a routed model's
        self.lanes = lanes
        self.t0 = t0
        self.args = args
        self.ms = 0.0                 # dispatch to tokens on the host


class InferenceEngine:
    """Continuous-batching generation server for a functional GPT model.

    ::

        eng = InferenceEngine(cfg, params, n_slots=8)
        req = eng.submit(prompt_ids, max_new_tokens=64, temperature=0.8)
        for tok in req.stream(): ...
        eng.shutdown()

    ``params`` is a gpt_init-layout pytree (flat blocks — stage-stacked
    training layouts must be unstacked first).

    ``int8_weights=True`` quantizes the block matmul weights to int8
    per-channel (models.gpt.quantize_gpt_weights) for the DECODE step —
    the steady-state batched tick runs through the Pallas fused int8
    matmul (ops/int8_matmul.py; dequant in the kernel epilogue, int8 at
    2x the bf16 MXU rate on v5e). Prefill chunks keep the fp weights, so
    admission numerics are unchanged; decode tokens are
    near-greedy-identical but not pinned bit-for-bit (weight rounding).
    Default off.

    The cache is a PagedKVCache block pool: per-slot memory proportional
    to live tokens, admission gated on free BLOCKS (the per-slot ceiling
    is ``cfg.seq_len``, kept as ``eng.max_len``), prompt prefill chunked
    at ``prefill_chunk`` tokens per tick and interleaved with decode,
    and the Pallas paged-attention kernel on TPU. ``block_size`` tokens
    per pool block; ``n_blocks`` defaults to worst-case (every slot at
    seq_len) — size it smaller to actually overcommit. What a block IS
    the model says (``ServingModel.state_pad``): for a model whose
    layers keep a recurrent state (``models/retention.py``) a block is
    one sequence's whole state, a slot owns exactly one however long it
    runs, ``n_blocks`` is the states the pool holds (one is the sink),
    admission is by free states, no tick can run out of blocks, and
    ``block_size`` is replaced by the model's own pad granule for
    prefill chunks. The chunk program is told how many of a padded
    chunk's tokens are real, and a slot's first chunk starts from a
    zero state (on resume after a preemption too). ``paged`` selects
    nothing: ``None`` and ``True`` build this engine, ``False`` raises.

    ``draft=(draft_cfg, draft_params)`` enables speculative decoding:
    ``spec_k`` proposals per slot per tick from the draft, one target
    verify pass, rejection-sampling acceptance — greedy token-identical
    to ``draft=None``, sampled output keeps the target distribution.
    The draft must share the vocabulary and its positional table must
    cover the engine's ``max_len``. It keeps a private fixed-slot
    KVCache, filled by the same chunks as the target's pool.

    ``mesh`` (None = follow FLAGS_serving_mesh) runs the decode over a
    multi-chip mesh: slots shard over "data", weights over "model";
    ``n_slots`` must divide by the data degree and ``n_heads`` (target
    and draft) by the model degree. Not combinable with
    ``int8_weights`` (the quantized pytree has no spec table yet).

    ``tokenizer`` (serving.tokenizer.ByteTokenizer or anything with the
    same encode/decode/stream_detokenizer surface) enables the text
    front end: ``submit(text=...)`` and request ``stream_text()``.

    ``prefix_cache`` (None = follow FLAGS_prefix_cache; not combinable
    with ``draft``) turns on radix-tree prefix
    sharing: prompts that repeat a cached prefix splice its refcounted
    blocks instead of re-prefilling, with copy-on-write on a
    partially-used last block and LRU-by-leaf reclaim ahead of
    preemption. Greedy output stays token-identical to the cold cache.

    ``watchdog`` (True or a dict; default off, and when off every
    compiled program is bit-identical to a watchdog-free build) arms the
    per-tick NaN/latency sentinel: each decode tick also returns a
    per-slot all-finite verdict over the logits; a poisoned slot FAILS
    only its own request (finish_reason ``"watchdog"``, error
    :class:`WatchdogTripped`) and the engine auto-restarts from the last
    healthy state — healthy streams are requeued with their token
    history and replayed through the preemption-resume path
    (token-identical continuations), the device cache and prefix tree
    are rebuilt from scratch. Composes with ``draft=`` (ISSUE 14): the
    speculative verify program computes the same per-slot verdict over
    its k+1 verify positions, and a restart rebuilds the draft's KV
    cache alongside the target's (the prefill chunks re-seed both).
    Options: ``latency_budget_ms`` (None disables the latency rung)
    with ``latency_trips`` consecutive slow ticks per stall verdict,
    and ``max_restarts`` before the engine fails open requests loudly.

    ``flight_dir`` (ISSUE 15) arms the process-wide crash flight
    recorder (``monitor.arm_flight_recorder`` — idempotent, shared by
    every engine in the process) and makes the scheduler-abort and
    watchdog-restart paths dump the ring of recent spans/gauge deltas
    there as a self-contained chrome-trace at the moment of failure.

    ``embedding_tables`` (ISSUE 16) arms the recommender ranking path:
    a ``{name: (rows, dim) array}`` dict (optionally ``(tables,
    score_fn)`` to score with a trained model, or a ready
    :class:`~paddle_tpu.sparse.EmbeddingRanker`) is placed row-sharded
    over the engine mesh's "model" axis and :meth:`rank` resolves a
    request's sparse features against it inside one jitted lookup+score
    step (the shard_map all-to-all exchange — no host hop between
    lookup and MLP). The HTTP frontend exposes it as ``POST /v1/rank``.
    Independent of the generation path: no compiled generation program
    changes when it is armed.
    """

    def __init__(self, cfg, params, n_slots: int = 4, queue_size: int = 64,
                 eos_id: Optional[int] = None, seed: int = 0,
                 int8_weights: bool = False, paged: Optional[bool] = None,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 prefill_chunk: int = 64, tps_window_ticks: int = 64,
                 draft=None, spec_k: int = 4, mesh=None, tokenizer=None,
                 prefix_cache: Optional[bool] = None, watchdog=None,
                 overload=None, replica_id: Optional[int] = None,
                 flight_dir: Optional[str] = None,
                 embedding_tables=None):
        # ``paged`` is accepted because the serve workload files under
        # benchmarks/ pass ``"paged": true`` through build_engine(**engine);
        # the `benchmark` PR that drops that key drops this argument
        if paged is not None and not paged:
            raise ValueError(
                "paged=False: the fixed-slot target path is gone; the "
                "engine serves from the PagedKVCache only (drop the "
                "argument)")
        # per-tick NaN/latency sentinel + auto-restart (off by default;
        # when off the engine's compiled programs are bit-identical to a
        # build without it — the health output is gated at trace time)
        if watchdog:
            defaults = {"latency_budget_ms": None, "latency_trips": 3,
                        "max_restarts": 3}
            if watchdog is not True:
                unknown = set(dict(watchdog)) - set(defaults)
                if unknown:
                    raise ValueError(f"unknown watchdog option(s) "
                                     f"{sorted(unknown)}")
                defaults.update(dict(watchdog))
            self._watchdog = defaults
        else:
            self._watchdog = None
        self._restarts = 0
        self._slow_ticks = 0
        # the model's pool layout, step functions and parameter specs are
        # reached through its configuration object (models/serving_api.py)
        self._model = cfg.serving_model()
        if hasattr(cfg, "fused_mlp") and cfg.fused_mlp is None:
            # pin the fused-MLP choice NOW (graftlint GL002): chunk
            # programs compile lazily per padded chunk length, so a
            # FLAGS_fused_kernels flip mid-serving would otherwise split
            # the engine across fused and unfused programs per length
            import dataclasses as _dc

            cfg = _dc.replace(cfg, fused_mlp=bool(native.fused_kernels[0]))
        self.cfg = cfg
        self._mesh = self._resolve_mesh(mesh)
        use_prefix = native.prefix_cache[0] if prefix_cache is None \
            else bool(prefix_cache)
        for option, asked in (("draft", draft is not None),
                              ("prefix_cache", use_prefix),
                              ("int8_weights", int8_weights),
                              ("mesh", self._mesh is not None)):
            if asked and option in self._model.refuses:
                raise ValueError(self._model.refuses[option])
        self._shards = int(self._mesh.shape["data"]) \
            if self._mesh is not None else 1
        if self._mesh is not None:
            if int8_weights:
                raise ValueError("int8_weights and mesh are not yet "
                                 "combinable (no spec table for the "
                                 "quantized pytree)")
            if n_slots % self._shards != 0:
                raise ValueError(f"n_slots={n_slots} not divisible by the "
                                 f"data degree {self._shards}")
            model_deg = int(self._mesh.shape["model"])
            if cfg.n_heads % model_deg != 0:
                raise ValueError(f"n_heads={cfg.n_heads} not divisible by "
                                 f"the model degree {model_deg}")
        self._moe = bool(getattr(cfg, "moe_layer_ids", ()))
        # router stats ride LAST in a step program's outputs
        self._routed = self._moe or self._model.routed
        # chunk router stats not yet read: (span args, device stats)
        self._moe_pending = []
        if self._moe:
            import dataclasses as _dc

            if int8_weights:
                raise ValueError("int8_weights and MoE are not combinable "
                                 "(no quantized layout for the expert "
                                 "pytree)")
            if draft is not None:
                raise ValueError("draft= and MoE are not combinable: "
                                 "speculative verify has no routed-expert "
                                 "path (gpt_verify_step rejects MoE)")
            if self._mesh is not None:
                model_deg = int(self._mesh.shape["model"])
                if model_deg > 1 and cfg.moe_experts % model_deg != 0:
                    raise ValueError(
                        f"moe_experts={cfg.moe_experts} not divisible by "
                        f"the model degree {model_deg} — experts shard "
                        "over the \"model\" axis")
                cfg = _dc.replace(
                    cfg, moe_axis="model" if model_deg > 1 else None)
            else:
                cfg = _dc.replace(cfg, moe_axis=None)
            self.cfg = cfg
        self._params = self._put_params(cfg, params)
        self.int8_weights = bool(int8_weights)
        if int8_weights:
            from ..models.gpt import quantize_gpt_weights
            from ..monitor.stats import INT8_MATMUL_CALLS

            self._decode_params = jax.device_put(
                quantize_gpt_weights(params))
            INT8_MATMUL_CALLS.add()
        else:
            self._decode_params = self._params
        # cache construction args, kept for the watchdog's restart path
        # (a restart rebuilds the device cache from scratch)
        self._cache_args = (n_slots, n_blocks, block_size)
        self.cache = self._build_cache()
        self.block_size = self.cache.block_size
        self.max_len = cfg.seq_len   # positional table = per-slot cap
        if prefill_chunk % self.block_size != 0:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be a multiple of "
                f"block_size={self.block_size} (chunks must start "
                "block-aligned)")
        self.prefill_chunk = int(prefill_chunk)
        # the pool's arrays ride as positional arguments 1..n, all
        # donated: (params, kb, vb, ...) for the per-head pair
        self._n_pool = len(self.cache.pool)
        pool_args = tuple(range(1, 1 + self._n_pool))
        # the step programs by kind: (function, donated arguments, how a
        # signature names it); one jit a signature, made by _program
        self._kinds = {"decode": (self._decode_paged_fn, pool_args, "w{}"),
                       "chunk": (self._chunk_fn, pool_args, "c{}_w{}")}
        self._jits = {}
        self.n_slots = self.cache.n_slots
        if use_prefix and draft is not None:
            raise ValueError("prefix_cache and draft= are not combinable: "
                             "the draft's fixed cache holds no K/V for a "
                             "skipped prefix, so every hit would force a "
                             "full draft prefill")
        if use_prefix and self._moe:
            raise ValueError("prefix_cache and MoE are not combinable: "
                             "prefix reuse verifies through "
                             "gpt_verify_step_paged, which has no "
                             "routed-expert path")
        if use_prefix:
            self._prefix = RadixPrefixCache(self.cache)
            self._kinds["tail"] = (self._tail_fn, (1, 2), "c{}_w{}")
            self._cow_jit = jax.jit(self._cow_fn, donate_argnums=(0, 1))
        else:
            self._prefix = None
        self._init_draft(draft, spec_k)
        # the draft always decodes against its own fixed-slot cache —
        # k short steps over a small model don't need paging (built here
        # AND by the watchdog restart's _reset_cache on its thread)
        self.draft_cache = self._build_draft_cache() \
            if self.draft is not None else None
        self.tokenizer = tokenizer
        # all-true token mask reused by every unconstrained tick: host
        # template for constrained batches, device-resident copy so the
        # common path ships no (slots, vocab) buffer per tick
        self._ones_mask = np.ones((self.n_slots, cfg.vocab_size), bool)
        self._mask_dev = jax.device_put(self._ones_mask)
        # the decode tick dispatched and not yet read (a _Tick), and the
        # sampled tokens of the last plain tick, on the device: the next
        # tick's input for the lanes it carries on
        self._inflight = None
        # placed as the program places its sampled tokens, so either may
        # be the next tick's input to the one compiled program
        self._prev_toks = jax.device_put(
            np.zeros(self.n_slots, np.int32),
            None if self._mesh is None
            else NamedSharding(self._mesh, P("data")))
        self.eos_id = eos_id
        self._queue: collections.deque = collections.deque()
        self._queue_size = int(queue_size)
        self._cv = threading.Condition()
        self._slots: List[Optional[_Slot]] = [None] * self.n_slots
        self._stop = False
        self._drain = True
        self._error: Optional[BaseException] = None  # scheduler crash cause
        self._base_key = jax.random.key(seed)
        self._rid = 0            # next request id (per-request RNG stream)
        self._warm_seq = 0       # warm_prefix sequence (its own rid space)
        self._evacuate = False   # lifecycle drain: scheduler raises
        #                          ReplicaEvacuated at its next loop check
        self._die_tick = None    # lifecycle chaos: fail_at_tick target
        self._ticks = 0          # scheduler loop iterations (span tagging)
        self._admit_seq = 0
        self._spec_prop = 0      # lifetime draft proposals / acceptances
        self._spec_acc = 0       # behind the acceptance-rate gauge
        # float running totals behind the int ms gauges (prefetch.py idiom:
        # sub-ms ticks still accumulate)
        self._prefill_ms = 0.0
        self._decode_ms = 0.0
        # tokens/s: sliding window over the last N tick completions, so a
        # load spike/dip shows in trace reports instead of being averaged
        # into the engine's lifetime
        self._window: collections.deque = collections.deque(
            maxlen=max(2, int(tps_window_ticks)))  # (t, n_tokens)
        SERVING_SHARDS.set(self._shards)
        # overload-hardening surface (ISSUE 13): the brownout controller
        # (None = every schedule decision bit-identical to a build
        # without it), the router-assigned replica identity, the
        # router-installed failover hook stamped onto each request, and
        # the scheduler heartbeat behind the router's tick-age health
        self.overload = overload
        self.replica_id = replica_id
        self.failover = None
        # crash flight recorder (ISSUE 15): arming is process-global and
        # idempotent — every engine in the process shares one ring, and
        # the abort/watchdog paths dump it the moment they fire
        self.flight_dir = flight_dir
        if flight_dir:
            arm_flight_recorder(flight_dir)
        # serving-side sparse lookup (ISSUE 16): tables placed over THIS
        # engine's mesh; built before the scheduler thread starts so a
        # rank() race with startup is impossible
        self._ranker = None
        if embedding_tables is not None:
            from ..sparse.ranking import EmbeddingRanker

            if isinstance(embedding_tables, EmbeddingRanker):
                self._ranker = embedding_tables
            elif isinstance(embedding_tables, tuple):
                tables, score_fn = embedding_tables
                self._ranker = EmbeddingRanker(tables, score_fn=score_fn,
                                               mesh=self._mesh)
            else:
                self._ranker = EmbeddingRanker(dict(embedding_tables),
                                               mesh=self._mesh)
        self._last_tick_t = time.monotonic()
        # cross-host fleet (ISSUE 19): closures parked by other threads
        # for the scheduler to run between ticks — the KV export/import
        # path touches the donated pool buffers, which only the
        # scheduler thread may do (guarded by self._cv)
        self._host_calls: collections.deque = collections.deque()
        # the traced window's programs (kept while tracing only), whether
        # its on_stop callback is registered, and when the scheduler's
        # current stretch with no work began (None while it has work)
        self._programs = ProgramLog()
        self._trace_armed = False
        self._idle_t0 = None
        self._thread = threading.Thread(target=self._run,
                                        name="serving-scheduler", daemon=True)
        self._thread.start()

    # -- multi-chip placement ------------------------------------------------
    def _resolve_mesh(self, mesh):
        """Explicit ``mesh`` wins; else FLAGS_serving_mesh=D builds a
        (data=D, model=rest) mesh over every visible device; else None
        (single chip — the pinned PR-7 path)."""
        if mesh is not None:
            return mesh
        degree = int(native.serving_mesh[0])
        if degree <= 0:
            return None
        from jax.sharding import Mesh

        from ..parallel.mesh import AXES
        devices = jax.devices()
        if len(devices) % degree != 0:
            raise ValueError(
                f"FLAGS_serving_mesh={degree} does not divide the "
                f"{len(devices)} visible devices")
        arr = np.array(devices).reshape(degree, 1, 1,
                                        len(devices) // degree)
        return Mesh(arr, AXES)

    def _put_params(self, cfg, params):
        if self._mesh is None:
            return jax.device_put(params)
        from ..parallel.sharding import shard_params
        return shard_params(params, cfg.serving_model().param_specs(cfg),
                            self._mesh)

    def _put_cache(self, buf):
        return jax.device_put(buf, NamedSharding(self._mesh, _CACHE_SPEC))

    # -- speculative-decoding setup ------------------------------------------
    def _init_draft(self, draft, spec_k: int) -> None:
        if draft is None:
            self.draft = None
            self.draft_cfg = None
            self.spec_k = 0
            return
        draft_cfg, draft_params = draft
        if int(spec_k) < 1:
            raise ValueError(f"spec_k={spec_k} must be >= 1")
        if draft_cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{self.cfg.vocab_size} (the acceptance rule compares "
                "distributions over one vocabulary)")
        # chunks are block-padded, so the draft cache (and its
        # positional table) must cover max_len rounded up to a block
        draft_len = -(-self.max_len // self.block_size) * self.block_size
        if draft_cfg.seq_len < draft_len:
            raise ValueError(
                f"draft seq_len {draft_cfg.seq_len} < engine cache span "
                f"{draft_len} — the draft must reach every position the "
                "target can")
        if getattr(draft_cfg, "fused_mlp", None) is None:
            import dataclasses as _dc

            draft_cfg = _dc.replace(
                draft_cfg, fused_mlp=bool(native.fused_kernels[0]))
        if self._mesh is not None:
            model_deg = int(self._mesh.shape["model"])
            if draft_cfg.n_heads % model_deg != 0:
                raise ValueError(
                    f"draft n_heads={draft_cfg.n_heads} not divisible by "
                    f"the model degree {model_deg}")
        self.draft_cfg = draft_cfg
        self._draft_model = draft_cfg.serving_model()
        self._draft_params = self._put_params(draft_cfg, draft_params)
        self.draft = (draft_cfg, self._draft_params)
        self.spec_k = int(spec_k)
        self._draft_len = draft_len
        self._kinds["spec"] = (self._spec_paged_fn, (2, 3, 4, 5), "w{}")
        self._kinds["chunk_spec"] = (self._chunk_spec_fn, (2, 3, 4, 5),
                                     "c{}_w{}")

    def _build_cache(self):
        """Fresh zeroed block pool + accounting (construction and the
        watchdog restart both route here)."""
        n_slots, n_blocks, block_size = self._cache_args
        cache = PagedKVCache(self.cfg, n_slots, n_blocks=n_blocks,
                             block_size=block_size, shards=self._shards)
        if self._mesh is not None:
            cache.kb = self._put_cache(cache.kb)
            cache.vb = self._put_cache(cache.vb)
        return cache

    def _build_draft_cache(self):
        """Fresh zeroed draft KV cache (construction and the watchdog
        restart both route here, so the rebuild matches the original)."""
        cache = KVCache(self.draft_cfg, self.n_slots,
                        max_len=self._draft_len)
        if self._mesh is not None:
            cache.k = self._put_cache(cache.k)
            cache.v = self._put_cache(cache.v)
        return cache

    # -- compiled programs ---------------------------------------------------
    def _program(self, kind: str, *dims):
        """(jitted program, signature) of ``kind`` at one signature (a
        table width; a chunk's padded length and width), made on first
        use under a name of its own: ``jit__decode_paged_fn_w64`` on the
        profiler's ``XLA Modules`` line, so that each run there names
        its executable, and so the window's ``op_scopes`` table of it.
        One executable a jit, as one a signature before."""
        got = self._jits.get((kind, dims))
        if got is None:
            fn, donate, form = self._kinds[kind]
            sig = form.format(*dims)

            def run(*args):
                return fn(*args)

            run.__name__ = run.__qualname__ = f"{fn.__name__}_{sig}"
            got = self._jits[kind, dims] = (
                jax.jit(run, donate_argnums=donate), sig)
        return got

    def _sample_args(self, logits, base_key, rids, steps, temps, top_ks,
                    top_ps, mask):
        with jax.named_scope("sampling"):
            keys = stream_keys(base_key, rids, steps)
            return sample_tokens_streams(logits, keys, temps, top_ks,
                                         top_ps, mask=mask)

    def _decode_paged_fn(self, params, *args):
        # args: the pool's arrays, then (tables, positions, tokens,
        # prev_toks, use_prev, base_key, rids, steps, temps, top_ks,
        # top_ps, mask); a lane whose use_prev is set takes its input
        # from prev_toks, the tokens the tick before sampled
        pool, (tables, positions, tokens, prev_toks, use_prev, base_key,
               rids, steps, temps, top_ks, top_ps, mask) = \
            args[:self._n_pool], args[self._n_pool:]
        tokens = jnp.where(use_prev, prev_toks, tokens)
        got = self._model.decode_step_paged(
            self.cfg, params, pool, tables, positions, tokens)
        logits, pool = got[0], got[1]
        toks = self._sample_args(logits, base_key, rids, steps, temps,
                                 top_ks, top_ps, mask)
        out = (toks,)
        if self._watchdog is not None:
            out = out + (logits_finite(logits),)
        out = out + tuple(pool)
        if self._routed:
            out = out + (got[2],)
        return out

    def _tail_fn(self, params, kb, vb, table_row, tokens, start):
        # prefix-cache tail chunk: continue a prefill from an UNALIGNED
        # cached length (the radix match ends wherever the shared prompt
        # diverges); only the final chunk's last live row is read
        logits, (kb, vb) = self._model.prefill_prefix(
            self.cfg, params, (kb, vb), table_row, tokens, start)
        return logits, kb, vb

    def _cow_fn(self, kb, vb, src, dst):
        # copy-on-write: duplicate ONE pool block's rows (every layer)
        # into a freshly-allocated block before the slot extends it
        kr = jax.lax.dynamic_slice_in_dim(kb, src, 1, axis=0)
        vr = jax.lax.dynamic_slice_in_dim(vb, src, 1, axis=0)
        kb = jax.lax.dynamic_update_slice_in_dim(kb, kr, dst, axis=0)
        vb = jax.lax.dynamic_update_slice_in_dim(vb, vr, dst, axis=0)
        return kb, vb

    def _chunk_fn(self, params, *args):
        # one prefill chunk: writes the chunk's rows into the pool,
        # returns the chunk logits (only the final chunk's last live row
        # is read); args: the pool's arrays, then (table_row, tokens,
        # start, n_true: how many of the padded chunk's tokens are real,
        # which a model whose cache is a recurrent state must know); a
        # routed model's router stats ride last
        pool, (table_row, tokens, start, n_true) = \
            args[:self._n_pool], args[self._n_pool:]
        got = self._model.prefill_chunk(
            self.cfg, params, pool, table_row, tokens, start, n_true)
        return (got[0],) + tuple(got[1]) + tuple(got[2:])

    def _chunk_spec_fn(self, params, dparams, kb, vb, dk, dv, table_row,
                       slot, tokens, start):
        # paged target chunk + the same chunk appended to the draft's
        # fixed cache row (gpt_verify_step doubles as a chunk append)
        logits, (kb, vb) = self._model.prefill_chunk(
            self.cfg, params, (kb, vb), table_row, tokens, start)
        row_k = jax.lax.dynamic_slice_in_dim(dk, slot, 1, axis=0)
        row_v = jax.lax.dynamic_slice_in_dim(dv, slot, 1, axis=0)
        _, (row_k, row_v) = self._draft_model.verify_step(
            self.draft_cfg, dparams, (row_k, row_v),
            jnp.reshape(start, (1,)), tokens)
        dk = jax.lax.dynamic_update_slice_in_dim(dk, row_k, slot, axis=0)
        dv = jax.lax.dynamic_update_slice_in_dim(dv, row_v, slot, axis=0)
        return logits, kb, vb, dk, dv

    def _draft_propose(self, dparams, dk, dv, positions, tokens, base_key,
                       rids, steps, temps, top_ks, top_ps):
        """spec_k autoregressive draft steps (unrolled into the one spec
        program): returns proposed tokens (B, K), the distributions they
        were drawn from (B, K, V), and the updated draft cache."""
        cur = tokens
        d_toks, d_logits = [], []
        for j in range(self.spec_k):
            lg, (dk, dv) = self._draft_model.decode_step(
                self.draft_cfg, dparams, (dk, dv), positions + j, cur)
            with jax.named_scope("sampling"):
                keys = stream_keys(base_key, rids, steps + j)
                dkeys = jax.vmap(
                    lambda kk: jax.random.fold_in(kk, DRAFT_SALT))(keys)
                cur = sample_tokens_streams(lg, dkeys, temps, top_ks,
                                            top_ps)
            d_toks.append(cur)
            d_logits.append(lg)
        return (jnp.stack(d_toks, axis=1), jnp.stack(d_logits, axis=1),
                dk, dv)

    def _spec_paged_fn(self, params, dparams, kb, vb, dk, dv, tables,
                       positions, tokens, base_key, rids, steps, temps,
                       top_ks, top_ps):
        d_toks, d_logits, dk, dv = self._draft_propose(
            dparams, dk, dv, positions, tokens, base_key, rids, steps,
            temps, top_ks, top_ps)
        vtokens = jnp.concatenate([tokens[:, None], d_toks], axis=1)
        t_logits, (kb, vb) = self._model.verify_step_paged(
            self.cfg, params, (kb, vb), tables, positions, vtokens)
        with jax.named_scope("sampling"):
            keys = stream_keys(base_key, rids, steps)
            out, n_emit = spec_accept(t_logits, d_logits, d_toks, keys,
                                      temps, top_ks, top_ps)
        if self._watchdog is not None:
            health = logits_finite(
                jnp.reshape(t_logits, (t_logits.shape[0], -1)))
            return out, n_emit, health, kb, vb, dk, dv
        return out, n_emit, kb, vb, dk, dv

    # -- public API ----------------------------------------------------------
    def submit(self, prompt: Optional[Sequence[int]] = None,
               max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_id: Optional[int] = None, deadline_s: Optional[float] = None,
               block: bool = True, timeout: Optional[float] = None,
               text: Optional[str] = None,
               constraint=None, trace=None) -> GenerationRequest:
        """Queue a generation request; returns its streaming handle.

        Exactly one of ``prompt`` (token ids) and ``text`` must be given;
        ``text`` requires the engine's tokenizer, encodes through it, and
        defaults ``eos_id`` to the tokenizer's (so ``stream_text()``
        terminates naturally). Backpressure: when the bounded queue is
        full, ``block=True`` waits (up to ``timeout`` seconds) for space
        and raises :class:`QueueFull` on timeout; ``block=False`` raises
        immediately. ``deadline_s`` is a wall-clock budget from now — a
        request over budget is evicted with ``finish_reason="deadline"``
        wherever it is (queued or mid-decode).

        ``constraint`` (serving.constrained.TokenConstraint) masks every
        sampled token through the compiled automaton — structured
        decoding; the stream finishes with ``finish_reason="stop"`` when
        the match completes.

        ``trace`` (monitor.TraceContext, ISSUE 15) is the request's
        causal tracing identity — minted at HTTP admission by the front
        end and stamped onto every span/flow event the request touches,
        across failover hops. It never influences sampling: with tracing
        off the token stream is pinned bit-identical.
        """
        if text is not None:
            if prompt is not None:
                raise ValueError("pass prompt OR text, not both")
            if self.tokenizer is None:
                raise ValueError("submit(text=...) needs an engine "
                                 "tokenizer — InferenceEngine(tokenizer=...)")
            prompt = self.tokenizer.encode(text)
            if eos_id is None and self.eos_id is None:
                eos_id = self.tokenizer.eos_id
        if prompt is None:
            raise ValueError("provide a prompt (token ids) or text")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.size >= self.max_len:
            # block capacity is checked at admission, not here
            raise ValueError(
                f"prompt length {prompt.size} leaves no room to generate "
                f"(positional table seq_len={self.max_len})")
        if self.cache.blocks_for(prompt.size + 1) > \
                self.cache.max_slot_blocks:
            raise ValueError(
                f"prompt length {prompt.size} can never fit one shard of "
                f"the block pool ({self.cache.max_slot_blocks} blocks x "
                f"{self.block_size} tokens)")
        cursor = None
        if constraint is not None:
            if getattr(constraint, "vocab_size", self.cfg.vocab_size) \
                    > self.cfg.vocab_size:
                raise ValueError(
                    f"constraint vocab {constraint.vocab_size} exceeds the "
                    f"model vocab {self.cfg.vocab_size}")
            cursor = constraint.cursor() if hasattr(constraint, "cursor") \
                else constraint
            CONSTRAINED_REQUESTS.add(1)
        req = GenerationRequest(
            prompt, max_new_tokens, temperature, top_k, top_p,
            self.eos_id if eos_id is None else eos_id,
            None if deadline_s is None else time.monotonic() + deadline_s,
            constraint=cursor)
        req.trace = trace
        req._tokenizer = self.tokenizer
        with self._cv:
            self._check_open()
            if len(self._queue) >= self._queue_size:
                if not block:
                    raise QueueFull(
                        f"serving queue at capacity ({self._queue_size})")
                ok = self._cv.wait_for(
                    lambda: self._stop
                    or len(self._queue) < self._queue_size, timeout)
                if not ok:
                    raise QueueFull(
                        f"serving queue still full after {timeout}s")
                self._check_open()
            # the request id is the RNG stream identity: assigned in
            # submission order, so a stream's sampled tokens are a pure
            # function of (seed, rid) — batch neighbors can't perturb it
            req.rid = self._rid
            self._rid += 1
            req._failover = self.failover
            req._t_submit = time.monotonic()
            self._queue.append(req)
            SERVING_QUEUE_DEPTH.set(len(self._queue))
            self._end_idle()
            self._cv.notify_all()
        return req

    def adopt_request(self, req: GenerationRequest) -> None:
        """Router failover entry: enqueue a request ANOTHER replica was
        serving when it died. The preemption-resume contract rebuilds
        decode state from ``prompt + generated[:-1]`` with the last
        token restored, and the request KEEPS its rid — with replicas
        sharing a seed, the continuation is token-identical to the run
        the dead replica would have produced. Bypasses the queue bound
        (failover must not drop work a user already holds a handle to)."""
        if req.trace is not None:
            # the causal timeline continues on THIS replica: record the
            # hop so chrome-trace/request_report show one connected
            # request across the failover instead of two half-streams
            prev = getattr(req, "_replica", None)
            req.trace.hop(prev, self.replica_id)
            if recording():
                t = time.perf_counter()
                emit_complete(
                    "serving.failover_hop", t, 0.0, cat="serving",
                    args=req.trace.args(
                        rid=req.rid, hop_from=prev,
                        hop_to=self.replica_id))
                emit_flow("t", req.trace.trace_id, t)
        with self._cv:
            self._check_open()
            if req.tokens:
                seq = np.concatenate(
                    [req.prompt, np.asarray(req.tokens[:-1],
                                            np.int32)]).astype(np.int32)
                req._resume = (seq, int(req.tokens[-1]))
            else:
                req._resume = None      # nothing emitted: just start over
            req._failover = self.failover
            req._t_submit = time.monotonic()
            # keep future rids clear of the adopted one: rid collisions
            # would alias two requests onto one RNG stream
            self._rid = max(self._rid, req.rid + 1)
            self._queue.append(req)
            SERVING_QUEUE_DEPTH.set(len(self._queue))
            self._end_idle()
            self._cv.notify_all()

    # -- replica lifecycle (serving/lifecycle.py, ISSUE 14) ------------------
    def warm_prefix(self, prompt) -> GenerationRequest:
        """Queue a prefill-only background request — the radix re-warm
        primitive. The prompt is prefilled (and, with the prefix cache
        on, inserted into the radix tree) and exactly one token is generated
        and discarded by the caller. The request id comes from a
        DEDICATED space above ``2**30``, so warm replay neither collides
        with nor shifts the numbering of live request ids — a rejoined
        replica's sampled streams stay pure functions of (seed, rid)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1 or prompt.size >= self.max_len:
            raise ValueError(f"warm prefix length {prompt.size} outside "
                             f"(0, {self.max_len})")
        req = GenerationRequest(prompt, 1, 0.0, 0, 1.0, None, None)
        req._tokenizer = self.tokenizer
        with self._cv:
            self._check_open()
            req.rid = _WARM_RID_BASE + self._warm_seq
            self._warm_seq += 1
            req._t_submit = time.monotonic()
            self._queue.append(req)        # warm runs pre-traffic: the
            SERVING_QUEUE_DEPTH.set(len(self._queue))  # bound is moot
            self._end_idle()
            self._cv.notify_all()
        return req

    def evacuate(self) -> None:
        """Ask the scheduler to stop by FAILING every open stream with
        :class:`ReplicaEvacuated` — through the router failover hook,
        each one is adopted by a survivor and replayed token-identically
        (the preemption-resume contract). The drain-shrink terminal
        step: callers must have already stopped routing new work here."""
        with self._cv:
            self._evacuate = True
            self._cv.notify_all()

    def fail_at_tick(self, ticks_ahead: int = 1) -> None:
        """Chaos/operator hook: make the scheduler raise InjectedCrash
        ``ticks_ahead`` busy ticks from now — the replica_flap fault's
        deterministic crash-after-rejoin, also usable as a manual
        replica kill. A real crash in every observable way (failover,
        supervisor respawn ladder, spans)."""
        with self._cv:
            self._die_tick = self._ticks + max(1, int(ticks_ahead))
            self._cv.notify_all()

    def lower_decode(self, table_width: Optional[int] = None):
        """Lower the batched one-token decode program over this engine's
        own weights and cache — assert-on-HLO testing, the serving twin
        of ``DistributedTrainStep.lower``. ``table_width`` picks the
        program's block-table width bucket (default: the widest). Nothing
        runs and nothing is donated; ``.compile().as_text()`` is the HLO
        the tick executes."""
        n = self.n_slots
        i32 = np.zeros(n, np.int32)
        tail = (self._base_key, i32, i32, np.zeros(n, np.float32), i32,
                np.ones(n, np.float32), self._mask_dev)

        def lower(eng):
            width = eng.cache.table_width if table_width is None \
                else eng._width_bucket(int(table_width))
            return eng._program("decode", width)[0].lower(
                eng._decode_params, *eng.cache.pool,
                np.zeros((n, width), np.int32), i32, i32, eng._prev_toks,
                np.zeros(n, bool), *tail)

        # on the scheduler thread: between ticks no donated buffer is
        # mid-flight
        return self.run_on_scheduler(lower)

    # -- KV-block streaming (pod disaggregation, serving/pod.py, ISSUE 19) ---
    def run_on_scheduler(self, fn, timeout: Optional[float] = None):
        """Run ``fn(engine)`` ON the scheduler thread, between ticks, and
        return its result (re-raising its exception). This is the only
        safe way for another thread to touch the donated pool buffers or
        the radix tree: between ticks no jit call is in flight and the
        refcount tables are consistent. Called from the scheduler thread
        itself, runs inline (the warm/export composition)."""
        if threading.current_thread() is self._thread:
            return fn(self)
        call = _HostCall(fn)
        with self._cv:
            self._check_open()
            self._host_calls.append(call)
            self._end_idle()
            self._cv.notify_all()
        if not call.done.wait(timeout):
            raise TimeoutError("scheduler did not service the host call "
                               f"within {timeout}s")
        if call.error is not None:
            raise call.error
        return call.result

    def export_kv_prefix(self, tokens, timeout: Optional[float] = None):
        """Serialize the cached KV blocks covering ``tokens`` — the
        prefill side of disaggregated serving. Matches the radix tree
        (longest cached prefix, capped at len-1 like every splice) and
        gathers the matched pool rows to host memory. Returns ``None``
        when nothing is cached, else a dict with ``matched_len``,
        ``block_size``, ``dtype``, ``shape`` and host-numpy ``kb``/``vb``
        of shape (n_blocks, layers, heads, block_size, head_dim). The
        gather runs on the scheduler thread (:meth:`run_on_scheduler`);
        the returned arrays are copies, safe to ship over RPC."""
        if self._prefix is None:
            raise RuntimeError("export_kv_prefix needs prefix_cache=True")
        toks = np.asarray(tokens, np.int32).reshape(-1)

        def _export(eng):
            m_len, blocks, shard = 0, [], 0
            for d in range(eng.cache.shards):
                m, bl = eng._prefix.match(d, toks)
                if m > m_len:
                    m_len, blocks, shard = m, bl, d
            if m_len <= 0 or not blocks:
                return None
            idx = jnp.asarray(np.asarray(blocks, np.int32))
            kb = np.asarray(jax.device_get(eng.cache.kb[idx]))
            vb = np.asarray(jax.device_get(eng.cache.vb[idx]))
            return {"matched_len": int(m_len),
                    "block_size": int(eng.block_size),
                    "dtype": str(kb.dtype), "shape": list(kb.shape),
                    "kb": kb, "vb": vb}

        return self.run_on_scheduler(_export, timeout=timeout)

    def import_kv_prefix(self, tokens, kb, vb, matched_len: int,
                         timeout: Optional[float] = None) -> int:
        """Splice streamed KV blocks (an :meth:`export_kv_prefix` payload
        from a prefill-role peer) into this engine's pool and radix tree
        — the decode side of disaggregated serving. Best-effort: returns
        the number of tokens now cached for the prefix (0 when the pool
        has no room), after which a plain ``submit`` of the same prompt
        hits the radix tree and splices exactly like a local prefix hit
        — the pinned token-identity guarantee carries over unchanged."""
        if self._prefix is None:
            raise RuntimeError("import_kv_prefix needs prefix_cache=True")
        toks = np.asarray(tokens, np.int32).reshape(-1)[:int(matched_len)]
        kb = np.asarray(kb)
        vb = np.asarray(vb)
        n = int(kb.shape[0])
        if toks.size <= 0 or n == 0:
            return 0
        if n != self.cache.blocks_for(toks.size) or kb.shape != vb.shape:
            raise ValueError(
                f"import_kv_prefix: {n} streamed blocks do not cover "
                f"{toks.size} tokens at block_size {self.block_size}")

        def _import(eng):
            # already warm (idempotent re-stream)? keep the local copy
            have = max(eng._prefix.peek(d, toks)
                       for d in range(eng.cache.shards))
            if have >= toks.size:
                return int(have)
            # target the shard with the most reclaimable room
            best_d, room = 0, -1
            for d in range(eng.cache.shards):
                avail = (eng.cache.free_blocks_of(d)
                         + eng._prefix.evictable_count(d))
                if avail > room:
                    best_d, room = d, avail
            if room < n:
                return 0
            short = n - eng.cache.free_blocks_of(best_d)
            if short > 0 and eng._prefix.evict(best_d, short) < short:
                return 0
            blocks = []
            for _ in range(n):
                b = eng.cache.alloc_block(best_d)
                if b is None:          # lost the race: roll back cleanly
                    for bb in blocks:
                        eng.cache.unref_block(bb)
                    return 0
                blocks.append(b)
            idx = jnp.asarray(np.asarray(blocks, np.int32))
            dt = eng.cache.kb.dtype
            eng.cache.kb = eng.cache.kb.at[idx].set(jnp.asarray(kb, dt))
            eng.cache.vb = eng.cache.vb.at[idx].set(jnp.asarray(vb, dt))
            eng._prefix.insert(best_d, toks, blocks)
            # insert() took a tree reference on every chunk it adopted;
            # drop the alloc-time reference so the tree is sole owner and
            # duplicates of chunks it already held free immediately
            for b in blocks:
                eng.cache.unref_block(b)
            eng.cache.update_gauges()
            return int(eng._prefix.peek(best_d, toks))

        return self.run_on_scheduler(_import, timeout=timeout)

    def export_kv_range(self, tokens, start_block: int,
                        max_blocks: Optional[int] = None,
                        timeout: Optional[float] = None):
        """Incremental slice of :meth:`export_kv_prefix` for resumable
        chunked streaming (ISSUE 20): export only the cached blocks from
        ``start_block`` onward, so finished prefill chunks ship while
        the next chunk computes. While the prefill is still running only
        FULL blocks are exported (a partial tail block would be
        re-written by the next chunk); once the whole prefix is cached
        (``done=True``) the partial tail block ships too. Returns a dict
        with ``matched_len``/``start_block``/``n_blocks``/``done`` plus
        host-numpy ``kb``/``vb`` (possibly 0-length — poll again)."""
        if self._prefix is None:
            raise RuntimeError("export_kv_range needs prefix_cache=True")
        toks = np.asarray(tokens, np.int32).reshape(-1)
        start = int(start_block)

        def _export(eng):
            m_len, blocks, shard = 0, [], 0
            for d in range(eng.cache.shards):
                m, bl = eng._prefix.match(d, toks)
                if m > m_len:
                    m_len, blocks, shard = m, bl, d
            bs = int(eng.block_size)
            # match() caps at len-1 by design, so "whole prefix cached"
            # is m_len >= size-1 — the same terminal every splice uses
            done = m_len >= toks.size - 1
            avail = len(blocks) if done else m_len // bs
            if not done:
                # mid-prefill visibility: the radix insert only happens
                # when the WHOLE prompt is cached, so a slot still
                # prefilling this prompt is invisible to match() — scan
                # live slots and ship their finished FULL blocks while
                # the next chunk computes (the partial tail rides the
                # radix entry once ``done`` flips). Safe: this runs on
                # the scheduler thread between ticks, and a slot's
                # prompt blocks are never rewritten once filled.
                for slot in range(eng.n_slots):
                    st = eng._slots[slot]
                    if st is None:
                        continue
                    pr = np.asarray(st.req.prompt, np.int32).reshape(-1)
                    n_full = min(int(st.length), toks.size) // bs
                    if (n_full > avail and pr.size >= toks.size
                            and np.array_equal(pr[:toks.size], toks)):
                        tbl = eng.cache.block_tables[slot]
                        blocks = [int(b) for b in tbl[:n_full]]
                        avail, m_len = n_full, n_full * bs
            lo = min(start, avail)
            hi = avail if max_blocks is None \
                else min(avail, lo + int(max_blocks))
            out = {"matched_len": int(m_len), "start_block": int(lo),
                   "n_blocks": int(hi - lo), "block_size": bs,
                   "done": bool(done),
                   # prefix tokens covered by blocks [0, hi) — the
                   # n_tokens a receiver passes to import_kv_chunk
                   "covered_tokens": int(min(m_len, hi * bs))}
            if hi > lo:
                idx = jnp.asarray(np.asarray(blocks[lo:hi], np.int32))
                out["kb"] = np.asarray(jax.device_get(eng.cache.kb[idx]))
                out["vb"] = np.asarray(jax.device_get(eng.cache.vb[idx]))
            return out

        return self.run_on_scheduler(_export, timeout=timeout)

    def import_kv_chunk(self, tokens, kb, vb, start_block: int,
                        n_tokens: int,
                        timeout: Optional[float] = None) -> int:
        """Splice ONE streamed chunk (an :meth:`export_kv_range` slice)
        into the pool + radix tree, extending a prefix whose earlier
        blocks were imported by previous chunks. Returns the receiver's
        high-water mark — the number of prefix tokens now cached — which
        is the ack the sender resumes from: a chunk that arrives out of
        order (its ``start_block`` is past what this engine holds) is
        dropped and the current mark returned, so a lost frame rewinds
        the stream instead of corrupting it. Idempotent on re-delivery."""
        if self._prefix is None:
            raise RuntimeError("import_kv_chunk needs prefix_cache=True")
        n_tok = int(n_tokens)
        toks = np.asarray(tokens, np.int32).reshape(-1)[:n_tok]
        kb = np.asarray(kb)
        vb = np.asarray(vb)
        n = int(kb.shape[0])
        start = int(start_block)
        if toks.size != n_tok or n_tok <= 0:
            raise ValueError(f"import_kv_chunk: prompt carries {toks.size} "
                             f"tokens, chunk claims {n_tok}")
        if n == 0 or kb.shape != vb.shape \
                or start + n != self.cache.blocks_for(n_tok):
            raise ValueError(
                f"import_kv_chunk: {n} blocks at {start} do not land on "
                f"{n_tok} tokens at block_size {self.block_size}")

        def _import(eng):
            bs = int(eng.block_size)
            # the shard holding the deepest copy of this prefix is the
            # stream target; its peek is the ack high-water mark
            best_d, have = 0, -1
            for d in range(eng.cache.shards):
                p = eng._prefix.peek(d, toks)
                if p > have:
                    best_d, have = d, p
            if have >= n_tok:
                return int(have)           # idempotent re-delivery
            if have < start * bs:
                return int(have)           # gap: sender must rewind
            _, ex_blocks = eng._prefix.match(best_d, toks)
            room = (eng.cache.free_blocks_of(best_d)
                    + eng._prefix.evictable_count(best_d))
            if room < n:
                return int(have)
            short = n - eng.cache.free_blocks_of(best_d)
            if short > 0 and eng._prefix.evict(best_d, short) < short:
                return int(have)
            blocks = []
            for _ in range(n):
                b = eng.cache.alloc_block(best_d)
                if b is None:
                    for bb in blocks:
                        eng.cache.unref_block(bb)
                    return int(have)
                blocks.append(b)
            idx = jnp.asarray(np.asarray(blocks, np.int32))
            dt = eng.cache.kb.dtype
            eng.cache.kb = eng.cache.kb.at[idx].set(jnp.asarray(kb, dt))
            eng.cache.vb = eng.cache.vb.at[idx].set(jnp.asarray(vb, dt))
            # the first start blocks are the tree's own nodes from the
            # previous chunks — insert() dedupes them by chunk key and
            # only adopts (and refs) the new tail
            eng._prefix.insert(best_d, toks,
                               list(ex_blocks[:start]) + blocks)
            for b in blocks:
                eng.cache.unref_block(b)
            eng.cache.update_gauges()
            return int(eng._prefix.peek(best_d, toks))

        return self.run_on_scheduler(_import, timeout=timeout)

    # -- health surface (EngineRouter / frontend readyz) ---------------------
    @property
    def alive(self) -> bool:
        """Scheduler running and able to make progress."""
        return self._thread.is_alive() and not self._stop \
            and self._error is None

    @property
    def busy(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def tick_age(self) -> float:
        """Seconds since the scheduler last completed a loop iteration
        (fresh even when idle — the idle wait beats every 50ms)."""
        with self._cv:
            return time.monotonic() - self._last_tick_t

    def pool_headroom(self) -> float:
        """Free fraction of the pool's blocks — the /readyz
        admission-headroom signal."""
        total = self.cache.n_blocks - self.cache.shards
        return self.cache.free_blocks_count / max(1, total)

    def generate(self, prompt: Sequence[int] = None, **kw) -> List[int]:
        """Blocking convenience wrapper: submit + result."""
        return self.submit(prompt, **kw).result()

    def rank(self, slots, dense=None):
        """Score a batch of sparse-feature requests against the armed
        embedding tables (``embedding_tables=``): ``slots`` = {name:
        (B, L) int ids}, optional ``dense`` = (B, n_dense) floats.
        Returns (B,) numpy scores. Thread-safe (the lookup runs on the
        caller's thread — it shares no state with the scheduler)."""
        if self._ranker is None:
            raise RuntimeError(
                "ranking not enabled: construct the engine with "
                "embedding_tables= to arm the sparse lookup path")
        return self._ranker.rank(slots, dense=dense)

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the scheduler. ``drain=True`` finishes every submitted
        request first; ``drain=False`` evicts them with
        ``finish_reason="shutdown"``."""
        with self._cv:
            self._stop = True
            self._drain = drain
            self._cv.notify_all()
        self._thread.join(timeout)

    @property
    def occupancy(self) -> int:
        return self.cache.occupancy

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    # -- scheduler -----------------------------------------------------------
    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    self._last_tick_t = time.monotonic()
                    if self._evacuate:
                        # lifecycle drain-shrink: fail every open stream
                        # with the adoption-triggering cause (see
                        # evacuate()) — raised here so it runs on the
                        # scheduler thread, never racing a live tick
                        raise ReplicaEvacuated(
                            f"replica {self.replica_id} evacuated "
                            "(drain-shrink)")
                    if TRACING[0] and not self._trace_armed:
                        self._arm_trace()
                    busy = bool(self._queue) or self._inflight is not None \
                        or any(s is not None for s in self._slots)
                    if self._stop and (not self._drain or not busy):
                        break
                    # run_on_scheduler closures (ISSUE 19): popped under
                    # the lock, run outside it — between ticks, so the
                    # pool buffers are quiescent (no donated jit call in
                    # flight) and the radix tree is consistent. With a
                    # tick in flight they wait: the turn below reads it
                    # and leaves none in flight (_may_go_ahead)
                    calls = None
                    if self._host_calls and self._inflight is None:
                        calls = list(self._host_calls)
                        self._host_calls.clear()
                    if not busy and not calls:
                        if self._idle_t0 is None:
                            self._idle_t0 = time.perf_counter()
                        self._cv.wait(0.05)
                        continue
                    self._end_idle()
                    die = self._die_tick
                if calls:
                    for c in calls:
                        c.run(self)
                    if not busy:
                        continue
                self._ticks += 1
                if die is not None and self._ticks >= die:
                    # fail_at_tick (replica_flap chaos / operator kill):
                    # indistinguishable from a real scheduler crash
                    raise _faults.InjectedCrash(
                        f"injected flap crash (replica {self.replica_id}, "
                        f"tick {self._ticks})")
                if _faults.ENABLED[0]:
                    # serving chaos hooks (tick-keyed, per replica):
                    # slow_tick stalls the scheduler (drives the brownout
                    # EWMA and the watchdog latency rung), replica_crash
                    # kills it (drives router failover)
                    f = _faults.FAULTS.take_tick(
                        "slow_tick", self.replica_id, self._ticks)
                    if f is not None:
                        FAULTS_INJECTED.add()
                        time.sleep(f.secs)
                    f = _faults.FAULTS.take_tick(
                        "replica_crash", self.replica_id, self._ticks)
                    if f is not None:
                        FAULTS_INJECTED.add()
                        raise _faults.InjectedCrash(
                            f"injected replica crash (replica "
                            f"{self.replica_id}, tick {self._ticks})")
                # one span tree per scheduler turn; every child carries
                # the turn's id as ``tick``
                with span("serving.turn", cat="serving",
                          args=self._tick_args()):
                    with span("serving.admit", cat="serving",
                              args=self._tick_args()):
                        self._admit()
                    self._prefill_chunk_tick()
                    if self._inflight is not None or any(
                            s is not None for s in self._slots):
                        self._decode_tick()
        except BaseException as e:  # noqa: BLE001 — fail every request, not silently
            self._abort(e)
        finally:
            with self._cv:
                self._end_idle()
                self._stop = True
                leftovers = list(self._queue)
                self._queue.clear()
                stranded = list(self._host_calls)
                self._host_calls.clear()
                SERVING_QUEUE_DEPTH.set(0)
                self._cv.notify_all()
            for c in stranded:
                c.fail(RuntimeError(
                    "engine shut down before the host call ran"))
            for req in leftovers:
                req._finish(SHUTDOWN)
            for s, st in enumerate(self._slots):
                if st is not None:
                    self._evict(s, SHUTDOWN)

    def _tick_args(self, **extra) -> Optional[dict]:
        """Span args carrying the turn's id — built only when something
        records (the off path allocates nothing)."""
        if not recording():
            return None
        extra["tick"] = self._ticks
        return extra

    def _chain_args(self, req: GenerationRequest, **extra) -> dict:
        """Args of a link in a request's chain: its ``rid`` always, its
        trace ids too where a front end minted a context (call under
        ``recording()`` only: a trace span id is allocated)."""
        args = self._tick_args(rid=req.rid, **extra)
        return args if req.trace is None else req.trace.args(**args)

    # -- the traced window ---------------------------------------------------
    def _end_idle(self) -> None:
        """Work reached the scheduler (a request or a host call queued),
        or it stops: its stretch with none (no queue, no open slot, no
        tick in flight, no host call) ends, one ``serving.idle`` span on
        the scheduler's line where anything records. Under ``self._cv``,
        on whichever thread brought the work: the scheduler's wake-up is
        time with work."""
        t0, self._idle_t0 = self._idle_t0, None
        if t0 is not None and recording():
            emit_complete("serving.idle", t0, time.perf_counter() - t0,
                          cat="serving", tid=self._tid())

    def _tid(self) -> int:
        return (self._thread.ident or 0) & 0x7FFFFFFF

    def _arm_trace(self) -> None:
        """Register the window's ``on_stop`` callback, once a window."""
        with self._cv:
            if not self._trace_armed:
                self._trace_armed = True
                on_stop(self._on_trace_stop)

    def _note_program(self, fn, args, signature: str) -> None:
        """A dispatch under tracing: the window keeps the avals of
        ``fn`` (one program at one signature, ``_program``) the first time
        it sees it."""
        if self._programs.note(fn, args, signature):
            self._arm_trace()

    def _on_trace_stop(self, writer) -> None:
        """``on_stop``: the idle stretch still open, up to now (what is
        left of it is another window's); one ``op_scopes`` table for each
        program the window dispatched, its labels the engine's scopes;
        then a ``serving.op_scopes`` span over the callback's own time
        (the engine serves on, untraced, meanwhile) and the
        ``serving_engine`` event: the spans the engine records and the
        callback's seconds. Runs on the thread that stops the trace."""
        t0 = time.perf_counter()
        with self._cv:
            self._trace_armed = False
            if self._idle_t0 is not None:
                writer.add_complete("serving.idle", self._idle_t0,
                                    t0 - self._idle_t0, tid=self._tid(),
                                    cat="serving")
                self._idle_t0 = t0
        tables = 0
        try:
            tables = self._programs.emit(writer, known=_SCOPES)
        finally:
            seconds = time.perf_counter() - t0
            writer.add_complete("serving.op_scopes", t0, seconds,
                                cat="serving", args={"tables": tables})
            writer.add_metadata("serving_engine", {
                "spans": list(_SPANS), "tables": tables,
                "seconds": seconds, "replica": self.replica_id})

    def _check_open(self) -> None:
        """Fail fast once the scheduler is gone: nothing will ever drain
        the queue again, so enqueueing would hang the caller forever.
        After a crash the stored cause rides the error so callers see WHY
        the engine died, not just that it is closed."""
        if not self._stop:
            return
        if self._error is not None:
            raise RuntimeError(
                f"InferenceEngine scheduler crashed: "
                f"{type(self._error).__name__}: {self._error}") \
                from self._error
        raise RuntimeError("InferenceEngine is shut down")

    def _abort(self, err: BaseException) -> None:
        # black-box dump at the moment of death: the last ring of spans/
        # gauge deltas, named per host so multi-host dumps merge (no-op
        # when no flight recorder is armed; never raises)
        dump_flight(f"engine_abort_{type(err).__name__}",
                    extra={"replica": self.replica_id,
                           "error": f"{type(err).__name__}: {err}"})
        with self._cv:
            # close the engine BEFORE failing requests so a racing
            # submit() cannot slip into the dead queue
            self._error = err
            self._stop = True
            leftovers = list(self._queue)
            self._queue.clear()
            self._cv.notify_all()
        for s, st in enumerate(self._slots):
            if st is not None:
                # clear the slot FIRST: a router failover may leave the
                # request unfinished (adopted by a survivor), and the
                # _run finally block must not re-finish it as SHUTDOWN
                self._slots[s] = None
                st.req._finish(ERROR, err)
        for req in leftovers:
            req._finish(ERROR, err)

    def _shed_expired(self) -> None:
        """Shed queued work that can no longer finish — deadline-expired
        or cancelled requests leave the queue at the NEXT tick, before
        any prefill is spent on them, wherever they sit in line (not
        just at the head). The front end maps an empty-handed deadline
        finish to 503 + Retry-After; ``serving_deadline_sheds`` counts
        the sheds so overload_report can tell shed load from served."""
        now = time.monotonic()
        shed: List[GenerationRequest] = []
        with self._cv:
            if not self._queue:
                return
            keep: collections.deque = collections.deque()
            for req in self._queue:
                if req._cancelled or (req.deadline is not None
                                      and now > req.deadline):
                    shed.append(req)
                else:
                    keep.append(req)
            if not shed:
                return
            self._queue = keep
            SERVING_QUEUE_DEPTH.set(len(self._queue))
            self._cv.notify_all()   # wake submitters blocked on full
        for req in shed:
            if req._cancelled:
                req._finish(CANCELLED)
            else:
                SERVING_DEADLINE_SHEDS.add(1)
                req._finish(DEADLINE)

    def _admit(self) -> None:
        """Move queued requests into free slots: capacity-check the head
        of the queue against the free-block pool of a shard that also
        has a free slot (queue-until-available — a too-long prompt waits
        for evictions instead of being rejected; multi-chip admission
        lands in the shard with the most free blocks), then park the
        prompt on the slot for the chunked-prefill tick."""
        self._shed_expired()
        while self.cache.free_count > 0:
            with self._cv:
                if not self._queue:
                    break
                head = self._queue[0]
                seq = head._resume[0] if head._resume is not None \
                    else head.prompt
                place = self._admit_place(seq)
                if place is None:
                    break   # head-of-line waits for blocks to free up
                req = self._queue.popleft()
                SERVING_QUEUE_DEPTH.set(len(self._queue))
                self._cv.notify_all()   # wake submitters blocked on full
            if req._cancelled:
                req._finish(CANCELLED)
                continue
            if req.deadline is not None and time.monotonic() > req.deadline:
                # expired while queued: shed BEFORE spending any prefill
                SERVING_DEADLINE_SHEDS.add(1)
                req._finish(DEADLINE)
                continue
            if req._t_submit:
                wait_ms = (time.monotonic() - req._t_submit) * 1e3
                SERVING_QUEUE_WAIT_MS.observe(wait_ms)
                if self.overload is not None:
                    self.overload.observe_queue_wait(wait_ms)
                if recording():
                    # first link of the request's chain: submit -> admit
                    emit_complete(
                        "serving.queue_wait",
                        time.perf_counter() - wait_ms / 1e3, wait_ms / 1e3,
                        cat="serving",
                        args=self._chain_args(
                            req, resumed=req._resume is not None))
            shard, m_len, m_blocks = place
            slot = self.cache.alloc(prefer_shard=shard)
            st = _Slot(req)
            self._admit_seq += 1
            st.admit_order = self._admit_seq
            if req._resume is not None:
                st.resume_last = req._resume[1]
                req._resume = None
            if self._prefix is not None:
                m_len = self._splice_prefix(slot, m_len, m_blocks)
                self._prefix.note_lookup(m_len, seq.size)
            if m_len > 0:
                st.length = m_len
                st.tail_mode = True
                self.cache.lengths[slot] = m_len
            st.pending = seq[m_len:]
            self._slots[slot] = st
        SERVING_SLOT_OCCUPANCY.set(self.cache.occupancy)

    def _admit_place(self, seq):
        """Where the head request should land: ``(shard, matched_len,
        matched_blocks)``, or None to queue-until-available.

        Without the prefix cache this is PR-10's most-free-blocks shard
        pick. With it, each eligible shard is scored by the radix match
        its tree offers — a shard only needs free blocks for the
        UNCACHED tail (+1 when the last matched block is partially used
        and must be CoW-duplicated), and LRU tree leaves count toward
        capacity because the scheduler reclaims them before giving up."""
        need_total = int(seq.size) + 1
        if self._prefix is None:
            shard = self.cache.admit_shard(need_total)
            return None if shard is None else (shard, 0, [])
        best = None          # (headroom, shard)
        for d in self.cache.free_slot_shards:
            m_len, m_blocks = self._prefix.match(d, seq)
            need = self.cache.blocks_for(need_total) - len(m_blocks) \
                + (1 if m_len % self.block_size else 0)
            avail = self.cache.free_blocks_of(d) \
                + self._prefix.evictable_count(d)
            if need <= avail and (best is None or avail - need > best[0]):
                best = (avail - need, d)
        if best is None:
            return None
        d = best[1]
        # reclaim LRU leaves to cover the shortfall, then RE-match: the
        # eviction could have clipped the matched path itself (only when
        # the tree is down to this very prefix)
        m_len, m_blocks = self._prefix.match(d, seq)
        need = self.cache.blocks_for(need_total) - len(m_blocks) \
            + (1 if m_len % self.block_size else 0)
        short = need - self.cache.free_blocks_of(d)
        if short > 0:
            self._prefix.evict(d, short)
            m_len, m_blocks = self._prefix.match(d, seq)
            need = self.cache.blocks_for(need_total) - len(m_blocks) \
                + (1 if m_len % self.block_size else 0)
            if need > self.cache.free_blocks_of(d):
                return None
        return d, m_len, m_blocks

    def _splice_prefix(self, slot: int, m_len: int, m_blocks) -> int:
        """Wire a radix match into a fresh slot's table: take one
        reference per matched block, and copy-on-write the last block
        when the match ends mid-block (the slot will write offsets the
        tree's readers must never see change). Returns the matched
        length actually kept (trimmed to the block boundary if the CoW
        allocation loses a race with pool pressure)."""
        if m_len == 0:
            return 0
        self.cache.splice(slot, m_blocks)
        if m_len % self.block_size == 0:
            return m_len
        nb = self.cache.alloc_block(self.cache.shard_of(slot))
        if nb is None:
            # no block for the copy: drop the partial block from the
            # match instead (its full-block prefix is still shared)
            self.cache.block_tables[slot].pop()
            self.cache.unref_block(m_blocks[-1])
            return (m_len // self.block_size) * self.block_size
        src = int(m_blocks[-1])
        self.cache.kb, self.cache.vb = self._cow_jit(
            self.cache.kb, self.cache.vb, np.int32(src), np.int32(nb))
        self.cache.replace_block(slot, len(m_blocks) - 1, nb)
        PREFIX_COW_COPIES.add(1)
        return m_len

    def _reclaim_blocks(self, slot: int, n_tokens: int) -> bool:
        """Try to make ``grow(slot, n_tokens)`` succeed by evicting LRU
        prefix-tree leaves from the slot's shard — the reclaim step that
        runs BEFORE youngest-first preemption ever fires."""
        if self._prefix is None:
            return False
        shard = self.cache.shard_of(slot)
        missing = self.cache.blocks_for(n_tokens) \
            - len(self.cache.block_tables[slot]) \
            - self.cache.free_blocks_of(shard)
        if missing <= 0:
            return True
        return self._prefix.evict(shard, missing) >= missing

    def _width_bucket(self, n_blocks: int) -> int:
        b = 1
        while b < n_blocks:
            b *= 2
        return min(b, self.cache.table_width)

    def _stream_key(self, rid: int, draw: int):
        """Host-side stream key for a prompt's first token: the same
        (seed, request, draw) fold the batched steps compute in-jit."""
        return jax.random.fold_in(
            jax.random.fold_in(self._base_key, rid % (2**31 - 1)), draw)

    def _mask_row(self, req: GenerationRequest) -> np.ndarray:
        """(1, V) bool sampling mask for one request's next token —
        all-true when unconstrained, the automaton's live-token set
        (padded to the model vocab) otherwise."""
        if req.constraint is None:
            return self._ones_mask[:1]
        m = req.constraint.mask()
        if m.shape[0] == self.cfg.vocab_size:
            return m[None]
        out = np.zeros((1, self.cfg.vocab_size), bool)
        out[0, :m.shape[0]] = m
        return out

    def _push_first(self, st: _Slot, tok: int) -> None:
        """Stream a request's first token and close the middle link of
        its chain: ``serving.admit_to_first`` (admit -> first token)."""
        st.req._push(tok)
        self._note_tokens(1)
        if recording():
            emit_complete(
                "serving.admit_to_first", st.t_admit,
                time.perf_counter() - st.t_admit, cat="serving",
                args=self._chain_args(st.req, chunks=st.chunks))

    # -- chunked prefill + preemption ----------------------------------------
    def _open_decode_streams(self) -> int:
        return sum(1 for st in self._slots
                   if st is not None and st.pending is None)

    def _prefill_chunk_tick(self) -> None:
        """Advance every mid-prefill slot by at most one prefill_chunk —
        the decode tick follows in the same scheduler iteration, so open
        streams never wait more than a chunk's work per tick."""
        for slot in range(self.n_slots):
            st = self._slots[slot]
            if st is None or st.pending is None:
                continue
            if st.req._cancelled:
                self._evict(slot, CANCELLED)
            elif st.req.deadline is not None \
                    and time.monotonic() > st.req.deadline:
                self._evict(slot, DEADLINE)
            else:
                self._prefill_one_chunk(slot, st)

    def _prefill_one_chunk(self, slot: int, st: _Slot) -> None:
        pending = st.pending
        chunk_cap = self.prefill_chunk
        if self.overload is not None:
            # brownout rung 2: shrink chunks so long prompts yield the
            # scheduler to open streams more often (re-rounded to the
            # block size, floored at one block)
            chunk_cap = max(self.block_size,
                            (self.overload.prefill_chunk(chunk_cap)
                             // self.block_size) * self.block_size)
        c_true = min(int(pending.size), chunk_cap)
        bs = self.block_size
        c_pad = -(-c_true // bs) * bs    # one compile per padded length
        if st.tail_mode:
            # prefix-matched slots continue from an UNALIGNED length;
            # clamp the pad so scatter positions never run past the
            # table (near the seq_len cap the pad is trimmed odd — a
            # rare extra compile, not a corruption)
            c_pad = min(c_pad, self.cache.table_width * bs - st.length)
        while not self.cache.grow(slot, st.length + c_pad):
            # pool exhausted: reclaim LRU prefix-tree leaves first, then
            # preempt strictly-younger work, else wait for an eviction
            # (the oldest slot is never preempted, so the engine always
            # makes progress — no preemption livelock)
            if self._reclaim_blocks(slot, st.length + c_pad):
                continue
            if self._inflight is not None:
                # the victim may be a lane of the tick in flight: read
                # that tick first (its evictions may free the blocks)
                self._sync()
                continue
            victim = self._youngest_slot(exclude=slot)
            if victim is None \
                    or self._slots[victim].admit_order <= st.admit_order:
                return
            self._preempt(victim)
        last = c_true == pending.size
        t0 = time.perf_counter()
        ck_args = {"slot": slot, "start": st.length, "chunk": c_true,
                   "tick": self._ticks,
                   "open_streams": self._open_decode_streams()}
        flow = None
        if st.req.trace is not None and recording():
            ck_args.update(st.req.trace.args(rid=st.req.rid))
            flow = st.req.trace.trace_id
        with span("serving.prefill_chunk", cat="serving", args=ck_args,
                  flow=flow):
            toks = np.zeros((1, c_pad), np.int32)
            toks[0, :c_true] = pending[:c_true]
            row = self.cache.table_row(slot)[:self._width_bucket(
                self.cache.blocks_for(st.length + c_pad))]
            if st.tail_mode:
                kind, args = "tail", (self._params, self.cache.kb, self.cache.vb,
                        jnp.asarray(row), jnp.asarray(toks),
                        np.int32(st.length))
            elif self.draft is not None:
                kind, args = "chunk_spec", (self._params, self._draft_params, self.cache.kb,
                        self.cache.vb, self.draft_cache.k,
                        self.draft_cache.v, jnp.asarray(row), np.int32(slot),
                        jnp.asarray(toks), np.int32(st.length))
            else:
                kind, args = "chunk", (self._params, *self.cache.pool, jnp.asarray(row),
                        jnp.asarray(toks), np.int32(st.length),
                        np.int32(c_true))
            fn, sig = self._program(kind, c_pad, row.size)
            if TRACING[0]:
                self._note_program(fn, args, sig)
            got = fn(*args)
            if st.tail_mode:
                logits, self.cache.kb, self.cache.vb = got
            elif self.draft is not None:
                (logits, self.cache.kb, self.cache.vb, self.draft_cache.k,
                 self.draft_cache.v) = got
            else:
                logits = got[0]
                self.cache.pool = tuple(got[1:1 + self._n_pool])
                if self._model.routed:
                    # read once the device is known to be past the chunk
                    # (the tick's wait, a first token): dispatch stays
                    # asynchronous, and the span's args are filled then
                    self._moe_pending.append((ck_args, got[-1]))
        ck_ms = (time.perf_counter() - t0) * 1e3
        self._note_ms(SERVING_PREFILL_MS, "_prefill_ms", ck_ms)
        SERVING_PREFILL_CHUNK_MS.observe(ck_ms)
        SERVING_PREFILL_CHUNKS.add(1)
        st.chunks += 1
        st.length += c_true
        self.cache.lengths[slot] = st.length
        st.pending = None if last else pending[c_true:]
        self.cache.update_gauges()
        if not last:
            return
        if self._prefix is not None and st.length >= st.req.prompt.size:
            # the whole prompt is cached now — register it so the NEXT
            # identical prefix splices these blocks instead of computing
            self._prefix.insert(self.cache.shard_of(slot), st.req.prompt,
                                self.cache.block_tables[slot])
        if st.resume_last is not None:
            # resumed after preemption: the "next" token was already
            # streamed before the preemption — just rebuild decode state
            st.last_token = st.resume_last
            st.resume_last = None
            return
        # the eager slice + sample + int() is where the host waits for
        # the chunk (and whatever was queued ahead of it) to finish
        with span("serving.first_token", cat="serving",
                  args=self._tick_args(rid=st.req.rid, slot=slot)):
            tok = sample_one(
                logits[0:1, c_true - 1], self._stream_key(st.req.rid, 0),
                st.req.temperature, st.req.top_k, st.req.top_p,
                mask=jnp.asarray(self._mask_row(st.req)))
            st.last_token = tok
            st.generated = 1
            self._note_moe_pending()
            self._push_first(st, tok)
            reason = self._finish_reason(st, tok)
            if reason is not None:
                self._evict(slot, reason)

    def _youngest_slot(self, exclude: int) -> Optional[int]:
        best = None
        for s, st in enumerate(self._slots):
            if st is None or s == exclude:
                continue
            if best is None \
                    or st.admit_order > self._slots[best].admit_order:
                best = s
        return best

    def _preempt(self, slot: int) -> None:
        """Return a slot's blocks to the pool and its request to the HEAD
        of the queue; it resumes later by re-prefilling prompt+generated
        (recompute preemption — tokens already streamed are unaffected)."""
        st = self._slots[slot]
        self._slots[slot] = None
        self.cache.release(slot)
        SERVING_PREEMPTIONS.add(1)
        if st.req.tokens:
            # decode state: cache held prompt + tokens[:-1]; tokens[-1] is
            # the next decode input
            seq = np.concatenate(
                [st.req.prompt,
                 np.asarray(st.req.tokens[:-1], np.int32)]).astype(np.int32)
            st.req._resume = (seq, int(st.req.tokens[-1]))
        else:
            st.req._resume = None       # mid-prefill: just start over
        with self._cv:
            self._queue.appendleft(st.req)
            SERVING_QUEUE_DEPTH.set(len(self._queue))
        SERVING_SLOT_OCCUPANCY.set(self.cache.occupancy)

    def _grow_for_decode(self, active: List[int]) -> List[int]:
        """Ensure each decoding slot's table covers its next write
        position, preempting the youngest slot when the pool runs dry.
        Oldest slots get blocks first (FIFO fairness)."""
        ready = []
        for s in sorted(active, key=lambda s: self._slots[s].admit_order):
            st = self._slots[s]
            if st is None:       # preempted as a victim earlier this tick
                continue
            while not self.cache.grow(s, st.length + 1):
                if self._reclaim_blocks(s, st.length + 1):
                    continue
                victim = self._youngest_slot(exclude=s)
                if victim is None:
                    # alone and the pool is spent: nothing will ever free
                    # a block — cache capacity reached
                    self._evict(s, LENGTH)
                    break
                if self._slots[victim].admit_order <= st.admit_order:
                    break        # only younger work is preemptible: stall
                self._preempt(victim)
            else:
                ready.append(s)
        return [s for s in ready if self._slots[s] is not None]

    def _try_spec_grow(self, active: List[int]) -> bool:
        """Spec headroom: grow every active table to cover the k
        proposals + bonus WITHOUT preempting anyone (speculation is an
        optimization, never worth evicting work for). False → this tick
        falls back to the plain one-token program."""
        for s in active:
            st = self._slots[s]
            if not self.cache.grow(s, st.length + self.spec_k + 1):
                return False
        return True

    def _shard_load(self, active: List[int]) -> List[int]:
        per = self.n_slots // self._shards
        load = [0] * self._shards
        for s in active:
            load[s // per] += 1
        return load

    def _may_go_ahead(self, now: float) -> bool:
        """Whether this turn's tick may leave before the tick in flight
        is read. Each refusal is something the scheduler observes, not a
        knob: a speculative or a watchdog engine (the tick's outputs
        decide what stands), fault injection, a live constrained row (its
        mask needs the token), a lane in flight cancelled or past its
        deadline, host calls waiting for a quiescent pool, shutdown and
        evacuation. A refused turn reads the tick in flight first and
        reads its own tick before it ends."""
        if (self.draft is not None or self._watchdog is not None
                or _faults.ENABLED[0] or self._stop or self._evacuate
                or self._host_calls):
            return False
        if any(st is not None and st.pending is None
               and st.req.constraint is not None for st in self._slots):
            return False
        if self._inflight is not None:
            for s, st in self._inflight.lanes.items():
                if self._slots[s] is st and (
                        st.req._cancelled
                        or (st.req.deadline is not None
                            and now > st.req.deadline)):
                    return False
        return True

    def _decode_tick(self) -> None:
        """One decode tick. Where ``_may_go_ahead`` allows, tick n+1
        leaves from the host's projected state while tick n is in
        flight: a lane of tick n is one token on, and its input is tick
        n's output, on the device. Only then is tick n read and emitted,
        so the read, the emit and the next turn's host work run while
        the device runs tick n+1. Otherwise the tick in flight is read
        first and this tick is read in the same turn."""
        now = time.monotonic()
        ahead = self._may_go_ahead(now)
        if not ahead:
            self._sync()
        with span("serving.decode_prep", cat="serving",
                  args=self._tick_args()):
            batch = self._prep_decode(now, ahead)
        if batch is _NO_ROOM:
            # a table cannot grow from its shard's free blocks alone: read
            # the tick in flight, then reclaim or preempt as a synchronous
            # tick does
            self._sync()
            ahead = False
            with span("serving.decode_prep", cat="serving",
                      args=self._tick_args()):
                batch = self._prep_decode(now, False)
        if batch is None:
            self._sync()        # nothing to dispatch: read the tick in flight
            return
        done = self._dispatch_decode(batch, ahead)
        if done is not None:
            self._emit_tick(done)
        # the router stats of this turn's chunks, which the device runs
        # ahead of the tick just dispatched: read after the emit, so that
        # tick n's tokens do not wait for them
        self._note_moe_pending()

    def _prep_decode(self, now: float, ahead: bool):
        """The tick's host work ahead of the dispatch: sweep, grow, the
        batch's host arrays and block tables. With ``ahead`` a lane of
        the tick in flight is projected one token on (its position, its
        draw and its table) and takes that tick's output as its input;
        a lane whose token in flight is its last sits the tick out.
        Returns the batch, None when no lane decodes, or _NO_ROOM when a
        projected table cannot grow from free blocks alone."""
        for s, st in enumerate(self._slots):
            if st is None:
                continue
            if st.req._cancelled:
                self._evict(s, CANCELLED)
            elif st.req.deadline is not None and now > st.req.deadline:
                self._evict(s, DEADLINE)
        flying = self._inflight.lanes if self._inflight is not None else {}
        active, carried = [], set()
        for s, st in enumerate(self._slots):
            if st is None or st.pending is not None:
                continue
            if flying.get(s) is st:
                if (st.generated + 1 >= st.req.max_new_tokens
                        or st.length + 1 >= self.max_len):
                    continue
                carried.add(s)
            active.append(s)
        if not active:
            return None
        # speculation needs k+1 positions of cache headroom on every
        # active slot; a near-cap slot drops the whole tick to the plain
        # one-token program (correct, just unaccelerated) rather than
        # splitting the batch across two programs. Constrained rows
        # force the same fallback: draft proposals are not mask-aware,
        # so speculating through an automaton would emit illegal tokens.
        constrained = [s for s in active
                       if self._slots[s].req.constraint is not None]
        use_spec = (self.draft is not None
                    and (self.overload is None
                         or self.overload.spec_allowed())
                    and all(self._slots[s].length + self.spec_k + 1
                            <= self.max_len for s in active))
        if use_spec and constrained:
            use_spec = False
            CONSTRAINED_FALLBACK_TICKS.add(1)
        if use_spec:
            use_spec = self._try_spec_grow(active)
        if ahead:
            for s in sorted(active,
                            key=lambda s: self._slots[s].admit_order):
                st = self._slots[s]
                if not self.cache.grow(s, st.length + 1 + (s in carried)):
                    return _NO_ROOM
        elif not use_spec:
            active = self._grow_for_decode(active)
            if not active:
                return None

        if _faults.ENABLED[0]:
            # serving_nan fault (FLAGS_fault_inject, keyed by REQUEST id):
            # NaN the slot's cached K/V — the deterministic stand-in for
            # poisoned HBM — so the watchdog path is testable on CPU
            for s in active:
                f = _faults.FAULTS.take_request("serving_nan",
                                               self._slots[s].req.rid)
                if f is not None:
                    FAULTS_INJECTED.add()
                    self._poison_slot(s)

        positions = np.zeros(self.n_slots, np.int32)
        tokens = np.zeros(self.n_slots, np.int32)
        use_prev = np.zeros(self.n_slots, bool)
        temps = np.zeros(self.n_slots, np.float32)
        top_ks = np.zeros(self.n_slots, np.int32)
        top_ps = np.ones(self.n_slots, np.float32)
        rids = np.zeros(self.n_slots, np.int32)
        steps = np.zeros(self.n_slots, np.int32)
        for s in active:
            st = self._slots[s]
            on = s in carried
            positions[s] = st.length + on
            tokens[s] = st.last_token
            use_prev[s] = on
            temps[s] = st.req.temperature
            top_ks[s] = st.req.top_k
            top_ps[s] = st.req.top_p
            rids[s] = st.req.rid % (2**31 - 1)
            steps[s] = len(st.req.tokens) + on
        # per-slot sampling mask: the device-resident all-true buffer on
        # unconstrained ticks (no per-tick transfer), a fresh host array
        # carrying each constrained row's automaton mask otherwise
        if constrained:
            masks = self._ones_mask.copy()
            for s in constrained:
                masks[s] = self._mask_row(self._slots[s].req)[0]
            mask_arg = jnp.asarray(masks)
        else:
            mask_arg = self._mask_dev
        return (active, positions, tokens, use_prev, rids, steps, temps,
                top_ks, top_ps, mask_arg, use_spec)

    def _dispatch_decode(self, batch, ahead: bool) -> Optional[_Tick]:
        """Dispatch the tick (``serving.decode_step``), then read the
        tick whose tokens are due: with ``ahead`` the one that was in
        flight, and this one stays in flight; else this one. Returns the
        tick read, or None."""
        (active, positions, tokens, use_prev, rids, steps, temps, top_ks,
         top_ps, mask_arg, use_spec) = batch
        # which way the tick's sampling goes, from the rows' parameters
        # (a tie that overflows the candidates sorts unseen from here)
        path = sample_path(temps, top_ks, top_ps)
        if path == "greedy":
            SERVING_SAMPLE_TICKS_GREEDY.add(1)
        elif path == "select":
            SERVING_SAMPLE_TICKS_SELECT.add(1)
        else:
            SERVING_SAMPLE_TICKS_SORT.add(1)
        behind = self._inflight is not None     # a tick is still unread
        if behind:
            SERVING_DECODE_TICKS_AHEAD.add(1)
        else:
            SERVING_DECODE_TICKS_SYNCED.add(1)
        span_args = {"batch": len(active), "tick": self._ticks,
                     "sample_path": path, "ahead": int(behind)}
        if self.replica_id is not None:
            span_args["replica"] = self.replica_id
        if self._shards > 1:
            span_args["shards"] = self._shards
            span_args["shard_load"] = self._shard_load(active)
        if use_spec:
            span_args["spec_k"] = self.spec_k
        # the span's event holds span_args itself: what the tick's read
        # adds later (router stats, speculation counts) lands in it
        with span("serving.decode_step", cat="serving", args=span_args):
            t0 = time.perf_counter()
            if use_spec:
                outs = self._spec_dispatch(active, positions, tokens, rids,
                                           steps, temps, top_ks, top_ps)
            else:
                # table width bucketed to the live maximum (next pow2):
                # attention/gather work tracks LIVE tokens, not the
                # worst-case table — one compile per width bucket,
                # log2(table_width) programs total
                tables = self.cache.tables_array(active)
                held = [len(self.cache.block_tables[s]) for s in active]
                tables = tables[:, :self._width_bucket(max(held))]
                # how much of the tabled width is live: the decode
                # kernel walks the live blocks only
                live, tabled = sum(held), tables.size
                span_args["decode_blocks_live"] = live
                span_args["decode_blocks_tabled"] = tabled
                SERVING_DECODE_BLOCKS_LIVE.add(live)
                SERVING_DECODE_BLOCKS_TABLED.add(tabled)
                if self.cache.state_blocks:
                    # each active lane's whole state is read, decayed
                    # and written back by this tick, whatever its context
                    span_args["state_slots_live"] = live
                    SERVING_STATE_SLOTS_LIVE.add(live)
                else:
                    # one new row a layer for each active lane; a grid
                    # over every lane would write n_slots x layers
                    layers = self.cache.pool[0].shape[1]
                    span_args["kv_rows_written"] = len(active) * layers
                    span_args["kv_rows_grid"] = self.n_slots * layers
                    SERVING_KV_ROWS_WRITTEN.add(len(active) * layers)
                fn, sig = self._program("decode", tables.shape[1])
                args = (self._decode_params, *self.cache.pool, tables,
                        positions, tokens, self._prev_toks, use_prev,
                        self._base_key, rids, steps, temps, top_ks, top_ps,
                        mask_arg)
                if TRACING[0]:
                    self._note_program(fn, args, sig)
                got = fn(*args)
                moe_stats = health = None
                if self._routed:
                    *got, moe_stats = got
                if self._watchdog is not None:
                    out, health, *pool = got
                else:
                    out, *pool = got
                self.cache.pool = tuple(pool)
                self._prev_toks = out
                outs = (out, None, health, moe_stats)
            tick = _Tick(*outs, {s: self._slots[s] for s in active}, t0,
                         span_args)
            if ahead:
                done, self._inflight = self._inflight, tick
            else:
                done = tick
            if done is not None:
                self._read(done)
        return done

    def _spec_dispatch(self, active, positions, tokens, rids, steps, temps,
                       top_ks, top_ps):
        """Dispatch the one-program speculative tick: draft proposes
        spec_k, target verifies k+1 positions, rejection sampling
        accepts. Returns its outputs on the device: (out_tokens (B, k+1),
        n_emit (B,), health (B,) or None, no router stats) — health only
        when the watchdog is armed, computed over every verify position
        inside the same compiled program."""
        health = None
        tables = self.cache.tables_array(active)
        tables = tables[:, :self._width_bucket(
            max(len(self.cache.block_tables[s]) for s in active))]
        args = (self._decode_params, self._draft_params, self.cache.kb,
                self.cache.vb, self.draft_cache.k, self.draft_cache.v,
                tables, positions, tokens, self._base_key, rids, steps,
                temps, top_ks, top_ps)
        fn, sig = self._program("spec", tables.shape[1])
        if TRACING[0]:
            self._note_program(fn, args, sig)
        got = fn(*args)
        if self._watchdog is not None:
            (out, n_emit, health, self.cache.kb, self.cache.vb,
             self.draft_cache.k, self.draft_cache.v) = got
        else:
            (out, n_emit, self.cache.kb, self.cache.vb,
             self.draft_cache.k, self.draft_cache.v) = got
        return out, n_emit, health, None

    def _read(self, tick: _Tick) -> None:
        """Block on a tick's tokens (``serving.device_wait``, whose
        ``reads`` is the turn that dispatched it); then its router stats,
        and a speculative tick's counts, go to its span's args."""
        with span("serving.device_wait", cat="serving",
                  args=self._tick_args(reads=tick.args["tick"])):
            tick.out = np.asarray(tick.out)
            if tick.n_emit is not None:
                tick.n_emit = np.asarray(tick.n_emit)
            if tick.health is not None:
                tick.health = np.asarray(tick.health)
        tick.ms = (time.perf_counter() - tick.t0) * 1e3
        if tick.moe is not None:
            self._note_moe(tick.moe, tick.args)
        if tick.n_emit is not None:
            tick.args["proposed"] = self.spec_k * len(tick.lanes)
            tick.args["accepted"] = int(sum(int(tick.n_emit[s]) - 1
                                            for s in tick.lanes))

    def _sync(self) -> None:
        """Read the tick in flight, if one is, and emit its tokens."""
        tick, self._inflight = self._inflight, None
        if tick is None:
            return
        self._read(tick)
        self._emit_tick(tick)
        self._note_moe_pending()

    def _emit_tick(self, tick: _Tick) -> None:
        """A read tick's latency to the gauges and the watchdog, then its
        tokens to their streams (``serving.emit``). A lane whose slot no
        longer holds the request it ran for (the stream ended at the
        tick before, or was cancelled) has its result discarded."""
        tick_ms = tick.ms
        self._note_ms(SERVING_DECODE_MS, "_decode_ms", tick_ms)
        SERVING_DECODE_TICK_MS.observe(tick_ms)
        if self.overload is not None:
            self.overload.observe_tick(tick_ms)
        if self._watchdog is not None:
            poisoned = [] if tick.health is None else \
                [s for s in tick.lanes if not bool(tick.health[s])]
            if poisoned:
                SERVING_WATCHDOG_TRIPS.add(len(poisoned))
                # the whole tick's outputs are dropped: poisoned streams
                # fail, healthy ones resume by replay — token-identical,
                # the same exactness contract as preemption-resume
                self._watchdog_restart(poisoned)
                return
            self._watchdog_latency(tick_ms)

        # push, finish, evict and the gauges: host work after the wait
        out, n_emit = tick.out, tick.n_emit
        with span("serving.emit", cat="serving", args=self._tick_args()):
            emitted = discarded = 0
            traced = []   # (req, tokens pushed) for per-request tick events
            for s, st in tick.lanes.items():
                if self._slots[s] is not st:
                    discarded += 1
                    continue
                burst = [int(out[s])] if n_emit is None \
                    else [int(t) for t in out[s, :int(n_emit[s])]]
                pushed = 0
                for tok in burst:
                    st.length += 1
                    st.generated += 1
                    st.last_token = tok
                    self.cache.lengths[s] = st.length
                    st.req._push(tok)
                    emitted += 1
                    pushed += 1
                    reason = self._finish_reason(st, tok)
                    if reason is not None:
                        self._evict(s, reason)
                        break
                if st.req.trace is not None:
                    traced.append((st.req, pushed))
            if traced and recording():
                # one per-request decode-tick event per traced participant:
                # the causal twin of the BATCHED serving.decode_step span,
                # letting request_report/chrome attribute this tick's time
                # to each request riding it (gated — no cost untraced)
                dur = tick_ms / 1e3
                for req, n_toks in traced:
                    rq_args = req.trace.args(rid=req.rid, tokens=n_toks,
                                             tick=tick.args["tick"])
                    if self.replica_id is not None:
                        rq_args["replica"] = self.replica_id
                    emit_complete("serving.decode_tick", tick.t0, dur,
                                  cat="serving", args=rq_args)
                    emit_flow("t", req.trace.trace_id, tick.t0)
            if discarded:
                tick.args["lanes_discarded"] = discarded
                SERVING_DECODE_LANES_DISCARDED.add(discarded)
            if n_emit is not None:
                self._note_spec(tick.args["proposed"], tick.args["accepted"])
            self._note_tokens(emitted)
            SERVING_SLOT_OCCUPANCY.set(self.cache.occupancy)
            # refresh kv_fragmentation vs lengths
            self.cache.update_gauges()

    def _finish_reason(self, st: _Slot, tok: int) -> Optional[str]:
        """Why generation stops after emitting ``tok`` (None = keep
        going). Called exactly once per emitted token, so this is also
        where a constrained request's automaton consumes the token."""
        if st.req.eos_id is not None and tok == st.req.eos_id:
            return EOS
        if st.req.constraint is not None:
            alive = st.req.constraint.advance(tok)
            if st.req.constraint.finished or not alive:
                return STOP    # match complete (or an unmasked escape-
                #                hatch token killed it) — stream is done
        if st.generated >= st.req.max_new_tokens:
            return LENGTH
        if st.length >= self.max_len:
            return LENGTH      # cache slot full — nothing further fits
        return None

    def _evict(self, slot: int, reason: str) -> None:
        st = self._slots[slot]
        self._slots[slot] = None
        self.cache.release(slot)
        SERVING_EVICTIONS.add(1)
        SERVING_SLOT_OCCUPANCY.set(self.cache.occupancy)
        st.req._finish(reason)

    # -- watchdog: NaN/latency sentinel + auto-restart -----------------------
    def _poison_slot(self, slot: int) -> None:
        """serving_nan fault effect: overwrite the slot's cached K/V
        blocks with NaN (the deterministic stand-in for poisoned HBM / a
        bad collective)."""
        nan = float("nan")
        rows = jnp.asarray(self.cache.block_tables[slot], jnp.int32)
        self.cache.kb = self.cache.kb.at[rows].set(nan)
        self.cache.vb = self.cache.vb.at[rows].set(nan)

    def _watchdog_latency(self, tick_ms: float) -> None:
        """Latency rung of the sentinel: ``latency_trips`` consecutive
        decode ticks over ``latency_budget_ms`` is a stall verdict —
        counted and timestamped for the trace, not restarted (a restart
        cannot make compute faster; an operator can)."""
        budget = self._watchdog["latency_budget_ms"]
        if not budget:
            return
        if tick_ms <= float(budget):
            self._slow_ticks = 0
            return
        self._slow_ticks += 1
        if self._slow_ticks >= int(self._watchdog["latency_trips"]):
            self._slow_ticks = 0
            SERVING_WATCHDOG_TRIPS.add()

    def _watchdog_restart(self, poisoned: List[int]) -> None:
        """Engine auto-restart from the last healthy state: fail ONLY the
        poisoned requests, requeue every healthy open stream with its
        token history (admission replays it through the preemption-resume
        path — continuations are token-identical because the per-request
        RNG streams are pure functions of (seed, rid, draw)), and rebuild
        the device cache + prefix tree from scratch — the old pool may
        hold NaN rows behind shared blocks or the garbage sink."""
        self._restarts += 1
        if self._restarts > int(self._watchdog["max_restarts"]):
            # the last rung: a persistently-poisoned engine fails loudly
            # (scheduler _abort fails every open request with this cause;
            # _abort also writes the flight dump)
            raise WatchdogTripped(
                f"watchdog restart budget exhausted "
                f"(max_restarts={self._watchdog['max_restarts']})")
        SERVING_WATCHDOG_RESTARTS.add()
        dump_flight("serving_watchdog_restart",
                    extra={"replica": self.replica_id,
                           "poisoned": sorted(poisoned),
                           "restart": self._restarts})
        bad = set(poisoned)
        healthy = sorted(
            ((st.admit_order, s) for s, st in enumerate(self._slots)
             if st is not None and s not in bad), reverse=True)
        for s in bad:
            st = self._slots[s]
            self._slots[s] = None
            SERVING_EVICTIONS.add(1)
            st.req._finish(WATCHDOG, WatchdogTripped(
                f"non-finite decode logits (request {st.req.rid})"))
        # youngest first through appendleft => oldest ends up at the
        # queue head, preserving admission order on replay
        for _, s in healthy:
            st = self._slots[s]
            self._slots[s] = None
            if st.req.tokens:
                seq = np.concatenate(
                    [st.req.prompt,
                     np.asarray(st.req.tokens[:-1],
                                np.int32)]).astype(np.int32)
                st.req._resume = (seq, int(st.req.tokens[-1]))
            else:
                st.req._resume = None   # mid-prefill: just start over
            with self._cv:
                self._queue.appendleft(st.req)
        self._reset_cache()
        with self._cv:
            SERVING_QUEUE_DEPTH.set(len(self._queue))
        SERVING_SLOT_OCCUPANCY.set(0)

    def _reset_cache(self) -> None:
        """Fresh zeroed cache buffers + accounting (and a fresh prefix
        tree — cached prefixes may reference poisoned blocks; dropping
        the cache costs recompute, never correctness)."""
        self.cache = self._build_cache()
        if self._prefix is not None:
            self._prefix = RadixPrefixCache(self.cache)
        if self.draft is not None:
            # the draft's K/V were computed alongside the poisoned
            # target rows — rebuild its fixed cache too, so the spec
            # path resumes from the same clean slate (ISSUE 14)
            self.draft_cache = self._build_draft_cache()
        self.cache.update_gauges()

    # -- gauges --------------------------------------------------------------
    def _note_moe(self, moe_stats, span_args=None) -> None:
        """Publish per-tick router stats: busiest-expert share (ppm
        gauge + per-expert % histogram — the spread IS the imbalance)
        and the cumulative dropped-assignment counter. Decode is
        dropless (C=T), so dropped stays 0 there; the counter exists
        for parity with training capacity accounting."""
        if self._model.routed:
            # a model that holds a share of its experts: (assignments per
            # expert over all of them, rows computed here, experts read,
            # the grouped kernel's row tiles: 0 where it did not run);
            # dropless by construction
            counts, held, reads, tiles = moe_stats
            dropped = 0
        else:
            (counts, dropped), held = moe_stats, None
        counts = np.asarray(counts, np.int64)
        total = int(counts.sum())
        if held is not None:
            held, reads, tiles = (int(np.asarray(v))
                                  for v in (held, reads, tiles))
            MOE_ASSIGNMENTS_ROUTED.add(total)
            MOE_ASSIGNMENTS_HELD.add(held)
            MOE_EXPERT_READS.add(reads)
            MOE_KERNEL_TILES.add(tiles)
            if span_args is not None:
                span_args.update(moe_assignments_routed=total,
                                 moe_assignments_held=held,
                                 moe_expert_reads=reads,
                                 moe_kernel_tiles=tiles)
        if total > 0:
            shares = counts / total
            MOE_EXPERT_LOAD.set(int(float(shares.max()) * 1e6))
            for sh in shares:
                MOE_EXPERT_SHARE_PCT.observe(float(sh) * 100.0)
        nd = int(np.asarray(dropped))
        if nd:
            MOE_TOKENS_DROPPED.add(nd)
        if span_args is not None and total > 0:
            span_args["moe_busiest_pct"] = round(
                float(counts.max()) / total * 100.0, 2)
            span_args["moe_dropped"] = nd

    def _note_moe_pending(self) -> None:
        """Router stats of chunks dispatched earlier, now that the
        device has run them: counters, and the args of each chunk's
        ``serving.prefill_chunk`` span (the event holds the dict)."""
        if not self._moe_pending:
            return
        pending, self._moe_pending = self._moe_pending, []
        for args, stats in pending:
            self._note_moe(stats, args)

    def _note_ms(self, gauge, attr: str, ms: float) -> None:
        old = getattr(self, attr)
        new = old + ms
        setattr(self, attr, new)
        gauge.add(int(new) - int(old))

    def _note_spec(self, proposed: int, accepted: int) -> None:
        SPEC_PROPOSED.add(proposed)
        SPEC_ACCEPTED.add(accepted)
        self._spec_prop += proposed
        self._spec_acc += accepted
        if self._spec_prop > 0:
            SPEC_ACCEPTANCE_RATE.set(
                int(round(100.0 * self._spec_acc / self._spec_prop)))

    def _note_tokens(self, n: int) -> None:
        # sliding window over the last N tick completions (deque maxlen):
        # the gauge tracks RECENT rate, so a load spike or an idle dip is
        # visible in trace reports instead of being flattened into a
        # lifetime average
        now = time.monotonic()
        self._window.append((now, n))
        window_span = now - self._window[0][0]
        if len(self._window) >= 2 and window_span > 0:
            total = sum(c for _, c in self._window)
            SERVING_TOKENS_PER_S.set(max(1, int(total / window_span)))
