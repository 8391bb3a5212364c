"""paddle_tpu.serving — continuous-batching inference engine and its
production traffic layer (ISSUE 4/7/10/11).

The generation-side counterpart of ``paddle_tpu.inference``: where the
Predictor serves one compiled program per call (the reference's
AnalysisPredictor shape), this package serves AUTOREGRESSIVE workloads —
many concurrent requests sharing one jitted KV-cache decode step,
Orca-style continuous batching instead of request-at-a-time — and, as
of ISSUE 11, speaks HTTP to real multi-tenant traffic.

Layers, bottom up:

- :mod:`kv_cache` — :class:`PagedKVCache`, the cache the engine serves
  the target model from: a shared block pool
  ``(n_blocks, layers, heads, block_size, head_dim)`` + per-slot block
  tables, host-side free lists and PER-BLOCK REFCOUNTS — slot memory
  proportional to LIVE tokens, admission gated on free blocks,
  ``free_slot`` decrements instead of freeing so blocks can be SHARED
  across slots (``splice``/``ref_block``/``replace_block`` are the
  prefix cache's contract), with ``kv_blocks_free`` / ``kv_blocks_used``
  / ``kv_fragmentation`` gauges and loud ``AssertionError`` on
  refcount/free-list corruption. ``shards=D`` (multi-chip) partitions
  the pool into per-shard block ranges. The pool's layout and what a
  block is come from the model (``models/serving_api.py``): for a model
  whose layers keep a recurrent state (``models/retention.py``) a block
  is one sequence's whole state, a slot owns one, and admission is by
  free states. :class:`KVCache`, fixed-slot
  donated buffers ``(slots, layers, heads, max_len, head_dim)``, is the
  speculative DRAFT model's private cache and nothing else;
- :mod:`prefix_cache` — :class:`~prefix_cache.RadixPrefixCache`
  (``FLAGS_prefix_cache=1``): a host-side radix tree keyed by token-id
  block chunks over that pool. Admission walks it, bumps refcounts on
  matched blocks and splices them into the new slot's table, so a
  shared system prompt prefills ONCE and fans out; only the uncached
  tail runs (``models.gpt_prefill_prefix`` continues from an unaligned
  cached length), a partially-used last block is copy-on-write
  duplicated first, and eviction is LRU-by-leaf over refcount-0 nodes —
  composing with, not replacing, pool-exhaustion preemption. Greedy
  output is pinned token-identical to the cache-cold engine;
- :func:`paddle_tpu.models.gpt_prefill_chunk` /
  ``gpt_decode_step_paged`` / ``gpt_verify_step_paged`` /
  ``gpt_prefill_prefix`` (the target's steps over the pool) and
  ``gpt_decode_step`` / ``gpt_verify_step`` (the draft's, over its
  fixed cache) — the cache-aware forward variants (they live with the
  model, reached through ``cfg.serving_model()``);
- :mod:`sampling` — fused greedy/temperature/top-k/top-p with per-slot
  parameters, per-REQUEST RNG streams, the speculative accept/resample
  rule, and per-row token MASKS (``mask=``) so constrained rows ride
  the same compiled program;
- :mod:`constrained` — structured decoding: JSON-schema / regex →
  byte-level DFA → per-state vocabulary masks
  (:func:`~constrained.compile_constraint`,
  :class:`~constrained.TokenConstraint`); pass the result to
  ``submit(constraint=...)`` and the stream ends with
  ``finish_reason="stop"`` when the match completes;
- :mod:`tokenizer` — the byte-level text front end:
  :class:`ByteTokenizer` (byte floor + optional merge vocab file) and
  :class:`StreamDetokenizer` for utf-8-safe live text streaming;
- :mod:`engine` — the scheduler: bounded queue with backpressure,
  block-capacity admission, CHUNKED prefill interleaved with decode
  (prefix-cache splicing; LRU tree reclaim, then youngest-first
  preemption), one batched decode step per tick, speculative decoding
  (``draft=``), multi-chip decode (``mesh=``/``FLAGS_serving_mesh``),
  eviction without draining, deadlines/cancellation, graceful shutdown,
  and the serving_*/prefix_*/constrained_* gauges + trace spans;
- :mod:`overload` — the brownout degradation ladder (ISSUE 13):
  :class:`~overload.OverloadController` EWMAs queue wait and decode
  tick latency against budgets and, with hysteresis, steps healthy →
  no_spec → small_chunks → capped_tokens → shed_bronze → shed_silver;
  the engine consults it for speculation/chunking, the front end for
  per-lane token caps and 503 sheds. No controller attached = pinned
  bit-identical serving;
- :mod:`router` — :class:`~router.EngineRouter` fronts N replica
  engines: least-loaded placement with radix-prefix affinity, health
  from scheduler liveness + tick-age heartbeat, and on replica death
  the open healthy streams are ADOPTED by survivors through the
  preemption-resume contract (token-identical continuations; only
  watchdog-poisoned requests fail). The replica set is DYNAMIC
  (``add_replica`` / ``remove_replica`` under the router lock, warming
  and draining states, orphan parking when the whole fleet dies). One
  replica, no faults = a pass-through pinned token-identical to the
  bare engine;
- :mod:`lifecycle` — :class:`~lifecycle.ReplicaSupervisor` (ISSUE 14)
  closes the health loop: replica death/wedge → respawn through an
  immediate → exponential-backoff → quarantine → give-up-loudly
  ladder, radix prefix RE-WARM from the router's hottest routed
  prefixes before the replacement takes traffic, and brownout-driven
  autoscaling (sustained rung >= ``scale_up_rung`` grows toward
  ``max_replicas``; sustained rung 0 + low occupancy drains-and-
  shrinks, migrating open streams to survivors token-identically).
  No supervisor = bit-identical to the PR-13 router;
- :mod:`rpc` — the stdlib cross-host transport (ISSUE 19): one
  length-prefixed JSON-header + binary-blob frame over TCP
  (:class:`~rpc.RpcServer` / :class:`~rpc.RpcClient` with a per-client
  socket pool so parked long-polls never delay health probes), a
  zero-copy numpy array codec (bfloat16/fp8 via ml_dtypes names), and
  the two-level error contract — :class:`~rpc.RpcError` (transport:
  dead peer, torn frame, timeout — the failover signal) vs
  :class:`~rpc.RpcRemoteError` (the remote handler raised; ``.etype``
  carries the remote type so ``QueueFull`` maps back);
- :mod:`pod` — the cross-HOST fleet (ISSUE 19): hosts run a
  :class:`~pod.HostAgent` (engines + RPC server + registry heartbeat
  over the elastic :class:`FileKVStore`'s checksummed binary records);
  clients :func:`~pod.connect_fleet` into a :class:`~pod.FleetRouter`
  whose :class:`~pod.RemoteReplica` proxies expose the SAME
  submit/stream/adopt/health surface as an in-process engine — router
  affinity, token-replay failover, the supervisor ladder and the
  frontend all compose unchanged across machines. Role-split replicas
  disaggregate serving: prefill-role hosts run chunked prefill only
  and stream finished KV blocks to decode-role hosts, which splice
  them through the refcounted block table (token-identical to
  monolithic, greedy AND sampled); :class:`~pod.FleetScheduler`
  assigns roles, sizes pools per phase and pre-warms decode replicas
  from :class:`~pod.ArrivalRateForecaster` arrival-rate windows ahead
  of the brownout ladder. Host loss = heartbeat staleness → open
  streams re-route through the PR-13 failover contract
  (``tools/trace_report.py fleet_report`` turns the fleet spans into
  per-host utilization and KV-transfer verdicts);
- :mod:`frontend` — the network surface (``python -m
  paddle_tpu.serving.frontend``): a stdlib-asyncio HTTP server with
  OpenAI-style ``/v1/completions`` and ``/v1/chat/completions`` (SSE
  streaming), ``/v1/models``, ``/metrics`` (Prometheus text exposition:
  HELP/TYPE for every gauge + the source-recorded latency histograms as
  ``_bucket``/``_sum``/``_count`` series, ISSUE 15), and
  ``/healthz`` / ``/readyz`` probes; per-tenant API-key auth with
  token-bucket admission and SLO lanes drained by weighted fair
  queuing over prefill chunks. The status contract: **429** = the
  tenant broke its own rate/stream budget; **503 + Retry-After** =
  the server shed the work (engine queue saturated, ``deadline_s``
  expired before generation started, brownout shed rung). Deadlines
  propagate end to end (HTTP admission → WFQ lane → engine admission →
  response waits), an SSE client that disconnects has its engine
  request cancelled (slot/blocks/prefix refs released), and
  ``response_format`` compiles to a :mod:`constrained` automaton.
  ``tools/trace_report.py frontend_report`` / ``overload_report`` turn
  its spans into per-tenant SLO and brownout/replica verdicts.

Defaults: ``FLAGS_prefix_cache=0`` keeps every prefill cache-cold;
``FLAGS_serving_mesh=0`` + ``draft=None`` pin the
single-chip non-speculative engine; ``overload=None`` + no router
pin the PR-11 front end bit-identical.
"""
from .constrained import (ConstraintCursor, TokenConstraint,
                          compile_constraint, compile_regex,
                          schema_to_regex)
from .engine import (GenerationRequest, InferenceEngine, QueueFull,
                     ReplicaEvacuated, WatchdogTripped)
from .kv_cache import KVCache, PagedKVCache, cache_insert
from .lifecycle import ReplicaFailed, ReplicaSupervisor
from .overload import RUNG_NAMES, OverloadController
from .pod import (ArrivalRateForecaster, FleetRegistry, FleetRouter,
                  FleetScheduler, HostAgent, RemoteReplica,
                  RemoteReplicaError, connect_fleet)
from .prefix_cache import RadixPrefixCache
from .router import EngineRouter
from .rpc import RpcClient, RpcError, RpcRemoteError, RpcServer
from .sampling import sample_tokens, sample_tokens_streams, spec_accept, \
    stream_keys
from .tokenizer import ByteTokenizer, StreamDetokenizer

__all__ = [
    "InferenceEngine", "GenerationRequest", "QueueFull",
    "WatchdogTripped", "ReplicaEvacuated",
    "KVCache", "PagedKVCache", "cache_insert", "RadixPrefixCache",
    "OverloadController", "RUNG_NAMES", "EngineRouter",
    "ReplicaSupervisor", "ReplicaFailed",
    "sample_tokens", "sample_tokens_streams", "stream_keys", "spec_accept",
    "ByteTokenizer", "StreamDetokenizer",
    "TokenConstraint", "ConstraintCursor", "compile_constraint",
    "compile_regex", "schema_to_regex",
    "HostAgent", "RemoteReplica", "RemoteReplicaError", "FleetRegistry",
    "FleetRouter",
    "FleetScheduler", "ArrivalRateForecaster", "connect_fleet",
    "RpcServer", "RpcClient", "RpcError", "RpcRemoteError",
]
