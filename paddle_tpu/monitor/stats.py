"""Stat gauges (reference paddle/fluid/platform/monitor.h StatRegistry,
STAT_ADD/STAT_RESET macros).

A `Stat` is a named int64 gauge; the `StatRegistry` is the process-wide
thread-safe singleton holding them. Hot paths (framework.core.apply_op,
distributed collectives) hold module-level references to their pre-created
Stat objects so an increment is one lock + one add — no dict lookup, no
allocation, matching the reference's `STAT_INT64(name); STAT_ADD(...)`
static-registration idiom.

Stats live host-side only (they count host-visible events: dispatches,
compiles, cache hits, collective launches, NaN trips); device-side memory
gauges are filled on demand by :func:`update_memory_stats`.
"""
from __future__ import annotations

import re
import threading

__all__ = [
    "Stat", "StatRegistry", "stat_add", "stat_get", "stat_reset",
    "stat_names", "stat_snapshot", "reset_all_stats", "update_memory_stats",
    "DEFAULT_STATS",
    "Histogram", "DEFAULT_HISTOGRAMS", "hist_observe", "get_histogram",
    "histogram_snapshot", "hist_delta", "hist_quantile", "prometheus_text",
]


class Stat:
    """One named int64 counter/gauge (reference monitor.h StatValue)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def add(self, delta: int = 1) -> None:
        with self._lock:
            self._value += delta

    # reference StatValue::increase/decrease
    increase = add

    def decrease(self, delta: int = 1) -> None:
        self.add(-delta)

    def set(self, value: int) -> None:
        with self._lock:
            self._value = int(value)

    def get(self) -> int:
        return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0

    def __repr__(self):
        return f"Stat({self.name}={self._value})"


# log2-spaced default bucket bounds (milliseconds): 0.125ms .. 8.192s.
# Fixed and shared by every default histogram so cross-metric quantile
# comparisons and the bench agreement gate read off one resolution —
# "within bucket resolution" means within one factor-of-2 bucket.
DEFAULT_BUCKETS_MS = tuple(2.0 ** k for k in range(-3, 14))


class Histogram:
    """Fixed-bucket latency histogram (Prometheus histogram semantics:
    cumulative ``_bucket{le=...}`` + ``_sum`` + ``_count``).

    Buckets are log-spaced and FIXED at construction — observation is
    one lock + one bisect-free linear scan over ~17 bounds (cheap next
    to the time.monotonic() call that produced the sample), and two
    snapshots diff cleanly because the bounds never move.
    """

    __slots__ = ("name", "bounds", "_counts", "_count", "_sum", "_lock")

    def __init__(self, name: str, bounds=DEFAULT_BUCKETS_MS):
        self.name = name
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(set(self.bounds)):
            raise ValueError(f"histogram bounds must be strictly "
                             f"increasing, got {bounds}")
        self._counts = [0] * (len(self.bounds) + 1)   # +1 = +Inf overflow
        self._count = 0
        self._sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        i = 0
        for b in self.bounds:
            if v <= b:
                break
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v

    def snapshot(self) -> dict:
        """{"bounds", "counts" (per-bucket, NON-cumulative, +Inf last),
        "count", "sum"} — a value object two of which diff cleanly."""
        with self._lock:
            return {"bounds": list(self.bounds),
                    "counts": list(self._counts),
                    "count": self._count, "sum": self._sum}

    def quantile(self, q: float) -> float:
        return hist_quantile(self.snapshot(), q)

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._count = 0
            self._sum = 0.0

    def __repr__(self):
        return f"Histogram({self.name}, count={self._count})"


def hist_delta(before: dict, after: dict) -> dict:
    """Snapshot difference (same bounds): the observations made between
    the two snapshots — how bench scopes a histogram to one run leg."""
    if before["bounds"] != after["bounds"]:
        raise ValueError("histogram snapshots have different bounds")
    return {"bounds": list(after["bounds"]),
            "counts": [a - b for a, b in zip(after["counts"],
                                             before["counts"])],
            "count": after["count"] - before["count"],
            "sum": after["sum"] - before["sum"]}


def hist_quantile(snap: dict, q: float) -> float:
    """Quantile estimate from a snapshot: linear interpolation inside
    the bucket where the cumulative count crosses ``q`` (Prometheus
    ``histogram_quantile`` semantics; the +Inf bucket clamps to the last
    finite bound). NaN-free: an empty snapshot returns 0.0."""
    count = snap["count"]
    if count <= 0:
        return 0.0
    rank = q * count
    cum = 0
    lo = 0.0
    for i, c in enumerate(snap["counts"]):
        nxt = cum + c
        if nxt >= rank and c > 0:
            if i >= len(snap["bounds"]):
                return float(snap["bounds"][-1])    # +Inf bucket: clamp
            hi = snap["bounds"][i]
            frac = (rank - cum) / c
            return float(lo + (hi - lo) * frac)
        cum = nxt
        if i < len(snap["bounds"]):
            lo = snap["bounds"][i]
    return float(snap["bounds"][-1])


class StatRegistry:
    """Thread-safe singleton registry of Stats (monitor.h StatRegistry)."""

    _instance = None
    _instance_lock = threading.Lock()

    @classmethod
    def instance(cls) -> "StatRegistry":
        if cls._instance is None:
            with cls._instance_lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance

    def __init__(self):
        self._stats: dict[str, Stat] = {}
        self._hists: dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def get_stat(self, name: str) -> Stat:
        s = self._stats.get(name)
        if s is None:
            with self._lock:
                s = self._stats.setdefault(name, Stat(name))
        return s

    def get_histogram(self, name: str,
                      bounds=DEFAULT_BUCKETS_MS) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, Histogram(name, bounds))
        return h

    def histogram_snapshot(self) -> dict:
        with self._lock:
            hists = sorted(self._hists.items())
        return {n: h.snapshot() for n, h in hists}

    def add(self, name: str, delta: int = 1) -> None:
        self.get_stat(name).add(delta)

    def get(self, name: str) -> int:
        return self.get_stat(name).get()

    def reset(self, name: str) -> None:
        self.get_stat(name).reset()

    def reset_all(self) -> None:
        with self._lock:
            for s in self._stats.values():
                s.reset()
            for h in self._hists.values():
                h.reset()

    def names(self):
        with self._lock:
            return sorted(self._stats)

    def snapshot(self) -> dict:
        with self._lock:
            return {n: s.get() for n, s in sorted(self._stats.items())}


_registry = StatRegistry.instance()


def stat_add(name: str, delta: int = 1) -> None:
    _registry.add(name, delta)


def stat_get(name: str) -> int:
    return _registry.get(name)


def stat_reset(name: str) -> None:
    _registry.reset(name)


def stat_names():
    return _registry.names()


def stat_snapshot() -> dict:
    return _registry.snapshot()


def reset_all_stats() -> None:
    _registry.reset_all()


def hist_observe(name: str, value: float) -> None:
    _registry.get_histogram(name).observe(value)


def get_histogram(name: str) -> Histogram:
    return _registry.get_histogram(name)


def histogram_snapshot() -> dict:
    return _registry.histogram_snapshot()


# -- pre-registered stats (the subsystem's standing dashboard) --------------
#
# Hot paths import these module-level handles directly; everything else
# reads them by name through stat_get.

DEFAULT_STATS = (
    "op_dispatch",        # apply_op eager dispatches
    "jit_cache_hit",      # op-level jit cache hits (PreparedOp-cache analog)
    "jit_cache_miss",     # op-level jit cache misses
    "jit_compile",        # new jax.jit wrappers built (one per miss)
    "grad_jit_hit",       # grad-enabled dispatch: cached jitted-VJP hits
    "grad_jit_miss",      # grad-enabled dispatch: cache misses (new aval key)
    "grad_jit_compile",   # new fwd+vjp jit pairs built (one per miss)
    "collective_calls",   # distributed.* collective API launches
    "train_steps",        # compiled/eager training steps completed
    "nan_inf_trips",      # FLAGS_check_nan_inf violations raised
    "host_memory_bytes",  # gauge: peak host RSS (update_memory_stats)
    "device_memory_bytes",  # gauge: device bytes in use (update_memory_stats)
    # input-and-step fast path (ISSUE 3)
    "prefetch_queue_depth",  # gauge: batches staged ahead by DevicePrefetcher
    "h2d_copy_ms",        # cumulative host->device copy dispatch time (ms)
    "shm_ring_full",      # DataLoader shm batches that waited for a free slot
    "shm_batches",        # batches shipped via the shared-memory transport
    "step_async_syncs",   # async-step loss/metric materializations (blocking reads)
    # serving engine (ISSUE 4)
    "serving_queue_depth",     # gauge: requests waiting for a cache slot
    "serving_slot_occupancy",  # gauge: KV-cache slots currently generating
    "serving_prefill_ms",      # cumulative prompt-prefill wall time (ms)
    "serving_decode_ms",       # cumulative batched decode-tick wall time (ms)
    "serving_tokens_per_s",    # gauge: recent generation rate (tokens/s)
    "serving_evictions",       # sequences evicted from slots (eos/len/deadline/cancel)
    "serving_prefill_chunks",  # prefill chunks dispatched
    "serving_decode_blocks_live",    # active slots' table entries, a tick
    "serving_decode_blocks_tabled",  # n_slots x table width, the same ticks
    "serving_state_slots_live",      # lanes whose recurrent state a tick moved
    "serving_kv_rows_written",       # active lanes x layers: rows put into a paged pool
    # decode ticks by the path their sampling takes (serving/sampling.py):
    "serving_sample_ticks_greedy",   # no row samples: argmax
    "serving_sample_ticks_select",   # samples, no sort of the vocabulary
    "serving_sample_ticks_sort",     # the rows' parameters force the one sort
    # decode ticks by whether another was still unread at their dispatch
    "serving_decode_ticks_ahead",    # left with the tick before in flight
    "serving_decode_ticks_synced",   # left with no tick unread
    "serving_decode_lanes_discarded",  # lane results of a tick never pushed
    # paged KV cache (ISSUE 7)
    "kv_blocks_free",          # gauge: pool blocks on the free list
    "kv_blocks_used",          # gauge: pool blocks owned by live slots
    "kv_fragmentation",        # gauge: % of used-block capacity holding no live token
    "serving_preemptions",     # slots preempted back to the queue on pool exhaustion
    # self-healing training (ISSUE 5)
    "faults_injected",        # FLAGS_fault_inject faults actually fired
    "sentinel_trips",         # in-jit health verdict trips observed by the guardian
    "rollbacks",              # guardian rewinds to the host snapshot
    "preempt_saves",          # SIGTERM-forced priority checkpoint saves
    "watchdog_stalls",        # stalled-step detections by the watchdog thread
    "guardian_heartbeat_ms",  # gauge: monotonic ms of the last guarded step
    # Pallas kernel library + comm/compute overlap (ISSUE 6)
    "fused_optimizer_steps",  # fused (flat-buffer) optimizer steps taken
    "fused_kernel_calls",     # fused LN/MLP kernel dispatches (eager surface)
    "int8_matmul_calls",      # int8 weight-quantized matmul dispatches
    "grad_overlap_buckets",   # grad all-reduce buckets issued inside backward
    # speculative + multi-chip serving (ISSUE 10)
    "spec_proposed",           # draft tokens proposed by the speculative path
    "spec_accepted",           # draft tokens accepted by target verification
    "spec_acceptance_rate",    # gauge: % of proposed draft tokens accepted
    "serving_shards",          # gauge: "data"-axis shards the engine decodes over
    # fleet.auto hybrid-parallel planner (ISSUE 9)
    "plan_candidates_considered",   # legal candidates scored by the planner
    "zero_level",                   # gauge: chosen ZeRO stage (0-3)
    "pipeline_bubble_frac",         # gauge: chosen plan's bubble, ppm (1e-6)
    "planner_hbm_headroom_bytes",   # gauge: HBM budget minus chosen plan's need
    # radix prefix cache + serving front end (ISSUE 11)
    "prefix_matched_tokens",    # prompt tokens served from the radix tree
    "prefix_lookup_tokens",     # prompt tokens looked up at admission
    "prefix_hit_rate",          # gauge: % of looked-up prompt tokens matched
    "prefix_cache_blocks",      # gauge: pool blocks pinned by the radix tree
    "prefix_evictions",         # LRU-leaf tree nodes reclaimed to the pool
    "prefix_cow_copies",        # copy-on-write duplications of shared blocks
    "frontend_requests",        # HTTP generation requests accepted
    "frontend_429s",            # requests rejected by tenant admission (429)
    "frontend_queue_wait_ms",   # cumulative WFQ lane wait before submission
    "frontend_active_streams",  # gauge: generation streams currently open
    "constrained_requests",     # requests decoding under a token-mask automaton
    "constrained_fallback_ticks",  # spec ticks dropped to the plain program
    # pod-level resilience (ISSUE 12)
    "pod_hosts_alive",          # gauge: hosts with a fresh, non-tombstoned lease
    "elastic_resizes",          # pod resizes (replan+reshard+resume) after host loss
    "serving_watchdog_trips",   # serving sentinel verdicts (NaN tick / latency stall)
    "serving_watchdog_restarts",  # engine restarts from the last healthy state
    # overload-hardened serving (ISSUE 13)
    "serving_deadline_sheds",   # requests shed deadline-expired BEFORE any prefill
    "frontend_load_sheds",      # HTTP requests answered 503 (overload/deadline shed)
    "brownout_rung",            # gauge: current degradation-ladder rung (0=healthy)
    "brownout_steps",           # ladder transitions (up or down) taken
    "router_failovers",         # streams requeued to a survivor replica
    "serving_replicas_healthy",  # gauge: routable replicas behind the EngineRouter
    # elastic replica lifecycle (ISSUE 14)
    "serving_replicas_target",   # gauge: replica count the supervisor steers toward
    "serving_replica_restarts",  # replicas respawned after death/wedge/watchdog abort
    "serving_scale_events",      # autoscale transitions (grow or drain-shrink) completed
    "prefix_warm_tokens",        # prompt tokens replayed to re-warm a rejoined radix tree
    # sparse embedding / recommender stack (ISSUE 16)
    "embedding_lookup_ids",      # ids resolved through sparse lookup paths
    "embedding_unique_ratio",    # gauge: unique/total ids in the last batch, ppm
    "embedding_exchange_bytes",  # all-to-all bytes moved by sharded lookups
    "sparse_rows_touched",       # table rows updated by sparse optimizer steps
    # kernel autotuner + fp8 path (ISSUE 17)
    "autotune_hits",          # block configs served from the autotune cache
    "autotune_misses",        # cache misses that triggered a trial sweep
    "autotune_trials_ms",     # cumulative wall ms spent timing trial configs
    "fused_kernel_fallbacks",  # Pallas entries that fell back to composed jnp
    "fp8_matmul_calls",       # fp8 (e4m3) matmul dispatches
    # mixture-of-experts serving stats (ISSUE 18)
    "moe_expert_load",        # gauge: busiest-expert share of routed tokens, ppm
    "moe_tokens_dropped",     # routed assignments dropped past expert capacity
    # an expert layer that holds a share of its experts (models/mla.py)
    "moe_assignments_routed",  # tokens x top-k x expert layers, over all experts
    "moe_assignments_held",    # of those, rows computed by experts held here
    "moe_expert_reads",        # (run, layer, held expert) with at least one row
    "moe_kernel_tiles",        # row tiles the grouped expert kernel ran
    # cross-host serving fleet (ISSUE 19)
    "fleet_hosts",            # gauge: fleet hosts with a fresh heartbeat
    "fleet_replicas",         # gauge: remote replica proxies attached to the router
    "fleet_kv_transfer_bytes",  # KV block bytes streamed prefill-host -> decode-host
    "fleet_kv_exports",       # prefix exports served by prefill-role replicas
    "fleet_kv_imports",       # prefix imports spliced into decode-role pools
    "fleet_prefill_routed",   # requests whose prefill ran on a prefill-role host
    "fleet_direct_fallbacks",  # disaggregated submits that fell back to direct decode
    "fleet_reroutes",         # host-loss events that re-routed streams to survivors
    "fleet_prewarms",         # replicas pre-warmed by the arrival-rate forecaster
    "rpc_calls",              # RPC round trips issued by remote replica proxies
    "rpc_errors",             # RPC round trips that failed (transport or remote)
    # fleet network fault tolerance (ISSUE 20)
    "rpc_retries",            # idempotent RPC calls re-sent after a transport error
    "rpc_breaker_state",      # gauge: per-peer circuit breakers currently OPEN
    "rpc_deadline_sheds",     # frames shed by the receiver: deadline already expired
    "fleet_kv_chunks_streamed",  # KV chunks shipped by the resumable streaming path
    "fleet_kv_resume_tails",  # decode-side local tail prefills after a mid-stream loss
    "flight_collects",        # fleet-wide flight-recorder collection sweeps
)

for _n in DEFAULT_STATS:
    _registry.get_stat(_n)

OP_DISPATCH = _registry.get_stat("op_dispatch")
JIT_CACHE_HIT = _registry.get_stat("jit_cache_hit")
JIT_CACHE_MISS = _registry.get_stat("jit_cache_miss")
JIT_COMPILE = _registry.get_stat("jit_compile")
GRAD_JIT_HIT = _registry.get_stat("grad_jit_hit")
GRAD_JIT_MISS = _registry.get_stat("grad_jit_miss")
GRAD_JIT_COMPILE = _registry.get_stat("grad_jit_compile")
COLLECTIVE_CALLS = _registry.get_stat("collective_calls")
TRAIN_STEPS = _registry.get_stat("train_steps")
NAN_INF_TRIPS = _registry.get_stat("nan_inf_trips")
HOST_MEMORY_BYTES = _registry.get_stat("host_memory_bytes")
DEVICE_MEMORY_BYTES = _registry.get_stat("device_memory_bytes")
PREFETCH_QUEUE_DEPTH = _registry.get_stat("prefetch_queue_depth")
H2D_COPY_MS = _registry.get_stat("h2d_copy_ms")
SHM_RING_FULL = _registry.get_stat("shm_ring_full")
SHM_BATCHES = _registry.get_stat("shm_batches")
STEP_ASYNC_SYNCS = _registry.get_stat("step_async_syncs")
SERVING_QUEUE_DEPTH = _registry.get_stat("serving_queue_depth")
SERVING_SLOT_OCCUPANCY = _registry.get_stat("serving_slot_occupancy")
SERVING_PREFILL_MS = _registry.get_stat("serving_prefill_ms")
SERVING_DECODE_MS = _registry.get_stat("serving_decode_ms")
SERVING_TOKENS_PER_S = _registry.get_stat("serving_tokens_per_s")
SERVING_EVICTIONS = _registry.get_stat("serving_evictions")
SERVING_PREFILL_CHUNKS = _registry.get_stat("serving_prefill_chunks")
SERVING_DECODE_BLOCKS_LIVE = _registry.get_stat("serving_decode_blocks_live")
SERVING_DECODE_BLOCKS_TABLED = _registry.get_stat(
    "serving_decode_blocks_tabled")
SERVING_STATE_SLOTS_LIVE = _registry.get_stat("serving_state_slots_live")
SERVING_KV_ROWS_WRITTEN = _registry.get_stat("serving_kv_rows_written")
SERVING_SAMPLE_TICKS_GREEDY = _registry.get_stat(
    "serving_sample_ticks_greedy")
SERVING_SAMPLE_TICKS_SELECT = _registry.get_stat(
    "serving_sample_ticks_select")
SERVING_SAMPLE_TICKS_SORT = _registry.get_stat("serving_sample_ticks_sort")
SERVING_DECODE_TICKS_AHEAD = _registry.get_stat("serving_decode_ticks_ahead")
SERVING_DECODE_TICKS_SYNCED = _registry.get_stat(
    "serving_decode_ticks_synced")
SERVING_DECODE_LANES_DISCARDED = _registry.get_stat(
    "serving_decode_lanes_discarded")
KV_BLOCKS_FREE = _registry.get_stat("kv_blocks_free")
KV_BLOCKS_USED = _registry.get_stat("kv_blocks_used")
KV_FRAGMENTATION = _registry.get_stat("kv_fragmentation")
SERVING_PREEMPTIONS = _registry.get_stat("serving_preemptions")
FAULTS_INJECTED = _registry.get_stat("faults_injected")
SENTINEL_TRIPS = _registry.get_stat("sentinel_trips")
ROLLBACKS = _registry.get_stat("rollbacks")
PREEMPT_SAVES = _registry.get_stat("preempt_saves")
WATCHDOG_STALLS = _registry.get_stat("watchdog_stalls")
GUARDIAN_HEARTBEAT_MS = _registry.get_stat("guardian_heartbeat_ms")
FUSED_OPTIMIZER_STEPS = _registry.get_stat("fused_optimizer_steps")
FUSED_KERNEL_CALLS = _registry.get_stat("fused_kernel_calls")
INT8_MATMUL_CALLS = _registry.get_stat("int8_matmul_calls")
GRAD_OVERLAP_BUCKETS = _registry.get_stat("grad_overlap_buckets")
SPEC_PROPOSED = _registry.get_stat("spec_proposed")
SPEC_ACCEPTED = _registry.get_stat("spec_accepted")
SPEC_ACCEPTANCE_RATE = _registry.get_stat("spec_acceptance_rate")
SERVING_SHARDS = _registry.get_stat("serving_shards")
PLAN_CANDIDATES_CONSIDERED = _registry.get_stat("plan_candidates_considered")
ZERO_LEVEL = _registry.get_stat("zero_level")
PIPELINE_BUBBLE_FRAC = _registry.get_stat("pipeline_bubble_frac")
PLANNER_HBM_HEADROOM_BYTES = _registry.get_stat("planner_hbm_headroom_bytes")
POD_HOSTS_ALIVE = _registry.get_stat("pod_hosts_alive")
ELASTIC_RESIZES = _registry.get_stat("elastic_resizes")
SERVING_WATCHDOG_TRIPS = _registry.get_stat("serving_watchdog_trips")
SERVING_WATCHDOG_RESTARTS = _registry.get_stat("serving_watchdog_restarts")
PREFIX_MATCHED_TOKENS = _registry.get_stat("prefix_matched_tokens")
PREFIX_LOOKUP_TOKENS = _registry.get_stat("prefix_lookup_tokens")
PREFIX_HIT_RATE = _registry.get_stat("prefix_hit_rate")
PREFIX_CACHE_BLOCKS = _registry.get_stat("prefix_cache_blocks")
PREFIX_EVICTIONS = _registry.get_stat("prefix_evictions")
PREFIX_COW_COPIES = _registry.get_stat("prefix_cow_copies")
FRONTEND_REQUESTS = _registry.get_stat("frontend_requests")
FRONTEND_429S = _registry.get_stat("frontend_429s")
FRONTEND_QUEUE_WAIT_MS = _registry.get_stat("frontend_queue_wait_ms")
FRONTEND_ACTIVE_STREAMS = _registry.get_stat("frontend_active_streams")
CONSTRAINED_REQUESTS = _registry.get_stat("constrained_requests")
CONSTRAINED_FALLBACK_TICKS = _registry.get_stat("constrained_fallback_ticks")
SERVING_DEADLINE_SHEDS = _registry.get_stat("serving_deadline_sheds")
FRONTEND_LOAD_SHEDS = _registry.get_stat("frontend_load_sheds")
BROWNOUT_RUNG = _registry.get_stat("brownout_rung")
BROWNOUT_STEPS = _registry.get_stat("brownout_steps")
ROUTER_FAILOVERS = _registry.get_stat("router_failovers")
SERVING_REPLICAS_HEALTHY = _registry.get_stat("serving_replicas_healthy")
SERVING_REPLICAS_TARGET = _registry.get_stat("serving_replicas_target")
SERVING_REPLICA_RESTARTS = _registry.get_stat("serving_replica_restarts")
SERVING_SCALE_EVENTS = _registry.get_stat("serving_scale_events")
PREFIX_WARM_TOKENS = _registry.get_stat("prefix_warm_tokens")
EMBEDDING_LOOKUP_IDS = _registry.get_stat("embedding_lookup_ids")
EMBEDDING_UNIQUE_RATIO = _registry.get_stat("embedding_unique_ratio")
EMBEDDING_EXCHANGE_BYTES = _registry.get_stat("embedding_exchange_bytes")
SPARSE_ROWS_TOUCHED = _registry.get_stat("sparse_rows_touched")
AUTOTUNE_HITS = _registry.get_stat("autotune_hits")
AUTOTUNE_MISSES = _registry.get_stat("autotune_misses")
AUTOTUNE_TRIALS_MS = _registry.get_stat("autotune_trials_ms")
FUSED_KERNEL_FALLBACKS = _registry.get_stat("fused_kernel_fallbacks")
FP8_MATMUL_CALLS = _registry.get_stat("fp8_matmul_calls")
MOE_EXPERT_LOAD = _registry.get_stat("moe_expert_load")
MOE_TOKENS_DROPPED = _registry.get_stat("moe_tokens_dropped")
MOE_ASSIGNMENTS_ROUTED = _registry.get_stat("moe_assignments_routed")
MOE_ASSIGNMENTS_HELD = _registry.get_stat("moe_assignments_held")
MOE_EXPERT_READS = _registry.get_stat("moe_expert_reads")
MOE_KERNEL_TILES = _registry.get_stat("moe_kernel_tiles")
FLEET_HOSTS = _registry.get_stat("fleet_hosts")
FLEET_REPLICAS = _registry.get_stat("fleet_replicas")
FLEET_KV_TRANSFER_BYTES = _registry.get_stat("fleet_kv_transfer_bytes")
FLEET_KV_EXPORTS = _registry.get_stat("fleet_kv_exports")
FLEET_KV_IMPORTS = _registry.get_stat("fleet_kv_imports")
FLEET_PREFILL_ROUTED = _registry.get_stat("fleet_prefill_routed")
FLEET_DIRECT_FALLBACKS = _registry.get_stat("fleet_direct_fallbacks")
FLEET_REROUTES = _registry.get_stat("fleet_reroutes")
FLEET_PREWARMS = _registry.get_stat("fleet_prewarms")
RPC_CALLS = _registry.get_stat("rpc_calls")
RPC_ERRORS = _registry.get_stat("rpc_errors")
RPC_RETRIES = _registry.get_stat("rpc_retries")
RPC_BREAKER_STATE = _registry.get_stat("rpc_breaker_state")
RPC_DEADLINE_SHEDS = _registry.get_stat("rpc_deadline_sheds")
FLEET_KV_CHUNKS_STREAMED = _registry.get_stat("fleet_kv_chunks_streamed")
FLEET_KV_RESUME_TAILS = _registry.get_stat("fleet_kv_resume_tails")
FLIGHT_COLLECTS = _registry.get_stat("flight_collects")


# -- pre-registered latency histograms (ISSUE 15) ---------------------------
#
# Recorded AT THE SOURCE (engine scheduler / frontend dispatcher), so the
# p50/p99 numbers are live, scrapeable series under GET /metrics. All
# share DEFAULT_BUCKETS_MS.

DEFAULT_HISTOGRAMS = (
    ("serving_first_token_ms",
     "submit-to-first-token latency per request (ms)"),
    ("serving_per_token_ms",
     "steady-state inter-token latency per request, "
     "(t_last - t_first)/(n-1) (ms)"),
    ("serving_queue_wait_ms",
     "queue wait before work starts: WFQ lane wait and engine "
     "admission wait (ms)"),
    ("serving_decode_tick_ms",
     "batched decode tick wall latency, dispatch to tokens on the host; "
     "it includes the device time of any prefill chunk or tick queued "
     "ahead of the tick (ms)"),
    ("serving_prefill_chunk_ms",
     "host latency of the asynchronous DISPATCH of one prefill chunk "
     "(about a millisecond whatever the chunk costs the device, which "
     "shows in the next tick or first-token wait) (ms)"),
    ("moe_expert_share_pct",
     "per-expert share of routed assignments per decode tick (%) — "
     "one observation per expert per tick, so the spread IS the "
     "imbalance (uniform router: all mass at 100/E)"),
    ("fleet_kv_transfer_ms",
     "prefill-host -> decode-host KV block stream wall latency per "
     "prompt: export + transport + pool splice (ms)"),
    ("fleet_arrival_gap_ms",
     "inter-arrival gap between fleet submissions (ms) — the "
     "arrival-rate series the pre-warm forecaster reads (rps = "
     "1000/median gap)"),
    ("rpc_call_ms",
     "remote-replica RPC round-trip wall latency (ms)"),
)

HISTOGRAM_HELP = dict(DEFAULT_HISTOGRAMS)

for _n, _ in DEFAULT_HISTOGRAMS:
    _registry.get_histogram(_n)

SERVING_FIRST_TOKEN_MS = _registry.get_histogram("serving_first_token_ms")
SERVING_PER_TOKEN_MS = _registry.get_histogram("serving_per_token_ms")
SERVING_QUEUE_WAIT_MS = _registry.get_histogram("serving_queue_wait_ms")
SERVING_DECODE_TICK_MS = _registry.get_histogram("serving_decode_tick_ms")
SERVING_PREFILL_CHUNK_MS = _registry.get_histogram(
    "serving_prefill_chunk_ms")
MOE_EXPERT_SHARE_PCT = _registry.get_histogram("moe_expert_share_pct")
FLEET_KV_TRANSFER_MS = _registry.get_histogram("fleet_kv_transfer_ms")
FLEET_ARRIVAL_GAP_MS = _registry.get_histogram("fleet_arrival_gap_ms")
RPC_CALL_MS = _registry.get_histogram("rpc_call_ms")


# -- Prometheus text exposition (ISSUE 15 satellite) ------------------------

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str, prefix: str = "paddle_tpu_") -> str:
    """Sanitize to a legal Prometheus metric name: invalid characters
    (the per-axis gauges' ``.``, benchmark rows' ``@``) become ``_``,
    and a leading digit is prefixed."""
    n = _PROM_BAD.sub("_", str(name))
    if n and n[0].isdigit():
        n = "_" + n
    return prefix + n


def _prom_num(v: float) -> str:
    """Format a float the Prometheus text format accepts (no trailing
    noise: 0.125 -> '0.125', 8192.0 -> '8192')."""
    return format(float(v), "g")


def prometheus_text(prefix: str = "paddle_tpu_") -> str:
    """The full registry in Prometheus text exposition format 0.0.4:
    every gauge with ``# HELP``/``# TYPE``, every histogram as
    cumulative ``_bucket{le=...}`` series plus ``_sum``/``_count`` —
    what GET /metrics serves."""
    lines = []
    for name, value in stat_snapshot().items():
        m = _prom_name(name, prefix)
        lines.append(f"# HELP {m} int64 gauge {name}")
        lines.append(f"# TYPE {m} gauge")
        lines.append(f"{m} {int(value)}")
    for name, snap in histogram_snapshot().items():
        m = _prom_name(name, prefix)
        help_txt = HISTOGRAM_HELP.get(name, f"latency histogram {name}")
        lines.append(f"# HELP {m} {help_txt}")
        lines.append(f"# TYPE {m} histogram")
        cum = 0
        for bound, c in zip(snap["bounds"], snap["counts"]):
            cum += c
            lines.append(f'{m}_bucket{{le="{_prom_num(bound)}"}} {cum}')
        lines.append(f'{m}_bucket{{le="+Inf"}} {snap["count"]}')
        lines.append(f"{m}_sum {_prom_num(snap['sum'])}")
        lines.append(f"{m}_count {snap['count']}")
    return "\n".join(lines) + "\n"


# per-mesh-axis device-memory gauges published by the last
# update_memory_stats call ("device_memory_bytes.<axis>"); tracked so a
# refresh can zero the axes that disappeared (mesh torn down, buffers freed)
_mem_axis_gauges: set = set()


def _buffer_axes(arr) -> set:
    """Mesh axes a live buffer is sharded over (empty = replicated /
    single-device)."""
    spec = getattr(getattr(arr, "sharding", None), "spec", None)
    axes = set()
    if spec is not None:
        for part in spec:
            if part is None:
                continue
            for ax in (part if isinstance(part, (tuple, list)) else (part,)):
                if ax is not None:
                    axes.add(str(ax))
    return axes


def update_memory_stats() -> dict:
    """Refresh the host/device memory gauges and return {name: bytes}.

    Host side reads the process peak RSS; device side sums
    ``bytes_in_use`` over visible jax devices (not every backend reports
    memory_stats — missing values leave the gauge unchanged). Device
    bytes are additionally SPLIT PER MESH AXIS: every live buffer's size
    is attributed to the mesh axis (or axes) its PartitionSpec shards it
    over — ``device_memory_bytes.data``, ``.model``, ... — with
    unsharded buffers under ``device_memory_bytes.replicated``, so a
    memory regression can be pinned to the parallelism dimension that
    grew (ROADMAP monitor follow-up).
    """
    out = {}
    try:
        import resource

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        HOST_MEMORY_BYTES.set(int(rss_kb) * 1024)
    except Exception:
        pass
    try:
        import jax

        total = 0
        seen = False
        for d in jax.devices():
            ms = getattr(d, "memory_stats", None)
            if ms is None:
                continue
            try:
                total += int((ms() or {}).get("bytes_in_use", 0))
                seen = True
            except Exception:
                continue
        if seen:
            DEVICE_MEMORY_BYTES.set(total)
    except Exception:
        pass
    try:
        import jax

        per_axis: dict = {}
        for arr in jax.live_arrays():
            try:
                nbytes = int(arr.nbytes)
            except Exception:
                continue
            axes = _buffer_axes(arr) or {"replicated"}
            for ax in axes:
                per_axis[ax] = per_axis.get(ax, 0) + nbytes
        for ax, nbytes in per_axis.items():
            name = f"device_memory_bytes.{ax}"
            _registry.get_stat(name).set(nbytes)
            _mem_axis_gauges.add(name)
            out[name] = nbytes
        for name in _mem_axis_gauges - {
                f"device_memory_bytes.{ax}" for ax in per_axis}:
            _registry.get_stat(name).set(0)
            out[name] = 0
    except Exception:
        pass
    out["host_memory_bytes"] = HOST_MEMORY_BYTES.get()
    out["device_memory_bytes"] = DEVICE_MEMORY_BYTES.get()
    return out
