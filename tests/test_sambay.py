"""The SambaY model (``models/sambay.py``, the kernels of
``ops/hybrid_attention.py`` and ``ops/selective_scan.py``) against its
family's plain reference (``benchmarks/families/sambay``: one full
forward pass over the sequence, the window by a mask, the Mamba
recurrence as a loop over tokens, every layer for every row) on seeded
weights at tiny widths: 8 layers (Mamba, window, Mamba, window, Mamba
with memory, full, GMU, cross), hidden 64, 8 query heads over 4 key
heads of 8, window 8, chunks of 16, blocks of 4. Prefill through chunks
(prompts shorter than the window, longer than two chunks), decode
through the ring (long enough to wrap it), the states and the shared
pool, a slot reused after release, a preempted request, mixed lengths in
one batch; the cross-decoder's skip; the cache manager's two kinds of
array and admission; the kernels in interpret mode; the refusals; the
benchmark's readers on a trace without their sources.

Tolerances: the program and the reference are both float32 here, so
they differ by summation order alone: logits of magnitude up to 6 agree
to 2e-5 (read 1e-5 on the prefill cases while this was written), and
``ATOL`` = 5e-4 leaves room for another machine's order. The same
reference with bf16 operands (the rounding a lower precision than the
configuration's would bring) lies 0.36 away
(``test_tolerance_would_catch_bf16``), hundreds of times the
tolerance."""
import dataclasses
import importlib.util
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.spec import load_family
from paddle_tpu import monitor
from paddle_tpu.models import (gpt_tiny, phi4_mini_flash,
                               sambay_decode_step_paged, sambay_init,
                               sambay_prefill_chunk, sambay_tiny)
from paddle_tpu.models.sambay import sambay_param_shapes
from paddle_tpu.ops import hybrid_attention as ha
from paddle_tpu.ops import selective_scan as ss
from paddle_tpu.serving import InferenceEngine
from paddle_tpu.serving.kv_cache import PagedKVCache

FAMILY = load_family("sambay")
SEED = 2 ** 31 + 41
ATOL = 5e-4          # float32 against float32 (module docstring)
BS, CHUNK = 4, 16    # block size, prefill chunk (the pad is the window, 8)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes_of(cfg, **extra):
    s = {k: v for k, v in dataclasses.asdict(cfg).items()
         if k not in ("dtype", "param_dtype")}
    s.update(dtype="float32", param_dtype="float32", init_std=0.2, **extra)
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = sambay_tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, FAMILY.make_params(sizes, SEED)


def tokens_of(n, salt=0):
    return np.asarray(jax.random.randint(jax.random.key(100 + salt), (n,),
                                         0, 256), np.int32)


def ref_logits(params, tokens, sizes, lo, hi, lowp=None):
    buf = np.zeros(-(-len(tokens) // 64) * 64, np.int32)
    buf[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAMILY.reference.logits_at(
            params, buf, sizes, lo, hi, lowp))


def ref_greedy(params, prompt, n, sizes):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(
            ref_logits(params, np.asarray(seq), sizes, len(seq) - 1,
                       len(seq))[0])))
    return seq[len(prompt):]


def fresh_pool(cfg, n_slots=2, n_blocks=40, fill=None):
    model = cfg.serving_model()
    specs = model.pool_spec(cfg, n_blocks, BS) \
        + model.slot_spec(cfg, n_slots, BS)
    if fill is None:
        return tuple(jnp.zeros(a.shape, a.dtype) for a in specs)
    return tuple(jnp.full(a.shape, fill, a.dtype) for a in specs)


def prefill(cfg, params, pool, slot, table, toks, chunk=CHUNK):
    """The engine's chunk calls for one prompt, padded to the window.
    -> (the last chunk's logits (V,), pool, every chunk's logits)."""
    n, got = len(toks), []
    pad = cfg.window
    for at in range(0, n, chunk):
        c = min(chunk, n - at)
        t = np.zeros((1, -(-c // pad) * pad), np.int32)
        t[0, :c] = toks[at:at + c]
        lg, pool = sambay_prefill_chunk(
            cfg, params, pool, jnp.asarray(table), jnp.asarray(t),
            jnp.int32(at), jnp.int32(c), jnp.int32(slot), at + c == n)
        got.append(np.asarray(lg))
    return got[-1][0, 0], pool, got


def table_for(first, n=16):
    return np.arange(first, first + n, dtype=np.int32)


# -- sizes and layout ---------------------------------------------------------

def test_published_sizes_are_the_defaults_and_the_count():
    cfg = phi4_mini_flash()
    assert (cfg.hidden, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.ffn, cfg.vocab_size, cfg.window) == \
        (2560, 32, 40, 20, 64, 10240, 200064, 512)
    assert (cfg.d_state, cfg.d_conv, cfg.inner, cfg.dt_rank) == \
        (16, 4, 5120, 160)
    leaves = jax.tree_util.tree_leaves(
        FAMILY.weights.shapes(sizes_of(cfg)),
        is_leaf=lambda s: isinstance(s, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == 3_852_562_944


def test_family_weights_have_the_models_layout(tiny):
    cfg, sizes, params = tiny
    mine = sambay_init(cfg, 0)
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == \
        jax.tree_util.tree_map(lambda a: a.shape, mine)
    assert jax.tree_util.tree_map(lambda a: a.shape, mine) == \
        sambay_param_shapes(cfg)


def test_cache_is_whole_lane_tiles_and_its_bytes():
    cfg = phi4_mini_flash()
    model = cfg.serving_model()
    paged = model.pool_spec(cfg, 8193, 64)
    own = model.slot_spec(cfg, 64, 64)
    assert [a.shape for a in paged] == [(8193, 1, 64, 1280)] * 2
    assert [a.shape for a in own] == [(65 * 8, 8, 64, 1280)] * 2 + [
        (9, 64, 8, 5120), (9, 64, 16, 5120)]
    assert all(a.shape[-1] % 128 == 0 for a in paged + own)
    ring = 2 * 8 * 512 * 1280 * 2
    assert ring == 20_971_520                          # 20.97 MB a slot
    assert 9 * (3 + 16) * 5120 * 4 == 3_502_080       # 3.50 MB counted
    assert paged[0].shape[-1] * 2 * 2 == 5120          # B a token


# -- the model's entry points against the reference ---------------------------

@pytest.mark.parametrize("n", [5, 37])
def test_prefill_matches_the_reference(tiny, n):
    """A prompt shorter than the window (5), and one longer than the
    window and two chunks (37 = 16 + 16 + 5): the last real row's logits.
    The slot arrays start full of garbage: a first chunk ignores it."""
    cfg, sizes, params = tiny
    toks = tokens_of(n, 2)
    last, pool, chunks = prefill(cfg, params, fresh_pool(cfg, fill=3.0), 1,
                                 table_for(1), toks)
    np.testing.assert_allclose(last, ref_logits(params, toks, sizes, n - 1,
                                                n)[0], atol=ATOL)
    # a chunk that does not end the prompt runs no cross-decoder row
    assert all(not np.any(c) for c in chunks[:-1])


def test_tolerance_would_catch_bf16(tiny):
    """The reference with bf16 operands lies far outside ``ATOL``."""
    cfg, sizes, params = tiny
    toks = tokens_of(37, 2)
    f32 = ref_logits(params, toks, sizes, 20, 37)
    bf16 = ref_logits(params, toks, sizes, 20, 37, lowp="bf16")
    assert np.abs(f32 - bf16).max() > 20 * ATOL


def test_prefill_then_decode_matches_the_reference(tiny):
    """Two prompts in chunks into slots 1 and 0, then one-token ticks in
    a batch of 3 lanes (lane 2 dead) for 20 tokens: the ring of 8 wraps
    twice; every tick's logits against a full reference pass."""
    cfg, sizes, params = tiny
    a, b = tokens_of(21, 3), tokens_of(6, 4)
    pool = fresh_pool(cfg, n_slots=3, n_blocks=40)
    ta, tb = table_for(1, 12), table_for(20, 12)
    la, pool, _ = prefill(cfg, params, pool, 1, ta, a)
    lb, pool, _ = prefill(cfg, params, pool, 0, tb, b)
    seqs = {1: list(a), 0: list(b)}
    nxt = {1: int(np.argmax(la)), 0: int(np.argmax(lb))}
    tables = jnp.asarray(np.stack([tb, ta, np.zeros(12, np.int32)]))
    for _ in range(20):
        positions = np.zeros(3, np.int32)
        toks = np.zeros(3, np.int32)
        for lane, seq in seqs.items():
            positions[lane], toks[lane] = len(seq), nxt[lane]
            seq.append(nxt[lane])
        lg, pool = sambay_decode_step_paged(
            cfg, params, pool, tables, jnp.asarray(positions),
            jnp.asarray(toks))
        for lane, seq in seqs.items():
            want = ref_logits(params, np.asarray(seq), sizes, len(seq) - 1,
                              len(seq))[0]
            np.testing.assert_allclose(lg[lane], want, atol=ATOL)
            nxt[lane] = int(np.argmax(want))
    # the dead lane's states were not touched
    assert not np.any(np.asarray(pool[4][:, 2]))
    assert not np.any(np.asarray(pool[5][:, 2]))


def test_a_chunk_of_garbage_slot_state_is_not_read_on_a_first_chunk(tiny):
    cfg, sizes, params = tiny
    toks = tokens_of(11, 5)
    clean, _, _ = prefill(cfg, params, fresh_pool(cfg), 0, table_for(1),
                          toks)
    dirty, pool, _ = prefill(cfg, params, fresh_pool(cfg, fill=-7.0), 0,
                             table_for(1), toks)
    np.testing.assert_allclose(clean, dirty, atol=1e-5)
    # the other slot's arrays kept their garbage
    assert float(jnp.min(pool[4][:, 1])) == -7.0


# -- through the engine -------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    cfg, _, params = tiny
    eng = InferenceEngine(cfg, params, n_slots=2, block_size=BS,
                          n_blocks=64, prefill_chunk=CHUNK)
    yield eng
    eng.shutdown(drain=False, timeout=30)


@pytest.mark.parametrize("n,new", [(5, 14), (37, 4)])
def test_engine_greedy_tokens_are_the_references(tiny, engine, n, new):
    cfg, sizes, params = tiny
    prompt = tokens_of(n, 6 + n)
    got = engine.submit(prompt, max_new_tokens=new).result(timeout=300)
    assert got == ref_greedy(params, prompt, new, sizes)


def test_mixed_lengths_in_one_batch_with_a_slot_reused(tiny, engine):
    """Three requests of unequal length over two slots: they batch
    together, the third takes the slot the first released (its states
    start from zero), and each equals its reference."""
    cfg, sizes, params = tiny
    prompts = [tokens_of(n, 7 + i) for i, n in enumerate((3, 29, 12))]
    lens = (4, 9, 6)
    reqs = [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts, lens)]
    together = [r.result(timeout=300) for r in reqs]
    assert together == [ref_greedy(params, p, m, sizes)
                        for p, m in zip(prompts, lens)]


def test_preempted_request_resumes_token_identical(tiny, engine):
    """A decoding request is preempted (its blocks and slot released,
    the request requeued with what it streamed) and re-prefills prompt
    plus generated from zero states: the same tokens as unpreempted."""
    cfg, sizes, params = tiny
    prompt = tokens_of(13, 11)
    want = ref_greedy(params, prompt, 12, sizes)
    pre0 = monitor.stat_get("serving_preemptions")
    req = engine.submit(prompt, max_new_tokens=12)
    deadline = time.monotonic() + 120
    done = False
    while not done and time.monotonic() < deadline:
        def preempt(eng):
            for s, st in enumerate(eng._slots):
                if st is not None and st.req is req and st.pending is None \
                        and 3 <= len(req.tokens) < 10:
                    eng._preempt(s)
                    return True
            return False
        done = engine.run_on_scheduler(preempt, timeout=60)
    assert done and monitor.stat_get("serving_preemptions") - pre0 == 1
    assert req.result(timeout=300) == want


def test_spans_and_counters_of_the_hybrid_cache(tiny, engine):
    counters = ("serving_state_slots_live", "serving_shared_kv_contexts",
                "serving_window_rows", "serving_cross_rows")
    before = {c: monitor.stat_get(c) for c in counters}
    monitor.start_tracing()
    try:
        reqs = [engine.submit(tokens_of(n, 30 + n), max_new_tokens=5)
                for n in (20, 7)]
        [r.result(timeout=300) for r in reqs]
    finally:
        events = monitor.stop_tracing().events()
    ticks = [e["args"] for e in events if e["name"] == "serving.decode_step"]
    assert ticks and all(1 <= a["state_slots_live"] <= 2 for a in ticks)
    for key in ("state_slots_live", "shared_kv_contexts", "window_rows"):
        assert sum(a[key] for a in ticks) == \
            monitor.stat_get("serving_" + key) - before["serving_" + key]
    assert all(a["window_rows"] <= min(a["shared_kv_contexts"],
                                       8 * a["state_slots_live"])
               for a in ticks)
    chunks = [e["args"] for e in events
              if e["name"] == "serving.prefill_chunk"]
    # 20 = 16 + 4 and 7: one cross-decoder row a prompt, in its last chunk
    assert sorted(a["chunk"] for a in chunks) == [4, 7, 16]
    assert sorted(a["cross_rows"] for a in chunks) == [0, 1, 1]
    assert all(a["cross_rows"] == 0 for a in chunks if a["chunk"] == 16)
    assert monitor.stat_get("serving_cross_rows") \
        - before["serving_cross_rows"] == 2


# -- the cache manager ---------------------------------------------------------

def test_cache_holds_paged_blocks_and_slot_arrays(tiny):
    cfg = tiny[0]
    cache = PagedKVCache(cfg, n_slots=3, n_blocks=9, block_size=BS)
    assert not cache.state_blocks and cache.block_size == BS
    assert len(cache.pool) == 6
    assert cache.pool[0].shape[0] == 9 and cache.pool[2].shape[0] == 4 * 2
    assert cache.pool[4].shape[1] == cache.pool[5].shape[1] == 3
    assert cache.blocks_for(9) == 3


def test_admission_needs_a_slot_and_the_blocks(tiny):
    cfg = tiny[0]
    cache = PagedKVCache(cfg, n_slots=2, n_blocks=9, block_size=BS)
    assert cache.admit_shard(33) is None           # 9 blocks > 8 free
    assert cache.admit_shard(32) == 0
    s = cache.alloc()
    assert cache.grow(s, 24)                        # 6 of the 8 blocks
    assert cache.admit_shard(9) is None             # 3 blocks > 2 free
    assert cache.admit_shard(8) == 0
    t = cache.alloc()
    assert cache.grow(t, 4)
    assert cache.admit_shard(1) is None and cache.alloc() is None  # slots
    cache.release(s)
    assert cache.admit_shard(28) == 0


def test_engine_waits_for_blocks_without_preempting(tiny):
    """Two slots over 12 blocks of 4: two 30-token prompts (8 blocks
    each with their chunk's padding) cannot run together; the second
    waits in the queue and both finish as their references."""
    cfg, sizes, params = tiny
    pre0 = monitor.stat_get("serving_preemptions")
    eng = InferenceEngine(cfg, params, n_slots=2, block_size=BS,
                          n_blocks=13, prefill_chunk=CHUNK)
    try:
        prompts = [tokens_of(30, 50 + i) for i in range(2)]
        reqs = [eng.submit(p, max_new_tokens=2) for p in prompts]
        got = [r.result(timeout=300) for r in reqs]
    finally:
        eng.shutdown(drain=False, timeout=30)
    assert got == [ref_greedy(params, p, 2, sizes) for p in prompts]
    assert monitor.stat_get("serving_preemptions") == pre0


def test_a_paged_model_reports_no_hybrid_args():
    from paddle_tpu.models import gpt_init
    cfg = gpt_tiny()
    eng = InferenceEngine(cfg, gpt_init(cfg, 0), n_slots=2)
    monitor.start_tracing()
    try:
        eng.submit(tokens_of(5), max_new_tokens=3).result(timeout=300)
    finally:
        events = monitor.stop_tracing().events()
        eng.shutdown(drain=False, timeout=30)
    args = [e["args"] for e in events
            if e["name"] in ("serving.decode_step", "serving.prefill_chunk")]
    assert args and not any(k in a for a in args for k in (
        "state_slots_live", "shared_kv_contexts", "window_rows",
        "cross_rows"))


# -- the kernels, in interpret mode -------------------------------------------

@pytest.mark.parametrize("width,lengths", [(4, (13, 0, 5)), (8, (32, 1, 17)),
                                           (8, (32, 32, 32))])
def test_hybrid_decode_kernel_equals_the_composed_path(width, lengths):
    rng = np.random.default_rng(width)
    B, nq, nkv, d, L, bs = 3, 8, 4, 8, 2, 4
    q = jnp.asarray(rng.standard_normal((B, nq, d)), jnp.float32)
    qw = ha.wide_queries(q, nkv)
    nb = 1 + B * width
    kp = jnp.asarray(rng.standard_normal((nb, L, bs, nkv * d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, L, bs, nkv * d)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * width).reshape(B, width),
                         jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = ha.hybrid_decode(qw, kp, vp, tables, lengths, 1, nkv // 2, 0.35)
    got = ha.hybrid_decode(qw, kp, vp, tables, lengths, 1, nkv // 2, 0.35,
                           interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not np.any(np.asarray(got)[np.asarray(lengths) == 0])


def test_wide_queries_score_each_head_on_its_own_key_head():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)   # 4 heads
    got = ha.wide_queries(q, 4)[0] @ k.reshape(-1)
    want = [float(q[0, j] @ k[2 * (j // 4) + j % 2]) for j in range(8)]
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("n_true", [16, 11, 0])
def test_selective_scan_kernel_equals_the_composed_path(n_true):
    rng = np.random.default_rng(n_true)
    T, Di, N = 16, 256, 4
    x = jnp.asarray(rng.standard_normal((T, Di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (T, Di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 3.0, (N, Di)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((T, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((T, N)), jnp.float32)
    D = jnp.ones((Di,), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((N, Di)), jnp.float32)
    y1, s1 = ss.selective_scan(x, dt, A, B, C, D, s0, n_true)
    y2, s2 = ss.selective_scan(x, dt, A, B, C, D, s0, n_true, interpret=True)
    np.testing.assert_allclose(np.asarray(y2)[:n_true],
                               np.asarray(y1)[:n_true], atol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    if n_true == 0:
        np.testing.assert_array_equal(s1, s0)
    # the recurrence, one step at a time
    s = np.asarray(s0)
    for t in range(n_true):
        s = np.exp(np.asarray(dt[t]) * np.asarray(A)) * s \
            + np.asarray(B[t])[:, None] * np.asarray(dt[t] * x[t])
    np.testing.assert_allclose(s1, s, atol=1e-4)


# -- what the engine refuses, and the benchmark's own names ------------------

@pytest.mark.parametrize("option,kw,needle", [
    ("draft", {"draft": "same"}, "no roll-back"),
    ("prefix_cache", {"prefix_cache": True}, "none is kept"),
    ("int8_weights", {"int8_weights": True}, "no quantized layout"),
    ("mesh", {"mesh": "one"}, "no sharded layout"),
])
def test_engine_refuses_with_the_models_sentence(tiny, option, kw, needle):
    cfg, _, params = tiny
    if "draft" in kw:
        kw = {"draft": (cfg, params)}
    if "mesh" in kw:
        from jax.sharding import Mesh

        from paddle_tpu.parallel.mesh import AXES
        kw = {"mesh": Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                           AXES)}
    with pytest.raises(ValueError, match=needle) as e:
        InferenceEngine(cfg, params, n_slots=2, block_size=BS,
                        prefill_chunk=CHUNK, **kw)
    assert str(e.value) == cfg.serving_model().refuses[option]
    assert "SambaYConfig" in str(e.value)


def test_engine_refuses_a_chunk_that_is_not_whole_windows(tiny):
    cfg, _, params = tiny
    with pytest.raises(ValueError, match="multiple of 8"):
        InferenceEngine(cfg, params, n_slots=2, block_size=BS,
                        prefill_chunk=12)


def test_train_side_names_refuse_with_the_arithmetic():
    for call in (lambda: FAMILY.train_loss(None),
                 lambda: FAMILY.param_specs(None),
                 lambda: FAMILY.train_flops_per_token({}, 1),
                 lambda: FAMILY.leaf_norms({})):
        with pytest.raises(NotImplementedError, match="61.6 GB"):
            call()


def test_work_counts():
    s = sizes_of(phi4_mini_flash())
    from benchmarks.families.sambay import work
    assert work.row_bytes(s) == 5120
    # every weight but the norms, Mamba's convolution, dt bias, A and D,
    # and the attention layers' biases, lambdas and output norm
    assert work.matmul_params(s) == 3_852_562_944 - 332_800 \
        - 9 * 117_760 - 9 * 8_064 - 7 * 5_504
    # the once-a-prompt part spread over the prompt
    f = FAMILY.forward_flops_per_token
    assert f(s, 4096, causal_mean=True) < f(s, 4096) / 2
    flops, byts = work.hybrid_decode(100, 50, s)
    assert byts == 8 * 150 * 5120
    assert flops == 8 * 150 * 40 * (128 + 256)


# -- the benchmark's new readers on a trace without their sources ------------

def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("r_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _empty_ctx():
    trace = types.SimpleNamespace(ops={0: []}, modules={0: []})
    spans = [{"name": "serving.prefill_chunk", "ph": "X", "ts": 0,
              "dur": 5, "args": {"chunk": 512, "start": 0}},
             {"name": "serving.decode_step", "ph": "X", "ts": 9, "dur": 5,
              "args": {"batch": 3}}]
    return types.SimpleNamespace(
        trace=trace, program_events=spans, stat_delta={}, hist_delta={},
        values={}, sizes=sizes_of(phi4_mini_flash()),
        spec=types.SimpleNamespace(family=FAMILY,
                                   config={"family": "sambay"}))


@pytest.mark.parametrize("name", ["tick_ssm_ms.serve",
                                  "cross_skip_share.serve"])
def test_new_module_readers_read_nothing_without_their_sources(name):
    assert _reader(name).read(_empty_ctx()) is None


@pytest.mark.parametrize("work", ["hybrid_decode", "ssm_chunk"])
def test_kernel_work_reads_nothing_without_its_span_args(work):
    ctx = _empty_ctx()
    assert FAMILY.KERNEL_WORK[work](ctx, 3) is None
    from benchmarks.lib import readers
    metric = {"hybrid_decode": "hybrid_diff_decode",
              "ssm_chunk": "selective_scan_chunk"}[work]
    assert readers.device_trace_kernel(
        ctx, {"pattern": metric, "work": work}) is None


def test_cross_skip_share_reads_the_chunk_spans():
    ctx = _empty_ctx()
    ctx.program_events = [
        {"name": "serving.prefill_chunk", "ph": "X", "ts": 0, "dur": 1,
         "args": {"chunk": c, "start": 0, "cross_rows": r}}
        for c, r in ((512, 0), (512, 0), (100, 1))]
    assert _reader("cross_skip_share.serve").read(ctx) == \
        pytest.approx(100.0 * (1 - 1 / 1124))


def test_reference_does_not_depend_on_its_row_blocks(tiny, monkeypatch):
    """192 rows in one block of attention rows and in three: the same
    logits (a length that is not whole blocks once broke the blocking)."""
    cfg, sizes, params = tiny
    toks = tokens_of(192, 9)
    one = ref_logits(params, toks, sizes, 100, 192)
    monkeypatch.setattr(FAMILY.reference, "QB", 64)
    np.testing.assert_array_equal(ref_logits(params, toks, sizes, 100, 192),
                                  one)
