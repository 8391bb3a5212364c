"""The power-retention model (``models/retention.py``, the kernels of
``ops/power_retention.py``) against its family's plain reference
(``benchmarks/families/brumby``: the ATTENTION form, which shares no
formulation with the program's recurrence) on seeded weights at tiny
widths (hidden 64, 4 query heads over 2 key/value heads of 16, 136
distinct products a key, 3 layers): ``phi``, the full forward, chunked
prefill at lengths that are multiples of neither the chunk nor the pad,
prefill then decode through ``InferenceEngine``, batching against
serving alone with a slot reused, resume after a preemption, the
kernels in interpret mode, the cache manager's accounting of states,
the refusals and the tracing."""
import dataclasses
import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.spec import load_family
from paddle_tpu import monitor
from paddle_tpu.models import (brumby_14b, gpt_tiny,
                               retention_decode_step_paged, retention_forward,
                               retention_init, retention_prefill_chunk,
                               retention_tiny)
from paddle_tpu.ops import power_retention as pr
from paddle_tpu.serving import InferenceEngine
from paddle_tpu.serving.kv_cache import PagedKVCache

FAMILY = load_family("brumby")
SEED = 2 ** 31 + 34
PAD = 128            # the model's pad granule (ServingModel.state_pad)


def sizes_of(cfg, **extra):
    s = {k: v for k, v in dataclasses.asdict(cfg).items()
         if k not in ("dtype", "param_dtype")}
    s.update(dtype="float32", param_dtype="float32", init_std=0.2, **extra)
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = retention_tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, FAMILY.make_params(sizes, SEED)


def tokens_of(n, salt=0):
    return np.asarray(jax.random.randint(jax.random.key(100 + salt), (n,),
                                         0, 256), np.int32)


def ref_logits(params, tokens, sizes, lo, hi):
    buf = np.zeros(-(-len(tokens) // 64) * 64, np.int32)
    buf[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAMILY.reference.logits_at(
            params, buf, sizes, lo, hi)[0])


def ref_greedy(params, prompt, n, sizes):
    """n greedy tokens after ``prompt``, each from a full reference pass."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(
            ref_logits(params, np.asarray(seq), sizes, len(seq) - 1,
                       len(seq))[0])))
    return seq[len(prompt):]


def rnd(i, *shape):
    return jax.random.normal(jax.random.key(i), shape)


# -- phi and the layout -------------------------------------------------------

@pytest.mark.parametrize("d", [16, 32, 128])
def test_phi_dot_phi_is_the_squared_product(d):
    u, w = rnd(1, 5, d), rnd(2, 5, d)
    got = jnp.sum(pr.phi(u) * pr.phi(w), -1)
    np.testing.assert_allclose(got, jnp.sum(u * w, -1) ** 2, rtol=2e-5,
                               atol=2e-5)


def test_state_layout_is_whole_lane_and_sublane_tiles():
    assert pr.phi_width(128) == 9216 and pr.phi_width(128) % 128 == 0
    assert pr.phi_width(16) % 128 == 0
    assert pr.state_rows(128) == 136 and pr.state_rows(128) % 8 == 0
    cfg = brumby_14b(n_layers=8)
    (spec,) = cfg.serving_model().pool_spec(cfg, 17, 128)
    assert spec.shape == (17, 8, 8, 136, 9216) and spec.dtype == jnp.float32
    assert cfg.phi_dim == 8256


def test_published_sizes_are_the_defaults():
    cfg = brumby_14b()
    assert (cfg.hidden, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.ffn, cfg.vocab_size, cfg.seq_len) == \
        (5120, 40, 40, 8, 128, 17408, 151936, 32768)
    assert cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-6
    per_layer = sum(int(np.prod(s[1:])) for s in jax.tree_util.tree_leaves(
        FAMILY.weights.shapes(sizes_of(cfg))["layers"],
        is_leaf=lambda s: isinstance(s, tuple)))
    assert per_layer == 330_352_896


def test_family_weights_have_the_models_layout(tiny):
    cfg, sizes, params = tiny
    mine = retention_init(cfg, 0)
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == \
        jax.tree_util.tree_map(lambda a: a.shape, mine)


# -- the model against the attention-form reference ---------------------------

@pytest.mark.parametrize("chunk", [None, 16, 7])
def test_full_forward_matches_the_attention_form(tiny, chunk):
    cfg, sizes, params = tiny
    toks = tokens_of(45)
    got = retention_forward(cfg, params, jnp.asarray(toks)[None], chunk)[0]
    np.testing.assert_allclose(got, ref_logits(params, toks, sizes, 0, 45),
                               atol=2e-4)


def test_long_memory_gates_near_one(tiny):
    """Gates spread from 0 to 1 (a scaled ``wg``): some heads keep
    every chunk that came before."""
    cfg, sizes, params = tiny
    params = dict(params, layers=dict(
        params["layers"], wg=params["layers"]["wg"] * 6.0))
    toks = tokens_of(60, 1)
    got = retention_forward(cfg, params, jnp.asarray(toks)[None], 16)[0]
    np.testing.assert_allclose(got, ref_logits(params, toks, sizes, 0, 60),
                               atol=5e-4)


def fresh_pool(cfg, n_blocks=4, fill=0.0):
    return tuple(jnp.full(a.shape, fill, a.dtype) for a in
                 cfg.serving_model().pool_spec(cfg, n_blocks, PAD))


def prefill(cfg, params, pool, block, toks, chunk, pad):
    """Chunked prefill of ``toks`` into ``block`` as the engine does it:
    chunks of ``chunk`` tokens, each end-padded to a multiple of
    ``pad``. -> (logits of the last real row, pool)."""
    row = jnp.full((1,), block, jnp.int32)
    at = 0
    while at < len(toks):
        n = min(chunk, len(toks) - at)
        buf = np.zeros((1, -(-n // pad) * pad), np.int32)
        buf[0, :n] = toks[at:at + n]
        lg, pool = retention_prefill_chunk(
            cfg, params, pool, row, jnp.asarray(buf), jnp.int32(at),
            jnp.int32(n))
        at += n
    return lg[0, n - 1], pool


@pytest.mark.parametrize("n", [5, 19, 33, 47])
def test_chunked_prefill_does_not_fold_the_padded_tail(tiny, n):
    """Prompt lengths that are multiples of neither the chunk (16) nor
    the pad (8): the last real row's logits, and the state that is left,
    are those of exactly ``n`` tokens. The block starts full of
    garbage: ``start == 0`` ignores it."""
    cfg, sizes, params = tiny
    toks = tokens_of(n, 2)
    last, pool = prefill(cfg, params, fresh_pool(cfg, fill=3.0), 2, toks,
                         16, 8)
    np.testing.assert_allclose(last, ref_logits(params, toks, sizes, n - 1,
                                                n)[0], atol=2e-4)
    _, exact = prefill(cfg, params, fresh_pool(cfg), 2, toks, n, 1)
    np.testing.assert_allclose(pool[0][2], exact[0][2], atol=1e-4)
    # every other block kept its garbage, the sink too
    assert float(jnp.min(pool[0][jnp.array([0, 1, 3])])) == 3.0


def test_prefill_then_decode_matches_the_reference(tiny):
    """The cached path at the model's own entry points: a prompt in
    chunks, then one-token steps in a batch whose other lanes are dead
    or hold another sequence; every step's logits against a full
    reference pass."""
    cfg, sizes, params = tiny
    a, b = tokens_of(21, 3), tokens_of(9, 4)
    pool = fresh_pool(cfg, n_blocks=5)
    la, pool = prefill(cfg, params, pool, 3, a, 16, 8)
    lb, pool = prefill(cfg, params, pool, 1, b, 16, 8)
    seqs = {0: list(a), 2: list(b)}
    nxt = {0: int(jnp.argmax(la)), 2: int(jnp.argmax(lb))}
    tables = jnp.asarray([[3], [0], [1], [0]], jnp.int32)
    for _ in range(4):
        positions = np.zeros(4, np.int32)
        toks = np.zeros(4, np.int32)
        for lane, seq in seqs.items():
            positions[lane], toks[lane] = len(seq), nxt[lane]
            seq.append(nxt[lane])
        sink = pool[0][0]
        lg, pool = retention_decode_step_paged(
            cfg, params, pool, tables, jnp.asarray(positions),
            jnp.asarray(toks))
        np.testing.assert_array_equal(pool[0][0], sink)   # dead lanes
        for lane, seq in seqs.items():
            want = ref_logits(params, np.asarray(seq), sizes, len(seq) - 1,
                              len(seq))[0]
            np.testing.assert_allclose(lg[lane], want, atol=3e-4)
            nxt[lane] = int(np.argmax(want))


# -- through the engine -------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    cfg, _, params = tiny
    eng = InferenceEngine(cfg, params, n_slots=2, n_blocks=3,
                          prefill_chunk=PAD)
    yield eng
    eng.shutdown(drain=False, timeout=30)


def test_engine_greedy_tokens_are_the_references(tiny, engine):
    cfg, sizes, params = tiny
    prompt = tokens_of(37, 5)
    got = engine.submit(prompt, max_new_tokens=6).result(timeout=300)
    assert got == ref_greedy(params, prompt, 6, sizes)


def test_engine_prompt_longer_than_a_chunk(tiny, engine):
    """137 tokens: a whole 128-token chunk, then 9 padded to 128."""
    cfg, sizes, params = tiny
    prompt = tokens_of(137, 6)
    got = engine.submit(prompt, max_new_tokens=3).result(timeout=300)
    assert got == ref_greedy(params, prompt, 3, sizes)


def test_batched_unequal_lengths_equal_each_alone_with_a_slot_reused(
        tiny, engine):
    """Three requests of unequal length over two slots: the third takes
    the slot, and the state, the first to finish released."""
    cfg, sizes, params = tiny
    prompts = [tokens_of(n, 7 + i) for i, n in enumerate((12, 50, 29))]
    lens = (3, 9, 5)
    reqs = [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts, lens)]
    together = [r.result(timeout=300) for r in reqs]
    alone = [engine.submit(p, max_new_tokens=m).result(timeout=300)
             for p, m in zip(prompts, lens)]
    assert together == alone
    assert together == [ref_greedy(params, p, m, sizes)
                        for p, m in zip(prompts, lens)]


def test_sampled_requests_do_not_depend_on_their_neighbours(tiny):
    cfg, _, params = tiny
    outs = []
    for n_slots in (1, 3):
        eng = InferenceEngine(cfg, params, n_slots=n_slots, seed=5,
                              prefill_chunk=PAD)
        try:
            reqs = [eng.submit(tokens_of(10 + 7 * i, 20 + i),
                               max_new_tokens=5, temperature=0.9, top_k=20,
                               top_p=0.9) for i in range(3)]
            outs.append([r.result(timeout=300) for r in reqs])
        finally:
            eng.shutdown(drain=False, timeout=30)
    assert outs[0] == outs[1]


def test_preempted_request_resumes_token_identical(tiny, engine):
    """A decoding request is preempted (its state released, the request
    requeued with what it has streamed) and re-prefills prompt plus
    generated into a fresh state: the same tokens as unpreempted."""
    cfg, sizes, params = tiny
    prompt = tokens_of(23, 11)
    want = engine.submit(prompt, max_new_tokens=12).result(timeout=300)
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


def test_spans_and_counter_say_how_many_states_a_tick_moved(tiny, engine):
    live0 = monitor.stat_get("serving_state_slots_live")
    monitor.start_tracing()
    try:
        reqs = [engine.submit(tokens_of(8, 30 + i), max_new_tokens=4)
                for i in range(2)]
        [r.result(timeout=300) for r in reqs]
    finally:
        events = monitor.stop_tracing().events()
    ticks = [e["args"] for e in events if e["name"] == "serving.decode_step"]
    assert ticks and all(1 <= a["state_slots_live"] <= 2 for a in ticks)
    assert sum(a["state_slots_live"] for a in ticks) == \
        monitor.stat_get("serving_state_slots_live") - live0
    chunks = [e["args"] for e in events
              if e["name"] == "serving.prefill_chunk"]
    assert [a["chunk"] for a in chunks] == [8, 8]
    import sys
    sys.path.insert(0, "tools")
    import trace_report
    out = io.StringIO()
    got = trace_report.serving_report(trace_report.aggregate(events),
                                      file=out, events=events)
    assert got["state_slots_live"] == sum(a["state_slots_live"]
                                          for a in ticks)
    assert "state_slots_live_a_tick" in out.getvalue()


def test_a_paged_model_reports_no_state_slots():
    from paddle_tpu.models import gpt_init
    cfg = gpt_tiny()
    eng = InferenceEngine(cfg, gpt_init(cfg, 0), n_slots=2)
    monitor.start_tracing()
    try:
        eng.submit(tokens_of(5), max_new_tokens=3).result(timeout=300)
    finally:
        events = monitor.stop_tracing().events()
        eng.shutdown(drain=False, timeout=30)
    ticks = [e["args"] for e in events if e["name"] == "serving.decode_step"]
    assert ticks and all("state_slots_live" not in a for a in ticks)
    assert not eng.cache.state_blocks


# -- the kernels in interpret mode --------------------------------------------

@pytest.mark.kernels
@pytest.mark.parametrize("d,hq,hkv", [(16, 4, 2), (32, 6, 2)])
def test_decode_kernel_equals_the_composed_path(d, hq, hkv):
    B, L, NB = 5, 2, 7
    pool = rnd(0, NB, L, hkv, pr.state_rows(d), pr.phi_width(d)) * 0.1
    q, k, v = rnd(1, B, hq, d), rnd(2, B, hkv, d), rnd(3, B, hkv, d)
    lg = -jnp.abs(rnd(4, B, hkv))
    blocks = jnp.array([3, 0, 5, 1, 0], jnp.int32)
    live = blocks > 0
    ya, pa = pr.retention_decode(q, k, v, lg, pool, blocks, live, 1, 1e-6)
    yb, pb = pr.retention_decode(q, k, v, lg, pool, blocks, live, 1, 1e-6,
                                 interpret=True)
    np.testing.assert_allclose(ya, yb, atol=2e-4)
    np.testing.assert_allclose(pa, pb, atol=1e-5)
    # in place: only the live lanes' blocks at the layer moved
    others = jnp.array([0, 2, 4, 6])
    np.testing.assert_array_equal(pb[others], pool[others])
    np.testing.assert_array_equal(pb[:, 0], pool[:, 0])
    assert float(jnp.max(jnp.abs(yb[jnp.array([1, 4])]))) == 0.0
    assert float(jnp.max(jnp.abs(pb[3, 1] - pool[3, 1]))) > 0.01


@pytest.mark.kernels
def test_decode_kernel_with_no_live_lane():
    d, hkv = 16, 2
    pool = rnd(0, 3, 1, hkv, pr.state_rows(d), pr.phi_width(d))
    blocks = jnp.zeros((2,), jnp.int32)
    y, got = pr.retention_decode(
        rnd(1, 2, 4, d), rnd(2, 2, hkv, d), rnd(3, 2, hkv, d),
        -jnp.ones((2, hkv)), pool, blocks, blocks > 0, 0, 1e-6,
        interpret=True)
    np.testing.assert_array_equal(got, pool)
    assert float(jnp.max(jnp.abs(y))) == 0.0


@pytest.mark.kernels
@pytest.mark.parametrize("start,n_true", [(0, 32), (32, 19), (64, 1)])
def test_chunk_kernel_equals_the_composed_path(start, n_true):
    d, hq, hkv, C = 16, 4, 2, 32
    pool = rnd(0, 5, 2, hkv, pr.state_rows(d), pr.phi_width(d)) * 0.1
    q, k, v = rnd(5, C, hq, d) * .5, rnd(6, C, hkv, d) * .5, rnd(7, C, hkv, d)
    lg = -jnp.abs(rnd(8, C, hkv)) * 0.3
    ya, pa = pr.retention_chunk(q, k, v, lg, pool, 3, 1, start, n_true, 1e-6)
    yb, pb = pr.retention_chunk(q, k, v, lg, pool, 3, 1, start, n_true, 1e-6,
                                interpret=True)
    np.testing.assert_allclose(ya[:n_true], yb[:n_true], atol=1e-5)
    np.testing.assert_allclose(pa, pb, atol=1e-6)
    others = jnp.array([0, 1, 2, 4])
    np.testing.assert_array_equal(pb[others], pool[others])
    np.testing.assert_array_equal(pb[:, 0], pool[:, 0])


def test_chunk_then_steps_equal_one_chunk():
    """The recurrence is one: C tokens as a chunk leave the state, and
    give the outputs, that C one-token steps do."""
    d, hq, hkv, C = 16, 4, 2, 12
    shape = (3, 1, hkv, pr.state_rows(d), pr.phi_width(d))
    q, k, v = rnd(5, C, hq, d) * .5, rnd(6, C, hkv, d) * .5, rnd(7, C, hkv, d)
    lg = -jnp.abs(rnd(8, C, hkv)) * 0.3
    eps = 1e-2     # a normaliser near 0 divides rounding by itself
    yc, pc = pr.retention_chunk(q, k, v, lg, jnp.zeros(shape), 1, 0, 0, C,
                                eps)
    ps = jnp.zeros(shape)
    blocks = jnp.ones((1,), jnp.int32)
    for t in range(C):
        y, ps = pr.retention_decode(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                                    lg[t:t + 1], ps, blocks, blocks > 0, 0,
                                    eps)
        np.testing.assert_allclose(y[0], yc[t], atol=1e-4)
    np.testing.assert_allclose(ps, pc, atol=1e-5)


# -- the cache manager's accounting of states ---------------------------------

def test_cache_a_block_is_a_state(tiny):
    cfg = tiny[0]
    cache = PagedKVCache(cfg, n_slots=4, n_blocks=3, block_size=16)
    assert cache.state_blocks and cache.table_width == 1
    assert cache.block_size == PAD           # the model's, not the caller's
    assert [cache.blocks_for(n) for n in (0, 1, 127, 128, 10 ** 6)] == \
        [0, 1, 1, 1, 1]
    assert cache.pool[0].shape[0] == 3 and cache.max_slot_blocks == 2
    used0 = monitor.stat_get("kv_blocks_used")
    assert used0 == 0 and monitor.stat_get("kv_blocks_free") == 2
    # admission: a free slot AND a free state, whatever the length
    slots = []
    for n in (5, 9000):
        assert cache.admit_shard(n) == 0
        s = cache.alloc()          # takes the slot's state with it
        assert len(cache.block_tables[s]) == 1 and cache.grow(s, n)
        assert cache.block_tables[s] != [0]
        slots.append(s)
    assert cache.admit_shard(1) is None and not cache.can_admit(1)
    assert cache.alloc() is None
    assert cache.free_count == 2             # slots are left, states not
    assert monitor.stat_get("kv_blocks_used") == 2
    assert monitor.stat_get("kv_fragmentation") == 0
    # growing a slot that has its state never fails and takes nothing
    assert cache.grow(slots[0], 10 ** 6)
    assert len(cache.block_tables[slots[0]]) == 1
    assert cache.tables_array(slots).shape == (4, 1)
    assert list(cache.tables_array([slots[1]])[:, 0]) == \
        [0, cache.block_tables[slots[1]][0], 0, 0]
    freed = cache.block_tables[slots[0]][0]
    cache.release(slots[0])
    assert monitor.stat_get("kv_blocks_used") == 1
    assert cache.admit_shard(123456) == 0
    with pytest.raises(AssertionError, match="double-freed"):
        cache.unref_block(freed)
    with pytest.raises(AssertionError, match="sink"):
        cache.unref_block(0)


def test_cache_default_pool_is_a_state_a_slot_and_the_sink(tiny):
    cache = PagedKVCache(tiny[0], n_slots=3)
    assert cache.n_blocks == 4


def test_cache_of_a_token_block_model_is_what_it_was():
    cfg = gpt_tiny()
    cache = PagedKVCache(cfg, n_slots=2, block_size=16)
    assert not cache.state_blocks and cache.block_size == 16
    assert cache.table_width == -(-cfg.seq_len // 16)
    assert cache.blocks_for(17) == 2


def test_engine_admits_by_free_states(tiny):
    """4 slots over 2 states: two requests run, the others wait in the
    queue for a state, never for tokens; none is preempted."""
    cfg, _, params = tiny
    pre0 = monitor.stat_get("serving_preemptions")
    eng = InferenceEngine(cfg, params, n_slots=4, n_blocks=3,
                          prefill_chunk=PAD)
    try:
        reqs = [eng.submit(tokens_of(20 + i, 40 + i), max_new_tokens=6)
                for i in range(5)]
        peak = 0
        while not all(r.done for r in reqs):
            peak = max(peak, monitor.stat_get("kv_blocks_used"))
            time.sleep(0.002)
        assert all(len(r.result(timeout=300)) == 6 for r in reqs)
        assert 1 <= peak <= 2
    finally:
        eng.shutdown(drain=False, timeout=30)
    assert monitor.stat_get("serving_preemptions") == pre0


# -- what the engine refuses --------------------------------------------------

@pytest.mark.parametrize("option,kw,needle", [
    ("draft", {"draft": "same"}, "no roll-back of the state"),
    ("prefix_cache", {"prefix_cache": True}, "no snapshots are kept"),
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
        InferenceEngine(cfg, params, n_slots=2, **kw)
    assert str(e.value) == cfg.serving_model().refuses[option]
    assert "RetentionConfig" in str(e.value)


def test_train_side_names_refuse_with_the_arithmetic():
    for call in (lambda: FAMILY.train_loss(None),
                 lambda: FAMILY.param_specs(None),
                 lambda: FAMILY.train_flops_per_token({}, 1),
                 lambda: FAMILY.leaf_norms({})):
        with pytest.raises(NotImplementedError, match="29.5 GB"):
            call()


def test_work_counts_do_not_depend_on_the_context():
    s = sizes_of(brumby_14b(n_layers=8))
    f = FAMILY.forward_flops_per_token
    assert f(s, 512) == f(s, 16384) == f(s, 16384, causal_mean=True)
    from benchmarks.families.brumby import work
    assert work.matmul_params(s) == 8 * (330_352_896 - 2 * 5120 - 256) \
        + 5120 * 151936
    assert work.state_bytes(s) * 8 == pytest.approx(272.6e6, rel=1e-3)
    flops, byts = work.retention_decode(3, s)
    assert byts == 3 * 8 * 2 * work.state_bytes(s)
    assert flops == 3 * 8 * (8 * 3 * 8256 * 128 + 40 * 2 * 8256 * 129)
