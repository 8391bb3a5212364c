"""The latent-attention expert model (``models/mla.py``) against its
family's plain reference (``benchmarks/families/sarvam_mla``) on seeded
weights at tiny widths: the full forward, chunked prefill and absorbed
paged decode through the pool, the share of the experts, the grouped
expert FFN, the router's bias, YaRN by hand, the pool's shape, the
engine's refusals, the three counters, and that a ``GPTConfig`` engine
is what it was."""
import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.spec import load_family
from paddle_tpu import monitor
from paddle_tpu.models import (GPTConfig, MLAConfig, gpt_init, gpt_tiny,
                               mla_decode_step_paged, mla_forward, mla_init,
                               mla_prefill_chunk, mla_tiny, sarvam_105b)
from paddle_tpu.models.mla import yarn_inv_freq, yarn_mscale
from paddle_tpu.monitor import stats, trace
from paddle_tpu.nn.moe import moe_ffn_held, moe_route_sigmoid
from paddle_tpu.ops import mla_attention
from paddle_tpu.serving import InferenceEngine
from paddle_tpu.serving.kv_cache import PagedKVCache

FAMILY = load_family("sarvam_mla")
SEED = 2 ** 31 + 30


def sizes_of(cfg, **extra):
    s = {k: v for k, v in dataclasses.asdict(cfg).items()
         if k not in ("dtype", "param_dtype")}
    s.update(dtype="float32", param_dtype="float32", init_std=0.2, **extra)
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = mla_tiny(experts_held=4, expert_offset=2)
    sizes = sizes_of(cfg)
    return cfg, sizes, FAMILY.make_params(sizes, SEED)


def ref_logits(params, tokens, sizes, lo, hi):
    buf = np.zeros(-(-len(tokens) // 64) * 64, np.int32)
    buf[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAMILY.reference.logits_at(
            params, buf, sizes, lo, hi)[0])


# -- the model against the reference -----------------------------------------

def test_family_weights_have_the_models_layout(tiny):
    cfg, sizes, params = tiny
    mine = mla_init(cfg, 0)
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == \
        jax.tree_util.tree_map(lambda a: a.shape, mine)
    # the held experts and the vocabulary slice only
    assert params["moe"]["w_gate"].shape == (2, 4, 64, 32)
    assert params["moe"]["router_w"].shape == (2, 64, 8)
    assert params["head"].shape == (64, 256)


def test_full_forward_matches_the_reference(tiny):
    """float32 on both sides, the same mathematics in another order
    (grouped experts against one expert at a time, attention in head
    groups): logits of std 1.6 agree to 1e-4 (1.3e-5 read)."""
    cfg, sizes, params = tiny
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 100)
    got, _ = mla_forward(cfg, params, jnp.asarray(toks[None], jnp.int32))
    want = ref_logits(params, toks, sizes, 0, 100)
    assert float(np.std(want)) > 0.5
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-4)


def test_chunked_prefill_then_paged_decode_matches_the_reference(tiny):
    """Prefill by chunks into the latent pool, then absorbed decode out
    of it: logits at every served position equal the reference's full
    forward over the same tokens (5e-4: the absorbed order of the key
    and value products; 4e-5 read)."""
    cfg, sizes, params = tiny
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 41).astype(np.int32)
    n_new, bs, chunk = 9, 8, 16
    cache = PagedKVCache(cfg, n_slots=2, n_blocks=12, block_size=bs)
    assert [a.shape for a in cache.pool] == [(12, 3, 8, 128)]
    slot = cache.alloc()
    pool, served, logits_seen = cache.pool, [], []
    start = 0
    while start < len(prompt):                     # chunks of 16, padded
        c_true = min(chunk, len(prompt) - start)
        c_pad = -(-c_true // bs) * bs
        assert cache.grow(slot, start + c_pad)
        toks = np.zeros((1, c_pad), np.int32)
        toks[0, :c_true] = prompt[start:start + c_true]
        lg, pool, st = mla_prefill_chunk(
            cfg, params, pool, jnp.asarray(cache.table_row(slot)[:8]),
            jnp.asarray(toks), np.int32(start))
        start += c_true
    nxt = int(jnp.argmax(lg[0, c_true - 1]))
    logits_seen.append(np.asarray(lg[0, c_true - 1]))
    length = len(prompt)
    for _ in range(n_new - 1):
        served.append(nxt)
        assert cache.grow(slot, length + 1)
        tables = cache.tables_array([slot])[:, :8]
        lg, pool, st = mla_decode_step_paged(
            cfg, params, pool, jnp.asarray(tables),
            jnp.asarray([length, 0], jnp.int32),
            jnp.asarray([nxt, 0], jnp.int32))
        logits_seen.append(np.asarray(lg[0]))
        nxt = int(jnp.argmax(lg[0]))
        length += 1
    served.append(nxt)
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])
    want = ref_logits(params, seq, sizes, len(prompt) - 1, len(seq) - 1)
    np.testing.assert_allclose(np.stack(logits_seen), want, atol=5e-4)
    # the lane that holds no request read no expert: 1 token x 2 layers
    counts, held, reads, tiles = st
    assert int(counts.sum()) == 1 * cfg.top_k * cfg.n_moe_layers
    gaps, _ = FAMILY.served_gaps(params, prompt, served, sizes, 128)
    # positions with a near tie for the last expert are not judged
    assert 0 < gaps.shape[0] <= n_new and float(gaps.max()) < 1e-3


@pytest.mark.kernels
@pytest.mark.parametrize("W,group", [(8, 1), (8, 4), (2, 4), (8, 8),
                                     (32, 16)])
def test_latent_decode_kernel_matches_its_composed_math(W, group):
    """Interpret mode: the Pallas kernel's mathematics on the live walk
    (G blocks a step as one tile, online softmax across a slot's steps,
    blocks past the last live one clamped and masked, a padded row, a
    traced layer), with dead slots between live ones and lengths on a
    block's and a step's edge."""
    rng = np.random.default_rng(2)
    B, nh, R, Dr, bs, L = 6, 4, 32, 8, 8, 2
    pool = jnp.asarray(rng.standard_normal((B * W + 1, L, bs, 128)),
                       jnp.float32)
    ql = jnp.asarray(rng.standard_normal((B, nh, R)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, nh, Dr)), jnp.float32)
    full = W * bs
    lens = np.asarray([0, 1, min(group * bs, full), 0, max(full - 3, 1), full])
    live = np.arange(W)[None, :] * bs < lens[:, None]
    tables = jnp.asarray(np.where(
        live, 1 + rng.permutation(B * W).reshape(B, W), 0), jnp.int32)
    lengths = jnp.asarray(lens, jnp.int32)
    want = mla_attention._mla_decode_reference(ql, qr, pool, tables, lengths,
                                               0.2, 1)
    got = jax.jit(lambda li: mla_attention._mla_decode(
        ql, qr, pool, tables, lengths, li, scale=0.2, interpret=True,
        group=group))(jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got)[lens > 0],
                               np.asarray(want)[lens > 0], atol=2e-5)
    # a slot with no token costs no step: its row is zeros
    assert not np.asarray(got)[lens == 0].any()


@pytest.mark.kernels
def test_latent_decode_takes_the_walk_its_model_builds():
    """``decode_walk`` handed in is the walk built inside; one of another
    table width is refused."""
    rng = np.random.default_rng(4)
    B, nh, R, Dr, bs, L, W = 3, 4, 32, 8, 8, 2, 8
    pool = jnp.asarray(rng.standard_normal((B * W + 1, L, bs, 128)),
                       jnp.float32)
    ql = jnp.asarray(rng.standard_normal((B, nh, R)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, nh, Dr)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * W).reshape(B, W), jnp.int32)
    lengths = jnp.asarray([0, 37, W * bs], jnp.int32)
    inside = mla_attention.mla_decode_arrays(ql, qr, pool, tables, lengths,
                                             0.2, 1, interpret=True)
    handed = mla_attention.mla_decode_arrays(
        ql, qr, pool, tables, lengths, 0.2, 1, interpret=True,
        walk=mla_attention.decode_walk(lengths, W, bs))
    np.testing.assert_array_equal(np.asarray(inside), np.asarray(handed))
    with pytest.raises(ValueError, match="decode_walk"):
        mla_attention.mla_decode_arrays(
            ql, qr, pool, tables, lengths, 0.2, 1, interpret=True,
            walk=mla_attention.decode_walk(lengths, 4 * W, bs))


# -- the expert layer ----------------------------------------------------------

def _expert_layer(seed=3, T=24, H=16, M=8, E=8, k=3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.5,   # noqa: E731
                               jnp.float32)
    return dict(x=f(T, H), rw=f(H, E), rb=f(E) * 0.2, wg=f(E, H, M),
                wu=f(E, H, M), wd=f(E, M, H), T=T, E=E, k=k)


def _per_token_loop(d, gates, idx, held):
    """The routed sum one token and one chosen expert at a time."""
    x, out = np.asarray(d["x"]), np.zeros_like(np.asarray(d["x"]))
    for t in range(d["T"]):
        for r in range(d["k"]):
            e = int(idx[t, r])
            if e not in held:
                continue
            g = x[t] @ np.asarray(d["wg"][e])
            h = g / (1 + np.exp(-g)) * (x[t] @ np.asarray(d["wu"][e]))
            out[t] += float(gates[t, r]) * (h @ np.asarray(d["wd"][e]))
    return out


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The sizing guide's test: the routed parts that the shares give
    add up to what the whole layer gives (the shared expert, which every
    chip computes alike, is outside ``moe_ffn_held`` and counted once by
    the model)."""
    d = _expert_layer()
    gates, idx = moe_route_sigmoid(d["rw"], d["rb"], d["x"], top_k=d["k"],
                                   scale=2.5)
    whole, counts, held, reads, tiles = moe_ffn_held(
        d["wg"], d["wu"], d["wd"], d["x"], gates, idx, n_experts=8,
        expert_offset=0, n_held=8)
    assert int(held) == d["T"] * d["k"] == int(counts.sum())
    assert int(tiles) == 0                  # off the TPU: no kernel ran
    parts, rows = 0.0, 0
    for share in range(4):
        lo = 2 * share
        y, c, h, r, _ = moe_ffn_held(
            d["wg"][lo:lo + 2], d["wu"][lo:lo + 2], d["wd"][lo:lo + 2],
            d["x"], gates, idx, n_experts=8, expert_offset=lo, n_held=2)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
        parts, rows = parts + y, rows + int(h)
    assert rows == int(held)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(whole),
        _per_token_loop(d, np.asarray(gates), np.asarray(idx), range(8)),
        atol=1e-5)


def test_the_models_share_is_its_experts_part_plus_the_shared_expert():
    """Through the model: four configurations that hold experts [0, 2),
    [2, 4), ... of the same weights; their expert layers' outputs less
    the residual and the shared expert's part, which each computes
    alike, add up to the uncut layer's."""
    from paddle_tpu.models import mla

    whole_cfg = mla_tiny(n_layers=2)
    params = mla_init(whole_cfg, 5, std=0.3)
    p = jax.tree_util.tree_map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.key(0), (20, whole_cfg.hidden))
    z = mla._rms(x, p["ln2"], whole_cfg.rms_eps)
    shared = mla._gated_mlp(jnp.float32, z, p["s_gate"], p["s_up"],
                            p["s_down"])
    whole, _ = mla._moe_ffn(whole_cfg, p, mla._expert_weights(params), 0, x)
    routed = 0.0
    for lo in range(0, 8, 2):
        cfg = mla_tiny(n_layers=2, experts_held=2, expert_offset=lo)
        part = {"moe": {k: (v[:, lo:lo + 2] if k in ("w_gate", "w_up",
                                                     "w_down") else v)
                        for k, v in params["moe"].items()}}
        y, _ = mla._moe_ffn(cfg, p, mla._expert_weights(part), 0, x)
        routed = routed + (y - x - shared)
    np.testing.assert_allclose(np.asarray(x + routed + shared),
                               np.asarray(whole), atol=2e-5)


def test_grouped_ffn_with_an_empty_and_a_full_expert_and_stacked_layers():
    """Against the per-token loop: expert 5 gets every token, expert 1
    none; the held experts sit at groups [4, 8) of a stack of three
    layers' experts, addressed in place."""
    d = _expert_layer(seed=4, k=2)
    T = d["T"]
    idx = np.stack([np.full(T, 5), np.where(np.arange(T) % 2, 4, 7)], 1)
    gates = np.random.default_rng(0).random((T, 2)).astype(np.float32)
    stack = lambda w: jnp.concatenate([w[:4] * 0 + 9.0, w[4:], w[:4]])  # noqa
    y, counts, held, reads, _ = moe_ffn_held(
        stack(d["wg"]), stack(d["wu"]), stack(d["wd"]), d["x"],
        jnp.asarray(gates), jnp.asarray(idx, jnp.int32), n_experts=8,
        expert_offset=4, n_held=4, group_base=jnp.int32(4))
    assert list(np.asarray(counts)) == [0, 0, 0, 0, T // 2, T, 0, T // 2]
    assert int(held) == 2 * T and int(reads) == 3
    np.testing.assert_allclose(
        np.asarray(y), _per_token_loop(d, gates, idx, range(4, 8)),
        atol=1e-5)
    # tokens left out (lanes with no request) give nothing and read less
    live = jnp.asarray(np.arange(T) % 2 == 0)
    y2, c2, h2, r2, _ = moe_ffn_held(
        d["wg"], d["wu"], d["wd"], d["x"], jnp.asarray(gates),
        jnp.asarray(idx, jnp.int32), n_experts=8, expert_offset=0, n_held=8,
        live=live)
    assert int(h2) == T and int(r2) == 2          # 5 and 7: even tokens
    assert float(jnp.abs(y2[1::2]).max()) == 0.0


def test_the_bias_changes_the_choice_and_not_the_gates():
    d = _expert_layer(seed=6)
    plain_g, plain_i = moe_route_sigmoid(d["rw"], d["rb"] * 0, d["x"],
                                         top_k=3, scale=2.5)
    bias = jnp.zeros(8).at[6].set(10.0)           # expert 6 always chosen
    g, i = moe_route_sigmoid(d["rw"], bias, d["x"], top_k=3, scale=2.5)
    assert bool(jnp.all(jnp.any(i == 6, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain_i == 6, axis=-1)))
    s = np.asarray(jax.nn.sigmoid(d["x"] @ d["rw"]))
    chosen = np.take_along_axis(s, np.asarray(i), -1)
    # gates are the UNBIASED scores of the chosen, normalised and scaled
    np.testing.assert_allclose(
        np.asarray(g), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 2.5, rtol=1e-5)
    # ties go to the lower index
    _, tie = moe_route_sigmoid(jnp.zeros((16, 8)), jnp.zeros(8), d["x"][:4],
                               top_k=3, scale=1.0)
    assert np.asarray(tie).tolist() == [[0, 1, 2]] * 4


# -- rotary positions by hand --------------------------------------------------

def test_yarn_frequencies_and_mscale_against_numbers_worked_by_hand():
    """dim 64, base 10000, factor 40 over 4096, beta 32 / 1: the ramp
    runs from dimension floor(10.47) = 10 to ceil(22.51) = 23."""
    inv = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    assert inv.shape == (32,)
    by_hand = {0: 1.0, 10: 0.0562341, 16: 0.01 * (6 / 13 / 40 + 7 / 13),
               23: 0.00133352 / 40, 31: 0.000133352 / 40}
    for i, want in by_hand.items():
        assert inv[i] == pytest.approx(want, rel=1e-4), i
    ref = FAMILY.reference.yarn_inv_freq(dict(
        qk_rope_dim=64, rope_theta=10000.0, rope_factor=40.0,
        rope_orig_len=4096, rope_beta_fast=32.0, rope_beta_slow=1.0))
    np.testing.assert_allclose(inv, ref, rtol=1e-12)
    assert yarn_mscale(40.0, 1.0) == pytest.approx(1.3688879, rel=1e-6)
    cfg = sarvam_105b()
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * 1.3688879 ** 2, rel=1e-6)
    assert cfg.softmax_scale == pytest.approx(0.135234, rel=1e-5)


def test_the_preset_is_the_published_model_and_takes_the_cut():
    cfg = sarvam_105b()
    assert (cfg.hidden, cfg.n_layers, cfg.n_heads, cfg.vocab_size) == \
        (4096, 32, 64, 262144)
    assert (cfg.cache_row, cfg.pool_row) == (576, 640)
    cut = sarvam_105b(n_layers=6, experts_held=32, expert_offset=32,
                      vocab_size=65536, seq_len=16384)
    (spec,) = cut.serving_model().pool_spec(cut, 6801, 64)
    assert spec.shape == (6801, 6, 64, 640) and spec.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="are not among 128"):
        sarvam_105b(experts_held=32, expert_offset=100)


# -- the engine ----------------------------------------------------------------

def _engine(cfg, params, **kw):
    base = dict(n_slots=2, block_size=8, n_blocks=24,
                prefill_chunk=16, prefix_cache=False)
    base.update(kw)
    return InferenceEngine(cfg, params, **base)


@pytest.mark.parametrize("kw, sentence", [
    (dict(draft=(gpt_tiny(), None)), "cannot take draft= yet"),
    (dict(prefix_cache=True), "cannot use prefix_cache yet"),
    (dict(int8_weights=True), "cannot take int8_weights yet"),
    (dict(mesh="any"), "cannot take mesh= yet"),
])
def test_the_engine_refuses_what_the_model_cannot_do(tiny, kw, sentence):
    cfg, _, params = tiny
    with pytest.raises(ValueError, match=sentence):
        _engine(cfg, params, **kw)


def _serving_report(events):
    """``tools/trace_report.py --section serving`` over ``events``."""
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.devnull, "w") as sink:
        return mod.serving_report(mod.aggregate(events), file=sink,
                                  events=events)


def test_the_engine_serves_it_and_counts_a_routing_made_by_hand(tiny):
    """Greedy tokens through submit / chunked prefill / paged decode
    equal the full forward's; the three counters and the span arguments
    count exactly what the router did."""
    cfg, sizes, params = tiny
    before = {k: stats.stat_get(k) for k in (
        "moe_assignments_routed", "moe_assignments_held",
        "moe_expert_reads", "moe_kernel_tiles", "moe_tokens_dropped")}
    prompt = np.random.default_rng(7).integers(0, 256, 16).astype(np.int32)
    eng = _engine(cfg, params)
    monitor.start_tracing()
    try:
        out = eng.submit(prompt, max_new_tokens=4,
                         temperature=0.0).result(timeout=300)
    finally:
        events = monitor.stop_tracing().events()
        eng.shutdown()
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    lg, _ = mla_forward(cfg, params, jnp.asarray(seq[None]))
    assert out == np.asarray(jnp.argmax(lg[0], -1))[15:19].tolist()
    # by hand: one chunk of 16 tokens, then 3 ticks of 1 live token, each
    # routed top-2 in 2 expert layers
    got = {k: stats.stat_get(k) - v for k, v in before.items()}
    assert got["moe_assignments_routed"] == (16 + 3) * 2 * 2
    assert got["moe_tokens_dropped"] == 0
    held = reads = 0
    for start, n in ((0, 16), (16, 1), (17, 1), (18, 1)):
        _, (c, h, r, _) = mla_forward(cfg, params,
                                      jnp.asarray(seq[None, :start + n]))
        _, (c0, h0, r0, _) = mla_forward(cfg, params,
                                         jnp.asarray(seq[None, :start])) \
            if start else (None, (0, 0, 0, 0))
        held += int(h) - int(h0)
    assert got["moe_assignments_held"] == held
    spans = [e for e in events if e.get("ph") == "X" and e["name"] in (
        "serving.decode_step", "serving.prefill_chunk")]
    assert len(spans) == 4
    assert sum(e["args"]["moe_assignments_routed"] for e in spans) == 76
    assert sum(e["args"]["moe_assignments_held"] for e in spans) == held
    assert sum(e["args"]["moe_expert_reads"] for e in spans) \
        == got["moe_expert_reads"] > 0
    # off the TPU the grouped kernel does not run, and says so
    assert got["moe_kernel_tiles"] == 0
    assert all(e["args"]["moe_kernel_tiles"] == 0 for e in spans)
    report = _serving_report(events)
    assert report["chunk_moe_expert_reads"] \
        + report["decode_moe_expert_reads"] == got["moe_expert_reads"]
    assert report["chunk_moe_kernel_tiles"] == 0 \
        == report["decode_moe_kernel_tiles"] == report["chunk_moe_reread"]


def test_a_gpt_engine_is_what_it_was():
    """The pool's two arrays and their shapes, the jitted functions'
    names (the trace's metrics find ``_decode_paged_fn`` and
    ``_chunk_fn`` by them) and their (params, kb, vb, ...) signatures
    with both pool arrays donated."""
    cfg = gpt_tiny(seq_len=64, param_dtype=jnp.float32)
    eng = _engine(cfg, gpt_init(cfg, 0), n_blocks=9)
    try:
        assert isinstance(cfg, GPTConfig) and not isinstance(cfg, MLAConfig)
        shape = (9, cfg.n_layers, cfg.n_heads, 8, cfg.head_dim)
        assert [a.shape for a in eng.cache.pool] == [shape, shape]
        assert eng.cache.kb is eng.cache.pool[0]
        assert eng.cache.vb is eng.cache.pool[1]
        i32 = np.zeros(2, np.int32)
        dec = eng._program("decode", 4)[0].lower(
            eng._decode_params, eng.cache.kb, eng.cache.vb,
            np.zeros((2, 4), np.int32), i32, i32, eng._prev_toks,
            np.zeros(2, bool), eng._base_key, i32, i32,
            np.zeros(2, np.float32), i32, np.ones(2, np.float32),
            eng._mask_dev)
        chk = eng._program("chunk", 16, 4)[0].lower(
            eng._params, eng.cache.kb, eng.cache.vb, np.zeros(4, np.int32),
            np.zeros((1, 16), np.int32), np.int32(0), np.int32(16))
        for low, name, n_out in ((dec, "jit__decode_paged_fn_w4", 3),
                                 (chk, "jit__chunk_fn_c16_w4", 3)):
            text = low.as_text()
            assert f"module @{name} " in text
            donated = [a.donated for a in jax.tree_util.tree_leaves(
                low.args_info[0][1:3])]
            assert donated == [True, True]
            assert len(jax.tree_util.tree_leaves(low.out_info)) == n_out
    finally:
        eng.shutdown()


def test_the_programs_carry_the_router_and_experts_scopes(tiny):
    cfg, _, params = tiny
    eng = _engine(cfg, params)
    try:
        i32 = np.zeros(2, np.int32)
        low = eng._program("decode", 4)[0].lower(
            eng._decode_params, *eng.cache.pool, np.zeros((2, 4), np.int32),
            i32, i32, eng._prev_toks, np.zeros(2, bool), eng._base_key, i32,
            i32, np.zeros(2, np.float32), i32, np.ones(2, np.float32),
            eng._mask_dev)
        assert "module @jit__decode_paged_fn_w4 " in low.as_text()
        labels = set(trace.op_scopes(low.compile().as_text()).values())
        for scope in ("router", "experts", "attn", "kv_pool", "mlp", "head",
                      "embed", "sampling", "ln"):
            assert "forward/" + scope in labels, (scope, labels)
    finally:
        eng.shutdown()


@pytest.mark.kernels
def test_the_decode_step_walks_live_lanes_only_and_builds_the_walk_once(
        tiny, monkeypatch):
    """``mla_decode_step_paged`` through both kernels, attention and
    the row writer (interpret mode), against the composed path, dead
    lanes between live ones; the two work-lists' running sums over the
    lanes stand once each in the program, outside the scan over the
    expert layers."""
    import functools

    from paddle_tpu.models import mla as mla_model

    cfg, sizes, params = tiny
    rng = np.random.default_rng(7)
    cache = PagedKVCache(cfg, n_slots=5, n_blocks=12, block_size=8)
    assert cache.grow(1, 22) and cache.grow(3, 9)
    pool = tuple(jnp.asarray(rng.standard_normal(a.shape), a.dtype)
                 for a in cache.pool)
    tables = jnp.asarray(cache.tables_array([1, 3])[:, :4])
    pos = jnp.asarray([0, 21, 0, 8, 0], jnp.int32)
    toks = jnp.asarray([0, 7, 0, 11, 0], jnp.int32)
    want = mla_decode_step_paged(cfg, params, pool, tables, pos, toks)
    monkeypatch.setattr(mla_model, "mla_decode_arrays", functools.partial(
        mla_attention.mla_decode_arrays, interpret=True))
    monkeypatch.setattr(mla_model, "pool_write_rows", functools.partial(
        mla_model.pool_write_rows, interpret=True))
    step = functools.partial(mla_decode_step_paged, cfg)
    got = jax.jit(step)(params, pool, tables, pos, toks)
    live = np.asarray([1, 3])
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live], atol=5e-4)
    assert np.isfinite(np.asarray(got[0])).all()
    np.testing.assert_allclose(np.asarray(got[1][0])[1:],
                               np.asarray(want[1][0])[1:], atol=1e-5)
    # the writer's kernel gives a lane with no request no step: the sink
    # block is as it was
    np.testing.assert_array_equal(np.asarray(got[1][0])[0],
                                  np.asarray(pool[0])[0])

    def builds(jaxpr, in_scan=False):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "cumsum" \
                    and eqn.outvars[0].aval.shape == (5,) \
                    and eqn.outvars[0].aval.dtype == jnp.int32:
                yield in_scan
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from builds(sub, in_scan
                                  or eqn.primitive.name == "scan")

    jaxpr = jax.make_jaxpr(step)(params, pool, tables, pos, toks)
    # two lists: the kernel's live blocks, the row writer's live lanes
    assert list(builds(jaxpr.jaxpr)) == [False, False]
