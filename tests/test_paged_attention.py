"""Pallas paged-attention decode kernel (ISSUE 7): interpret-mode parity
vs the composed jnp reference, block-table gather correctness vs plain
contiguous attention, garbage-sink/zero-length safety, fallback routing,
and model-level agreement between the paged and contiguous decode steps.
Registered under the ``-m kernels`` marker with the other Pallas parity
suites."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.flash_attention import _attention_reference
from paddle_tpu.ops.paged_attention import (_paged_attention_reference,
                                            _paged_decode,
                                            paged_attention_arrays)
from paddle_tpu.ops.pool_write import (live_lanes, pool_write_rows,
                                       write_rows_composed)

pytestmark = pytest.mark.kernels

RNG = np.random.default_rng(0)


def _pool(nb, nh, bs, hd, dtype=jnp.float32):
    kb = jnp.asarray(RNG.normal(size=(nb, nh, bs, hd)), dtype)
    vb = jnp.asarray(RNG.normal(size=(nb, nh, bs, hd)), dtype)
    return kb, vb


def _tables(rows, W):
    out = np.zeros((len(rows), W), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return jnp.asarray(out)


class TestPagedReference:
    def test_matches_contiguous_attention(self):
        """Gathering blocks in table order must equal plain attention
        over the contiguous K/V those blocks hold."""
        nh, hd, bs, W = 4, 16, 8, 4
        kb, vb = _pool(10, nh, bs, hd)
        tables = _tables([[3, 7, 1, 9]], W)
        length = 27
        q = jnp.asarray(RNG.normal(size=(1, nh, hd)), jnp.float32)
        k = kb[tables[0]].transpose(1, 0, 2, 3).reshape(nh, W * bs, hd)
        v = vb[tables[0]].transpose(1, 0, 2, 3).reshape(nh, W * bs, hd)
        want = _attention_reference(q[:, :, None], k[None, :, :length],
                                    v[None, :, :length], causal=False,
                                    scale=0.25)[:, :, 0]
        got = _paged_attention_reference(q, kb, vb, tables,
                                         jnp.asarray([length]), 0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestKernelParity:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                           (jnp.bfloat16, 2e-2)])
    def test_interpret_parity(self, dtype, tol):
        """The kernel (interpret mode on CPU) must reproduce the composed
        reference over mixed-depth slots and sink-padded tables."""
        nh, hd, bs, W, nb, B = 8, 64, 16, 4, 12, 3
        kb, vb = _pool(nb, nh, bs, hd, dtype)
        q = jnp.asarray(RNG.normal(size=(B, nh, hd)), dtype)
        tables = _tables([[5, 2, 9], [1, 7, 3, 11], [4]], W)
        lengths = jnp.asarray([37, 64, 1], jnp.int32)
        want = _paged_attention_reference(q, kb, vb, tables, lengths,
                                          0.125)
        got = _paged_decode(q, kb, vb, tables, lengths, 0.125,
                            interpret=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    def test_single_block_and_partial_length(self):
        nh, hd, bs = 8, 64, 16
        kb, vb = _pool(4, nh, bs, hd)
        q = jnp.asarray(RNG.normal(size=(1, nh, hd)), jnp.float32)
        tables = _tables([[2]], 1)
        for length in (1, 7, 16):
            want = _paged_attention_reference(
                q, kb, vb, tables, jnp.asarray([length]), 0.125)
            got = _paged_decode(q, kb, vb, tables,
                                jnp.asarray([length], jnp.int32), 0.125,
                                interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-6, atol=2e-6)

    def test_zero_length_slot_is_finite(self):
        """Unoccupied batch lanes (length 0, all-sink table) must come
        back finite, never NaN — the engine discards them host-side."""
        nh, hd, bs = 8, 64, 16
        kb, vb = _pool(4, nh, bs, hd)
        q = jnp.asarray(RNG.normal(size=(2, nh, hd)), jnp.float32)
        tables = _tables([[], [1, 2]], 2)
        lengths = jnp.asarray([0, 20], jnp.int32)
        got = _paged_decode(q, kb, vb, tables, lengths, 0.125,
                            interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        want = _paged_attention_reference(q, kb, vb, tables, lengths, 0.125)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=2e-6, atol=2e-6)

    def test_entry_routes_to_reference_off_tpu(self):
        """The routed entry must be the composed reference bit-for-bit on
        CPU (the fallback contract every caller relies on), including
        gpt_tiny's untileable head_dim."""
        for nh, hd in ((8, 64), (4, 16)):
            kb, vb = _pool(6, nh, 8, hd)
            q = jnp.asarray(RNG.normal(size=(1, nh, hd)), jnp.float32)
            tables = _tables([[1, 4]], 3)
            lengths = jnp.asarray([11], jnp.int32)
            want = _paged_attention_reference(q, kb, vb, tables, lengths,
                                              1.0 / np.sqrt(hd))
            got = paged_attention_arrays(q, kb, vb, tables, lengths)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _loop_steps(lengths, W, bs, G):
    """The work-list by a plain loop: (slot, first column, first?, last?)
    for every group of G live blocks, slot by slot in table order."""
    steps = []
    for b, ln in enumerate(lengths):
        blocks = min(max(-(-int(ln) // bs), 0), W)
        for c in range(0, blocks, G):
            steps.append((b, c, int(c == 0), int(c + G >= blocks)))
    return steps


class TestLiveWalk:
    """ops/block_walk.live_steps, the grid of both table-walking decode
    kernels: live blocks only, G a step."""

    BS = 4

    @staticmethod
    def _lengths(W, G, bs):
        """Slots that are empty, hold one token, end on a block's or a
        step's edge, fall one short of or one past it, and fill the
        table; dead slots between live ones."""
        full = W * bs
        mix = [0, 1, bs, 0, min(G * bs, full), min(G * bs + 1, full),
               full, max(full - 1, 0), 1, min(2 * G * bs, full), 0]
        return [mix, [0] * 5, [full] * 3, [0, 0, 3, 0]]

    @pytest.mark.parametrize("G", [1, 4, 8])
    @pytest.mark.parametrize("W", [1, 2, 8, 32])
    def test_matches_the_loop(self, W, G):
        from paddle_tpu.ops.block_walk import live_steps

        bs = self.BS
        for lengths in self._lengths(W, G, bs):
            want = _loop_steps(lengths, W, bs, G)
            walk = jax.jit(live_steps, static_argnums=(1, 2, 3))(
                jnp.asarray(lengths, jnp.int32), W, bs, G)
            slot, col, first, last = (np.asarray(a) for a in walk[:4])
            count = int(walk.count[0])
            N = len(lengths) * -(-W // G)
            assert slot.shape == col.shape == first.shape == last.shape \
                == (N,) and slot.dtype == np.int32
            assert count == len(want)
            got = list(zip(slot[:count], col[:count], first[:count],
                           last[:count]))
            assert got == want
            # every live block is named exactly once, in table order,
            # and no step names a table's padding
            named = [(b, c + j) for b, c, _, _ in got for j in range(G)
                     if (c + j) * bs < lengths[b] and c + j < W]
            assert named == [(b, i) for b, ln in enumerate(lengths)
                             for i in range(min(-(-ln // bs), W))]
            # the tail names the last live step again and marks nothing
            if count:
                assert (slot[count:] == slot[count - 1]).all()
                assert (col[count:] == col[count - 1]).all()
            assert not first[count:].any() and not last[count:].any()

    def test_a_length_past_the_table_is_the_full_table(self):
        from paddle_tpu.ops.block_walk import live_steps

        walk = live_steps(jnp.asarray([10 ** 6, -3]), 8, 4, 4)
        assert int(walk.count[0]) == 2
        assert np.asarray(walk.col)[:2].tolist() == [0, 4]


class TestLiveWalkKernel:
    """The kernel on the live walk (interpret mode) against the composed
    reference: dead slots between live ones, lengths on every edge, a
    traced layer, G from 1 to past the table width."""

    @pytest.mark.parametrize("W,G", [(1, 1), (2, 4), (8, 4), (8, 8),
                                     (32, 8)])
    def test_matches_reference(self, W, G):
        L, nh, hd, bs, B = 3, 4, 64, 8, 6
        nb = B * W + 1
        kb = jnp.asarray(RNG.normal(size=(nb, L, nh, bs, hd)), jnp.float32)
        vb = jnp.asarray(RNG.normal(size=(nb, L, nh, bs, hd)), jnp.float32)
        q = jnp.asarray(RNG.normal(size=(B, nh, hd)), jnp.float32)
        full = W * bs
        lens = [0, min(G * bs, full), 1, 0, full, max(full - 3, 1)]
        live = np.arange(W)[None, :] * bs < np.asarray(lens)[:, None]
        tables = jnp.asarray(np.where(
            live, 1 + RNG.permutation(B * W).reshape(B, W), 0), jnp.int32)
        lengths = jnp.asarray(lens, jnp.int32)
        want = _paged_attention_reference(q, kb[:, 1], vb[:, 1], tables,
                                          lengths, 0.125)
        got = jax.jit(lambda li: _paged_decode(
            q, kb, vb, tables, lengths, 0.125, interpret=True, layer=li,
            group=G))(jnp.int32(1))
        rows = np.asarray(lens) > 0
        np.testing.assert_allclose(np.asarray(got)[rows],
                                   np.asarray(want)[rows],
                                   rtol=2e-6, atol=2e-6)
        # a slot with no token costs no step: its row is zeros
        assert not np.asarray(got)[~rows].any()

    def test_a_walk_handed_in_is_the_walk_built_inside(self):
        """The model builds ``decode_walk`` once a tick and hands it to
        every layer's call; a walk of another shape is refused."""
        from paddle_tpu.ops.paged_attention import decode_walk

        nh, hd, bs, W, B = 4, 64, 8, 8, 3
        kb, vb = _pool(B * W + 1, nh, bs, hd)
        q = jnp.asarray(RNG.normal(size=(B, nh, hd)), jnp.float32)
        tables = jnp.asarray(1 + np.arange(B * W).reshape(B, W), jnp.int32)
        lengths = jnp.asarray([0, 37, 64], jnp.int32)
        inside = paged_attention_arrays(q, kb, vb, tables, lengths,
                                        interpret=True)
        handed = paged_attention_arrays(
            q, kb, vb, tables, lengths, interpret=True,
            walk=decode_walk(lengths, W, bs))
        np.testing.assert_array_equal(np.asarray(inside),
                                      np.asarray(handed))
        with pytest.raises(ValueError, match="decode_walk"):
            paged_attention_arrays(q, kb, vb, tables, lengths,
                                   interpret=True,
                                   walk=decode_walk(lengths, 4 * W, bs))


class TestPagedDecodeStep:
    def test_paged_decode_step_matches_contiguous(self):
        """gpt_decode_step_paged over a chunk-prefilled block pool must
        match gpt_decode_step over the contiguous cache, logits-exact to
        fp tolerance."""
        from paddle_tpu.models import (gpt_decode_step,
                                       gpt_decode_step_paged, gpt_init,
                                       gpt_prefill, gpt_prefill_chunk,
                                       gpt_tiny)
        from paddle_tpu.serving import KVCache, PagedKVCache, cache_insert

        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64)
        params = gpt_init(cfg, seed=3)
        prompt = RNG.integers(0, cfg.vocab_size, 9).astype(np.int32)
        S = prompt.size

        # contiguous: whole-prompt prefill + one decode step
        logits, (ke, ve) = gpt_prefill(cfg, params, jnp.asarray(prompt[None]))
        cache = KVCache(cfg, n_slots=2)
        k, v = cache_insert(cache.k, cache.v, 0, ke[0], ve[0])
        tok = int(jnp.argmax(logits[0, S - 1]))
        want, _ = gpt_decode_step(
            cfg, params, (k, v), jnp.asarray([S, 0], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32))

        # paged: chunked prefill into the block pool + one paged step
        paged = PagedKVCache(cfg, n_slots=2, block_size=8)
        assert paged.grow(0, 16)
        row = jnp.asarray(paged.table_row(0))
        toks = np.zeros((1, 16), np.int32)
        toks[0, :S] = prompt
        lg, (kb, vb) = gpt_prefill_chunk(
            cfg, params, (paged.kb, paged.vb), row, jnp.asarray(toks),
            jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lg[0, :S]),
                                   np.asarray(logits[0]),
                                   rtol=2e-5, atol=2e-5)
        tables = jnp.asarray(paged.tables_array([0]))
        got, _ = gpt_decode_step_paged(
            cfg, params, (kb, vb), tables, jnp.asarray([S, 0], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=2e-4, atol=2e-4)


class TestPoolWriteRows:
    """The decode tick's row writer (``ops/pool_write.py``): the kernel
    in interpret mode against the composed loop of ``pool_put``, bit for
    bit, and a lane with no request writes nothing."""

    POOLS = {
        # (n_blocks, L, *block), the dtype, arrays in the pool
        "heads_f32": ((7, 3, 4, 8, 32), jnp.float32, 2),
        "heads_bf16": ((7, 3, 4, 16, 128), jnp.bfloat16, 2),
        "latent": ((7, 2, 64, 640), jnp.bfloat16, 1),
    }
    LIVE = {"all": [1, 1, 1, 1, 1], "one": [0, 0, 1, 0, 0],
            "none": [0, 0, 0, 0, 0], "some": [0, 1, 1, 0, 1]}

    @pytest.mark.parametrize("first_off", [0, 5, -1])
    @pytest.mark.parametrize("live", sorted(LIVE))
    @pytest.mark.parametrize("pool", sorted(POOLS))
    def test_kernel_matches_the_composed_loop(self, pool, live, first_off):
        shape, dtype, n_arrays = self.POOLS[pool]
        bs, L = shape[-2], shape[1]
        alive = np.asarray(self.LIVE[live], bool)
        B = alive.shape[0]
        pools = tuple(jnp.asarray(RNG.normal(size=shape), dtype)
                      for _ in range(n_arrays))
        rows = tuple(jnp.asarray(
            RNG.normal(size=(B,) + shape[2:-2] + shape[-1:]), dtype)
            for _ in range(n_arrays))
        # a live lane owns a block of its own; a lane with no request
        # names the sink, as its table row does. Offsets step through
        # the block from 0, an odd row or the last one
        blk = np.where(alive, 1 + np.arange(B), 0).astype(np.int32)
        off = ((first_off % bs + 3 * np.arange(B)) % bs).astype(np.int32)
        lanes = live_lanes(jnp.asarray(np.where(alive, 1 + off, 0)))
        assert int(lanes.count[0]) == alive.sum()
        assert list(np.asarray(lanes.lane)[:alive.sum()]) == \
            list(np.flatnonzero(alive))
        layer = L - 1
        got = jax.jit(lambda li: pool_write_rows(
            pools, rows, blk, off, li, lanes=lanes, interpret=True))(
                jnp.int32(layer))                 # a traced layer index
        want = write_rows_composed(pools, rows, jnp.asarray(blk),
                                   jnp.asarray(off), layer)
        for g, w, was, row in zip(got, want, pools, rows):
            assert g.dtype == was.dtype and g.shape == was.shape
            g, w, was = np.asarray(g), np.asarray(w), np.asarray(was)
            # the composed loop writes a dead lane's row into the sink
            np.testing.assert_array_equal(g[1:], w[1:])
            # the kernel: the live lanes' rows and nothing else, the
            # sink included
            only = was.copy()
            for b in np.flatnonzero(alive):
                only[blk[b], layer, ..., off[b], :] = np.asarray(row)[b]
            np.testing.assert_array_equal(g, only)

    def test_entry_routes_to_the_composed_loop_off_tpu(self):
        shape, dtype, _ = self.POOLS["heads_f32"]
        pools = tuple(jnp.asarray(RNG.normal(size=shape), dtype)
                      for _ in range(2))
        rows = tuple(jnp.asarray(RNG.normal(size=(3, 4, 32)), dtype)
                     for _ in range(2))
        blk, off = jnp.asarray([2, 0, 5]), jnp.asarray([7, 1, 0])
        got = pool_write_rows(pools, rows, blk, off, 1,
                              lanes=live_lanes(jnp.asarray([8, 0, 1])))
        want = write_rows_composed(pools, rows, blk, off, 1)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestPoolInPlace:
    """The paged steps address the WHOLE 5-D pool by (block, layer): the
    kernel entry reads a layer's blocks straight out of it, and no paged
    model step cuts a layer's slab out of the pool, copies it or puts it
    back."""

    @pytest.mark.parametrize("layer", [0, 2, 4])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                           (jnp.bfloat16, 2e-2)])
    def test_layer_entry_matches_reference_on_the_slice(self, dtype, tol,
                                                        layer):
        """The 5-D ``layer=`` entry (interpret mode) against the composed
        reference on that layer's slice, traced layer index as the
        model's scan passes it."""
        L, nh, hd, bs, W, nb, B = 5, 8, 64, 16, 4, 12, 3
        kb = jnp.asarray(RNG.normal(size=(nb, L, nh, bs, hd)), dtype)
        vb = jnp.asarray(RNG.normal(size=(nb, L, nh, bs, hd)), dtype)
        q = jnp.asarray(RNG.normal(size=(B, nh, hd)), dtype)
        tables = _tables([[5, 2, 9], [1, 7, 3, 11], [4]], W)
        lengths = jnp.asarray([37, 64, 1], jnp.int32)
        want = _paged_attention_reference(q, kb[:, layer], vb[:, layer],
                                          tables, lengths, 0.125)
        got = jax.jit(lambda li: paged_attention_arrays(
            q, kb, vb, tables, lengths, scale=0.125, interpret=True,
            layer=li))(jnp.int32(layer))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)
        # off-TPU routing: the composed gather at (block, layer) is the
        # reference on the slice, bit for bit
        routed = paged_attention_arrays(q, kb, vb, tables, lengths,
                                        scale=0.125, layer=layer)
        np.testing.assert_array_equal(np.asarray(routed), np.asarray(want))

    @staticmethod
    def _walk(jaxpr, in_scan=False):
        """(equation, inside a scan body?) for every equation of a jaxpr
        and of every jaxpr nested in it."""
        for eqn in jaxpr.eqns:
            yield eqn, in_scan
            inner = in_scan or eqn.primitive.name == "scan"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from TestPoolInPlace._walk(sub, inner)

    @pytest.mark.parametrize("step", ["decode", "decode_kernel", "chunk",
                                      "verify"])
    def test_no_step_materialises_a_slab(self, step, monkeypatch):
        """Structure of the paged programs: inside the layer scan no
        equation yields an array of the slab's shape
        (n_blocks, nh, bs, hd), and the only pool-shaped values are the
        carry and the in-place writes into it: the composed path's
        ``dynamic_update_slice`` (with the pin that keeps the pool's
        layout) or, where the decode step's row writer runs its kernel
        (``decode_kernel``: what a TPU runs), that one call a layer."""
        from paddle_tpu.models import (gpt_decode_step_paged, gpt_init,
                                       gpt_prefill_chunk, gpt_tiny,
                                       gpt_verify_step_paged)
        from paddle_tpu.models import gpt as gpt_model
        from paddle_tpu.serving import PagedKVCache

        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64)
        params = gpt_init(cfg, seed=0)
        # 7 blocks: no other array of the programs has the slab's shape
        cache = PagedKVCache(cfg, n_slots=2, block_size=8, n_blocks=7)
        pool = (cache.kb, cache.vb)
        tables = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.asarray([9, 0], jnp.int32)
        if step == "decode_kernel":
            monkeypatch.setattr(gpt_model, "pool_write_rows",
                                functools.partial(pool_write_rows,
                                                  interpret=True))
        if step.startswith("decode"):
            jaxpr = jax.make_jaxpr(
                lambda p, kv: gpt_decode_step_paged(
                    cfg, p, kv, tables, pos, jnp.asarray([1, 2])))(
                        params, pool)
        elif step == "chunk":
            jaxpr = jax.make_jaxpr(
                lambda p, kv: gpt_prefill_chunk(
                    cfg, p, kv, tables[0], jnp.zeros((1, 16), jnp.int32),
                    jnp.int32(8)))(params, pool)
        else:
            jaxpr = jax.make_jaxpr(
                lambda p, kv: gpt_verify_step_paged(
                    cfg, p, kv, tables, pos,
                    jnp.zeros((2, 3), jnp.int32)))(params, pool)
        pool_shape = tuple(cache.kb.shape)
        slab_shape = pool_shape[:1] + pool_shape[2:]
        writes = scans = 0
        for eqn, in_scan in self._walk(jaxpr.jaxpr):
            name = eqn.primitive.name
            scans += name == "scan"
            if name == "pallas_call":
                name = eqn.params["name"]
            for out in eqn.outvars:
                shape = tuple(getattr(out.aval, "shape", ()))
                assert shape != slab_shape, (
                    f"{step}: {name} yields a layer's slab {shape}")
                if shape == pool_shape and in_scan:
                    assert name in ("dynamic_update_slice",
                                    "layout_constraint",
                                    "pool_write_rows"), (
                        f"{step}: {name} yields a pool-shaped value "
                        "inside the layer scan")
                    writes += name != "layout_constraint"
        assert scans == 1
        # pool-shaped values written a layer, K and V: one row a token
        # (decode 2, verify 2 x 3), one block a 8-token block of the
        # chunk (16 / 8), or the two outputs of the writer's one call
        assert writes == 2 * {"decode": 2, "decode_kernel": 1, "chunk": 2,
                              "verify": 6}[step]

    def test_moe_paged_steps_match_contiguous(self):
        """The unrolled MoE branches (a Python-int layer) write and read
        the pool in place like the scanned ones: chunked prefill into the
        pool and one paged decode step against the contiguous cache."""
        from paddle_tpu.models import (gpt_decode_step,
                                       gpt_decode_step_paged, gpt_init,
                                       gpt_prefill, gpt_prefill_chunk,
                                       gpt_tiny)
        from paddle_tpu.serving import KVCache, PagedKVCache, cache_insert

        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64, moe_experts=4,
                       moe_top_k=2, moe_every=2)
        assert cfg.moe_layer_ids == (1, 3)
        params = gpt_init(cfg, seed=3)
        prompt = RNG.integers(0, cfg.vocab_size, 9).astype(np.int32)
        S = prompt.size
        logits, (ke, ve) = gpt_prefill(cfg, params,
                                       jnp.asarray(prompt[None]))[:2]
        cache = KVCache(cfg, n_slots=2)
        k, v = cache_insert(cache.k, cache.v, 0, ke[0], ve[0])
        tok = int(jnp.argmax(logits[0, S - 1]))
        pos = jnp.asarray([S, 0], jnp.int32)
        toks1 = jnp.asarray([tok, 0], jnp.int32)
        want = gpt_decode_step(cfg, params, (k, v), pos, toks1)

        paged = PagedKVCache(cfg, n_slots=2, block_size=8)
        assert paged.grow(0, 16)
        row = jnp.asarray(paged.table_row(0))
        toks = np.zeros((1, 16), np.int32)
        toks[0, :S] = prompt
        lg, (kb, vb) = gpt_prefill_chunk(
            cfg, params, (paged.kb, paged.vb), row, jnp.asarray(toks),
            jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lg[0, :S]),
                                   np.asarray(logits[0]),
                                   rtol=2e-5, atol=2e-5)
        # the chunk wrote every layer's rows where the table says
        for li in range(cfg.n_layers):
            np.testing.assert_allclose(
                np.asarray(kb[row[0], li, :, :8]),
                np.asarray(ke[0, li, :, :8]), rtol=1e-6, atol=1e-6)
        got = gpt_decode_step_paged(
            cfg, params, (kb, vb), jnp.asarray(paged.tables_array([0])),
            pos, toks1)
        np.testing.assert_allclose(np.asarray(got[0][0]),
                                   np.asarray(want[0][0]),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(got[2][0]),
                                      np.asarray(want[2][0]))
        # the new token's row landed at (block of position 9, offset 1)
        np.testing.assert_allclose(
            np.asarray(got[1][0][row[1], :, :, 1]),
            np.asarray(want[1][0][0, :, :, S]), rtol=1e-5, atol=1e-5)


def walk_builds(jaxpr, n_slots):
    """(outside, inside) the layer scan: how many equations build a
    work-list (the attention kernel's live blocks, the row writer's live
    lanes), each told by its running sum over an int32 (n_slots,)."""
    found = [0, 0]
    for eqn, in_scan in TestPoolInPlace._walk(jaxpr.jaxpr):
        if eqn.primitive.name == "cumsum" \
                and eqn.outvars[0].aval.shape == (n_slots,) \
                and eqn.outvars[0].aval.dtype == jnp.int32:
            found[in_scan] += 1
    return tuple(found)


class TestWalkInTheModel:
    """``gpt_decode_step_paged`` builds the live walk and the writer's
    list of live lanes once a tick, hands them to the kernels at every
    layer, and gives a lane with no request length 0."""

    @staticmethod
    def _tick(moe=False):
        from paddle_tpu.models import gpt_init, gpt_tiny
        from paddle_tpu.serving import PagedKVCache

        kw = dict(moe_experts=4, moe_top_k=2, moe_every=2) if moe else {}
        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64, **kw)
        params = gpt_init(cfg, seed=5)
        cache = PagedKVCache(cfg, n_slots=5, block_size=8)
        # lanes 1 and 3 hold requests of 21 and 8 cached tokens; 0, 2, 4
        # hold none (table rows of the sink, position 0)
        assert cache.grow(1, 22) and cache.grow(3, 9)
        pool = tuple(jnp.asarray(RNG.normal(size=a.shape), a.dtype)
                     for a in (cache.kb, cache.vb))
        tables = jnp.asarray(cache.tables_array([1, 3])[:, :4])
        pos = jnp.asarray([0, 21, 0, 8, 0], jnp.int32)
        toks = jnp.asarray([0, 7, 0, 11, 0], jnp.int32)
        return cfg, params, pool, tables, pos, toks

    @pytest.mark.parametrize("moe", [False, True])
    def test_step_through_the_kernel_matches_the_composed_path(
            self, monkeypatch, moe):
        from paddle_tpu.models import gpt as gpt_model
        from paddle_tpu.models import gpt_decode_step_paged
        from paddle_tpu.ops import paged_attention as pa

        cfg, params, pool, tables, pos, toks = self._tick(moe)
        want = gpt_decode_step_paged(cfg, params, pool, tables, pos, toks)
        monkeypatch.setattr(pa, "paged_attention_arrays", functools.partial(
            pa.paged_attention_arrays, interpret=True))
        monkeypatch.setattr(gpt_model, "pool_write_rows", functools.partial(
            pool_write_rows, interpret=True))
        got = jax.jit(functools.partial(gpt_decode_step_paged, cfg))(
            params, pool, tables, pos, toks)
        live = np.asarray([1, 3])
        np.testing.assert_allclose(np.asarray(got[0])[live],
                                   np.asarray(want[0])[live],
                                   rtol=2e-4, atol=2e-4)
        assert np.isfinite(np.asarray(got[0])).all()
        for a, b, was in zip(got[1], want[1], pool):
            # rows of live lanes; in the composed path a dead lane
            # writes the sink block, in the writer's kernel nothing
            np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b)[1:],
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_array_equal(np.asarray(a)[0],
                                          np.asarray(was)[0])

    @pytest.mark.parametrize("moe", [False, True])
    def test_the_walk_is_built_once_a_tick(self, moe):
        """Two lists (live blocks, live lanes), both outside the layer
        loop."""
        from paddle_tpu.models import gpt_decode_step_paged

        cfg, params, pool, tables, pos, toks = self._tick(moe)
        jaxpr = jax.make_jaxpr(functools.partial(
            gpt_decode_step_paged, cfg))(params, pool, tables, pos, toks)
        assert walk_builds(jaxpr, 5) == (2, 0)
