"""Pallas paged-attention decode kernel (ISSUE 7): interpret-mode parity
vs the composed jnp reference, block-table gather correctness vs plain
contiguous attention, garbage-sink/zero-length safety, fallback routing,
and model-level agreement between the paged and contiguous decode steps.
Registered under the ``-m kernels`` marker with the other Pallas parity
suites."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.flash_attention import _attention_reference
from paddle_tpu.ops.paged_attention import (_paged_attention_reference,
                                            _paged_decode,
                                            paged_attention_arrays)

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


class TestRaggedDecode:
    """FLAGS_ragged_decode (ISSUE 17): the live-length-clamped K/V index
    map only changes WHICH blocks are DMA'd (dead iterations re-address
    the last live block, whose copy the pipeline elides) — the masked
    compute is untouched, so the output must be bit-identical."""

    def test_ragged_bit_identical_across_lengths(self):
        nh, hd, bs, W, nb, B = 8, 64, 16, 4, 20, 4
        kb, vb = _pool(nb, nh, bs, hd)
        q = jnp.asarray(RNG.normal(size=(B, nh, hd)), jnp.float32)
        tables = _tables([[5, 2, 9, 14], [1, 7, 3, 11], [4, 8, 6, 13],
                          [10, 15, 17, 19]], W)
        # the boundary lengths: 1 token, one-short-of-a-block, exactly
        # one block, and the full table
        lengths = jnp.asarray([1, bs - 1, bs, W * bs], jnp.int32)
        base = _paged_decode(q, kb, vb, tables, lengths, 0.125,
                             interpret=True, ragged=False)
        ragged = _paged_decode(q, kb, vb, tables, lengths, 0.125,
                               interpret=True, ragged=True)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(ragged))
        want = _paged_attention_reference(q, kb, vb, tables, lengths,
                                          0.125)
        np.testing.assert_allclose(np.asarray(ragged), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

    def test_zero_length_ragged_is_finite(self):
        nh, hd, bs = 8, 64, 16
        kb, vb = _pool(4, nh, bs, hd)
        q = jnp.asarray(RNG.normal(size=(2, nh, hd)), jnp.float32)
        tables = _tables([[], [1, 2]], 2)
        lengths = jnp.asarray([0, 20], jnp.int32)
        base = _paged_decode(q, kb, vb, tables, lengths, 0.125,
                             interpret=True, ragged=False)
        ragged = _paged_decode(q, kb, vb, tables, lengths, 0.125,
                               interpret=True, ragged=True)
        assert np.isfinite(np.asarray(ragged)).all()
        np.testing.assert_array_equal(np.asarray(base), np.asarray(ragged))

    def test_flag_routes_and_stays_identical(self):
        import paddle_tpu as paddle
        from paddle_tpu.ops import paged_attention as pa

        nh, hd, bs = 8, 64, 16
        kb, vb = _pool(6, nh, bs, hd)
        q = jnp.asarray(RNG.normal(size=(1, nh, hd)), jnp.float32)
        tables = _tables([[1, 4]], 3)
        lengths = jnp.asarray([19], jnp.int32)
        off = paged_attention_arrays(q, kb, vb, tables, lengths,
                                     interpret=True)
        paddle.set_flags({"FLAGS_ragged_decode": 1})
        try:
            assert pa._ragged[0]
            on = paged_attention_arrays(q, kb, vb, tables, lengths,
                                        interpret=True)
        finally:
            paddle.set_flags({"FLAGS_ragged_decode": 0})
        assert not pa._ragged[0]
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


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


class TestPoolInPlace:
    """The paged steps address the WHOLE 5-D pool by (block, layer): the
    kernel entry reads a layer's blocks straight out of it, and no paged
    model step cuts a layer's slab out of the pool, copies it or puts it
    back."""

    @pytest.mark.parametrize("layer", [0, 2, 4])
    @pytest.mark.parametrize("ragged", [False, True])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                           (jnp.bfloat16, 2e-2)])
    def test_layer_entry_matches_reference_on_the_slice(self, dtype, tol,
                                                        ragged, layer):
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
            ragged=ragged, layer=li))(jnp.int32(layer))
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

    @pytest.mark.parametrize("step", ["decode", "chunk", "verify"])
    def test_no_step_materialises_a_slab(self, step):
        """Structure of the three paged programs: inside the layer scan
        no equation yields an array of the slab's shape
        (n_blocks, nh, bs, hd), and the only pool-shaped values are the
        carry and the in-place writes into it (with the pin that keeps
        its layout)."""
        from paddle_tpu.models import (gpt_decode_step_paged, gpt_init,
                                       gpt_prefill_chunk, gpt_tiny,
                                       gpt_verify_step_paged)
        from paddle_tpu.serving import PagedKVCache

        cfg = gpt_tiny(dtype=jnp.float32, seq_len=64)
        params = gpt_init(cfg, seed=0)
        # 7 blocks: no other array of the programs has the slab's shape
        cache = PagedKVCache(cfg, n_slots=2, block_size=8, n_blocks=7)
        pool = (cache.kb, cache.vb)
        tables = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.asarray([9, 0], jnp.int32)
        if step == "decode":
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
            scans += eqn.primitive.name == "scan"
            for out in eqn.outvars:
                shape = tuple(getattr(out.aval, "shape", ()))
                assert shape != slab_shape, (
                    f"{step}: {eqn.primitive.name} yields a layer's slab "
                    f"{shape}")
                if shape == pool_shape and in_scan:
                    assert eqn.primitive.name in (
                        "dynamic_update_slice", "layout_constraint"), (
                        f"{step}: {eqn.primitive.name} yields a pool-"
                        "shaped value inside the layer scan")
                    writes += eqn.primitive.name == "dynamic_update_slice"
        assert scans == 1
        # K and V: one row a token (decode 2, verify 2 x 3), or one
        # block a 8-token block of the chunk (16 / 8)
        assert writes == 2 * {"decode": 2, "chunk": 2, "verify": 6}[step]

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
