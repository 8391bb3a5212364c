"""The held experts' grouped-FFN kernel (``ops/moe_gmm.py``) in the
interpreter against ``jax.lax.ragged_dot``'s path of ``nn/moe.py:
moe_ffn_held`` at tiny widths: empty groups, one row a group, one group
holding every row, a group larger than the row tile, a traced
``group_base`` into a stack of layers' experts, a ``live`` mask, a
float32 output from bf16 tokens; and the kernel's work-list (live tile
count, repeated tail, the weights' re-read factor)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.moe import moe_ffn_held
from paddle_tpu.ops import moe_gmm

pytestmark = pytest.mark.kernels

H, M, E = 128, 256, 8


def _weights(G, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(r.normal(size=s) * 0.1, dtype)  # noqa: E731
    return w(G, H, M), w(G, H, M), w(G, M, H)


def _tokens(T, seed=1, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.normal(size=(T, H)), dtype)


def _gates(idx, seed=2):
    return jnp.asarray(np.random.default_rng(seed).random(idx.shape) + 0.5,
                       jnp.float32)


def _both(w, x, gates, idx, **kw):
    """(kernel's result, ragged_dot's result), each the whole tuple."""
    idx = jnp.asarray(idx, jnp.int32)
    got = moe_ffn_held(*w, x, gates, idx, interpret=True, **kw)
    want = moe_ffn_held(*w, x, gates, idx, **kw)
    return got, want


def _tiles_for(sizes, tm):
    return int(sum(-(-int(s) // tm) for s in sizes))


ROUTINGS = {
    # held experts 0 and 2 get no row
    "empty_groups": lambda T: np.stack(
        [np.where(np.arange(T) % 2, 1, 3), np.full(T, 5)], 1),
    # each of the 8 experts exactly one row
    "one_row_a_group": lambda T: np.arange(2 * T).reshape(T, 2) % E,
    # every row on expert 6 (fewer than a tile)
    "one_group_all_rows": lambda T: np.full((T, 1), 6),
    # expert 2 gets 40 rows, expert 7 35: more than a tile each
    "group_over_the_tile": lambda T: np.stack(
        [np.full(T, 2), np.where(np.arange(T) < 5, 0, 7)], 1),
}
TOKENS = {"empty_groups": 12, "one_row_a_group": 4,
          "one_group_all_rows": 10, "group_over_the_tile": 40}


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_kernel_matches_ragged_dot(case):
    T = TOKENS[case]
    idx = ROUTINGS[case](T)
    w, x = _weights(E), _tokens(T)
    (y, c, h, r, tiles), (y0, c0, h0, r0, t0) = _both(
        w, x, _gates(idx), idx, n_experts=E, expert_offset=0, n_held=E)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    assert (int(h), int(r)) == (int(h0), int(r0)) and int(t0) == 0
    tm = moe_gmm.row_tile(idx.size, E)
    assert int(tiles) == _tiles_for(np.asarray(c), tm)
    if case == "group_over_the_tile":
        assert tm == 32 and int(tiles) == 2 + 1 + 2 > int(r) == 3


def test_a_traced_group_base_addresses_a_stack_in_place():
    """The held experts 4..7 of 8 are groups [12, 16) of a stack of three
    layers' 8 experts; each layer's weights differ, so a wrong base
    shows."""
    T = 16
    r = np.random.default_rng(3)
    idx = np.stack([r.integers(0, E, T), r.integers(0, E, T)], 1)
    idx[:, 1] = np.where(idx[:, 1] == idx[:, 0], (idx[:, 0] + 1) % E,
                         idx[:, 1])
    w, x = _weights(3 * E, seed=4), _tokens(T)
    got, want = _both(w, x, _gates(idx), idx, n_experts=E, expert_offset=4,
                      n_held=4, group_base=jnp.int32(12))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5, rtol=2e-5)
    assert int(got[3]) == int(want[3]) > 0
    # the same experts cut out of the stack, at base 0
    cut = tuple(a[12:16] for a in w)
    alone = moe_ffn_held(*cut, x, _gates(idx), jnp.asarray(idx, jnp.int32),
                         n_experts=E, expert_offset=4, n_held=4,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(got[0]),
                               atol=1e-6)
    jitted = jax.jit(lambda gb: moe_ffn_held(
        *w, x, _gates(idx), jnp.asarray(idx, jnp.int32), n_experts=E,
        expert_offset=4, n_held=4, group_base=gb, interpret=True)[0])
    np.testing.assert_allclose(np.asarray(jitted(jnp.int32(12))),
                               np.asarray(got[0]), atol=1e-6)


def test_lanes_left_out_give_nothing_and_take_no_tile():
    T = 16
    idx = np.stack([np.arange(T) % E, (np.arange(T) + 3) % E], 1)
    live = jnp.asarray(np.arange(T) < 5)
    (y, c, h, r, tiles), want = _both(
        _weights(E, seed=5), _tokens(T), _gates(idx), idx, n_experts=E,
        expert_offset=0, n_held=E, live=live)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                               atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(y[5:]).max()) == 0.0
    assert int(h) == 10 and int(tiles) == int(r) == int(want[3])
    # no live lane: no row, no tile, a zero output
    (y, _, h, r, tiles), _ = _both(
        _weights(E, seed=5), _tokens(T), _gates(idx), idx, n_experts=E,
        expert_offset=0, n_held=E, live=jnp.zeros((T,), bool))
    assert (int(h), int(r), int(tiles)) == (0, 0, 0)
    assert float(jnp.abs(y).max()) == 0.0


def test_bf16_tokens_with_a_float32_output():
    """bf16 operands, float32 out: the kernel forms the gated product in
    float32 before its bf16 rounding, ragged_dot rounds ``g`` and ``u``
    first, so the two agree to bf16's resolution."""
    T = 24
    r = np.random.default_rng(6)
    idx = np.stack([r.integers(0, 4, T), r.integers(4, E, T)], 1)
    w, x = _weights(E, seed=7, dtype=jnp.bfloat16), _tokens(T,
                                                             dtype=jnp.bfloat16)
    (y, *_), (y0, *_) = _both(w, x, _gates(idx), idx, n_experts=E,
                              expert_offset=2, n_held=5,
                              out_dtype=jnp.float32)
    assert y.dtype == y0.dtype == jnp.float32
    scale = float(jnp.abs(y0).max())
    assert float(jnp.abs(y - y0).max()) < 2e-2 * scale
    (yb, *_), _ = _both(w, x, _gates(idx), idx, n_experts=E, expert_offset=2,
                        n_held=5)
    assert yb.dtype == jnp.bfloat16


def test_the_work_list_on_a_known_layout():
    sizes = [0, 3, 16, 17, 0, 40]
    tm = 16
    length = moe_gmm.n_tiles(sum(sizes), len(sizes), tm)
    assert length == -(-(76 + 6 * 15) // 16) == 11
    t = moe_gmm.expert_tiles(jnp.asarray(sizes), tm, length)
    assert int(t.count[0]) == 7
    assert list(np.asarray(t.group)) == [1, 2, 3, 3, 5, 5, 5] + [5] * 4
    assert list(np.asarray(t.first)) == [0, 3, 19, 35, 36, 52, 68] + [68] * 4
    assert list(np.asarray(t.rows)) == [3, 16, 16, 1, 16, 16, 8] + [8] * 4
    # tiles over the experts read: experts 3 and 5 are read 2 and 3 times
    reads = sum(s > 0 for s in sizes)
    assert int(t.count[0]) / reads == 7 / 4
    empty = moe_gmm.expert_tiles(jnp.zeros(4, jnp.int32), tm, 5)
    assert int(empty.count[0]) == 0
    assert not np.asarray(empty.rows).any()


def test_the_static_length_holds_any_routing():
    r = np.random.default_rng(8)
    for tm in (16, 32, 64):
        for _ in range(20):
            n_held, rows = int(r.integers(1, 40)), int(r.integers(0, 600))
            sizes = np.bincount(r.integers(0, n_held, rows),
                                minlength=n_held)
            length = moe_gmm.n_tiles(rows, n_held, tm)
            t = moe_gmm.expert_tiles(jnp.asarray(sizes), tm, length)
            assert int(t.count[0]) == _tiles_for(sizes, tm) <= length
            live = int(t.count[0])
            assert int(np.asarray(t.rows)[:live].sum()) == rows


def test_the_row_tile_follows_the_rows_a_group_gets():
    # a docqa decode tick (32 lanes x 8) and its 512-token chunk
    assert moe_gmm.row_tile(32 * 8, 128) == 16
    assert moe_gmm.row_tile(512 * 8, 128) == 64
    assert moe_gmm.row_tile(10 ** 6, 8) == 128
