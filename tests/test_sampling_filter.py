"""The sampling filter chain selects, it does not sort (ISSUE 33): held
to the two-sort chain it replaced, written out below as the oracle.

For every input the kept set (positions not -inf) and the kept values
equal the oracle's on every row that samples with a filter enabled, ties
at the k-th value included, except where a row's exclusive mass lies
within 1e-6 of its top_p (the bounded path's softmax sums K_CAP columns,
the oracle's V). The one-sort path is bit-identical. Rows that sample
with no filter come back scaled and whole; greedy rows are never read
(callers argmax the raw logits)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import gpt_init, gpt_tiny
from paddle_tpu.serving import InferenceEngine, engine as engine_mod
from paddle_tpu.serving import sampling
from paddle_tpu.serving.sampling import K_CAP

WIDTHS = (1000, 50304)


# -- the oracle: the two-sort chain as it stood before ISSUE 33 -------------

def oracle_filter(logits, temperature, top_k, top_p):
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    keep = exclusive_cum < top_p[:, None]
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(scaled >= cutoff, scaled, -jnp.inf)


def oracle_filter_cond(logits, temperature, top_k, top_p):
    need = jnp.any(top_k > 0) | jnp.any(top_p < 1.0)
    return jax.lax.cond(
        need,
        lambda lg: oracle_filter(lg, temperature, top_k, top_p),
        lambda lg: lg / jnp.maximum(temperature, 1e-6)[:, None],
        logits)


def oracle_sample_one(logits, key, temperature, top_k, top_p, mask=None):
    """The eager first token as the engine drew it before ISSUE 33."""
    logits = sampling._apply_mask(logits.astype(jnp.float32), mask)
    t = jnp.float32(temperature)[None]
    scaled = oracle_filter(logits, t, jnp.int32(top_k)[None],
                           jnp.float32(top_p)[None])
    gumbel = jax.random.gumbel(key, logits.shape, jnp.float32)
    return int(sampling._finish(logits, scaled, gumbel, t)[0])


# -- inputs ------------------------------------------------------------------

def _logits(rows, width, rounding, seed):
    x = np.random.default_rng(seed).normal(0.0, 2.0, (rows, width))
    x = jnp.asarray(x, jnp.float32)
    if rounding == "bf16":      # a bf16 model's logits: many exact ties
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _params(rows):
    """rows: a list of (temperature, top_k, top_p)."""
    t, k, p = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


def _filters(t, k, p):
    return np.asarray((t > 0) & ((k > 0) | (p < 1.0)))


GREEDY, DEAD, PLAIN = (0.0, 40, 0.95), (0.0, 0, 1.0), (0.7, 0, 1.0)


def bounded_rows():
    """Every row that filters has 0 < top_k <= K_CAP."""
    return [GREEDY, DEAD, PLAIN] + [
        (temp, k, p) for k, temp in ((1, 1.0), (40, 0.8), (K_CAP, 0.3))
        for p in (0.1, 0.95, 1.0)]


def sorting_rows(width):
    """Parameters that force the one sort, beside rows that would not."""
    return [GREEDY, PLAIN, (0.8, 40, 0.95), (1.0, 0, 0.1), (0.8, 0, 0.95),
            (0.5, -1, 0.5)] + [
        (0.8, k, p) for k in (K_CAP + 1, width) for p in (0.1, 0.95, 1.0)]


def assert_same_kept(got, want, top_p, rows):
    """Kept sets and kept values equal on ``rows``; a position may differ
    only where the oracle's exclusive mass there lies within 1e-6 of the
    row's top_p."""
    got, want = np.asarray(got), np.asarray(want)
    for r in np.flatnonzero(rows):
        diff = np.flatnonzero(got[r] != want[r])
        if not diff.size:
            continue
        # the row as the oracle's nucleus saw it: its top-k survivors,
        # the exclusive mass of a value = the mass of all values above it
        full = np.where(np.isfinite(want[r]), want[r], got[r])
        alive = np.sort(full[np.isfinite(full)].astype(np.float64))[::-1]
        mass = np.exp(alive - alive[0])
        mass /= mass.sum()
        for i in diff:
            v = float(full[i])
            assert np.isfinite(v), (r, i)
            above = mass[alive > v].sum()
            assert abs(above - float(top_p[r])) <= 1e-6, (
                r, i, above, float(top_p[r]))


# -- the property: new chain == two-sort oracle ------------------------------

_cond = jax.jit(lambda *a: sampling._filter_logits_cond(*a))


@pytest.mark.parametrize("rounding", ["f32", "bf16"])
@pytest.mark.parametrize("width", WIDTHS)
class TestAgainstTheTwoSortOracle:
    def test_bounded_rows_select(self, width, rounding, monkeypatch):
        lg = _logits(12, width, rounding, seed=width)
        t, k, p = _params(bounded_rows())
        # the candidates alone give the cut-offs wherever a row fits:
        # all do but, among bf16's ties, some whose top_k is K_CAP itself
        # (they sort: test_ties_at_the_kth_value), made greedy here
        _, fits = sampling._select_cutoff(sampling._scale(lg, t), k, p)
        assert bool(jnp.all(fits | (k == K_CAP)))
        assert rounding == "bf16" or bool(jnp.all(fits))
        t = jnp.where(fits, t, 0.0)
        want = oracle_filter(lg, t, k, p)
        rows = _filters(t, k, p)
        assert rows.sum() >= 7
        scaled = sampling._scale(lg, t)
        cutoff, fits = sampling._select_cutoff(scaled, k, p)
        assert_same_kept(sampling._keep(scaled, cutoff), want, p, rows)
        # the jitted chain takes that path: a sort that suppressed
        # everything would show
        monkeypatch.setattr(
            sampling, "_sort_cutoff",
            lambda s, k, p: jnp.full((s.shape[0], 1), jnp.inf))
        got = jax.jit(lambda *a: sampling._filter_logits_cond(*a))(
            lg, t, k, p)
        assert_same_kept(got, want, p, rows)
        # rows that do not filter come back scaled and whole
        np.testing.assert_array_equal(np.asarray(got)[~rows],
                                      np.asarray(scaled)[~rows])

    def test_forcing_parameters_sort_once_bit_identical(
            self, width, rounding, monkeypatch):
        lg = _logits(12, width, rounding, seed=width + 1)
        t, k, p = _params(sorting_rows(width))
        want = np.asarray(oracle_filter(lg, t, k, p))
        rows = _filters(t, k, p)
        # the unconditional one-sort chain: every row, every bit
        np.testing.assert_array_equal(
            np.asarray(sampling._filter_logits(lg, t, k, p)), want)
        # the jitted chain sorts too: candidates that kept everything
        # would show
        monkeypatch.setattr(
            sampling, "_select_cutoff",
            lambda s, k, p: (jnp.full((s.shape[0], 1), -jnp.inf),
                             jnp.ones(s.shape[0], bool)))
        got = np.asarray(jax.jit(
            lambda *a: sampling._filter_logits_cond(*a))(lg, t, k, p))
        np.testing.assert_array_equal(got[rows], want[rows])
        np.testing.assert_array_equal(
            got[~rows], np.asarray(sampling._scale(lg, t))[~rows])

    def test_ties_at_the_kth_value(self, width, rounding):
        """More than K_CAP values tied at the k-th overflow the
        candidates: the row does not fit, the chain sorts, and the
        result is the oracle's to the bit. A tie the candidates hold
        stays on the bounded path with every tied value kept."""
        lg = np.array(_logits(4, width, rounding, seed=width + 2))
        big = float(lg.max()) + 1.0
        lg[0, :5], lg[0, 5:K_CAP + 75] = big + 1.0, big   # 5 above, a tie
        lg[1, :3], lg[1, 3:13] = big + 1.0, big           # a held tie
        lg = jnp.asarray(lg)
        rows = [(0.8, 40, 0.95), (0.8, 5, 1.0), (0.8, 40, 0.95), DEAD]
        t, k, p = _params(rows)
        want = np.asarray(oracle_filter(lg, t, k, p))
        _, fits = sampling._select_cutoff(sampling._scale(lg, t), k, p)
        assert np.asarray(fits).tolist() == [False, True, True, True]
        got = np.asarray(_cond(lg, t, k, p))
        np.testing.assert_array_equal(got[:3], want[:3])
        assert np.isfinite(got[0]).sum() >= 40      # top_p cuts the tie
        # without the overflowing row the batch selects; the held tie
        # survives whole: 3 above the 10 tied at the 5th
        got = np.asarray(_cond(lg[1:], t[1:], k[1:], p[1:]))
        assert_same_kept(got, want[1:], p[1:], _filters(t, k, p)[1:])
        assert np.isfinite(got[0]).sum() == 13

    def test_mask_allows_fewer_tokens_than_top_k(self, width, rounding):
        """Constrained decoding masks before the filter: with 10 allowed
        tokens and top_k 40 the k-th value is a suppressed one, every
        suppressed entry ties with it, and the chain must sort."""
        lg = _logits(3, width, rounding, seed=width + 3)
        allowed = np.zeros((3, width), bool)
        allowed[:, 7:width:width // 10][:, :10] = True
        allowed[2] = True
        mask = jnp.asarray(allowed)
        t, k, p = _params([(0.8, 40, 0.95), (1.0, 40, 1.0),
                           (0.8, 40, 0.95)])
        masked = sampling._apply_mask(lg, mask)
        want = np.asarray(oracle_filter(masked, t, k, p))
        got = np.asarray(_cond(masked, t, k, p))
        np.testing.assert_array_equal(got, want)
        keys = jax.random.split(jax.random.key(width), 3)
        toks = np.asarray(jax.jit(sampling.sample_tokens_streams)(
            lg, keys, t, k, p, mask))
        assert allowed[np.arange(3), toks].all()


# -- the entry points ---------------------------------------------------------

@pytest.mark.parametrize("params, path", [
    ([DEAD, GREEDY], "greedy"),
    ([DEAD, (0.8, 40, 0.95)], "select"),
    ([PLAIN, (0.8, K_CAP, 1.0)], "select"),
    ([PLAIN, DEAD], "select"),
    ([(0.8, 40, 0.95), (0.8, K_CAP + 1, 1.0)], "sort"),
    ([(0.8, 0, 0.95)], "sort"),
    ([(0.0, 0, 0.5), (0.8, 40, 0.95)], "select"),   # a greedy row's top_p
])
def test_sample_path_names_the_way_the_batch_goes(params, path):
    t, k, p = (np.asarray(a) for a in _params(params))
    assert sampling.sample_path(t, k, p) == path


@pytest.mark.parametrize("temperature, top_k, top_p", [
    GREEDY, DEAD, PLAIN, (0.8, 40, 0.95), (0.8, 1, 1.0), (0.8, K_CAP, 0.5),
    (0.8, K_CAP + 1, 0.95), (0.8, 0, 0.95), (1.0, 700, 1.0)])
def test_sample_one_draws_the_oracles_token(temperature, top_k, top_p):
    """The eager first token: path picked on the host, same key, same
    draw as the two-sort chain, with and without a mask."""
    lg = _logits(1, 1000, "bf16", seed=5)
    mask = jnp.asarray(np.arange(1000)[None] % 3 != 0)
    for i in range(8):
        key = jax.random.key(i)
        for m in (None, mask):
            assert sampling.sample_one(
                lg, key, temperature, top_k, top_p, mask=m) == \
                oracle_sample_one(lg, key, temperature, top_k, top_p, m)


def test_sample_one_falls_to_the_sort_on_an_overflowing_tie():
    lg = np.array(_logits(1, 1000, "f32", seed=6))
    lg[0, :K_CAP + 60] = float(lg.max()) + 1.0
    lg = jnp.asarray(lg)
    seen = set()
    for i in range(40):
        key = jax.random.key(i)
        tok = sampling.sample_one(lg, key, 0.8, 40, 0.95)
        assert tok == oracle_sample_one(lg, key, 0.8, 40, 0.95)
        seen.add(tok)
    assert max(seen) < K_CAP + 60 and len(seen) > 10


# -- the engine ---------------------------------------------------------------

CFG = gpt_tiny(dtype=jnp.float32, seq_len=64)
PARAMS = gpt_init(CFG, seed=3)
SAMPLED = [dict(temperature=0.8, top_k=40, top_p=0.95),
           dict(temperature=0.8, top_k=40, top_p=0.95),
           dict(temperature=0.9, top_p=0.6),            # top_p alone: sorts
           dict(temperature=1.0),                       # no filter
           dict()]                                      # greedy


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n).astype(np.int32)


def _serve(requests, new=6, **kw):
    eng = InferenceEngine(CFG, PARAMS, n_slots=4, block_size=8,
                          prefill_chunk=16, seed=11, **kw)
    try:
        reqs = [eng.submit(_prompt(9 + 7 * i, i), max_new_tokens=new, **r)
                for i, r in enumerate(requests)]
        return [r.result(timeout=120) for r in reqs]
    finally:
        eng.shutdown(drain=False, timeout=30)


class TestEngine:
    def test_streams_equal_the_oracle_patched_in_place(self, monkeypatch):
        got = _serve(SAMPLED)
        monkeypatch.setattr(sampling, "_filter_logits_cond",
                            oracle_filter_cond)
        monkeypatch.setattr(engine_mod, "sample_one", oracle_sample_one)
        want = _serve(SAMPLED)
        assert got == want
        assert all(len(toks) == 6 for toks in got)
