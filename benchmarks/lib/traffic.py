"""One general generator. A traffic mix is a data file under
``benchmarks/traffic/``; this module turns it, a rate, a window length
and ``--seed`` into the inputs of a run.

Every seed gets the same schedule: lengths and inter-arrival gaps are
the quantiles of their distributions at (i + 0.5) / n, laid out by the
mix's own ``pattern_seed`` in blocks of ``BLOCK`` requests (each block
draws one value from each BLOCK-th of the distribution, so every couple
of seconds carries about the same work; bursts are milder than a true
Poisson process gives). ``--seed`` draws the token ids (and, in the
drivers, the weights and the engine's sampling key) and nothing of the
schedule. A tail over some fifty requests hangs on which long prompt
meets which burst: on the chip the same schedule run four times reads
its 95th percentiles within 0.4%, the same sizes and gaps in a free
order per seed within 6% (chat) to 25% (long prompts), and with only
neighbours in rank changing places still within 5% to 17% (PERF.md,
PR 25). No bound could hold those, so the order is the mix's, not the
seed's; another order is another ``pattern_seed``, that is another mix.
"""
import math
from statistics import NormalDist

import numpy as np


def _rng(seed, salt):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, salt])


BLOCK = 8


def arrange(values, rng):
    """The values (any order) spread over blocks of BLOCK so that each
    block holds one value of every BLOCK-th of their sorted order; the
    rng orders each block and the blocks."""
    v = np.sort(np.asarray(values))
    nb = -(-len(v) // BLOCK)
    blocks = [rng.permutation(v[j::nb]) for j in range(nb)]
    return np.concatenate([blocks[j] for j in rng.permutation(nb)])


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lengths(dist, n):
    """n whole numbers: the quantile grid of ``dist`` clipped to its
    [min, max]. dist: {"dist": "lognormal", "median", "sigma", "min",
    "max"} or {"dist": "fixed", "value"}."""
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf(float(q)) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def length_range(dist):
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


def arrivals(kind, rate, n):
    """Sorted due times (seconds from the window's start) of n requests
    at ``rate`` a second. "poisson": exponential gaps at their quantile
    grid (the mix's pattern orders them); "uniform": evenly spaced."""
    if kind == "uniform":
        return np.arange(n) / rate
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    return -np.log1p(-_quantiles(n)) / rate      # gaps, not yet ordered


def open_loop(mix, rate, seconds, seed, vocab):
    """The requests due in a window of ``seconds``: a list of dicts with
    ``due`` (s), ``prompt`` (int32 ids), ``max_new_tokens``, ``greedy``
    and the sampling parameters, in order of ``due``."""
    n = max(1, int(math.floor(rate * seconds)))
    pat = int(mix.get("pattern_seed", 0))
    gaps = arrivals(mix["arrivals"], rate, n)
    if mix["arrivals"] == "poisson":
        gaps = arrange(gaps, _rng(pat, 1))
        # each request is due in the middle of its gap, and the gaps
        # fill the window exactly
        due = (np.cumsum(gaps) - gaps / 2.0) * (seconds / gaps.sum())
    else:
        due = gaps
    plen = arrange(lengths(mix["prompt"], n), _rng(pat, 2))
    olen = arrange(lengths(mix["output"], n), _rng(pat, 3))
    n_greedy = int(round(mix["greedy_share"] * n))
    greedy = arrange(np.arange(n) < n_greedy, _rng(pat, 4))
    tok = _rng(seed, 5)
    samp = mix.get("sampling", {})
    out = []
    for i in range(n):
        g = bool(greedy[i])
        out.append({
            "index": i, "due": float(due[i]),
            "prompt": tok.integers(0, vocab, int(plen[i])).astype(np.int32),
            "max_new_tokens": int(olen[i]), "greedy": g,
            "temperature": 0.0 if g else float(samp.get("temperature", 1.0)),
            "top_k": 0 if g else int(samp.get("top_k", 0)),
            "top_p": 1.0 if g else float(samp.get("top_p", 1.0)),
        })
    return out


def train_batches(mix, seed, vocab):
    """A ring of distinct (tokens, labels) batches, labels the next
    token, every row different."""
    rng = _rng(seed, 7)
    ring = []
    for _ in range(int(mix["ring"])):
        seq = rng.integers(0, vocab, (int(mix["batch"]), int(mix["seq"]) + 1))
        ring.append((seq[:, :-1].astype(np.int32),
                     seq[:, 1:].astype(np.int32)))
    return ring
