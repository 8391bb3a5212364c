"""The comparisons that decide ``correct``. Pure functions of readings:
the drivers gather the readings, the tests feed them faults."""
import statistics

from .harness import compare

TINY_GRAD = 1e-3     # leaves whose reference gradient is under this share
#                      of the median leaf's move under Adam by round-off
#                      alone: left out of the change comparison


def worst_leaf_gap(got, ref, skip=()):
    """max over leaves of |‖got‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖):
    the gap between the norms, not the norm of the difference."""
    med = statistics.median(ref.values())
    worst, at = 0.0, None
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(got[k] - r) / max(r, med, 1e-30)
        if gap >= worst:
            worst, at = gap, k
    return worst, at


def train(got, ref, limits):
    """got / ref: {"losses", "grad_norms", "change_norms"}. Returns the
    compared numbers, each beside its limit."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], ref["losses"]))
    g, g_at = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    tiny = [k for k, v in ref["grad_norms"].items() if v < TINY_GRAD * med]
    c, c_at = worst_leaf_gap(got["change_norms"], ref["change_norms"],
                             skip=tiny)
    out = dict([
        compare("loss_rel_gap", loss_gap, limits["loss_rel_gap"]),
        compare("grad_norm_gap", g, limits["grad_norm_gap"]),
        compare("change_norm_gap", c, limits["change_norm_gap"])])
    return out, {"grad_worst_leaf": g_at, "change_worst_leaf": c_at,
                 "left_out_of_change": tiny}


def serve(gaps_max, n_tokens, bad_requests, limits):
    """gaps_max: the widest gap by which a served greedy token's logit
    lies below the reference's best; bad_requests: sampled or greedy
    requests of the sample whose token count or ids are wrong."""
    return dict([
        compare("served_logit_gap", gaps_max, limits["served_logit_gap"]),
        compare("malformed_requests", bad_requests, 0, "=="),
        compare("compared_tokens", n_tokens, limits["compared_tokens_min"],
                ">=")])
