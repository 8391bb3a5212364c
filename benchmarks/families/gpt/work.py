"""Operations and bytes the GPT block and its kernels need, from shapes
(moved here from ``benchmarks/lib/work.py`` and ``lib/readers.py``
letter for letter). No recompute, no padding, no position-table
"matmul": what a perfect implementation would still have to do."""


def matmul_params(s):
    """Weights that take part in a matmul for every token: the four
    block projections of every layer, and the tied output head."""
    H, L = s["hidden"], s["n_layers"]
    M = H * s["mlp_ratio"]
    return L * (H * 3 * H + H * H + 2 * H * M) + s["vocab_size"] * H


def forward_flops_per_token(s, context, causal_mean=False):
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included). With ``causal_mean`` the token is the average one
    of a causal pass over ``context`` positions: it sees half of them."""
    attended = context / 2.0 if causal_mean else context
    attn = 4.0 * attended * s["hidden"] * s["n_layers"]     # QK^T and PV
    return 2.0 * matmul_params(s) + attn


def train_flops_per_token(s, seq):
    """Forward plus backward (twice the forward) of a causal LM step."""
    return 3.0 * forward_flops_per_token(s, seq, causal_mean=True)


def flash_forward(batch, heads, seq, head_dim, bytes_per=2):
    """Causal flash attention forward: two matmuls over the lower
    triangle; reads q, k, v and writes o once."""
    flops = 4.0 * batch * heads * seq * seq * head_dim * 0.5
    byts = 4.0 * batch * heads * seq * head_dim * bytes_per
    return flops, byts


def flash_backward(batch, heads, seq, head_dim, bytes_per=2):
    """Causal flash attention backward: five matmuls over the lower
    triangle (recomputed scores, dv, dp, dq, dk); reads q, k, v, o, do
    and writes dq, dk, dv."""
    flops = 10.0 * batch * heads * seq * seq * head_dim * 0.5
    byts = 8.0 * batch * heads * seq * head_dim * bytes_per
    return flops, byts


def paged_decode(contexts_sum, heads, head_dim, n_layers, bytes_per=2):
    """Paged decode attention over every live context, all layers:
    reads each cached key and value once (HBM-bound: 1 FLOP a byte)."""
    byts = 2.0 * contexts_sum * heads * head_dim * bytes_per * n_layers
    flops = 4.0 * contexts_sum * heads * head_dim * n_layers
    return flops, byts


def _flash_args(ctx):
    s = ctx.sizes
    return (ctx.mix["batch"], s["n_heads"], ctx.mix["seq"],
            s["hidden"] // s["n_heads"])


# what a metric file's "work" names: (ctx, number of kernel events) ->
# (flops, bytes) in all, or None where there is nothing to count
KERNEL_WORK = {
    "flash_forward": lambda ctx, n: tuple(
        n * x for x in flash_forward(*_flash_args(ctx))),
    "flash_backward": lambda ctx, n: tuple(
        n * x for x in flash_backward(*_flash_args(ctx))),
    "paged_decode": lambda ctx, n: (
        paged_decode(ctx.values["traced_decode_contexts"],
                     ctx.sizes["n_heads"],
                     ctx.sizes["hidden"] // ctx.sizes["n_heads"],
                     ctx.sizes["n_layers"])
        if ctx.values.get("traced_decode_contexts") else None),
}
