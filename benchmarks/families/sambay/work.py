"""Operations and bytes the SambaY block and its two kernels need, from
shapes: what a perfect implementation would still have to do.

A token's matmuls are every projection of the layers it runs and the
tied head. In a prompt every token runs the self-decoder, layer L/2 and
layer L/2 + 1's key/value projection; the rest of layer L/2 + 1, the
cross-decoder and the head run ONCE a prompt, for its last token (the
model's prefill saving, which is exact). A decoded token runs every
layer. Attention reads ``min(context, window)`` rows in a window layer
and the whole context in layer L/2 + 1 and the cross-attention layers;
a query head's row costs ``2 d`` operations for its score and ``2 (2 d)``
for its value head. A key/value row is ``2 n_kv d`` values in bf16."""


def _dims(s):
    H, d = s["hidden"], s["head_dim"]
    return (H, s["expand"] * H, d, s["n_heads"] * d,
            s["n_kv_heads"] * d, s["ffn"])


def _mlp(s):
    H, _, _, _, _, F = _dims(s)
    return 3 * H * F


def ssm_params(s):
    H, Di, _, _, _, _ = _dims(s)
    return 2 * H * Di + Di * (s["dt_rank"] + 2 * s["d_state"]) \
        + s["dt_rank"] * Di + Di * H


def prompt_params(s):
    """Matmul weights every prompt token runs through."""
    H, _, _, nq, nkv, _ = _dims(s)
    P = s["n_layers"] // 4
    window = H * nq + H * 2 * nkv + nq * H
    return P * (ssm_params(s) + window + 2 * _mlp(s)) \
        + ssm_params(s) + _mlp(s) + H * 2 * nkv


def once_params(s):
    """Matmul weights that run once a prompt, for its last token, and
    the head."""
    H, Di, _, nq, _, _ = _dims(s)
    P = s["n_layers"] // 4
    cross = 2 * H * Di + _mlp(s) + H * nq + nq * H + _mlp(s)
    return H * nq + nq * H + _mlp(s) + (P - 1) * cross \
        + H * s["vocab_size"]


def matmul_params(s):
    """Weights that take part in a matmul for a decoded token."""
    return prompt_params(s) + once_params(s)


def row_flops(s):
    """One attention row read by every query head of one layer."""
    d = s["head_dim"]
    return s["n_heads"] * (2.0 * d + 2.0 * 2 * d)


def row_bytes(s):
    """One key/value row of one layer, bf16: 5,120 B at the published
    widths."""
    return 2.0 * 2 * s["n_kv_heads"] * s["head_dim"]


def scan_flops(s):
    """One token through one Mamba layer's recurrence: the decay's
    exponent and product, the input's product and sum, the read-out's
    product and sum over the state, and ``D x``."""
    return 7.0 * s["d_state"] * s["expand"] * s["hidden"]


def forward_flops_per_token(s, context, causal_mean=False):
    """Forward FLOPs of one token at ``context``; with ``causal_mean``
    the mean over a prompt of that length, the once-a-prompt part
    spread over its tokens."""
    P, W = s["n_layers"] // 4, s["window"]
    n = float(context)
    if causal_mean:
        win = (n + 1) / 2 if n <= W else (W * (W + 1) / 2 + (n - W) * W) / n
        self_part = 2.0 * prompt_params(s) + P * win * row_flops(s) \
            + (P + 1) * scan_flops(s)
        once = 2.0 * once_params(s) + P * n * row_flops(s)
        return self_part + once / n
    return 2.0 * matmul_params(s) + (P * min(n, W) + P * n) * row_flops(s) \
        + (P + 1) * scan_flops(s)


def train_flops_per_token(s, seq):
    from .program import NO_TRAINING

    raise NotImplementedError(
        NO_TRAINING.format(name="train_flops_per_token"))


def hybrid_decode(pool_rows, window_rows, s):
    """Rows of the shared pool read by its ``P`` readers (layer L/2 + 1
    and the cross-attention layers: ``pool_rows`` counts a row once) and
    rows of the rings read by the ``P`` window layers (``window_rows``
    counts a row once): every row read once a layer, every query head's
    score and value over it."""
    P = s["n_layers"] // 4
    rows = P * (float(pool_rows) + float(window_rows))
    return rows * row_flops(s), rows * row_bytes(s)


def ssm_chunk(tokens, chunks, s):
    """``tokens`` real tokens in ``chunks`` prefill chunks through the
    ``P + 1`` Mamba layers: the recurrence a token, its ``x``, ``dt``
    and ``y`` float32 in and out, ``B`` and ``C``; the state read and
    written once a chunk."""
    Di, N = s["expand"] * s["hidden"], s["d_state"]
    m = s["n_layers"] // 4 + 1
    flops = float(tokens) * m * scan_flops(s)
    byts = m * (float(tokens) * (3 * Di + 2 * N) * 4.0
                + float(chunks) * 2 * N * Di * 4.0)
    return flops, byts


def _spans(ctx, span):
    return [e["args"] for e in ctx.program_events or ()
            if e.get("ph") == "X" and e["name"] == span and e.get("args")]


def _decode_work(ctx, n):
    """The ticks' rows from the ``serving.decode_step`` spans, and the
    rows the prompts' last tokens read in the chunks that ran them
    (``cross_rows`` 1: the chunk's context)."""
    ticks = [a for a in _spans(ctx, "serving.decode_step")
             if "shared_kv_contexts" in a]
    if not ticks:
        return None
    pool = sum(a["shared_kv_contexts"] for a in ticks)
    window = sum(a["window_rows"] for a in ticks)
    pool += sum(a["start"] + a["chunk"]
                for a in _spans(ctx, "serving.prefill_chunk")
                if a.get("cross_rows"))
    return hybrid_decode(pool, window, ctx.sizes)


def _chunk_work(ctx, n):
    chunks = [a for a in _spans(ctx, "serving.prefill_chunk")
              if "cross_rows" in a]
    tokens = sum(a["chunk"] for a in chunks)
    return ssm_chunk(tokens, len(chunks), ctx.sizes) if tokens else None


# what a metric file's "work" names: (ctx, number of kernel events) ->
# (flops, bytes) in all, or None where there is nothing to count
KERNEL_WORK = {"hybrid_decode": _decode_work, "ssm_chunk": _chunk_work}
