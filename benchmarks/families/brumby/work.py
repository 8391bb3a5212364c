"""Operations and bytes the power-retention block and its two kernels
need, from shapes: what a perfect implementation of the recurrence would
still have to do. A key's ``phi`` counts its D = d (d + 1) / 2 = 8256
distinct products (not the 9216 columns the pool stores them in), a
state D x 128 values and D of the normaliser (not the 136 rows stored),
nothing is recomputed, and nothing depends on the context's length."""


def phi_dim(s):
    return s["head_dim"] * (s["head_dim"] + 1) // 2


def matmul_params(s):
    """Weights that take part in a matmul for every token: query, key,
    value, gate and output projections and the gated MLP of every layer,
    and the head."""
    H, d = s["hidden"], s["head_dim"]
    nq, nkv = s["n_heads"] * d, s["n_kv_heads"] * d
    layer = H * nq + 2 * H * nkv + H * s["n_kv_heads"] + nq * H \
        + 3 * H * s["ffn"]
    return s["n_layers"] * layer + H * s["vocab_size"]


def retention_flops(s):
    """One token at one layer: per key/value head the decay, the outer
    product's multiply and its add over the D x 128 state (3 D x 128);
    per query head the read-out of 128 values and the normaliser (2 D x
    129)."""
    D, dv = phi_dim(s), s["head_dim"]
    return s["n_kv_heads"] * 3.0 * D * dv + s["n_heads"] * 2.0 * D * (dv + 1)


def state_bytes(s):
    """One sequence's state at one layer: every key/value head's D x 128
    values and D of the normaliser, float32."""
    return s["n_kv_heads"] * (phi_dim(s) * s["head_dim"] + phi_dim(s)) * 4.0


def forward_flops_per_token(s, context, causal_mean=False):
    """Forward FLOPs of one token: the matmuls and, in every layer, the
    state's update and read-out. The context's length does not enter."""
    return 2.0 * matmul_params(s) + s["n_layers"] * retention_flops(s)


def train_flops_per_token(s, seq):
    from .program import NO_TRAINING

    raise NotImplementedError(
        NO_TRAINING.format(name="train_flops_per_token"))


def retention_decode(slot_ticks, s):
    """``slot_ticks`` live (lane, tick) pairs, every layer: the state
    read and written once, the recurrence's operations."""
    n = float(slot_ticks) * s["n_layers"]
    return n * retention_flops(s), n * 2.0 * state_bytes(s)


def retention_chunk(tokens, chunks, s, bytes_per=2):
    """``tokens`` real tokens in ``chunks`` prefill chunks, every layer:
    the recurrence's operations a token; the state read and written once
    a chunk; each token's query, key and value heads in and its output
    out."""
    d = s["head_dim"]
    io = (s["n_heads"] + 2 * s["n_kv_heads"]) * d * bytes_per \
        + s["n_heads"] * d * 4.0
    flops = float(tokens) * s["n_layers"] * retention_flops(s)
    byts = s["n_layers"] * (float(chunks) * 2.0 * state_bytes(s)
                            + float(tokens) * io)
    return flops, byts


def _span_sum(ctx, span, key):
    """Sum of the argument ``key`` over the program's spans of that name
    in the traced sub-window, and how many carried it."""
    vals = [e["args"][key] for e in ctx.program_events or ()
            if e.get("ph") == "X" and e["name"] == span
            and key in (e.get("args") or {})]
    return sum(vals), len(vals)


def _decode_work(ctx, n):
    slots, _ = _span_sum(ctx, "serving.decode_step", "state_slots_live")
    return retention_decode(slots, ctx.sizes) if slots else None


def _chunk_work(ctx, n):
    tokens, chunks = _span_sum(ctx, "serving.prefill_chunk", "chunk")
    return retention_chunk(tokens, chunks, ctx.sizes) if tokens else None


# what a metric file's "work" names: (ctx, number of kernel events) ->
# (flops, bytes) in all, or None where there is nothing to count
KERNEL_WORK = {"retention_decode": _decode_work,
               "retention_chunk": _chunk_work}
