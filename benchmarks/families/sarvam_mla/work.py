"""Operations and bytes the latent-attention expert block and its two
kernels need, from shapes: what a perfect implementation would still
have to do for the share of the model that is held here. No padding (a
cached row is 576 values, not the 640 lanes it is stored in), no
recompute, nothing for experts that live elsewhere."""


def attention_params(s):
    """Weights of one layer's attention that a token's matmuls touch:
    query, latent down-projection, key/value up-projection, output."""
    H, nh = s["hidden"], s["n_heads"]
    return (H * nh * (s["qk_nope_dim"] + s["qk_rope_dim"])
            + H * (s["kv_lora_rank"] + s["qk_rope_dim"])
            + s["kv_lora_rank"] * nh * (s["qk_nope_dim"] + s["v_head_dim"])
            + nh * s["v_head_dim"] * H)


def expert_params(s):
    """One routed (or shared) expert: gate, up, down."""
    return 3 * s["hidden"] * s["expert_ffn"]


def matmul_params(s):
    """Weights that take part in a matmul for every token HERE: the
    attention of every layer, the dense layers, and per expert layer the
    router, the shared experts and the routed experts of the token's
    ``top_k`` that fall on the held ones (``top_k * held / n_experts``
    on average: 2 of 8); the slice of the head."""
    Ld = s["first_dense"]
    Lm = s["n_layers"] - Ld
    routed_here = s["top_k"] * s["experts_held"] / s["n_experts"]
    per_moe = (s["hidden"] * s["n_experts"]
               + (s["n_shared"] + routed_here) * expert_params(s))
    return (s["n_layers"] * attention_params(s)
            + Ld * 3 * s["hidden"] * s["dense_ffn"] + Lm * per_moe
            + s["hidden"] * s["vocab_size"])


def forward_flops_per_token(s, context, causal_mean=False):
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included): both score products over the context, 192 wide
    (keys) and 128 wide (values) a head, in every layer. With
    ``causal_mean`` the token is the average one of a causal pass."""
    attended = context / 2.0 if causal_mean else context
    wide = s["qk_nope_dim"] + s["qk_rope_dim"] + s["v_head_dim"]
    attn = 2.0 * attended * s["n_heads"] * wide * s["n_layers"]
    return 2.0 * matmul_params(s) + attn


def train_flops_per_token(s, seq):
    from .program import NO_TRAINING

    raise NotImplementedError(
        NO_TRAINING.format(name="train_flops_per_token"))


def mla_decode(contexts_sum, s, bytes_per=2):
    """Absorbed latent decode over every live context, all layers: each
    cached row (latent and rotary key) is read once and meets all heads:
    a score product 576 wide and a value product 512 wide a head."""
    row = s["kv_lora_rank"] + s["qk_rope_dim"]
    byts = float(contexts_sum) * s["n_layers"] * row * bytes_per
    flops = 2.0 * contexts_sum * s["n_layers"] * s["n_heads"] \
        * (row + s["kv_lora_rank"])
    return flops, byts


def moe_experts(held_rows, expert_reads, s, bytes_per=2):
    """The grouped expert matmuls: three products a held row; every
    (run, layer, held expert) with a row reads that expert's weights
    once; each row comes in and goes out once. An expert with no row
    reads nothing, so a skipped expert lowers the bytes."""
    flops = 2.0 * expert_params(s) * held_rows
    byts = (float(expert_reads) * expert_params(s)
            + 2.0 * held_rows * s["hidden"]) * bytes_per
    return flops, byts


# what a metric file's "work" names: (ctx, number of kernel events) ->
# (flops, bytes) in all, or None where there is nothing to count
KERNEL_WORK = {
    "mla_decode": lambda ctx, n: (
        mla_decode(ctx.values["traced_decode_contexts"], ctx.sizes)
        if ctx.values.get("traced_decode_contexts") else None),
    "moe_experts": lambda ctx, n: (
        moe_experts(ctx.values["traced_moe_held"],
                    ctx.values["traced_moe_reads"], ctx.sizes)
        if ctx.values.get("traced_moe_held") else None),
}
