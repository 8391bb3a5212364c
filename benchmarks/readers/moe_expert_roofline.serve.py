"""``moe_expert_roofline.serve``: the grouped expert matmuls' share of
their roofline.

Time: the device time of the ``ragged-dot`` kernels (the compiler's
grouped matmul that ``jax.lax.ragged_dot`` becomes on a TPU; its
``ragged-dot-metadata`` helper is not counted) in the traced sub-window.
Work: the held assignments and the expert reads that the engine writes
on its ``serving.decode_step`` and ``serving.prefill_chunk`` spans
(``moe_assignments_held``, ``moe_expert_reads``), summed over the spans
of the same sub-window, through the family's ``KERNEL_WORK`` entry
``moe_experts``. A program without the arguments, or a trace without the
kernel, reads nothing."""
from benchmarks.lib import harness, work, xplane

PATTERN = r"ragged-dot(?!-metadata)"
SPANS = ("serving.decode_step", "serving.prefill_chunk")


def read(ctx):
    table = ctx.spec.family.KERNEL_WORK
    if ctx.trace is None or "moe_experts" not in table:
        return None
    held = reads = 0
    for e in ctx.program_events or ():
        args = e.get("args") or {}
        if e.get("ph") == "X" and e["name"] in SPANS \
                and "moe_assignments_held" in args:
            held += args["moe_assignments_held"]
            reads += args["moe_expert_reads"]
    evs = xplane.kernel_events(ctx.trace, PATTERN)
    seconds = sum(e.dur for e in evs) / 1e9
    if not held or not evs or seconds <= 0:
        return None
    ctx.values["traced_moe_held"] = held
    ctx.values["traced_moe_reads"] = reads
    flops, byts = table["moe_experts"](ctx, len(evs))
    least, bound = work.roofline_seconds(flops, byts, ctx.peak)
    harness.say(f"kernel {PATTERN}: {len(evs)} events, {seconds:.6f} s for "
                f"{held} held rows and {reads} expert reads, least "
                f"{least:.6f} s ({bound}-bound)")
    return 100.0 * least / seconds
