"""The roofline every kernel's work is held against. What a model or a
kernel needs in operations and bytes is counted by its family, from
shapes, in ``benchmarks/families/<family>/`` (the GPT block's counts and
the flash and paged kernels', which stood here until PR 29, are in
``benchmarks/families/gpt/work.py``)."""


def roofline_seconds(flops, byts, peak):
    """Least time the chip could take, and which bound sets it."""
    tc, tm = flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
