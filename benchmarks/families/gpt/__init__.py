"""The ``gpt`` family: the GPT-2-era block of ``paddle_tpu/models/gpt.py``
(LayerNorm, learned positions, fused q, k and v, tanh GELU, tied head),
BERT-base's widths among its presets. What a family gives the harness
(``benchmarks/lib/spec.py`` has the list) is gathered here; the code is
in the files beside this one, moved out of ``benchmarks/lib`` by PR 29.
"""
from .program import param_specs, train_loss  # noqa: F401
from .reference import leaf_norms, served_gaps, train_readings  # noqa: F401
from .weights import make_params  # noqa: F401
from .work import (KERNEL_WORK, forward_flops_per_token,  # noqa: F401
                   train_flops_per_token)
