"""The ``brumby`` family: the power-retention block of
``paddle_tpu/models/retention.py`` (Qwen3-14B's block with the softmax
attention swapped for power retention of degree 2: RMSNorm, 40 query
heads over 8 key/value heads of 128 with per-head RMSNorm and rotary
positions, one log-sigmoid gate a key/value head, a normalised read-out,
a SiLU-gated MLP, no biases, untied head). What a family gives the
harness (``benchmarks/lib/spec.py`` has the list) is gathered here; the
code is in the files beside this one. Serving only: the four train-side
names refuse by a sentence."""
from .program import param_specs, train_loss  # noqa: F401
from .reference import leaf_norms, served_gaps, train_readings  # noqa: F401
from .weights import make_params  # noqa: F401
from . import reference  # noqa: F401
from .work import (KERNEL_WORK, forward_flops_per_token,  # noqa: F401
                   train_flops_per_token)
