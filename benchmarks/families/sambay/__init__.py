"""The ``sambay`` family: the SambaY decoder-hybrid-decoder of
``paddle_tpu/models/sambay.py`` (Phi-4-mini-flash-reasoning: Mamba
layers and 512-token window layers of differential attention, one
full-attention layer whose keys and values seven cross-attention layers
read, gated memory units that read the last Mamba layer's output, a
LayerNorm before every mixer and every gated SiLU MLP, the head tied to
the embedding). What a family gives the harness (``benchmarks/lib/
spec.py`` has the list) is gathered here; the code is in the files
beside this one. Serving only: the four train-side names refuse by a
sentence."""
from .program import param_specs, train_loss  # noqa: F401
from .reference import leaf_norms, served_gaps, train_readings  # noqa: F401
from .weights import make_params  # noqa: F401
from . import reference  # noqa: F401
from .work import (KERNEL_WORK, forward_flops_per_token,  # noqa: F401
                   train_flops_per_token)
