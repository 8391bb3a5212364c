"""The ``sarvam_mla`` family: the latent-attention mixture-of-experts
block of ``paddle_tpu/models/mla.py`` (RMSNorm, an uncompressed query
and a 512-wide cached latent with a decoupled YaRN rotary key, a leading
dense SiLU-gated layer, then sigmoid-routed expert layers with a
selection-only bias, a shared expert and normalised scaled gates, untied
head), of which a chip holds a share of the experts and a slice of the
vocabulary. What a family gives the harness (``benchmarks/lib/spec.py``
has the list) is gathered here; the code is in the files beside this
one. Serving only: the four train-side names refuse by a sentence."""
from .program import param_specs, train_loss  # noqa: F401
from .reference import leaf_norms, served_gaps, train_readings  # noqa: F401
from .weights import make_params  # noqa: F401
from . import reference  # noqa: F401
from .work import (KERNEL_WORK, forward_flops_per_token,  # noqa: F401
                   train_flops_per_token)
