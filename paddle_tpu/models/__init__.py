"""paddle_tpu.models — flagship model families (functional, shardable).

These are the models the reference ships training configs for (driver
BASELINE.json: LeNet/ResNet-50 in paddle.vision, BERT/ERNIE/GPT via Fleet).
Vision models live in paddle_tpu.vision.models (Layer API); the language
models here are written functionally — pure ``forward(params, batch)`` over
a param pytree with PartitionSpec tables — because that is the shape the
compiled hybrid-parallel path (paddle_tpu.parallel) consumes directly.
"""
from .gpt import (
    GPTConfig,
    gpt_init,
    gpt_forward,
    gpt_loss,
    gpt_param_specs,
    gpt_prefill,
    gpt_prefill_chunk,
    gpt_decode_step,
    gpt_decode_step_paged,
    gpt_verify_step,
    gpt_verify_step_paged,
    gpt_truncate,
    gpt_tiny,
    gpt_small,
    gpt_1p3b,
    gpt_nano,
    bert_base_config,
)
from .mla import (
    MLAConfig,
    mla_init,
    mla_forward,
    mla_prefill_chunk,
    mla_decode_step_paged,
    mla_param_specs,
    mla_tiny,
    sarvam_105b,
)
from .retention import (
    RetentionConfig,
    brumby_14b,
    retention_decode_step_paged,
    retention_forward,
    retention_init,
    retention_param_specs,
    retention_prefill_chunk,
    retention_tiny,
)
from .sambay import (
    SambaYConfig,
    phi4_mini_flash,
    sambay_decode_step_paged,
    sambay_init,
    sambay_param_specs,
    sambay_prefill_chunk,
    sambay_tiny,
)
from .serving_api import ServingModel
from .dlrm import (
    DLRMConfig,
    dlrm_init,
    dlrm_forward,
    dlrm_forward_from_emb,
    dlrm_loss,
    dlrm_loss_from_emb,
    dlrm_param_specs,
    dlrm_score_fn,
    dlrm_tiny,
    synthetic_ctr_batches,
)

__all__ = [
    "GPTConfig", "gpt_init", "gpt_forward", "gpt_loss", "gpt_param_specs",
    "gpt_prefill", "gpt_prefill_chunk",
    "gpt_decode_step", "gpt_decode_step_paged",
    "gpt_verify_step", "gpt_verify_step_paged", "gpt_truncate",
    "gpt_tiny", "gpt_small", "gpt_1p3b", "gpt_nano", "bert_base_config",
    "MLAConfig", "mla_init", "mla_forward", "mla_prefill_chunk",
    "mla_decode_step_paged", "mla_param_specs", "mla_tiny", "sarvam_105b",
    "RetentionConfig", "brumby_14b", "retention_init", "retention_forward",
    "retention_prefill_chunk", "retention_decode_step_paged",
    "retention_param_specs", "retention_tiny",
    "SambaYConfig", "phi4_mini_flash", "sambay_init", "sambay_prefill_chunk",
    "sambay_decode_step_paged", "sambay_param_specs", "sambay_tiny",
    "ServingModel",
    "DLRMConfig", "dlrm_init", "dlrm_forward", "dlrm_forward_from_emb",
    "dlrm_loss", "dlrm_loss_from_emb", "dlrm_param_specs", "dlrm_score_fn",
    "dlrm_tiny", "synthetic_ctr_batches",
]
