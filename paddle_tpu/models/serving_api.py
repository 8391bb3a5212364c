"""What a model's configuration object tells the serving engine.

``cfg.serving_model()`` returns one :class:`ServingModel`: the shapes of
the paged pool's arrays, the parameter specs, and the step functions the
engine's compiled programs are built from. ``serving/kv_cache.py`` sizes
its pool from ``pool_spec``; ``serving/engine.py`` reaches every model
function through this object, so a second model family needs no edit
there. The engine serves the target model from the paged pool only, so a
new family gives ``pool_spec``, ``param_specs``, ``prefill_chunk`` and
``decode_step_paged``. An optional step a model lacks is ``None`` here
and its engine option is named in ``refuses``: the engine raises that
sentence at construction.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional

__all__ = ["ServingModel"]


@dataclasses.dataclass(frozen=True)
class ServingModel:
    name: str
    # (cfg, n_blocks, block_size) -> tuple of jax.ShapeDtypeStruct: the
    # paged pool's arrays, each (n_blocks, n_layers, ...), block-major
    pool_spec: Callable
    param_specs: Callable                  # (cfg) -> PartitionSpec tree
    # (cfg, params, pool, table_row, tokens, start) -> (logits, pool[, stats])
    prefill_chunk: Callable
    # (cfg, params, pool, tables, positions, tokens) -> (logits, pool[, stats])
    decode_step_paged: Callable
    # what a target needs to be verified against a draft (``draft=``)
    # and to reuse a cached prefix (``prefix_cache``)
    verify_step_paged: Optional[Callable] = None
    prefill_prefix: Optional[Callable] = None
    # what a DRAFT model must give: it keeps a private fixed-slot KVCache
    # (serving/kv_cache.py), proposes through ``decode_step`` and is
    # filled a chunk at a time through ``verify_step``
    decode_step: Optional[Callable] = None
    verify_step: Optional[Callable] = None
    # every step function returns router stats as a third element:
    # (assignments per expert (E,), rows computed here, experts read)
    routed: bool = False
    # engine option ("draft", "prefix_cache", "int8_weights", "mesh") ->
    # the sentence that refuses it
    refuses: Mapping[str, str] = dataclasses.field(default_factory=dict)
