"""What a model's configuration object tells the serving engine.

``cfg.serving_model()`` returns one :class:`ServingModel`: the shapes of
the pool's arrays, what a block of the pool is, the parameter specs,
and the step functions the engine's compiled programs are built from.
``serving/kv_cache.py`` sizes its pool from ``pool_spec`` and learns
from ``state_pad`` whether a block is a run of ``block_size`` tokens
(attention's cached rows: a slot owns as many as its tokens fill) or ONE
SEQUENCE'S WHOLE RECURRENT STATE at every layer (fixed size: a slot owns
exactly one, whatever its length); ``serving/engine.py`` reaches every
model function through this object, so a further model family needs no
edit there. The engine serves the target model from the pool only, so a
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
    # pool's arrays, each (n_blocks, n_layers, ...), block-major. A block
    # is block_size tokens' rows, or (``state_pad``) a sequence's state
    pool_spec: Callable
    param_specs: Callable                  # (cfg) -> PartitionSpec tree
    # (cfg, params, pool, table_row, tokens, start, n_true) -> (logits,
    # pool[, stats]): tokens (1, C) end-padded to whole blocks, the first
    # n_true real. Rows past a paged pool's length are never read, so a
    # token-block model may ignore n_true; a recurrent state must not
    # fold the padding in. ``start == 0`` is a sequence's first chunk
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
    # None: a block of the pool holds ``block_size`` tokens. An int: a
    # block is one sequence's whole recurrent state (``blocks_for`` any
    # length is 1, a table is one entry wide, admission is by free
    # states), and the int is the granule, in tokens, that a prefill
    # chunk is padded to: the cache's ``block_size``, whatever was asked
    state_pad: Optional[int] = None
    # every step function returns router stats as a third element:
    # (assignments per expert (E,), rows computed here, experts read)
    routed: bool = False
    # engine option ("draft", "prefix_cache", "int8_weights", "mesh") ->
    # the sentence that refuses it
    refuses: Mapping[str, str] = dataclasses.field(default_factory=dict)
