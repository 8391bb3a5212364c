"""KV caches for the serving engine: the paged block pool the target
model is served from (:class:`PagedKVCache`), and the fixed-slot cache a
speculative DRAFT model keeps for itself (:class:`KVCache`), which only
``InferenceEngine(draft=...)`` builds.

The reference's inference stack keeps per-predictor scratch memory alive
across runs (AnalysisPredictor zero-copy tensors); the autoregressive
analog is the decode cache. The fixed-slot one is ONE pair of device
buffers

    k, v : (n_slots, n_layers, n_heads, max_len, head_dim)   cfg.dtype

allocated once and donated through every jitted chunk and speculative
tick, so steady-state serving allocates nothing and the draft's steps
have a single static shape regardless of which slots are live. A draft
row belongs to the engine slot of the same index; per-slot write
positions and attention masks come from the ``positions`` argument of
:func:`paddle_tpu.models.gpt_decode_step`, so slots at different
generation depths batch into one program.

Slot bookkeeping (free lists, per-slot lengths, block tables) is
host-side — it changes at request granularity, not token granularity,
and keeping it out of the device state keeps the decode step free of
host syncs.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..monitor.stats import (KV_BLOCKS_FREE, KV_BLOCKS_USED,
                             KV_FRAGMENTATION)

__all__ = ["KVCache", "PagedKVCache", "cache_insert"]


def cache_insert(k_cache, v_cache, slot, k_new, v_new):
    """Write one sequence's prefill entries into a slot (the tests'
    oracles fill a :class:`KVCache` with it; the engine fills the draft's
    a chunk at a time through ``verify_step``).

    k_new/v_new: (L, nh, S, hd) with S <= max_len (gpt_prefill output for
    one sequence); ``slot`` may be traced — one compiled insert serves
    every slot. Positions >= S keep whatever they held; decode overwrites
    position S, S+1, ... before ever attending to them."""
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new[None].astype(k_cache.dtype), (slot, 0, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new[None].astype(v_cache.dtype), (slot, 0, 0, 0, 0))
    return k_cache, v_cache


class KVCache:
    """The draft model's slotted decode cache: device buffers + host-side
    slot accounting."""

    def __init__(self, cfg, n_slots: int, max_len: Optional[int] = None,
                 dtype=None):
        if max_len is None:
            max_len = cfg.seq_len
        if max_len > cfg.seq_len:
            raise ValueError(
                f"max_len={max_len} exceeds the model's positional table "
                f"(cfg.seq_len={cfg.seq_len})")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.dtype = cfg.dtype if dtype is None else dtype
        shape = (self.n_slots, cfg.n_layers, cfg.n_heads, self.max_len,
                 cfg.head_dim)
        self.k = jnp.zeros(shape, self.dtype)
        self.v = jnp.zeros(shape, self.dtype)
        # host-side per-slot token counts (== next write position)
        self.lengths = np.zeros(self.n_slots, np.int32)
        self._free: List[int] = list(range(self.n_slots))

    # -- slot accounting -----------------------------------------------------
    def alloc(self) -> Optional[int]:
        """Claim a free slot (None when full). Contents are whatever the
        previous occupant left — prefill overwrites them."""
        if not self._free:
            return None
        slot = self._free.pop(0)
        self.lengths[slot] = 0
        return slot

    def release(self, slot: int) -> None:
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free")
        self.lengths[slot] = 0
        self._free.append(slot)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def nbytes(self) -> int:
        return int(self.k.nbytes) + int(self.v.nbytes)

    def __repr__(self):
        return (f"KVCache(slots={self.n_slots}, max_len={self.max_len}, "
                f"occupied={self.occupancy}, {self.nbytes / 1e6:.1f}MB)")


class PagedKVCache:
    """Paged decode cache (ISSUE 7), the engine's one cache for the
    target model: a shared block pool
    plus per-slot block tables — vLLM-style PagedAttention memory, TPU
    shaped.

    Device side: the donated pool buffers ``pool``, a tuple whose layout
    the MODEL gives (``cfg.serving_model().pool_spec``, models/
    serving_api.py): every array is ``(n_blocks, n_layers, ...)``. The
    GPT family's is one pair

        kb, vb : (n_blocks, n_layers, n_heads, block_size, head_dim)

    (``kb`` / ``vb`` name ``pool[0]`` / ``pool[1]`` of such a pair); a
    latent-attention model's is one array ``(n_blocks, n_layers,
    block_size, row)``. The allocator, the tables and the gauges below
    know nothing of it.

    WHAT A BLOCK IS the model says too (``ServingModel.state_pad``). For
    the two above it is a run of ``block_size`` tokens, and the rest of
    this text describes that. For a model whose layers keep a recurrent
    state (``models/retention.py``) a block is ONE SEQUENCE'S WHOLE
    STATE at every layer, ``(n_layers, ...)`` of a fixed size
    (``state_blocks`` is then True): a slot's table has one entry,
    ``blocks_for(n)`` is 1 for any ``n > 0``, admission needs a free
    slot and a free state and never counts tokens (``alloc`` takes the
    state with the slot), ``grow`` after it can not fail, the
    ``kv_blocks_*`` gauges count states and ``kv_fragmentation`` reads
    0. ``block_size`` is then the granule a
    prefill chunk is padded to, the model's constant, whatever the
    caller passed. Block 0 stays the sink: the table of a lane without
    a request names it, and the model's decode step leaves such a lane
    out. The refcount, copy-on-write and splice machinery below serves
    the prefix cache, which such a model refuses.

    A model may also keep arrays a SLOT owns whole beside paged ones
    (``ServingModel.slot_spec``; ``models/sambay.py``: window rings and
    Mamba states beside one paged attention layer). They follow the
    paged arrays in ``pool``, are indexed by slot, not by block, and need
    no accounting here: a slot's are its own from ``alloc`` to
    ``release``, and the model's first chunk of a sequence starts them
    from zero. Blocks, tables and admission are as for any paged model.

    Block-major: one (block, layer) pair is one contiguous
    ``(n_heads, block_size, head_dim)`` run, which is what the paged
    steps (models/gpt.py) address — they write the new tokens' rows and
    read a table's blocks in place, at a layer, and never cut a layer's
    ``(n_blocks, n_heads, block_size, head_dim)`` slab out of the pool —
    and a whole block (every layer) is one contiguous run too, which
    keeps the copy-on-write block copy cheap.

    Unlike :class:`KVCache`, a slot does not own a contiguous max_len
    strip — it owns however many ``block_size``-token blocks its prompt
    and generation have actually filled, named in order by its block
    table. Cache memory is therefore proportional to LIVE tokens, and a
    prompt is admissible whenever enough free blocks exist, regardless
    of any per-slot length budget (up to ``cfg.seq_len``, the positional
    table).

    The first block of each shard range is RESERVED as that shard's
    garbage sink: it is never allocated, table padding entries (and the
    sink-filled tables of unoccupied batch lanes) point at it, so the
    batched decode step's stale-lane scatter writes land where no live
    slot ever reads. With the default ``shards=1`` that is pool block 0,
    exactly the ISSUE-7 layout.

    Multi-chip layout (ISSUE 10, ``shards=D``): the pool is partitioned
    into D contiguous shard ranges so the device buffers can shard over
    the mesh "data" axis — shard d owns blocks ``[d*per, (d+1)*per)``,
    slot s belongs to shard ``s // (n_slots // D)``, and a slot only
    ever allocates (and sinks its garbage) inside its OWN shard's range,
    so every block-table lookup, scatter and gather in the decode step
    stays local to the chip holding that slot's lane. Free lists are
    per-shard; admission asks :meth:`admit_shard` for the shard that can
    host a request (free slot + enough free blocks, most-free wins).

    Host side: the free lists, per-slot tables and lengths — request/
    block-granularity bookkeeping kept out of the jitted step, exactly
    like KVCache's slot accounting. Double-frees in the block free list
    raise ``AssertionError`` (a corrupted free list silently cross-wires
    two requests' caches — fail loudly instead). The pool exports
    ``kv_blocks_free`` / ``kv_blocks_used`` gauges and a
    ``kv_fragmentation`` percentage (share of used-block capacity not
    holding a live token) through the StatRegistry, aggregated over
    shards.

    Refcounted sharing (ISSUE 11, the radix prefix cache): every
    allocated block carries a reference count. ``grow``/``alloc_block``
    hand out blocks at refcount 1; :meth:`ref_block` lets another owner
    (a second slot's table, or the prefix tree itself) pin the same
    block, and releasing a table *unrefs* instead of freeing — a block
    only returns to its shard's free list when the LAST reference drops
    (``free_slot``-decrements-instead-of-freeing is what lets one
    prefilled system prompt fan out under thousands of streams).
    Writers never mutate a shared block: a slot that must extend a
    partially-filled shared block first :meth:`replace_block`\\ s it
    with a copy-on-write duplicate (the device-side copy is the
    engine's one-compile ``_cow_jit`` program). ``kv_fragmentation``
    counts each pool block's capacity once however many slots read it,
    so heavy sharing legitimately drives the gauge toward 0.
    """

    def __init__(self, cfg, n_slots: int, n_blocks: Optional[int] = None,
                 block_size: int = 16, dtype=None, shards: int = 1):
        if block_size < 1:
            raise ValueError(f"block_size={block_size} must be >= 1")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        model = cfg.serving_model()
        # a block is a sequence's whole state, not block_size tokens
        self.state_blocks = model.state_pad is not None
        self.block_size = int(model.state_pad if self.state_blocks
                              else block_size)
        self.shards = int(shards)
        if self.shards < 1:
            raise ValueError(f"shards={shards} must be >= 1")
        if self.n_slots % self.shards != 0:
            raise ValueError(f"n_slots={n_slots} not divisible by "
                             f"shards={shards}")
        # widest table any slot can need: the positional table is the
        # per-slot length ceiling
        self.table_width = 1 if self.state_blocks \
            else -(-cfg.seq_len // self.block_size)
        if n_blocks is None:
            # worst case every slot runs to seq_len, +1 sink per shard
            n_blocks = self.shards + self.n_slots * self.table_width
        self.n_blocks = int(n_blocks)
        if self.n_blocks % self.shards != 0:
            raise ValueError(f"n_blocks={self.n_blocks} not divisible by "
                             f"shards={shards}")
        self.blocks_per_shard = self.n_blocks // self.shards
        if self.blocks_per_shard < 2:
            raise ValueError(
                f"n_blocks={self.n_blocks} must give every shard >= 2 "
                "blocks (the first block of each shard range is its "
                "reserved garbage sink)")
        self.dtype = cfg.dtype if dtype is None else dtype
        specs = model.pool_spec(cfg, self.n_blocks, self.block_size)
        # a slot's own arrays follow the paged ones, in their own types
        own = () if model.slot_spec is None \
            else model.slot_spec(cfg, self.n_slots, self.block_size)
        self.pool = tuple(
            jnp.zeros(a.shape, a.dtype if dtype is None else dtype)
            for a in specs) + tuple(jnp.zeros(a.shape, a.dtype) for a in own)
        self.lengths = np.zeros(self.n_slots, np.int32)
        self.block_tables: List[List[int]] = [[] for _ in range(self.n_slots)]
        # per-shard free lists; the first block of each range is the sink
        self._free: List[List[int]] = [
            list(range(d * self.blocks_per_shard + 1,
                       (d + 1) * self.blocks_per_shard))
            for d in range(self.shards)]
        self._free_set = set(b for free in self._free for b in free)
        self._refs: dict = {}      # allocated block -> reference count
        self._slot_free: List[int] = list(range(self.n_slots))
        self._update_gauges()

    # -- shard topology ------------------------------------------------------
    @property
    def slots_per_shard(self) -> int:
        return self.n_slots // self.shards

    def shard_of(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def sink_of(self, shard: int) -> int:
        return shard * self.blocks_per_shard

    @property
    def max_slot_blocks(self) -> int:
        """Largest block count one slot can ever own (its shard's pool
        minus the sink) — the submit-time can-never-fit bound."""
        return self.blocks_per_shard - 1

    # -- slot accounting (same surface as KVCache) ---------------------------
    def alloc(self, prefer_shard: Optional[int] = None) -> Optional[int]:
        """Claim a free slot (None when there is none). Where a block is
        a sequence's state the slot takes its one state with it, so
        admission reserves it: None too when the slot's shard has no
        free state."""
        for i, s in enumerate(self._slot_free):
            shard = self.shard_of(s)
            if prefer_shard is not None and shard != prefer_shard:
                continue
            if self.state_blocks and not self._free[shard]:
                continue
            slot = self._slot_free.pop(i)
            break
        else:
            return None
        self.lengths[slot] = 0
        self.block_tables[slot] = []
        if self.state_blocks:
            self.grow(slot, 1)
        return slot

    def release(self, slot: int) -> None:
        if slot in self._slot_free:
            raise ValueError(f"slot {slot} is already free")
        self.free_blocks(self.block_tables[slot])
        self.block_tables[slot] = []
        self.lengths[slot] = 0
        self._slot_free.append(slot)

    @property
    def free_count(self) -> int:
        return len(self._slot_free)

    @property
    def occupancy(self) -> int:
        return self.n_slots - len(self._slot_free)

    # -- block accounting ----------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        if self.state_blocks:
            return min(int(n_tokens), 1)
        return -(-int(n_tokens) // self.block_size)

    def can_admit(self, n_tokens: int) -> bool:
        """Some shard has enough free blocks to cache ``n_tokens``? (The
        admission gate — replaces the fixed engine's ``prompt >=
        max_len`` hard reject; pair with :meth:`admit_shard` to also
        require a free slot in that shard.)"""
        need = self.blocks_for(n_tokens)
        return any(need <= len(free) for free in self._free)

    def admit_shard(self, n_tokens: int) -> Optional[int]:
        """The shard that should host a new request needing ``n_tokens``
        cached: a free slot AND enough free blocks, most free blocks
        wins (keeps shard load balanced). None when no shard qualifies."""
        need = self.blocks_for(n_tokens)
        free_slots = {self.shard_of(s) for s in self._slot_free}
        best = None
        for d in range(self.shards):
            if d in free_slots and need <= len(self._free[d]):
                if best is None or len(self._free[d]) > len(self._free[best]):
                    best = d
        return best

    @property
    def free_slot_shards(self) -> set:
        """Shards that currently have at least one free slot."""
        return {self.shard_of(s) for s in self._slot_free}

    @property
    def free_blocks_count(self) -> int:
        return sum(len(free) for free in self._free)

    @property
    def used_blocks_count(self) -> int:
        return self.n_blocks - self.shards - self.free_blocks_count

    def free_blocks_of(self, shard: int) -> int:
        return len(self._free[shard])

    def grow(self, slot: int, n_tokens: int) -> bool:
        """Extend ``slot``'s table to cover positions < n_tokens, from
        its OWN shard's free list. All-or-nothing: returns False
        (allocating nothing) when that list cannot supply every needed
        block. Fresh blocks start at refcount 1 (this table)."""
        need = self.blocks_for(n_tokens)
        table = self.block_tables[slot]
        extra = need - len(table)
        if extra <= 0:
            return True
        free = self._free[self.shard_of(slot)]
        if extra > len(free):
            return False
        for _ in range(extra):
            b = free.pop(0)
            self._free_set.discard(b)
            self._refs[b] = 1
            table.append(b)
        self._update_gauges()
        return True

    def alloc_block(self, shard: int) -> Optional[int]:
        """One free block from ``shard``'s list at refcount 1 (the
        copy-on-write destination), or None when the shard is dry."""
        free = self._free[shard]
        if not free:
            return None
        b = free.pop(0)
        self._free_set.discard(b)
        self._refs[b] = 1
        self._update_gauges()
        return b

    def ref_block(self, block: int) -> None:
        """Pin one more reference on an allocated block (a second slot's
        table, or the prefix tree adopting it)."""
        b = int(block)
        if b not in self._refs:
            raise AssertionError(
                f"KV block {b} ref'd while not allocated (use-after-free)")
        self._refs[b] += 1

    def ref_count(self, block: int) -> int:
        return self._refs.get(int(block), 0)

    def unref_block(self, block: int) -> None:
        """Drop one reference; the LAST drop returns the block to its
        shard's free list (this is ``free_slot`` decrementing instead of
        freeing — shared prefix blocks survive their first owner)."""
        b = int(block)
        if b in self._free_set:
            raise AssertionError(
                f"KV block {b} double-freed (free-list corruption)")
        shard, local = divmod(b, self.blocks_per_shard)
        if not 0 <= shard < self.shards or local == 0:
            raise AssertionError(f"KV block {b} outside pool or a "
                                 "reserved shard sink")
        refs = self._refs.get(b)
        if refs is None:
            raise AssertionError(
                f"KV block {b} unref'd while not allocated "
                "(refcount corruption)")
        if refs > 1:
            self._refs[b] = refs - 1
            return
        del self._refs[b]
        self._free[shard].append(b)
        self._free_set.add(b)

    def free_blocks(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.unref_block(b)
        self._update_gauges()

    def splice(self, slot: int, blocks: Sequence[int]) -> None:
        """Seed an empty slot table with already-allocated (shared)
        blocks, taking one reference per block — the prefix-cache hit
        path. Blocks must belong to the slot's shard (the decode
        step's lookups stay chip-local)."""
        table = self.block_tables[slot]
        if table:
            raise AssertionError(
                f"splice into slot {slot} with a non-empty table")
        shard = self.shard_of(slot)
        for b in blocks:
            if int(b) // self.blocks_per_shard != shard:
                raise AssertionError(
                    f"KV block {b} spliced across shards "
                    f"(slot {slot} is shard {shard})")
            self.ref_block(b)
            table.append(int(b))
        self._update_gauges()

    def replace_block(self, slot: int, index: int, new_block: int) -> int:
        """Swap one table entry for ``new_block`` (the copy-on-write
        commit: the caller has already device-copied the old block's
        rows into ``new_block`` via the engine's cow program). Drops
        this table's reference on the old block and returns it."""
        table = self.block_tables[slot]
        old = table[index]
        table[index] = int(new_block)
        self.unref_block(old)
        self._update_gauges()
        return old

    def table_row(self, slot: int) -> np.ndarray:
        """This slot's table as a fixed-width int32 row, sink-padded
        (with the slot's OWN shard sink, so padding lookups stay
        shard-local)."""
        row = np.full(self.table_width,
                      self.sink_of(self.shard_of(slot)), np.int32)
        table = self.block_tables[slot]
        row[:len(table)] = table
        return row

    def tables_array(self, slots=None) -> np.ndarray:
        """(n_slots, table_width) int32 for the batched decode step; rows
        not in ``slots`` (and all padding) point at their shard's
        garbage sink."""
        out = np.empty((self.n_slots, self.table_width), np.int32)
        for s in range(self.n_slots):
            out[s] = self.sink_of(self.shard_of(s))
        for s in (range(self.n_slots) if slots is None else slots):
            table = self.block_tables[s]
            out[s, :len(table)] = table
        return out

    # -- gauges --------------------------------------------------------------
    def _update_gauges(self) -> None:
        used = self.used_blocks_count
        KV_BLOCKS_FREE.set(self.free_blocks_count)
        KV_BLOCKS_USED.set(used)
        # a state is whole whatever its sequence's length: nothing to fragment
        cap = 0 if self.state_blocks else used * self.block_size
        live = int(self.lengths.sum())
        KV_FRAGMENTATION.set(
            0 if cap == 0 else int(round(100.0 * (1.0 - min(1.0, live / cap)))))

    update_gauges = _update_gauges

    # -- the per-head pair's two names ---------------------------------------
    def _pair(self):
        if len(self.pool) != 2:
            raise AttributeError(
                f"this pool is {len(self.pool)} array(s), not a key/value "
                "pair: address it as .pool")
        return self.pool

    @property
    def kb(self):
        return self._pair()[0]

    @kb.setter
    def kb(self, value):
        self.pool = (value, self._pair()[1])

    @property
    def vb(self):
        return self._pair()[1]

    @vb.setter
    def vb(self, value):
        self.pool = (self._pair()[0], value)

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.pool)

    def __repr__(self):
        return (f"PagedKVCache(slots={self.n_slots}, "
                f"blocks={self.n_blocks}x{self.block_size}, "
                f"shards={self.shards}, "
                f"used={self.used_blocks_count}, occupied={self.occupancy}, "
                f"{self.nbytes / 1e6:.1f}MB)")
