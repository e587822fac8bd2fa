"""Paged KV cache: fixed-size blocks, free-list allocator, slot page tables.

The continuous-batching scheduler (serving/scheduler.py) bounds each slot's
KV memory by its *actual* sequence length instead of the serving window:
the physical cache is a pool of ``num_blocks`` fixed-size blocks shared by
all slots, and a per-slot **page table** maps logical block index -> physical
block id.  Blocks are allocated on demand (prompt blocks at admission, one
block whenever decode crosses a block boundary) and returned to the free
list the moment a request retires, so the pool can be oversubscribed
relative to ``num_slots * max_len`` (DESIGN.md §8).

Layout per layer stack (mirrors ``models.lm.init_cache`` stack names)::

    {"kv": (L, num_blocks, block_size, KV * 2 * hd),   # int8 when quantized
     ["kv_scale": (L, num_blocks, block_size, KV * 2) bf16,]
     "page_table": (num_slots, max_blocks) int32}

A block row holds one position of every KV head: head ``g``'s keys in the
lanes ``[g * 2hd, g * 2hd + hd)`` and its values in the next ``hd``
(``models.kvcache.fuse_kv``).  With ``2 * hd`` a multiple of 128 a row is a
whole number of vregs, so the chip stores the pool row-major and unpadded
and the paged-attention kernel (``kernels/paged_attention.py``) reads it as
it lies.  A (..., KV, hd) layout would not do: the chip would store it
transposed to avoid padding ``hd`` = 64 lanes, and every kernel call would
first copy the pool into a layout the kernel can read.

``page_table`` rides inside the cache tree, one per stack.  The decode
forward (``models.lm``) carries the stack's pool leaves through its layer
loop and hands each layer the whole pool, its layer index and the
(num_slots, max_blocks) table; ``models.attention`` writes the step's rows
into the pool in place and takes the paged decode path whenever the key is
present.  **Block 0 is a reserved sink**: retired slots' page tables point
at it, so the fixed-shape decode step can keep writing for inactive rows
without corrupting live blocks; reads past a slot's length are masked
exactly like contiguous-cache padding.

Host-side bookkeeping (:class:`BlockAllocator`, :class:`PageTableManager`)
is plain numpy — the device only ever sees the pool leaves and the int32
table, and every jitted step keeps a static shape.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

__all__ = ["BlockAllocator", "PageTableManager", "blocks_for",
           "init_paged_cache", "with_page_table", "insert_prefill_paged",
           "init_slot_cache", "insert_prefill_rows", "paged_pool_bytes"]


def blocks_for(length: int, block_size: int) -> int:
    """Number of blocks covering ``length`` positions."""
    return -(-max(int(length), 0) // block_size)


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` physical blocks.

    Block 0 is reserved as the sink (module docstring) and never handed
    out; ``alloc`` is all-or-nothing so a request can never be admitted
    with a partial page set.

    Refcounts enable the radix prefix cache's copy-on-write sharing
    (serving/radix_cache.py): a block allocated once (``rc == 1``) may be
    ``ref``'d by every slot whose prompt matched it in the trie, and only
    returns to the free list when the last holder ``free``'s it.  Non-shared
    operation is unchanged — rc stays 1 from alloc to free.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the reserved sink)")
        self.num_blocks = num_blocks
        self._free: deque = deque(range(1, num_blocks))
        self._rc = np.zeros(num_blocks, np.int32)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        return int(self._rc[block])

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks (rc=1 each), or None (no side effect) if
        unavailable."""
        if n > len(self._free):
            return None
        blocks = [self._free.popleft() for _ in range(n)]
        self._rc[blocks] = 1
        return blocks

    def ref(self, blocks: List[int]) -> None:
        """Add one holder to each (already-allocated) block."""
        for b in blocks:
            if not 1 <= b < self.num_blocks or self._rc[b] < 1:
                raise ValueError(f"ref on unallocated block id {b}")
            self._rc[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one holder per block; last holder returns it to the pool."""
        for b in blocks:
            if not 1 <= b < self.num_blocks or self._rc[b] < 1:
                raise ValueError(f"freeing invalid block id {b}")
            self._rc[b] -= 1
            if self._rc[b] == 0:
                self._free.append(b)


class PageTableManager:
    """Slot page tables + allocator, the scheduler's memory authority.

    ``table`` is the (num_slots, max_blocks) int32 array shipped to the
    device each step; unallocated entries stay 0 (the sink block).
    """

    def __init__(self, num_slots: int, max_blocks: int, num_blocks: int,
                 block_size: int):
        self.block_size = block_size
        self.max_blocks = max_blocks
        self.allocator = BlockAllocator(num_blocks)
        self.table = np.zeros((num_slots, max_blocks), np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in range(num_slots)]
        # bumped on every table mutation — lets the scheduler skip the
        # host->device table upload on steps where nothing changed
        self.version = 0
        # most blocks ever simultaneously held (telemetry: the pool size a
        # non-oversubscribed run of this workload would have needed)
        self.high_water = 0

    def allocated(self, slot: int) -> int:
        return len(self._slot_blocks[slot])

    def blocks(self, slot: int) -> List[int]:
        """The slot's physical blocks in logical order (copy)."""
        return list(self._slot_blocks[slot])

    @property
    def used_blocks(self) -> int:
        """Blocks currently held by slots (sink block excluded)."""
        return self.allocator.num_blocks - 1 - self.allocator.free_blocks

    def admit(self, slot: int, length: int,
              shared: Optional[List[int]] = None) -> bool:
        """Allocate pages covering ``length`` positions for a fresh slot.

        ``shared``: physical blocks matched in the radix prefix cache
        (serving/radix_cache.py) forming the head of the slot's logical
        pages.  They are refcounted (copy-on-write — decode never writes
        into them; writes start past the shared prefix in slot-private
        blocks) and only the remainder is freshly allocated, all-or-nothing.
        """
        shared = list(shared or [])
        need = blocks_for(length, self.block_size)
        if need > self.max_blocks:
            raise ValueError(
                f"request needs {need} blocks > max_blocks_per_slot "
                f"{self.max_blocks}; raise max_len/block budget")
        if len(shared) > need:
            raise ValueError(f"{len(shared)} shared blocks exceed the "
                             f"{need}-block request")
        blocks = self.allocator.alloc(need - len(shared))
        if blocks is None:
            return False
        if self._slot_blocks[slot]:
            raise RuntimeError(f"slot {slot} admitted while holding blocks")
        self.allocator.ref(shared)
        self._slot_blocks[slot] = shared + blocks
        self.table[slot, :] = 0
        self.table[slot, :need] = self._slot_blocks[slot]
        self.version += 1
        self.high_water = max(self.high_water, self.used_blocks)
        return True

    def ensure(self, slot: int, pos: int) -> bool:
        """Grow the slot's pages so logical position ``pos`` is writable."""
        need = blocks_for(pos + 1, self.block_size)
        held = self._slot_blocks[slot]
        if need <= len(held):
            return True
        if need > self.max_blocks:
            return False
        blocks = self.allocator.alloc(need - len(held))
        if blocks is None:
            return False
        self.table[slot, len(held):need] = blocks
        held.extend(blocks)
        self.version += 1
        self.high_water = max(self.high_water, self.used_blocks)
        return True

    def trim(self, slot: int, length: int) -> int:
        """Shrink a slot's pages to cover only ``length`` positions.

        The speculative-decode rollback primitive (DESIGN.md §13): a
        rejected draft leaves KV written past the committed length, which
        the masks already hide — but the tail *blocks* the lookahead
        allocated stay held.  Under pool pressure the scheduler trims them
        back to the committed length so waiting requests can admit.
        Returns the number of blocks freed (0 when nothing to trim).
        """
        keep = blocks_for(length, self.block_size)
        held = self._slot_blocks[slot]
        if keep >= len(held):
            return 0
        tail = held[keep:]
        del held[keep:]
        self.allocator.free(tail)
        self.table[slot, keep:] = 0
        self.version += 1
        return len(tail)

    def release(self, slot: int) -> None:
        """Retire a slot: free its blocks, point its table at the sink."""
        self.allocator.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self.table[slot, :] = 0
        self.version += 1


# --------------------------------------------------------------------------
# Device-side cache trees
# --------------------------------------------------------------------------

def _stack_layers(cfg: ModelConfig) -> Dict[str, int]:
    """Stack-name -> layer-count map matching ``models.lm.init_cache``."""
    if cfg.num_experts and cfg.first_k_dense:
        return {"dense_stack": cfg.first_k_dense,
                "moe_stack": cfg.num_layers - cfg.first_k_dense}
    if cfg.num_experts:
        return {"moe_stack": cfg.num_layers}
    return {"stack": cfg.num_layers}


def supports_paged(cfg: ModelConfig) -> bool:
    """Families whose decode cache is plain per-layer GQA K/V blocks."""
    return cfg.family in ("dense", "moe") and not cfg.use_mla


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_blocks: int,
                     block_size: int, max_blocks: int) -> Dict[str, Any]:
    """Allocate the block pools (+ zeroed page tables) for every stack."""
    if not supports_paged(cfg):
        raise ValueError(
            f"paged KV cache supports dense/moe GQA families, not "
            f"{cfg.family}{'/mla' if cfg.use_mla else ''} — the scheduler "
            f"falls back to the contiguous slot cache (init_slot_cache)")
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    quant = cfg.kv_cache_dtype == "int8"
    kv_dtype = jnp.int8 if quant else cfg.cdtype

    def pool(n: int) -> Dict[str, Any]:
        d = {"kv": jnp.zeros((n, num_blocks, block_size, kv * 2 * hd),
                             kv_dtype)}
        if quant:
            d["kv_scale"] = jnp.zeros((n, num_blocks, block_size, kv * 2),
                                      jnp.bfloat16)
        d["page_table"] = jnp.zeros((num_slots, max_blocks), jnp.int32)
        return d

    return {name: pool(n) for name, n in _stack_layers(cfg).items()}


def with_page_table(cache: Dict[str, Any], table: np.ndarray,
                    sharding=None) -> Dict[str, Any]:
    """Swap the (num_slots, max_blocks) page table into every stack.

    Called when the table changed (admission / growth / retirement): a
    few KiB per stack, each stack its own buffer, since the cache is
    donated.  ``sharding``: placement for the uploaded table — pass the sharding the
    compiled step echoes its table output with (NamedSharding(mesh, P())
    under the serve step's axis_rules), so steady-state steps that feed the
    echoed cache back hit the same executable signature."""
    def put():
        return (jax.device_put(table, sharding) if sharding is not None
                else jnp.asarray(table))

    return {name: dict(stack, page_table=put())
            for name, stack in cache.items()}


def paged_pool_bytes(cache: Dict[str, Any]) -> int:
    """Persistent device bytes of the block pools (page tables included)."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache))


def insert_prefill_paged(cache: Dict[str, Any], prefill_cache: Dict[str, Any],
                         page_row: jax.Array) -> Dict[str, Any]:
    """Scatter a batch-1 prefill cache into one slot's pages.

    ``prefill_cache`` leaves are (L, 1, P, KV, hd) from a ``mode="full"``
    forward; ``page_row`` is the slot's (max_blocks,) page-table row.  All P
    padded positions are written — tail positions beyond the prompt map to
    the slot's own partially-filled last block or to the sink block, and are
    either overwritten by decode or masked by the live length.  Quantizes
    on the way in when the pool is int8.  Pure function of arrays: jit it
    once, with the cache donated so the pool is written in place.
    """
    from repro.models import kvcache as kvq
    out = {}
    for name, stack in cache.items():
        bs = stack["kv"].shape[2]
        k_new = prefill_cache[name]["k"][:, 0]  # (L, P, KV, hd)
        rows = kvq.fuse_kv(k_new, prefill_cache[name]["v"][:, 0])
        # every (layer, position) row by its full index: a scatter whose
        # window is one row keeps the pool's layout, so it updates in place
        # (with the layer axis as a window dim XLA transposes the whole
        # pool in and out)
        j = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
        layer = jnp.arange(rows.shape[0], dtype=jnp.int32)[:, None]
        blk, off = page_row.astype(jnp.int32)[j // bs], j % bs
        new = dict(stack)
        if "kv_scale" in stack:
            rows, scales = kvq.quantize_rows(rows, k_new.shape[2])
            new["kv_scale"] = stack["kv_scale"].at[layer, blk, off].set(
                scales)
        new["kv"] = stack["kv"].at[layer, blk, off].set(
            rows.astype(stack["kv"].dtype))
        out[name] = new
    return out


# --------------------------------------------------------------------------
# Contiguous slot cache (fallback for MLA latent caches)
# --------------------------------------------------------------------------
#
# MLA's latent cache is already rank-compressed and tiny per position, so
# the scheduler keeps it contiguous: each slot owns row ``s`` of a regular
# (L, num_slots, max_len, ...) cache and decodes at its own position via the
# per-row write path in models/attention.py.  Admission/retirement need no
# allocator — the slot row is the allocation.

def init_slot_cache(cfg: ModelConfig, num_slots: int, max_len: int):
    """Contiguous per-slot cache — ``models.lm.init_cache`` sized to slots."""
    from repro.models import lm as lm_mod
    return lm_mod.init_cache(cfg, num_slots, max_len)


def insert_prefill_rows(cache: Any, prefill_cache: Any, slot) -> Any:
    """Write a batch-1 prefill cache into slot row ``slot``.

    Generic over cache layouts: every leaf whose name has a kv-seq axis gets
    the prefill values at positions [0, P); the prefill leaf is broadcast /
    quantized to the cache layout where needed.  Stateful leaves (SSM) are
    written wholesale into the slot row.  Pure function of arrays: jit once.
    """
    from repro.models import kvcache as kvq

    def walk(c, p, name):
        if isinstance(c, dict):
            out = {}
            for k in c:
                if k in ("k_scale", "v_scale") and isinstance(p, dict) \
                        and k not in p:
                    # int8 cache + bf16 prefill: scales come from quantizing
                    # the matching k/v prefill leaf below.
                    src = p[k[0]]  # "k" or "v"
                    out[k] = walk(c[k], kvq.quantize_kv(src)[1], k)
                elif isinstance(p, dict) and k in p:
                    src = p[k]
                    if k in ("k", "v") and c[k].dtype == jnp.int8:
                        src = kvq.quantize_kv(src)[0]
                    out[k] = walk(c[k], src, k)
                else:
                    out[k] = c[k]
            return out
        # c: (L, num_slots, ...), p: (L, 1, ...)
        zeros = (0,) * (c.ndim - 2)
        start = (jnp.zeros((), jnp.int32),
                 jnp.asarray(slot, jnp.int32)) + zeros
        return jax.lax.dynamic_update_slice(c, p.astype(c.dtype), start)

    return walk(cache, prefill_cache, "")
