"""Paged decode attention Pallas TPU kernel: one query token per slot
against the live blocks of a paged KV pool, read where the pool lies
(DESIGN.md §8).

Pool layout (``serving/paged_cache.py``): one array per layer stack,
``(L, num_blocks, block_size, KV * 2 * hd)``.  KV head ``g`` owns the lanes
``[g * 2hd, (g + 1) * 2hd)``: its keys in the first ``hd``, its values in
the second.  A block row is a whole number of 128-lane vregs whenever
``2 * hd`` is, so the chip stores the pool row-major and unpadded, and the
kernel takes it as it is (left in HBM), with no relayout.

The grid runs over groups of ``SLOTS_PER_STEP`` slots at most, so the
query and output blocks in VMEM stay the same size whatever the slot
count.  One walk crosses the whole grid: it visits the live chunks of the
live slots in turn, ``pages`` blocks per chunk, and never a retired slot
(one whose page table starts at the sink block 0: its output is zeros,
and the scheduler reads no token of it).  The count of chunks is data, so
nothing is compiled per length.  The blocks of the next chunk, in the same
group or a later one, are DMA'd from the pool, through the page table and
the layer index (scalar prefetch), into one half of a two-chunk VMEM
buffer while the chunk in the other half is scored; a block past the
slot's length is not fetched (its stale rows are masked), and a chunk with
one live block is scored on that block alone.  A grid over (slot, chunk)
with one pipelined operand per block was measured slower: its per-operand
cost is paid on every grid step, live or not, ~77 ns per (slot, block) of
the page table on one v5e.

Each KV head's query heads are scored together against that head's lanes
of the fetched rows.  Where ``hd`` is a whole number of vregs the keys and
values are read apart; otherwise (``hd`` = 64) the head's 128 lanes are
read once, the query is zero in the value lanes, and the weighted sum
lands the output in them.  The online softmax runs in float32 (logits,
running max, denominator, accumulator); probabilities enter the value
matmul in the pool's dtype, as ``models.attention.dense_attention`` does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention"]

_NEG_INF = -1e30
#: bytes of pool rows a chunk fetches: sixteen of smollm-360m's 16-row
#: blocks (KV 5 x 2 x hd 64, bfloat16); wider rows take fewer blocks
CHUNK_BYTES = 16 * 16 * 640 * 2
#: slots whose query and output blocks one grid step holds in VMEM
SLOTS_PER_STEP = 16


def _kernel(layer_ref, table_ref, len_ref, q_ref, pool_ref, o_ref,
            buf, sems, m_ref, l_ref, acc_ref, order_ref, walk_ref, *,
            pages: int, max_blocks: int, hd: int):
    bs = pool_ref.shape[2]
    chunk = pages * bs
    slots = len_ref.shape[0]
    group, kv = o_ref.shape[0], o_ref.shape[1]
    kw, vw = q_ref.shape[-1], o_ref.shape[-1]  # lanes read as keys, values
    layer = layer_ref[0]
    first = pl.program_id(0) * group

    def live(slot):
        return table_ref[slot * max_blocks] != 0  # not at the sink block

    def chunks(slot):
        return (len_ref[slot] + chunk - 1) // chunk

    def slot_at(p):
        return order_ref[jnp.minimum(p, slots - 1)]

    def copies(slot, c, half):
        """(live, descriptor) of each block of chunk ``c`` of ``slot``."""
        out = []
        for j in range(pages):
            page = c * pages + j
            block = table_ref[slot * max_blocks
                              + jnp.minimum(page, max_blocks - 1)]
            out.append((page * bs < len_ref[slot], pltpu.make_async_copy(
                pool_ref.at[layer, block], buf.at[half, pl.ds(j * bs, bs)],
                sems.at[half])))
        return out

    def fetch(slot, c, half):
        for is_live, cp in copies(slot, c, half):
            pl.when(is_live)(cp.start)

    @pl.when(pl.program_id(0) == 0)
    def _start():
        def add(slot, n):  # the live slots, in order
            order_ref[n] = slot
            return n + live(slot).astype(jnp.int32)

        n = jax.lax.fori_loop(0, slots, add, jnp.int32(0))
        walk_ref[0] = 0  # position in ``order``
        walk_ref[1] = 0  # chunk of that slot
        walk_ref[2] = 0  # chunks walked: the buffer half
        walk_ref[3] = n
        pl.when(n > 0)(lambda: fetch(order_ref[0], 0, 0))

    o_ref[...] = jnp.zeros_like(o_ref)  # retired slots
    work = jax.lax.fori_loop(
        0, group, lambda s, w: w + jnp.where(
            live(first + s), chunks(first + s), 0), jnp.int32(0))
    n_live = walk_ref[3]

    def step(_, carry):
        p, c, k = carry
        slot, half = slot_at(p), k % 2
        last = c + 1 >= chunks(slot)
        nxt_p, nxt_c = jnp.where(last, p + 1, p), jnp.where(last, 0, c + 1)
        pl.when(nxt_p < n_live)(
            lambda: fetch(slot_at(nxt_p), nxt_c, 1 - half))
        for is_live, cp in copies(slot, c, half):
            pl.when(is_live)(cp.wait)

        @pl.when(c == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        length = len_ref[slot]

        def attend(n: int):
            """Online-softmax update over the chunk's first ``n`` rows."""
            pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
            key = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            for g in range(kv):
                lo = g * 2 * hd
                k_rows = buf[half, pl.ds(0, n), pl.ds(lo, kw)]
                v_rows = k_rows if kw == 2 * hd else \
                    buf[half, pl.ds(0, n), pl.ds(lo + hd, vw)]
                # rows past the length are stale, perhaps never written
                v_rows = jnp.where(pos < length, v_rows, 0).astype(
                    v_rows.dtype)
                s = jax.lax.dot_general(q_ref[slot - first, g], k_rows,
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = jnp.where(key < length, s, _NEG_INF)
                m_prev = m_ref[g]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                pr = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[g] = l_ref[g] * corr + jnp.sum(pr, axis=-1,
                                                     keepdims=True)
                acc_ref[g] = acc_ref[g] * corr + jax.lax.dot_general(
                    pr.astype(v_rows.dtype), v_rows,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[g] = m_new

        # a chunk with one live block (short tails) scores it alone
        one_block = length - c * chunk <= bs
        pl.when(one_block)(lambda: attend(bs))
        pl.when(jnp.logical_not(one_block))(lambda: attend(chunk))

        @pl.when(last)
        def _finish():
            o_ref[slot - first] = (acc_ref[...] / jnp.maximum(
                l_ref[...], 1e-30)).astype(o_ref.dtype)

        return nxt_p, nxt_c, k + 1

    p, c, k = jax.lax.fori_loop(0, work, step,
                                (walk_ref[0], walk_ref[1], walk_ref[2]))
    walk_ref[0], walk_ref[1], walk_ref[2] = p, c, k


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                    page_table: jax.Array, lengths: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Decode attention of one token per slot over a paged pool.

    q: (B, H, hd), pre-scaled; pool: (L, num_blocks, block_size,
    KV * 2 * hd) in the module's layout; layer: () int32, the pool's layer
    to read; page_table: (B, max_blocks) int32, a retired slot's row at
    the sink block 0; lengths: (B,) int32, each slot's live positions
    (>= 1, the token being decoded included).  Returns (B, H, hd) in q's
    dtype, zeros for retired slots.
    """
    b, h, hd = q.shape
    _, _, bs, width = pool.shape
    kv = width // (2 * hd)
    if width != kv * 2 * hd or h % kv:
        raise ValueError(f"pool width {width} is not KV * 2 * {hd} for "
                         f"{h} query heads")
    max_blocks = page_table.shape[1]
    pages = max(1, min(max_blocks,
                       CHUNK_BYTES // (bs * width * pool.dtype.itemsize)))
    group = max(d for d in range(1, min(b, SLOTS_PER_STEP) + 1)
                if b % d == 0)
    # keys and values apart where hd fills whole vregs, else both from the
    # head's 2 * hd lanes, with the query zero in the value lanes
    kw = vw = hd if hd % 128 == 0 else 2 * hd
    qh = q.reshape(b, kv, h // kv, hd)
    if kw > hd:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, kw - hd)))
    kernel = functools.partial(_kernel, pages=pages, max_blocks=max_blocks,
                               hd=hd)
    block = lambda w: pl.BlockSpec(  # noqa: E731
        (group, kv, h // kv, w), lambda i, *_: (i, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b // group,),
            in_specs=[block(kw), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block(vw),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, width), pool.dtype),  # 2 chunks
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((kv, h // kv, 1), jnp.float32),  # running max m
                pltpu.VMEM((kv, h // kv, 1), jnp.float32),  # denominator l
                pltpu.VMEM((kv, h // kv, vw), jnp.float32),  # accumulator
                pltpu.SMEM((b,), jnp.int32),  # the live slots, in order
                pltpu.SMEM((4,), jnp.int32),  # the walk's place
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv, h // kv, vw), q.dtype),
        # the walk carries from one grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      qh.astype(q.dtype), pool)
    return out[..., vw - hd:].reshape(b, h, hd)
