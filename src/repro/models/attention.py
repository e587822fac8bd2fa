"""Attention: GQA (bias / qk-norm / cross variants) and MLA (deepseek-v3),
with a memory-efficient blockwise softmax for long sequences and an
absorbed-matmul decode path for MLA.

All projections route through ``common.linear`` and are therefore LRD-aware.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import shard
from repro.kernels import ops as kops
from repro.models import common
from repro.models.common import Params, apply_rope, linear, rmsnorm, rmsnorm_init
from repro.obs import scope

# --------------------------------------------------------------------------
# Softmax attention cores
# --------------------------------------------------------------------------

def dense_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                    kv_len: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,Dk/Dv). GQA via head-group broadcast.

    K/V stay in their storage dtype with fp32 ACCUMULATION via
    preferred_element_type — an explicit .astype(f32) on the operands
    materializes an fp32 copy of the whole KV cache per layer (§Perf C1:
    2 x 435 GB/step/device for qwen2-72b decode_32k, 82% of all traffic).

    ``kv_len`` masks decode reads beyond the live length: (B,) gives one
    length per row; (B, Sq) gives a length per row *per query position* —
    the chunked speculative verify step (serving/speculative.py) feeds k+1
    tokens at once and position j may only attend to kv_len[b, j] keys, so
    in-chunk causality comes from the same mask that hides stale tail KV.
    """
    b, sq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d)
    logits = jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                        preferred_element_type=jnp.float32)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        tpos = jnp.arange(k.shape[1])
        logits = jnp.where(qpos[:, None] >= tpos[None, :], logits, -1e30)
    if kv_len is not None:  # decode: mask beyond current length
        logits = _mask_kv_len(logits, k.shape[1], kv_len)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _mask_kv_len(logits, t: int, kv_len: jax.Array) -> jax.Array:
    """Apply a per-row (B,) or per-row-per-query (B, Sq) length mask to
    (b, kv, g, q, t) decode logits."""
    if kv_len.ndim == 2:  # (B, Sq): chunked decode, per-query lengths
        valid = jnp.arange(t)[None, None, :] < kv_len[:, :, None]  # (b, q, t)
        return jnp.where(valid[:, None, None, :, :], logits, -1e30)
    valid = jnp.arange(t)[None, :] < kv_len.reshape(-1, 1)
    return jnp.where(valid[:, None, None, None, :], logits, -1e30)


def int8_dense_attention(q, k_q, k_scale, v_q, v_scale, *,
                         kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Decode attention straight on int8 KV pools (DESIGN.md §11).

    The per-(batch, position, head) quantization scales are rank-1 in the
    (q, t) logit matrix, so they fold in AFTER the QKᵀ matmul (k) and into
    the probabilities BEFORE the PV matmul (v) — no dequantized
    (B, T, KV, hd) copy of either pool is ever materialized, where the
    bf16 round trip materializes both per layer per step.  Algebraically
    identical to dequantize-then-attend (same products, different
    association), asserted ≤1e-5 in tests/test_int8_decode.py.

    q: (B, Sq, H, D); k_q/v_q: (B, T, KV, D) int8; scales: (B, T, KV, 1).
    """
    b, sq, h, d = q.shape
    t, kv = k_q.shape[1], k_q.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d)
    # (B, T, KV, 1) -> (B, KV, 1, 1, T): broadcast over (g, q), rank-1 in t
    ks = jnp.moveaxis(k_scale[..., 0], 1, 2)[:, :, None, None, :]
    vs = jnp.moveaxis(v_scale[..., 0], 1, 2)[:, :, None, None, :]
    logits = jnp.einsum("bqkgd,btkd->bkgqt", qg, k_q,
                        preferred_element_type=jnp.float32)
    logits = logits * ks.astype(jnp.float32)
    if kv_len is not None:
        logits = _mask_kv_len(logits, t, kv_len)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p * vs.astype(jnp.float32), v_q,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, block_q: int, block_kv: int) -> jax.Array:
    """Flash-style online-softmax attention in pure JAX.

    Outer scan over q blocks (output written per block, bf16), inner scan
    over kv blocks with an (m, l, acc) online-softmax carry sized one
    q-block — peak temp O(B * bq * H * D) fp32 instead of O(B*Sq*Sk).
    Causal masking is applied per block pair; block pairs entirely in the
    future still run (masked) — the ~2x FLOPs overhead vs. ideal causal
    shows up in the roofline MODEL_FLOPS ratio and is a §Perf iteration
    target (DESIGN.md §6).
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    bq = min(block_q, sq)
    bkv = min(block_kv, sk)
    if sq % bq or sk % bkv:
        return dense_attention(q, k, v, causal=causal)
    g = h // kvh
    nq, nk = sq // bq, sk // bkv
    dv = v.shape[-1]

    qb = jnp.moveaxis(q.reshape(b, nq, bq, kvh, g, d), 1, 0)  # (nq,b,bq,kvh,g,d)
    kb = jnp.moveaxis(k.reshape(b, nk, bkv, kvh, d), 1, 0)  # (nk,b,bkv,kvh,d)
    vb = jnp.moveaxis(v.reshape(b, nk, bkv, kvh, dv), 1, 0)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def q_block(_, inputs):
        i, qi = inputs  # qi: (b,bq,kvh,g,d)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_block(carry, kv_in):
            m, l, acc = carry  # (b,bq,kvh,g), same, (b,bq,kvh,g,dv)
            j, kj, vj = kv_in
            logits = jnp.einsum("bqkgd,btkd->bqkgt", qi, kj,
                                preferred_element_type=jnp.float32)
            if causal:
                qpos = i * bq + jnp.arange(bq)
                kpos = j * bkv + jnp.arange(bkv)
                mask = qpos[:, None] >= kpos[None, :]  # (bq,bkv)
                logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgt,btkd->bqkgd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, bq, kvh, g), -1e30, jnp.float32)
        l0 = jnp.zeros((b, bq, kvh, g), jnp.float32)
        a0 = jnp.zeros((b, bq, kvh, g, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb))
        out_i = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, out_i

    _, out = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))
    out = jnp.moveaxis(out, 0, 1)  # (b,nq,bq,kvh,g,dv)
    return out.reshape(b, sq, h, dv)


def attention_core(q, k, v, cfg: ModelConfig, *, causal: bool,
                   interpret: bool = False) -> jax.Array:
    """``interpret`` runs the flash kernel in Pallas interpret mode (the
    kernel policy's flag, for CPU validation).  Scores, mask, softmax and
    the weighted sum: the ``attn_core`` scope."""
    with scope("attn_core"):
        if cfg.attention_impl == "flash":
            out = _flash_path(q, k, v, cfg, causal=causal,
                              interpret=interpret)
            if out is not None:
                return out
        if cfg.attention_impl == "dense" \
                or q.shape[1] <= cfg.attention_block_q:
            return dense_attention(q, k, v, causal=causal)
        return blockwise_attention(q, k, v, causal=causal,
                                   block_q=cfg.attention_block_q,
                                   block_kv=cfg.attention_block_kv)


def _flash_path(q, k, v, cfg: ModelConfig, *, causal: bool, interpret: bool):
    """Pallas flash-attention (opt-in, attention_impl='flash').

    KV heads are broadcast to Q heads (GQA grouping handled outside the
    kernel); falls back to blockwise when shapes don't tile.  ``interpret``
    (the kernel policy's flag) runs the kernel on CPU for validation.
    """
    from repro.kernels.flash_attention import flash_attention

    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    bq = min(cfg.attention_block_q, sq)
    bkv = min(cfg.attention_block_kv, sk)
    if sq % bq or sk % bkv or h % kvh or d % 8:
        return None
    g = h // kvh
    kb = jnp.repeat(k, g, axis=2) if g > 1 else k
    vb = jnp.repeat(v, g, axis=2) if g > 1 else v
    # (B,S,H,D) -> (B*H, S, D); q comes pre-scaled by 1/sqrt(d) from the
    # projection, but the kernel applies its own scale -> undo here.
    q2 = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d) * (d ** 0.5)
    k2 = jnp.swapaxes(kb, 1, 2).reshape(b * h, sk, d)
    v2 = jnp.swapaxes(vb, 1, 2).reshape(b * h, sk, v.shape[-1])
    out = flash_attention(q2, k2, v2, causal=causal, block_q=bq, block_kv=bkv,
                          interpret=interpret)
    return jnp.swapaxes(out.reshape(b, h, sq, v.shape[-1]), 1, 2)


# --------------------------------------------------------------------------
# Decode-cache addressing (contiguous slots + paged blocks)
# --------------------------------------------------------------------------

def _row_positions(pos, batch: int):
    """Normalize ``pos`` to (per_row (B,) int32 or None, scalar start).

    Scalar ``pos`` keeps the legacy fixed-batch semantics (every row writes
    at the same offset); a (B,) vector means slot-indexed continuous decode
    where each batch row sits at its own sequence position.
    """
    pos_arr = jnp.asarray(pos)
    if pos_arr.ndim >= 1 and pos_arr.size == batch and batch > 1:
        return pos_arr.reshape(-1).astype(jnp.int32), None
    flat = pos_arr.reshape(-1)
    return None, (flat[0] if flat.size else pos_arr).astype(jnp.int32)


def _update_rows(cache_leaf: jax.Array, new: jax.Array, rows) -> jax.Array:
    """Write one decode step (B, s, ...) into (B, Smax, ...) at per-row
    offsets (s = 1 plain decode, k+1 for the speculative verify chunk)."""
    zeros = (0,) * (cache_leaf.ndim - 2)
    with scope("kv_write"):
        return jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p,) + zeros)
        )(cache_leaf, new.astype(cache_leaf.dtype), rows)


def _paged_write(pool: jax.Array, layer, rows: jax.Array, blk: jax.Array,
                 off: jax.Array) -> jax.Array:
    """Write one decode step's rows into layer ``layer`` of the stacked
    pool, in place: the pool rides in the layer loop's carry and only the
    written rows move.

    pool: (L, num_blocks, block_size, W); rows: (B, S, W); blk/off: (B, S)
    physical block and offset of each row.  S is 1 for plain decode and k+1
    for the speculative verify chunk.  Distinct slots own distinct blocks,
    so indices never collide; retired slots point at the reserved sink
    block 0 (serving/paged_cache.py) and their writes land there
    harmlessly.
    """
    with scope("kv_write"):
        return pool.at[layer, blk.reshape(-1), off.reshape(-1)].set(
            rows.reshape(-1, pool.shape[-1]).astype(pool.dtype))


def _paged_gather(pool: jax.Array, layer, page_table: jax.Array) -> jax.Array:
    """Gather every slot's logical view of layer ``layer`` from the pool.

    pool: (L, num_blocks, block_size, W); page_table: (B, max_blocks)
    int32 -> (B, max_blocks * block_size, W).  Positions beyond a slot's
    length read whatever sits in its tail blocks (or the sink block);
    attention masks them via ``kv_len`` exactly like contiguous-cache
    padding.  The gather path: int8 pools and multi-token chunks.
    """
    with scope("kv_gather"):
        b, mb = page_table.shape
        return pool[layer, page_table].reshape(b, mb * pool.shape[2],
                                               pool.shape[3])


def _gqa_paged_decode(cache: Params, q, k_new, v_new, rows, length, *,
                      native_int8: bool, interpret: bool,
                      ) -> Tuple[jax.Array, Params]:
    """Write this step's k/v into the paged pool and attend over it.

    cache: {"kv"[, "kv_scale"]: the whole stacked pool (serving/
    paged_cache.py), "layer": this layer's index, "page_table": (B,
    max_blocks)}.  ``k_new``/``v_new`` are (B, S, KV, hd) with S >= 1: row
    b writes at logical positions rows[b] .. rows[b]+S-1.  A bf16 pool with
    S == 1 attends through the paged-attention kernel, which reads only
    each slot's live blocks where they lie; int8 pools and chunks gather
    per-slot views for dense attention (``native_int8``: straight on the
    int8 values and scales, else dequantized).  Returns (out, new_cache)
    with the pool leaves updated.
    """
    from repro.models import kvcache as kvq
    pt, layer = cache["page_table"], cache["layer"]
    b, s, heads = k_new.shape[0], k_new.shape[1], k_new.shape[2]
    bs = cache["kv"].shape[2]
    positions = rows[:, None] + jnp.arange(s, dtype=rows.dtype)  # (B, S)
    blk = pt[jnp.arange(b)[:, None], positions // bs]
    off = positions % bs
    new_rows = kvq.fuse_kv(k_new, v_new)
    new_cache = dict(cache)
    int8_pool = "kv_scale" in cache
    if int8_pool:
        with scope("kv_write"):
            new_rows, scales = kvq.quantize_rows(new_rows, heads)
        new_cache["kv_scale"] = _paged_write(cache["kv_scale"], layer,
                                             scales, blk, off)
    new_cache["kv"] = _paged_write(cache["kv"], layer, new_rows, blk, off)
    if kops.paged_attention_kernel(int8_pool=int8_pool, chunk=s,
                                   interpret=interpret,
                                   shape=cache["kv"].shape):
        from repro.kernels.paged_attention import paged_attention
        with scope("attn_core"):
            out = paged_attention(q[:, 0], new_cache["kv"], layer, pt,
                                  length[:, 0], interpret=interpret)
        return out[:, None], new_cache
    k, v = kvq.split_kv(_paged_gather(new_cache["kv"], layer, pt), heads)
    with scope("attn_core"):
        if not int8_pool:
            out = dense_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                                  causal=False, kv_len=length)
        else:
            ks, vs = kvq.split_kv(
                _paged_gather(new_cache["kv_scale"], layer, pt), heads)
            if native_int8:
                out = int8_dense_attention(q, k, ks, v, vs, kv_len=length)
            else:
                out = dense_attention(q, kvq.dequantize_kv(k, ks, q.dtype),
                                      kvq.dequantize_kv(v, vs, q.dtype),
                                      causal=False, kv_len=length)
    return out, new_cache


def _gqa_contiguous_decode(cache: Params, q, k_new, v_new, rows, start,
                           length, *, native_int8: bool,
                           ) -> Tuple[jax.Array, Params]:
    """Write this step's k/v into a contiguous (B, Smax, KV, hd) cache at
    per-row offsets ``rows`` (or the shared ``start``) and attend over it."""
    if "k_scale" in cache:  # int8-quantized cache (§Perf C2)
        from repro.models import kvcache as kvq
        with scope("kv_write"):
            new_cache = kvq.update_quantized_kv(
                cache, k_new, v_new,
                rows if rows is not None else start)
        new_cache = {kk: shard(vv, "batch", "kv_seq", "kv_heads", None)
                     for kk, vv in new_cache.items()}
        if native_int8:
            k_cache = (new_cache["k"], new_cache["k_scale"])
            v_cache = (new_cache["v"], new_cache["v_scale"])
        else:
            k_cache = kvq.dequantize_kv(new_cache["k"],
                                        new_cache["k_scale"], q.dtype)
            v_cache = kvq.dequantize_kv(new_cache["v"],
                                        new_cache["v_scale"], q.dtype)
    else:
        if rows is not None:  # slot-indexed: per-row write offsets
            k_cache = _update_rows(cache["k"], k_new, rows)
            v_cache = _update_rows(cache["v"], v_new, rows)
        else:
            with scope("kv_write"):
                k_cache = jax.lax.dynamic_update_slice(
                    cache["k"], k_new.astype(cache["k"].dtype),
                    (0, start, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(
                    cache["v"], v_new.astype(cache["v"].dtype),
                    (0, start, 0, 0))
        k_cache = shard(k_cache, "batch", "kv_seq", "kv_heads", None)
        v_cache = shard(v_cache, "batch", "kv_seq", "kv_heads", None)
        new_cache = {"k": k_cache, "v": v_cache}
    with scope("attn_core"):
        if isinstance(k_cache, tuple):  # native int8: pools + scales
            out = int8_dense_attention(q, k_cache[0], k_cache[1],
                                       v_cache[0], v_cache[1],
                                       kv_len=length)
        else:
            out = dense_attention(q, k_cache, v_cache, causal=False,
                                  kv_len=length)
    return out, new_cache


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def gqa_init(dec, key, path: str, cfg: ModelConfig, *, cross: bool = False,
             stack: Tuple[int, ...] = ()) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dec.linear(ks[0], f"{path}/wq", d, h * hd, bias=cfg.qkv_bias, stack=stack),
        "wk": dec.linear(ks[1], f"{path}/wk", d, kv * hd, bias=cfg.qkv_bias, stack=stack),
        "wv": dec.linear(ks[2], f"{path}/wv", d, kv * hd, bias=cfg.qkv_bias, stack=stack),
        "wo": dec.linear(ks[3], f"{path}/wo", h * hd, d, stack=stack),
    }
    if cfg.qk_norm:
        p["q_norm"] = {k_: jnp.broadcast_to(v_, stack + v_.shape) if stack else v_
                       for k_, v_ in rmsnorm_init(hd, cfg.pdtype).items()}
        p["k_norm"] = {k_: jnp.broadcast_to(v_, stack + v_.shape) if stack else v_
                       for k_, v_ in rmsnorm_init(hd, cfg.pdtype).items()}
    if cross:
        p["gate"] = jnp.zeros(stack + (1,), cfg.pdtype)  # tanh-gated cross-attn
    return p


def _project_qkv(p, x, kv_src, cfg, rope, *, use_pallas=False):
    """q, k, v projections with their norms and RoPE: ``attn_proj``."""
    with scope("attn_proj"):
        return _project_qkv_body(p, x, kv_src, cfg, rope, use_pallas)


def _project_qkv_body(p, x, kv_src, cfg, rope, use_pallas):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    b, s = x.shape[0], x.shape[1]
    q = linear(p["wq"], x, use_pallas=use_pallas).reshape(b, s, h, hd)
    src = kv_src if kv_src is not None else x
    t = src.shape[1]
    k = linear(p["wk"], src, use_pallas=use_pallas).reshape(b, t, kvh, hd)
    v = linear(p["wv"], src, use_pallas=use_pallas).reshape(b, t, kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if rope is not None:
        qcos, qsin, kcos, ksin = rope
        q = apply_rope(q, qcos, qsin)
        k = apply_rope(k, kcos, ksin)
    q = q * (hd ** -0.5)
    return q, k, v


def gqa_apply(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    rope=None,
    mode: str = "full",  # "full" | "decode"
    cache: Optional[Params] = None,
    pos: Optional[jax.Array] = None,
    kv_src: Optional[jax.Array] = None,
    causal: bool = True,
    use_pallas: bool = False,
) -> Tuple[jax.Array, Optional[Params]]:
    b, s = x.shape[0], x.shape[1]
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    cross = kv_src is not None

    if mode == "full":
        q, k, v = _project_qkv(p, x, kv_src, cfg, rope, use_pallas=use_pallas)
        q = shard(q, "batch", "seq", "heads", None)
        k = shard(k, "batch", "kv_seq", "kv_heads", None)
        v = shard(v, "batch", "kv_seq", "kv_heads", None)
        out = attention_core(q, k, v, cfg, causal=causal and not cross,
                             interpret=kops.as_policy(use_pallas).interpret)
        new_cache = {"k": k, "v": v} if not cross else {"k": k, "v": v}
    else:  # decode: s == 1 (plain) or k+1 (verify chunk); cache (B, Smax, KV, hd)
        assert cache is not None and pos is not None
        if cross:
            # cross-attn kv computed at prefill; just read the cache
            with scope("attn_proj"):
                q = linear(p["wq"], x,
                           use_pallas=use_pallas).reshape(b, s, h, hd)
                if cfg.qk_norm:
                    q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
                q = q * (hd ** -0.5)
            k, v = cache["k"], cache["v"]
            with scope("attn_core"):
                out = dense_attention(q, k, v, causal=False)
            new_cache = cache
        else:
            q, k_new, v_new = _project_qkv(p, x, None, cfg, rope, use_pallas=use_pallas)
            rows, start = _row_positions(pos, b)
            base = rows if rows is not None else jnp.broadcast_to(start, (b,))
            # (B, Sq) per-query lengths: query j attends to positions
            # < pos+j+1, which is both the live-length mask and the
            # in-chunk causal mask of the speculative verify step.
            length = (base[:, None].astype(jnp.int32) + 1
                      + jnp.arange(s, dtype=jnp.int32)[None, :])
            policy = kops.as_policy(use_pallas)
            native_int8 = policy.int8_decode == "native"
            if "page_table" in cache:  # paged block pool (DESIGN.md §8)
                if rows is None:
                    rows = jnp.broadcast_to(start, (b,))
                out, new_cache = _gqa_paged_decode(
                    cache, q, k_new, v_new, rows, length,
                    native_int8=native_int8, interpret=policy.interpret)
            else:
                out, new_cache = _gqa_contiguous_decode(
                    cache, q, k_new, v_new, rows, start, length,
                    native_int8=native_int8)

    with scope("attn_proj"):
        out = out.reshape(b, s, h * hd)
        out = shard(out, "batch", "seq", "heads")
        y = linear(p["wo"], out, use_pallas=use_pallas)
        if cross and "gate" in p:
            y = y * jnp.tanh(p["gate"].astype(jnp.float32)).astype(y.dtype)
    return y, new_cache


# --------------------------------------------------------------------------
# MLA (deepseek-v3)
# --------------------------------------------------------------------------

def mla_init(dec, key, path: str, cfg: ModelConfig, stack: Tuple[int, ...] = ()) -> Params:
    d = cfg.d_model
    h = cfg.num_heads
    qh = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    stackb = lambda p_: {k_: (jnp.broadcast_to(v_, stack + v_.shape) if stack else v_)
                         for k_, v_ in p_.items()}
    return {
        "q_down": dec.linear(ks[0], f"{path}/q_down", d, cfg.q_lora_rank, stack=stack),
        "q_norm": stackb(rmsnorm_init(cfg.q_lora_rank, cfg.pdtype)),
        "q_up": dec.linear(ks[1], f"{path}/q_up", cfg.q_lora_rank, h * qh, stack=stack),
        "kv_down": dec.linear(ks[2], f"{path}/kv_down", d,
                              cfg.kv_lora_rank + cfg.qk_rope_head_dim, stack=stack),
        "kv_norm": stackb(rmsnorm_init(cfg.kv_lora_rank, cfg.pdtype)),
        "kv_up": dec.linear(ks[3], f"{path}/kv_up", cfg.kv_lora_rank,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), stack=stack),
        "wo": dec.linear(ks[4], f"{path}/wo", h * cfg.v_head_dim, d, stack=stack),
    }


def _mla_q(p, x, cfg, rope, use_pallas):
    b, s = x.shape[0], x.shape[1]
    h = cfg.num_heads
    nd, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = rmsnorm(p["q_norm"], linear(p["q_down"], x, use_pallas=use_pallas), cfg.norm_eps)
    q = linear(p["q_up"], cq, use_pallas=use_pallas).reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    if rope is not None:
        cos, sin = rope
        q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def mla_apply(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    rope_q=None,
    rope_k=None,
    mode: str = "full",
    cache: Optional[Params] = None,
    pos: Optional[jax.Array] = None,
    use_pallas: bool = False,
) -> Tuple[jax.Array, Optional[Params]]:
    b, s = x.shape[0], x.shape[1]
    h = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat = cfg.kv_lora_rank
    scale = (nd + rd) ** -0.5

    with scope("attn_proj"):
        ckv_full = linear(p["kv_down"], x, use_pallas=use_pallas)  # (B,S,lat+rd)
        ckv = rmsnorm(p["kv_norm"], ckv_full[..., :lat], cfg.norm_eps)
        k_rope = ckv_full[..., lat:].reshape(b, s, 1, rd)
        if rope_k is not None:
            cos, sin = rope_k
            k_rope = apply_rope(k_rope, cos, sin)
        q_nope, q_rope = _mla_q(p, x, cfg, rope_q, use_pallas)

    if mode == "full":
        with scope("attn_proj"):
            kv = linear(p["kv_up"], ckv,
                        use_pallas=use_pallas).reshape(b, s, h, nd + vd)
            k_nope, v = kv[..., :nd], kv[..., nd:]
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rd))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1) * scale
        q = shard(q, "batch", "seq", "heads", None)
        k = shard(k, "batch", "kv_seq", "heads", None)
        v = shard(v, "batch", "kv_seq", "heads", None)
        out = attention_core(q, k, v, cfg, causal=True,
                             interpret=kops.as_policy(use_pallas).interpret)
        new_cache = {"ckv": ckv, "kr": k_rope[..., 0, :]}
    else:
        # Absorbed decode: score in latent space, never materialize per-head K/V.
        assert cache is not None and pos is not None
        rows, start = _row_positions(pos, b)
        if rows is not None:  # slot-indexed continuous decode (DESIGN.md §8)
            ckv_cache = _update_rows(cache["ckv"], ckv, rows)
            kr_cache = _update_rows(cache["kr"], k_rope[:, :, 0, :], rows)
        else:
            with scope("kv_write"):
                ckv_cache = jax.lax.dynamic_update_slice(
                    cache["ckv"], ckv.astype(cache["ckv"].dtype),
                    (0, start, 0))
                kr_cache = jax.lax.dynamic_update_slice(
                    cache["kr"], k_rope[:, :, 0, :].astype(cache["kr"].dtype),
                    (0, start, 0))
        ckv_cache = shard(ckv_cache, "batch", "kv_seq", None)
        with scope("attn_core"):
            out = _mla_absorbed(p, q_nope, q_rope, ckv_cache, kr_cache, x,
                                rows, start, cfg, scale)
        new_cache = {"ckv": ckv_cache, "kr": kr_cache}

    with scope("attn_proj"):
        y = linear(p["wo"], out.reshape(b, s, h * vd), use_pallas=use_pallas)
    return y, new_cache


def _mla_absorbed(p, q_nope, q_rope, ckv_cache, kr_cache, x, rows, start,
                  cfg: ModelConfig, scale):
    """MLA's absorbed decode attention: scores in the latent space."""
    b, s, h = q_nope.shape[0], q_nope.shape[1], cfg.num_heads
    nd, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    lat = cfg.kv_lora_rank
    w_kv = p["kv_up"]["kernel"] if "kernel" in p["kv_up"] else (
        jnp.dot(p["kv_up"]["u"], p["kv_up"]["v"]))
    w_kv = w_kv.reshape(lat, h, nd + vd)
    w_uk, w_uv = w_kv[..., :nd], w_kv[..., nd:]
    # latent cache stays bf16; fp32 only through accumulation (§Perf C1)
    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    logits = (
        jnp.einsum("bshl,btl->bhst", q_lat, ckv_cache,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bshr,btr->bhst", q_rope, kr_cache,
                     preferred_element_type=jnp.float32)
    ) * scale
    base = rows if rows is not None else jnp.broadcast_to(start, (b,))
    # (B, S, T) mask: per-row live length, advancing per chunk position
    # (in-chunk causality for the speculative verify step, S > 1).
    length = (base[:, None].astype(jnp.int32) + 1
              + jnp.arange(s, dtype=jnp.int32)[None, :])
    valid = (jnp.arange(logits.shape[-1])[None, None, :]
             < length[:, :, None])
    logits = jnp.where(valid[:, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx_lat = jnp.einsum("bhst,btl->bshl", probs.astype(x.dtype), ckv_cache,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.einsum("bshl,lhv->bshv", ctx_lat, w_uv,
                      preferred_element_type=jnp.float32).astype(x.dtype)
