"""int8-quantized KV cache (beyond-paper decode lever, §Perf C2).

decode_32k-class cells are bound by reading the KV cache every step; int8
storage with per-(position, head) scales halves that floor vs bf16.  The
paper's LRD compresses weights, not caches — this is the cache-side
complement (deepseek's MLA latent cache being the low-rank-projection
variant of the same idea).

Scales are stored per (batch, position, kv_head): one bf16 scalar per
head-vector — 1/head_dim overhead.  Dequantization fuses into the attention
matmul's operand read on TPU (register-level convert); accuracy cost is
~0.4% relative on the logits (see tests/test_kvcache.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["quantize_kv", "dequantize_kv", "init_quantized_kv", "update_quantized_kv",
           "fuse_kv", "split_kv", "quantize_rows"]


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (..., hd) -> (int8 values, bf16 scales (..., 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def init_quantized_kv(stack: Tuple[int, ...], batch: int, length: int,
                      kv_heads: int, head_dim: int) -> dict:
    return {
        "k": jnp.zeros(stack + (batch, length, kv_heads, head_dim), jnp.int8),
        "v": jnp.zeros(stack + (batch, length, kv_heads, head_dim), jnp.int8),
        "k_scale": jnp.zeros(stack + (batch, length, kv_heads, 1), jnp.bfloat16),
        "v_scale": jnp.zeros(stack + (batch, length, kv_heads, 1), jnp.bfloat16),
    }


def update_quantized_kv(cache: dict, k_new: jax.Array, v_new: jax.Array,
                        start) -> dict:
    """Write one step's k/v (B, 1, KV, hd) at position ``start``.

    ``start`` is either a scalar (all rows share one position — fixed-batch
    decode) or a (B,) vector of per-row positions (slot-indexed continuous
    decode, serving/scheduler.py): each batch row writes at its own offset.
    """
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    start = jnp.asarray(start)
    if start.ndim >= 1 and start.size > 1:
        upd = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0, 0)))
        pos = start.reshape(-1).astype(jnp.int32)
        return {
            "k": upd(cache["k"], kq, pos),
            "v": upd(cache["v"], vq, pos),
            "k_scale": upd(cache["k_scale"], ks, pos),
            "v_scale": upd(cache["v_scale"], vs, pos),
        }
    at = (0, start.reshape(()), 0, 0)
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], kq, at),
        "v": jax.lax.dynamic_update_slice(cache["v"], vq, at),
        "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], ks, at),
        "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], vs, at),
    }


# --------------------------------------------------------------------------
# Paged pool rows (serving/paged_cache.py)
# --------------------------------------------------------------------------

def fuse_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """k, v: (..., KV, hd) -> pool rows (..., KV * 2 * hd): each KV head's
    keys, then its values."""
    return jnp.stack([k, v], axis=-2).reshape(k.shape[:-2] + (-1,))


def split_kv(rows: jax.Array, heads: int) -> Tuple[jax.Array, jax.Array]:
    """Pool rows (..., KV * 2 * w) -> k, v: (..., KV, w); ``w`` is hd for
    values and 1 for the int8 pool's scales."""
    x = rows.reshape(rows.shape[:-1] + (heads, 2, -1))
    return x[..., 0, :], x[..., 1, :]


def quantize_rows(rows: jax.Array, heads: int) -> Tuple[jax.Array, jax.Array]:
    """Pool rows -> (int8 rows, bf16 scales (..., KV * 2)): one scale per
    head's key vector and per head's value vector, as ``quantize_kv``."""
    q, scale = quantize_kv(rows.reshape(rows.shape[:-1] + (heads, 2, -1)))
    return (q.reshape(rows.shape),
            scale.reshape(rows.shape[:-1] + (heads * 2,)))
