"""Shared layer primitives: linear (dense OR LRD-factorized), norms,
embeddings, RoPE, FFN.

``linear`` is the single dispatch point for the paper's technique: a param
group with a ``kernel`` runs dense, one with ``u``/``v`` runs the factorized
path (optionally through the fused Pallas kernel).  Every projection in every
model goes through it, which is what makes LRD a one-flag transform across
the whole zoo.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.obs import scope

Params = Dict[str, Any]


def linear(p: Params, x: jax.Array, *,
           use_pallas: "bool | kops.KernelPolicy" = False) -> jax.Array:
    """y = x @ W (+ b), where W may be factorized as u @ v (LRD).

    ``use_pallas`` is either the legacy bool or a :class:`kops.KernelPolicy`
    carrying the static sequential-freezing group and block sizes; every
    model forwards it verbatim, so the launch layer sets it once per
    compiled step (see launch/steps.py).
    """
    pol = kops.as_policy(use_pallas)
    if "kernel" in p:
        y = jnp.dot(x, p["kernel"], preferred_element_type=jnp.float32).astype(x.dtype)
    elif "kernel_q" in p:
        y = _int8_dense(p, x, pol)
    elif "u_q" in p:
        y = _int8_lowrank(p, x, pol)
    else:
        u, v = p["u"], p["v"]
        if pol.use_pallas:
            y = kops.lowrank_apply(
                x, u, v, use_kernel=True, interpret=pol.interpret,
                block_m=pol.block_m, block_k=pol.block_k, block_n=pol.block_n,
                freeze_group=pol.freeze_group, autotune=pol.autotune,
                double_buffer=pol.double_buffer)
        else:
            t = jnp.dot(x, u, preferred_element_type=jnp.float32).astype(x.dtype)
            y = jnp.dot(t, v, preferred_element_type=jnp.float32).astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


def _int8_dense(p: Params, x: jax.Array, pol: "kops.KernelPolicy") -> jax.Array:
    """int8-exported dense kernel (serving/export.py quantize_factors).

    ``int8_decode="native"`` consumes the int8 values directly (TPU/interpret:
    exact-int32 Pallas kernel; elsewhere the weight-only f32 formulation) —
    ``"bf16"`` is the legacy round trip that dequantizes the full weight and
    runs a bf16 GEMM, kept as the serve-bench baseline."""
    if pol.int8_decode == "bf16":
        w = (p["kernel_q"].astype(jnp.float32)
             * p["kernel_scale"].astype(jnp.float32)).astype(jnp.bfloat16)
        return jnp.dot(x.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return kops.int8_apply(
        x, p["kernel_q"], p["kernel_scale"],
        use_kernel=None if pol.use_pallas else False,
        interpret=pol.interpret, block_m=pol.block_m, block_k=pol.block_k,
        block_n=pol.block_n)


def _int8_lowrank(p: Params, x: jax.Array, pol: "kops.KernelPolicy") -> jax.Array:
    """int8-exported factor pair — same decode-mode contract as
    :func:`_int8_dense`; the native TPU path is the fused requantizing
    kernel (kernels/int8_matmul.int8_lowrank_matmul)."""
    if pol.int8_decode == "bf16":
        u = (p["u_q"].astype(jnp.float32)
             * p["u_scale"].astype(jnp.float32)).astype(jnp.bfloat16)
        v = (p["v_q"].astype(jnp.float32)
             * p["v_scale"].astype(jnp.float32)).astype(jnp.bfloat16)
        t = jnp.dot(x.astype(jnp.bfloat16), u,
                    preferred_element_type=jnp.float32).astype(jnp.bfloat16)
        return jnp.dot(t, v, preferred_element_type=jnp.float32).astype(x.dtype)
    return kops.int8_lowrank_apply(
        x, p["u_q"], p["u_scale"], p["v_q"], p["v_scale"],
        use_kernel=None if pol.use_pallas else False,
        interpret=pol.interpret, block_m=pol.block_m, block_k=pol.block_k,
        block_n=pol.block_n)


def out_features(p: Params) -> int:
    for k in ("kernel", "kernel_q", "v", "v_q"):
        if k in p:
            return p[k].shape[-1]
    raise KeyError(f"no weight leaf in {sorted(p)}")


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # fp32 statistics. (§Perf iteration A1 tried bf16-I/O with a dtype=f32
    # reduction; REFUTED: XLA sinks the convert into the square and
    # materializes the fp32 tensor anyway, +13% HBM bytes — see
    # EXPERIMENTS.md §Perf.)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "ln_bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["ln_bias"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Embeddings
# --------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int, dtype) -> Params:
    table = jax.random.normal(key, (vocab, d), jnp.float32) * 0.01
    return {"embedding": table.astype(dtype)}


def mask_vocab(logits: jax.Array, true_vocab: int) -> jax.Array:
    """-inf the padded vocab tail (elementwise — keeps the vocab sharding)."""
    if logits.shape[-1] == true_vocab:
        return logits
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(iota < true_vocab, logits, -1e30)


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_table(seq_len: int, head_dim: int, theta: float,
               *, offset: int = 0, positions: Optional[jax.Array] = None):
    """(cos, sin) tables, each (S, head_dim/2), float32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions is None:
        positions = jnp.arange(seq_len, dtype=jnp.float32) + offset
    else:
        positions = positions.astype(jnp.float32)
    ang = positions[..., None] * freqs  # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast over batch & heads
        c, s = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, half)
        c, s = cos[:, :, None, :], sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------

def ffn_init(dec, key, path: str, d: int, f: int, activation: str, dtype,
             stack: Tuple[int, ...] = ()) -> Params:
    ks = jax.random.split(key, 3)
    if activation == "swiglu":
        return {
            "gate": dec.linear(ks[0], f"{path}/gate", d, f, dtype=dtype, stack=stack),
            "up": dec.linear(ks[1], f"{path}/up", d, f, dtype=dtype, stack=stack),
            "down": dec.linear(ks[2], f"{path}/down", f, d, dtype=dtype, stack=stack),
        }
    return {
        "wi": dec.linear(ks[0], f"{path}/wi", d, f, dtype=dtype, stack=stack),
        "down": dec.linear(ks[1], f"{path}/down", f, d, dtype=dtype, stack=stack),
    }


def ffn(p: Params, x: jax.Array, *,
        use_pallas: "bool | kops.KernelPolicy" = False) -> jax.Array:
    with scope("ffn"):
        return _ffn(p, x, use_pallas)


def _ffn(p: Params, x: jax.Array, use_pallas) -> jax.Array:
    from repro.distributed import shard  # local import to avoid cycles

    if "gate" in p:
        pol = kops.as_policy(use_pallas)
        if (pol.use_pallas and "u" in p["gate"] and "u" in p["up"]
                and "bias" not in p["gate"] and "bias" not in p["up"]):
            # Both branches factorized: one fused SwiGLU-first-half kernel —
            # the rank-r intermediates AND the two (M, F) branch outputs stay
            # in VMEM.
            h = kops.lowrank_ffn_apply(
                x, p["gate"]["u"], p["gate"]["v"], p["up"]["u"], p["up"]["v"],
                use_kernel=True, interpret=pol.interpret, block_m=pol.block_m,
                block_k=pol.block_k, block_n=pol.block_n,
                freeze_group=pol.freeze_group, autotune=pol.autotune)
        else:
            g = linear(p["gate"], x, use_pallas=use_pallas)
            u = linear(p["up"], x, use_pallas=use_pallas)
            h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(linear(p["wi"], x, use_pallas=use_pallas).astype(jnp.float32)).astype(x.dtype)
    if h.ndim == 3:
        h = shard(h, "batch", "seq", "mlp")
    return linear(p["down"], h, use_pallas=use_pallas)


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL with fp32 log-softmax.

    SPMD-friendly over a model-sharded vocab axis: the gold logit is taken
    with a masked sum (partial-sum + all-reduce under SPMD) rather than
    ``take_along_axis`` (whose sharded-gather lowering forces full-vocab
    all-gathers — measured 5x per-device activation blow-up on the 16x16
    dry-run).  max/sum reductions over the sharded axis lower to cheap
    all-reduces.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    shifted = lf - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
