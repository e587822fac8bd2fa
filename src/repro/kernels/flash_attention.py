"""Causal flash-attention Pallas TPU kernel (forward).

§Perf cells A/B identified attention score-block HBM round-trips as a top
memory-term contributor: the pure-JAX blockwise attention writes each
(bq x bkv) fp32 logits block to HBM several times (einsum -> mask -> max ->
exp -> weighted sum live in separate fusions).  This kernel keeps the whole
online-softmax state — logits block, running max m, denominator l, output
accumulator — in VMEM; HBM sees only q/k/v reads and one output write.

Layout: grid (batch*kv_heads, q_blocks, kv_blocks), kv innermost so the
scratch accumulators persist across the kv loop for a fixed q block (same
accumulation pattern as kernels/lowrank_matmul.py).  Causality is handled
by masking the diagonal block and skipping future blocks with pl.when —
on TPU the skipped iterations cost only the (empty) grid step, recovering
the ~2x masked-block waste the roofline's MODEL/HLO ratio exposes.

GQA: pass k/v already grouped per q-head group (the wrapper broadcasts kv
heads); head_dim and block sizes must be MXU-friendly multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, block_q: int, block_kv: int, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: process a block only if it overlaps the allowed triangle
    run = True if not causal else (kj * block_kv <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bkv, d)
        v = v_ref[0]  # (bkv, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_kv), 0)
            kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                            (block_q, block_kv), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_kv: int = 512, interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D); k/v: (BH, Sk, D/Dv) — batch*heads flattened.

    Sq % block_q == 0, Sk % block_kv == 0 (wrapper pads / falls back).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    dv = v.shape[2]
    bq = min(block_q, sq)
    bkv = min(block_kv, sk)
    assert sq % bq == 0 and sk % bkv == 0, (q.shape, k.shape, bq, bkv)
    grid = (bh, sq // bq, sk // bkv)
    scale = d ** -0.5
    kernel = functools.partial(_kernel, causal=causal, block_q=bq,
                               block_kv=bkv, scale=scale)
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # denominator l
            pltpu.VMEM((bq, dv), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
