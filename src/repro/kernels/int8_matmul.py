"""int8 x int8 -> int32 Pallas decode matmuls (DESIGN.md §11).

The serve-time export (serving/export.py, ``quantize_factors="int8"``)
stores factor groups as int8 values + per-output-column fp32 scales.  The
naive way to consume them is the bf16 round-trip — dequantize every weight
element, every decode step, then run bf16 matmuls — which pays a full
extra pass over the weight bytes and caps the MXU at bf16 peak.  These
kernels consume the quantized operands natively:

* ``int8_matmul``: y_i32[M, S] = x_q[M, C] @ w_q[C, S] with **exact int32
  accumulation** on the int8 MXU path (2x bf16 peak on v5e) — scales are
  applied by the caller AFTER accumulation, over the (M, S) output instead
  of the (C, S) weights.  That post-accumulation contract is what makes
  the CPU fallback (kernels/ops.int8_apply) a faithful stand-in: same
  algebra, different accumulator.
* ``int8_lowrank_matmul``: the fused decode path for a factor pair —
  t_i32 = x_q @ u_q stays in VMEM, is rescaled (per-column u_scale),
  re-quantized per row, and fed straight into the second int8 matmul
  against v_q; HBM never sees the rank-r intermediate OR an f32/bf16 copy
  of either factor.  Per-row x scales factor out of the re-quantization
  (q(a*x) == q(x) for a > 0 row-wise), so the caller folds them into the
  (M, S) output, keeping the kernel free of per-row scale plumbing.

Both run under ``interpret=True`` off-TPU: the int32 accumulation is exact
there too (tests/test_autotune.py), which is what lets CI pin the
quantized-decode numerics without a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["int8_matmul", "int8_lowrank_matmul", "quantize_rowwise",
           "quantize_colwise"]


def quantize_rowwise(x: jax.Array):
    """Dynamic per-row symmetric int8: (values int8, scales f32 (..., 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


def quantize_colwise(w: jax.Array):
    """Static per-output-column symmetric int8 for weights/factors:
    (values int8, scales f32 (..., 1, S))."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _i8_dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


# --------------------------------------------------------------------------
# dense: y_i32 = x_q @ w_q
# --------------------------------------------------------------------------

def _dense_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _i8_dot(x_ref[...], w_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "block_n", "interpret"))
def int8_matmul(x_q: jax.Array, w_q: jax.Array, *, block_m: int = 256,
                block_k: int = 512, block_n: int = 256,
                interpret: bool = False) -> jax.Array:
    """Exact x_q (M, C) @ w_q (C, S) -> int32 (M, S).  Scales are the
    caller's business — applied post-accumulation over the output."""
    m, c = x_q.shape
    s = w_q.shape[1]
    assert w_q.shape[0] == c, (x_q.shape, w_q.shape)
    assert x_q.dtype == jnp.int8 and w_q.dtype == jnp.int8
    assert m % block_m == 0 and c % block_k == 0 and s % block_n == 0, (
        f"shapes ({m},{c},{s}) not divisible by blocks "
        f"({block_m},{block_k},{block_n})")
    return pl.pallas_call(
        _dense_kernel,
        name="int8_matmul",
        grid=(m // block_m, s // block_n, c // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_q, w_q)


# --------------------------------------------------------------------------
# fused low-rank decode: (x_q @ u_q) -> rescale/requant in VMEM -> @ v_q
# --------------------------------------------------------------------------

def _lowrank_kernel(x_ref, u_ref, us_ref, v_ref, vs_ref, o_ref, acc_ref,
                    *, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _i8_dot(x_ref[...], u_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _project():
        # t in x_q units: int32 accumulator * per-column u scales.  The
        # per-row x scales cancel out of the re-quantization below and are
        # folded into the output by the caller.
        t = acc_ref[...].astype(jnp.float32) * us_ref[...]
        tmax = jnp.maximum(jnp.max(jnp.abs(t), axis=1, keepdims=True), 1e-8)
        ts = tmax / 127.0
        tq = jnp.clip(jnp.round(t / ts), -127, 127).astype(jnp.int8)
        y = _i8_dot(tq, v_ref[...]).astype(jnp.float32)
        o_ref[...] = (y * ts * vs_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "block_n", "interpret"))
def int8_lowrank_matmul(x_q: jax.Array, u_q: jax.Array, u_scale: jax.Array,
                        v_q: jax.Array, v_scale: jax.Array, *,
                        block_m: int = 256, block_k: int = 512,
                        block_n: int = 256,
                        interpret: bool = False) -> jax.Array:
    """Fused ((x_q @ u_q) requantized) @ v_q with scales applied
    post-accumulation, f32 output in x_q units (caller multiplies by the
    per-row x scales).  x_q: (M, C) int8; u_q: (C, R) int8 with u_scale
    (1, R) f32; v_q: (R, S) int8 with v_scale (1, S) f32 -> (M, S) f32."""
    m, c = x_q.shape
    r = u_q.shape[1]
    s = v_q.shape[1]
    assert u_q.shape[0] == c and v_q.shape[0] == r
    assert u_scale.shape == (1, r) and v_scale.shape == (1, s)
    assert m % block_m == 0 and c % block_k == 0 and s % block_n == 0, (
        f"shapes ({m},{c},{s}) not divisible by blocks "
        f"({block_m},{block_k},{block_n})")
    kernel = functools.partial(_lowrank_kernel, out_dtype=jnp.float32)
    return pl.pallas_call(
        kernel,
        name="int8_lowrank_matmul",
        grid=(m // block_m, s // block_n, c // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),  # x_q
            pl.BlockSpec((block_k, r), lambda i, j, k: (k, 0)),  # u_q
            pl.BlockSpec((1, r), lambda i, j, k: (0, 0)),  # u_scale
            pl.BlockSpec((r, block_n), lambda i, j, k: (0, j)),  # v_q
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),  # v_scale
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, r), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_q, u_q, u_scale.astype(jnp.float32), v_q,
      v_scale.astype(jnp.float32))
