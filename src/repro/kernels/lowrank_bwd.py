"""Fused backward Pallas TPU kernels for the low-rank matmul y = (x @ U) @ V.

Autodiff through the un-fused reference composition re-materializes the
rank-r intermediates in HBM twice per backward step — ``t = x @ U`` for dV
and ``dt = dy @ Vᵀ`` for dU/dx — which re-introduces exactly the memory-bound
pathology the fused forward removes (DESIGN.md §3).  These kernels keep every
(M, r) intermediate in a VMEM scratch accumulator:

* ``lowrank_matmul_dx``:  dx = (dy @ Vᵀ) @ Uᵀ — the mirror image of the
  forward kernel: grid (M/bm, C/bk, S/bn), S innermost; ``dt`` accumulates in
  VMEM across the S loop and the second matmul (against Uᵀ) fires on the last
  S step.
* ``lowrank_matmul_du``:  dU = xᵀ @ (dy @ Vᵀ) — grid (C/bk, M/bm, S/bn);
  ``dt`` is rebuilt per (k, m) tile in VMEM and immediately contracted into a
  VMEM (bk, r) output accumulator, so neither (M, r) nor any (M, C)-sized
  temporary ever reaches HBM.  ``dt`` is recomputed C/bk times — FLOPs (on
  the idle MXU) traded for HBM bytes (the bound resource).
* ``lowrank_matmul_dv``:  dV = (x @ U)ᵀ @ dy — symmetric: grid
  (S/bn, M/bm, C/bk) with ``t`` rebuilt per (n, m) tile (S/bn recomputes).

All three assume the same block divisibility as the forward kernel (the
``ops.lowrank_apply`` dispatcher resolves one block choice per call and the
VJP reuses it) and keep the full rank r per tile.  Where rank quantization
(Algorithm 1) put r on a multiple of the MXU tile, the r-contractions waste
no systolic-array lanes in the backward either; an r off the tile (see
lowrank_matmul.py) pads them here as in the forward.

Transposed operands are never materialized: the kernels read the same U/V/x
blocks the forward reads and phrase the transpose as ``dot_general``
contracting dimension numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["lowrank_matmul_dx", "lowrank_matmul_du", "lowrank_matmul_dv"]


def _dot_t2(a, b):
    """a @ bᵀ without materializing bᵀ: (m, k) x (n, k) -> (m, n)."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dot_t1(a, b):
    """aᵀ @ b without materializing aᵀ: (k, m) x (k, n) -> (m, n)."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# dx = (dy @ Vᵀ) @ Uᵀ
# --------------------------------------------------------------------------

def _dx_kernel(dy_ref, u_ref, v_ref, o_ref, dt_ref, *, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dt_ref[...] = jnp.zeros_like(dt_ref)

    # dt[bm, r] += dy[bm, bs] @ V[r, bs]ᵀ, accumulated over S blocks.
    dt_ref[...] += _dot_t2(dy_ref[...], v_ref[...])

    # Final S block: dx[bm, bc] = dt[bm, r] @ U[bc, r]ᵀ straight from VMEM.
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _project():
        dt = dt_ref[...].astype(dy_ref.dtype)
        o_ref[...] = _dot_t2(dt, u_ref[...]).astype(out_dtype)


def _dx_kernel_db(dy_ref, u_ref, v_hbm_ref, o_ref, dt_ref, v_buf, v_sem,
                  *, out_dtype, block_n):
    """dx with an explicit two-slot DMA pipeline on the V stream (the
    k-loop-varying operand here) — mirror of ``lowrank_matmul._kernel_db``:
    tile k+1's (r, bn) copy is started before tile k's is awaited, so the
    transfer hides under the dy@Vᵀ MXU step."""
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    def v_copy(slot, kk):
        return pltpu.make_async_copy(
            v_hbm_ref.at[:, pl.ds(kk * block_n, block_n)],
            v_buf.at[slot], v_sem.at[slot])

    @pl.when(k == 0)
    def _warmup():
        dt_ref[...] = jnp.zeros_like(dt_ref)
        v_copy(0, 0).start()

    @pl.when(k + 1 < nk)
    def _prefetch_next():
        v_copy((k + 1) % 2, k + 1).start()

    v_copy(k % 2, k).wait()
    dt_ref[...] += _dot_t2(dy_ref[...], v_buf[k % 2])

    @pl.when(k == nk - 1)
    def _project():
        dt = dt_ref[...].astype(dy_ref.dtype)
        o_ref[...] = _dot_t2(dt, u_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "interpret",
                     "double_buffer"),
)
def lowrank_matmul_dx(
    dy: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    double_buffer: bool = False,
) -> jax.Array:
    """dx = (dy @ vᵀ) @ uᵀ.  dy: (M, S); u: (C, R); v: (R, S) -> (M, C).

    ``double_buffer`` switches the V stream to the explicit two-slot DMA
    pipeline (same numerics)."""
    m, s = dy.shape
    c, r = u.shape
    assert v.shape == (r, s), (dy.shape, u.shape, v.shape)
    assert m % block_m == 0 and c % block_k == 0 and s % block_n == 0, (
        f"shapes ({m},{c},{s}) not divisible by blocks "
        f"({block_m},{block_k},{block_n})")

    grid = (m // block_m, c // block_k, s // block_n)
    if double_buffer:
        kernel = functools.partial(_dx_kernel_db, out_dtype=dy.dtype,
                                   block_n=block_n)
        return pl.pallas_call(
            kernel,
            name="lowrank_dx_db",
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, k)),  # dy
                pl.BlockSpec((block_k, r), lambda i, j, k: (j, 0)),  # u
                pl.BlockSpec(memory_space=pl.ANY),  # v: manual DMA
            ],
            out_specs=pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, c), dy.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_m, r), jnp.float32),  # dt
                pltpu.VMEM((2, r, block_n), v.dtype),  # two-slot V buffer
                pltpu.SemaphoreType.DMA((2,)),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(dy, u, v)
    kernel = functools.partial(_dx_kernel, out_dtype=dy.dtype)
    return pl.pallas_call(
        kernel,
        name="lowrank_dx",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, k)),  # dy
            pl.BlockSpec((block_k, r), lambda i, j, k: (j, 0)),  # u
            pl.BlockSpec((r, block_n), lambda i, j, k: (0, k)),  # v
        ],
        out_specs=pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, c), dy.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(dy, u, v)


# --------------------------------------------------------------------------
# dU = xᵀ @ (dy @ Vᵀ)
# --------------------------------------------------------------------------

def _du_kernel(x_ref, dy_ref, v_ref, o_ref, dt_ref, du_ref, *, out_dtype):
    i = pl.program_id(1)  # M block
    k = pl.program_id(2)  # S block (innermost)

    @pl.when(k == 0)
    def _zero_dt():
        dt_ref[...] = jnp.zeros_like(dt_ref)

    @pl.when(jnp.logical_and(i == 0, k == 0))
    def _zero_du():
        du_ref[...] = jnp.zeros_like(du_ref)

    dt_ref[...] += _dot_t2(dy_ref[...], v_ref[...])

    last_s = k == pl.num_programs(2) - 1

    @pl.when(last_s)
    def _contract():
        dt = dt_ref[...].astype(x_ref.dtype)
        du_ref[...] += _dot_t1(x_ref[...], dt)  # (bk, r)

    @pl.when(jnp.logical_and(i == pl.num_programs(1) - 1, last_s))
    def _emit():
        o_ref[...] = du_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "interpret", "out_dtype"),
)
def lowrank_matmul_du(
    x: jax.Array,
    dy: jax.Array,
    v: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """dU = xᵀ @ (dy @ vᵀ).  x: (M, C); dy: (M, S); v: (R, S) -> (C, R).

    ``out_dtype`` must be the primal u's dtype (defaults to v's — correct
    whenever the factor pair shares a dtype); the custom_vjp caller passes
    it explicitly.
    """
    m, c = x.shape
    r, s = v.shape
    assert dy.shape == (m, s), (x.shape, dy.shape, v.shape)
    assert m % block_m == 0 and c % block_k == 0 and s % block_n == 0, (
        f"shapes ({m},{c},{s}) not divisible by blocks "
        f"({block_m},{block_k},{block_n})")

    grid = (c // block_k, m // block_m, s // block_n)
    out_dtype = out_dtype or v.dtype
    kernel = functools.partial(_du_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        name="lowrank_du",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda j, i, k: (i, j)),  # x
            pl.BlockSpec((block_m, block_n), lambda j, i, k: (i, k)),  # dy
            pl.BlockSpec((r, block_n), lambda j, i, k: (0, k)),  # v
        ],
        out_specs=pl.BlockSpec((block_k, r), lambda j, i, k: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((c, r), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, r), jnp.float32),  # dt tile
            pltpu.VMEM((block_k, r), jnp.float32),  # dU accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dy, v)


# --------------------------------------------------------------------------
# dV = (x @ U)ᵀ @ dy
# --------------------------------------------------------------------------

def _dv_kernel(x_ref, u_ref, dy_ref, o_ref, t_ref, dv_ref, *, out_dtype):
    i = pl.program_id(1)  # M block
    k = pl.program_id(2)  # C block (innermost)

    @pl.when(k == 0)
    def _zero_t():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(jnp.logical_and(i == 0, k == 0))
    def _zero_dv():
        dv_ref[...] = jnp.zeros_like(dv_ref)

    t_ref[...] += jnp.dot(x_ref[...], u_ref[...],
                          preferred_element_type=jnp.float32)

    last_c = k == pl.num_programs(2) - 1

    @pl.when(last_c)
    def _contract():
        t = t_ref[...].astype(x_ref.dtype)
        dv_ref[...] += _dot_t1(t, dy_ref[...])  # (r, bn)

    @pl.when(jnp.logical_and(i == pl.num_programs(1) - 1, last_c))
    def _emit():
        o_ref[...] = dv_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "interpret", "out_dtype"),
)
def lowrank_matmul_dv(
    x: jax.Array,
    u: jax.Array,
    dy: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """dV = (x @ u)ᵀ @ dy.  x: (M, C); u: (C, R); dy: (M, S) -> (R, S).

    ``out_dtype`` must be the primal v's dtype (defaults to u's — correct
    whenever the factor pair shares a dtype); the custom_vjp caller passes
    it explicitly.
    """
    m, c = x.shape
    r = u.shape[1]
    s = dy.shape[1]
    assert u.shape[0] == c and dy.shape[0] == m, (x.shape, u.shape, dy.shape)
    assert m % block_m == 0 and c % block_k == 0 and s % block_n == 0, (
        f"shapes ({m},{c},{s}) not divisible by blocks "
        f"({block_m},{block_k},{block_n})")

    grid = (s // block_n, m // block_m, c // block_k)
    out_dtype = out_dtype or u.dtype
    kernel = functools.partial(_dv_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        name="lowrank_dv",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda j, i, k: (i, k)),  # x
            pl.BlockSpec((block_k, r), lambda j, i, k: (k, 0)),  # u
            pl.BlockSpec((block_m, block_n), lambda j, i, k: (i, j)),  # dy
        ],
        out_specs=pl.BlockSpec((r, block_n), lambda j, i, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((r, s), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, r), jnp.float32),  # t tile
            pltpu.VMEM((r, block_n), jnp.float32),  # dV accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, u, dy)
