"""Fused low-rank SwiGLU first half:  silu((x Ug) Vg) * ((x Uu) Vu).

The FFN is where LRD pays most (d_ff >> d_model mats), and after
decomposition a SwiGLU block runs FOUR matmuls whose rank-r intermediates
and two (m, f) branch outputs all round-trip HBM before the elementwise
silu*mul.  This kernel fuses the whole first half: both rank-r intermediates
live in VMEM scratch across the C loop, both branch projections and the
gated product happen per output tile — HBM sees x once and the gated
activation once.

Grid (M/bm, F/bn, C/bk), C innermost (same accumulation pattern as
lowrank_matmul.py).  Saves vs unfused, per call: 2*m*r (intermediates)
+ 3*m*f (two branch outputs written+one reread) element round-trips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["lowrank_gated_ffn"]


def _kernel(x_ref, gu_ref, gv_ref, uu_ref, uv_ref, o_ref, gacc_ref, uacc_ref,
            *, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        gacc_ref[...] = jnp.zeros_like(gacc_ref)
        uacc_ref[...] = jnp.zeros_like(uacc_ref)

    x = x_ref[...]
    gacc_ref[...] += jnp.dot(x, gu_ref[...], preferred_element_type=jnp.float32)
    uacc_ref[...] += jnp.dot(x, uu_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _project():
        g = jnp.dot(gacc_ref[...].astype(x.dtype), gv_ref[...],
                    preferred_element_type=jnp.float32)
        u = jnp.dot(uacc_ref[...].astype(x.dtype), uv_ref[...],
                    preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(g) * u).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def lowrank_gated_ffn(x, gu, gv, uu, uv, *, block_m: int = 256,
                      block_k: int = 512, block_n: int = 256,
                      interpret: bool = False) -> jax.Array:
    """x: (M, C); gate factors gu (C, Rg), gv (Rg, F); up factors uu (C, Ru),
    uv (Ru, F).  Returns silu(x gu gv) * (x uu uv): (M, F)."""
    m, c = x.shape
    rg, ru = gu.shape[1], uu.shape[1]
    f = gv.shape[1]
    assert uv.shape[1] == f and gv.shape[0] == rg and uv.shape[0] == ru
    assert m % block_m == 0 and c % block_k == 0 and f % block_n == 0, (
        (m, c, f), (block_m, block_k, block_n))
    grid = (m // block_m, f // block_n, c // block_k)
    kernel = functools.partial(_kernel, out_dtype=x.dtype)
    return pl.pallas_call(
        kernel,
        name="lowrank_ffn_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((block_k, rg), lambda i, j, k: (k, 0)),  # gu
            pl.BlockSpec((rg, block_n), lambda i, j, k: (0, j)),  # gv
            pl.BlockSpec((block_k, ru), lambda i, j, k: (k, 0)),  # uu
            pl.BlockSpec((ru, block_n), lambda i, j, k: (0, j)),  # uv
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, rg), jnp.float32),
            pltpu.VMEM((block_m, ru), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, gu, gv, uu, uv)
