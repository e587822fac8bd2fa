"""Fused low-rank matmul Pallas TPU kernel:  y = (x @ U) @ V.

This is the compute hot-spot the paper optimizes — the decomposed linear
layer.  Executed naively, the rank-r intermediate ``t = x @ U`` round-trips
HBM between the two matmuls, which is exactly why the paper observes that LRD
alone yields only +6..13% throughput: the decomposed layer is *memory*-bound
unless r is tiny.  TPU adaptation (DESIGN.md §2):

* grid (M/bm, S/bn, C/bk); the (bm, r) intermediate lives in a VMEM scratch
  accumulator for the whole k-loop and never touches HBM;
* rank r is the contracting dim of the second matmul — rank quantization
  (Algorithm 1, analytic-tpu backend) puts it on a multiple of the MXU tile
  (128) when its sweep window holds one, and then the second matmul wastes
  no systolic-array lanes; where the window holds none (smollm-360m's q/o
  and k/v: ranks 239 and 80) r stays off the tile and the lanes are padded;
* block shapes default to (256, 512, 256): x-tile 256x512x2B = 256 KiB,
  U-tile 512 x r, V-tile r x 256 — for r <= 512 the whole working set is
  < 2 MiB, far under the ~16 MiB/core VMEM budget, leaving room for
  double-buffered pipelining of the k-loop.

The k-loop (C blocks) is the innermost grid dim, so the scratch accumulator
carries across k for a fixed (m, n) tile — standard Pallas accumulation
pattern.  The second matmul fires once, on the last k step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["lowrank_matmul"]


def _kernel(x_ref, u_ref, v_ref, o_ref, acc_ref, *, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # First matmul, accumulated over C blocks: t[bm, r] += x[bm, bk] @ U[bk, r]
    acc_ref[...] += jnp.dot(
        x_ref[...], u_ref[...], preferred_element_type=jnp.float32
    )

    # Second matmul on the final C block: y[bm, bn] = t[bm, r] @ V[r, bn].
    # The intermediate is read straight out of VMEM — no HBM round-trip.
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _project():
        t = acc_ref[...].astype(x_ref.dtype)
        o_ref[...] = jnp.dot(
            t, v_ref[...], preferred_element_type=jnp.float32
        ).astype(out_dtype)


def _kernel_db(x_ref, u_hbm_ref, v_ref, o_ref, acc_ref, u_buf, u_sem,
               *, out_dtype, block_k):
    """Explicit two-slot DMA pipeline for the U stream.

    U stays in ``pl.ANY`` (compiler-placed, HBM at these sizes) and is
    copied tile-by-tile into a double-buffered VMEM scratch: at k-step k the
    copy for tile k+1 is STARTED before the copy for tile k is awaited, so
    the (bk, r) U transfer for the next step overlaps the x@U MXU work of
    the current one.  The BlockSpec grid pipeline does the same for x/V
    implicitly; this is the explicit variant the autotuner can A/B
    (``KernelPolicy.double_buffer``) and the template for streams BlockSpec
    can't express (e.g. decode-time paged pools).
    """
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    def u_copy(slot, kk):
        return pltpu.make_async_copy(
            u_hbm_ref.at[pl.ds(kk * block_k, block_k), :],
            u_buf.at[slot], u_sem.at[slot])

    @pl.when(k == 0)
    def _warmup():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_copy(0, 0).start()

    @pl.when(k + 1 < nk)
    def _prefetch_next():
        # slot (k+1) % 2 was consumed at step k-1 — free to overwrite
        u_copy((k + 1) % 2, k + 1).start()

    u_copy(k % 2, k).wait()
    acc_ref[...] += jnp.dot(
        x_ref[...], u_buf[k % 2], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _project():
        t = acc_ref[...].astype(x_ref.dtype)
        o_ref[...] = jnp.dot(
            t, v_ref[...], preferred_element_type=jnp.float32
        ).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "interpret",
                     "double_buffer"),
)
def lowrank_matmul(
    x: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    double_buffer: bool = False,
) -> jax.Array:
    """Fused ``(x @ u) @ v``.

    x: (M, C); u: (C, R); v: (R, S) -> (M, S).  M, C, S must be divisible by
    the respective block sizes (``ops.resolve_blocks`` picks ones that are).  The
    full rank R is kept per-tile (low-rank by construction: R <= 512 after
    quantization in every config we ship).  ``double_buffer`` switches the U
    stream to the explicit two-slot DMA pipeline (same numerics — asserted
    in tests/test_kernels.py).
    """
    m, c = x.shape
    r = u.shape[1]
    s = v.shape[1]
    assert u.shape[0] == c and v.shape[0] == r, (x.shape, u.shape, v.shape)
    assert m % block_m == 0 and c % block_k == 0 and s % block_n == 0, (
        f"shapes ({m},{c},{s}) not divisible by blocks ({block_m},{block_k},{block_n})"
    )

    grid = (m // block_m, s // block_n, c // block_k)
    if double_buffer:
        kernel = functools.partial(_kernel_db, out_dtype=x.dtype,
                                   block_k=block_k)
        return pl.pallas_call(
            kernel,
            name="lowrank_fwd_db",
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),  # x
                pl.BlockSpec(memory_space=pl.ANY),  # u: manual DMA
                pl.BlockSpec((r, block_n), lambda i, j, k: (0, j)),  # v
            ],
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_m, r), jnp.float32),  # acc
                pltpu.VMEM((2, block_k, r), u.dtype),  # two-slot U buffer
                pltpu.SemaphoreType.DMA((2,)),  # one DMA sem per slot
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(x, u, v)
    kernel = functools.partial(_kernel, out_dtype=x.dtype)
    return pl.pallas_call(
        kernel,
        name="lowrank_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((block_k, r), lambda i, j, k: (k, 0)),  # u
            pl.BlockSpec((r, block_n), lambda i, j, k: (0, j)),  # v
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, u, v)
