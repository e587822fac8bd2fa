"""jit'd public wrappers around the Pallas kernels, with counted
fallbacks to the jnp reference path.

``lowrank_apply`` is the single entry point every model layer uses for a
factorized linear — it routes to the fused Pallas kernel when (a) the
caller asked for it and the platform can run it (TPU, or interpret mode
for validation) and (b) :func:`resolve_blocks` finds legal blocks for the
shape; otherwise it runs the mathematically identical jnp path (the only
path exercised inside the 512-device SPMD dry-run — see DESIGN.md §3).
``lowrank_ffn_apply`` is the same dispatcher for the fused low-rank SwiGLU
first half.

Both fused forwards carry a freezing-aware ``jax.custom_vjp`` whose backward
is the Pallas kernel set in :mod:`repro.kernels.lowrank_bwd` — the rank-r
intermediates stay in VMEM scratch, and a *static* ``freeze_group`` (the
sequential-freezing phase, Algorithm 2) elides the frozen factor's gradient
kernel at trace time, so it is never emitted rather than dead-code-eliminated
after the fact (DESIGN.md §3).

:class:`KernelPolicy` is how the launch layer threads those static choices
through the model zoo: every model function already forwards its
``use_pallas`` argument verbatim down to :func:`repro.models.common.linear`,
so the policy rides that argument and no intermediate signature changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as _shmod
from repro.kernels import ref
from repro.kernels.int8_matmul import (int8_lowrank_matmul, int8_matmul,
                                       quantize_rowwise)
from repro.kernels.lowrank_bwd import (lowrank_matmul_du, lowrank_matmul_dv,
                                       lowrank_matmul_dx)
from repro.kernels.lowrank_ffn import lowrank_gated_ffn
from repro.kernels.lowrank_matmul import lowrank_matmul
from repro.obs import registry as obs_registry

__all__ = [
    "KernelPolicy", "as_policy", "kernel_available",
    "lowrank_apply", "lowrank_matmul_vjp",
    "lowrank_ffn_apply", "lowrank_ffn_vjp",
    "int8_apply", "int8_lowrank_apply",
    "Fallback", "capture_fallbacks", "resolve_blocks",
]

_log = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# Fallback accounting
# --------------------------------------------------------------------------
#
# Every dispatcher below can take the jnp reference path (off-TPU, blocks
# that fit no VMEM budget, shard_map regions with no legal mapping).  A
# TIMING harness that thinks it measured the kernel while it measured the
# fallback poisons the autotune table, and a chip run that thinks it ran
# the paper's kernels while it ran jnp measures the wrong program.
# ``capture_fallbacks`` records every fallback decision made while the
# context is open (dispatch runs in Python at trace time, so notes fire
# exactly when a call traces); kernels/autotune.py refuses to mint a
# ``source="measured"`` entry whenever the capture is non-empty.
#
# Beyond the capture context, every fallback ALSO (a) increments the
# ``kernel_fallbacks{op, reason}`` counter in the default metrics registry
# (repro.obs — visible in production paths, not only tests) and (b) logs
# once per unique (op, reason, shape): at WARNING for reasons that mean a
# kernel the caller asked for degraded (VMEM overflow, mesh mapping
# failures), at DEBUG for the expected ones ("platform" off-TPU,
# "disabled" by policy) so CPU runs aren't spammed.  ``chip_smoke.py``
# fails on any entry of the counter from its train phase.


@dataclasses.dataclass(frozen=True)
class Fallback:
    """One dispatcher decision to run the jnp path instead of the kernel."""

    op: str
    reason: str  # "platform" | "disabled" | "vmem" | "mesh-*" | ...
    #              ("indivisible": the int8 decode dispatchers only)
    shape: Tuple[int, ...] = ()


_FALLBACK_SINKS: List[List[Fallback]] = []
_LOGGED_FALLBACKS: set = set()


@contextlib.contextmanager
def capture_fallbacks():
    """Collect every dispatcher fallback taken while open (nestable)."""
    sink: List[Fallback] = []
    _FALLBACK_SINKS.append(sink)
    try:
        yield sink
    finally:
        _FALLBACK_SINKS.remove(sink)


# reasons that are expected on the current host/policy — everything else
# means a kernel the caller explicitly requested quietly degraded
_EXPECTED_FALLBACK_REASONS = ("platform", "disabled", "int8_pool", "chunk")


def _note_fallback(op: str, reason: str, shape: Tuple[int, ...] = ()) -> None:
    fb = Fallback(op, reason, tuple(int(d) for d in shape))
    for sink in _FALLBACK_SINKS:
        sink.append(fb)
    obs_registry.default_registry().counter(
        "kernel_fallbacks",
        "dispatcher took the jnp reference path").inc(op=op, reason=reason)
    key = (op, reason, fb.shape)
    if key not in _LOGGED_FALLBACKS:  # once per unique (op, reason, shape)
        _LOGGED_FALLBACKS.add(key)
        level = (logging.DEBUG if reason in _EXPECTED_FALLBACK_REASONS
                 else logging.WARNING)
        _log.log(level, "kernel fallback: op=%s reason=%s shape=%s "
                 "(jnp reference path used)", op, reason, fb.shape)


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Static per-step kernel dispatch choices.

    Hashable and compared by value: it is closed over by the jit'd train
    step, so one compiled executable exists per distinct policy (in
    practice: one per sequential-freezing phase, exactly like the ``phase``
    static argument it derives from).

    ``freeze_group`` names the factor group frozen this phase (0 = u,
    1 = v, per ``core.freezing``); the matching backward kernel is not
    emitted.  ``interpret`` runs the Pallas kernels in interpret mode
    (CPU validation).  The block sizes feed every kernel launch.

    ``autotune`` consults the active :class:`~repro.kernels.autotune.
    TuningTable` at trace time — a hit overrides the static block sizes
    for that (op, shape-bucket, dtype, freeze_phase); a miss keeps them.
    ``double_buffer`` selects the explicit two-slot DMA pipeline variant
    of the fused fwd/dx kernels (prefetch the next U/V tile while the
    rank-r intermediate is in the MXU).  ``int8_decode`` picks how the
    serving engine consumes rank-quantized int8 exports: ``"native"``
    (int8 x int8 -> int32 kernels / weight-only f32 fallback) or
    ``"bf16"`` (legacy dequantize-everything round trip, kept as the
    benchmark baseline).
    """

    use_pallas: bool = False
    freeze_group: Optional[int] = None
    interpret: bool = False
    block_m: int = 256
    block_k: int = 512
    block_n: int = 256
    autotune: bool = False
    double_buffer: bool = False
    int8_decode: str = "native"

    def __bool__(self) -> bool:  # `if use_pallas:` keeps working
        return self.use_pallas


def as_policy(use_pallas: Union[bool, KernelPolicy, None]) -> KernelPolicy:
    """Normalize the ``use_pallas`` argument (legacy bool or policy)."""
    if isinstance(use_pallas, KernelPolicy):
        return use_pallas
    return KernelPolicy(use_pallas=bool(use_pallas))


# --------------------------------------------------------------------------
# lowrank matmul: fused forward + freezing-aware fused backward
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def lowrank_matmul_vjp(x, u, v, block_m, block_k, block_n, interpret,
                       freeze_group, double_buffer=False):
    return lowrank_matmul(x, u, v, block_m=block_m, block_k=block_k,
                          block_n=block_n, interpret=interpret,
                          double_buffer=double_buffer)


def _lr_fwd(x, u, v, block_m, block_k, block_n, interpret, freeze_group,
            double_buffer=False):
    y = lowrank_matmul(x, u, v, block_m=block_m, block_k=block_k,
                       block_n=block_n, interpret=interpret,
                       double_buffer=double_buffer)
    return y, (x, u, v)


def _lr_bwd(block_m, block_k, block_n, interpret, freeze_group,
            double_buffer, res, dy):
    x, u, v = res
    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n,
              interpret=interpret)
    dx = lowrank_matmul_dx(dy, u, v, double_buffer=double_buffer, **kw)
    # freeze_group is STATIC: the frozen factor's kernel is absent from the
    # jaxpr, not emitted-then-DCE'd.  The zeros cotangent is dropped by the
    # upstream stop_gradient transpose.
    du = (jnp.zeros(u.shape, u.dtype) if freeze_group == 0
          else lowrank_matmul_du(x, dy, v, out_dtype=u.dtype, **kw))
    dv = (jnp.zeros(v.shape, v.dtype) if freeze_group == 1
          else lowrank_matmul_dv(x, u, dy, out_dtype=v.dtype, **kw))
    return dx, du, dv


lowrank_matmul_vjp.defvjp(_lr_fwd, _lr_bwd)


def kernel_available(platform: str | None = None) -> bool:
    platform = platform or jax.default_backend()
    return platform == "tpu"


def _divisible(m: int, c: int, s: int, bm: int, bk: int, bn: int) -> bool:
    return m % bm == 0 and c % bk == 0 and s % bn == 0


# Mosaic's default scoped-VMEM limit is 16 MiB on the v5e this repo
# targets; the rest is headroom for the compiler's own temporaries (dot
# results, masks) that the estimate below does not count.
VMEM_BUDGET = 12 * 2 ** 20


def _vmem_bytes(bm: int, bk: int, bn: int, r: int, itemsize: int) -> int:
    """Peak VMEM of the fwd/dx/du/dv kernels at one block choice.

    Each of the four streams the same four tiles — (bm, bk), (bm, bn),
    (bk, r), (r, bn) — double-buffered by the grid pipeline, and holds f32
    scratch of (bm, r) plus one of (bk, r) / (r, bn)."""
    tiles = bm * bk + bm * bn + bk * r + r * bn
    return 2 * itemsize * tiles + 4 * r * (bm + max(bk, bn))


def resolve_blocks(m: int, c: int, s: int, r: int, dtype,
                   blocks: Tuple[int, int, int]
                   ) -> Optional[Tuple[int, int, int]]:
    """Blocks the fused low-rank kernels can run ``(m, c) x (c, r) x (r, s)``
    with, or None.

    Each requested block is kept where it divides its dimension; otherwise
    the whole dimension is the block, which the TPU tiling always accepts
    (a block equal to the array dimension needs no (8, 128) alignment).
    smollm-360m's 960-wide residual and 320-wide K/V take this path at the
    default (256, 512, 256).  None when the resulting tiles overflow
    :data:`VMEM_BUDGET`.  ``r`` is the rank the kernel holds per tile (the
    sum of both branches' ranks for the gated FFN)."""
    bm, bk, bn = (b if d % b == 0 else d for d, b in zip((m, c, s), blocks))
    if _vmem_bytes(bm, bk, bn, r, jnp.dtype(dtype).itemsize) > VMEM_BUDGET:
        return None
    return bm, bk, bn


def _tuned_blocks(op: str, m: int, c: int, r: int, s: int, dtype,
                  freeze_group: Optional[int],
                  blocks: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Trace-time tuning-table consult (shapes are static under jit).

    A hit overrides the policy blocks IF the winning blocks still divide
    the actual shape (the table buckets m, so a 512-bucket winner may not
    divide an m=384 call — then the requested blocks stand).  A miss, or
    no active table, keeps the requested blocks: an empty table is never
    worse than the legacy fixed config.
    """
    from repro.kernels import autotune  # deferred: autotune imports ops

    table = autotune.get_table()
    if table is None:
        return blocks
    e = table.lookup(op, m, c, r, s, dtype, freeze_phase=freeze_group)
    if e is None or not _divisible(m, c, s, e.block_m, e.block_k, e.block_n):
        return blocks
    return (e.block_m, e.block_k, e.block_n)


# --------------------------------------------------------------------------
# shard_map compatibility (DESIGN.md §9)
# --------------------------------------------------------------------------
#
# A pallas_call is a custom call: the SPMD partitioner cannot split it, so
# tracing one under a >1-device mesh would force XLA to all-gather every
# operand — including the factors, defeating both TP and the frozen-factor
# zero-traffic contract.  Under an active multi-device ``axis_rules`` mesh
# the dispatchers therefore run the fused kernels inside a FULL-MANUAL
# ``shard_map``: batch rows over the DP axes, the second factor / output
# columns over ``model``, the first factor and rank dim replicated (matching
# FROZEN_PARAM_RULES / the all-gathered ZeRO layout).  The backward is a
# wrapper-level ``custom_vjp`` whose cotangent psums are built per factor
# ONLY when that factor is trainable — with a static ``freeze_group`` the
# frozen factor's backward kernel AND its cross-device psum are absent from
# the jaxpr (the cotangent is a host-built literal zeros outside the mapped
# region), extending the §3 kernel-absence contract to collectives.


def _multi_device_mesh() -> bool:
    """True when tracing under a >1-device ``axis_rules`` mesh — where the
    BARE pallas_call path is forbidden (the partitioner would replicate
    it); the choice is then shard_map or the jnp fallback, never bare."""
    mesh = _shmod.current_mesh()
    return mesh is not None and mesh.devices.size > 1


def _sharded_ctx(m: int, s: int) -> Optional[Tuple]:
    """(mesh, batch_axes, model_axis) when the fused kernels must run under
    shard_map; None for single-device / no-mesh / already-manual tracing."""
    mesh = _shmod.current_mesh()
    if mesh is None or mesh.devices.size <= 1:
        return None
    if _shmod.current_manual_axes():
        return None  # enclosing shard_map owns the mapping (e.g. int8 DP)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    act = _shmod._CTX.act_rules or _shmod.ACT_RULES
    spec = _shmod._resolve_spec((m, s), ("batch", None), act, mesh)
    part = spec[0]
    batch_axes = (() if part is None
                  else (part,) if isinstance(part, str) else tuple(part))
    model_axis = ("model" if sizes.get("model", 1) > 1 and s % sizes["model"] == 0
                  else None)
    if not batch_axes and model_axis is None:
        return None
    return mesh, batch_axes, model_axis


def _axis_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 1
    for a in axes:
        total *= sizes[a]
    return total


def _bpart(batch_axes):
    if not batch_axes:
        return None
    return batch_axes[0] if len(batch_axes) == 1 else batch_axes


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _lowrank_sharded(x, u, v, mesh, batch_axes, model_axis,
                     block_m, block_k, block_n, interpret, freeze_group):
    """Fused low-rank matmul under full-manual shard_map (see module notes).

    Specs: ``x (M, C)`` rows over ``batch_axes``; ``u (C, r)`` replicated;
    ``v (r, S)`` columns over ``model_axis``; out ``(M, S)`` rows x cols.
    """
    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n,
              interpret=interpret)
    return shard_map(
        functools.partial(lowrank_matmul, **kw), mesh=mesh,
        in_specs=(P(_bpart(batch_axes), None), P(), P(None, model_axis)),
        out_specs=P(_bpart(batch_axes), model_axis),
        check_vma=False)(x, u, v)


def _lr_sharded_fwd(x, u, v, mesh, batch_axes, model_axis,
                    block_m, block_k, block_n, interpret, freeze_group):
    y = _lowrank_sharded(x, u, v, mesh, batch_axes, model_axis,
                         block_m, block_k, block_n, interpret, freeze_group)
    return y, (x, u, v)


def _lr_sharded_bwd(mesh, batch_axes, model_axis, block_m, block_k, block_n,
                    interpret, freeze_group, res, dy):
    x, u, v = res
    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n,
              interpret=interpret)
    bp = _bpart(batch_axes)
    model = (model_axis,) if model_axis else ()

    def inner(x, u, v, dy):
        # dt/t recompute is per-shard; cotangents of replicated operands are
        # partial over the axes their contraction is mapped on and must be
        # psummed — EXCEPT the frozen factor's, which is never built.
        dx = lowrank_matmul_dx(dy, u, v, **kw)
        if model:
            dx = jax.lax.psum(dx, model)
        outs = [dx]
        if freeze_group != 0:
            du = lowrank_matmul_du(x, dy, v, out_dtype=u.dtype, **kw)
            if batch_axes + model:
                du = jax.lax.psum(du, batch_axes + model)
            outs.append(du)
        if freeze_group != 1:
            dv = lowrank_matmul_dv(x, u, dy, out_dtype=v.dtype, **kw)
            if batch_axes:
                dv = jax.lax.psum(dv, batch_axes)
            outs.append(dv)
        return tuple(outs)

    out_specs = [P(bp, None)]
    if freeze_group != 0:
        out_specs.append(P())
    if freeze_group != 1:
        out_specs.append(P(None, model_axis))
    outs = shard_map(
        inner, mesh=mesh,
        in_specs=(P(bp, None), P(), P(None, model_axis), P(bp, model_axis)),
        out_specs=tuple(out_specs), check_vma=False)(x, u, v, dy)
    outs = list(outs)
    dx = outs.pop(0)
    du = jnp.zeros(u.shape, u.dtype) if freeze_group == 0 else outs.pop(0)
    dv = jnp.zeros(v.shape, v.dtype) if freeze_group == 1 else outs.pop(0)
    return dx, du, dv


_lowrank_sharded.defvjp(_lr_sharded_fwd, _lr_sharded_bwd)


def _kernel_plan(op: str, m: int, c: int, r: int, s: int, dtype,
                 blocks: Tuple[int, int, int], *, use_kernel: Optional[bool],
                 interpret: bool, autotune: bool,
                 freeze_group: Optional[int], vmem_rank: Optional[int] = None):
    """Where one fused low-rank call runs.

    Returns ``(blocks, sctx)`` for the kernel path — ``sctx`` is the
    ``(mesh, batch_axes, model_axis)`` shard_map mapping under a
    multi-device mesh, else None — or None after noting the fallback.
    Under a multi-device mesh the bare pallas_call would be replicated by
    the partitioner (gathering every operand, frozen factors included), so
    the choice there is shard_map or the jnp path, never the bare kernel;
    blocks then resolve against the LOCAL shapes.
    """
    use = use_kernel if use_kernel is not None else (
        kernel_available() or interpret)
    if not use:
        _note_fallback(op, "disabled" if use_kernel is not None
                       else "platform", (m, c, s))
        return None
    if autotune:
        blocks = _tuned_blocks(op, m, c, r, s, dtype, freeze_group, blocks)
    m_l, s_l, sctx = m, s, None
    if _multi_device_mesh():
        sctx = _sharded_ctx(m, s)
        if sctx is None:
            _note_fallback(op, "mesh-no-mapping", (m, c, s))
            return None
        mesh, batch_axes, model_axis = sctx
        m_l = m // _axis_size(mesh, batch_axes)
        s_l = s // (_axis_size(mesh, (model_axis,)) if model_axis else 1)
    resolved = resolve_blocks(m_l, c, s_l, vmem_rank or r, dtype, blocks)
    if resolved is None:
        _note_fallback(op, "vmem", (m, c, s))
        return None
    return resolved, sctx


def lowrank_apply(
    x: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    use_kernel: bool | None = None,
    interpret: bool = False,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 256,
    freeze_group: Optional[int] = None,
    autotune: bool = False,
    double_buffer: bool = False,
) -> jax.Array:
    """y = (x @ u) @ v for arbitrary-batch x (..., C)."""
    c, r = u.shape
    s = v.shape[1]
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    plan = _kernel_plan("lowrank_fwd", m, c, r, s, x.dtype,
                        (block_m, block_k, block_n), use_kernel=use_kernel,
                        interpret=interpret, autotune=autotune,
                        freeze_group=freeze_group)
    if plan is not None:
        (bm, bk, bn), sctx = plan
        if sctx is not None:
            y = _lowrank_sharded(x.reshape(m, c), u, v, *sctx, bm, bk, bn,
                                 interpret, freeze_group)
        else:
            y = lowrank_matmul_vjp(x.reshape(m, c), u, v, bm, bk, bn,
                                   interpret, freeze_group, double_buffer)
        return y.reshape(*lead, s)
    # One freeze contract on all paths: stop_gradient the frozen factor so
    # a shape-dependent fallback can't silently train it.
    if freeze_group == 0:
        u = jax.lax.stop_gradient(u)
    elif freeze_group == 1:
        v = jax.lax.stop_gradient(v)
    return ref.lowrank_matmul_ref(x.reshape(m, c), u, v).reshape(*lead, s)


# --------------------------------------------------------------------------
# lowrank gated FFN: fused forward + freezing-aware backward
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def lowrank_ffn_vjp(x, gu, gv, uu, uv, block_m, block_k, block_n, interpret,
                    freeze_group):
    return lowrank_gated_ffn(x, gu, gv, uu, uv, block_m=block_m,
                             block_k=block_k, block_n=block_n,
                             interpret=interpret)


def _ffn_fwd(x, gu, gv, uu, uv, block_m, block_k, block_n, interpret,
             freeze_group):
    y = lowrank_gated_ffn(x, gu, gv, uu, uv, block_m=block_m,
                          block_k=block_k, block_n=block_n,
                          interpret=interpret)
    return y, (x, gu, gv, uu, uv)


def _ffn_bwd(block_m, block_k, block_n, interpret, freeze_group, res, dy):
    x, gu, gv, uu, uv = res
    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n,
              interpret=interpret)
    # Recompute the branch pre-activations with the fused forward kernel —
    # cheaper in HBM bytes than stashing two (M, F) tensors across the step.
    g = lowrank_matmul(x, gu, gv, **kw)
    up = lowrank_matmul(x, uu, uv, **kw)
    gf, upf = g.astype(jnp.float32), up.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    sg = jax.nn.sigmoid(gf)
    silu_g = gf * sg
    # d silu(g)/dg = sigmoid(g) * (1 + g * (1 - sigmoid(g)))
    dg = (dyf * upf * (sg * (1.0 + gf * (1.0 - sg)))).astype(x.dtype)
    dup = (dyf * silu_g).astype(x.dtype)

    dx = (lowrank_matmul_dx(dg, gu, gv, **kw)
          + lowrank_matmul_dx(dup, uu, uv, **kw))
    if freeze_group == 0:
        dgu = jnp.zeros(gu.shape, gu.dtype)
        duu = jnp.zeros(uu.shape, uu.dtype)
    else:
        dgu = lowrank_matmul_du(x, dg, gv, out_dtype=gu.dtype, **kw)
        duu = lowrank_matmul_du(x, dup, uv, out_dtype=uu.dtype, **kw)
    if freeze_group == 1:
        dgv = jnp.zeros(gv.shape, gv.dtype)
        duv = jnp.zeros(uv.shape, uv.dtype)
    else:
        dgv = lowrank_matmul_dv(x, gu, dg, out_dtype=gv.dtype, **kw)
        duv = lowrank_matmul_dv(x, uu, dup, out_dtype=uv.dtype, **kw)
    return dx, dgu, dgv, duu, duv


lowrank_ffn_vjp.defvjp(_ffn_fwd, _ffn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _lowrank_ffn_sharded(x, gu, gv, uu, uv, mesh, batch_axes, model_axis,
                         block_m, block_k, block_n, interpret, freeze_group):
    """Fused low-rank SwiGLU under full-manual shard_map.

    Same layout contract as :func:`_lowrank_sharded`: x rows over the DP
    axes, ``gv``/``uv`` (and the gated output) columns over ``model``,
    ``gu``/``uu`` and both rank dims replicated.
    """
    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n,
              interpret=interpret)
    bp = _bpart(batch_axes)
    return shard_map(
        functools.partial(lowrank_gated_ffn, **kw), mesh=mesh,
        in_specs=(P(bp, None), P(), P(None, model_axis),
                  P(), P(None, model_axis)),
        out_specs=P(bp, model_axis), check_vma=False)(x, gu, gv, uu, uv)


def _ffn_sharded_fwd(x, gu, gv, uu, uv, mesh, batch_axes, model_axis,
                     block_m, block_k, block_n, interpret, freeze_group):
    y = _lowrank_ffn_sharded(x, gu, gv, uu, uv, mesh, batch_axes, model_axis,
                             block_m, block_k, block_n, interpret,
                             freeze_group)
    return y, (x, gu, gv, uu, uv)


def _ffn_sharded_bwd(mesh, batch_axes, model_axis, block_m, block_k, block_n,
                     interpret, freeze_group, res, dy):
    x, gu, gv, uu, uv = res
    kw = dict(block_m=block_m, block_k=block_k, block_n=block_n,
              interpret=interpret)
    bp = _bpart(batch_axes)
    model = (model_axis,) if model_axis else ()

    def inner(x, gu, gv, uu, uv, dy):
        # per-shard recompute of the branch pre-activations (§3 trade),
        # local in both the row and column shards
        g = lowrank_matmul(x, gu, gv, **kw)
        up = lowrank_matmul(x, uu, uv, **kw)
        gf, upf = g.astype(jnp.float32), up.astype(jnp.float32)
        dyf = dy.astype(jnp.float32)
        sg = jax.nn.sigmoid(gf)
        silu_g = gf * sg
        dg = (dyf * upf * (sg * (1.0 + gf * (1.0 - sg)))).astype(x.dtype)
        dup = (dyf * silu_g).astype(x.dtype)

        dx = (lowrank_matmul_dx(dg, gu, gv, **kw)
              + lowrank_matmul_dx(dup, uu, uv, **kw))
        if model:
            dx = jax.lax.psum(dx, model)
        outs = [dx]
        if freeze_group != 0:
            dgu = lowrank_matmul_du(x, dg, gv, out_dtype=gu.dtype, **kw)
            duu = lowrank_matmul_du(x, dup, uv, out_dtype=uu.dtype, **kw)
            if batch_axes + model:
                dgu = jax.lax.psum(dgu, batch_axes + model)
                duu = jax.lax.psum(duu, batch_axes + model)
            outs += [dgu, duu]
        if freeze_group != 1:
            dgv = lowrank_matmul_dv(x, gu, dg, out_dtype=gv.dtype, **kw)
            duv = lowrank_matmul_dv(x, uu, dup, out_dtype=uv.dtype, **kw)
            if batch_axes:
                dgv = jax.lax.psum(dgv, batch_axes)
                duv = jax.lax.psum(duv, batch_axes)
            outs += [dgv, duv]
        return tuple(outs)

    out_specs = [P(bp, None)]
    if freeze_group != 0:
        out_specs += [P(), P()]
    if freeze_group != 1:
        out_specs += [P(None, model_axis), P(None, model_axis)]
    outs = list(shard_map(
        inner, mesh=mesh,
        in_specs=(P(bp, None), P(), P(None, model_axis), P(),
                  P(None, model_axis), P(bp, model_axis)),
        out_specs=tuple(out_specs), check_vma=False)(x, gu, gv, uu, uv, dy))
    dx = outs.pop(0)
    if freeze_group == 0:
        dgu, duu = jnp.zeros(gu.shape, gu.dtype), jnp.zeros(uu.shape, uu.dtype)
    else:
        dgu, duu = outs.pop(0), outs.pop(0)
    if freeze_group == 1:
        dgv, duv = jnp.zeros(gv.shape, gv.dtype), jnp.zeros(uv.shape, uv.dtype)
    else:
        dgv, duv = outs.pop(0), outs.pop(0)
    return dx, dgu, dgv, duu, duv


_lowrank_ffn_sharded.defvjp(_ffn_sharded_fwd, _ffn_sharded_bwd)


def lowrank_ffn_apply(
    x: jax.Array,
    gu: jax.Array, gv: jax.Array,
    uu: jax.Array, uv: jax.Array,
    *,
    use_kernel: bool | None = None,
    interpret: bool = False,
    block_m: int = 256,
    block_k: int = 512,
    block_n: int = 256,
    freeze_group: Optional[int] = None,
    autotune: bool = False,
) -> jax.Array:
    """silu((x gu) gv) * ((x uu) uv) for arbitrary-batch x (..., C)."""
    c = gu.shape[0]
    f = gv.shape[1]
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    # the fused forward holds both branches' rank-r accumulators per tile
    plan = _kernel_plan("lowrank_ffn", m, c, gu.shape[1], f, x.dtype,
                        (block_m, block_k, block_n), use_kernel=use_kernel,
                        interpret=interpret, autotune=autotune,
                        freeze_group=freeze_group,
                        vmem_rank=gu.shape[1] + uu.shape[1])
    if plan is not None:
        (bm, bk, bn), sctx = plan
        if sctx is not None:
            y = _lowrank_ffn_sharded(x.reshape(m, c), gu, gv, uu, uv, *sctx,
                                     bm, bk, bn, interpret, freeze_group)
        else:
            y = lowrank_ffn_vjp(x.reshape(m, c), gu, gv, uu, uv,
                                bm, bk, bn, interpret, freeze_group)
        return y.reshape(*lead, f)
    if freeze_group == 0:
        gu, uu = jax.lax.stop_gradient(gu), jax.lax.stop_gradient(uu)
    elif freeze_group == 1:
        gv, uv = jax.lax.stop_gradient(gv), jax.lax.stop_gradient(uv)
    return ref.lowrank_gated_ffn_ref(x.reshape(m, c), gu, gv, uu, uv
                                     ).reshape(*lead, f)


# --------------------------------------------------------------------------
# int8 decode dispatchers (serving's rank-quantized export path)
# --------------------------------------------------------------------------
#
# ``serving/export.py(quantize_factors="int8")`` stores weights as int8
# values + per-output-column f32 scales.  These dispatchers consume them
# natively: on TPU (or interpret mode) via the kernels in
# ``kernels/int8_matmul.py`` — exact int32 accumulation, scales applied
# post-accumulation over the (M, S) output; everywhere else via the
# weight-only f32 formulation ``x @ (w_q.astype(f32) * w_scale)``, which
# XLA-CPU fuses (convert + scale sink into the GEMM packing — measured
# faster than scale-folding after the matmul) and which still beats the
# bf16 dequantize-everything round trip it replaces.  The fallback skips
# activation quantization (weight-only), so it is slightly MORE accurate
# than the kernel path; parity tolerances live in tests/test_int8_decode.py.


def int8_apply(x: jax.Array, w_q: jax.Array, w_scale: jax.Array, *,
               use_kernel: bool | None = None, interpret: bool = False,
               block_m: int = 256, block_k: int = 512, block_n: int = 256,
               ) -> jax.Array:
    """y = x @ dequant(w_q) for per-output-column int8 dense weights."""
    c, s = w_q.shape
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    ws = w_scale.reshape(1, s).astype(jnp.float32)
    use = use_kernel if use_kernel is not None else (kernel_available() or interpret)
    if use and _multi_device_mesh():
        _note_fallback("int8_dense", "mesh", (m, c, s))
    elif use and _divisible(m, c, s, block_m, block_k, block_n):
        x_q, x_scale = quantize_rowwise(x.reshape(m, c))
        acc = int8_matmul(x_q, w_q, block_m=block_m, block_k=block_k,
                          block_n=block_n, interpret=interpret)
        y = acc.astype(jnp.float32) * x_scale * ws
        return y.astype(x.dtype).reshape(*lead, s)
    elif use:
        _note_fallback("int8_dense", "indivisible", (m, c, s))
    else:
        _note_fallback(
            "int8_dense",
            "disabled" if use_kernel is not None else "platform", (m, c, s))
    y = jnp.dot(x.reshape(m, c).astype(jnp.float32),
                w_q.astype(jnp.float32) * ws)
    return y.astype(x.dtype).reshape(*lead, s)


def int8_lowrank_apply(x: jax.Array, u_q: jax.Array, u_scale: jax.Array,
                       v_q: jax.Array, v_scale: jax.Array, *,
                       use_kernel: bool | None = None,
                       interpret: bool = False, block_m: int = 256,
                       block_k: int = 512, block_n: int = 256) -> jax.Array:
    """y = (x @ dequant(u_q)) @ dequant(v_q) for int8 factor pairs.

    The kernel path fuses both int8 matmuls with an in-VMEM requantized
    rank-r intermediate (per-row x scales factor out of the requantization
    and are folded into the output here)."""
    c, r = u_q.shape
    s = v_q.shape[1]
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    us = u_scale.reshape(1, r).astype(jnp.float32)
    vs = v_scale.reshape(1, s).astype(jnp.float32)
    use = use_kernel if use_kernel is not None else (kernel_available() or interpret)
    if use and _multi_device_mesh():
        _note_fallback("int8_lowrank", "mesh", (m, c, s))
    elif use and _divisible(m, c, s, block_m, block_k, block_n):
        x_q, x_scale = quantize_rowwise(x.reshape(m, c))
        y = int8_lowrank_matmul(x_q, u_q, us, v_q, vs, block_m=block_m,
                                block_k=block_k, block_n=block_n,
                                interpret=interpret)
        return (y * x_scale).astype(x.dtype).reshape(*lead, s)
    elif use:
        _note_fallback("int8_lowrank", "indivisible", (m, c, s))
    else:
        _note_fallback(
            "int8_lowrank",
            "disabled" if use_kernel is not None else "platform", (m, c, s))
    xf = x.reshape(m, c).astype(jnp.float32)
    t = jnp.dot(xf, u_q.astype(jnp.float32) * us)
    y = jnp.dot(t, v_q.astype(jnp.float32) * vs)
    return y.astype(x.dtype).reshape(*lead, s)


# --------------------------------------------------------------------------
# paged decode attention
# --------------------------------------------------------------------------

def paged_attention_kernel(*, int8_pool: bool, chunk: int, interpret: bool,
                           shape: Tuple[int, ...]) -> bool:
    """Whether a paged decode step attends through the paged-attention
    kernel (``kernels/paged_attention.py``), chosen by what the trace
    shows: a bf16 pool, one token per slot (``chunk`` == 1), a TPU or
    interpret mode, one device.  Otherwise notes why and returns False:
    the caller gathers the slots' views and runs dense attention.

    ``int8_pool`` and ``chunk`` (the speculative verify and radix extend
    chunks) are the gather path by design; ``mesh``: under a multi-device
    mesh the partitioner would replicate the bare kernel, pool and all."""
    reason = ("int8_pool" if int8_pool else "chunk" if chunk > 1
              else "platform" if not (kernel_available() or interpret)
              else "mesh" if _multi_device_mesh() else None)
    if reason is not None:
        _note_fallback("paged_attention", reason, shape)
    return reason is None
