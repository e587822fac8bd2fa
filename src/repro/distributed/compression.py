"""Gradient compression for the data-parallel all-reduce.

With a multi-pod mesh, data parallelism across pods makes the gradient
all-reduce the dominant traffic on the slowest (inter-pod DCN) link; on a
single-pod ``(data, model)`` mesh the same sync runs over ICI.
``value_and_grad_compressed`` computes the loss/grads under a
*partial-manual* shard_map: ONE data-parallel axis is manual (``pod`` when
the mesh has one, else ``data``) — each manual shard computes grads on its
own batch slice — while the remaining axes stay with the SPMD partitioner.
The manual-axis mean is then performed explicitly in **int8** (4x fewer
bytes on the wire — visible in the step's jaxpr as an int8 ``psum`` and in
the dry-run HLO as an int8 all-reduce), with per-tensor dynamic scales.

Because ``params`` here is the TRAINABLE partition of the partitioned train
state (DESIGN.md §7/§9), the quantize/psum tree covers exactly the
trainable leaves: a frozen factor is differentiated, quantized, and synced
exactly never — ``tests/test_sharded_train.py`` asserts the jaxpr carries
no psum at any frozen-factor shape.

Overflow-safe by construction: each shard quantizes to ``[-127//n,
127//n]``, so the int8 ring-sum cannot wrap.  The residual quantization
error can be fed back by the caller (error-feedback tree in the train loop).

Caveats (data-axis mode): inside the manual region the params enter
replicated over the manual axis (``in_specs=P()``), so pairing int8
compression with FSDP param storage re-gathers the trainable partition per
step.  And the data axis is only taken manual when it is the SOLE >1 mesh
axis (pure-DP meshes — the shard-scaling ladder, single-axis host runs):
partial-manual shard_map over ``data`` with a >1 *auto* ``model`` axis
trips an XLA sharding-propagation check on current jax
(``IsManualSubgroup``), so on TP meshes the call warns once and falls back
to plain ``value_and_grad`` — the SPMD partitioner's own all-reduce, which
is already trainable-only.  Pod meshes keep the original behavior (manual
over ``pod``, params never pod-sharded).
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

_warned_tp_fallback = False


def _quantize_pmean(g: jax.Array, axis: str, n: int) -> jax.Array:
    """int8 mean over manual ``axis`` (``n`` shards), per-tensor scales."""
    if g.dtype == jnp.int32 or g.ndim == 0:
        return jax.lax.pmean(g, axis)
    limit = max(127 // max(n, 1), 1)
    amax = jnp.max(jnp.abs(g)).astype(jnp.float32) + 1e-12
    scale = amax / limit
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -limit, limit).astype(jnp.int8)
    q_sum = jax.lax.psum(q, axis)  # int8 on the wire
    scale_mean = jax.lax.pmean(scale, axis)  # scalar consensus scale
    return q_sum.astype(jnp.float32) * scale_mean / n


def value_and_grad_compressed(
    loss_fn: Callable, params: Any, batch: Any, mesh, mode: str,
) -> Tuple[jax.Array, Any]:
    """(loss, grads) with int8 gradient sync over the outermost DP axis.

    ``params`` is the TRAINABLE partition of the train state (a
    ``None``-holed tree under sequential freezing — DESIGN.md §7): frozen
    factors are differentiated, quantized, and synced exactly never; the
    returned grad tree carries the same holes.  Falls back to plain
    ``value_and_grad`` when compression is off or no DP axis has size > 1
    (nothing to sync explicitly — the SPMD partitioner's own all-reduce,
    if any, is already trainable-only because only trainable grads exist).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axis = next((a for a in ("pod", "data") if sizes.get(a, 1) > 1), None)
    if mode == "none" or axis is None:
        return jax.value_and_grad(loss_fn)(params, batch)
    if axis == "data" and any(s > 1 for a, s in sizes.items() if a != "data"):
        # see module docstring: data-manual + auto TP axes crashes XLA's
        # sharding propagation on current jax — fall back to the SPMD
        # partitioner's implicit (trainable-only) grad all-reduce.
        global _warned_tp_fallback
        if not _warned_tp_fallback:
            _warned_tp_fallback = True
            warnings.warn(
                "grad_compression='int8' requested on a mesh with a >1 "
                "model axis: the explicit int8 data-axis sync only "
                "supports pure-DP meshes; falling back to the implicit "
                "(uncompressed) gradient all-reduce. Use a (N,1) mesh or "
                "a pod mesh for int8 sync. Warned once per process.",
                UserWarning, stacklevel=2)
        return jax.value_and_grad(loss_fn)(params, batch)
    n = sizes[axis]

    def local(p, b):
        # inside the manual region, sharding constraints must not reference
        # the manual axis (Manual/Auto axes cannot mix in one spec):
        # re-enter the rules context with the batch rule demoted to the
        # remaining (auto) DP axes, and record the manual axis so nested
        # shard_map dispatchers (kernels.ops) stand down.
        from repro.distributed import sharding as shmod
        act = dict(shmod._CTX.act_rules or shmod.ACT_RULES)
        act["batch"] = ("data", None) if axis == "pod" else (None,)
        prm = shmod._CTX.param_rules or shmod.PARAM_RULES
        with shmod.axis_rules(mesh, act=act, params=prm,
                              manual=frozenset({axis})):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
        g = jax.tree_util.tree_map(
            functools.partial(_quantize_pmean, axis=axis, n=n), g)
        return jax.lax.pmean(loss, axis), g

    batch_specs = jax.tree_util.tree_map(
        lambda x: P(*((axis,) + (None,) * (x.ndim - 1))), batch)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(), batch_specs),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )(params, batch)
