"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (required for the dry-run's
XLA_FLAGS ordering; see dryrun.py)."""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape: Sequence[int], names: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    The sharding rules (``repro.distributed``) place arrays with
    ``NamedSharding`` and let GSPMD propagate the rest, which needs Auto
    axes; jax's own default is Explicit.  ``devices`` (optional) builds the
    mesh over an explicit device subset.
    """
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) '(data, model)' single pod; (2,16,16) '(pod, data, model)'
    for the 512-chip two-pod config.  The pod axis is pure DP over DCN;
    growing it is how the design scales to N pods (DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over the locally available devices.

    Used by tests, CPU runs, and the sharded train driver's ``--mesh host``
    path.  The mesh is built over the FIRST ``data * model`` devices, so
    sub-meshes (e.g. 1-, 2-, 4-way cells of a forced 8-device host
    platform, or 4 of a 6-accelerator box — leftover devices idle) come
    out of the same call; see the README "Multi-device training"
    quickstart for the
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` recipe.

    Raises ``ValueError`` with the actual counts when ``data * model``
    exceeds the available devices, instead of letting ``mesh_utils`` fail
    with an opaque reshape error.
    """
    n = len(jax.devices())
    if data < 1 or model < 1:
        raise ValueError(f"make_host_mesh: axis sizes must be >= 1, "
                         f"got data={data} model={model}")
    need = data * model
    if need > n:
        raise ValueError(
            f"make_host_mesh: requested (data={data}) x (model={model}) = "
            f"{need} devices, but only {n} device(s) are available — the "
            f"mesh size must not exceed the device count. On CPU, force a "
            f"host platform with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"(set before jax initializes).")
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:need])
