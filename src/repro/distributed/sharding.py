"""Logical-axis sharding rules with divisibility-aware fallbacks.

MaxText-style: model code annotates tensors with *logical* axes
(``shard(x, "batch", "seq", None)``); an active rule table maps each logical
axis to mesh axes, skipping candidates whose mesh axes are missing, already
used by an earlier dim, or do not divide the dimension.  This is what lets a
single model definition run on (16,16), (2,16,16) and a 1-device CPU mesh —
GQA with 8 KV heads on a 16-way model axis simply falls through to the next
candidate instead of failing to partition (DESIGN.md §5).

Two rule tables each for params and activations:

* ``PARAM_RULES``           FSDP on: weights sharded over ("data", "model") —
                            ZeRO-3; the scan body all-gathers one layer slice
                            at a time (overlapped by XLA's async collectives).
* ``PARAM_RULES_NO_FSDP``   TP only (weights replicated across data).
* ``FROZEN_PARAM_RULES``    the FROZEN partition of a sequentially-frozen
                            train state (DESIGN.md §9): replicated across
                            the data/pod axes and TP-sharded over model only
                            where the forward consumes the shard locally, so
                            a frozen factor appears in NO cross-device
                            collective — no grad all-reduce (it has no grad),
                            no FSDP all-gather (it is not storage-sharded).
* ``ACT_RULES``             standard: batch over (pod, data), heads/mlp/vocab
                            over model, sequence replicated.
* ``ACT_RULES_SP``          sequence-parallel decode: long KV caches / SSM
                            state sharded over model (long_500k cells).

The ``pod`` axis is deliberately absent from every param rule: parameters are
never sharded across pods, so the only cross-pod (DCN) traffic is the
gradient all-reduce (DESIGN.md §5).
"""

from __future__ import annotations

import contextlib
import re
import threading
import warnings
from typing import Any, Dict, FrozenSet, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisCandidate = Union[None, str, Tuple[str, ...]]
RuleTable = Dict[str, Tuple[AxisCandidate, ...]]

# --------------------------------------------------------------------------
# Rule tables
# --------------------------------------------------------------------------

PARAM_RULES: RuleTable = {
    "embed": (("data",), None),
    "mlp": ("model", None),
    "heads": ("model", None),
    "kv_heads": ("model", None),
    "vocab": ("model", None),
    "expert": ("model", None),
    # LRD factors have ONE ordinary dim each (u: embed x r, v: r x out), so
    # the rank dim must take whichever mesh axis the sibling dim didn't —
    # otherwise factors stay 16-way sharded and 72B-scale optimizer state
    # blows past HBM.  This is *storage* sharding (ZeRO); the factor is
    # all-gathered before use, so MXU rank alignment is unaffected.
    "rank": (("data",), ("model",), None),
    "conv": (None,),
}

PARAM_RULES_NO_FSDP: RuleTable = dict(PARAM_RULES, embed=(None,))

# Frozen-partition layout (DESIGN.md §9): no ZeRO storage sharding at all —
# the rank dim stays replicated (sharding it over data/model would force an
# all-gather before every use), output-feature dims keep the TP ``model``
# sharding the activations consume locally.  The result is a placement with
# zero collectives attached to the factor: "replicated-and-parked per host".
FROZEN_PARAM_RULES: RuleTable = dict(
    PARAM_RULES_NO_FSDP, rank=(None,), conv=(None,))

ACT_RULES: RuleTable = {
    "batch": (("pod", "data"), "data", None),
    "seq": (None,),
    "embed": (None,),
    "heads": ("model", None),
    "kv_heads": ("model", None),
    "mlp": ("model", None),
    "vocab": ("model", None),
    "expert": ("model", None),
    "kv_seq": (None,),
    "frames": (None,),
}

# Sequence-parallel decode: the KV cache / attention keys shard over model.
ACT_RULES_SP: RuleTable = dict(
    ACT_RULES, kv_seq=("model", None), kv_heads=(None,), heads=("model", None)
)

# --------------------------------------------------------------------------
# Context
# --------------------------------------------------------------------------

class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.act_rules: Optional[RuleTable] = None
        self.param_rules: Optional[RuleTable] = None
        self.manual_axes: FrozenSet[str] = frozenset()


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, *, act: RuleTable = ACT_RULES,
               params: RuleTable = PARAM_RULES,
               manual: FrozenSet[str] = frozenset()):
    """Activate ``mesh`` + rule tables for :func:`shard` / :func:`param_specs`.

    ``manual`` names the mesh axes that are *manual* (shard_map) in the
    enclosing region — e.g. the DP axes inside
    ``distributed.compression.value_and_grad_compressed``.  Constraint
    resolution must not reference a manual axis, and nested shard_map
    dispatchers (``kernels.ops``) use it to stand down rather than
    double-map an axis.
    """
    prev = (_CTX.mesh, _CTX.act_rules, _CTX.param_rules, _CTX.manual_axes)
    _CTX.mesh, _CTX.act_rules, _CTX.param_rules = mesh, act, params
    _CTX.manual_axes = frozenset(manual)
    try:
        yield
    finally:
        (_CTX.mesh, _CTX.act_rules, _CTX.param_rules,
         _CTX.manual_axes) = prev


def current_mesh() -> Optional[Mesh]:
    """The mesh of the innermost active :func:`axis_rules` context."""
    return _CTX.mesh


def current_manual_axes() -> FrozenSet[str]:
    """Mesh axes that are manual (shard_map) in the enclosing region."""
    return _CTX.manual_axes


# --------------------------------------------------------------------------
# Resolution
# --------------------------------------------------------------------------

def _resolve_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                  rules: RuleTable, mesh: Mesh) -> P:
    """Map logical axes -> PartitionSpec honoring divisibility + axis reuse."""
    used: set = set()
    parts = []
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, ax in zip(shape, axes):
        chosen = None
        for cand in rules.get(ax, (None,)) if ax else (None,):
            if cand is None:
                break
            names = (cand,) if isinstance(cand, str) else tuple(cand)
            if any(n not in sizes or n in used for n in names):
                continue
            total = 1
            for n in names:
                total *= sizes[n]
            if dim % total == 0:
                chosen = names
                break
        if chosen:
            used.update(chosen)
            parts.append(chosen[0] if len(chosen) == 1 else tuple(chosen))
        else:
            parts.append(None)
    return P(*parts)


_warned_no_rules = False


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Annotate activation ``x`` with logical axes.

    Contract: the annotation only takes effect inside an active
    :func:`axis_rules` context — that is what supplies the mesh and the
    logical→mesh rule table.  **Outside any context this is a silent
    no-op** (by design: model code is written once and also runs
    single-device / in unit tests), except that the FIRST such call in a
    process emits a ``UserWarning`` so a launch-layer bug — building a
    sharded step without entering ``axis_rules`` — surfaces instead of
    silently producing a fully-replicated program.  Step builders
    (``launch/steps.py``) always trace model code under ``axis_rules``.
    """
    if _CTX.mesh is None or _CTX.act_rules is None:
        global _warned_no_rules
        if not _warned_no_rules:
            _warned_no_rules = True
            warnings.warn(
                "repro.distributed.sharding.shard() called outside an "
                "axis_rules(mesh, ...) context: sharding annotations are "
                "no-ops and the traced program will be unpartitioned. "
                "Wrap the trace in `with axis_rules(mesh): ...` (done "
                "automatically by launch/steps step builders). This "
                "warning is emitted once per process.",
                UserWarning, stacklevel=2)
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard: {len(axes)} axes for rank-{x.ndim} tensor {x.shape}")
    spec = _resolve_spec(x.shape, axes, _CTX.act_rules, _CTX.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


# --------------------------------------------------------------------------
# Parameter specs (path-based)
# --------------------------------------------------------------------------

# (regex over "parent/leaf", base logical axes for the trailing dims)
_PARAM_PATTERNS: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embedding$", ("vocab", "embed")),
    (r"(unembed|lm_head|head)/kernel$", ("embed", "vocab")),
    (r"(unembed|lm_head|head)/u$", ("embed", "rank")),
    (r"(unembed|lm_head|head)/v$", ("rank", "vocab")),
    (r"wq/kernel$", ("embed", "heads")),
    (r"wq/u$", ("embed", "rank")),
    (r"wq/v$", ("rank", "heads")),
    (r"(wk|wv)/kernel$", ("embed", "kv_heads")),
    (r"(wk|wv)/u$", ("embed", "rank")),
    (r"(wk|wv)/v$", ("rank", "kv_heads")),
    (r"wo/kernel$", ("heads", "embed")),
    (r"wo/u$", ("heads", "rank")),
    (r"wo/v$", ("rank", "embed")),
    (r"(gate|up|wi|in_proj)/kernel$", ("embed", "mlp")),
    (r"(gate|up|wi|in_proj)/u$", ("embed", "rank")),
    (r"(gate|up|wi|in_proj)/v$", ("rank", "mlp")),
    (r"(down|out_proj)/kernel$", ("mlp", "embed")),
    (r"(down|out_proj)/u$", ("mlp", "rank")),
    (r"(down|out_proj)/v$", ("rank", "embed")),
    # MLA latents: the latent dim behaves like a rank dim for sharding.
    (r"(q_down|kv_down)/kernel$", ("embed", "rank")),
    (r"(q_up|kv_up)/kernel$", ("rank", "heads")),
    (r"(q_up|kv_up)/u$", (None, "rank")),
    (r"(q_up|kv_up)/v$", ("rank", "heads")),
    (r"router/kernel$", ("embed", None)),
    (r"conv1d/kernel$", (None, "mlp")),
    (r"wq/bias$", ("heads",)),
    (r"(wk|wv)/bias$", ("kv_heads",)),
    (r"(gate|up|wi|in_proj)/bias$", ("mlp",)),
    (r"(down|out_proj|wo)/bias$", ("embed",)),
    (r"(cross_wk|cross_wv)/kernel$", ("embed", "kv_heads")),
)


# int8 export artifacts (serving/export.py quantize_factors="int8") store a
# factor as sibling leaves ``<name>_q`` (int8 values, same shape) and
# ``<name>_scale`` (f32, shape (..., 1, S) — one scale per output column).
# Both resolve through the float leaf's pattern: the path is rewritten to the
# base name and the scale's broadcast dims of size 1 fall through the
# divisibility check to None on their own.
_INT8_EXPORT_LEAF = re.compile(r"(/(?:u|v|kernel))_(?:q|scale)$")


def _logical_axes_for(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    path = _INT8_EXPORT_LEAF.sub(r"\1", path)
    base: Optional[Tuple[Optional[str], ...]] = None
    for pattern, axes in _PARAM_PATTERNS:
        if re.search(pattern, path):
            base = axes
            break
    if base is None:
        base = (None,) * min(ndim, 2)
    extra = ndim - len(base)
    lead: Tuple[Optional[str], ...] = ()
    if extra > 0:
        # leading dims: expert stacks get the expert axis, layer stacks None
        if "experts" in path:
            lead = (None,) * (extra - 1) + ("expert",)
        else:
            lead = (None,) * extra
    return lead + base


def param_specs(params: Any, mesh: Optional[Mesh] = None,
                rules: Optional[RuleTable] = None) -> Any:
    """PartitionSpec pytree for a param tree (works on arrays or SDS).

    ``None`` leaves — the holes of a freezing partition
    (``core.freezing.partition``) — map to ``None``, so the spec tree of a
    partition lines up leaf-for-leaf with the partition itself and path
    resolution is identical to the full tree's.
    """
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.param_rules or PARAM_RULES
    assert mesh is not None, "param_specs needs a mesh (pass one or use axis_rules)"

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}" if path else k) for k, v in tree.items()}
        if tree is None:
            return None
        axes = _logical_axes_for(path, tree.ndim)
        return _resolve_spec(tree.shape, axes, rules, mesh)

    return walk(params, "")


def place_at_paths(tree: Any, mesh: Mesh, rules: RuleTable,
                   paths: Sequence[str]) -> Any:
    """device_put only the leaves under the given subtree paths to their
    rule-resolved ``NamedSharding``; every other leaf passes through
    untouched.

    The surgical-re-placement primitive of in-training rank adaptation
    (``launch.steps.repartition_state``): a truncated factor group's leaves
    are brand-new arrays with default placement, and — unlike a plain phase
    swap — BOTH factors of the group changed shape, so re-placement is by
    group *path*, not by factor group id.  Specs are resolved against the
    tree's CURRENT (post-truncation) shapes, so divisibility fallbacks
    re-apply at the new ranks.
    """
    specs = param_specs(tree, mesh, rules)
    prefixes = tuple(paths)

    def covered(path: str) -> bool:
        return any(path == p or path.startswith(p + "/") for p in prefixes)

    def walk(t, s, path):
        if isinstance(t, dict):
            return {k: walk(v, s[k], f"{path}/{k}" if path else k)
                    for k, v in t.items()}
        if t is None or s is None or not covered(path):
            return t
        return jax.device_put(t, NamedSharding(mesh, s))

    return walk(tree, specs, "")


def paged_pool_specs(cache: Any, mesh: Mesh, kv_heads: int) -> Any:
    """PartitionSpec pytree for a paged serving cache (DESIGN.md §14).

    Pool leaves (L, num_blocks, block_size, KV * 2 * hd) — and the int8
    pool's scale sibling (..., KV * 2) — shard their last dim over
    ``model`` when ``kv_heads`` divides: each KV head's lanes are
    contiguous (serving/paged_cache.py), so a device holds whole heads.
    Page tables and anything else stay replicated.  The scheduler pins
    its programs' cache *outputs* to exactly these specs
    (``out_shardings``) so the executable's output placement matches the
    init/upload placement and the compile-once contract holds.

    Mesh axes of size 1 are pruned from the resolved specs: naming them is
    semantically replication, but GSPMD normalizes jit *output* shardings
    to ``P()`` on such axes, and the init-vs-echo spec mismatch would key
    a second executable per step (breaking compile-once on exactly the
    1-device meshes the contract is easiest to hold on).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def prune(spec: P) -> P:
        parts = []
        for p in spec:
            names = () if p is None else ((p,) if isinstance(p, str)
                                          else tuple(p))
            names = tuple(n for n in names if sizes.get(n, 1) > 1)
            parts.append(None if not names
                         else names[0] if len(names) == 1 else names)
        while parts and parts[-1] is None:  # P(None,...) != P() as a key
            parts.pop()
        return P(*parts)

    def walk(tree, name):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if name in ("kv", "kv_scale"):
            axes = (None, None, None, "kv_heads")
            return prune(_resolve_spec(tree.shape[:-1] + (kv_heads,), axes,
                                       ACT_RULES, mesh))
        return P()

    return walk(cache, "")


def named_shardings(params: Any, mesh: Optional[Mesh] = None,
                    rules: Optional[RuleTable] = None) -> Any:
    """``NamedSharding`` pytree for a param tree (``param_specs`` + mesh).

    This is the placement tree the sharded train driver feeds to
    ``jax.device_put`` / ``jax.jit(in_shardings=...)``: the TRAINABLE
    partition resolves under the run's param rules (FSDP or TP), the
    FROZEN partition under :data:`FROZEN_PARAM_RULES` (see
    ``launch.steps.state_shardings``).  ``None`` holes pass through, so a
    freezing partition maps leaf-for-leaf.
    """
    mesh = mesh or _CTX.mesh
    specs = param_specs(params, mesh, rules)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))
