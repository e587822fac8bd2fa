"""Step builders: train_step / prefill_step / serve_step per RunConfig,
plus abstract ``input_specs`` (ShapeDtypeStruct stand-ins with shardings —
the dry-run lowers against these, no allocation ever happens).

The sequential-freezing phase is a STATIC argument: the returned train_step
is ``step_fn(phase)(state, batch)``; each phase compiles once.  The
:class:`TrainState` is PARTITIONED for that phase (DESIGN.md §7): frozen
factors live in ``state.frozen`` and enter the loss as a non-differentiated
argument, so ``value_and_grad``, the microbatch scan accumulators, grad
compression, the grad norm, and the optimizer all run over
``state.trainable`` only — no gradient, no accumulator, and no optimizer
state ever exists for a frozen factor.  The phase also reaches the fused
Pallas paths as the ``freeze_group`` of the
:class:`repro.kernels.ops.KernelPolicy` threaded through every layer's
``use_pallas`` argument (the frozen factor's backward kernel is never
emitted, DESIGN.md §3).  ``repartition_state`` performs the host-side
Algorithm-2 phase swap, rotating parked optimizer moments so unfreezing
never resets them.

Sharded placement (DESIGN.md §9): :func:`state_shardings` /
:func:`make_sharded_train_state` place the partitioned state on a mesh —
trainable per the run's FSDP/TP layout, frozen under
``FROZEN_PARAM_RULES`` (replicated over the DP axes: no collective ever
touches a frozen factor), opt over the trainable partition;
:func:`shard_batch` places per-step data, :func:`packed_state_shardings`
builds the elastic-restore target map, :func:`check_state_placement`
audits the contract, and ``repartition_state(mesh=...)`` re-places only
the swapped factor group at a phase boundary.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from repro.core import freezing, rank_adapt
from repro.core.decompose import Decomposer
from repro.core.policy import LM_DEFAULT, NO_LRD
from repro.distributed import (ACT_RULES, ACT_RULES_SP, FROZEN_PARAM_RULES,
                               PARAM_RULES, PARAM_RULES_NO_FSDP, axis_rules,
                               named_shardings, param_specs,
                               place_at_paths, shard)
from repro.distributed.compression import value_and_grad_compressed
from repro.kernels.ops import KernelPolicy
from repro.models import encdec as encdec_mod, lm
from repro.models.common import cross_entropy
from repro.obs import scope, span, watch_compiles
from repro.optim import init_moments, init_optimizer
from repro.optim.optimizers import OptState, apply_updates

# every program this module builds is compiled through jax: count them
# (jit_compiles / jit_compile_s, DESIGN.md §12)
watch_compiles()


class TrainState(NamedTuple):
    """Partitioned train state (DESIGN.md §7).

    ``trainable``/``frozen`` are complementary ``None``-holed views of one
    param tree (``core.freezing.partition``); ``opt`` is allocated over the
    trainable partition only.  ``state.params`` merges the two views back
    into the full tree (pure restructuring — no copies).
    """
    trainable: Any
    frozen: Any
    opt: Any

    @property
    def params(self) -> Any:
        return freezing.merge(self.trainable, self.frozen)


def make_train_state(optim_cfg, params, phase: int = -1):
    """Partition ``params`` for ``phase`` and build the matching state.

    Returns ``(state, parked)`` where ``parked = (mu, nu)`` holds the zero
    optimizer moments of the frozen partition as HOST numpy arrays — they
    are not part of the compiled step and never occupy device memory, which
    is what makes the freeze-phase optimizer-state saving real.
    """
    trainable, frozen = freezing.partition(params, phase)
    opt = init_optimizer(optim_cfg, trainable)
    return (TrainState(trainable, frozen, opt),
            init_moments(optim_cfg, frozen, on_host=True))


def _tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        if size is None:  # non-array leaf (python scalar step count)
            continue
        total += int(size) * np.dtype(leaf.dtype).itemsize
    return total


def partition_bytes(state: TrainState) -> Dict[str, int]:
    """Live bytes of each partition of the CONCRETE state (telemetry,
    DESIGN.md §12): what the per-step ``trainable/frozen/opt`` records and
    the rank-adaptation benchmark both report, so the freeze-phase and
    rank-truncation savings are observable per step, not just asserted by
    ``abstract_state``.  Parked host moments are excluded — they hold no
    device memory by contract."""
    return {"trainable_bytes": _tree_bytes(state.trainable),
            "frozen_bytes": _tree_bytes(state.frozen),
            "opt_bytes": _tree_bytes(state.opt)}


def _park(tree):
    """Move moment leaves to host numpy (releases the device buffers)."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)), tree)


def _unpark(tree, mesh=None, rules=None):
    """device_put host leaves rotating back into the live state; leaves
    already on device pass through.  With ``mesh``/``rules`` the unparked
    leaves land directly under their target opt-layout ``NamedSharding``
    (elastic: parked slices are mesh-agnostic host numpy)."""
    if tree == () or mesh is None or mesh.devices.size <= 1:
        return jax.tree_util.tree_map(
            lambda x: x if isinstance(x, jax.Array) else jax.device_put(x),
            tree)
    shs = named_shardings(tree, mesh, rules)
    return jax.tree_util.tree_map(
        lambda x, sh: x if isinstance(x, jax.Array) else jax.device_put(x, sh),
        tree, shs)


def repartition_state(optim_cfg, state: TrainState, parked, new_phase: int,
                      *, mesh=None, run: Optional[RunConfig] = None,
                      schedule=None, boundary: Optional[int] = None):
    """Host-side Algorithm-2 phase transition.

    Re-partitions the merged params for ``new_phase`` and rotates the
    per-group optimizer-state slices: moments of leaves that stay trainable
    carry over in place, moments of newly-frozen leaves move to host
    (parked), and the parked moments of newly-unfrozen leaves are
    device_put back in — alternation never resets momentum / Adam moments,
    and parked slices never sit in device memory.  Call it between steps,
    outside jit.

    With ``schedule`` (a ``core.rank_adapt.RankSchedule``) the swap also
    fires the in-training rank adaptation (DESIGN.md §10): groups whose
    scheduled target sits below their live rank are Eckart–Young-truncated
    on the MERGED params (``svd.truncate_factors``) and BOTH the live and
    parked Adam-moment slices are cut to the new rank BEFORE the partition
    is rebuilt — so grads, scan accumulators, compression buffers, and the
    optimizer state all carry the new shapes only, and the trainable
    partition shrinks monotonically.  ``boundary`` (the swap index) gates
    ``schedule.start_boundary``.

    With ``mesh`` (and ``run`` for the rule tables) the swap is
    SHARD-AWARE (DESIGN.md §9): the two partitions live under different
    placements (trainable: FSDP/TP param rules; frozen:
    ``FROZEN_PARAM_RULES``), so exactly the leaves whose factor group
    appears in ``freezing.groups_to_replace(old, new)`` are device_put to
    their new placement; every other param/moment buffer is untouched —
    a phase swap never resets the sharding (or the contents) of the rest
    of the state.  Unparked moments are placed directly with their target
    opt-layout sharding.  A truncated group is the one exception: both its
    factors are fresh arrays, so its params AND moments are re-placed by
    group path (``distributed.place_at_paths``), re-resolving divisibility
    at the new ranks.

    On the trace the swap is a ``freeze.swap`` span holding one span per
    stage: ``freeze.merge``, ``freeze.rank_adapt`` (with an active
    schedule), ``freeze.partition``, ``freeze.place`` (on a mesh),
    ``freeze.unpark`` and ``freeze.park``.
    """
    with span("freeze.swap", phase=new_phase):
        return _repartition(optim_cfg, state, parked, new_phase, mesh, run,
                            schedule, boundary)


def _repartition(optim_cfg, state, parked, new_phase, mesh, run, schedule,
                 boundary):
    """:func:`repartition_state`'s body, one ``freeze.*`` span per stage."""
    with span("freeze.merge"):
        old_phase = freezing.phase_of_partition(state.trainable, state.frozen)
        params = freezing.merge(state.trainable, state.frozen)
        moments = freezing.merge_moments((state.opt.mu, state.opt.nu), parked)
    trunc = {}
    if schedule is not None and schedule.active:
        with span("freeze.rank_adapt"):
            trunc = rank_adapt.plan_rank_map(params, schedule, boundary)
            if trunc:
                params = rank_adapt.truncate_params(params, trunc)
                moments = rank_adapt.slice_moments(moments, trunc)
    with span("freeze.partition"):
        trainable, frozen = freezing.partition(params, new_phase)
        active, parked = freezing.partition_moments(moments, new_phase)
    if mesh is None or mesh.devices.size <= 1:
        with span("freeze.unpark"):
            opt = OptState(state.opt.step, *(_unpark(t) for t in active))
        with span("freeze.park"):
            parked = tuple(_park(t) for t in parked)
        return TrainState(trainable, frozen, opt), parked

    prm = _param_rules(run) if run is not None else PARAM_RULES
    opt_rules = _opt_rules(run) if run is not None else prm
    moved = freezing.groups_to_replace(old_phase, new_phase)
    with span("freeze.place"):
        trainable = _place_moved(
            trainable, named_shardings(trainable, mesh, prm), moved)
        frozen = _place_moved(
            frozen, named_shardings(frozen, mesh, FROZEN_PARAM_RULES), moved)
    with span("freeze.unpark"):
        mu, nu = (_unpark(t, mesh, opt_rules) for t in active)
    if trunc:
        with span("freeze.place"):
            paths = tuple(trunc)
            trainable = place_at_paths(trainable, mesh, prm, paths)
            frozen = place_at_paths(frozen, mesh, FROZEN_PARAM_RULES, paths)
            mu = place_at_paths(mu, mesh, opt_rules, paths)
            if nu != ():
                nu = place_at_paths(nu, mesh, opt_rules, paths)
    opt = OptState(state.opt.step, mu, nu)
    with span("freeze.park"):
        parked = tuple(_park(t) for t in parked)
    return TrainState(trainable, frozen, opt), parked


def _place_moved(tree, shardings, moved_groups, name: str = ""):
    """device_put the leaves whose factor group is in ``moved_groups`` to
    their sharding; leave everything else alone (shared buffers intact)."""
    if isinstance(tree, dict):
        return {k: _place_moved(v, shardings[k], moved_groups, k)
                for k, v in tree.items()}
    if tree is None:
        return None
    if freezing.factor_group(name) in moved_groups:
        return jax.device_put(tree, shardings)
    return tree


# --------------------------------------------------------------------------
# sharded state placement (DESIGN.md §9)
# --------------------------------------------------------------------------

def state_shardings(run: RunConfig, mesh, state: TrainState) -> TrainState:
    """``NamedSharding`` pytree mirroring a partitioned :class:`TrainState`.

    The placement contract of the sharded driver, in one tree:

    * ``trainable``  — the run's param layout (FSDP ZeRO-3 or TP);
    * ``frozen``     — ``FROZEN_PARAM_RULES``: replicated over the DP axes,
      TP-sharded over ``model`` only where consumed locally, so a frozen
      factor appears in no cross-device collective;
    * ``opt``        — the optimizer layout over the trainable partition
      (data-sharded under ``zero1``), scalar ``step`` replicated.

    Works on concrete or abstract states; feed it to ``jax.device_put``,
    ``jax.jit(in_shardings=..., out_shardings=...)``, or placement asserts.
    """
    tr = named_shardings(state.trainable, mesh, _param_rules(run))
    fr = named_shardings(state.frozen, mesh, FROZEN_PARAM_RULES)
    step = NamedSharding(mesh, P())
    mu = named_shardings(state.opt.mu, mesh, _opt_rules(run))
    nu = (() if state.opt.nu == ()
          else named_shardings(state.opt.nu, mesh, _opt_rules(run)))
    return TrainState(tr, fr, OptState(step, mu, nu))


def batch_shardings(batch, mesh):
    """Leading-dim-over-(pod, data) ``NamedSharding`` tree for a batch."""
    from repro.distributed.sharding import _resolve_spec

    def sh(x):
        spec = _resolve_spec(x.shape, ("batch",) + (None,) * (x.ndim - 1),
                             ACT_RULES, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(sh, batch)


def shard_batch(batch, mesh):
    """device_put a (host) batch with its DP sharding — the per-step data
    placement of the sharded train loop (a ``data.shard`` span)."""
    with span("data.shard"):
        return jax.tree_util.tree_map(jax.device_put, batch,
                                      batch_shardings(batch, mesh))


def make_sharded_train_state(run: RunConfig, params, phase: int, mesh):
    """:func:`make_train_state` + placement on ``mesh``.

    Returns ``(state, parked)`` with every device leaf carrying the
    :func:`state_shardings` ``NamedSharding`` (trainable sharded per the
    run's layout, frozen replicated-over-DP, opt over trainable only) and
    ``parked`` on host, exactly as in the single-device path.
    """
    state, parked = make_train_state(run.optim, params, phase)
    shs = state_shardings(run, mesh, state)
    place = lambda t, s: jax.tree_util.tree_map(jax.device_put, t, s)
    opt = OptState(jax.device_put(state.opt.step, shs.opt.step),
                   place(state.opt.mu, shs.opt.mu),
                   place(state.opt.nu, shs.opt.nu) if state.opt.nu != () else ())
    return (TrainState(place(state.trainable, shs.trainable),
                       place(state.frozen, shs.frozen), opt), parked)


def packed_state_shardings(run: RunConfig, mesh, phase: int,
                           rank_map: Optional[Dict[str, int]] = None):
    """Target shardings for a ``pack_phased_state`` checkpoint tree.

    The elastic-resume placement map (``checkpoint.load_checkpoint``'s
    ``shardings`` argument): params split per the ``phase`` partition
    (trainable -> param layout, frozen -> ``FROZEN_PARAM_RULES``), active
    moments under the opt layout, and ``None`` at the PARKED moment slices
    so those leaves stay host numpy through the restore — the saved tree
    was written mesh-agnostically, so this works across any source/target
    mesh pair.

    ``rank_map`` is the checkpoint's live rank map (saved in the manifest
    ``extra`` once a rank schedule has truncated): the eval_shape tree is
    rewritten to those non-uniform ranks before specs resolve, so a
    mid-schedule resume shards truncated factors by their SAVED shapes, not
    the config's initial ranks.
    """
    shapes = jax.eval_shape(lambda: init_params(run)[0])
    if rank_map:
        shapes = rank_adapt.apply_rank_map_to_shapes(shapes, rank_map)
    trainable, frozen = freezing.partition(shapes, phase)
    params_sh = freezing.merge(
        named_shardings(trainable, mesh, _param_rules(run)),
        named_shardings(frozen, mesh, FROZEN_PARAM_RULES))
    mu_sh = named_shardings(trainable, mesh, _opt_rules(run))
    nu_sh = mu_sh if run.optim.name == "adamw" else ()
    return {"params": params_sh, "step": NamedSharding(mesh, P()),
            "mu": mu_sh, "nu": nu_sh}


def check_state_placement(run: RunConfig, mesh, state: TrainState) -> None:
    """Raise if any device leaf of ``state`` deviates from the placement
    contract (:func:`state_shardings`).  Host-side sharding comparison —
    touches no data; the sharded driver runs it after the first step."""
    shs = state_shardings(run, mesh, state)

    def walk(t, s, path):
        if isinstance(t, dict):
            for k in t:
                walk(t[k], s[k], f"{path}/{k}")
            return
        if t is None or s is None or not isinstance(t, jax.Array):
            return
        if t.sharding != s:
            raise AssertionError(
                f"placement drift at {path}: {t.sharding} != expected {s}")

    walk(state.trainable, shs.trainable, "trainable")
    walk(state.frozen, shs.frozen, "frozen")
    walk(state.opt.mu, shs.opt.mu, "opt.mu")
    if state.opt.nu != ():
        walk(state.opt.nu, shs.opt.nu, "opt.nu")


def make_decomposer(run: RunConfig) -> Decomposer:
    policy = (LM_DEFAULT.with_alpha(run.lrd.alpha)
              .with_quantize(run.lrd.rank_quantize)
              .with_min_dim(run.lrd.min_dim)) if run.lrd.enabled else NO_LRD
    return Decomposer(policy, dtype=run.model.pdtype)


def init_params(run: RunConfig, key=None):
    key = key if key is not None else jax.random.PRNGKey(run.seed)
    dec = make_decomposer(run)
    if run.model.family == "encdec":
        params = encdec_mod.encdec_init(key, run.model, dec)
    else:
        params = lm.lm_init(key, run.model, dec)
    return params, dec.plan


# --------------------------------------------------------------------------
# forward dispatch (family-aware)
# --------------------------------------------------------------------------

def kernel_policy(run: RunConfig, phase: int = -1) -> KernelPolicy:
    """The static kernel-dispatch policy for one compiled step.

    ``phase`` is the sequential-freezing phase; group ``phase`` is frozen
    (u at phase 0, v at phase 1 — core/freezing.py), so the fused VJP skips
    that factor's backward kernel entirely.

    With ``lrd.pallas_autotune`` the dispatchers consult the active
    :class:`repro.kernels.autotune.TuningTable` at trace time;
    ``lrd.pallas_autotune_table`` names the JSON to activate (loaded once —
    an already-active table is never replaced, so a CLI/test that installed
    its own table keeps it).
    """
    if run.lrd.pallas_autotune and run.lrd.pallas_autotune_table:
        from repro.kernels import autotune
        if autotune.get_table() is None:
            autotune.load_table(run.lrd.pallas_autotune_table)
    return KernelPolicy(
        use_pallas=run.lrd.use_pallas_kernel,
        freeze_group=freezing.frozen_group_for_phase(phase),
        interpret=run.lrd.pallas_interpret,
        block_m=run.lrd.pallas_block_m,
        block_k=run.lrd.pallas_block_k,
        block_n=run.lrd.pallas_block_n,
        autotune=run.lrd.pallas_autotune,
        double_buffer=run.lrd.pallas_double_buffer,
        int8_decode=run.lrd.int8_decode,
    )


def _forward_full(params, batch, run: RunConfig, *, return_hidden=False,
                  mode: str = "full", phase: int = -1):
    cfg = run.model
    kw = dict(remat=run.dist.remat, use_pallas=kernel_policy(run, phase))
    if cfg.family == "encdec":
        memory = encdec_mod.encode(params, batch["frames"], cfg,
                                   remat=run.dist.remat)
        logits, cache = encdec_mod.decode(params, batch["tokens"], memory, cfg,
                                          mode=mode, **kw)
        return logits, cache, jnp.zeros((), jnp.float32), None
    out = lm.lm_apply(params, batch["tokens"], cfg, mode=mode,
                      vision_embeddings=batch.get("vision_embeddings"),
                      return_hidden=return_hidden, **kw)
    if return_hidden:
        logits, cache, aux, hidden = out
        return logits, cache, aux, hidden
    logits, cache, aux = out
    return logits, cache, aux, None


def _loss_fn(trainable, frozen, batch, run: RunConfig, phase: int):
    """Loss over the trainable partition.  ``frozen`` is a plain (non-
    differentiated) argument: the merged tree re-enters the forward, but no
    cotangent is ever requested for a frozen leaf — no ``stop_gradient``
    masking, the backward over frozen factors is simply never built."""
    cfg = run.model
    params = freezing.merge(trainable, frozen)
    need_h = cfg.use_mtp
    logits, _, aux, hidden = _forward_full(params, batch, run,
                                           return_hidden=need_h, mode="train",
                                           phase=phase)
    with scope("loss"):
        loss = cross_entropy(logits, batch["labels"])
    if cfg.use_mtp:
        mtp_lg = lm.mtp_logits(params, hidden, batch["tokens"], cfg,
                               use_pallas=kernel_policy(run, phase))
        with scope("loss"):
            # padded shift-by-one: predict labels shifted left, mask the
            # last 2 slots
            mtp_labels = jnp.roll(batch["labels"], -1, axis=1)
            loss = loss + cfg.mtp_loss_weight * cross_entropy(
                mtp_lg, mtp_labels, mask=lm.mtp_loss_mask(batch["tokens"]))
    if cfg.num_experts:
        with scope("loss"):
            loss = loss + 0.01 * aux
    return loss


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def _param_rules(run: RunConfig):
    if run.dist.param_layout == "zero1":
        return PARAM_RULES_NO_FSDP
    return PARAM_RULES if run.dist.fsdp else PARAM_RULES_NO_FSDP


def _opt_rules(run: RunConfig):
    # ZeRO-1: optimizer state (and grad accumulators) sharded over data too.
    if run.dist.param_layout == "zero1":
        return PARAM_RULES
    return _param_rules(run)


def build_train_step(run: RunConfig, mesh):
    """Returns step(phase) -> fn(state, batch) -> (state, metrics)."""

    def train_step(state: TrainState, batch, *, phase: int):
        # trace-time guard: the static phase must match the partition, or
        # the fused-kernel freeze_group would elide the wrong backward.
        freezing.check_partition(state.trainable, state.frozen, phase)
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        prm = _param_rules(run)
        with axis_rules(mesh, act=act, params=prm):
            def loss_for(trainable, b):
                return _loss_fn(trainable, state.frozen, b, run=run,
                                phase=phase)

            m = run.dist.microbatches
            if m > 1:
                # grad accumulators must carry explicit shardings — an
                # unconstrained scan carry ends up replicated (measured
                # 26 GiB/device for qwen2-72b's down-proj factor alone).
                # Under ZeRO-1 they take the optimizer-state (data-sharded)
                # layout: the per-microbatch add lowers to a reduce-scatter.
                # Only the trainable partition is accumulated: frozen
                # factors contribute no carry at all.
                gspecs = param_specs(state.trainable, mesh, _opt_rules(run))
                pin = lambda t: jax.tree_util.tree_map(
                    lambda x, sp: jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, sp)), t, gspecs)

                # Microbatches ride the scan xs via a (m, B/m, ...) reshape —
                # a dynamic_slice along the SHARDED batch dim would force XLA
                # to all-gather the whole batch per microbatch (measured:
                # 32 GiB fp32 replica of vision_embeddings on the VLM cell).
                def regroup(x):
                    y = x.reshape((m, x.shape[0] // m) + x.shape[1:])
                    return shard(y, None, "batch", *([None] * (y.ndim - 2)))

                batch_r = jax.tree_util.tree_map(regroup, batch)

                adt = jnp.dtype(run.dist.accum_dtype)

                def acc_body(carry, mb):
                    gsum, lsum = carry
                    l, g = jax.value_and_grad(loss_for)(state.trainable, mb)
                    gsum = pin(jax.tree_util.tree_map(
                        lambda a, b: (a + b.astype(adt)), gsum, g))
                    return (gsum, lsum + l), None

                zeros = pin(jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, adt), state.trainable))
                (gsum, lsum), _ = jax.lax.scan(
                    acc_body, (zeros, jnp.zeros((), jnp.float32)), batch_r)
                loss = lsum / m
                grads = jax.tree_util.tree_map(lambda g: g / m, gsum)
            else:
                loss, grads = value_and_grad_compressed(
                    loss_for, state.trainable, batch, mesh,
                    run.dist.grad_compression)
                if mesh.devices.size > 1:
                    # pin the synced grads to the optimizer layout: under
                    # zero1 the DP all-reduce lowers to a reduce-scatter;
                    # either way the update consumes grads in the exact
                    # layout the moments live in (no resharding copy).
                    # Covers the trainable partition only — frozen factors
                    # have no grad leaf to pin.
                    gspecs = param_specs(state.trainable, mesh,
                                         _opt_rules(run))
                    grads = jax.tree_util.tree_map(
                        lambda g, sp: jax.lax.with_sharding_constraint(
                            g, NamedSharding(mesh, sp)), grads, gspecs)

            with scope("optim"):
                new_trainable, new_opt = apply_updates(
                    run.optim, state.trainable, grads, state.opt)
                # square in the grad dtype, accumulate in f32: a f32
                # pre-cast materializes a full fp32 copy of every grad leaf
                # at once (measured +5 GiB/device on deepseek-v3).
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g), dtype=jnp.float32)
                    for g in jax.tree_util.tree_leaves(grads)))
            return (TrainState(new_trainable, state.frozen, new_opt),
                    {"loss": loss, "grad_norm": gnorm})

    return train_step


def compile_train_step(run: RunConfig, mesh, train_step, state: TrainState,
                       batch, phase: int):
    """Ahead-of-time compile one freezing phase of ``train_step``.

    Explicit in/out shardings and a DONATED state: ``in_shardings ==
    out_shardings`` lets every updated buffer alias its predecessor.  The
    batch shardings come from the batch's actual structure.  Returns the
    compiled executable, so callers can read its HLO or memory analysis
    without a second compile."""
    shs = state_shardings(run, mesh, state)
    return jax.jit(
        functools.partial(train_step, phase=phase), donate_argnums=(0,),
        in_shardings=(shs, batch_shardings(batch, mesh)),
        out_shardings=(shs, None)).lower(state, batch).compile()


# --------------------------------------------------------------------------
# prefill / decode
# --------------------------------------------------------------------------

def build_prefill_step(run: RunConfig, mesh):
    def prefill_step(params, batch):
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        with axis_rules(mesh, act=act, params=_param_rules(run)):
            logits, cache, _, _ = _forward_full(params, batch, run)
            return logits[:, -1], cache

    return prefill_step


def build_slot_prefill_step(run: RunConfig, mesh):
    """Prefill for the continuous-batching scheduler (DESIGN.md §8).

    Like :func:`build_prefill_step` but takes ``last_pos`` — the index of
    each row's final *real* prompt token — so prompts padded to the
    engine's fixed prefill length still hand back the logits the first
    generated token must be sampled from.  One compile per prefill shape.
    """

    def slot_prefill_step(params, batch, last_pos):
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        with axis_rules(mesh, act=act, params=_param_rules(run)):
            logits, cache, _, _ = _forward_full(params, batch, run)
            idx = jnp.asarray(last_pos, jnp.int32).reshape(-1)
            last = logits[jnp.arange(logits.shape[0]), idx]
            return last, cache

    return slot_prefill_step


def build_serve_step(run: RunConfig, mesh):
    """One decode step for the whole engine lifetime.

    ``pos`` may be a scalar (legacy fixed-batch decode) or a (B,) vector of
    per-slot positions (continuous batching): the models' decode paths
    write each row's KV at its own offset, build per-row RoPE tables, and
    mask per-row lengths, so slot recycling never changes a shape and the
    step compiles exactly once (serving/scheduler.py asserts this).
    """
    cfg = run.model

    def serve_step(params, cache, token, pos, extras=None):
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        with axis_rules(mesh, act=act, params=_param_rules(run)):
            kw = dict(use_pallas=kernel_policy(run))
            if cfg.family == "encdec":
                memory = (extras or {}).get("memory")
                logits, new_cache = encdec_mod.decode(
                    params, token, memory, cfg, mode="decode", cache=cache,
                    pos=pos, **kw)
            else:
                logits, new_cache, _ = lm.lm_apply(
                    params, token, cfg, mode="decode", cache=cache, pos=pos,
                    vision_embeddings=(extras or {}).get("vision_embeddings"), **kw)
            next_token = jnp.argmax(logits[:, -1:], axis=-1).astype(token.dtype)
            return logits, new_cache, next_token

    return serve_step


def build_draft_chain(run: RunConfig, mesh, k: int):
    """k sequential draft-decode steps fused into ONE program (DESIGN.md
    §13): token j's argmax feeds step j+1 inside the trace, so the whole
    draft phase costs one dispatch instead of k — at serving batch sizes
    the per-dispatch overhead is a large share of a decode step, and it is
    exactly the cost the draft model's smaller matmuls cannot shrink.

    Returns ``(new_cache, chunk)`` where chunk (B, k+1) is the pending
    token followed by the k drafted tokens — the verify step's input,
    ready as-is.  ``k`` is static: one compile per engine lifetime.
    """
    cfg = run.model

    def draft_chain(params, cache, token, pos):
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        with axis_rules(mesh, act=act, params=_param_rules(run)):
            kw = dict(use_pallas=kernel_policy(run))
            toks = [token]
            for j in range(k):
                logits, cache, _ = lm.lm_apply(
                    params, toks[-1], cfg, mode="decode", cache=cache,
                    pos=pos + j, **kw)
                toks.append(jnp.argmax(logits[:, -1:], axis=-1)
                            .astype(token.dtype))
            return cache, jnp.concatenate(toks, axis=1)

    return draft_chain


def build_verify_step(run: RunConfig, mesh):
    """Chunked full-model verify for speculative decoding (DESIGN.md §13).

    Like :func:`build_serve_step`, but ``tokens`` is a (B, k+1) chunk —
    the pending token followed by k draft tokens — fed at per-row start
    positions ``pos``.  Returns the greedy next token at EVERY chunk
    position (the same ``jnp.argmax`` the serve step applies to its single
    position, so accepted tokens are the ones plain decode would emit),
    plus the updated cache with all k+1 positions written.  One compile
    for the engine lifetime: the chunk width is fixed by ``speculative_k``.
    """
    cfg = run.model

    def verify_step(params, cache, tokens, pos):
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        with axis_rules(mesh, act=act, params=_param_rules(run)):
            logits, new_cache, _ = lm.lm_apply(
                params, tokens, cfg, mode="decode", cache=cache, pos=pos,
                use_pallas=kernel_policy(run))
            next_tokens = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
            return new_cache, next_tokens

    return verify_step


def build_extend_step(run: RunConfig, mesh):
    """Suffix prefill onto a radix-cache prefix hit (DESIGN.md §14).

    When admission matches the head of a prompt in the radix prefix cache
    (serving/radix_cache.py), the shared blocks already hold that prefix's
    KV — only the suffix needs a forward.  This is the verify step's
    chunked decode specialized to batch 1: ``tokens`` is the (1, P) padded
    suffix fed at start position ``start`` (= matched prefix length), run
    against a single-slot VIEW of the paged cache whose page table is the
    slot's row — writes land in the slot's private tail blocks (never in a
    shared block: ``start`` is block-aligned and the shared region ends
    there), reads see the shared prefix through the row exactly as decode
    will.  Returns the updated pools (original full page table restored)
    and the greedy next token at every suffix position, so the engine
    samples the first generated token at ``suffix_len - 1``.  P is fixed by
    ``prefill_len``: one compile per engine lifetime.
    """
    cfg = run.model

    def extend_step(params, cache, tokens, page_row, start):
        act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES
        with axis_rules(mesh, act=act, params=_param_rules(run)):
            row = page_row.astype(jnp.int32).reshape(1, -1)
            view = {name: dict(stack, page_table=row)
                    for name, stack in cache.items()}
            pos = jnp.asarray(start, jnp.int32).reshape(1)
            logits, new_view, _ = lm.lm_apply(
                params, tokens, cfg, mode="decode", cache=view, pos=pos,
                use_pallas=kernel_policy(run))
            out = {name: dict(stack, page_table=cache[name]["page_table"])
                   for name, stack in new_view.items()}
            next_tokens = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
            return out, next_tokens

    return extend_step


# --------------------------------------------------------------------------
# abstract input specs (dry-run)
# --------------------------------------------------------------------------

def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def batch_specs(run: RunConfig, mesh) -> Dict[str, jax.ShapeDtypeStruct]:
    from repro.distributed.sharding import _resolve_spec
    cfg, shp = run.model, run.shape
    b, s = shp.global_batch, shp.seq_len
    sp2 = _resolve_spec((b, s), ("batch", None), ACT_RULES, mesh)
    out = {
        "tokens": _sds((b, s), jnp.int32, mesh, sp2),
        "labels": _sds((b, s), jnp.int32, mesh, sp2),
    }
    sp3 = _resolve_spec((b, 1, 1), ("batch", None, None), ACT_RULES, mesh)
    if cfg.family == "encdec":
        out["frames"] = _sds((b, cfg.encoder_frames, cfg.d_model), cfg.cdtype,
                             mesh, sp3)
    if cfg.family == "vlm":
        out["vision_embeddings"] = _sds((b, cfg.num_image_tokens, cfg.d_model),
                                        cfg.cdtype, mesh, sp3)
    return out


_CACHE_AXES = {
    "k": (None, "batch", "kv_seq", "kv_heads", None),
    "v": (None, "batch", "kv_seq", "kv_heads", None),
    "k_scale": (None, "batch", "kv_seq", "kv_heads", None),
    "v_scale": (None, "batch", "kv_seq", "kv_heads", None),
    "ckv": (None, "batch", "kv_seq", None),
    "kr": (None, "batch", "kv_seq", None),
    "ssm": (None, "batch", "heads", None, None),
    "conv": (None, "batch", None, "mlp"),
    "c": (None, "batch", "heads", None, None),
    "n": (None, "batch", "heads", None),
    "m": (None, "batch", "heads"),
}


def cache_specs(cache_shapes, run: RunConfig, mesh):
    from repro.distributed.sharding import _resolve_spec
    act = ACT_RULES_SP if run.dist.sequence_parallel else ACT_RULES

    def walk(tree, name):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        axes = _CACHE_AXES.get(name, (None,) * tree.ndim)
        axes = (None,) * (tree.ndim - len(axes)) + axes[-tree.ndim:] \
            if tree.ndim >= len(axes) else axes[-tree.ndim:]
        spec = _resolve_spec(tree.shape, axes, act, mesh)
        return jax.ShapeDtypeStruct(tree.shape, tree.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return walk(cache_shapes, "")


def _attach_shardings(shapes, mesh, rules):
    """Abstract tree -> same tree with ``NamedSharding``s attached, specs
    resolved per ``rules`` — THE way abstract leaves get placements, shared
    by :func:`abstract_params` (full tree) and :func:`abstract_state` (the
    per-partition rule split)."""
    specs = param_specs(shapes, mesh, rules)
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        shapes, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def abstract_params(run: RunConfig, mesh):
    """eval_shape over init + attach param-layout shardings."""
    shapes = jax.eval_shape(lambda: init_params(run)[0])
    return _attach_shardings(shapes, mesh, _param_rules(run))


def run_phase(run: RunConfig, epoch: int = 0) -> int:
    """The freezing phase the run sits in at ``epoch`` (-1 when LRD or
    freezing is off)."""
    if not run.lrd.enabled:
        return -1
    return freezing.phase_for_epoch(epoch, run.lrd.freeze_mode,
                                    run.lrd.epochs_per_phase)


def abstract_state(run: RunConfig, mesh, phase: Optional[int] = None,
                   rank_map: Optional[Dict[str, int]] = None):
    """Abstract partitioned TrainState: eval_shape over init + shardings.

    The optimizer-state stand-ins cover the trainable partition only, so
    dry-run memory analysis reports the structural freeze-phase saving
    (≈ half the factor moments during any frozen phase), and the FROZEN
    stand-ins carry the ``FROZEN_PARAM_RULES`` placement (replicated over
    DP — DESIGN.md §9), so the same analysis reports the frozen partition's
    replication cost honestly.  ``phase`` defaults to the run's epoch-0
    phase.  ``rank_map`` rewrites factor groups to scheduled (possibly
    non-uniform) ranks first — the dry-run prices each rank-adaptation
    boundary by passing the trajectory maps from
    ``rank_adapt.decay_rank_maps`` here.
    """
    if phase is None:
        phase = run_phase(run)
    shapes = jax.eval_shape(lambda: init_params(run)[0])
    if rank_map:
        shapes = rank_adapt.apply_rank_map_to_shapes(shapes, rank_map)
    trainable_s, frozen_s = freezing.partition(shapes, phase)
    trainable = _attach_shardings(trainable_s, mesh, _param_rules(run))
    frozen = _attach_shardings(frozen_s, mesh, FROZEN_PARAM_RULES)
    opt_shapes = jax.eval_shape(lambda p: init_optimizer(run.optim, p),
                                trainable)
    ospecs = param_specs(trainable, mesh, _opt_rules(run))

    def attach(shapes):
        return jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            shapes, ospecs)

    mu = attach(opt_shapes.mu)
    nu = attach(opt_shapes.nu) if opt_shapes.nu != () else ()
    step_s = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return TrainState(trainable, frozen, OptState(step_s, mu, nu))


def abstract_cache(run: RunConfig, mesh):
    cfg, shp = run.model, run.shape
    if cfg.family == "encdec":
        shapes = jax.eval_shape(
            lambda: encdec_mod.encdec_init_cache(cfg, shp.global_batch, shp.seq_len))
    else:
        shapes = jax.eval_shape(
            lambda: lm.init_cache(cfg, shp.global_batch, shp.seq_len))
    return cache_specs(shapes, run, mesh)


def decode_extras_specs(run: RunConfig, mesh):
    from repro.distributed.sharding import _resolve_spec
    cfg, shp = run.model, run.shape
    sp3 = _resolve_spec((shp.global_batch, 1, 1), ("batch", None, None),
                        ACT_RULES, mesh)
    if cfg.family == "encdec":
        return {"memory": _sds((shp.global_batch, cfg.encoder_frames, cfg.d_model),
                               cfg.cdtype, mesh, sp3)}
    if cfg.family == "vlm":
        return {"vision_embeddings": _sds(
            (shp.global_batch, cfg.num_image_tokens, cfg.d_model),
            cfg.cdtype, mesh, sp3)}
    return None
