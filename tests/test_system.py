"""End-to-end behaviour tests: training convergence (LRD vs dense, freezing
variants), checkpoint/restore resumption, serving engine generation, gradient
compression correctness, optimizer semantics, data-pipeline determinism."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import (DistConfig, LRDConfig, OptimConfig, RunConfig,
                                ShapeConfig)
from repro.core import freezing
from repro.data import LMBatchIterator
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.optim import init_optimizer
from repro.optim.optimizers import apply_updates


def _train(arch="smollm-360m", steps_n=12, lrd=False, freeze="none",
           microbatches=1, seq=32, batch=4, seed=0, steps_per_epoch=4,
           n_batches=2, optimizer="sgdm", epochs_per_phase=1,
           total_steps=None):
    """Train on a small cycling batch set (memorization): exercises the full
    step machinery with a guaranteed loss-decrease signal."""
    cfg = get_smoke_config(arch)
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", seq, batch, "train"),
        lrd=LRDConfig(enabled=lrd, min_dim=16, freeze_mode=freeze,
                      epochs_per_phase=epochs_per_phase,
                      rank_quantize=False),  # smoke dims < MXU tile: skip the guard
        dist=DistConfig(fsdp=False, remat="none", microbatches=microbatches),
        optim=OptimConfig(name=optimizer, lr=2e-2, warmup_steps=2,
                          total_steps=total_steps or steps_n))
    key = jax.random.PRNGKey(seed)
    params, plan = steps.init_params(run, key)

    def phase_at(i):
        return steps.run_phase(run, i // steps_per_epoch)

    cur_phase = phase_at(0)
    state, parked = steps.make_train_state(run.optim, params, cur_phase)
    mesh = make_host_mesh(1, 1)
    train = steps.build_train_step(run, mesh)
    data = LMBatchIterator(cfg.vocab_size, seq, batch, seed=seed)
    it = iter(data)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(n_batches)]
    fns = {}
    losses = []
    for i in range(steps_n):
        phase = phase_at(i)
        if phase != cur_phase:
            state, parked = steps.repartition_state(run.optim, state, parked,
                                                    phase)
            cur_phase = phase
        if phase not in fns:
            fns[phase] = jax.jit(functools.partial(train, phase=phase))
        state, m = fns[phase](state, batches[i % n_batches])
        losses.append(float(m["loss"]))
    return losses, state, plan


def test_training_loss_decreases():
    losses, _, _ = _train(steps_n=15)
    assert losses[-1] < losses[0] - 0.05
    assert all(np.isfinite(l) for l in losses)


def test_lrd_training_converges():
    losses, _, plan = _train(steps_n=15, lrd=True)
    assert losses[-1] < losses[0] - 0.05
    assert any(lp.use_decomposed for lp in plan.layers.values())


def test_sequential_freezing_converges():
    losses, _, _ = _train(steps_n=16, lrd=True, freeze="sequential")
    assert losses[-1] < losses[0] - 0.03


def test_microbatching_matches_full_batch():
    """grad accumulation over microbatches == single big batch (same data)."""
    l1, s1, _ = _train(steps_n=3, microbatches=1, batch=4, seed=3)
    l2, s2, _ = _train(steps_n=3, microbatches=2, batch=4, seed=3)
    assert abs(l1[0] - l2[0]) < 1e-3
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-3)


def test_checkpoint_roundtrip_and_resume():
    import tempfile

    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.checkpoint.store import latest_checkpoint

    _, state, _ = _train(steps_n=3)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 3, state, extra={"data": {"step": 3, "seed": 17}})
        save_checkpoint(d, 6, state, extra={"data": {"step": 6, "seed": 17}})
        latest = latest_checkpoint(d)
        assert latest.name == "step_00000006"
        restored, step, extra = load_checkpoint(latest)
        assert step == 6 and extra["data"]["step"] == 6
        flat_a = jax.tree_util.tree_leaves(state)
        flat_b = jax.tree_util.tree_leaves(restored)
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_ignores_incomplete():
    import tempfile
    from pathlib import Path

    from repro.checkpoint import save_checkpoint
    from repro.checkpoint.store import latest_checkpoint

    _, state, _ = _train(steps_n=1)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, state)
        # simulate a crash mid-save at step 2: dir exists, no .complete
        broken = Path(d) / "step_00000002"
        broken.mkdir()
        (broken / "manifest.json").write_text("{}")
        assert latest_checkpoint(d).name == "step_00000001"


def test_optimizer_partition_excludes_frozen_leaves():
    """Partitioned semantics: the frozen factor has NO optimizer state and
    never reaches apply_updates; merge returns it untouched."""
    params = {"wq": {"u": jnp.ones((4, 2)), "v": jnp.ones((2, 4))}}
    cfg = OptimConfig(name="sgdm", lr=0.1, warmup_steps=0, total_steps=10,
                      weight_decay=0.0, schedule="constant")
    trainable, frozen = freezing.partition(params, 0)  # u frozen
    assert trainable["wq"]["u"] is None and frozen["wq"]["v"] is None
    opt = init_optimizer(cfg, trainable)
    # opt state exists for v only — u contributes no leaf at all
    assert opt.mu["wq"]["u"] is None
    assert len(jax.tree_util.tree_leaves(opt.mu)) == 1
    grads = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.5), trainable)
    new_trainable, new_opt = apply_updates(cfg, trainable, grads, opt)
    merged = freezing.merge(new_trainable, frozen)
    np.testing.assert_array_equal(np.asarray(merged["wq"]["u"]),
                                  np.asarray(params["wq"]["u"]))
    assert not np.array_equal(np.asarray(merged["wq"]["v"]),
                              np.asarray(params["wq"]["v"]))
    assert float(jnp.sum(jnp.abs(new_opt.mu["wq"]["v"]))) > 0.0


def test_repartition_rotates_moments_without_reset():
    """Algorithm-2 phase swap must carry momentum through freeze/unfreeze:
    phase 0 trains v (builds mu_v), swap to phase 1 parks mu_v and restores
    mu_u, swap back restores mu_v exactly."""
    params = {"wq": {"u": jnp.ones((4, 2)), "v": jnp.ones((2, 4))}}
    cfg = OptimConfig(name="sgdm", lr=0.1, warmup_steps=0, total_steps=10,
                      weight_decay=0.0, schedule="constant")
    state, parked = steps.make_train_state(cfg, params, 0)
    grads = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.5),
                                   state.trainable)
    new_trainable, new_opt = apply_updates(cfg, state.trainable, grads,
                                           state.opt)
    state = steps.TrainState(new_trainable, state.frozen, new_opt)
    mu_v = np.asarray(state.opt.mu["wq"]["v"])
    assert np.abs(mu_v).sum() > 0.0

    state1, parked1 = steps.repartition_state(cfg, state, parked, 1)
    assert state1.opt.mu["wq"]["v"] is None  # v moments parked...
    np.testing.assert_array_equal(np.asarray(parked1[0]["wq"]["v"]), mu_v)
    assert state1.opt.mu["wq"]["u"] is not None  # ...u moments live (zeros)

    state0, parked0 = steps.repartition_state(cfg, state1, parked1, 0)
    np.testing.assert_array_equal(np.asarray(state0.opt.mu["wq"]["v"]), mu_v)
    # params round-trip untouched by the two swaps
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(state0.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partitioned_step_matches_masked_reference_trajectory():
    """Acceptance: the partitioned train step reproduces the pre-refactor
    semantics (full-tree grads with stop_gradient masking + mask-skipped
    SGD updates) to <= 1e-5 over a two-phase run on the smollm config."""
    cfg = get_smoke_config("smollm-360m")
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 32, 4, "train"),
        lrd=LRDConfig(enabled=True, min_dim=16, freeze_mode="sequential",
                      rank_quantize=False),
        dist=DistConfig(fsdp=False, remat="none"),
        optim=OptimConfig(name="sgdm", lr=2e-2, warmup_steps=2,
                          total_steps=8, weight_decay=1e-4))
    params, _ = steps.init_params(run, jax.random.PRNGKey(4))
    data = LMBatchIterator(cfg.vocab_size, 32, 4, seed=4)
    it = iter(data)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(2)]

    # --- reference: full-tree masked training (pre-refactor contract) -----
    from repro.optim.optimizers import make_schedule
    sched = make_schedule(run.optim)

    def ref_loss(p, b, phase):
        masked = freezing.apply_freeze(p, freezing.freeze_mask(p, phase))
        none_holes = freezing.partition(masked, -1)[1]
        return steps._loss_fn(masked, none_holes, b, run=run, phase=phase)

    @functools.partial(jax.jit, static_argnums=(3,))
    def ref_step(p, mu, opt_step, phase, b):
        loss, g = jax.value_and_grad(ref_loss)(p, b, phase)
        mask = freezing.freeze_mask(p, phase)
        lr = sched(opt_step)
        new_mu = jax.tree_util.tree_map(
            lambda m, mu_l, g_l: (run.optim.momentum * mu_l + g_l) if m else mu_l,
            mask, mu, g)
        new_p = jax.tree_util.tree_map(
            lambda m, p_l, mu_l: (p_l.astype(jnp.float32) - lr * (
                mu_l + run.optim.weight_decay * p_l.astype(jnp.float32))
            ).astype(p_l.dtype) if m else p_l,
            mask, p, new_mu)
        return new_p, new_mu, opt_step + 1, loss

    ref_p = params
    ref_mu = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    ref_losses, opt_step = [], jnp.zeros((), jnp.int32)
    for i in range(8):
        phase = freezing.phase_for_epoch(i // 4, "sequential")
        ref_p, ref_mu, opt_step, l = ref_step(ref_p, ref_mu, opt_step, phase,
                                              batches[i % 2])
        ref_losses.append(float(l))

    # --- partitioned path (same data, same init) --------------------------
    mesh = make_host_mesh(1, 1)
    train = steps.build_train_step(run, mesh)
    state, parked = steps.make_train_state(run.optim, params, 0)
    cur_phase, fns, losses = 0, {}, []
    for i in range(8):
        phase = freezing.phase_for_epoch(i // 4, "sequential")
        if phase != cur_phase:
            state, parked = steps.repartition_state(run.optim, state, parked,
                                                    phase)
            cur_phase = phase
        if phase not in fns:
            fns[phase] = jax.jit(functools.partial(train, phase=phase))
        state, m = fns[phase](state, batches[i % 2])
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=0, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_data_pipeline_deterministic_and_resumable():
    a = LMBatchIterator(256, 16, 4, seed=5)
    b1 = a.ds.next_batch()
    b2 = a.ds.next_batch()
    st = a.state_dict()
    b3 = a.ds.next_batch()
    fresh = LMBatchIterator(256, 16, 4, seed=5)
    fresh.load_state_dict(st)
    b3r = fresh.ds.next_batch()
    np.testing.assert_array_equal(b3["tokens"], b3r["tokens"])
    assert not np.array_equal(b1["tokens"], b2["tokens"])


def test_serving_engine_generates():
    cfg = get_smoke_config("smollm-360m")
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 16, 2, "decode"),
                    lrd=LRDConfig(enabled=False),
                    dist=DistConfig(fsdp=False, remat="none"))
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    from repro.serving import ServeEngine
    eng = ServeEngine(run, params, make_host_mesh(1, 1), max_len=32)
    prompts = np.random.randint(0, cfg.vocab_size, size=(2, 12), dtype=np.int32)
    out = eng.generate(prompts, max_new=6)
    assert out.shape == (2, 6)
    assert (out >= 0).all() and (out < cfg.vocab_padded).all()


def test_grad_compression_quantize_accuracy():
    from jax import shard_map

    from repro.distributed.compression import _quantize_pmean
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("pod",))
    g = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 0.01
    out = shard_map(
        lambda x: _quantize_pmean(x, axis="pod", n=1), mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)(g)
    err = np.abs(np.asarray(out) - np.asarray(g)).max()
    scale = float(jnp.max(jnp.abs(g))) / 127
    assert err <= scale * 1.01  # quantization error bounded by one step


def test_straggler_monitor():
    from repro.launch.train import StragglerMonitor
    mon = StragglerMonitor(factor=2.0)
    for _ in range(10):
        assert mon.observe(0.1) is False
    assert mon.observe(0.5) is True
    assert mon.observe(0.1) is False


def test_checkpoint_manager_async_save_and_resume():
    import tempfile

    from repro.checkpoint import CheckpointManager

    _, state, _ = _train(steps_n=2)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, save_every=1, keep=2, async_save=True)
        assert mgr.maybe_save(1, state, extra={"data": {"step": 1}})
        assert mgr.maybe_save(2, state, extra={"data": {"step": 2}})
        mgr.wait()
        restored = mgr.restore()
        assert restored is not None
        _, step, extra = restored
        assert step == 2 and extra["data"]["step"] == 2
        mgr.close()


def test_checkpoint_preserves_tuple_structure():
    """NamedTuple state must round-trip as a tuple at the ROOT too (a leading
    '/' in flattened keys once wrapped the tree in {'': ...})."""
    import tempfile

    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.checkpoint.store import latest_checkpoint

    state, _ = steps.make_train_state(OptimConfig(name="sgdm"),
                                      {"w": jnp.ones((2, 2))})
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, state)
        restored, _, _ = load_checkpoint(latest_checkpoint(d))
        assert isinstance(restored, tuple) and len(restored) == 3
        params_r, frozen_r, opt_r = restored
        assert set(params_r) == {"w"}
        assert frozen_r == {"w": None}  # partition holes survive the trip
        assert len(opt_r) == 3 and opt_r[2] == ()  # (step, mu, nu=())


def test_checkpoint_roundtrip_across_phase_boundary():
    """Save in phase 0, restore via the phased pack/unpack, continue into
    phase 1: loss/metrics must match an uninterrupted run exactly."""
    import tempfile

    from repro.checkpoint import (CheckpointManager, pack_phased_state,
                                  unpack_phased_state)
    from repro.optim.optimizers import OptState

    kw = dict(lrd=True, freeze="sequential", steps_per_epoch=4, seed=11,
              optimizer="adamw")
    full_losses, full_state, _ = _train(steps_n=10, **kw)

    # re-run the first 3 steps (all phase 0) and checkpoint mid-phase-0
    losses_a, state_a, _ = _train(steps_n=3, total_steps=10, **kw)
    cfg = get_smoke_config("smollm-360m")
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 32, 4, "train"),
        lrd=LRDConfig(enabled=True, min_dim=16, freeze_mode="sequential",
                      rank_quantize=False),
        dist=DistConfig(fsdp=False, remat="none"),
        optim=OptimConfig(name="adamw", lr=2e-2, warmup_steps=2,
                          total_steps=10))
    # parked moments after 3 steps of phase 0 are still the init zeros
    _, parked_a = steps.make_train_state(run.optim, state_a.params, 0)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, save_every=1, keep=2, async_save=False)
        assert mgr.maybe_save(3, pack_phased_state(state_a, parked_a),
                              extra={"phase": 0})
        saved, start_step, extra = mgr.restore()
        assert start_step == 3 and extra["phase"] == 0
        (tr, fr, opt_t), parked = unpack_phased_state(saved, extra["phase"])
        state = steps.TrainState(
            jax.tree_util.tree_map(jnp.asarray, tr),
            jax.tree_util.tree_map(jnp.asarray, fr),
            OptState(jnp.asarray(opt_t[0]),
                     jax.tree_util.tree_map(jnp.asarray, opt_t[1]),
                     jax.tree_util.tree_map(jnp.asarray, opt_t[2])))
        parked = tuple(jax.tree_util.tree_map(jnp.asarray, p) for p in parked)
        mgr.close()

    # continue steps 3..9 — crosses the phase boundary at step 4
    mesh = make_host_mesh(1, 1)
    train = steps.build_train_step(run, mesh)
    data = LMBatchIterator(cfg.vocab_size, 32, 4, seed=11)
    it = iter(data)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(2)]
    cur_phase, fns, losses_b = 0, {}, []
    for i in range(3, 10):
        phase = freezing.phase_for_epoch(i // 4, "sequential")
        if phase != cur_phase:
            state, parked = steps.repartition_state(run.optim, state, parked,
                                                    phase)
            cur_phase = phase
        if phase not in fns:
            fns[phase] = jax.jit(functools.partial(train, phase=phase))
        state, m = fns[phase](state, batches[i % 2])
        losses_b.append(float(m["loss"]))

    np.testing.assert_allclose(losses_a + losses_b, full_losses, rtol=0,
                               atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(full_state.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)
