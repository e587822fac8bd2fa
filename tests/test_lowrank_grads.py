"""Interpret-mode parity + freezing tests for the fused low-rank backward.

The fused forward kernels pair with Pallas backward kernels through a
``jax.custom_vjp`` (kernels/ops.py).  These tests check, per shape and dtype:

* dx/dU/dV from the kernel path == ``jax.grad`` of the jnp reference
  composition (kernels/ref.py), to <= 1e-4 in f32;
* non-block-divisible shapes run whole-dimension blocks, and a shape no
  block fits in VMEM falls back to the reference path and still honors
  the freeze contract;
* a static ``freeze_group`` makes the frozen factor's gradient *symbolically
  absent* — its backward kernel does not appear in the jaxpr (checked with
  ``jax.make_jaxpr``), as opposed to emitted-then-DCE'd — and the same holds
  for the jaxpr of a full ``build_train_step`` train step.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

interpret = pytest.mark.interpret


def _mats(key, m, c, r, s, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (m, c), jnp.float32).astype(dtype)
    u = (jax.random.normal(k2, (c, r), jnp.float32) / np.sqrt(c)).astype(dtype)
    v = (jax.random.normal(k3, (r, s), jnp.float32) / np.sqrt(r)).astype(dtype)
    return x, u, v


def _grads(fn, *args):
    return jax.grad(fn, argnums=tuple(range(len(args))))(*args)


def _kernel_names(jaxpr) -> set:
    """Names of the Pallas kernels in a jaxpr (the stable ``name=`` every
    ``pallas_call`` in ``repro.kernels`` passes)."""
    return set(re.findall(r"name=(\w+)", str(jaxpr)))


# (m, c, r, s, bm, bk, bn); the last two are NOT divisible by the requested
# blocks: the dispatcher resolves whole-dimension blocks for those dims.
SHAPES = [
    (256, 512, 64, 256, 128, 256, 128),
    (512, 1024, 128, 512, 256, 512, 256),
    (256, 512, 96, 384, 128, 256, 128),   # r, s off the MXU-tile grid
    (128, 256, 32, 128, 128, 256, 128),
    (100, 130, 16, 70, 128, 256, 128),    # no block divides -> whole dims
    (192, 512, 64, 256, 128, 256, 128),   # m indivisible by bm -> whole m
]


@pytest.mark.parametrize("m,c,r,s,bm,bk,bn", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@interpret
def test_lowrank_matmul_grads_match_ref(m, c, r, s, bm, bk, bn, dtype):
    x, u, v = _mats(jax.random.PRNGKey(m + c + r + s), m, c, r, s, dtype)
    dy = jax.random.normal(jax.random.PRNGKey(7), (m, s), jnp.float32)

    def f_kernel(x, u, v):
        y = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True,
                              block_m=bm, block_k=bk, block_n=bn)
        return jnp.vdot(y.astype(jnp.float32), dy)

    def f_ref(x, u, v):
        return jnp.vdot(ref.lowrank_matmul_ref(x, u, v).astype(jnp.float32), dy)

    gk = _grads(f_kernel, x, u, v)
    gr = _grads(f_ref, x, u, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    for name, a, b in zip(("dx", "du", "dv"), gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("freeze_group", [None, 0, 1])
@interpret
def test_lowrank_matmul_freeze_group_grads(freeze_group):
    m, c, r, s, bm, bk, bn = 128, 256, 32, 128, 128, 256, 128
    x, u, v = _mats(jax.random.PRNGKey(3), m, c, r, s, jnp.float32)

    def f(x, u, v):
        y = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True,
                              block_m=bm, block_k=bk, block_n=bn,
                              freeze_group=freeze_group)
        return jnp.sum(y ** 2)

    def f_ref(x, u, v):
        return jnp.sum(ref.lowrank_matmul_ref(x, u, v) ** 2)

    dx, du, dv = _grads(f, x, u, v)
    rx, ru, rv = _grads(f_ref, x, u, v)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), rtol=1e-4, atol=1e-4)
    if freeze_group == 0:
        assert float(jnp.abs(du).max()) == 0.0
    else:
        np.testing.assert_allclose(np.asarray(du), np.asarray(ru), rtol=1e-4, atol=1e-4)
    if freeze_group == 1:
        assert float(jnp.abs(dv).max()) == 0.0
    else:
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("freeze_group", [0, 1])
@interpret
def test_freeze_group_honored_on_fallback_path(freeze_group):
    """A shape whose whole-dimension tiles overflow the VMEM budget takes
    the jnp fallback — the freeze contract must hold there too
    (stop_gradient), not only on the kernel path."""
    m, c, r, s = 2000, 1100, 16, 70  # no default block divides; too big whole
    x, u, v = _mats(jax.random.PRNGKey(21), m, c, r, s, jnp.float32)

    def f(x, u, v):
        y = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True,
                              freeze_group=freeze_group)
        return jnp.sum(y ** 2)

    with ops.capture_fallbacks() as sink:
        dx, du, dv = _grads(f, x, u, v)
    assert [fb.reason for fb in sink] == ["vmem"]
    frozen = du if freeze_group == 0 else dv
    live = dv if freeze_group == 0 else du
    assert float(jnp.abs(frozen).max()) == 0.0
    assert float(jnp.abs(live).max()) > 0.0
    assert float(jnp.abs(dx).max()) > 0.0


@interpret
def test_frozen_factor_kernel_not_emitted():
    """The frozen factor's backward kernel must be absent from the jaxpr —
    never emitted, not merely dead-code-eliminated after the fact."""
    m, c, r, s, bm, bk, bn = 128, 256, 32, 128, 128, 256, 128
    x, u, v = _mats(jax.random.PRNGKey(5), m, c, r, s, jnp.float32)

    def loss_for(fg):
        def loss(x, u, v):
            y = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True,
                                  block_m=bm, block_k=bk, block_n=bn,
                                  freeze_group=fg)
            return jnp.sum(y ** 2)
        return loss

    both = _kernel_names(jax.make_jaxpr(
        jax.grad(loss_for(None), argnums=(0, 1, 2)))(x, u, v))
    assert {"lowrank_du", "lowrank_dv", "lowrank_dx"} <= both

    fz0 = _kernel_names(jax.make_jaxpr(
        jax.grad(loss_for(0), argnums=(0, 1, 2)))(x, u, v))
    assert "lowrank_du" not in fz0 and "lowrank_dv" in fz0

    fz1 = _kernel_names(jax.make_jaxpr(
        jax.grad(loss_for(1), argnums=(0, 1, 2)))(x, u, v))
    assert "lowrank_dv" not in fz1 and "lowrank_du" in fz1


@pytest.mark.parametrize("freeze_group", [None, 0, 1])
@interpret
def test_lowrank_ffn_grads_match_ref(freeze_group):
    m, c, rg, ru, f = 128, 256, 32, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(ks[0], (m, c), jnp.float32)
    gu = jax.random.normal(ks[1], (c, rg)) / np.sqrt(c)
    gv = jax.random.normal(ks[2], (rg, f)) / np.sqrt(rg)
    uu = jax.random.normal(ks[3], (c, ru)) / np.sqrt(c)
    uv = jax.random.normal(ks[4], (ru, f)) / np.sqrt(ru)

    def fk(x, gu, gv, uu, uv):
        y = ops.lowrank_ffn_apply(x, gu, gv, uu, uv, use_kernel=True,
                                  interpret=True, block_m=128, block_k=256,
                                  block_n=128, freeze_group=freeze_group)
        return jnp.sum(y ** 2)

    def fr(x, gu, gv, uu, uv):
        return jnp.sum(ref.lowrank_gated_ffn_ref(x, gu, gv, uu, uv) ** 2)

    gk = _grads(fk, x, gu, gv, uu, uv)
    gr = _grads(fr, x, gu, gv, uu, uv)
    names = ("dx", "dgu", "dgv", "duu", "duv")
    frozen = {0: ("dgu", "duu"), 1: ("dgv", "duv")}.get(freeze_group, ())
    for name, a, b in zip(names, gk, gr):
        if name in frozen:
            assert float(jnp.abs(a).max()) == 0.0, name
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4, err_msg=name)


@interpret
def test_train_step_jaxpr_elides_frozen_factor_kernels():
    """End-to-end: the jaxpr of a real build_train_step train step, with the
    fused kernels enabled, contains no backward kernel for the factor group
    frozen by the sequential-freezing phase."""
    from repro.configs import get_smoke_config
    from repro.configs.base import (DistConfig, LRDConfig, OptimConfig,
                                    RunConfig, ShapeConfig)
    from repro.data import LMBatchIterator
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh

    cfg = get_smoke_config("smollm-360m")
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 16, 4, "train"),
        lrd=LRDConfig(enabled=True, min_dim=16, freeze_mode="sequential",
                      rank_quantize=False, use_pallas_kernel=True,
                      pallas_interpret=True, pallas_block_m=32,
                      pallas_block_k=64, pallas_block_n=32),
        dist=DistConfig(fsdp=False, remat="none"),
        optim=OptimConfig(name="sgdm", lr=1e-2, warmup_steps=2, total_steps=8))
    params, plan = steps.init_params(run, jax.random.PRNGKey(0))
    assert any(lp.use_decomposed for lp in plan.layers.values())
    mesh = make_host_mesh(1, 1)
    train = steps.build_train_step(run, mesh)
    it = iter(LMBatchIterator(cfg.vocab_size, 16, 4, seed=0))
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}

    def jaxpr_for(phase):
        state, _ = steps.make_train_state(run.optim, params, phase)
        return _kernel_names(jax.make_jaxpr(
            functools.partial(train, phase=phase))(state, batch))

    unfrozen = jaxpr_for(-1)
    # fused forwards actually on the hot path
    assert {"lowrank_fwd", "lowrank_ffn_fwd"} <= unfrozen
    assert "lowrank_du" in unfrozen and "lowrank_dv" in unfrozen

    phase0 = jaxpr_for(0)  # group 0 (u) frozen
    assert "lowrank_du" not in phase0 and "lowrank_dv" in phase0
    assert "lowrank_dx" in phase0

    phase1 = jaxpr_for(1)  # group 1 (v) frozen
    assert "lowrank_dv" not in phase1 and "lowrank_du" in phase1


@interpret
def test_train_step_runs_with_pallas_interpret():
    """Two real optimizer steps through the fused fwd+bwd kernel path."""
    from repro.configs import get_smoke_config
    from repro.configs.base import (DistConfig, LRDConfig, OptimConfig,
                                    RunConfig, ShapeConfig)
    from repro.data import LMBatchIterator
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh

    cfg = get_smoke_config("smollm-360m")
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 16, 4, "train"),
        lrd=LRDConfig(enabled=True, min_dim=16, freeze_mode="sequential",
                      rank_quantize=False, use_pallas_kernel=True,
                      pallas_interpret=True, pallas_block_m=32,
                      pallas_block_k=64, pallas_block_n=32),
        dist=DistConfig(fsdp=False, remat="none"),
        optim=OptimConfig(name="sgdm", lr=1e-2, warmup_steps=2, total_steps=8))
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    state, _ = steps.make_train_state(run.optim, params, 0)
    train = steps.build_train_step(run, make_host_mesh(1, 1))
    it = iter(LMBatchIterator(cfg.vocab_size, 16, 4, seed=0))
    step0 = jax.jit(functools.partial(train, phase=0))
    for _ in range(2):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, m = step0(state, batch)
    assert np.isfinite(float(m["loss"]))


def _leaf_paths(tree):
    """'/'-joined dict paths of non-None leaves."""
    out = []

    def walk(t, path=""):
        if isinstance(t, dict):
            for k, v in t.items():
                walk(v, f"{path}/{k}")
        elif t is not None:
            out.append(path)

    walk(tree)
    return out


def test_train_step_opt_state_and_accumulators_exclude_frozen():
    """Extends the kernel-absence contract to the optimizer and the grad
    accumulators: at a frozen phase, the train step's output opt state has
    NO leaf for the frozen factor group, and the microbatch scan carries no
    accumulator of a frozen-factor shape — structurally absent from the
    jaxpr, not zero-filled."""
    from repro.configs import get_smoke_config
    from repro.configs.base import (DistConfig, LRDConfig, OptimConfig,
                                    RunConfig, ShapeConfig)
    from repro.data import LMBatchIterator
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh

    cfg = get_smoke_config("smollm-360m")
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 16, 4, "train"),
        lrd=LRDConfig(enabled=True, min_dim=16, freeze_mode="sequential",
                      rank_quantize=False),
        dist=DistConfig(fsdp=False, remat="none", microbatches=2),
        optim=OptimConfig(name="adamw", lr=1e-3, warmup_steps=2,
                          total_steps=8))
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    train = steps.build_train_step(run, make_host_mesh(1, 1))
    it = iter(LMBatchIterator(cfg.vocab_size, 16, 4, seed=0))
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}

    def outputs_and_jaxpr(phase):
        state, _ = steps.make_train_state(run.optim, params, phase)
        fn = functools.partial(train, phase=phase)
        out, _ = jax.eval_shape(fn, state, batch)
        return state, out, jax.make_jaxpr(fn)(state, batch)

    def scan_carry_shapes(jaxpr):
        shapes = []
        for eqn in jaxpr.jaxpr.eqns:
            if eqn.primitive.name == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                shapes += [tuple(v.aval.shape)
                           for v in eqn.invars[nc:nc + ncar]]
        return shapes

    # phase 0: every u factor is frozen
    state0, out0, jaxpr0 = outputs_and_jaxpr(0)
    u_paths = [p for p in _leaf_paths(params) if p.endswith("/u")]
    assert u_paths  # decomposition actually produced factors
    for tree in (out0.opt.mu, out0.opt.nu):
        mu_paths = _leaf_paths(tree)
        assert mu_paths and not any(p.endswith("/u") for p in mu_paths)
    assert any(p.endswith("/v") for p in _leaf_paths(out0.opt.mu))

    # grad-accumulator check: frozen-factor shapes absent from the scan
    # carry (shapes unique to the frozen partition, so no false match)
    frozen_shapes = {tuple(l.shape)
                     for l in jax.tree_util.tree_leaves(state0.frozen)}
    train_shapes = {tuple(l.shape)
                    for l in jax.tree_util.tree_leaves(state0.trainable)}
    frozen_only = frozen_shapes - train_shapes
    assert frozen_only  # the check below has teeth
    carry0 = scan_carry_shapes(jaxpr0)
    assert carry0  # microbatch scan present
    assert not (set(carry0) & frozen_only)
    assert set(carry0) & train_shapes  # trainable accumulators ARE carried

    # unfrozen baseline: the same shapes DO appear in the scan carry
    _, out_all, jaxpr_all = outputs_and_jaxpr(-1)
    assert set(scan_carry_shapes(jaxpr_all)) & frozen_only
    assert any(p.endswith("/u") for p in _leaf_paths(out_all.opt.mu))
