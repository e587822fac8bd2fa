"""Sharded, freezing-aware training (DESIGN.md §5/§9).

The heavyweight assertions run in ONE subprocess with a forced 8-device
host platform (jax pins the device count at first init, so the main test
process — 1 CPU device — cannot host them):

* placement contract: trainable sharded per the param layout, frozen
  replicated over the DP axes, opt over the trainable partition only;
* per SEQUENTIAL phase, the compiled sharded train step's gradient-sync
  collective bytes (all-reduce/all-gather/reduce-scatter) sit STRICTLY
  below the no-freeze step's on the same mesh — freezing a factor group
  removes its wire traffic, not just its FLOPs;
* with int8 grad compression, the step's jaxpr contains int8 psums over
  trainable grads only — no psum at a frozen-factor shape (the exact
  jaxpr-level mirror of PR 1/2's kernel- and opt-state-absence checks:
  psum operands are real grad leaves, so shape matching is sound here,
  unlike post-SPMD HLO where bitcast packing aliases layouts);
* the fused Pallas kernels dispatch through shard_map under the mesh
  (interpret mode), match the jnp oracle fwd+bwd, and elide the frozen
  factor's backward kernel AND its psum;
* elastic resume: a checkpoint written on a 1-device mesh restores onto
  the (4,2) 8-device mesh and the next step's loss matches the 1-device
  continuation to <= 1e-5;
* in-training rank adaptation (DESIGN.md §10): under a decaying rank
  schedule the per-step gradient-sync collective bytes strictly decrease
  at every freezing-phase boundary on the pure-DP mesh — each scheduled
  truncation removes its slice of wire traffic.

The in-process tests cover the cheap satellites: ``make_host_mesh``
validation, the one-time ``shard()`` no-context warning, and
``FROZEN_PARAM_RULES`` spec resolution.
"""

import subprocess
import sys
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


# --------------------------------------------------------------------------
# satellites (in-process)
# --------------------------------------------------------------------------

def test_make_host_mesh_validates_device_count():
    from repro.launch.mesh import make_host_mesh

    m = make_host_mesh(1, 1)
    assert m.devices.size == 1
    n = len(jax.devices())
    with pytest.raises(ValueError, match="exceed"):
        make_host_mesh(n + 1, 1)  # always one more than available
    with pytest.raises(ValueError, match=">= 1"):
        make_host_mesh(0, 1)


def test_shard_warns_once_outside_axis_rules():
    import jax.numpy as jnp

    from repro.distributed import sharding as shmod

    prev = shmod._warned_no_rules
    shmod._warned_no_rules = False
    try:
        x = jnp.ones((4, 4))
        with pytest.warns(UserWarning, match="outside an\\s+axis_rules"):
            y = shmod.shard(x, "batch", None)
        assert y is x  # still a no-op
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # second call must NOT warn
            shmod.shard(x, "batch", None)
    finally:
        shmod._warned_no_rules = prev


def test_frozen_param_rules_have_no_dp_axes():
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.distributed.sharding import (FROZEN_PARAM_RULES, param_specs)

    devs = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    mesh = Mesh(devs, ("data", "model"))
    frozen = {"layers": {"wq": {"u": np.zeros((2, 64, 16), np.float32)},
                         "gate": {"v": np.zeros((2, 16, 64), np.float32)}}}
    specs = param_specs(frozen, mesh, FROZEN_PARAM_RULES)
    # u: fully replicated (no ZeRO rank sharding); v: TP over model only
    assert specs["layers"]["wq"]["u"] == P(None, None, None)
    assert specs["layers"]["gate"]["v"] == P(None, None, "model")
    for spec in (specs["layers"]["wq"]["u"], specs["layers"]["gate"]["v"]):
        flat = [a for part in spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)]
        assert "data" not in flat and "pod" not in flat


def test_groups_to_replace():
    from repro.core.freezing import groups_to_replace

    assert groups_to_replace(0, 1) == frozenset({0, 1})
    assert groups_to_replace(-1, 0) == frozenset({0})
    assert groups_to_replace(1, -1) == frozenset({1})
    assert groups_to_replace(0, 0) == frozenset()
    assert groups_to_replace(-1, -1) == frozenset()


# --------------------------------------------------------------------------
# the 8-device subprocess
# --------------------------------------------------------------------------

_PROG = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import dataclasses
import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_shapes
from repro.checkpoint import (load_checkpoint, pack_phased_state,
                              save_checkpoint, unpack_phased_state)
from repro.checkpoint.store import latest_checkpoint
from repro.configs import get_smoke_config
from repro.configs.base import (DistConfig, LRDConfig, OptimConfig,
                                RunConfig, ShapeConfig)
from repro.core import freezing
from repro.distributed.sharding import axis_rules
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.optim.optimizers import OptState

run = RunConfig(
    model=get_smoke_config("smollm-360m"),
    shape=ShapeConfig("b", 32, 8, "train"),
    lrd=LRDConfig(enabled=True, min_dim=16, rank_quantize=False,
                  freeze_mode="sequential"),
    dist=DistConfig(fsdp=False, remat="none", microbatches=1),
    optim=OptimConfig(name="adamw", lr=1e-2, warmup_steps=0,
                      total_steps=100))
mesh = make_host_mesh(4, 2)
params, _ = steps.init_params(run, jax.random.PRNGKey(0))
params_h = jax.tree_util.tree_map(jax.device_get, params)
rng = np.random.default_rng(0)
batch_h = {{"tokens": rng.integers(0, run.model.vocab_size, (8, 32)).astype(np.int32),
           "labels": rng.integers(0, run.model.vocab_size, (8, 32)).astype(np.int32)}}

# ---- placement contract ---------------------------------------------------
state, parked = steps.make_sharded_train_state(run, params_h, 0, mesh)
steps.check_state_placement(run, mesh, state)
sh_leaves = [l.sharding for l in jax.tree_util.tree_leaves(state.trainable)]
assert all(isinstance(s, NamedSharding) for s in sh_leaves)
assert any(s.spec != P() and tuple(p for p in s.spec if p) for s in sh_leaves), \
    "no trainable leaf is sharded at all"

def frozen_axes(t, path=""):
    if isinstance(t, dict):
        for k, v in t.items():
            frozen_axes(v, path + "/" + k)
        return
    if t is None:
        return
    spec = t.sharding.spec
    flat = [a for part in spec if part is not None
            for a in ((part,) if isinstance(part, str) else part)]
    assert "data" not in flat and "pod" not in flat, (path, spec)

frozen_axes(state.frozen)
n_frozen = len(jax.tree_util.tree_leaves(state.frozen))
assert n_frozen > 0, "smoke run decomposed nothing - test is vacuous"
print("PLACEMENT_OK")

# ---- collective traffic: every frozen phase strictly below no-freeze ------
# (exact frozen-shape absence is asserted on the jaxpr of the explicit-psum
# path below, where operand shapes are real grad leaves; compiled HLO
# bitcast-packs activation collectives into arbitrary layouts, so here the
# structural claim is audited as BYTES: freezing a factor group removes its
# grad all-reduce + ZeRO gather traffic from the wire)
from repro.analysis.hlo import analyze_hlo

train = steps.build_train_step(run, mesh)
batch = steps.shard_batch(batch_h, mesh)
sync_bytes = {{}}
for phase in (-1, 0, 1):
    st, _ = steps.make_sharded_train_state(run, params_h, phase, mesh)
    shs = steps.state_shardings(run, mesh, st)
    fn = jax.jit(functools.partial(train, phase=phase), donate_argnums=(0,),
                 in_shardings=(shs, steps.batch_shardings(batch, mesh)),
                 out_shardings=(shs, None))
    compiled = fn.lower(st, batch).compile()
    txt = compiled.as_text()
    colls = collective_shapes(txt)
    assert any(c[0] == "all-reduce" for c in colls), \
        f"phase {{phase}}: no all-reduce at all - DP sync missing?"
    cb = analyze_hlo(txt).collective_bytes
    sync_bytes[phase] = sum(v for k, v in cb.items()
                            if k in ("all-reduce", "all-gather",
                                     "reduce-scatter"))
    st2, m = fn(st, batch)
    assert np.isfinite(float(m["loss"]))
    steps.check_state_placement(run, mesh, st2)
assert sync_bytes[0] < sync_bytes[-1], sync_bytes
assert sync_bytes[1] < sync_bytes[-1], sync_bytes
print("FROZEN_COLLECTIVE_OK", sync_bytes)

# ---- int8 DP compression: psums cover the trainable partition only --------
def psum_eqns(jaxpr, out=None):
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        if "psum" in str(eqn.primitive):
            out.extend((str(a.aval.dtype), tuple(a.aval.shape))
                       for a in eqn.invars if hasattr(a, "aval"))
        for val in eqn.params.values():
            if hasattr(val, "jaxpr"):
                psum_eqns(val.jaxpr, out)
            elif hasattr(val, "eqns"):
                psum_eqns(val, out)
    return out

run8 = dataclasses.replace(run, dist=dataclasses.replace(
    run.dist, grad_compression="int8"))
# int8 data-axis sync is pure-DP only: compile AND execute on (8,1)
mesh_dp = make_host_mesh(8, 1)
train8 = steps.build_train_step(run8, mesh_dp)
batch_dp = steps.shard_batch(batch_h, mesh_dp)
for phase in (0, 1):
    st, _ = steps.make_sharded_train_state(run8, params_h, phase, mesh_dp)
    fsh = set()
    for leaf in jax.tree_util.tree_leaves(st.frozen):
        fsh.add(tuple(leaf.shape))
        if leaf.ndim >= 3:
            fsh.add(tuple(leaf.shape[1:]))
    jaxpr = jax.make_jaxpr(functools.partial(train8, phase=phase))(st,
                                                                   batch_dp)
    psums = psum_eqns(jaxpr.jaxpr)
    assert any(dt == "int8" for dt, _ in psums), "no int8 psum on the wire"
    bad = [(dt, shp) for dt, shp in psums if shp in fsh]
    assert not bad, f"phase {{phase}}: psum at frozen shapes: {{bad}}"
    # the jaxpr claim must survive compilation + a real step (an earlier
    # revision crashed only at compile time, which make_jaxpr cannot see)
    shs8dp = steps.state_shardings(run8, mesh_dp, st)
    fn8 = jax.jit(functools.partial(train8, phase=phase),
                  donate_argnums=(0,),
                  in_shardings=(shs8dp, steps.batch_shardings(batch_dp,
                                                              mesh_dp)),
                  out_shardings=(shs8dp, None))
    txt8 = fn8.lower(st, batch_dp).compile().as_text()
    assert "all-reduce" in txt8 and "s8[" in txt8, \
        "int8 all-reduce missing from compiled step"
    _, m8 = fn8(st, batch_dp)
    assert np.isfinite(float(m8["loss"]))
# on a TP mesh the int8 path must FALL BACK (warn once) and still compile
import warnings as _warnings
train8_tp = steps.build_train_step(run8, mesh)
st, _ = steps.make_sharded_train_state(run8, params_h, 0, mesh)
with _warnings.catch_warnings(record=True) as wrec:
    _warnings.simplefilter("always")
    jx = jax.make_jaxpr(functools.partial(train8_tp, phase=0))(st, batch)
assert any("pure-DP" in str(w.message) for w in wrec), \
    "no TP-mesh int8 fallback warning"
assert not any(dt == "int8" for dt, _ in psum_eqns(jx.jaxpr)), \
    "int8 psum present on TP mesh - should have fallen back"
shs_tp = steps.state_shardings(run8, mesh, st)
fn_tp = jax.jit(functools.partial(train8_tp, phase=0), donate_argnums=(0,),
                in_shardings=(shs_tp, steps.batch_shardings(batch, mesh)),
                out_shardings=(shs_tp, None))
_, m_tp = fn_tp(st, batch)
assert np.isfinite(float(m_tp["loss"]))
print("INT8_PSUM_OK")

# ---- fused kernels via shard_map under the mesh (interpret mode) ----------
from repro.kernels import ops, ref

M, C, R, S = 32, 32, 8, 64
kkw = dict(interpret=True, block_m=8, block_k=16, block_n=16)
kx = jax.random.normal(jax.random.PRNGKey(3), (M, C), jnp.float32) * 0.5
ku = jax.random.normal(jax.random.PRNGKey(4), (C, R), jnp.float32) * 0.5
kv = jax.random.normal(jax.random.PRNGKey(5), (R, S), jnp.float32) * 0.5

def apply_sharded(x, u, v, fg=None):
    with axis_rules(mesh):
        return ops.lowrank_apply(x, u, v, use_kernel=True, freeze_group=fg,
                                 **kkw)

y = jax.jit(apply_sharded)(kx, ku, kv)
np.testing.assert_allclose(np.asarray(y),
                           np.asarray(ref.lowrank_matmul_ref(kx, ku, kv)),
                           rtol=1e-4, atol=1e-4)
gu, gv = jax.grad(lambda u, v: jnp.sum(apply_sharded(kx, u, v) ** 2),
                  argnums=(0, 1))(ku, kv)
gur, gvr = jax.grad(
    lambda u, v: jnp.sum(ref.lowrank_matmul_ref(kx, u, v) ** 2),
    argnums=(0, 1))(ku, kv)
np.testing.assert_allclose(np.asarray(gu), np.asarray(gur), rtol=2e-3,
                           atol=2e-3)
np.testing.assert_allclose(np.asarray(gv), np.asarray(gvr), rtol=2e-3,
                           atol=2e-3)
# frozen phase: no du kernel, no psum at u's shape
jx = jax.make_jaxpr(jax.grad(
    lambda v: jnp.sum(apply_sharded(kx, ku, v, fg=0) ** 2)))(kv)
psums = psum_eqns(jx.jaxpr)
assert (C, R) not in [s for _, s in psums], psums
assert "name=lowrank_du" not in str(jx) and "name=lowrank_dv" in str(jx)
print("KERNEL_SHMAP_OK")

# ---- elastic resume 1-device -> 8-device, loss parity ---------------------
import tempfile
ckpt_dir = tempfile.mkdtemp()
mesh1 = make_host_mesh(1, 1)
train1 = steps.build_train_step(run, mesh1)
state1, parked1 = steps.make_sharded_train_state(run, params_h, 0, mesh1)
fn1 = jax.jit(functools.partial(train1, phase=0), donate_argnums=(0,))
batch1 = steps.shard_batch(batch_h, mesh1)
for _ in range(2):
    state1, m1 = fn1(state1, batch1)
save_checkpoint(ckpt_dir, 2, pack_phased_state(state1, parked1),
                extra={{"phase": 0}})
_, mA = fn1(state1, batch1)          # 1-device continuation
loss_a = float(mA["loss"])

saved, step_n, extra = load_checkpoint(
    latest_checkpoint(ckpt_dir),
    shardings=steps.packed_state_shardings(run, mesh, 0))
assert step_n == 2 and int(extra["phase"]) == 0
(tr, fr, opt), parked_h = unpack_phased_state(saved, 0)
state8 = steps.TrainState(tr, fr, OptState(*opt))
steps.check_state_placement(run, mesh, state8)
for leaf in jax.tree_util.tree_leaves(state8.trainable):
    assert len(leaf.sharding.device_set) == 8
for t in parked_h:
    for leaf in jax.tree_util.tree_leaves(t):
        assert not isinstance(leaf, jax.Array), "parked slice landed on device"
shs8 = steps.state_shardings(run, mesh, state8)
fn8 = jax.jit(functools.partial(train, phase=0), donate_argnums=(0,),
              in_shardings=(shs8, steps.batch_shardings(batch, mesh)),
              out_shardings=(shs8, None))
_, mB = fn8(state8, batch)           # 8-device continuation of the SAME state
loss_b = float(mB["loss"])
assert abs(loss_a - loss_b) <= 1e-5, (loss_a, loss_b)
print("ELASTIC_OK", loss_a, loss_b)

# ---- in-training rank adaptation: sync bytes shrink every boundary --------
# (DESIGN.md §10) on the pure-DP mesh the gradient all-reduce covers exactly
# the trainable partition, so each scheduled truncation must remove its
# slice of wire traffic: per-step collective bytes STRICTLY decrease across
# the four segments (phase 0 full -> p1@0.75 -> p0@0.56 -> p1@0.42)
from repro.core import rank_adapt

run_ra = dataclasses.replace(run, lrd=dataclasses.replace(
    run.lrd, rank_schedule="decay", rank_decay=0.75, rank_min=2))
sched_ra = rank_adapt.schedule_from_config(run_ra.lrd)
train_ra = steps.build_train_step(run_ra, mesh_dp)
st_ra, parked_ra = steps.make_sharded_train_state(run_ra, params_h, 0,
                                                  mesh_dp)
seg_sync, seg_rank = [], []
for epoch in range(4):
    phase = epoch % 2
    if epoch > 0:
        st_ra, parked_ra = steps.repartition_state(
            run_ra.optim, st_ra, parked_ra, phase, mesh=mesh_dp, run=run_ra,
            schedule=sched_ra, boundary=epoch)
    shs_ra = steps.state_shardings(run_ra, mesh_dp, st_ra)
    fn_ra = jax.jit(functools.partial(train_ra, phase=phase),
                    in_shardings=(shs_ra,
                                  steps.batch_shardings(batch_dp, mesh_dp)),
                    out_shardings=(shs_ra, None))
    cb = analyze_hlo(fn_ra.lower(st_ra, batch_dp).compile().as_text()
                     ).collective_bytes
    seg_sync.append(sum(v for k, v in cb.items()
                        if k in ("all-reduce", "all-gather",
                                 "reduce-scatter", "all-to-all")))
    seg_rank.append(sum(rank_adapt.live_rank_map(st_ra.params).values()))
    st_ra, m_ra = fn_ra(st_ra, batch_dp)
    assert np.isfinite(float(m_ra["loss"]))
    steps.check_state_placement(run_ra, mesh_dp, st_ra)
assert all(a > b for a, b in zip(seg_rank, seg_rank[1:])), seg_rank
assert all(a > b for a, b in zip(seg_sync, seg_sync[1:])), \
    f"sync bytes must strictly decrease across rank-adapted phases: " \
    f"{{seg_sync}} (ranks {{seg_rank}})"
print("RANK_SYNC_OK", seg_sync)
'''


def test_sharded_train_8dev():
    prog = _PROG.format(src=SRC)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=1200)
    report = (out.stdout[-3000:] + "\n--- stderr ---\n" + out.stderr[-3000:])
    for marker in ("PLACEMENT_OK", "FROZEN_COLLECTIVE_OK", "INT8_PSUM_OK",
                   "KERNEL_SHMAP_OK", "ELASTIC_OK", "RANK_SYNC_OK"):
        assert marker in out.stdout, f"missing {marker}\n{report}"
