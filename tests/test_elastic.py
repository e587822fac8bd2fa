"""Elastic re-scaling: a checkpoint saved in THIS (1-device) process restores
onto an 8-device (2,4) mesh in a subprocess with re-sharding — node-failure
recovery and cluster resizing share this code path."""

import json
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np


def test_checkpoint_restores_onto_bigger_mesh(tmp_path):
    from repro.checkpoint import save_checkpoint

    params = {"layer": {"kernel": np.arange(16 * 8, dtype=np.float32).reshape(16, 8)},
              "scale": np.ones((8,), np.float32)}
    save_checkpoint(tmp_path, 7, params, extra={"note": "elastic"})

    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {str(Path("src").resolve())!r})
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import load_checkpoint
from repro.checkpoint.store import latest_checkpoint
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
shardings = {{
    "layer": {{"kernel": NamedSharding(mesh, P("data", "model"))}},
    "scale": NamedSharding(mesh, P(None)),
}}
state, step, extra = load_checkpoint(latest_checkpoint({str(tmp_path)!r}), shardings)
k = state["layer"]["kernel"]
assert step == 7 and extra["note"] == "elastic"
assert len(k.sharding.device_set) == 8, k.sharding
np.testing.assert_array_equal(
    np.asarray(k), np.arange(16 * 8, dtype=np.float32).reshape(16, 8))
print("ELASTIC_OK")
"""
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


def test_rank_adapted_checkpoint_restores_on_both_meshes():
    """Mid-schedule resume (DESIGN.md §10): save AFTER a scheduled
    truncation fired at a phase boundary, then restore onto a 1-device and
    an 8-device mesh.  The manifest's rank map drives the target shardings
    (``packed_state_shardings(rank_map=...)``), the restored ranks must
    match it exactly, resumed next-step loss parity is <= 1e-5 on both
    meshes, and a wrong expected map fails fast."""
    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {str(Path("src").resolve())!r})
import functools, json, tempfile
import jax
import numpy as np

from repro.checkpoint import (live_rank_map, load_checkpoint,
                              pack_phased_state, save_checkpoint,
                              unpack_phased_state)
from repro.checkpoint.store import latest_checkpoint
from repro.configs import get_smoke_config
from repro.configs.base import (DistConfig, LRDConfig, OptimConfig,
                                RunConfig, ShapeConfig)
from repro.core import rank_adapt
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.optim.optimizers import OptState

run = RunConfig(
    model=get_smoke_config("smollm-360m"),
    shape=ShapeConfig("b", 32, 8, "train"),
    lrd=LRDConfig(enabled=True, min_dim=16, rank_quantize=False,
                  freeze_mode="sequential", rank_schedule="decay",
                  rank_decay=0.75, rank_min=2),
    dist=DistConfig(fsdp=False, remat="none"),
    optim=OptimConfig(name="adamw", lr=1e-2, warmup_steps=0,
                      total_steps=100))
schedule = rank_adapt.schedule_from_config(run.lrd)
mesh1 = make_host_mesh(1, 1)
params, _ = steps.init_params(run, jax.random.PRNGKey(0))
params_h = jax.tree_util.tree_map(jax.device_get, params)
rng = np.random.default_rng(1)
batch_h = {{"tokens": rng.integers(0, run.model.vocab_size, (8, 32)).astype(np.int32),
            "labels": rng.integers(0, run.model.vocab_size, (8, 32)).astype(np.int32)}}

state, parked = steps.make_sharded_train_state(run, params_h, 0, mesh1)
ranks0 = rank_adapt.live_rank_map(state.params)
train1 = steps.build_train_step(run, mesh1)
b1 = steps.shard_batch(batch_h, mesh1)
fn_p0 = jax.jit(functools.partial(train1, phase=0))
for _ in range(2):
    state, _ = fn_p0(state, b1)
# the boundary swap fires the scheduled truncation
state, parked = steps.repartition_state(
    run.optim, state, parked, 1, mesh=mesh1, run=run,
    schedule=schedule, boundary=1)
rank_map = rank_adapt.live_rank_map(state.params)
assert all(rank_map[p] < ranks0[p] for p in ranks0), (ranks0, rank_map)
fn_p1 = jax.jit(functools.partial(train1, phase=1))
state, _ = fn_p1(state, b1)

ckpt_dir = tempfile.mkdtemp()
save_checkpoint(ckpt_dir, 3, pack_phased_state(state, parked),
                extra={{"phase": 1, "rank_map": rank_map}})
_, mA = fn_p1(state, b1)  # source-mesh continuation
loss_a = float(mA["loss"])

# the resume path learns the saved ranks from the manifest BEFORE loading
# any leaf — that map drives the target shardings
manifest = json.loads(
    (latest_checkpoint(ckpt_dir) / "manifest.json").read_text())
saved_map = {{p: int(r)
             for p, r in manifest["extra"]["rank_map"].items()}}
assert saved_map == rank_map, (saved_map, rank_map)

for mesh, tag in ((mesh1, "1dev"), (make_host_mesh(4, 2), "8dev")):
    saved, step_n, extra = load_checkpoint(
        latest_checkpoint(ckpt_dir),
        shardings=steps.packed_state_shardings(run, mesh, 1,
                                               rank_map=saved_map))
    assert step_n == 3 and int(extra["phase"]) == 1
    assert live_rank_map(saved) == rank_map
    (tr, fr, opt), parked_r = unpack_phased_state(
        saved, 1, expect_rank_map=rank_map)
    st = steps.TrainState(tr, fr, OptState(*opt))
    assert rank_adapt.live_rank_map(st.params) == rank_map
    for t in parked_r:
        for leaf in jax.tree_util.tree_leaves(t):
            assert not isinstance(leaf, jax.Array)
    trainm = steps.build_train_step(run, mesh)
    bm = steps.shard_batch(batch_h, mesh)
    shs = steps.state_shardings(run, mesh, st)
    fnm = jax.jit(functools.partial(trainm, phase=1),
                  in_shardings=(shs, steps.batch_shardings(bm, mesh)),
                  out_shardings=(shs, None))
    _, mB = fnm(st, bm)
    loss_b = float(mB["loss"])
    assert abs(loss_a - loss_b) <= 1e-5, (tag, loss_a, loss_b)
    if tag == "8dev":
        n_dev = {{len(l.sharding.device_set)
                 for l in jax.tree_util.tree_leaves(st.trainable)}}
        assert n_dev == {{8}}, n_dev
    # a stale/wrong manifest map must fail fast, not as a late jit error
    wrong = dict(rank_map); wrong[next(iter(wrong))] += 1
    try:
        unpack_phased_state(saved, 1, expect_rank_map=wrong)
    except ValueError as e:
        assert "rank" in str(e)
    else:
        raise AssertionError("wrong rank map did not raise")
print("RANK_ELASTIC_OK", loss_a)
"""
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=900)
    assert "RANK_ELASTIC_OK" in out.stdout, (
        out.stdout[-2000:] + "\n--- stderr ---\n" + out.stderr[-3000:])
