"""Shard-scaling bench (DESIGN.md §9): per-freeze-phase train-step walltime
and per-device collective bytes vs device count.

Runs the smoke LM's sharded train step over a ladder of host-mesh shapes —
(1,1), (2,1), (4,1), (8,1) data-parallel plus a (4,2) TP cell — for both
SEQUENTIAL freezing phases (and the no-freeze baseline at the ladder ends),
with the state placed exactly as the production driver places it
(``steps.make_sharded_train_state``: trainable sharded, frozen replicated
over DP, donated in/out shardings).  Per cell it records wall-clock per
step and the compiled step's per-device collective traffic by class
(``analysis.hlo``) — the structural claim under test: during any frozen
phase the factor group's gradient all-reduce AND storage all-gather bytes
are absent, so collective bytes at phase 0/1 sit strictly below the
no-freeze row of the same mesh.

Needs >= 8 devices and runs in-process when it has them; on a CPU with
fewer (the usual case) it re-execs itself under a forced 8-device host
platform (``benchmarks.common.rows_on_devices``).  Param layout is
TP/no-FSDP + ZeRO rank-dim storage sharding, the layout whose collective
schedule is tabulated in §9.

  PYTHONPATH=src python -m benchmarks.shard_scaling [--record] [--iters N]
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

ARCH = "smollm-360m"
MESHES = ((1, 1), (2, 1), (4, 1), (8, 1), (4, 2))  # (data, model)
NEEDED_DEVICES = 8


def _build_run(seq=64, batch=8):
    from repro.configs import get_smoke_config
    from repro.configs.base import (DistConfig, LRDConfig, OptimConfig,
                                    RunConfig, ShapeConfig)
    return RunConfig(
        model=get_smoke_config(ARCH),
        shape=ShapeConfig("b", seq, batch, "train"),
        lrd=LRDConfig(enabled=True, min_dim=16, rank_quantize=False,
                      freeze_mode="sequential"),
        dist=DistConfig(fsdp=False, remat="none", microbatches=1),
        optim=OptimConfig(name="adamw", lr=1e-3, warmup_steps=0,
                          total_steps=100))


def _run(iters: int):
    import jax

    from benchmarks.common import time_fn
    from repro.analysis.hlo import analyze_hlo
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh

    run = _build_run()
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    # host copy: cells DONATE their placed state, and device_put with an
    # unchanged sharding aliases rather than copies — placing from numpy
    # keeps the master weights alive across cells
    params = jax.tree_util.tree_map(lambda x: jax.device_get(x), params)
    key = jax.random.PRNGKey(1)
    batch_h = {
        "tokens": jax.device_get(jax.random.randint(
            key, (run.shape.global_batch, run.shape.seq_len), 0,
            run.model.vocab_size)),
        "labels": jax.device_get(jax.random.randint(
            key, (run.shape.global_batch, run.shape.seq_len), 0,
            run.model.vocab_size)),
    }

    rows = []
    for data, model in MESHES:
        mesh = make_host_mesh(data, model)
        train = steps.build_train_step(run, mesh)
        phases = (0, 1) if (data, model) not in ((1, 1), (8, 1)) \
            else (-1, 0, 1)
        for phase in phases:
            state, _ = steps.make_sharded_train_state(run, params, phase,
                                                      mesh)
            batch = steps.shard_batch(batch_h, mesh)
            compiled = steps.compile_train_step(run, mesh, train, state,
                                                batch, phase)
            coll = {k: int(v) for k, v in
                    analyze_hlo(compiled.as_text()).collective_bytes.items()}

            # time the AOT executable directly — a jit call would recompile
            # (the jit call cache is separate from lower().compile()) and
            # donation threads the state through the loop
            carry = {"state": state}

            def one_step():
                carry["state"], m = compiled(carry["state"], batch)
                return m["loss"]

            t = time_fn(one_step, iters=iters, warmup=1)
            rows.append({
                "arch": ARCH, "devices": data * model,
                "data": data, "model": model, "phase": phase,
                "us_per_step": t * 1e6,
                "collective_bytes": coll,
                "collective_total_bytes": sum(coll.values()),
            })
    return rows


def _print(rows):
    print("# shard scaling: mesh(data,model)/phase, us_per_step, "
          "collective bytes/device (by class)")
    for r in rows:
        cls = " ".join(f"{k}={v}" for k, v in
                       sorted(r["collective_bytes"].items())) or "none"
        print(f"({r['data']},{r['model']})/phase{r['phase']},"
              f"{r['us_per_step']:.0f},"
              f"total={r['collective_total_bytes']}B ({cls})")


def main(iters: int = 3, record: bool = False):
    from benchmarks.common import rows_on_devices

    rows = rows_on_devices(NEEDED_DEVICES, lambda: _run(iters),
                           "benchmarks.shard_scaling",
                           ("--child", "--iters", str(iters)))
    _print(rows)
    if record:
        from benchmarks.common import record as record_rows
        print(f"[recorded {record_rows('shard_scaling', rows)}]")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--record", action="store_true",
                    help="write benchmarks/results/BENCH_shard_scaling.json")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--json-out", default="", help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.child:
        rows = _run(a.iters)
        if a.json_out:
            Path(a.json_out).write_text(json.dumps(rows))
        _print(rows)
    else:
        main(iters=a.iters, record=a.record)
