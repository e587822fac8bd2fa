"""Production training driver — MESH-NATIVE (DESIGN.md §5/§9).

The loop is sharded end to end: it builds a ``(data, model)`` mesh over the
available devices (``--mesh-data/--mesh-model``; ``--mesh production`` for
the (16,16) / (2,16,16) pod meshes), places the partitioned TrainState via
``steps.make_sharded_train_state`` (trainable: FSDP/TP layout; frozen:
replicated-over-DP ``FROZEN_PARAM_RULES``; opt over the trainable partition
only), and jits the train step with explicit in/out shardings and a DONATED
state, so the updated state aliases the old buffers in place.  Batches are
device_put per step with their DP sharding.  After the first step the loop
asserts the placement contract (``steps.check_state_placement``).

Fault tolerance: auto-resume from the newest complete checkpoint (params,
optimizer, data-iterator state, freeze phase), atomic saves, SIGTERM =>
checkpoint-then-exit (preemption), straggler detection via per-step timing
EMA.  Elastic: checkpoints are mesh-agnostic, the manifest records the
source mesh for provenance, and restore device_puts every leaf under the
CURRENT mesh's shardings (``steps.packed_state_shardings``) — restarting
with a different device count or mesh shape re-shards on load
(tests/test_sharded_train.py round-trips 1-device -> 8-device).

Sequential freezing (paper Algorithm 2) drives a *static* phase argument:
one compiled step per phase, swapped per epoch.  The train state is
PARTITIONED per phase (DESIGN.md §7): at every phase boundary the loop
re-partitions params host-side and rotates the parked optimizer-moment
slices — shard-aware: only the leaves whose factor group swapped are
re-placed (``steps.repartition_state(mesh=...)``), so unfreezing never
resets momentum and a phase swap never reshards the rest of the state.

Usage (CPU demo; multi-device via the README "Multi-device training"
recipe, XLA_FLAGS=--xla_force_host_platform_device_count=8):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 200 --global-batch 8 --seq-len 128 --lrd --freeze sequential \
      [--mesh-data 4 --mesh-model 2]
"""

from __future__ import annotations

import argparse
import contextlib
import time
from pathlib import Path

import jax
import numpy as np

from repro.analysis import hlo as hlo_mod
from repro.checkpoint import (CheckpointManager, pack_phased_state,
                              unpack_phased_state)
from repro.core import rank_adapt
from repro.configs import SHAPES, get_config, get_smoke_config
from repro.configs.base import (DistConfig, LRDConfig, ObsConfig, OptimConfig,
                                RunConfig, ShapeConfig)
from repro.data import LMBatchIterator
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.obs import EventLog, install_host_hooks, uninstall_host_hooks
from repro.optim.optimizers import OptState


class StragglerMonitor:
    """Flags steps slower than ``factor`` x the running median step time.

    On a real multi-host deployment each host reports its step time into this
    monitor (via the coordination service); the launcher re-slices around
    hosts that stay flagged.  Single-process mode exercises the same logic.
    """

    def __init__(self, factor: float = 2.0, window: int = 32):
        self.factor = factor
        self.times: list = []
        self.window = window
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        self.times.append(dt)
        self.times = self.times[-self.window:]
        if len(self.times) < 8:
            return False
        med = float(np.median(self.times))
        if dt > self.factor * med:
            self.flagged += 1
            return True
        return False


def _parse_profile_steps(spec: str):
    """``"START:STOP"`` -> (start, stop) step indices, or (-1, -1)."""
    if not spec:
        return -1, -1
    try:
        a, b = spec.split(":")
        start, stop = int(a), int(b)
    except ValueError:
        raise SystemExit(f"--profile-steps expects START:STOP, got {spec!r}")
    if start < 0 or stop <= start:
        raise SystemExit(f"--profile-steps needs 0 <= START < STOP, got {spec!r}")
    return start, stop


def build_run(args) -> RunConfig:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeConfig("custom", args.seq_len, args.global_batch, "train")
    prof_start, prof_stop = _parse_profile_steps(args.profile_steps)
    return RunConfig(
        model=cfg,
        shape=shape,
        lrd=LRDConfig(enabled=args.lrd, alpha=args.alpha,
                      rank_quantize=not args.no_rank_opt,
                      freeze_mode=args.freeze, min_dim=args.lrd_min_dim,
                      epochs_per_phase=args.epochs_per_phase,
                      use_pallas_kernel=args.use_pallas,
                      pallas_interpret=args.pallas_interpret,
                      rank_schedule=args.rank_schedule,
                      rank_decay=args.rank_decay,
                      rank_energy_threshold=args.rank_energy,
                      rank_min=args.rank_min),
        dist=DistConfig(fsdp=args.fsdp, remat=args.remat,
                        microbatches=args.microbatches,
                        grad_compression=args.grad_compression),
        optim=OptimConfig(name=args.optimizer, lr=args.lr,
                          warmup_steps=args.warmup,
                          total_steps=args.steps),
        obs=ObsConfig(enabled=args.obs, run_dir=args.obs_dir,
                      log_format=args.log_format,
                      step_every=args.obs_step_every,
                      profile_start=prof_start, profile_stop=prof_stop),
        seed=args.seed,
    )


def build_parser() -> argparse.ArgumentParser:
    """The driver's command line; ``build_run(build_parser().parse_args(
    argv))`` is the RunConfig a ``train.py argv`` run trains with."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--steps-per-epoch", type=int, default=25)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lrd", action="store_true")
    ap.add_argument("--alpha", type=float, default=2.0)
    ap.add_argument("--no-rank-opt", action="store_true")
    ap.add_argument("--lrd-min-dim", type=int, default=128)
    ap.add_argument("--freeze", default="none",
                    choices=["none", "regular", "sequential"])
    ap.add_argument("--epochs-per-phase", type=int, default=1,
                    help="Algorithm-2 alternation cadence (sequential)")
    ap.add_argument("--rank-schedule", default="none",
                    choices=["none", "decay", "energy"],
                    help="in-training rank adaptation at phase boundaries "
                         "(DESIGN.md §10; needs --freeze sequential)")
    ap.add_argument("--rank-decay", type=float, default=0.75,
                    help="per-boundary rank multiplier (decay policy)")
    ap.add_argument("--rank-energy", type=float, default=0.98,
                    help="kept singular-value mass (energy policy)")
    ap.add_argument("--rank-min", type=int, default=2,
                    help="scheduled ranks never drop below this")
    ap.add_argument("--use-pallas", action="store_true",
                    help="fused low-rank kernels, fwd+bwd (TPU; with "
                         "--pallas-interpret also CPU validation)")
    ap.add_argument("--pallas-interpret", action="store_true",
                    help="run Pallas kernels in interpret mode")
    ap.add_argument("--optimizer", default="sgdm", choices=["sgdm", "adamw"])
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots", "sqrt"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none", choices=["none", "int8"])
    ap.add_argument("--mesh", default="host", choices=["host", "production"])
    ap.add_argument("--mesh-data", type=int, default=0,
                    help="host-mesh data-parallel ways (0 = all devices)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="host-mesh model-parallel (TP) ways")
    ap.add_argument("--ckpt-dir", default="runs/train_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs", action="store_true",
                    help="write schema-versioned telemetry JSONL "
                         "(events.jsonl in --obs-dir; DESIGN.md §12)")
    ap.add_argument("--obs-dir", default="",
                    help="telemetry directory (default: the run's "
                         "checkpoint directory)")
    ap.add_argument("--log-format", default="text",
                    choices=["text", "jsonl"],
                    help="console mirror: legacy text lines (default) or "
                         "the raw JSONL events")
    ap.add_argument("--obs-step-every", type=int, default=1,
                    help="emit a train_step record every N steps")
    ap.add_argument("--profile-steps", default="",
                    help="START:STOP — jax.profiler trace window over "
                         "these steps (saved under the obs dir)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    enable_compile_cache()
    run = build_run(args)
    if args.mesh == "production":
        mesh = make_production_mesh()
    else:
        data_ways = args.mesh_data or max(
            len(jax.devices()) // args.mesh_model, 1)
        mesh = make_host_mesh(data_ways, args.mesh_model)
    print(f"[mesh] {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} device(s))")

    # telemetry: one events.jsonl per run when --obs; the console mirror
    # renders the legacy [phase]/[rank-adapt]/[straggler]/[resume]/per-step
    # lines either way, so disabling telemetry changes no console output
    obs_dir = Path(run.obs.run_dir) if run.obs.run_dir else (
        Path(args.ckpt_dir) / f"{run.model.name}")
    if run.obs.enabled:
        obs_dir.mkdir(parents=True, exist_ok=True)
    obs = EventLog(obs_dir / "events.jsonl" if run.obs.enabled else None,
                   mirror=print, fmt=run.obs.log_format)
    # garbage-collection spans and counters only while tracing is wanted
    host_hooks = run.obs.enabled or run.obs.profile_start >= 0
    if host_hooks:
        install_host_hooks()

    params, plan = steps_mod.init_params(run)
    if run.lrd.enabled:
        print(plan.summary())
    schedule = rank_adapt.schedule_from_config(run.lrd)
    if schedule.active and run.lrd.freeze_mode != "sequential":
        print("[rank-adapt] --rank-schedule set but freezing is not "
              "sequential: no phase boundaries, schedule never fires")

    def phase_at(step: int) -> int:
        return steps_mod.run_phase(run, step // args.steps_per_epoch)

    cur_phase = phase_at(0)
    # placement: trainable sharded per the run's layout, frozen replicated
    # over DP, opt over the trainable partition, parked moments on host
    state, parked = steps_mod.make_sharded_train_state(run, params,
                                                       cur_phase, mesh)

    data = LMBatchIterator(run.model.vocab_size, run.shape.seq_len,
                           run.shape.global_batch, seed=args.seed + 17)

    mesh_info = {"axes": list(mesh.axis_names),
                 "shape": [int(s) for s in mesh.devices.shape]}
    ckpt = CheckpointManager(Path(args.ckpt_dir) / f"{run.model.name}", keep=3,
                             save_every=args.save_every)
    ckpt.install_sigterm_handler()
    start_step = 0
    restored = None
    if ckpt.latest_step() is not None:
        # elastic resume: the checkpoint is mesh-agnostic; place every leaf
        # directly under the CURRENT mesh's shardings (parked moment slices
        # carry no sharding and stay host numpy).  The saved rank map
        # rebuilds target shardings at the checkpoint's possibly-truncated,
        # non-uniform ranks (DESIGN.md §10).
        peeked = ckpt.peek_extra()
        saved_phase = int(peeked.get("phase", -1))
        saved_ranks = peeked.get("rank_map")
        restored = ckpt.restore(
            shardings=steps_mod.packed_state_shardings(
                run, mesh, saved_phase, rank_map=saved_ranks))
    if restored is not None:
        saved_state, start_step, extra = restored
        cur_phase = int(extra.get("phase", -1))
        (tr, fr, opt_r), parked_h = unpack_phased_state(
            saved_state, cur_phase, expect_rank_map=extra.get("rank_map"))
        state = steps_mod.TrainState(tr, fr, OptState(*opt_r))
        parked = tuple(jax.tree_util.tree_map(np.asarray, t) for t in parked_h)
        data.load_state_dict(extra["data"])
        src = extra.get("mesh", {})
        obs.emit("resume", step=start_step, phase=cur_phase,
                 src_mesh=src.get("shape", "?"), mesh=mesh_info["shape"])

    obs.emit("run_start", _mirror=False, kind="train", arch=run.model.name,
             steps=args.steps, steps_per_epoch=args.steps_per_epoch,
             start_step=start_step, mesh=mesh_info,
             freeze_mode=run.lrd.freeze_mode,
             rank_schedule=run.lrd.rank_schedule)

    train_step = steps_mod.build_train_step(run, mesh)
    step_fns = {}
    sync_cache = {}  # phase -> compiled step's cross-device sync bytes

    def fn_for(phase: int, batch):
        # one executable per phase, compiled ahead of time so the telemetry
        # layer can read the optimized HLO off the same executable the loop
        # runs (no second compile for the per-phase sync-bytes attribution)
        if phase not in step_fns:
            compiled = steps_mod.compile_train_step(run, mesh, train_step,
                                                    state, batch, phase)
            step_fns[phase] = compiled
            if run.obs.enabled:
                total, per = ((0, {}) if mesh.devices.size <= 1 else
                              hlo_mod.sync_bytes(compiled.as_text()))
                sync_cache[phase] = total
                obs.emit("phase_compile", _mirror=False, phase=phase,
                         sync_bytes_per_step=total, collectives=per)
        return step_fns[phase]

    monitor = StragglerMonitor()
    it = iter(data)
    losses = []
    # per-phase-segment facts attached to every train_step record; all
    # three only change at a phase swap, so they are cached, not recomputed
    # per step (the enabled path must stay cheap, the disabled path free)
    cur_ranks = rank_adapt.live_rank_map(state.params)
    part_bytes = steps_mod.partition_bytes(state)
    tokens_per_step = run.shape.global_batch * run.shape.seq_len
    profiling = False
    prof_dir = str(obs_dir / "profile")

    def end_profile(stop_step: int) -> None:
        nonlocal profiling
        jax.profiler.stop_trace()
        profiling = False
        obs.emit("profile_window", start_step=run.obs.profile_start,
                 stop_step=stop_step, trace_dir=prof_dir)

    def finish() -> None:
        if host_hooks:
            uninstall_host_hooks()
        obs.close()

    for step in range(start_step, args.steps):
        epoch = step // args.steps_per_epoch
        phase = phase_at(step)
        if phase != cur_phase:
            # Algorithm-2 phase swap: repartition params and rotate the
            # parked optimizer moments (host-side; only the swapped factor
            # group's leaves are re-placed — DESIGN.md §9).  With an active
            # rank schedule the same swap truncates scheduled factor groups
            # and slices their moments (DESIGN.md §10).
            boundary = epoch // max(args.epochs_per_phase, 1)
            ranks_before = cur_ranks
            with obs.span("phase_swap", epoch=epoch, phase=phase,
                          boundary=boundary):
                state, parked = steps_mod.repartition_state(
                    run.optim, state, parked, phase, mesh=mesh, run=run,
                    schedule=schedule if schedule.active else None,
                    boundary=boundary)
            cur_phase = phase
            cur_ranks = rank_adapt.live_rank_map(state.params)
            part_bytes = steps_mod.partition_bytes(state)
            if cur_ranks != ranks_before:
                # shapes changed: every cached executable (and its
                # in_shardings, resolved against the OLD shapes) is stale
                step_fns.clear()
                sync_cache.clear()
                shrunk = {p: f"{ranks_before[p]}->{r}"
                          for p, r in cur_ranks.items()
                          if r != ranks_before[p]}
                obs.emit("rank_adapt", epoch=epoch, boundary=boundary,
                         shrunk=shrunk, rank_map=cur_ranks)
        if run.obs.profile_start == step and not profiling:
            # a requested trace window that cannot start is an error: a
            # run without its trace is not the run that was asked for
            jax.profiler.start_trace(prof_dir)
            profiling = True
        # inside a trace window each step is one profiler step
        with (jax.profiler.StepTraceAnnotation("train", step_num=step)
              if profiling else contextlib.nullcontext()):
            batch = steps_mod.shard_batch(next(it), mesh)
            t0 = time.perf_counter()
            state, metrics = fn_for(phase, batch)(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
        losses.append(loss)
        if profiling and step + 1 >= run.obs.profile_stop:
            end_profile(step + 1)
        if step == start_step:
            steps_mod.check_state_placement(run, mesh, state)
        if monitor.observe(dt):
            obs.emit("straggler", step=step, step_time_s=dt,
                     median_s=float(np.median(monitor.times)))
        record = run.obs.enabled and step % max(run.obs.step_every, 1) == 0
        mirror = step % args.log_every == 0 or step == args.steps - 1
        if record or mirror:
            obs.emit("train_step", _mirror=mirror, step=step, epoch=epoch,
                     phase=phase, loss=loss,
                     grad_norm=float(metrics["grad_norm"]),
                     step_time_s=dt, tokens_per_s=tokens_per_step / dt,
                     total_rank=sum(cur_ranks.values()), rank_map=cur_ranks,
                     sync_bytes_per_step=sync_cache.get(phase, 0),
                     **part_bytes)
        if ckpt.due(step + 1) and ckpt.maybe_save(
                step + 1, pack_phased_state(state, parked),
                extra={"data": data.state_dict(), "phase": phase,
                       "mesh": mesh_info,
                       "rank_map": rank_adapt.live_rank_map(state.params)}):
            if ckpt.preempted:
                print(f"[preempt] checkpointed at step {step + 1}, exiting")
                if profiling:  # the open trace window is saved, not lost
                    end_profile(step + 1)
                obs.emit("run_end", _mirror=False, kind="train",
                         reason="preempt", final_step=step + 1)
                finish()
                return state, losses
    if profiling:
        end_profile(args.steps)
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    obs.emit("run_end", _mirror=False, kind="train", reason="complete",
             final_step=args.steps,
             final_loss=losses[-1] if losses else 0.0)
    finish()
    return state, losses


if __name__ == "__main__":
    main()
