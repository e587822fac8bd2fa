"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), record memory/cost analysis and
the post-SPMD HLO for the roofline harness.

A CPU-only analysis over 512 fake host devices: ``main`` pins the process
(and every ``--all`` child) to ``JAX_PLATFORMS=cpu`` with a forced
512-device host platform before jax first initializes — jax locks the
device count then — so a dry-run never takes an accelerator.  One cell per
process invocation keeps compile state isolated; ``--all`` orchestrates
subprocesses with a JSON result cache so a failed cell never loses prior
progress.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--out runs/dryrun]
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
from pathlib import Path

DEFAULT_OUT = Path("runs/dryrun")

#: the environment every dry-run process runs under
HOST_PLATFORM_ENV = {"JAX_PLATFORMS": "cpu",
                     "XLA_FLAGS": "--xla_force_host_platform_device_count=512"}


def build_cell(arch: str, shape_name: str, *, multi_pod: bool, lrd: bool,
               freeze: bool, fsdp: bool = True, remat: str = "sqrt",
               microbatches: int = 0, grad_compression: str = "none",
               param_layout: str = "fsdp", capacity_factor: float = 0.0,
               attn_blocks: str = "", kv_int8: bool = False,
               rank_schedule: str = "none", rank_decay: float = 0.75):
    """Build (fn, args, mesh, run) for one dry-run cell."""
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config, skip_reason
    from repro.configs.base import DistConfig, LRDConfig, OptimConfig, RunConfig
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh

    reason = skip_reason(arch, shape_name)
    if reason:
        raise SystemExit(f"SKIP: {reason}")

    cfg = get_config(arch)
    if capacity_factor:
        cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    if attn_blocks:
        bq, bkv = (int(x) for x in attn_blocks.split(","))
        cfg = dataclasses.replace(cfg, attention_block_q=bq, attention_block_kv=bkv)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    shape = SHAPES[shape_name]
    if microbatches == 0:  # auto: keep the remat stash (L*tokens*d/dev) ~<2GiB
        microbatches = 1
        if shape.kind == "train":
            dp = 32 if multi_pod else 16  # batch-sharding ways (pod x data)
            stash_per_dev = (cfg.num_layers * (shape.global_batch / dp)
                             * shape.seq_len * cfg.d_model * 2)
            while (stash_per_dev / microbatches > 2 * 2 ** 30
                   and shape.global_batch % (microbatches * 2 * dp) == 0):
                microbatches *= 2
    run = RunConfig(
        model=cfg,
        shape=shape,
        lrd=LRDConfig(enabled=lrd, alpha=2.0, rank_quantize=True,
                      freeze_mode="sequential" if freeze else "none",
                      rank_schedule=rank_schedule, rank_decay=rank_decay),
        dist=DistConfig(param_layout=param_layout,
                        fsdp=fsdp, remat=remat if shape.kind == "train" else "none",
                        # decode: shard the KV cache sequence over the model
                        # axis (flash-decode style) — kv_heads rarely divide
                        # the 16-way model axis, and a 32k cache at batch 128
                        # is 1.4 TB for qwen2-72b.
                        sequence_parallel=(shape.kind == "decode"),
                        microbatches=microbatches,
                        grad_compression=grad_compression,
                        accum_dtype="bfloat16" if cfg.num_params() > 100e9
                        else "float32"),
        optim=OptimConfig(
            name="adamw" if cfg.num_params() > 5e9 else "sgdm",
            # >100B params: bf16 moments, the standard HBM trick (8-bit Adam
            # territory) — fp32 m+v alone would be 10.5 GiB/chip for 340B.
            state_dtype="bfloat16" if cfg.num_params() > 100e9 else "float32"),
    )
    mesh = make_production_mesh(multi_pod=multi_pod)

    if shape.kind == "train":
        step = steps.build_train_step(run, mesh)
        phase = 0 if freeze else -1
        fn = functools.partial(step, phase=phase)
        # the abstract state is partitioned for the SAME static phase as the
        # step: the frozen partition has no opt/grad stand-ins at all, so
        # memory_analysis reports the structural freeze saving.
        args = (steps.abstract_state(run, mesh, phase=phase),
                steps.batch_specs(run, mesh))
        donate = (0,)  # donate TrainState: new params/opt alias the old buffers
    elif shape.kind == "prefill":
        fn = steps.build_prefill_step(run, mesh)
        args = (steps.abstract_params(run, mesh), steps.batch_specs(run, mesh))
        donate = ()
    else:  # decode
        fn = steps.build_serve_step(run, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed.sharding import ACT_RULES, _resolve_spec
        b = shape.global_batch
        tok_spec = _resolve_spec((b, 1), ("batch", None), ACT_RULES, mesh)
        token = jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                     sharding=NamedSharding(mesh, tok_spec))
        pos = jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        args = (steps.abstract_params(run, mesh), steps.abstract_cache(run, mesh),
                token, pos, steps.decode_extras_specs(run, mesh))
        donate = (1,)  # donate the KV cache: updated in place
    return fn, args, mesh, run, donate


def rank_adaptation_trajectory(run, mesh, boundaries: int) -> list:
    """Per-boundary STRUCTURAL byte accounting of an in-training rank
    schedule (DESIGN.md §10): live trainable/frozen/opt stand-in bytes of
    the abstract state after each phase swap, under the decay trajectory
    (``rank_adapt.decay_rank_maps`` — the energy policy has no analytic
    trajectory and is priced with the same decay estimate).  No allocation,
    no compile: pure eval_shape arithmetic, so every cell can afford it.
    """
    import jax
    import numpy as np

    from repro.core import rank_adapt
    from repro.launch import steps

    def tree_bytes(tree):
        return sum(int(np.prod(l.shape)) * jnp_itemsize(l.dtype)
                   for l in jax.tree_util.tree_leaves(tree))

    def jnp_itemsize(dtype):
        return np.dtype(dtype).itemsize

    schedule = rank_adapt.schedule_from_config(run.lrd)
    shapes = jax.eval_shape(lambda: steps.init_params(run)[0])
    maps = [None] + rank_adapt.decay_rank_maps(shapes, schedule, boundaries)
    rows = []
    for b, rmap in enumerate(maps):
        phase = b % 2  # sequential alternation starts at phase 0
        a = steps.abstract_state(run, mesh, phase=phase, rank_map=rmap)
        opt_bytes = tree_bytes(a.opt.mu) + (
            tree_bytes(a.opt.nu) if a.opt.nu != () else 0)
        rmap_now = rmap if rmap is not None else rank_adapt.live_rank_map(shapes)
        rows.append({
            "boundary": b,
            "phase": phase,
            "total_rank": int(sum(rmap_now.values())),
            "trainable_param_bytes": tree_bytes(a.trainable),
            "frozen_param_bytes": tree_bytes(a.frozen),
            "opt_bytes": opt_bytes,
            "trainable_partition_bytes": tree_bytes(a.trainable) + opt_bytes,
        })
    return rows


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, lrd: bool = True,
             freeze: bool = True, out_dir: Path = DEFAULT_OUT, tag: str = "",
             save_hlo: bool = True, rank_boundaries: int = 4,
             **build_kw) -> dict:
    import jax

    t0 = time.time()
    fn, args, mesh, run, donate = build_cell(arch, shape_name, multi_pod=multi_pod,
                                             lrd=lrd, freeze=freeze, **build_kw)
    t_build = time.time() - t0

    t0 = time.time()
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per device
        cost = cost[0] if cost else {}
    n_dev = mesh.devices.size
    mesh_tag = "multipod" if multi_pod else "singlepod"
    variant = ("lrd" if lrd else "dense") + (tag and f"-{tag}" or "")
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag, "variant": variant,
        "devices": int(n_dev),
        "status": "ok",
        "seconds": {"build": round(t_build, 2), "lower": round(t_lower, 2),
                    "compile": round(t_compile, 2)},
        "memory_per_device": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "total_bytes": int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                               + mem.output_size_in_bytes),
        },
        "cost_analysis_per_device": {
            "flops": float(cost.get("flops", -1.0)),
            "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
            "transcendentals": float(cost.get("transcendentals", -1.0)),
        },
    }
    if (run.lrd.rank_schedule != "none" and run.shape.kind == "train"
            and rank_boundaries > 0):
        result["rank_adaptation"] = rank_adaptation_trajectory(
            run, mesh, rank_boundaries)

    out_dir.mkdir(parents=True, exist_ok=True)
    stem = f"{arch}__{shape_name}__{mesh_tag}__{variant}"
    if save_hlo:
        hlo_text = compiled.as_text()
        hlo_path = out_dir / f"{stem}.hlo.txt"
        hlo_path.write_text(hlo_text)
        result["hlo_path"] = str(hlo_path)
        # per-device collective traffic of one step, by class — the number
        # the shard-scaling bench tracks vs device count, and the one the
        # frozen-factor zero-traffic contract (DESIGN.md §9) is audited on
        from repro.analysis.hlo import analyze_hlo
        result["collective_bytes_per_device"] = {
            k: int(v)
            for k, v in analyze_hlo(hlo_text).collective_bytes.items()}
    (out_dir / f"{stem}.json").write_text(json.dumps(result, indent=1))
    return result


def all_cells():
    from repro.configs import SHAPES, get_config, skip_reason
    from repro.configs.archs import ARCHS
    for arch in ARCHS:
        for shape in SHAPES:
            yield arch, shape, skip_reason(arch, shape)


def orchestrate(out_dir: Path, *, multi_pod_list=(False, True), lrd: bool = True,
                force: bool = False, timeout_s: int = 2400):
    """Subprocess-per-cell driver with a resume cache."""
    import subprocess

    results = []
    for arch, shape, reason in all_cells():
        for mp in multi_pod_list:
            mesh_tag = "multipod" if mp else "singlepod"
            variant = "lrd" if lrd else "dense"
            stem = f"{arch}__{shape}__{mesh_tag}__{variant}"
            cache = out_dir / f"{stem}.json"
            if reason:
                out_dir.mkdir(parents=True, exist_ok=True)
                rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                       "variant": variant, "status": "skip", "reason": reason}
                cache.write_text(json.dumps(rec, indent=1))
                results.append(rec)
                continue
            if cache.exists() and not force:
                rec = json.loads(cache.read_text())
                if rec.get("status") == "ok":
                    results.append(rec)
                    print(f"[cache] {stem}")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--out", str(out_dir)]
            if mp:
                cmd.append("--multi-pod")
            if not lrd:
                cmd.append("--dense")
            print(f"[run  ] {stem} ...", flush=True)
            t0 = time.time()
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=timeout_s,
                                  env=dict(os.environ, **HOST_PLATFORM_ENV))
            dt = time.time() - t0
            if proc.returncode != 0:
                rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                       "variant": variant, "status": "fail",
                       "stderr": proc.stderr[-4000:], "seconds": round(dt, 1)}
                cache.write_text(json.dumps(rec, indent=1))
                print(f"[FAIL ] {stem} ({dt:.0f}s)\n{proc.stderr[-1500:]}")
            else:
                rec = json.loads(cache.read_text())
                print(f"[ok   ] {stem} ({dt:.0f}s)")
            results.append(rec)
    ok = sum(1 for r in results if r["status"] == "ok")
    skip = sum(1 for r in results if r["status"] == "skip")
    fail = sum(1 for r in results if r["status"] == "fail")
    print(f"\ndry-run complete: {ok} ok, {skip} skip, {fail} fail "
          f"/ {len(results)} cells")
    return results


def main():
    # before jax first initializes: this module imports jax only lazily
    os.environ.update(HOST_PLATFORM_ENV)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dense", action="store_true", help="disable LRD (baseline)")
    ap.add_argument("--no-freeze", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--remat", default="sqrt",
                    choices=["none", "full", "dots", "sqrt"])
    ap.add_argument("--microbatches", type=int, default=0, help="0 = auto")
    ap.add_argument("--grad-compression", default="none", choices=["none", "int8"])
    ap.add_argument("--param-layout", default="fsdp", choices=["fsdp", "zero1"])
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--attn-blocks", default="", help="bq,bkv override")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--rank-schedule", default="none",
                    choices=["none", "decay", "energy"],
                    help="price an in-training rank schedule (per-boundary "
                         "shrinking-bytes trajectory in the cell JSON)")
    ap.add_argument("--rank-decay", type=float, default=0.75)
    ap.add_argument("--rank-boundaries", type=int, default=4,
                    help="phase swaps to price in the trajectory")
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    args = ap.parse_args()

    out = Path(args.out)
    if args.all:
        orchestrate(out, lrd=not args.dense, force=args.force)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    try:
        res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                       lrd=not args.dense, freeze=not args.no_freeze,
                       out_dir=out, tag=args.tag, fsdp=not args.no_fsdp,
                       remat=args.remat, microbatches=args.microbatches,
                       grad_compression=args.grad_compression,
                       param_layout=args.param_layout,
                       capacity_factor=args.capacity_factor,
                       attn_blocks=args.attn_blocks, kv_int8=args.kv_int8,
                       rank_schedule=args.rank_schedule,
                       rank_decay=args.rank_decay,
                       rank_boundaries=args.rank_boundaries)
    except SystemExit as e:
        print(e)
        return
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    mem = res["memory_per_device"]
    print(json.dumps(res, indent=1))
    print(f"\n{res['arch']} {res['shape']} {res['mesh']} [{res['variant']}]: "
          f"per-device {mem['total_bytes']/2**30:.2f} GiB "
          f"(args {mem['argument_bytes']/2**30:.2f} + temp {mem['temp_bytes']/2**30:.2f}), "
          f"flops/dev {res['cost_analysis_per_device']['flops']:.3e}")


if __name__ == "__main__":
    main()
