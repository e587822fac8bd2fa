"""Smoke run of the main path on a TPU, at smollm-360m's published widths.

Trains smollm-360m (32 layers, d_model 960, 15/5 heads, d_ff 2560, vocab
49152; random weights from ``launch/train.py``'s default seed) as the
low-rank-decomposed model the train driver builds with ``--lrd --freeze
sequential --use-pallas``: rank quantization on, the fused Pallas low-rank
kernels forward and backward.
A few optimizer steps run with the u factors frozen, then the Algorithm-2
phase swap (``steps.repartition_state``), then a few with the v factors
frozen.  The trained params then serve a handful of requests through
``ServeEngine`` (paged KV cache, continuous batching).

The run fails — non-zero exit, no result line — when JAX finds no TPU
(there is no CPU fallback), when any phase raises, when a loss is not
finite, when the train phase took any jnp fallback instead of a kernel, or
when serving drops a request or compiles decode more than once.  The last
line of stdout is the JSON result.  Step times are a smoke reading, not a
benchmark.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded train + TP serve vs one chip

``--chips 4`` runs only the multi-chip path, in this one process: train
steps in both phases on a (data=2, model=2) mesh with the kernels under
shard_map, the same steps on one chip, and a model=2 tensor-parallel serve
of the same prompts, compared for loss and greedy-token parity.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import RunConfig  # noqa: E402
from repro.data import LMBatchIterator  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch import train as train_driver  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.obs import default_registry  # noqa: E402
from repro.serving import ServeConfig, ServeEngine  # noqa: E402

ARCH = "smollm-360m"
STEPS_PER_PHASE = 4
# one chip's share of a training job: 8 sequences of 1024 tokens.  The
# compiled step's memory analysis for a v5e at these shapes is 0.75 GiB of
# arguments + 11.95 GiB of temporaries without remat, inside 16 GiB.
GLOBAL_BATCH, SEQ_LEN, REMAT = 8, 1024, "none"
SERVE_SLOTS, PROMPT_LEN, MAX_NEW, REQUESTS = 8, 128, 32, 12
# --chips 4: the sharded path and its one-chip reference run in float32 at
# cut depth, with float32 matmuls, so the loss comparison can hold the 1e-5
# bound that tests/test_sharded_train.py holds the elastic continuation
# to.  At the TPU's default precision a float32 matmul rounds its inputs to
# bfloat16, and a last-bit difference between two layouts flips some of
# those roundings: the one-chip and (2, 2) losses then differ by ~5e-5
# before the first update.  Depth is cut because the layers are one
# scanned program: 4 layers run every kernel shape, shard_map mapping and
# collective that 32 do, while four chips cost four times as much per
# second and float32 "highest" matmuls take several MXU passes each.
FOUR_CHIP_LAYERS = 4
LOSS_PARITY_TOL = 1e-5


class SmokeError(RuntimeError):
    """A phase ran but its result is wrong."""


def driver_run() -> RunConfig:
    """The RunConfig ``launch/train.py`` builds for this smoke run."""
    argv = ["--arch", ARCH, "--lrd", "--freeze", "sequential", "--use-pallas",
            "--global-batch", str(GLOBAL_BATCH), "--seq-len", str(SEQ_LEN),
            "--remat", REMAT, "--steps", str(2 * STEPS_PER_PHASE),
            "--warmup", "2"]
    return train_driver.build_run(train_driver.build_parser().parse_args(argv))


def kernel_fallbacks() -> dict:
    """Series of the ``kernel_fallbacks`` counter: ``{labels: count}``."""
    snap = default_registry().snapshot().get("kernel_fallbacks", {})
    return dict(snap.get("series", {}))


def _new_fallbacks(before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in kernel_fallbacks().items()
            if v != before.get(k, 0.0)}


def train_phase(run: RunConfig, mesh, params,
                steps_per_phase: int = STEPS_PER_PHASE) -> dict:
    """Sequential-freezing train steps: phase 0 (u frozen), the Algorithm-2
    swap, phase 1 (v frozen).  Raises :class:`SmokeError` on a non-finite
    loss.  Returns losses, compile and step seconds, the fallbacks the
    phase recorded, and the trained params."""
    before = kernel_fallbacks()
    train_step = steps.build_train_step(run, mesh)
    batches = iter(LMBatchIterator(run.model.vocab_size, run.shape.seq_len,
                                   run.shape.global_batch,
                                   seed=run.seed + 17))
    state, parked = steps.make_sharded_train_state(run, params, 0, mesh)
    losses, compile_s, step_s = {}, {}, []
    for phase in (0, 1):
        if phase:
            state, parked = steps.repartition_state(
                run.optim, state, parked, phase, mesh=mesh, run=run)
        batch = steps.shard_batch(next(batches), mesh)
        t0 = time.perf_counter()
        step = steps.compile_train_step(run, mesh, train_step, state, batch,
                                        phase)
        compile_s[phase] = time.perf_counter() - t0
        losses[phase] = []
        for i in range(steps_per_phase):
            if i:
                batch = steps.shard_batch(next(batches), mesh)
            t0 = time.perf_counter()
            state, metrics = jax.block_until_ready(step(state, batch))
            if i:  # the first call of an executable warms it up
                step_s.append(time.perf_counter() - t0)
            losses[phase].append(float(metrics["loss"]))
    bad = [l for ls in losses.values() for l in ls if not math.isfinite(l)]
    if bad:
        raise SmokeError(f"non-finite train loss: {losses}")
    return {"losses": losses, "compile_s": compile_s,
            "step_s": float(np.median(step_s)) if step_s else float("nan"),
            "fallbacks": _new_fallbacks(before), "params": state.params}


def serve_phase(run: RunConfig, params, *, mesh_model: int = 1,
                slots: int = SERVE_SLOTS, prompt_len: int = PROMPT_LEN,
                max_new: int = MAX_NEW, requests: int = REQUESTS) -> dict:
    """Serve ``requests`` greedy requests of 3/4..1x ``prompt_len`` tokens
    through the paged continuous-batching engine, after the first of them
    alone (which compiles the serving steps).  Raises
    :class:`SmokeError` unless every request completes with ``max_new``
    tokens and decode compiled exactly once."""
    before = kernel_fallbacks()
    engine = ServeEngine(run, params, config=ServeConfig(
        max_len=prompt_len + max_new, num_slots=slots,
        prefill_len=prompt_len, mesh_model=mesh_model))
    rng = np.random.default_rng(run.seed)
    lens = rng.integers(3 * prompt_len // 4, prompt_len + 1, requests)
    reqs = [{"prompt": rng.integers(0, run.model.vocab_size, int(n),
                                    dtype=np.int32), "max_new": max_new}
            for n in lens]
    t0 = time.perf_counter()
    engine.serve(reqs[:1])  # compiles the prefill, insert and decode steps
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = engine.serve(reqs)
    wall_s = time.perf_counter() - t0
    sched = engine.scheduler
    out = {"tokens": [r.tokens.tolist() for r in results],
           "layout": sched.layout, "first_s": first_s, "wall_s": wall_s,
           "generated": sum(r.token_count for r in results),
           "compiles": {"prefill": sched.prefill_compiles,
                        "insert": sched.insert_compiles,
                        "decode": sched.decode_compiles},
           "fallbacks": _new_fallbacks(before)}
    short = [r.rid for r in results if r.token_count != max_new]
    if len(results) != requests or short:
        raise SmokeError(f"serve: requests {short} did not complete "
                         f"({len(results)}/{requests} returned)")
    if sched.decode_compiles != 1:
        raise SmokeError(f"serve: decode compiled {sched.decode_compiles}x")
    return out


def _report_train(tag: str, run: RunConfig, t: dict) -> None:
    tokens = run.shape.global_batch * run.shape.seq_len
    for phase, ls in t["losses"].items():
        print(f"[{tag}] phase {phase} losses {ls}")
    print(f"[{tag}] compile s per phase {t['compile_s']}")
    print(f"[{tag}] steady step s {t['step_s']} ({tokens / t['step_s']:.0f} "
          f"tokens/s; smoke reading, not a benchmark)")
    print(f"[{tag}] kernel_fallbacks {t['fallbacks']}")


def _report_serve(tag: str, s: dict) -> None:
    print(f"[{tag}] first request {s['first_s']:.2f} s incl. compile of "
          f"prefill, insert and decode; then {len(s['tokens'])} requests, "
          f"{s['generated']} tokens in {s['wall_s']:.2f} s (smoke reading); "
          f"layout {s['layout']}, compiles {s['compiles']}")
    print(f"[{tag}] kernel_fallbacks {s['fallbacks']}")


def four_chip_run() -> RunConfig:
    """:func:`driver_run` cut to FOUR_CHIP_LAYERS layers, in float32."""
    base = driver_run()
    return dataclasses.replace(base, model=dataclasses.replace(
        base.model, num_layers=FOUR_CHIP_LAYERS, param_dtype="float32",
        compute_dtype="float32"))


def one_chip(run: RunConfig) -> None:
    mesh = make_host_mesh(1, 1)
    params, plan = steps.init_params(run)
    ranks = sorted({lp.rank for lp in plan.layers.values()
                    if lp.use_decomposed})
    print(f"[model] {run.model.name} {run.model.num_layers} layers, d_model "
          f"{run.model.d_model}, d_ff {run.model.d_ff}, vocab "
          f"{run.model.vocab_size}; LRD ranks {ranks}; batch "
          f"{run.shape.global_batch}x{run.shape.seq_len}, remat "
          f"{run.dist.remat}")
    t = train_phase(run, mesh, params)
    _report_train("train", run, t)
    if t["fallbacks"]:
        raise SmokeError(f"train phase took jnp fallbacks: {t['fallbacks']}")
    _report_serve("serve", serve_phase(run, t["params"]))


def four_chip(run: RunConfig) -> None:
    with jax.default_matmul_precision("highest"):
        _compare_four_chips(run)


def _compare_four_chips(run: RunConfig) -> None:
    # host copy: each train_phase donates the state it places
    params = jax.device_get(steps.init_params(run)[0])
    print(f"[model] {run.model.name} d_model {run.model.d_model}, "
          f"{run.model.num_layers} layers, {run.model.param_dtype}; batch "
          f"{run.shape.global_batch}x{run.shape.seq_len}")
    one = train_phase(run, make_host_mesh(1, 1), params)
    _report_train("train 1 chip", run, one)
    sharded = train_phase(run, make_host_mesh(2, 2), params)
    _report_train("train (data=2, model=2)", run, sharded)
    gap = max(abs(a - b) for p in (0, 1)
              for a, b in zip(one["losses"][p], sharded["losses"][p]))
    print(f"[parity] max |loss 1 chip - loss (2,2)| {gap:.3e} "
          f"(tol {LOSS_PARITY_TOL:.0e})")
    if one["fallbacks"] or sharded["fallbacks"]:
        raise SmokeError(f"train took jnp fallbacks: one chip "
                         f"{one['fallbacks']}, (2,2) {sharded['fallbacks']}")
    if not gap <= LOSS_PARITY_TOL:
        raise SmokeError(f"sharded train loss drifted {gap:.3e} from one chip")
    s1 = serve_phase(run, one["params"])
    _report_serve("serve 1 chip", s1)
    s2 = serve_phase(run, one["params"], mesh_model=2)
    _report_serve("serve model=2", s2)
    same = sum(a == b for a, b in zip(s1["tokens"], s2["tokens"]))
    print(f"[parity] greedy tokens equal in {same}/{len(s1['tokens'])} "
          f"requests")
    if same != len(s1["tokens"]):
        raise SmokeError("TP serve tokens differ from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train + TP serve comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); this "
              f"run has no CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"[device] platform {platform}, kind {kind}, count {len(devices)}")
    print(f"[device] compile cache {enable_compile_cache()}")
    if args.chips == 4:
        four_chip(four_chip_run())
    else:
        one_chip(driver_run())
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
