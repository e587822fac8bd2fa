"""Serving driver: continuous batching over a synthetic Poisson trace.

Replays ``--requests`` requests with exponential inter-arrival times at
``--rate`` req/s (random prompt lengths) through the scheduler-backed
``ServeEngine`` and prints throughput + latency percentiles.  All engine
knobs flow through one validated ``ServeConfig`` (serving/config.py):
``--export`` serves the rank-quantized Algorithm-1 artifact
(``--export-int8`` quantizes its factors); ``--spec-k`` decodes
self-speculatively, drafting k tokens per step with a rank-truncated
derivation of the served params (``--spec-rank`` / ``--spec-fraction``;
serving/speculative.py) — token-exact under greedy decode;
``--mesh-data/--mesh-model`` place params + paged pools on a TP mesh;
``--prefix-cache`` shares prompt prefixes through the radix cache
(serving/radix_cache.py).  Families the scheduler doesn't cover
(enc-dec, VLM, SSM/hybrid) fall back to the legacy fixed-batch path
automatically.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --slots 4 --requests 16 --rate 8 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from pathlib import Path

from repro.configs import get_config, get_smoke_config
from repro.configs.base import DistConfig, LRDConfig, RunConfig, ShapeConfig
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import EventLog, install_host_hooks, uninstall_host_hooks
from repro.serving import ServeConfig, ServeEngine


def poisson_trace(n: int, rate: float, prompt_len: int, vocab: int,
                  seed: int = 0):
    """n requests: exponential inter-arrivals at ``rate``/s, random prompts
    of 1/4..1x ``prompt_len`` tokens."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / max(rate, 1e-9), n))
    lens = rng.integers(max(prompt_len // 4, 1), prompt_len + 1, n)
    return [{"prompt": rng.integers(0, vocab, int(l), dtype=np.int32),
             "arrival": float(t)} for t, l in zip(arrivals, lens)]


def shared_prefix_trace(n: int, rate: float, prefix_len: int, suffix_len: int,
                        vocab: int, seed: int = 0):
    """n requests sharing one ``prefix_len``-token system prompt, each with
    a random 1..``suffix_len`` tail — the radix-prefix-cache workload
    (every request after the first can reuse the prefix's full blocks)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / max(rate, 1e-9), n))
    prefix = rng.integers(0, vocab, prefix_len, dtype=np.int32)
    return [{"prompt": np.concatenate(
                 [prefix, rng.integers(0, vocab, int(s), dtype=np.int32)]),
             "arrival": float(t)}
            for t, s in zip(arrivals, rng.integers(1, suffix_len + 1, n))]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="serving window (default prompt_len + max_new)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged pool size; 0 = fully provisioned")
    ap.add_argument("--lrd", action="store_true")
    ap.add_argument("--export", choices=("none", "analytic", "measured"),
                    default="none",
                    help="serve the rank-quantized Algorithm-1 artifact")
    ap.add_argument("--export-int8", action="store_true",
                    help="int8-quantize the export artifact's factors "
                         "(requires --export)")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="data axis of the serving mesh")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model (tensor-parallel) axis of the serving mesh")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prompt-prefix cache over the paged "
                         "block pool (serving/radix_cache.py)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens per step "
                         "(0 = plain decode; serving/speculative.py)")
    ap.add_argument("--spec-rank", type=int, default=0,
                    help="explicit draft rank (clamped per layer); 0 = "
                         "Algorithm-1 sweep scaled by --spec-fraction")
    ap.add_argument("--spec-fraction", type=float, default=0.5,
                    help="draft rank as a fraction of the sweep's "
                         "pre-cliff rank (used when --spec-rank is 0)")
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs", action="store_true",
                    help="write per-request/per-step telemetry JSONL "
                         "(events.jsonl in --obs-dir; DESIGN.md §12)")
    ap.add_argument("--obs-dir", default="runs/serve_obs",
                    help="telemetry directory for --obs")
    ap.add_argument("--log-format", default="text",
                    choices=["text", "jsonl"],
                    help="with jsonl, mirror every event to the console")
    args = ap.parse_args(argv)

    enable_compile_cache()
    obs = None
    if args.obs or args.log_format == "jsonl":
        path = None
        if args.obs:
            obs_dir = Path(args.obs_dir)
            obs_dir.mkdir(parents=True, exist_ok=True)
            path = obs_dir / "events.jsonl"
        # serving events have no legacy text lines, so a text-format
        # mirror stays silent; jsonl mirrors the raw events
        obs = EventLog(path, mirror=print if args.log_format == "jsonl"
                       else None, fmt=args.log_format)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    max_len = args.max_len or (args.prompt_len + args.max_new)
    shape = ShapeConfig("serve", max_len, args.slots, "decode")
    run = RunConfig(model=cfg, shape=shape,
                    lrd=LRDConfig(enabled=args.lrd, min_dim=16,
                                  rank_quantize=False),
                    dist=DistConfig(fsdp=False, remat="none"))
    params, plan = steps_mod.init_params(run)
    if plan.layers:
        print(plan.summary())

    if cfg.family in ("dense", "moe"):
        config = ServeConfig.from_args(args, max_len=max_len)
        engine = ServeEngine(run, params, config=config, obs=obs)
        if engine.export_report is not None:
            print(engine.export_report.summary())
        if config.mesh_model > 1 or config.mesh_data > 1:
            print(f"mesh: data={config.mesh_data} model={config.mesh_model} "
                  f"({engine.mesh.devices.size} devices)")
        if args.spec_k and engine.scheduler and engine.draft_report:
            print(engine.draft_report.summary())
        trace = poisson_trace(args.requests, args.rate, args.prompt_len,
                              cfg.vocab_size, args.seed)
        for r in trace:
            r["max_new"] = args.max_new
            if args.eos_id >= 0:
                r["eos_id"] = args.eos_id
        if obs is not None:
            obs.emit("run_start", _mirror=False, kind="serve",
                     arch=cfg.name, slots=args.slots,
                     requests=args.requests, rate=args.rate)
        if args.obs:  # garbage-collection spans and counters
            install_host_hooks()
        t0 = time.perf_counter()
        outs = engine.serve(trace)
        dt = time.perf_counter() - t0
        if args.obs:
            uninstall_host_hooks()
        stats = engine.scheduler.latency_stats()
        if obs is not None:
            obs.emit("run_end", _mirror=False, kind="serve", **stats)
            obs.close()
        print(f"{len(outs)} requests, "
              f"{int(stats['generated_tokens'])} tokens in {dt:.2f}s "
              f"({stats['tok_per_s']:.1f} tok/s; layout "
              f"{engine.scheduler.layout}, "
              f"{engine.scheduler.decode_compiles} decode compile)")
        print(f"latency p50 {stats['p50_latency_s'] * 1e3:.0f}ms  "
              f"p95 {stats['p95_latency_s'] * 1e3:.0f}ms  "
              f"p99 {stats['p99_latency_s'] * 1e3:.0f}ms  "
              f"first-token p50 {stats['p50_first_token_s'] * 1e3:.0f}ms  "
              f"queue-wait p50 {stats['p50_queue_wait_s'] * 1e3:.0f}ms  "
              f"preemptions {int(stats['preemptions'])}")
        if args.spec_k:
            print(f"speculative: k={args.spec_k}, "
                  f"{int(stats['spec_steps'])} steps, "
                  f"{int(stats['drafted_tokens'])} drafted / "
                  f"{int(stats['accepted_tokens'])} accepted "
                  f"(acceptance {stats['acceptance_rate']:.2f}; "
                  f"{engine.scheduler.draft_compiles} draft + "
                  f"{engine.scheduler.verify_compiles} verify compile)")
        if config.prefix_cache:
            print(f"prefix cache: {int(stats['prefix_hits'])}/"
                  f"{int(stats['prefix_lookups'])} hits, "
                  f"{int(stats['prefix_hit_tokens'])} prompt tokens reused "
                  f"({int(stats['prefill_tokens'])} prefilled; "
                  f"{engine.scheduler.extend_compiles} extend + "
                  f"{engine.scheduler.insert_compiles} insert compile)")
        print("sample:", outs[0][:16].tolist())
        return outs

    # fixed-batch fallback for extras-carrying / stateful families
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(args.slots, args.prompt_len), dtype=np.int32)
    extras = None
    if cfg.family == "vlm":
        extras = {"vision_embeddings": jax.numpy.asarray(
            rng.normal(0, 0.1, (args.slots, cfg.num_image_tokens, cfg.d_model)),
            dtype=cfg.cdtype)}
    if cfg.family == "encdec":
        from repro.models import encdec as ed
        frames = jax.numpy.asarray(
            rng.normal(0, 0.1, (args.slots, cfg.encoder_frames, cfg.d_model)),
            dtype=cfg.cdtype)
        extras = {"memory": ed.encode(params, frames, cfg)}
    engine = ServeEngine(run, params,
                         config=ServeConfig.from_args(args, max_len=max_len,
                                                      num_slots=0,
                                                      speculative_k=0,
                                                      prefix_cache=False))
    if engine.export_report is not None:
        print(engine.export_report.summary())
    t0 = time.perf_counter()
    out = engine.generate(prompts, max_new=args.max_new, extras=extras)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s incl. compile; fixed-batch path)")
    print("sample:", out[0][:16].tolist())
    return out


if __name__ == "__main__":
    main()
