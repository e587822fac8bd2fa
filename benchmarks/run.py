"""Benchmark harness — one function per paper table/figure + kernel + LM
throughput.  Prints ``name,us_per_call,derived`` CSV lines (plus per-table
sections).  ``--full`` also runs ResNet-101/152 (slow on CPU); ``--smoke``
runs only the fast, deterministic sections (kernel microbench incl. the
per-freeze-phase backward, and the analytic rank-sweep) — the CI-friendly
path documented in README.md.  ``--record`` writes each section's rows to
``benchmarks/results/BENCH_<section>.json`` (see benchmarks/BENCHMARKS.md).

  PYTHONPATH=src python -m benchmarks.run [--full | --smoke] [--record]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def _section(title):
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="also run ResNet-101/152 ladders (slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast deterministic sections only (kernels + "
                         "analytic rank sweep)")
    ap.add_argument("--record", action="store_true",
                    help="write rows to benchmarks/results/BENCH_*.json")
    args, _ = ap.parse_known_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    failures = []

    def guard(title, fn, record_as=None):
        _section(title)
        t0 = time.perf_counter()
        try:
            rows = fn()
            if args.record and record_as and rows is not None:
                from benchmarks.common import record
                print(f"[recorded {record(record_as, rows)}]")
        except Exception:  # keep the harness going; report at the end
            traceback.print_exc()
            failures.append(title)
        print(f"[{title}: {time.perf_counter() - t0:.1f}s]")

    from benchmarks import (fig2_rank_sweep, fig3_freezing_convergence,
                            kernel_microbench, lm_throughput,
                            rank_adaptation, serve_throughput, shard_scaling,
                            table1_resnet_throughput,
                            table2_decomposition_time, table3_accuracy,
                            table4_vit, train_freezing)

    if args.smoke:
        guard("Kernel microbench (fused low-rank fwd+bwd, per freeze phase)",
              kernel_microbench.main, record_as="kernel_microbench")
        guard("Fig 2: rank sweep (analytic only)",
              lambda: fig2_rank_sweep.main(measured=False),
              record_as="fig2_rank_sweep")
        guard("Train freezing: step walltime + live-state bytes "
              "(partitioned state)",
              train_freezing.main, record_as="train_freezing")
        guard("Rank adaptation: per-phase shrinking bytes + loss parity "
              "vs fixed ranks (decaying schedule)",
              lambda: rank_adaptation.main(smoke=True),
              record_as="rank_adaptation")
        guard("Shard scaling: per-phase step time + collective bytes vs "
              "device count (8-dev host mesh)",
              shard_scaling.main, record_as="shard_scaling")
        guard("Serve throughput: Poisson trace, dense vs LRD vs "
              "rank-quantized export",
              serve_throughput.main, record_as="serve_throughput")
        _section("summary")
        if failures:
            print(f"FAILED sections: {failures}")
            sys.exit(1)
        print("smoke benchmark sections completed")
        return

    guard("Table 1: ResNet-50 throughput ladder",
          lambda: table1_resnet_throughput.main("resnet50"))
    if args.full:
        guard("Table 1: ResNet-101",
              lambda: table1_resnet_throughput.main("resnet101", iters=2))
        guard("Table 1: ResNet-152",
              lambda: table1_resnet_throughput.main("resnet152", iters=2))
    guard("Table 2: decomposition time",
          lambda: table2_decomposition_time.main(
              variants=("resnet50", "resnet101", "resnet152") if args.full
              else ("resnet50",)))
    guard("Table 3: accuracy ladder (synthetic proxy)", table3_accuracy.main)
    guard("Table 4: ViT ladder", table4_vit.main)
    guard("Fig 2: rank sweep (cliff curve)", fig2_rank_sweep.main,
          record_as="fig2_rank_sweep")
    guard("Fig 3: sequential vs regular freezing",
          fig3_freezing_convergence.main)
    guard("Kernel microbench (fused low-rank fwd+bwd, per freeze phase)",
          kernel_microbench.main, record_as="kernel_microbench")
    guard("Train freezing: step walltime + live-state bytes "
          "(partitioned state)",
          train_freezing.main, record_as="train_freezing")
    guard("Shard scaling: per-phase step time + collective bytes vs "
          "device count (8-dev host mesh)",
          shard_scaling.main, record_as="shard_scaling")
    guard("Serve throughput: Poisson trace, dense vs LRD vs "
          "rank-quantized export",
          serve_throughput.main, record_as="serve_throughput")
    guard("LM train/decode throughput (smoke archs)", lm_throughput.main)

    _section("summary")
    if failures:
        print(f"FAILED sections: {failures}")
        sys.exit(1)
    print("all benchmark sections completed")


if __name__ == "__main__":
    main()
