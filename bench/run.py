"""Run one cell of the chip benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its limits are found by name from ``BENCHMARK.json`` (see
``bench/README.md``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and the result
carries its per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks``: each number the correctness check compared beside
its limit, also printed as the last lines of standard error).  The run
exits with a non-zero code and prints no result when JAX finds no
accelerator, or fewer chips than the cell asks for, or when a file the cell
needs is missing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import core
    try:
        files = core.cell_files(args.workload)
        if not (BENCH.parent / "src" / "repro").is_dir():
            raise core.SetupError("the program (src/repro) is not in this "
                                  "checkout")
        ctx = core.Context(workload=args.workload, config=files["config"],
                           traffic=files["traffic"], limits=files["limits"],
                           seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START)
        result = core.execute(ctx, files["benchmark"],
                              check_chips=files["cell"]["chips"])
    except core.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    core.print_checks(ctx)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
