"""Readings the limits of a cell are set from (run on the chip).

For each seed: the program's compared numbers against the float32
reference (a training cell's three checked steps run in set-up and need
no window; a serving cell serves ``--seconds`` at its own load).  For the
first ``--control-seeds`` seeds also the control (the reference in float8
e4m3 in the program's place) and the cell's planted faults, each against
the float32 reference: for training, "half of the batch left out, the
mean taken over the rest" (a state left unchanged reads 1 on
``change_gap`` by construction and is not run); for serving, "a token
altered where it is produced".

    python bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 3] [--seconds 0] [--out chiprun_out/calib.jsonl]

Prints one JSON line per seed and kind of reading.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window of each run (0 for training: the checked "
                         "steps run in set-up)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from harness import core
    files = core.cell_files(args.workload)
    core.require_chips(files["cell"]["chips"])
    core.enable_cache()
    driver = core.kind_driver(files["traffic"]["kind"])
    out = open(args.out, "a") if args.out else None
    seeds = [int(x) for x in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        ctx = core.Context(workload=args.workload, config=files["config"],
                           traffic=files["traffic"], limits=files["limits"],
                           seed=seed, seconds=args.seconds, trace=False,
                           t_start=time.perf_counter())
        t0 = time.perf_counter()
        driver.run(ctx)
        rows = [{"seed": seed, "what": "program", "setup_s": ctx.e2e["setup_s"],
                 "s": time.perf_counter() - t0,
                 **{k: c.value for k, c in ctx.checks.items()}}]
        if i < args.control_seeds:
            rows += driver.controls(ctx, seed)
        for row in rows:
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
