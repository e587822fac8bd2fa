"""Sweep the offered rate of an open-loop serving cell (run on the chip).

Builds the cell's engine once and serves the cell's mix at each rate for
``--seconds``, printing per rate: requests due, finished, still queued at
the end, TTFT and inter-token gap percentiles, the most slots busy at
once and how many requests waited over a second for a slot.  The knee is
the highest rate at which no request waits for a slot; the cell's
traffic file fixes its rate at about four fifths of it.  Sweep with
windows of the cell's own length: the slots fill only after a request's
whole life (some 24 s in the chat mix), so a shorter window overstates
the knee.

    python bench/tools/knee.py --workload smollm-360m.serve.chat \\
        --rates 2.4,2.6 --seconds 51 --seed 1
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from harness import core, serve
    files = core.cell_files(args.workload)
    core.require_chips(files["cell"]["chips"])
    core.enable_cache()
    mix = files["traffic"]
    ctx = core.Context(workload=args.workload, config=files["config"],
                       traffic=mix, limits=files["limits"], seed=args.seed,
                       seconds=args.seconds, trace=False, t_start=T_START)
    driver = core.kind_driver(mix["kind"])
    served = serve.build(ctx, mix)
    vocab = served.engine.run.model.vocab_size
    for rate in (float(x) for x in args.rates.split(",")):
        served.sched.reset_stats()
        served.tokens.clear()
        served.decode_steps.clear()
        m = dict(mix, rate=rate)
        reqs = serve.make_requests(m, args.seed, driver.requests_for(
            m, args.seconds), vocab, rate=rate)
        sub = serve.drive(served, reqs, args.seconds)
        ctx.e2e.clear()
        ctx.readings.clear()
        driver.measure(ctx, served, sub)
        row = {"rate": rate, **ctx.e2e, **ctx.readings,
               "failed": ctx.failed, "attempted": ctx.attempted}
        print(json.dumps(row), flush=True)
        # drain before the next rate
        sched = served.sched
        sched.queue.clear()
        while any(s.active for s in sched.slots):
            sched.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
