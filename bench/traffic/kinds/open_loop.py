"""Kind ``open_loop``: independent users arriving as a Poisson process.

Parameters (the traffic file): ``rate`` (requests per second, fixed in
the cell), ``prompt`` and ``output`` lognormal lengths (``median``,
``sigma``, ``min``, ``max``), the engine's ``slots``, ``prefill_len`` and
``max_len``, and ``check`` (how many served tokens and requests the
correctness check reads).

A window offers ``rate`` x ``seconds`` requests, rounded: the strata of
the exponential gaps and of the lengths, in an order drawn from the seed,
so every seed offers the same number of requests of the same sizes at the
same gaps, the last of them near the close.  Every
request of the window is in the scheduler's queue from the start with its
due time, so the generator is never late; each is admitted when due and
a slot is free.  ``ttft_p95_ms`` is the 95th percentile over the requests
due in the window of first token minus due time.  A request whose first
token comes after the close is waited for, up to ``AWAIT_S`` seconds
(outside the window and its trace), and counts with its whole wait; one
with none by then counts as missing (infinite).  ``itl_p50_ms`` is the
median of the gaps between consecutive tokens of a request, both inside
the window: one decode step.  Their 95th percentile
falls on one of two plateaus, one decode step with one or with two
batch-1 prefills in it, and moves between them from run to run, so it is
recorded (``itl_p95_ms`` on standard error) and holds no bound.
"""

from __future__ import annotations

import math
import sys

import numpy as np

from harness import core, serve


AWAIT_S = 60.0  # how long after the close a first token is waited for


def requests_for(mix: dict, seconds: float) -> int:
    """How many requests a window offers: the rate's expectation."""
    return max(1, round(mix["rate"] * seconds))


def run(ctx: core.Context) -> None:
    mix = ctx.traffic
    served = serve.build(ctx, mix)
    reqs = serve.make_requests(mix, ctx.seed, requests_for(mix, ctx.seconds),
                               served.engine.run.model.vocab_size,
                               rate=mix["rate"])
    ctx.setup_done()
    with core.Window(ctx):
        submitted = serve.drive(served, reqs, ctx.seconds)
    due = [rid for rid, r in submitted.items() if r.due < ctx.seconds]
    serve.await_first_tokens(served, due, AWAIT_S)
    measure(ctx, served, submitted)
    ctx.memory_peak_bytes = core.memory_peak()
    answered = serve.with_tokens(served.sched)
    serve.release(served)
    mixc = mix["check"]
    picked = serve.sample(answered, ctx.seed, mixc["tokens"],
                          mixc["max_requests"])
    got = serve.check(ctx, served, picked)
    ctx.check("token_gap", got["token_gap"])
    ctx.readings.update(checked=got, served=served, picked=picked)


def controls(ctx, seed) -> list:
    """The control and the planted fault against the float32 reference."""
    return serve.controls(ctx, seed)


def measure(ctx: core.Context, served: serve.Served, submitted: dict) -> None:
    sched, end = served.sched, ctx.seconds
    due = {rid: r for rid, r in submitted.items() if r.due < end}
    reqs = {r.rid: r for r in list(sched.finished.values())
            + [s.req for s in sched.slots if s.active] + list(sched.queue)}
    ttft, wait, missing = [], [], 0
    for rid, r in due.items():
        req = reqs.get(rid)
        first = req.t_first if req is not None else None
        if first is None:
            ttft.append(math.inf)
            missing += 1
        else:
            ttft.append(first - r.due)
        started = req.t_started if req is not None else None
        wait.append(math.inf if started is None else started - r.due)
    gaps = [b - a for times in served.tokens.values()
            for a, b in zip(times, times[1:]) if b <= end]
    ctx.attempted, ctx.failed = len(due), missing
    ctx.e2e["ttft_p95_ms"] = 1e3 * serve.p95(ttft)
    ctx.e2e["itl_p50_ms"] = (1e3 * float(np.median(gaps)) if gaps
                             else math.inf)
    ctx.readings.update(
        queue_wait_p95_ms=1e3 * serve.p95(wait),
        ttft_p50_ms=1e3 * float(np.median(ttft)) if ttft else math.inf,
        itl_p95_ms=1e3 * serve.p95(gaps),
        finished=len(sched.finished), due=len(due), gaps=len(gaps),
        slots_peak=max((len(d) for _, d in served.decode_steps), default=0),
        waited_over_1s=sum(1 for w in wait if w > 1.0),
        first_after_close=_after_close(due, reqs, end),
        longest_step_s=served.longest_step[0],
        longest_step_at_s=served.longest_step[1],
        compiles_in_window=served.compiles_in_window,
        generated=sum(1 for t in served.tokens.values() for x in t
                      if x <= end))
    print(f"open_loop: {len(due)} requests due, "
          f"{ctx.readings['first_after_close']} with the first token after "
          f"the close, {missing} with none {AWAIT_S:g} s later; "
          f"itl_p95_ms {ctx.readings['itl_p95_ms']!r}; "
          f"ttft_p50_ms {ctx.readings['ttft_p50_ms']!r}; slots busy at most "
          f"{ctx.readings['slots_peak']} of {len(sched.slots)}; "
          f"{ctx.readings['waited_over_1s']} waited over 1 s for a slot; "
          f"longest scheduler step {served.longest_step[0]!r} s at "
          f"{served.longest_step[1]!r} s; programs compiled in the window "
          f"{served.compiles_in_window}", file=sys.stderr)


def _after_close(due: dict, reqs: dict, end: float) -> int:
    """How many requests due in the window had no first token by the close."""
    n = 0
    for rid in due:
        req = reqs.get(rid)
        if req is None or req.t_first is None or req.t_first > end:
            n += 1
    return n
