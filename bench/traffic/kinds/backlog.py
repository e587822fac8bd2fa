"""Kind ``backlog``: offline generation from a queue that never runs dry.

Parameters (the traffic file): ``pending`` (requests kept waiting in the
scheduler's queue all window), ``prompt`` and ``output`` lognormal lengths,
the engine's ``slots``, ``prefill_len`` and ``max_len``, and ``check``.

The queue is topped up to ``pending`` before every scheduler step, so a
slot that frees is filled at once.  ``serve_tokens_per_s`` is every token
generated inside the window over the window.
"""

from __future__ import annotations

from harness import core, serve

STRATUM = 512  # requests drawn per stratified block


def run(ctx: core.Context) -> None:
    mix = ctx.traffic
    served = serve.build(ctx, mix)
    vocab = served.engine.run.model.vocab_size
    blocks = (serve.make_requests(mix, ctx.seed * 7919 + k, STRATUM, vocab)
              for k in range(1 << 30))
    reqs = (r for block in blocks for r in block)
    ctx.setup_done()
    with core.Window(ctx):
        serve.drive(served, reqs, ctx.seconds, keep_pending=mix["pending"])
    end = ctx.seconds
    generated = sum(1 for t in served.tokens.values() for x in t if x <= end)
    sched = served.sched
    ctx.attempted = len(sched.finished) + sum(1 for s in sched.slots
                                              if s.active)
    ctx.failed = 0
    ctx.e2e["serve_tokens_per_s"] = generated / end
    ctx.readings.update(generated=generated, finished=len(sched.finished),
                        decode_steps=served.decode_steps,
                        served_shapes=served.served_shapes)
    ctx.memory_peak_bytes = core.memory_peak()
    answered = serve.with_tokens(sched)
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
