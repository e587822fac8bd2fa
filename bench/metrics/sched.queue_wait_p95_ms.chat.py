"""sched.queue_wait_p95_ms.chat: how long requests waited for a slot.

95th percentile over the requests due in the window of the scheduler's
first prefill start (``Request.t_started``) minus the due time; a request
not started by the window's end counts as missing.  Moves ``ttft_p95_ms``.
"""


def read(ctx):
    return ctx.readings.get("queue_wait_p95_ms")
