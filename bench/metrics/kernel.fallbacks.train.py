"""kernel.fallbacks.train: low-rank calls the dispatcher sent to jnp.

The growth of the program's ``kernel_fallbacks{op, reason}`` counter while
set-up traced the two timed phase programs (it counts at trace time, once
per dispatch site).  A count.  Moves ``train_tokens_per_s``.
"""


def read(ctx):
    return ctx.readings.get("fallbacks")
