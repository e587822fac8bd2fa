"""device.idle_share.backlog: share of the traced window the device ran nothing.

1 - busy / window, busy being the union of the device's operation
intervals inside the ``bench.window`` span (``harness.trace``).
"""


def read(ctx):
    tr = ctx.trace_data
    if tr is None or not tr.ops:
        return None
    w = ctx.window_ns
    return 100.0 * (1.0 - tr.busy_s(w) / ((w[1] - w[0]) / 1e9))
