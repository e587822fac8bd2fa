"""decode.step_mfu.backlog: the decode step's share of the chip's peak.

For every decode step of the window, the least time the chip could take:
the larger of its required FLOPs over the peak and its required bytes
over HBM bandwidth, where the bytes are the served weights read once and
the K/V of the live lengths only (``harness.flops.decode_terms``), so the
required work is the same whatever reads it.  Summed over the steps and
divided by the device time of the decode program (``jit_serve_step``
executions in the trace).  Moves ``serve_tokens_per_s``.
"""

import sys

from harness import core, flops


def read(ctx):
    r, tr = ctx.readings, ctx.trace_data
    steps = r.get("decode_steps")
    if tr is None or not steps:
        return None
    device = tr.module_time_s(ctx.window_ns, lambda n: "serve_step" in n)
    if device <= 0:
        return None
    pk = core.peaks(core.device_info()["kind"])
    terms = flops.decode_terms(r["served_shapes"], ctx.config)
    least = mem = 0.0
    for _, lengths in steps:
        f, b = terms.work(lengths)
        t = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
        least += t
        mem += t if b / pk["hbm_bytes_per_s"] >= f / pk["bf16_flops_per_s"] \
            else 0.0
    print(f"decode.step_mfu.backlog: {len(steps)} decode steps, "
          f"{device:.3f} s of decode program, least {least:.3f} s "
          f"({100 * mem / least:.0f}% memory-bound)", file=sys.stderr)
    return 100.0 * least / device
