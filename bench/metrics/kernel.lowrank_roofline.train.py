"""kernel.lowrank_roofline.train: the fused low-rank kernels' roofline share.

The least time the chip could take for the kernel launches of the traced
window's steps (each launch the larger of its FLOPs over the peak and its
bytes over HBM bandwidth, ``harness.flops.lowrank_kernel_calls``, leaving
out the calls the dispatcher sent to jnp at trace time), over the summed
device time of the trace's operations named ``lowrank_*``.  Nothing is
read when the trace has no such operation.  Moves ``train_tokens_per_s``.
"""

import sys

from harness import core, flops
from harness.trace import base_name


def _is_kernel(name):
    return base_name(name).startswith("lowrank_")


def read(ctx):
    r, tr = ctx.readings, ctx.trace_data
    if tr is None or "steps" not in r:
        return None
    kernel_s = tr.op_time_s(ctx.window_ns, _is_kernel)
    if kernel_s <= 0:
        return None
    pk = core.peaks(core.device_info()["kind"])
    pf, pb = pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"]
    least = compute = launches = 0.0
    for ph in (0, 1):
        calls = flops.lowrank_kernel_calls(
            r["abstract"], r["tokens_per_step"], ph, r["fallback_shapes"],
            remat=r["remat"])
        n = r["steps"][ph]
        least += n * sum(c.count * c.least_s(pf, pb) for c in calls)
        compute += n * sum(c.count * c.flops / pf for c in calls
                           if c.bound(pf, pb) == "compute")
        launches += n * sum(c.count for c in calls)
    events = tr.op_count(ctx.window_ns, _is_kernel)
    print(f"kernel.lowrank_roofline.train: {launches:.0f} launches counted "
          f"from shapes, {events:.0f} lowrank_* events traced; "
          f"{100 * compute / least if least else 0:.1f}% of the least time "
          f"is compute-bound", file=sys.stderr)
    return 100.0 * least / kernel_s
