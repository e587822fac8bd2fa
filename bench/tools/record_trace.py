"""Record the small device trace the reduction's tests read (on the chip).

Traces a few jitted programs and one fused low-rank kernel launch under
``bench.*`` spans, copies the ``.xplane.pb`` to ``--out``, and prints the
trace's planes, lines and operation names, with what the reduction reads
from it (``harness.trace``) for the tests to hold.

    python bench/tools/record_trace.py --out chiprun_out/trace_small.xplane.pb
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from harness.core import profile_options
    from harness.trace import Trace

    x = jnp.ones((256, 512), jnp.bfloat16)
    u = jnp.ones((512, 128), jnp.bfloat16) * 0.01
    v = jnp.ones((128, 256), jnp.bfloat16) * 0.01
    kern = jax.jit(lambda x, u, v: ops.lowrank_apply(x, u, v, use_kernel=True))
    mm = jax.jit(lambda a: jnp.tanh(a @ a.T).sum())
    kern(x, u, v).block_until_ready()
    mm(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=str(BENCH.parent))
    jax.profiler.start_trace(tmp, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                kern(x, u, v).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.batch"):
                mm(x).block_until_ready()
                jnp.zeros(4).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(tmp)

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.out)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:25]
            print("  LINE", repr(line.name), len(evs), names)
            if evs and plane.name.startswith("/device"):
                print("    stats", [(k, str(v)[:80]) for k, v in evs[0].stats][:12])
    tr = Trace.from_file(args.out)
    w = tr.window()
    print(json.dumps({"window_s": (w[1] - w[0]) / 1e9, "busy_s": tr.busy_s(w),
                      "top_ops": tr.top_ops(w), "idle_gaps": tr.idle_gaps(w),
                      "bytes": os.path.getsize(args.out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
