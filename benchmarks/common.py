"""Shared benchmark utilities: timing, LRD method variants, tiny trainers."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Callable, Dict, Sequence, Tuple

from repro.core import freezing
from repro.core.decompose import Decomposer, apply_lrd
from repro.core.policy import (NO_LRD, RESNET_DEFAULT, DecompositionPolicy,
                               Rule)
# The one wall-clock timer (warm-up excluded, outputs blocked, median of
# iters) shared by every benchmark AND the kernel autotuner — a tuned block
# config "wins" under exactly the clock the benchmarks report.
from repro.kernels.autotune import time_fn  # noqa: F401  (re-export)


# Paper method ladder (Tables 1/3/4): Org -> LRD -> RankOpt -> Freeze -> Combined
def method_policies(base: DecompositionPolicy, alpha: float = 2.0):
    lrd = base.with_alpha(alpha).with_quantize(False).with_min_dim(32)
    ropt = base.with_alpha(alpha).with_quantize(True).with_min_dim(32)
    return {
        "org": (None, -1),
        "lrd": (lrd, -1),
        "rankopt": (ropt, -1),
        "freeze": (lrd, 0),  # phase 0 static freeze
        "combined": (ropt, 0),
    }


def csv_row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"


def record(name: str, rows, out_dir: str = "benchmarks/results") -> str:
    """Record benchmark rows as ``BENCH_<name>.json`` (see BENCHMARKS.md).

    Every script's ``main()`` returns its row dicts; ``run.py`` funnels them
    through here so perf numbers are diffable across PRs.  Returns the path.

    Beside the JSON, the same rows are mirrored as schema-versioned
    telemetry events (``BENCH_<name>.events.jsonl``, one ``bench_row``
    per row — repro.obs.schema): benchmark output and live training/
    serving telemetry share one schema, so ``analysis/obs_report.py``
    and any JSONL consumer read both without a second parser.
    """
    import json
    import pathlib

    from repro.obs import EventLog

    p = pathlib.Path(out_dir)
    p.mkdir(parents=True, exist_ok=True)
    path = p / f"BENCH_{name}.json"
    path.write_text(json.dumps(rows, indent=1, default=str))
    with EventLog(p / f"BENCH_{name}.events.jsonl") as log:
        log.emit("run_start", kind="bench", bench=name)
        for row in rows:
            log.emit("bench_row", bench=name, row=row)
        log.emit("run_end", kind="bench", bench=name, rows=len(rows))
    return str(path)


def rows_on_devices(needed: int, run_rows: Callable[[], list], module: str,
                    child_args: Sequence[str] = ()) -> list:
    """Rows of a benchmark section that needs ``needed`` devices.

    With that many devices, ``run_rows()`` runs in this process.  Otherwise,
    and only when this process's JAX is on the CPU, the section re-executes
    ``python -m <module> <child_args> --json-out FILE`` under a forced
    ``needed``-device host platform and reads the rows back: jax pins the
    device count at first init, and a process on the CPU holds no
    accelerator that its child could need.  A process on an accelerator
    with too few devices raises instead — one chip belongs to one process,
    so a child would fail to reach it, hang, or measure CPU devices.
    """
    import jax

    devices = jax.devices()
    if len(devices) >= needed:
        return run_rows()
    platform = devices[0].platform
    if platform != "cpu":
        raise RuntimeError(f"{module}: needs {needed} devices, found "
                           f"{len(devices)} {platform} device(s)")
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={needed}").strip()
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        out = Path(td) / "rows.json"
        proc = subprocess.run(
            [sys.executable, "-m", module, *child_args, "--json-out",
             str(out)], cwd=root, env=env, capture_output=True, text=True,
            timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(f"{module} child failed:\n{proc.stdout}\n"
                               f"{proc.stderr[-3000:]}")
        return json.loads(out.read_text())
