"""One run of one cell: find its files, check the device, run the traffic
kind's driver, read the per-layer metrics, print the result line.

Files are found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``  — the model as published, the cut, the
  LRD settings and the program preset it runs through;
* ``bench/traffic/<traffic>.json`` — the mix's parameters and its
  ``kind``, whose driver is ``bench/traffic/kinds/<kind>.py``;
* ``bench/limits/<cell>.json``     — the limit of each number the
  correctness check compares;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class SetupError(RuntimeError):
    """The run cannot start: wrong device, missing file."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SetupError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Check:
    """One number the correctness check compares, with its limit."""
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a traffic kind's driver gets, and what it hands back."""
    workload: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float  # process start, perf_counter seconds
    interpret: bool = False  # Pallas interpret mode (CPU tests only)
    smoke_model: Any = None  # a tiny ModelConfig (CPU tests only)
    # filled by the driver
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    readings: Dict[str, Any] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Check] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace_data: Any = None
    window_ns: Any = None

    def setup_done(self) -> float:
        s = time.perf_counter() - self.t_start
        self.e2e["setup_s"] = s
        return s

    def check(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise SetupError(f"no limit for {name!r} in limits of "
                             f"{self.workload}")
        self.checks[name] = Check(float(value), float(self.limits[name]))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks.values())


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def require_chips(chips: int) -> None:
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise SetupError("JAX found no accelerator (platform cpu); the "
                         "benchmark has no CPU path")
    if len(devices) < chips:
        raise SetupError(f"cell needs {chips} chips, JAX found "
                         f"{len(devices)}")


def enable_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program however fast it
    compiled, so a second run of a cell compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak() -> int:
    """Peak device memory of the fullest device, as the runtime reports
    it: the larger of the peak of live buffers and the peak the runtime
    reserved, which holds the programs' temporaries."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats() -> dict:
    import jax
    return dict(jax.local_devices()[0].memory_stats() or {})


def peaks(kind: str) -> dict:
    """The chip's published peaks, by ``device_kind``; unknown is an error."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["chips"][kind]


def free_device_memory() -> None:
    gc.collect()


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------

class Window:
    """The measured window: traced when ``ctx.trace``, always annotated."""

    def __init__(self, ctx: Context):
        self.ctx = ctx

    def __enter__(self):
        import jax
        if self.ctx.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=profile_options())
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self.ann.__exit__(*exc)
        if self.ctx.trace:
            jax.profiler.stop_trace()
        return False


def profile_options():
    """Device and host activity with the harness's spans; no Python
    function tracing, which would slow the host the window measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def read_trace(ctx: Context) -> None:
    from harness.trace import Trace
    tr = Trace.from_dir(str(TRACE_DIR))
    ctx.trace_data = tr
    ctx.window_ns = tr.window()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def cell_files(workload: str, benchmark: Optional[dict] = None) -> dict:
    bm = benchmark or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    conf = configs[cell["config"]]
    return {"benchmark": bm, "cell": cell,
            "config": load_json(ROOT / conf["file"]),
            "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            "limits": load_json(BENCH / "limits" / f"{workload}.json")}


def kind_driver(kind: str):
    return load_module(BENCH / "traffic" / "kinds" / f"{kind}.py",
                       f"bench_kind_{kind}")


def layer_metrics(bm: dict, workload: str, e2e_names) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, or,
    without a list, those whose end-to-end metric the cell reports."""
    out = []
    for m in bm["per_layer"]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif m["moves"] in e2e_names:
            out.append(m)
    return out


def e2e_metrics(bm: dict, workload: str) -> List[dict]:
    return [m for m in bm["end_to_end"]
            if m.get("workloads") is None or workload in m["workloads"]]


def execute(ctx: Context, benchmark: dict, *, check_chips: Optional[int],
            ) -> dict:
    """Run the cell's driver and assemble the result object."""
    if check_chips is not None:
        require_chips(check_chips)
    enable_cache()
    driver = kind_driver(ctx.traffic["kind"])
    driver.run(ctx)
    if ctx.trace:
        read_trace(ctx)
    e2e = e2e_metrics(benchmark, ctx.workload)
    if ctx.trace:
        metrics = {}
        for m in layer_metrics(benchmark, ctx.workload,
                               {x["name"] for x in e2e}):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(ctx.e2e[m["name"]]),
                               "unit": m["unit"]} for m in e2e}
    device = dict(device_info(), memory_peak_bytes=int(ctx.memory_peak_bytes))
    print(f"device memory after the run {memory_stats()}", file=sys.stderr)
    out: Dict[str, Any] = {"correct": ctx.correct,
                           "attempted": int(ctx.attempted),
                           "failed": int(ctx.failed), "metrics": metrics,
                           "device": device}
    if ctx.trace and ctx.trace_data is not None:
        tr, w = ctx.trace_data, ctx.window_ns
        device["busy_s"] = tr.busy_s(w)
        device["window_s"] = (w[1] - w[0]) / 1e9
        out["breakdown"] = {"device_ops": tr.top_ops(w, 10),
                            "idle_gaps": tr.idle_gaps(w, 10)}
    out["checks"] = {k: {"value": c.value, "limit": c.limit}
                     for k, c in ctx.checks.items()}
    return out


def print_checks(ctx: Context) -> None:
    for k, c in ctx.checks.items():
        print(f"check {k} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    print(f"correct {ctx.correct}", file=sys.stderr)
