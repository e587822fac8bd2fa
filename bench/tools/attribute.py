"""Run one cell traced and attribute its window to the program's own spans
and model scopes.

    python3 bench/tools/attribute.py --workload <cell> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1`` (the cell's driver, its checks and
the per-layer metrics ``BENCHMARK.json`` gives it), with the program's host
hooks on (``repro.obs.install_host_hooks``: ``host.gc`` spans), and the
trace read once more for what the harness's reduction keeps no record of:
the program's host spans (``harness.attribution``).  The last line of
standard output is the run's result object with an ``attribution`` object
added:

* ``idle_by_span`` — device-idle seconds of the window by the innermost
  program span open at each instant (``none``: no program span open);
* ``idle_by_bench_span`` — the same idle time by the innermost ``bench.*``
  span of the harness (what the breakdown's ``idle_gaps`` label, there by
  each gap's middle);
* ``bench_idle_under_program`` — for ``bench.batch``, ``bench.swap`` and
  ``bench.sched_step``: their idle seconds, and the part of it that lies
  under a program span;
* ``spans`` — per program span name in the window: count and seconds;
* ``prefill_ms`` — p50, p95, count and longest ``sched.prefill`` span
  (serving cells);
* ``sched_idle_ms_per_decode`` — idle under ``sched.*`` spans over the
  window's decode dispatches (serving cells);
* ``scopes`` — device seconds per model scope, apart by how each
  operation's scope was found (``own_s``, ``called_s``, ``near_s``:
  ``harness.attribution.ScopeMap``) and with each one's share of the
  device-op time (``shares``), the unscoped remainder by operation, the
  operations that took most time in each scope, and the seconds the phase
  programs took to compile again for their maps (training cells);
* ``host_counters`` — what ``jit_compiles``, ``jit_compile_s``,
  ``host_gc_pauses`` and ``host_gc_s`` (``repro.obs``) counted between
  the window's start and its end;
* ``e2e`` — the cell's end-to-end metrics as this traced run read them,
  to set against an untraced run of the same seed (the cost of tracing).

With ``--out`` the same object is written to that file.  Run it on the
accelerator, as ``bench/run.py`` is run.
"""

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

BENCH_IDLE = ("bench.batch", "bench.swap", "bench.sched_step")
HOST_COUNTERS = ("jit_compiles", "jit_compile_s", "host_gc_pauses",
                 "host_gc_s")


def _intersect(a, b):
    """Pairwise overlaps of two lists of (start, end), each first merged."""
    from harness.trace import union
    lo = min([x for x, _ in a] + [x for x, _ in b], default=0.0)
    hi = max([y for _, y in a] + [y for _, y in b], default=0.0)
    ua, ub = union(a, (lo, hi)), union(b, (lo, hi))
    out, i, j = [], 0, 0
    while i < len(ua) and j < len(ub):
        s, e = max(ua[i][0], ub[j][0]), min(ua[i][1], ub[j][1])
        if e > s:
            out.append((s, e))
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return out


def _pct(xs, q):
    from repro.obs.registry import percentile
    return percentile(xs, q) if xs else None


def attribute(ctx, spans) -> dict:
    from harness import attribution
    from harness.trace import Event
    tr, w = ctx.trace_data, ctx.window_ns
    spans = attribution.within(spans, w)
    split = attribution.idle_by_span(tr, spans, w)
    bench = [e for e in tr.spans if e.name != "bench.window"]
    out = {"e2e": dict(ctx.e2e),
           "idle_by_span": {k or "none": v for k, v in sorted(
               split.items(), key=lambda kv: -kv[1])},
           "idle_by_bench_span": {k or "none": v for k, v in sorted(
               attribution.idle_by_span(tr, bench, w).items(),
               key=lambda kv: -kv[1])},
           "bench_idle_under_program": {}, "spans": {}}
    prog_iv = [(e.start, e.end) for e in spans]
    for name in BENCH_IDLE:
        iv = [(e.start, e.end) for e in bench if e.name == name]
        if not iv:
            continue
        whole = attribution.idle_by_span(
            tr, [Event("x", a, b) for a, b in iv], w).get("x", 0.0)
        under = attribution.idle_by_span(
            tr, [Event("x", a, b) for a, b in _intersect(iv, prog_iv)],
            w).get("x", 0.0)
        out["bench_idle_under_program"][name] = {"idle_s": whole,
                                                 "under_program_s": under}
    for e in spans:
        d = out["spans"].setdefault(e.name, {"count": 0, "s": 0.0})
        d["count"] += 1
        d["s"] += (min(e.end, w[1]) - max(e.start, w[0])) / 1e9
    pre = attribution.durations(spans, "sched.prefill", w)
    if pre:
        out["prefill_ms"] = {"p50": 1e3 * _pct(pre, 50),
                             "p95": 1e3 * _pct(pre, 95),
                             "count": len(pre), "max": 1e3 * max(pre)}
    decodes = sum(1 for e in spans if e.name in ("sched.decode",
                                                 "sched.draft")
                  and w[0] <= e.start < w[1])
    if decodes:
        sched = sum(attribution.by_prefix(split, "sched.").values())
        out["sched_idle_ms_per_decode"] = 1e3 * sched / decodes
    parts = attribution.halves(tr, w)
    if parts is not None and "steps" in ctx.readings:
        maps = attribution.train_scope_maps(ctx)
        if maps is not None:
            table = attribution.scope_time(
                tr, [(parts[0], maps[0]), (parts[1], maps[1])])
            total = sum(table.values())
            split = attribution.sum_by(table, 0, 1)

            def by_scope(how):
                return dict(sorted(((sc, t) for (sc, h), t in split.items()
                                    if h == how), key=lambda kv: -kv[1]))

            by_how = attribution.sum_by(table, 1)
            rest = attribution.sum_by(
                {k: v for k, v in table.items() if k[0] is None}, 2)
            top = attribution.sum_by(table, 0, 2)
            out["scopes"] = {
                "steps": ctx.readings["steps"][0] + ctx.readings["steps"][1],
                **{f"{h}_s": by_scope(h) for h in attribution.HOW},
                "unscoped_s": dict(sorted(rest.items(),
                                          key=lambda kv: -kv[1])[:20]),
                "shares": {h or "unscoped": by_how.get(h, 0.0) / total
                           for h in attribution.HOW + (None,)} if total
                else None,
                "top_ops": [[sc or "unscoped", op, t] for (sc, op), t in
                            sorted(top.items(), key=lambda kv: -kv[1])[:24]],
                "maps_compile_s": ctx.scope_maps_s}
    return out


def _host_counters() -> dict:
    from repro.obs import default_registry
    reg = default_registry()
    return {k: reg.counter(k).total() for k in HOST_COUNTERS}


def traced_run(ctx, benchmark: dict, check_chips) -> tuple:
    """``core.execute`` of a traced run with the program's host hooks on,
    its spans kept from the trace before the reduction deletes it, and the
    host counters read as the window opens and before it closes; returns
    the result object and :func:`attribute`'s object."""
    from harness import attribution, core
    from repro.obs import install_host_hooks, uninstall_host_hooks
    kept = {}
    read_trace, window = core.read_trace, core.Window

    def read_keeping_spans(c):
        kept["spans"] = attribution.program_spans_from_dir(
            str(core.TRACE_DIR))
        read_trace(c)

    class CountedWindow(window):
        def __enter__(self):
            out = super().__enter__()
            kept["open"] = _host_counters()
            return out

        def __exit__(self, *exc):
            kept["close"] = _host_counters()
            return super().__exit__(*exc)

    core.read_trace, core.Window = read_keeping_spans, CountedWindow
    install_host_hooks()
    try:
        result = core.execute(ctx, benchmark, check_chips=check_chips)
    finally:
        core.read_trace, core.Window = read_trace, window
        uninstall_host_hooks()
    att = attribute(ctx, kept["spans"])
    att["host_counters"] = {k: kept["close"][k] - kept["open"][k]
                            for k in HOST_COUNTERS}
    return result, att


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from harness import core

    files = core.cell_files(args.workload)
    ctx = core.Context(workload=args.workload, config=files["config"],
                       traffic=files["traffic"], limits=files["limits"],
                       seed=args.seed, seconds=args.seconds, trace=True,
                       t_start=T_START)
    try:
        result, att = traced_run(ctx, files["benchmark"],
                                 files["cell"]["chips"])
    except core.SetupError as e:
        print(f"attribute: {e}", file=sys.stderr)
        return 3
    result["attribution"] = att
    core.print_checks(ctx)
    print(f"attribute: over the window {att['host_counters']}",
          file=sys.stderr)
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
