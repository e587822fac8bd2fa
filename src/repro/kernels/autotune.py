"""Block-size/grid autotuner for the fused Pallas kernels (DESIGN.md §11).

The fused low-rank forward/backward kernels, the fused SwiGLU first half,
and flash attention all take (block_m, block_k, block_n) launch knobs whose
best values depend on shape, dtype, and chip.  This module owns the search:

* **candidate generation** enumerates block triples that divide the problem
  and survive :func:`repro.analysis.roofline.prune_candidates` — the
  VMEM-fit test uses the double-buffered footprint (every streamed block
  lives in two slots at pipeline steady state) and per-dtype operand bytes,
  and the survivors come back ordered by the analytic roofline time;
* **measurement** times the analytically-best few candidates through the
  *real dispatcher* (``kernels.ops``) with a warm-up + median-of-k harness.
  The dispatcher can take its jnp fallback (off-TPU, tiles over the VMEM
  budget, manual-mesh regions); every fallback is captured via
  ``ops.capture_fallbacks`` and a timing that did not exercise the kernel
  is NEVER recorded as ``source="measured"`` — it demotes to the analytic
  winner with the fallback reason attached;
* **the tuning table** persists winners keyed by
  ``(op, shape-bucket, dtype, device_kind, freeze_phase)``.  The batch dim
  is bucketed to its next power of two (decode batches churn; weight dims
  don't), so the table stays O(distinct layer geometries), not O(shapes
  seen).  Entries recorded on another ``device_kind`` are stale and never
  served — retuning on the new chip overwrites them.

``kernels.ops`` consults the active table at trace time (shapes are static
under jit) when the :class:`~repro.kernels.ops.KernelPolicy` sets
``autotune=True``; a miss falls back to the analytically-best candidate so
an empty table is never worse than the legacy fixed blocks.

CLI (the CI smoke path — see .github/workflows/ci.yml)::

  PYTHONPATH=src python -m repro.kernels.autotune \
      --table /tmp/autotune.json --shapes 256x512x128x256 512x1024x128x512

A second run against the same table reports ``cache-hit`` per key and
re-measures nothing.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import roofline
from repro.obs import registry as obs_registry

__all__ = [
    "TuneEntry", "TuningTable", "time_fn", "candidate_blocks",
    "search", "get_table", "set_table", "load_table", "device_kind",
]

OPS = ("lowrank_fwd", "lowrank_dx", "lowrank_du", "lowrank_dv",
       "lowrank_ffn", "flash")
BLOCK_CHOICES = (128, 256, 512)
_SUBLANE = 8  # min second-to-last tile dim (fp32) — smallest legal block_m


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock seconds per call (warm-up excluded, outputs
    blocked).  The one timer shared by the autotuner and every benchmark
    (benchmarks/common.py re-exports it)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def device_kind() -> str:
    return jax.devices()[0].device_kind


def bucket_m(m: int) -> int:
    """Bucket the batch/token dim to its next power of two (>= 8).

    Weight geometry (c, r, s) keys exactly — there are few distinct layer
    shapes per model.  m is whatever the batch/scheduler produced; without
    bucketing every decode batch size would mint a new table row."""
    b = _SUBLANE
    while b < m:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class TuneEntry:
    block_m: int
    block_k: int
    block_n: int
    us: float  # measured (or predicted, per source) microseconds
    source: str  # "measured" | "analytic"
    device_kind: str
    fallback_reason: str = ""  # non-empty iff a measured run was demoted


def _key(op: str, m: int, c: int, r: int, s: int, dtype, kind: str,
         freeze_phase: Optional[int]) -> Tuple:
    fp = -1 if freeze_phase is None else int(freeze_phase)
    return (op, bucket_m(m), int(c), int(r), int(s),
            jnp.dtype(dtype).name, kind, fp)


class TuningTable:
    """Persistent map from tuned-op keys to winning block configs."""

    VERSION = 1

    def __init__(self, entries: Optional[Dict[Tuple, TuneEntry]] = None,
                 path: Optional[str] = None):
        self.entries: Dict[Tuple, TuneEntry] = dict(entries or {})
        self.path = path
        # per-table lookup outcomes; the same counts also feed the
        # ``autotune_lookups{op, result}`` counter in the default metrics
        # registry (repro.obs) so dispatch-time table efficacy is visible
        # alongside the kernel-fallback counters
        self.stats = {"hit": 0, "miss": 0, "stale": 0}

    def __len__(self) -> int:
        return len(self.entries)

    def lookup(self, op: str, m: int, c: int, r: int, s: int, dtype,
               *, freeze_phase: Optional[int] = None,
               kind: Optional[str] = None) -> Optional[TuneEntry]:
        """The winning entry for this op/shape-bucket, or None.

        Entries recorded under a different ``device_kind`` are stale — a
        table tuned on one chip must not steer launches on another — and
        are treated as misses (re-tuning overwrites them in place)."""
        kind = kind or device_kind()
        e = self.entries.get(_key(op, m, c, r, s, dtype, kind, freeze_phase))
        result = "hit" if e is not None else "miss"
        if e is not None and e.device_kind != kind:
            e, result = None, "stale"
        self.stats[result] += 1
        obs_registry.default_registry().counter(
            "autotune_lookups",
            "TuningTable consults at dispatch/search time").inc(
                op=op, result=result)
        return e

    def put(self, op: str, m: int, c: int, r: int, s: int, dtype,
            entry: TuneEntry, *, freeze_phase: Optional[int] = None) -> None:
        key = _key(op, m, c, r, s, dtype, entry.device_kind, freeze_phase)
        self.entries[key] = entry

    # -- persistence --------------------------------------------------------

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        assert path, "TuningTable.save needs a path"
        rows = []
        for (op, mb, c, r, s, dt, kind, fp), e in sorted(self.entries.items()):
            rows.append({"op": op, "m_bucket": mb, "c": c, "r": r, "s": s,
                         "dtype": dt, "device_kind": kind, "freeze_phase": fp,
                         **dataclasses.asdict(e)})
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps({"version": self.VERSION, "entries": rows},
                                indent=1))
        self.path = str(p)
        return str(p)

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        data = json.loads(pathlib.Path(path).read_text())
        assert data.get("version") == cls.VERSION, data.get("version")
        entries: Dict[Tuple, TuneEntry] = {}
        for row in data["entries"]:
            key = (row["op"], row["m_bucket"], row["c"], row["r"], row["s"],
                   row["dtype"], row["device_kind"], row["freeze_phase"])
            entries[key] = TuneEntry(
                block_m=row["block_m"], block_k=row["block_k"],
                block_n=row["block_n"], us=row["us"], source=row["source"],
                device_kind=row["device_kind"],
                fallback_reason=row.get("fallback_reason", ""))
        return cls(entries, path=path)


# Process-wide active table, consulted by kernels.ops at trace time.
_ACTIVE: Optional[TuningTable] = None


def get_table() -> Optional[TuningTable]:
    return _ACTIVE


def set_table(table: Optional[TuningTable]) -> Optional[TuningTable]:
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, table
    return prev


def load_table(path: str) -> TuningTable:
    """Load ``path`` (empty table if absent) and make it the active one."""
    p = pathlib.Path(path)
    table = TuningTable.load(str(p)) if p.exists() else TuningTable(path=str(p))
    set_table(table)
    return table


# --------------------------------------------------------------------------
# candidate generation + measurement
# --------------------------------------------------------------------------

def candidate_blocks(op: str, m: int, c: int, r: int, s: int, dtype,
                     *, specs: roofline.ChipSpecs = roofline.TPU_V5E_SPECS,
                     ) -> List[Tuple[int, int, int]]:
    """Legal (block_m, block_k, block_n) triples, roofline-pruned and
    ordered best-predicted-first.  Legal = divides the problem dims (the
    kernels' hard requirement) with the exact dims added as candidates so
    small decode shapes (m < 128) still tile."""
    def choices(dim: int) -> List[int]:
        ch = [b for b in BLOCK_CHOICES if dim % b == 0]
        if dim % _SUBLANE == 0 and dim <= max(BLOCK_CHOICES) and dim not in ch:
            ch.append(dim)  # whole-dim block for small shapes
        return ch or [dim]

    cands = [(bm, bk, bn)
             for bm in choices(m) for bk in choices(c) for bn in choices(s)]
    return roofline.prune_candidates(op, m, c, r, s, dtype, cands,
                                     specs=specs)


def _run_op(op: str, arrays, blocks: Tuple[int, int, int], interpret: bool):
    """One dispatcher-level call of ``op`` with explicit blocks — the same
    entry points the models use, so fallbacks fire exactly as they would
    in training/serving.  ``use_kernel=None`` (auto) keeps the dispatcher's
    platform gate live: forcing the kernel on a host that can't run it
    would crash at lowering instead of producing a capturable fallback."""
    from repro.kernels import ops
    bm, bk, bn = blocks
    kw = dict(use_kernel=None, interpret=interpret,
              block_m=bm, block_k=bk, block_n=bn)
    if op == "lowrank_fwd":
        x, u, v = arrays
        return ops.lowrank_apply(x, u, v, **kw)
    if op == "lowrank_ffn":
        x, gu, gv, uu, uv = arrays
        return ops.lowrank_ffn_apply(x, gu, gv, uu, uv, **kw)
    if op in ("lowrank_dx", "lowrank_du", "lowrank_dv"):
        x, u, v, dy = arrays
        grad_idx = {"lowrank_dx": 0, "lowrank_du": 1, "lowrank_dv": 2}[op]
        def loss(x, u, v):
            return jnp.vdot(ops.lowrank_apply(x, u, v, **kw), dy)
        return jax.grad(loss, argnums=grad_idx)(x, u, v)
    if op == "flash":
        from repro.kernels.flash_attention import flash_attention
        q, k, v = arrays
        return flash_attention(q, k, v, causal=True, block_q=bm,
                               block_kv=bn, interpret=interpret)
    raise ValueError(f"unknown op {op!r}")


def _make_arrays(op: str, m: int, c: int, r: int, s: int, dtype):
    ks = jax.random.split(jax.random.PRNGKey(m + c + r + s), 5)
    if op == "flash":
        q = jax.random.normal(ks[0], (4, m, r), jnp.float32) * 0.5
        k = jax.random.normal(ks[1], (4, s, r), jnp.float32) * 0.5
        v = jax.random.normal(ks[2], (4, s, r), jnp.float32)
        return tuple(a.astype(dtype) for a in (q, k, v))
    x = jax.random.normal(ks[0], (m, c), jnp.float32).astype(dtype)
    u = (jax.random.normal(ks[1], (c, r), jnp.float32) * 0.05).astype(dtype)
    v = (jax.random.normal(ks[2], (r, s), jnp.float32) * 0.1).astype(dtype)
    if op == "lowrank_ffn":
        uu = (jax.random.normal(ks[3], (c, r), jnp.float32) * 0.05).astype(dtype)
        uv = (jax.random.normal(ks[4], (r, s), jnp.float32) * 0.1).astype(dtype)
        return x, u, v, uu, uv
    if op in ("lowrank_dx", "lowrank_du", "lowrank_dv"):
        dy = jax.random.normal(ks[3], (m, s), jnp.float32).astype(dtype)
        return x, u, v, dy
    return x, u, v


def measure_candidate(op: str, m: int, c: int, r: int, s: int, dtype,
                      blocks: Tuple[int, int, int], *, interpret: bool = False,
                      iters: int = 3, warmup: int = 1,
                      ) -> Tuple[float, List[str]]:
    """(median seconds, fallback reasons) for one candidate through the
    real dispatcher.  A non-empty reason list means the timing measured the
    jnp fallback, not the kernel — the caller must not record it as
    ``measured``."""
    from repro.kernels import ops
    arrays = _make_arrays(op, m, c, r, s, dtype)
    with ops.capture_fallbacks() as fb:
        sec = time_fn(lambda: _run_op(op, arrays, blocks, interpret),
                      iters=iters, warmup=warmup)
    return sec, [f.reason for f in fb]


def search(shapes: Sequence[Tuple[int, int, int, int]],
           *, ops_list: Sequence[str] = ("lowrank_fwd",),
           dtype=jnp.float32, table: Optional[TuningTable] = None,
           freeze_phase: Optional[int] = None, budget: int = 4,
           measure: Optional[bool] = None, interpret: bool = False,
           iters: int = 3, warmup: int = 1, verbose: bool = False,
           ) -> TuningTable:
    """Tune every (op, shape) pair into ``table`` (the active table by
    default; created if none).

    ``measure=None`` measures exactly when the kernels can really run
    (TPU, or ``interpret=True``); otherwise the analytically-best pruned
    candidate is recorded with ``source="analytic"``.  Keys already present
    for this device_kind are cache hits and skipped."""
    from repro.kernels import ops as kops
    if table is None:
        table = get_table() or TuningTable()
        set_table(table)
    kind = device_kind()
    if measure is None:
        measure = kops.kernel_available() or interpret

    for op in ops_list:
        for (m, c, r, s) in shapes:
            hit = table.lookup(op, m, c, r, s, dtype,
                               freeze_phase=freeze_phase, kind=kind)
            if hit is not None:
                if verbose:
                    print(f"cache-hit: {op} {m}x{c}x{r}x{s} -> "
                          f"({hit.block_m},{hit.block_k},{hit.block_n}) "
                          f"[{hit.source}]")
                continue
            cands = candidate_blocks(op, m, c, r, s, dtype)
            if not cands:
                continue
            entry = None
            if measure:
                best, best_sec, reasons = None, float("inf"), []
                for cand in cands[:budget]:
                    sec, fb = measure_candidate(
                        op, m, c, r, s, dtype, cand, interpret=interpret,
                        iters=iters, warmup=warmup)
                    if fb:  # dispatcher fell back — timing is not the kernel
                        reasons = fb
                        break
                    if sec < best_sec:
                        best, best_sec = cand, sec
                if best is not None and not reasons:
                    entry = TuneEntry(*best, us=best_sec * 1e6,
                                      source="measured", device_kind=kind)
                elif reasons:
                    # demote: analytic winner, reason recorded — never a
                    # "measured" entry born from a fallback timing
                    entry = TuneEntry(
                        *cands[0],
                        us=roofline.kernel_candidate_time(
                            op, m, c, r, s, *cands[0], dtype) * 1e6,
                        source="analytic", device_kind=kind,
                        fallback_reason=reasons[0])
            if entry is None:
                entry = TuneEntry(
                    *cands[0],
                    us=roofline.kernel_candidate_time(
                        op, m, c, r, s, *cands[0], dtype) * 1e6,
                    source="analytic", device_kind=kind)
            table.put(op, m, c, r, s, dtype, entry,
                      freeze_phase=freeze_phase)
            if verbose:
                print(f"tuned: {op} {m}x{c}x{r}x{s} -> "
                      f"({entry.block_m},{entry.block_k},{entry.block_n}) "
                      f"{entry.us:.1f}us [{entry.source}]"
                      + (f" fallback={entry.fallback_reason}"
                         if entry.fallback_reason else ""))
    return table


# --------------------------------------------------------------------------
# CLI (CI smoke: table produced on run 1, all cache hits on run 2)
# --------------------------------------------------------------------------

def _parse_shape(text: str) -> Tuple[int, int, int, int]:
    parts = tuple(int(p) for p in text.lower().split("x"))
    assert len(parts) == 4, f"shape must be MxCxRxS, got {text!r}"
    return parts


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--table", required=True, help="tuning-table JSON path")
    ap.add_argument("--shapes", nargs="+", default=["256x512x128x256",
                                                    "512x1024x128x512"],
                    help="MxCxRxS shapes to tune")
    ap.add_argument("--ops", nargs="+", default=["lowrank_fwd", "lowrank_dx"],
                    choices=list(OPS))
    ap.add_argument("--budget", type=int, default=4,
                    help="candidates measured per key (analytically best k)")
    ap.add_argument("--interpret", action="store_true",
                    help="measure interpret-mode kernels (slow; CPU parity)")
    args = ap.parse_args(argv)

    table = load_table(args.table)
    loaded = len(table)
    print(f"table {args.table}: {loaded} entries loaded "
          f"({'cache' if loaded else 'fresh'}), device_kind={device_kind()}")
    search([_parse_shape(t) for t in args.shapes], ops_list=args.ops,
           table=table, budget=args.budget, interpret=args.interpret,
           verbose=True)
    path = table.save()
    print(f"saved {len(table)} entries -> {path}")


if __name__ == "__main__":
    main()
