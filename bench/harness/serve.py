"""Serving cells: the engine, the request generator, the loop that drives
the scheduler, and the check of served tokens against the reference.

The program serves through its normal path: ``ServeEngine`` with a
``ServeConfig`` (the rank-quantized ``export`` artifact of the
configuration file), and the engine's ``Scheduler``, stepped by this
module.  Requests are submitted with their due times on the scheduler's
own clock (``arrival``, virtual seconds from the window's start), so the
scheduler admits each when it is due, and every latency is taken from the
due time.

Request sizes are drawn by strata: ``n`` lengths at the quantiles
``(i + 0.5) / n`` of the mix's lognormal, clipped, then shuffled by the
seed, so every seed offers the same work in another order.  Token ids and
the order come from the seed.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import core, program, weights


# --------------------------------------------------------------------------
# the generator
# --------------------------------------------------------------------------

def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                            + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    return out


def strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the strata of a lognormal with the given median
    and sigma, clipped to [min, max]."""
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(strata(n)))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Req:
    due: float  # seconds from the window's start
    prompt: np.ndarray
    max_new: int


def make_requests(mix: dict, seed: int, n: int, vocab: int,
                  rate: Optional[float] = None) -> List[Req]:
    """``n`` requests of the mix: strata of lengths (and of Poisson gaps
    when ``rate`` is given) in an order drawn from the seed."""
    rng = np.random.default_rng(seed)
    plen = rng.permutation(lognormal_lengths(mix["prompt"], n))
    olen = rng.permutation(lognormal_lengths(mix["output"], n))
    if rate:
        due = np.cumsum(rng.permutation(-np.log(1.0 - strata(n)) / rate))
    else:
        due = np.zeros(n)
    return [Req(float(due[i]),
                rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                int(olen[i])) for i in range(n)]


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    engine: object
    sched: object
    served_shapes: object  # ShapeDtypeStruct tree of the exported params
    abstract: object  # the training-layout params the weights were made in
    tokens: Dict[int, List[float]]  # rid -> emission times (window clock)
    decode_steps: List[tuple]  # (time, [live KV lengths]) per decode step
    origin: float = 0.0
    longest_step: tuple = (0.0, 0.0)  # (seconds, start on the window clock)
    compiles_in_window: int = 0  # growth of the scheduler's program caches


def build(ctx: core.Context, mix: dict) -> Served:
    """The engine over the benchmark's weights, with every program the
    mix uses compiled and run once."""
    import jax
    from repro.serving import ServeConfig, ServeEngine

    cfg = ctx.config
    run_cfg = program.train_run(cfg, {"global_batch": 1,
                                      "seq_len": mix["max_len"],
                                      "remat": "none"},
                                interpret=ctx.interpret,
                                smoke_model=ctx.smoke_model)
    abstract = program.abstract_params(run_cfg)
    params = weights.make_params(abstract, ctx.seed)
    engine = ServeEngine(run_cfg, params, config=ServeConfig(
        max_len=mix["max_len"], num_slots=mix["slots"],
        prefill_len=mix["prefill_len"], block_size=cfg["serve"]["block_size"],
        export=cfg["serve"]["export"]))
    del params
    sched = engine.scheduler
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), engine.params)
    served = Served(engine, sched, shapes, abstract, {}, [])
    # warm-up: one request compiles and runs prefill, insert and decode
    warm = np.arange(1, 9, dtype=np.int32) % run_cfg.model.vocab_size
    sched.submit(warm, max_new=3)
    while sched.has_work():
        sched.step()
    jax.block_until_ready(sched.cache)
    sched.reset_stats()
    return served


def drive(served: Served, reqs: Sequence[Req], seconds: float, *,
          keep_pending: int = 0) -> Dict[int, Req]:
    """Step the scheduler for ``seconds`` of wall time.

    With ``keep_pending`` the queue is topped up from ``reqs`` so that at
    least that many requests are always waiting (a backlog); otherwise
    every request is submitted up front with its due time (open loop).
    Returns rid -> Req of every submitted request."""
    sched = served.sched
    decoded: List[int] = []

    def on_token(req, tok):
        t = time.monotonic() - served.origin
        served.tokens.setdefault(req.rid, []).append(t)
        if len(req.tokens) > 1:  # a decode step's token, not the prefill's
            decoded.append(int(req.prompt.size) + len(req.tokens) - 1)

    sched.on_token = on_token
    submitted: Dict[int, Req] = {}
    it = iter(reqs)

    def submit(r: Req) -> None:
        with core.span("bench.submit"):
            rid = sched.submit(r.prompt, max_new=r.max_new, arrival=r.due)
        submitted[rid] = r

    served.longest_step = (0.0, 0.0)
    served.origin = time.monotonic()
    sched.step()  # anchors the scheduler's clock at the window's start
    compiled = _compiled(sched)
    if not keep_pending:
        for r in it:
            submit(r)
    end = served.origin + seconds
    while True:
        now = time.monotonic()
        if now >= end:
            break
        if keep_pending:
            while len(sched.queue) < keep_pending:
                r = next(it, None)
                if r is None:
                    break
                submit(r)
        if not any(s.active for s in sched.slots):
            head = sched.queue[0].arrival if sched.queue else None
            if head is None:
                break
            wait = head - (now - served.origin)
            if wait > 0:
                time.sleep(min(wait, end - now, 0.002))
                continue
        decoded.clear()
        with core.span("bench.sched_step"):
            sched.step()
        took = time.monotonic() - now
        if took > served.longest_step[0]:
            served.longest_step = (took, now - served.origin)
        if decoded:
            served.decode_steps.append((time.monotonic() - served.origin,
                                        list(decoded)))
    sched.on_token = None
    served.compiles_in_window = _compiled(sched) - compiled
    return submitted


def _compiled(sched) -> int:
    return (sched.prefill_compiles + sched.insert_compiles
            + sched.decode_compiles)


def await_first_tokens(served: Served, rids: Sequence[int],
                       limit_s: float) -> None:
    """After the close, step the scheduler until every request in ``rids``
    has its first token, or ``limit_s`` seconds have passed."""
    sched = served.sched
    reqs = {r.rid: r for r in list(sched.finished.values())
            + [s.req for s in sched.slots if s.active] + list(sched.queue)}
    pending = [reqs[rid] for rid in rids if rid in reqs]
    deadline = time.monotonic() + limit_s
    while (any(r.t_first is None for r in pending) and sched.has_work()
           and time.monotonic() < deadline):
        sched.step()


def release(served: Served) -> None:
    """Drop the engine's device state (params, pools, programs)."""
    served.engine = None
    served.sched = None
    core.free_device_memory()


# --------------------------------------------------------------------------
# statistics
# --------------------------------------------------------------------------

def p95(values: Sequence[float]) -> float:
    """The 95th percentile, interpolated between the two nearest ranks;
    +inf (a missing answer) is infinite when either rank is."""
    if len(values) == 0:
        return math.inf
    x = np.sort(np.asarray(values, np.float64))
    k = 0.95 * (x.size - 1)
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    if math.isinf(x[hi]):
        return math.inf
    return float(x[lo] + (x[hi] - x[lo]) * (k - lo))


def with_tokens(sched) -> Dict[int, object]:
    """rid -> request of every request that served a token: finished, or
    still decoding when the window closed (its tokens so far are served
    tokens all the same)."""
    out = {rid: r for rid, r in sched.finished.items() if r.tokens}
    for s in sched.slots:
        if s.active and s.req.tokens:
            out[s.req.rid] = s.req
    return out


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------

def sample(served: Dict[int, object], seed: int, tokens: int,
           max_requests: int) -> List:
    """Requests drawn from the seed, the longest first, until their served
    tokens reach ``tokens`` or ``max_requests`` are drawn."""
    reqs = sorted(served.values(), key=lambda r: r.rid)
    if not reqs:
        return []
    longest = max(reqs, key=lambda r: r.prompt.size + len(r.tokens))
    rng = np.random.default_rng(seed + 1)
    order = [reqs[i] for i in rng.permutation(len(reqs))]
    out, n = [longest], len(longest.tokens)
    for r in order:
        if n >= tokens or len(out) >= max_requests:
            break
        if r is not longest:
            out.append(r)
            n += len(r.tokens)
    return out


def check(ctx: core.Context, served: Served, picked: Sequence,
          control: bool = False) -> dict:
    """Run the reference over the picked requests' prompts and served
    tokens; record ``token_gap``.  With ``control`` also return the gap
    of the tokens the float8 control puts first."""
    from harness import reference
    spec = reference.Spec.from_config(ctx.config)
    ctl = reference.Spec.from_config(ctx.config, mode="fp8") if control \
        else None
    params = reference.served_params(
        weights.make_params(served.abstract, ctx.seed), served.served_shapes)
    seqs = [(np.asarray(r.prompt), np.asarray(r.tokens, np.int32))
            for r in picked]
    mix = ctx.traffic
    gaps, ctl_gaps = reference.served_gaps(
        spec, params, seqs, mix["max_len"], mix["check"]["max_requests"],
        mix["check"]["tokens"] + mix["output"]["max"], ctl)
    del params
    out = {"token_gap": float(np.max(gaps)) if len(gaps) else math.inf,
           "tokens_checked": int(len(gaps)), "requests_checked": len(seqs)}
    if control:
        out["control_gap"] = float(np.max(ctl_gaps))
    return out


def controls(ctx: core.Context, seed: int) -> list:
    """Readings of the control (the float8 reference's first choices) and
    of the fault "a token altered where it is produced" (each sampled
    request's middle served token moved to the next id), on the requests
    the run's check sampled."""
    import copy
    served, picked = ctx.readings["served"], ctx.readings["picked"]
    got = check(ctx, served, picked, control=True)
    rows = [{"seed": seed, "what": "control_fp8",
             "token_gap": got["control_gap"]}]
    vocab = ctx.config["vocab_size"]
    bad = []
    for r in picked:
        r2 = copy.copy(r)
        r2.tokens = list(r.tokens)
        j = len(r2.tokens) // 2
        r2.tokens[j] = (r2.tokens[j] + 1) % vocab
        bad.append(r2)
    rows.append({"seed": seed, "what": "fault_token_altered",
                 "token_gap": check(ctx, served, bad)["token_gap"]})
    return rows
