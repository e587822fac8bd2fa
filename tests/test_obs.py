"""Telemetry subsystem (repro.obs): registry, events, schema, wiring.

Covers the metrics registry semantics, the JSONL event log (schema
validation at emit time, mirror behaviour, spans), an instrumented
smoke training run (phase/rank-boundary events and the attribution
report built from them), serving lifecycle events, kernel-fallback and
autotune counters, the benchmark-side schema emission, and the
no-op-overhead guard: with telemetry disabled no file is created and
the compiled step's jaxpr is byte-identical.
"""

import dataclasses
import json
import logging
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import (DistConfig, LRDConfig, ObsConfig, RunConfig,
                                ShapeConfig)
from repro.kernels import autotune as at
from repro.kernels import ops
from repro.launch import steps as steps_mod
from repro.launch import train as train_mod
from repro.launch.mesh import make_host_mesh
from repro.obs import (EventLog, MetricsRegistry, NULL_LOG, default_registry,
                       render_text, set_default_registry, validate_event,
                       validate_file, validate_lines)
from repro.analysis import obs_report
from repro.serving.scheduler import Scheduler


# -------------------------------------------------------------------------
# metrics registry
# -------------------------------------------------------------------------

def test_counter_labels_and_total():
    reg = MetricsRegistry()
    c = reg.counter("kernel_fallbacks", "test")
    c.inc(op="lowrank_fwd", reason="platform")
    c.inc(op="lowrank_fwd", reason="platform")
    c.inc(2, op="ffn_fwd", reason="indivisible")
    assert c.value(op="lowrank_fwd", reason="platform") == 2
    assert c.value(op="ffn_fwd", reason="indivisible") == 2
    assert c.value(op="nope", reason="nope") == 0
    assert c.total() == 4
    # get-or-create returns the same instance
    assert reg.counter("kernel_fallbacks", "test") is c


def test_gauge_set_and_snapshot():
    reg = MetricsRegistry()
    g = reg.gauge("serve_active_slots", "test")
    g.set(3)
    g.set(1, pool="a")
    assert g.value() == 3
    assert g.value(pool="a") == 1
    snap = reg.snapshot()
    assert "serve_active_slots" in snap


def test_histogram_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("step_time_s", "test")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count() == 100
    assert h.percentile(50) == pytest.approx(np.percentile(range(1, 101), 50))
    s = h.summary()
    assert set(s) >= {"count", "sum", "p50", "p95", "p99"}
    assert s["p99"] >= s["p95"] >= s["p50"]


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x", "test")
    with pytest.raises(ValueError):
        reg.gauge("x", "test")


def test_default_registry_swap():
    fresh = MetricsRegistry()
    prev = set_default_registry(fresh)
    try:
        assert default_registry() is fresh
    finally:
        set_default_registry(prev)


# -------------------------------------------------------------------------
# event log + schema
# -------------------------------------------------------------------------

def test_disabled_log_writes_nothing(tmp_path):
    log = EventLog(None)
    assert not log.enabled and not log.active
    log.emit("run_start", kind="train")  # must be a no-op, not an error
    log.close()
    assert list(tmp_path.iterdir()) == []
    assert NULL_LOG.active is False


def test_eventlog_emits_valid_jsonl(tmp_path):
    p = tmp_path / "events.jsonl"
    with EventLog(p) as log:
        assert log.enabled and log.active
        log.emit("run_start", kind="train")
        log.emit("train_step", step=0, epoch=0, phase=-1, loss=1.0,
                 grad_norm=0.5, step_time_s=0.1, tokens_per_s=640.0,
                 total_rank=0, trainable_bytes=10, frozen_bytes=0,
                 opt_bytes=10, sync_bytes_per_step=0)
        with log.span("phase_swap", epoch=1, phase=0) as extra:
            extra["boundary"] = 1
        log.emit("run_end", kind="train")
    n = validate_file(p)
    assert n == 4
    events = [json.loads(l) for l in p.read_text().splitlines()]
    assert [e["type"] for e in events] == [
        "run_start", "train_step", "phase_swap", "run_end"]
    assert all(e["schema"] == 1 and "ts" in e for e in events)
    swap = events[2]
    assert swap["boundary"] == 1 and swap["dur_s"] >= 0


def test_emit_rejects_missing_required_field(tmp_path):
    with EventLog(tmp_path / "e.jsonl") as log:
        with pytest.raises(ValueError):
            log.emit("train_step", step=0)  # missing loss etc.
        with pytest.raises(ValueError):
            log.emit("no_such_event_type")


def test_validate_lines_reports_line_numbers():
    good = json.dumps({"schema": 1, "ts": 0.0, "type": "run_start",
                       "kind": "x"})
    bad = json.dumps({"schema": 1, "ts": 0.0, "type": "rank_adapt"})
    with pytest.raises(ValueError, match="2"):
        validate_lines([good, bad])


def test_mirror_text_renders_legacy_lines(tmp_path):
    seen = []
    with EventLog(None, mirror=seen.append, fmt="text") as log:
        log.emit("train_step", step=7, epoch=1, phase=0, loss=2.5,
                 grad_norm=1.25, step_time_s=0.05, tokens_per_s=100.0,
                 total_rank=3, trainable_bytes=1, frozen_bytes=1,
                 opt_bytes=1, sync_bytes_per_step=0)
        log.emit("run_start", _mirror=False, kind="train")
    assert len(seen) == 1
    # exact legacy format the CI greps rely on
    assert seen[0].startswith("step     7 epoch   1 phase  0 loss 2.5000")
    assert "gnorm 1.250" in seen[0]


def test_mirror_jsonl_format():
    seen = []
    with EventLog(None, mirror=seen.append, fmt="jsonl") as log:
        log.emit("run_start", kind="serve")
    assert len(seen) == 1
    assert json.loads(seen[0])["type"] == "run_start"


def test_render_text_unknown_type_is_none():
    assert render_text({"type": "serve_step", "active_slots": 1,
                        "queued": 0}) is None


# -------------------------------------------------------------------------
# instrumented smoke training run (sequential freeze + rank decay)
# -------------------------------------------------------------------------

@pytest.fixture(scope="module")
def train_trace(tmp_path_factory):
    """One instrumented 8-step run: 3 phases, rank decay at boundaries."""
    d = tmp_path_factory.mktemp("obs_train")
    train_mod.main([
        "--arch", "smollm-360m", "--smoke", "--steps", "8",
        "--steps-per-epoch", "3", "--global-batch", "2", "--seq-len", "32",
        "--lrd", "--lrd-min-dim", "16", "--no-rank-opt",
        "--freeze", "sequential", "--rank-schedule", "decay",
        "--rank-decay", "0.6", "--rank-min", "2", "--log-every", "4",
        "--ckpt-dir", str(d / "ckpt"), "--save-every", "1000",
        "--obs", "--obs-dir", str(d / "events")])
    return d / "events" / "events.jsonl"


def test_train_trace_schema_valid(train_trace):
    assert validate_file(train_trace) > 0


def test_train_trace_event_coverage(train_trace):
    events = obs_report.load_events(train_trace)
    kinds = [e["type"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("train_step") == 8
    # 8 steps at 3 steps/epoch -> phase swaps entering epochs 1 and 2
    swaps = [e for e in events if e["type"] == "phase_swap"]
    assert [s["epoch"] for s in swaps] == [1, 2]
    assert all(s["dur_s"] >= 0 for s in swaps)
    # decay schedule truncates at every boundary
    adapts = [e for e in events if e["type"] == "rank_adapt"]
    assert len(adapts) == 2
    assert all(a["shrunk"] for a in adapts)
    assert all(isinstance(a["rank_map"], dict) and a["rank_map"]
               for a in adapts)
    # one phase_compile per compiled phase, with the sync-bytes breakdown
    compiles = [e for e in events if e["type"] == "phase_compile"]
    assert len(compiles) >= 3
    assert all(e["sync_bytes_per_step"] == 0 for e in compiles)  # 1 device


def test_train_trace_step_records(train_trace):
    events = obs_report.load_events(train_trace)
    steps = [e for e in events if e["type"] == "train_step"]
    for s in steps:
        assert s["step_time_s"] > 0 and s["tokens_per_s"] > 0
        assert s["trainable_bytes"] > 0 and s["opt_bytes"] > 0
        assert s["total_rank"] == sum(s["rank_map"].values())
    # rank decay: summed live rank strictly decreases across epochs
    by_epoch = {}
    for s in steps:
        by_epoch.setdefault(s["epoch"], s["total_rank"])
    ranks = [by_epoch[e] for e in sorted(by_epoch)]
    assert ranks == sorted(ranks, reverse=True) and len(set(ranks)) == 3


def test_report_attribution_on_trace(train_trace, capsys):
    events = obs_report.load_events(train_trace)
    rows = obs_report.train_attribution(events)
    assert len(rows) == 3
    # Algorithm-2 alternation: phase = epoch % 2
    assert [r["phase"] for r in rows] == [0, 1, 0]
    assert rows[0]["boundary"] is None
    assert rows[1]["rank_adapted"] and rows[2]["rank_adapted"]
    assert rows[1]["truncated_groups"] > 0
    for prev, r in zip(rows, rows[1:]):
        assert r["d_total_rank"] == r["total_rank"] - prev["total_rank"] < 0
        assert r["d_trainable_bytes"] < 0  # freezing + truncation shrink it
    out = obs_report.report([str(train_trace)])
    assert out["train"] == rows
    text = capsys.readouterr().out
    assert "per-phase attribution" in text and "d-step%" in text


def test_train_without_obs_writes_nothing(tmp_path, capsys):
    train_mod.main([
        "--arch", "smollm-360m", "--smoke", "--steps", "2",
        "--steps-per-epoch", "4", "--global-batch", "2", "--seq-len", "32",
        "--log-every", "1", "--ckpt-dir", str(tmp_path / "ckpt"),
        "--save-every", "1000"])
    assert not list(tmp_path.rglob("*.jsonl"))
    # legacy console lines survive untouched (CI greps)
    out = capsys.readouterr().out
    assert "step     0 epoch   0" in out and "loss" in out


def test_obs_config_does_not_change_jaxpr():
    cfg = get_smoke_config("smollm-360m")
    base = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 2, "train"),
                     lrd=LRDConfig(enabled=True, min_dim=16,
                                   rank_quantize=False),
                     dist=DistConfig(fsdp=False, remat="none"))
    mesh = make_host_mesh(1, 1)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}

    def jaxpr_for(run):
        params, _ = steps_mod.init_params(run)
        state, _ = steps_mod.make_sharded_train_state(run, params, -1, mesh)
        step = steps_mod.build_train_step(run, mesh)
        return str(jax.make_jaxpr(
            lambda st, b: step(st, b, phase=-1))(state, batch))

    on = dataclasses.replace(base, obs=ObsConfig(enabled=True, run_dir="/x"))
    assert jaxpr_for(base) == jaxpr_for(on)


def test_parse_profile_steps():
    assert train_mod._parse_profile_steps("") == (-1, -1)
    assert train_mod._parse_profile_steps("3:7") == (3, 7)
    with pytest.raises(SystemExit):
        train_mod._parse_profile_steps("7")


# -------------------------------------------------------------------------
# serving lifecycle events + extended latency stats
# -------------------------------------------------------------------------

def _serve_run(seed=0):
    cfg = get_smoke_config("smollm-360m")
    return RunConfig(model=cfg, shape=ShapeConfig("s", 32, 2, "decode"),
                     lrd=LRDConfig(enabled=False),
                     dist=DistConfig(fsdp=False, remat="none"))


def _prompts(n, vocab, lo=4, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(l), dtype=np.int32)
            for l in rng.integers(lo, hi, n)]


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("obs_serve")
    run = _serve_run()
    params, _ = steps_mod.init_params(run, jax.random.PRNGKey(0))
    mesh = make_host_mesh(1, 1)
    p = d / "events.jsonl"
    with EventLog(p) as log:
        sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                          prefill_len=24, block_size=4, num_blocks=10,
                          obs=log)
        for i, pr in enumerate(_prompts(3, run.model.vocab_size,
                                        lo=8, hi=14, seed=7)):
            sched.submit(pr, max_new=10, arrival=0.001 * i)
        sched.run()
        stats = sched.latency_stats()
    return p, stats


def test_serve_trace_schema_valid(serve_trace):
    p, _ = serve_trace
    assert validate_file(p) > 0


def test_serve_lifecycle_events(serve_trace):
    p, stats = serve_trace
    events = obs_report.load_events(p)
    by_type = {}
    for e in events:
        by_type.setdefault(e["type"], []).append(e)
    assert len(by_type["request_queued"]) == 3
    assert len(by_type["request_retired"]) == 3
    # exactly one first-token event per request, even across preemptions
    firsts = by_type["request_first_token"]
    assert sorted(e["rid"] for e in firsts) == sorted(
        e["rid"] for e in by_type["request_queued"])
    assert all(e["ttft_s"] >= 0 for e in firsts)
    # the tiny pool forces preemption; resume prefills are flagged
    assert by_type.get("request_preempted")
    assert any(e["resume"] for e in by_type["request_prefill"])
    assert all(e["queue_wait_s"] >= 0 for e in by_type["request_prefill"])
    # compile-cache watermarks: the paged engine runs exactly one
    # prefill, one pool-insert, and one decode compile overall
    compiles = {e["fn"]: e["compiles"] for e in by_type["compile_cache"]}
    assert compiles == {"prefill": 1, "insert": 1, "decode": 1}
    assert all(e["active_slots"] <= 2 for e in by_type["serve_step"])
    assert max(e["pool_high_water"] for e in by_type["serve_step"]) <= 10


def test_serve_summary_from_trace(serve_trace):
    p, stats = serve_trace
    s = obs_report.serve_summary(obs_report.load_events(p))
    assert s["queued"] == s["retired"] == 3
    assert s["preempted_requests"] >= 1
    assert s["generated_tokens"] == stats["generated_tokens"]
    assert s["compiles"] == {"prefill": 1, "insert": 1, "decode": 1}
    assert s["p99_latency_s"] >= s["p50_latency_s"]
    assert obs_report.render_serve(s).startswith("serving summary:")


def test_latency_stats_extended_keys(serve_trace):
    _, stats = serve_trace
    assert set(stats) == set(Scheduler.STAT_KEYS)
    assert stats["p99_latency_s"] >= stats["p95_latency_s"] \
        >= stats["p50_latency_s"]
    assert stats["preempted_requests"] >= 1
    assert stats["preemptions"] >= stats["preempted_requests"]
    assert stats["p50_queue_wait_s"] >= 0


def test_latency_stats_explicit_zeros_when_empty():
    run = _serve_run()
    params, _ = steps_mod.init_params(run, jax.random.PRNGKey(0))
    mesh = make_host_mesh(1, 1)
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32)
    stats = sched.latency_stats()
    assert stats == {k: 0.0 for k in Scheduler.STAT_KEYS}


def test_ttft_anchored_to_original_arrival(serve_trace):
    """A preempted request's TTFT is measured once, from submission."""
    p, _ = serve_trace
    events = obs_report.load_events(p)
    preempted = {e["rid"] for e in events if e["type"] == "request_preempted"}
    assert preempted
    firsts = [e for e in events if e["type"] == "request_first_token"
              and e["rid"] in preempted]
    assert len(firsts) == len(preempted)  # one TTFT sample per request


# -------------------------------------------------------------------------
# kernel fallback + autotune counters
# -------------------------------------------------------------------------

def test_kernel_fallback_counter_and_once_logging(caplog):
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    try:
        # no block of 256/512 divides (2000, 1100), and whole-dimension
        # tiles overflow the VMEM budget
        x = jnp.ones((1, 2000, 1100), jnp.float32)
        u = jnp.ones((1100, 3), jnp.float32)
        v = jnp.ones((3, 6), jnp.float32)
        with caplog.at_level(logging.WARNING, logger="repro.kernels.ops"):
            ops.lowrank_apply(x, u, v, use_kernel=True)   # vmem
            ops.lowrank_apply(x, u, v, use_kernel=True)   # same shape again
        c = reg.counter("kernel_fallbacks", "")
        assert c.value(op="lowrank_fwd", reason="vmem") == 2
        warned = [r for r in caplog.records if "vmem" in r.message]
        assert len(warned) == 1  # once per unique (op, reason, shape)
        with caplog.at_level(logging.WARNING, logger="repro.kernels.ops"):
            caplog.clear()
            ops.lowrank_apply(x, u, v, use_kernel=False)  # explicit opt-out
        assert c.value(op="lowrank_fwd", reason="disabled") == 1
        assert not caplog.records  # expected reasons stay at DEBUG
    finally:
        set_default_registry(prev)


def test_capture_fallbacks_sink_still_works():
    x = jnp.ones((2000, 1100), jnp.float32)
    u = jnp.ones((1100, 3), jnp.float32)
    v = jnp.ones((3, 6), jnp.float32)
    with ops.capture_fallbacks() as sink:
        ops.lowrank_apply(x, u, v, use_kernel=True)
    assert [f.reason for f in sink] == ["vmem"]


def test_autotune_lookup_stats():
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    try:
        table = at.TuningTable()
        entry = at.TuneEntry(block_m=8, block_k=8, block_n=8, us=1.0,
                             source="measured", device_kind="cpu")
        table.put("lowrank_fwd", 256, 64, 8, 64, jnp.float32, entry)
        hit = table.lookup("lowrank_fwd", 256, 64, 8, 64, jnp.float32,
                           kind="cpu")
        assert hit is entry
        miss = table.lookup("lowrank_fwd", 256, 64, 8, 999, jnp.float32,
                            kind="cpu")
        assert miss is None
        # a manually-keyed entry from another chip is stale, not a hit
        stale_key = at._key("lowrank_fwd", 256, 64, 8, 64, jnp.float32,
                            "tpu-v4", None)
        table.entries[stale_key] = entry  # device_kind=cpu under tpu-v4 key
        assert table.lookup("lowrank_fwd", 256, 64, 8, 64, jnp.float32,
                            kind="tpu-v4") is None
        assert table.stats == {"hit": 1, "miss": 1, "stale": 1}
        c = reg.counter("autotune_lookups", "")
        assert c.value(op="lowrank_fwd", result="hit") == 1
        assert c.value(op="lowrank_fwd", result="miss") == 1
        assert c.value(op="lowrank_fwd", result="stale") == 1
    finally:
        set_default_registry(prev)


# -------------------------------------------------------------------------
# benchmark emission + report fixtures
# -------------------------------------------------------------------------

def test_benchmark_record_emits_events(tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks.common import record
    finally:
        sys.path.pop(0)
    rows = [{"name": "a", "us": 1.5}, {"name": "b", "us": 2.5}]
    record("obstest", rows, out_dir=str(tmp_path))
    assert json.loads((tmp_path / "BENCH_obstest.json").read_text()) == rows
    p = tmp_path / "BENCH_obstest.events.jsonl"
    assert validate_file(p) == 4  # run_start + 2 rows + run_end
    events = obs_report.load_events(p)
    assert [e["type"] for e in events] == [
        "run_start", "bench_row", "bench_row", "run_end"]
    assert events[1]["row"] == rows[0]


def test_report_fixture_segments_and_deltas():
    def step(i, phase, dt, sync, trainable, rank):
        return {"schema": 1, "ts": float(i), "type": "train_step",
                "step": i, "epoch": i // 2, "phase": phase, "loss": 1.0,
                "grad_norm": 0.1, "step_time_s": dt, "tokens_per_s": 64 / dt,
                "total_rank": rank, "trainable_bytes": trainable,
                "frozen_bytes": 100 - trainable, "opt_bytes": trainable,
                "sync_bytes_per_step": sync}

    events = [
        {"schema": 1, "ts": 0.0, "type": "run_start", "kind": "train"},
        step(0, -1, 0.10, 1000, 80, 12), step(1, -1, 0.10, 1000, 80, 12),
        {"schema": 1, "ts": 2.0, "type": "phase_swap", "epoch": 1,
         "phase": 0, "dur_s": 0.01},
        {"schema": 1, "ts": 2.0, "type": "rank_adapt", "epoch": 1,
         "boundary": 1, "shrunk": {"g": [12, 8]}, "rank_map": {"g": 8}},
        step(2, 0, 0.08, 600, 50, 8), step(3, 0, 0.08, 600, 50, 8),
        {"schema": 1, "ts": 4.0, "type": "run_end", "kind": "train"},
    ]
    for e in events:
        validate_event(e)
    rows = obs_report.train_attribution(events)
    assert len(rows) == 2
    assert rows[0]["boundary"] is None and not rows[0]["rank_adapted"]
    r = rows[1]
    assert r["rank_adapted"] and r["boundary"] == 1
    assert r["truncated_groups"] == 1
    assert r["d_step_time_pct"] == pytest.approx(-20.0)
    assert r["d_sync_bytes"] == -400
    assert r["d_trainable_bytes"] == -30
    assert r["d_total_rank"] == -4
    text = obs_report.render_train(rows)
    assert "-20.0" in text and "-400" in text


def test_partition_bytes_accounting():
    run = _serve_run()
    params, _ = steps_mod.init_params(run, jax.random.PRNGKey(0))
    mesh = make_host_mesh(1, 1)
    state, _ = steps_mod.make_sharded_train_state(
        dataclasses.replace(run, shape=ShapeConfig("t", 32, 2, "train")),
        params, -1, mesh)
    b = steps_mod.partition_bytes(state)
    assert set(b) == {"trainable_bytes", "frozen_bytes", "opt_bytes"}
    assert b["trainable_bytes"] > 0 and b["opt_bytes"] > 0
    assert b["frozen_bytes"] == 0  # phase -1: nothing frozen
