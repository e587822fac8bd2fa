"""The program's spans and model scopes on the profiler's clock.

Host spans (``repro.obs.span``) land in a ``jax.profiler`` trace with the
scheduler's, the freezing swap's and the data feed's layer structure; model
scopes reach the compiled train step's ``op_name`` metadata in forward and
backward; the benchmark's reductions (``bench/harness/attribution.py``)
split idle time by the innermost program span and device time by scope;
the host hooks mark garbage collections and count compiles; and a
preempted run closes its trace window.
"""

import gc
import glob
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.configs.base import DistConfig, LRDConfig, RunConfig, ShapeConfig
from repro.launch import steps as steps_mod
from repro.launch import train as train_mod
from repro.launch.mesh import make_host_mesh
from repro.obs import MetricsRegistry, set_default_registry
from repro.serving.scheduler import Scheduler

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

from harness import attribution  # noqa: E402
from harness.trace import Event, Trace  # noqa: E402


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    try:
        yield reg
    finally:
        set_default_registry(prev)


def _spans_in(trace_dir):
    return attribution.program_spans_from_dir(str(trace_dir))


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


# -------------------------------------------------------------------------
# the span API
# -------------------------------------------------------------------------

def test_span_without_profiler_times_and_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("data.batch", step=3) as s:
        pass
    assert s.dur_s is not None and s.dur_s >= 0


def test_span_and_eventlog_span_reach_the_trace(tmp_path):
    log_path = tmp_path / "events.jsonl"
    with jax.profiler.trace(str(tmp_path / "tr")):
        with obs.span("data.shard", rows=2):
            jnp.ones(4).block_until_ready()
        with obs.EventLog(log_path) as log:
            with log.span("phase_swap", epoch=1, phase=1, boundary=0):
                pass
    from jax.profiler import ProfileData
    path = glob.glob(str(tmp_path / "tr" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events}
    assert {"data.shard", "phase_swap"} <= names
    ev = json.loads(log_path.read_text().splitlines()[0])
    assert ev["type"] == "phase_swap" and ev["dur_s"] >= 0


def test_scope_rejects_unknown_names():
    with obs.scope("attn_core"):
        pass
    with pytest.raises(ValueError):
        obs.scope("attention")


# -------------------------------------------------------------------------
# (a) scheduler and freezing spans nest as the layers do
# -------------------------------------------------------------------------

def _serve_run():
    cfg = get_smoke_config("smollm-360m")
    return RunConfig(model=cfg, shape=ShapeConfig("s", 32, 2, "decode"),
                     lrd=LRDConfig(enabled=False),
                     dist=DistConfig(fsdp=False, remat="none"))


def test_scheduler_spans_nest_in_sched_step(tmp_path):
    run = _serve_run()
    params, _ = steps_mod.init_params(run, jax.random.PRNGKey(0))
    sched = Scheduler(run, params, make_host_mesh(1, 1), num_slots=2,
                      max_len=32, prefill_len=24, block_size=4,
                      num_blocks=10)
    rng = np.random.default_rng(3)
    for n in (6, 9, 7):
        sched.submit(rng.integers(0, run.model.vocab_size, n,
                                  dtype=np.int32), max_new=4)
    sched.step()  # compiles prefill, insert and decode outside the trace
    admitted_before = sum(1 for s in sched.slots if s.active)
    with jax.profiler.trace(str(tmp_path / "tr")):
        for _ in range(12):
            if not sched.has_work():
                break
            sched.step()
    assert not sched.has_work()
    spans = _spans_in(tmp_path / "tr")
    steps = [e for e in spans if e.name == "sched.step"]
    assert steps
    for name in ("sched.admit", "sched.decode", "sched.readback",
                 "sched.emit"):
        kids = [e for e in spans if e.name == name]
        assert kids, name
        assert all(any(_inside(k, s) for s in steps) for k in kids), name
    for s in steps:  # one decode dispatch, then its readback, then emit
        kids = {k.name: k for k in spans if _inside(k, s)
                and k.name in ("sched.decode", "sched.readback",
                               "sched.emit")}
        if kids:
            assert (kids["sched.decode"].end <= kids["sched.readback"].start
                    <= kids["sched.readback"].end <= kids["sched.emit"].start)
    # one sched.prefill per admission inside the trace
    prefills = [e for e in spans if e.name == "sched.prefill"]
    assert len(prefills) == 3 - admitted_before >= 1
    admits = [e for e in spans if e.name == "sched.admit"]
    assert all(any(_inside(p, a) for a in admits) for p in prefills)


def _lrd_run(seq=16, batch=2):
    cfg = get_smoke_config("smollm-360m")
    return RunConfig(model=cfg, shape=ShapeConfig("t", seq, batch, "train"),
                     lrd=LRDConfig(enabled=True, min_dim=16,
                                   rank_quantize=False),
                     dist=DistConfig(fsdp=False, remat="none"))


def test_freeze_spans_nest_in_freeze_swap(tmp_path):
    run = _lrd_run()
    params, _ = steps_mod.init_params(run)
    mesh = make_host_mesh(1, 1)
    state, parked = steps_mod.make_sharded_train_state(run, params, 0, mesh)
    with jax.profiler.trace(str(tmp_path / "tr")):
        state, parked = steps_mod.repartition_state(
            run.optim, state, parked, 1, mesh=mesh, run=run)
        jax.block_until_ready(state)
    spans = _spans_in(tmp_path / "tr")
    swaps = [e for e in spans if e.name == "freeze.swap"]
    assert len(swaps) == 1
    kids = {e.name for e in spans if e.name.startswith("freeze.")
            and e.name != "freeze.swap"}
    # one device: nothing to place; no schedule: no rank adaptation
    assert kids == {"freeze.merge", "freeze.partition", "freeze.unpark",
                    "freeze.park"}
    assert all(_inside(e, swaps[0]) for e in spans
               if e.name.startswith("freeze."))


def test_data_spans_around_batch_and_placement(tmp_path):
    from repro.data import LMBatchIterator
    it = iter(LMBatchIterator(256, 16, 2, seed=1))
    mesh = make_host_mesh(1, 1)
    with jax.profiler.trace(str(tmp_path / "tr")):
        for _ in range(3):
            jax.block_until_ready(steps_mod.shard_batch(next(it), mesh))
    names = [e.name for e in _spans_in(tmp_path / "tr")]
    assert names.count("data.batch") == 3 and names.count("data.shard") == 3


# -------------------------------------------------------------------------
# (b) model scopes in the compiled train step
# -------------------------------------------------------------------------

_TRAIN_SCOPES = ("embed", "layers", "attn_proj", "attn_core", "ffn", "norm",
                 "head", "loss")


def _instructions(text):
    """(name, opcode, op_name or None) of every instruction that runs as
    an operation of its own: those inside fused computations and scalar
    combiners (``calls=``, ``to_apply=``) are left out."""
    import re
    inner = set(re.findall(r"(?:calls|to_apply)=%?([A-Za-z0-9_.\-]+)", text))
    out, current = [], None
    for line in text.splitlines():
        m = attribution._INSTR.match(line)
        if m is None:
            c = attribution._COMPUTATION.match(line)
            if c is not None:
                current = c.group(1)
            continue
        if current in inner:
            continue
        rest = line.split(" = ", 1)[1]
        if rest.startswith("("):  # a tuple shape
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            rest = rest[i + 1:].lstrip()
        else:
            rest = rest.split(" ", 1)[-1]
        op = re.match(r"([a-z][a-z0-9\-]*)\(", rest)
        o = attribution._OP_NAME.search(line)
        out.append((m.group(1), op.group(1) if op else "",
                    o.group(1) if o else None))
    return out


@pytest.mark.parametrize("phase", [0, 1])
def test_train_step_scopes_forward_and_backward(phase):
    run = _lrd_run(seq=32)
    mesh = make_host_mesh(1, 1)
    compiled = steps_mod.compile_train_step(
        run, mesh, steps_mod.build_train_step(run, mesh),
        steps_mod.abstract_state(run, mesh, phase),
        steps_mod.batch_specs(run, mesh), phase)
    text = compiled.as_text()
    instrs = _instructions(text)
    fwd, bwd = set(), set()
    for _, _, path in instrs:
        if path is None:
            continue
        sc = attribution.scope_of(path, obs.SCOPES)
        if sc is not None:
            (bwd if "transpose(" in path else fwd).add(sc)
    assert set(_TRAIN_SCOPES) <= fwd, fwd
    assert set(_TRAIN_SCOPES) <= bwd, bwd
    assert "optim" in fwd
    smap = attribution.scope_map(text, obs.SCOPES)
    work = [name for name, op, _ in instrs
            if op in ("fusion", "dot", "custom-call")]
    assert work
    # counted only where the instruction's own metadata names the scope:
    # its op_name, or its body's (the CPU compiler wraps single operations
    # in fusions with no metadata of their own); never a neighbour's guess
    named = {**smap.own, **smap.called}
    assert sum(1 for n in work if n in named) >= 0.9 * len(work)


def test_serve_step_scopes_paged_cache():
    run = _serve_run()
    params, _ = steps_mod.init_params(run, jax.random.PRNGKey(0))
    sched = Scheduler(run, params, make_host_mesh(1, 1), num_slots=2,
                      max_len=32, prefill_len=24, block_size=4,
                      num_blocks=10)
    sched.submit(np.arange(1, 7, dtype=np.int32), max_new=2)
    sched.step()
    text = sched._decode.lower(
        sched.params, sched.cache, jnp.asarray(sched._tokens),
        jnp.asarray(sched._positions), None).compile().as_text()
    found = set(attribution.scope_map(text, obs.SCOPES).own.values())
    assert {"kv_gather", "kv_write", "attn_core", "attn_proj"} <= found


def test_scopes_leave_the_jaxpr_alone():
    run = _lrd_run()
    params, _ = steps_mod.init_params(run)
    mesh = make_host_mesh(1, 1)
    state, _ = steps_mod.make_sharded_train_state(run, params, 0, mesh)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    step = steps_mod.build_train_step(run, mesh)
    with_scopes = jax.make_jaxpr(lambda st, b: step(st, b, phase=0))(
        state, batch)
    import contextlib
    import repro.models.attention as attn
    import repro.models.common as common
    import repro.models.lm as lm
    null = lambda name: contextlib.nullcontext()  # noqa: E731
    saved = [(m, m.scope) for m in (attn, common, lm, steps_mod)]
    try:
        for m, _ in saved:
            m.scope = null
        without = jax.make_jaxpr(lambda st, b: step(st, b, phase=0))(
            state, batch)
    finally:
        for m, f in saved:
            m.scope = f
    assert str(with_scopes) == str(without)


# -------------------------------------------------------------------------
# (c) the reductions, on hand-built traces
# -------------------------------------------------------------------------

DEV = "/device:TPU:0"


def _trace(ops, spans=()):
    return Trace({DEV: [Event(n, a, b) for n, a, b in ops]}, {},
                 sorted([Event(n, a, b) for n, a, b in spans],
                        key=lambda e: e.start))


def test_idle_by_innermost_span_nested_and_straddling():
    # busy [0,10] [40,50] [90,100]; idle (10,40) and (50,90)
    tr = _trace([("fusion.1", 0, 10), ("fusion.2", 40, 50),
                 ("fusion.3", 90, 100)])
    spans = [Event("sched.step", 5, 45), Event("sched.readback", 15, 30),
             Event("data.batch", 60, 70), Event("data.shard", 70, 80)]
    got = attribution.idle_by_span(tr, spans, (0, 100))
    # (10,40): step 10-15, readback 15-30, step 30-40
    # (50,90): nothing 50-60, batch 60-70 and shard 70-80 (a gap across
    # two spans), nothing 80-90
    assert got == pytest.approx({"sched.step": 15e-9,
                                 "sched.readback": 15e-9,
                                 "data.batch": 10e-9, "data.shard": 10e-9,
                                 None: 20e-9})
    assert sum(got.values()) == pytest.approx(70e-9)  # all the idle time
    assert attribution.by_prefix(got, "data.") == pytest.approx(
        {"data.batch": 10e-9, "data.shard": 10e-9})


def test_idle_by_span_clips_to_the_window_and_overlaps():
    tr = _trace([("fusion.1", 20, 30)])
    # a span from another thread overlapping the first: the later start wins
    spans = [Event("freeze.swap", 0, 60), Event("host.gc", 40, 70),
             Event("PJRT_Client_Compile", 200, 300)]
    got = attribution.idle_by_span(tr, spans, (10, 80))
    assert got == pytest.approx({"freeze.swap": 20e-9, "host.gc": 30e-9,
                                 None: 10e-9})


def test_span_durations_in_window():
    spans = [Event("sched.prefill", 0, 5e6), Event("sched.prefill", 2e7, 3e7),
             Event("sched.step", 0, 4e7)]
    assert attribution.durations(spans, "sched.prefill", (1, 1e9)) == \
        pytest.approx([0.01])


def test_scope_of_looks_through_transforms():
    sc = obs.SCOPES
    assert attribution.scope_of(
        "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
        "rematted_computation/attn_core/dot_general", sc) == "attn_core"
    assert attribution.scope_of("jit(step)/jvp(layers)/while/body/"
                                "dynamic_update_slice", sc) == "layers"
    assert attribution.scope_of("jit(step)/optim/sub", sc) == "optim"
    assert attribution.scope_of("jit(step)/jvp()/pow", sc) is None


def test_scope_map_reads_a_fusion_body_apart_from_neighbours():
    text = "\n".join([
        "%fused_computation (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        "  ROOT %multiply.1 = f32[8]{0} multiply(f32[8]{0} %param_0, "
        'f32[8]{0} %param_0), metadata={op_name="jit(s)/jvp(ffn)/mul"}',
        "}",
        "",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %wrapped_multiply = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, "
        "calls=%fused_computation",
        "  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %wrapped_multiply)",
        "}"])
    m = attribution.scope_map(text, obs.SCOPES)
    assert m.own == {"multiply.1": "ffn"}
    assert m.called == {"wrapped_multiply": "ffn"}  # its body's metadata
    assert m.near["copy.1"] == "ffn"  # a guess from its operand
    assert m.lookup("wrapped_multiply") == ("ffn", "called")
    assert m.lookup("copy.1") == ("ffn", "near")


def test_scope_time_maps_each_half_with_its_program():
    text0 = "\n".join([
        '  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(s)/transpose(jvp(attn_core))/mul"}',
        '  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(s)/jvp(ffn)/add"}',
        '  %copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.1)',
        '  ROOT %copy.3 = f32[8]{0} copy(f32[8]{0} %p)'])
    text1 = text0.replace("attn_core", "norm")
    m0 = attribution.scope_map(text0, obs.SCOPES)
    m1 = attribution.scope_map(text1, obs.SCOPES)
    assert m0.own == {"fusion.1": "attn_core", "fusion.2": "ffn"}
    # no scope of its own: guessed from its operand, and kept apart
    assert m0.near == {"copy.4": "attn_core"} and m0.called == {}
    assert m0.lookup("copy.3") == (None, None)
    assert m1.own["fusion.1"] == "norm" and m1.near["copy.4"] == "norm"
    tr = _trace([("fusion.1", 0, 10), ("fusion.2", 10, 15),
                 ("copy.3", 15, 20), ("while.4", 0, 20), ("copy.4", 20, 24),
                 ("fusion.1", 60, 70), ("copy.3", 70, 72)],
                spans=[("bench.swap", 30, 50)])
    parts = attribution.halves(tr, (0, 100))
    assert parts == ((0, 30), (50, 100))
    table = attribution.scope_time(tr, [(parts[0], m0), (parts[1], m1)])
    assert table == pytest.approx({
        ("attn_core", "own", "fusion"): 10e-9,
        ("ffn", "own", "fusion"): 5e-9,
        ("attn_core", "near", "copy"): 4e-9,
        ("norm", "own", "fusion"): 10e-9,
        (None, None, "copy"): 7e-9})  # the loop is left out
    assert attribution.sum_by(table, 0) == pytest.approx(
        {"attn_core": 14e-9, "ffn": 5e-9, "norm": 10e-9, None: 7e-9})
    assert attribution.sum_by(table, 1, 2)[("own", "fusion")] == \
        pytest.approx(25e-9)


# -------------------------------------------------------------------------
# (d) host hooks
# -------------------------------------------------------------------------

def test_host_hooks_mark_gc_and_uninstall(tmp_path, registry):
    obs.install_host_hooks()
    try:
        n = len(gc.callbacks)
        obs.install_host_hooks()  # idempotent
        assert len(gc.callbacks) == n
        with jax.profiler.trace(str(tmp_path / "tr")):
            gc.collect()
    finally:
        obs.uninstall_host_hooks()
    assert not any(isinstance(cb, obs.spans._GcHook) for cb in gc.callbacks)
    snap = registry.snapshot()
    assert snap["host_gc_pauses"]["series"]["generation=2"] >= 1
    assert snap["host_gc_s"]["series"][""] > 0
    gcs = [e for e in _spans_in(tmp_path / "tr") if e.name == "host.gc"]
    assert gcs
    before = registry.counter("host_gc_pauses").total()
    gc.collect()  # uninstalled: no longer counted
    assert registry.counter("host_gc_pauses").total() == before


def test_compiles_are_counted(registry):
    obs.watch_compiles()
    jax.jit(lambda x: x * 3 + 11.5)(jnp.arange(5.0)).block_until_ready()
    assert registry.counter("jit_compiles").total() >= 1
    assert registry.counter("jit_compile_s").total() > 0


# -------------------------------------------------------------------------
# the training entry point's trace window
# -------------------------------------------------------------------------

def test_preempted_run_closes_its_trace_window(tmp_path, monkeypatch):
    from repro.checkpoint import CheckpointManager

    def preempt_now(self):  # as if SIGTERM arrived before the first step
        self._preempted = True

    monkeypatch.setattr(CheckpointManager, "install_sigterm_handler",
                        preempt_now)
    train_mod.main([
        "--arch", "smollm-360m", "--smoke", "--steps", "6",
        "--steps-per-epoch", "3", "--global-batch", "2", "--seq-len", "16",
        "--ckpt-dir", str(tmp_path / "ckpt"), "--save-every", "1000",
        "--profile-steps", "0:4", "--obs", "--obs-dir", str(tmp_path / "o")])
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert glob.glob(str(tmp_path / "o" / "profile" / "**" / "*.xplane.pb"),
                     recursive=True)
    events = [json.loads(x) for x in
              (tmp_path / "o" / "events.jsonl").read_text().splitlines()]
    win = [e for e in events if e["type"] == "profile_window"]
    assert [(e["start_step"], e["stop_step"]) for e in win] == [(0, 1)]
    assert events[-1]["type"] == "run_end"
    assert events[-1]["reason"] == "preempt"
    assert not any(isinstance(cb, obs.spans._GcHook) for cb in gc.callbacks)
