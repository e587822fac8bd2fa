"""The program's spans and scopes read against a trace, at CPU size: the
``attn.core_ms_per_step.train`` reader on a hand-built trace named after the
tiny cell's own compiled programs, and ``tools/attribute.py`` on whole
traced runs of a tiny training and a tiny chat cell (the CPU trace has no
device operations, so all of the window reads idle and every program span
open in it takes its share)."""

import importlib.util

import pytest

from conftest import BENCH, CHAT, TRAIN_JOB, context, limits


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader():
    return _load(BENCH / "metrics" / "attn.core_ms_per_step.train.py",
                 "attn_core_reader")


def test_attn_core_reader_on_the_cells_programs():
    from harness import attribution
    from harness.trace import Event, Trace
    ctx = context(TRAIN_JOB, limits("smollm-360m.train.seqfreeze"))
    maps = attribution.train_scope_maps(ctx)
    core = {ph: [n for n, sc in maps[ph].own.items() if sc == "attn_core"]
            for ph in (0, 1)}
    other = [n for n, sc in maps[0].own.items() if sc == "ffn"]
    assert core[0] and core[1] and other
    dev = "/device:TPU:0"
    ops = [Event(core[0][0], 10, 30), Event(other[0], 30, 35),
           Event("copy.999999", 35, 40), Event(core[1][0], 60, 90)]
    spans = [Event("bench.window", 0, 100), Event("bench.swap", 45, 55)]
    ctx.trace_data = Trace({dev: ops}, {}, spans)
    ctx.window_ns = (0, 100)
    ctx.readings["steps"] = {0: 2, 1: 3}
    # 20 ns before the swap and 30 after, over 5 steps
    assert _reader().read(ctx) == pytest.approx(1e3 * 50e-9 / 5)


def test_attn_core_reader_reads_nothing_without_scopes(monkeypatch):
    import repro.obs
    from harness.trace import Event, Trace
    monkeypatch.delattr(repro.obs, "SCOPES")
    ctx = context(TRAIN_JOB, limits("smollm-360m.train.seqfreeze"))
    ctx.trace_data = Trace({"/device:TPU:0": [Event("fusion.1", 0, 5)]}, {},
                           [Event("bench.window", 0, 100),
                            Event("bench.swap", 45, 55)])
    ctx.window_ns = (0, 100)
    ctx.readings["steps"] = {0: 1, 1: 1}
    assert _reader().read(ctx) is None


@pytest.fixture
def attribute_run(monkeypatch, tmp_path):
    """A traced tiny run through ``tools/attribute.py``'s own path."""
    from harness import core
    monkeypatch.setattr(core, "enable_cache", lambda: "")
    monkeypatch.setattr(core, "TRACE_DIR", tmp_path / "trace")
    tool = _load(BENCH / "tools" / "attribute.py", "attribute_tool")

    def run(traffic, cell):
        ctx = context(traffic, limits(cell))
        ctx.trace = True
        bm = {"end_to_end": [{"name": "setup_s", "unit": "s"}],
              "per_layer": []}
        return tool.traced_run(ctx, bm, None)

    return run


def test_attribute_training_window(attribute_run):
    out, att = attribute_run(TRAIN_JOB, "smollm-360m.train.seqfreeze")
    assert out["correct"]
    spans = att["spans"]
    assert spans["data.batch"]["count"] == spans["data.shard"]["count"] >= 2
    assert spans["freeze.swap"]["count"] == 1
    assert {"freeze.merge", "freeze.partition", "freeze.unpark",
            "freeze.park"} <= set(spans)
    under = att["bench_idle_under_program"]
    for name in ("bench.batch", "bench.swap"):
        assert 0 < under[name]["under_program_s"] <= under[name]["idle_s"]
    assert att["scopes"]["maps_compile_s"] > 0
    counted = att["host_counters"]
    assert set(counted) == {"jit_compiles", "jit_compile_s",
                            "host_gc_pauses", "host_gc_s"}
    assert all(v >= 0 for v in counted.values())


def test_attribute_chat_window(attribute_run):
    out, att = attribute_run(CHAT, "smollm-360m.serve.chat")
    assert out["correct"]
    assert att["prefill_ms"]["count"] >= 1
    assert att["sched_idle_ms_per_decode"] > 0
    assert {"sched.step", "sched.admit", "sched.decode", "sched.readback",
            "sched.emit"} <= set(att["spans"])
