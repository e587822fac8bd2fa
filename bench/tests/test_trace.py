"""The trace reduction, on a trace recorded on a TPU v5e and on a
hand-built one."""

from pathlib import Path

import pytest

from harness.trace import Event, Trace, base_name, op_name, union

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "trace_small.xplane.pb"


def test_op_names_from_hlo_text():
    text = ("%lowrank_ffn_fwd.10 = bf16[8192,2560]{1,0:T(8,128)(2,1)S(1)} "
            "custom-call(bf16[8192,960]{1,0} %bitcast.737)")
    assert op_name(text) == "lowrank_ffn_fwd.10"
    assert base_name(text) == "lowrank_ffn_fwd"
    assert base_name("%copy-done = bf16[256,512]{1,0} copy-done(...)") == "copy-done"
    assert base_name("fusion.3.1") == "fusion"


def test_union_clips_and_merges():
    assert union([(5, 9), (0, 2), (1, 3), (8, 12)], (1, 10)) == [(1, 3), (5, 10)]
    assert union([(0, 1)], (2, 3)) == []


def _toy():
    dev = "/device:TPU:0"
    ops = {dev: [Event("lowrank_fwd.1", 10, 30), Event("fusion.2", 25, 40),
                 Event("while.3", 50, 90), Event("fusion.4", 55, 60),
                 Event("lowrank_dx.5", 70, 80)]}
    mods = {dev: [Event("jit_serve_step(1)", 10, 40),
                  Event("jit_other(2)", 50, 90)]}
    spans = [Event("bench.window", 0, 100), Event("bench.batch", 40, 50),
             Event("bench.train_step", 0, 40), Event("bench.swap", 90, 100)]
    return Trace(ops, mods, sorted(spans, key=lambda e: e.start))


def test_busy_idle_and_labels():
    tr = _toy()
    w = tr.window()
    assert w == (0, 100)
    assert tr.busy_s(w) == pytest.approx(70e-9)  # [10, 40] and [50, 90]
    gaps = dict(tr.idle_gaps(w))
    assert gaps == pytest.approx({"bench.train_step": 10e-9,
                                  "bench.batch": 10e-9,
                                  "bench.swap": 10e-9})


def test_kernel_and_program_time():
    tr = _toy()
    w = tr.window()
    lowrank = lambda n: base_name(n).startswith("lowrank_")
    assert tr.op_time_s(w, lowrank) == pytest.approx(30e-9)
    assert tr.op_count(w, lowrank) == 2
    assert tr.module_time_s(w, lambda n: "serve_step" in n) == pytest.approx(30e-9)
    top = dict(tr.top_ops(w))
    assert "while" not in top  # a loop only holds other operations
    assert top["fusion"] == pytest.approx(20e-9)


def test_recorded_tpu_trace():
    """A v5e trace: three launches of ``lowrank_fwd`` and a jitted matmul
    under ``bench.train_step`` / ``bench.batch`` spans."""
    tr = Trace.from_file(str(TRACE))
    assert list(tr.ops) == ["/device:TPU:0"]
    w = tr.window()
    assert (w[1] - w[0]) / 1e9 == pytest.approx(0.103653027)
    busy = tr.busy_s(w)
    assert 0 < busy < (w[1] - w[0]) / 1e9
    assert busy == pytest.approx(7.857e-06)
    top = tr.top_ops(w)
    assert top[0][0] == "lowrank_fwd"
    lowrank = lambda n: base_name(n).startswith("lowrank_")
    assert tr.op_time_s(w, lowrank) == pytest.approx(top[0][1])
    labels = [name for name, _ in tr.idle_gaps(w)]
    assert labels[0] == "bench.batch" and "bench.train_step" in labels
    assert {e.name for e in tr.spans} == {"bench.window", "bench.batch",
                                          "bench.train_step"}
