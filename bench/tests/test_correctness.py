"""The correctness check, at a size the CPU can run: a sound run passes,
the control (the reference in float8 in the program's place) reads far
above the program, and a run with its timed path broken underneath comes
out ``correct: false`` for each fault the cells can have.

The limits are the cells' own (``bench/limits``).  On the chip the same
control and faults were read at the cells' sizes (``PERF.md`` section 2,
``bench/tools/calibrate.py``); here the tiny model is float32, so the
program reads near zero and the control and faults read what the lower
precision or the fault alone costs.
"""

import functools

import jax
import pytest

from conftest import CHAT, TRAIN_JOB, context, limits

TRAIN_CELL = "smollm-360m.train.seqfreeze"
CHAT_CELL = "smollm-360m.serve.chat"


def _train_ctx(**kw):
    return context(TRAIN_JOB, limits(TRAIN_CELL), **kw)


def test_sound_train_run_is_correct(run_cell):
    ctx = _train_ctx()
    out = run_cell(ctx)
    assert out["correct"], out["checks"]
    assert list(out["checks"]) == ["loss_gap", "grad_gap", "change_gap",
                                   "frozen_moved"]


def test_train_control_reads_above_the_program(run_cell):
    from harness import core
    ctx = _train_ctx()
    run_cell(ctx)
    prog = {k: c.value for k, c in ctx.checks.items()}
    rows = core.kind_driver("train_job").controls(ctx, ctx.seed)
    ctl = next(r for r in rows if r["what"] == "control_fp8")
    assert any(ctl[k] > max(3 * prog[k], ctx.limits[k])
               for k in ("loss_gap", "grad_gap", "change_gap")), (ctl, prog)


def _broken_compile(monkeypatch, fault):
    from repro.launch import steps

    def compile_train_step(run, mesh, train_step, state, batch, phase):
        step = jax.jit(functools.partial(train_step, phase=phase))
        if fault == "unchanged":
            def call(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
        else:  # half the batch left out, the mean taken over the rest
            def call(state, batch):
                half = jax.tree_util.tree_map(
                    lambda x: x[: x.shape[0] // 2], batch)
                return step(state, half)
        return call

    monkeypatch.setattr(steps, "compile_train_step", compile_train_step)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(run_cell, monkeypatch, fault):
    _broken_compile(monkeypatch, fault)
    out = run_cell(_train_ctx())
    assert out["correct"] is False, (fault, out["checks"])


def test_sound_serve_run_is_correct(run_cell):
    out = run_cell(context(CHAT, limits(CHAT_CELL), seconds=2.0))
    assert out["correct"], out["checks"]


def test_serve_control_reads_above_the_program(run_cell):
    from harness import core
    ctx = context(CHAT, limits(CHAT_CELL), seconds=2.0)
    run_cell(ctx)
    prog = ctx.checks["token_gap"].value
    rows = core.kind_driver("open_loop").controls(ctx, ctx.seed)
    ctl = next(r for r in rows if r["what"] == "control_fp8")
    assert ctl["token_gap"] > 3 * prog, (ctl, prog)


def test_serve_token_altered_is_not_correct(run_cell, monkeypatch):
    from repro.serving.scheduler import Scheduler
    emit = Scheduler._emit

    def altered(self, slot, tok):
        return emit(self, slot, (tok + 1) % 256)

    monkeypatch.setattr(Scheduler, "_emit", altered)
    out = run_cell(context(CHAT, limits(CHAT_CELL), seconds=2.0))
    assert out["correct"] is False, out["checks"]
