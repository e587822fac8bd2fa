"""Continuous-batching scheduler: slot recycling under queue pressure,
single-compile contract, per-request eos/max-new, preemption resume, MLA
fallback layout, and the ServeEngine facade (incl. the legacy-path eos
masking and pad_cache scale-axis regressions)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DistConfig, LRDConfig, RunConfig, ShapeConfig
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.serving import ServeEngine
from repro.serving.scheduler import Scheduler


def _make(arch="smollm-360m", kv_dtype=None, seed=0):
    cfg = get_smoke_config(arch)
    if kv_dtype:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 32, 2, "decode"),
                    lrd=LRDConfig(enabled=False),
                    dist=DistConfig(fsdp=False, remat="none"))
    params, _ = steps.init_params(run, jax.random.PRNGKey(seed))
    return run, params, make_host_mesh(1, 1)


def _prompts(n, vocab, lo=4, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(lo, hi)), dtype=np.int32)
            for _ in range(n)]


def test_queued_request_admitted_into_freed_slot_single_compile():
    """Acceptance: more requests than slots — a queued request enters a slot
    freed mid-decode and completes, with exactly ONE compiled serve_step."""
    run, params, mesh = _make()
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                      prefill_len=16, block_size=4)
    prompts = _prompts(5, run.model.vocab_size)
    # request 0 retires first (max_new=3), freeing its slot for request 2
    rids = [sched.submit(p, max_new=(3 if i == 0 else 8))
            for i, p in enumerate(prompts)]
    # drive manually until the overflow request lands in a slot
    while not any(s.req is not None and s.req.rid == 2 for s in sched.slots):
        sched.step()
        assert sched.has_work()
    assert sched.finished[0].done  # slot freed by an eos/max-new retirement
    assert not sched.finished.get(1, None) or True
    out = sched.run()
    assert set(out) == set(rids)
    assert all(len(out[r]) == (3 if r == 0 else 8) for r in rids)
    # the whole run — prefills, slot churn, retirement — compiled the decode
    # step exactly once (and prefill/insert once each)
    assert sched.decode_compiles == 1
    assert sched.prefill_compiles == 1
    stats = sched.latency_stats()
    assert stats["requests"] == 5 and stats["generated_tokens"] == 3 + 4 * 8


def test_scheduler_matches_legacy_fixed_batch_engine():
    """Continuous batching is a scheduling change, not a numerics change:
    every request's greedy tokens equal a solo fixed-batch decode."""
    run, params, mesh = _make()
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                      prefill_len=16, block_size=4)
    prompts = _prompts(5, run.model.vocab_size, seed=3)
    rids = [sched.submit(p, max_new=6) for p in prompts]
    out = sched.run()
    eng = ServeEngine(run, params, mesh, max_len=32)  # legacy path
    for r, p in zip(rids, prompts):
        ref = eng.generate(p[None, :], max_new=6)
        assert out[r].tolist() == ref[0].tolist()


def test_per_request_eos_and_max_new():
    run, params, mesh = _make()
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                      prefill_len=16, block_size=4)
    prompts = _prompts(3, run.model.vocab_size, seed=5)
    rids = [sched.submit(p, max_new=8) for p in prompts]
    ref = sched.run()
    # pick each request's 3rd token as its own eos: generation must stop
    # there (inclusive), freeing the slot immediately
    sched2 = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                       prefill_len=16, block_size=4)
    rids2 = [sched2.submit(p, max_new=8, eos_id=int(ref[r][2]))
             for r, p in zip(rids, prompts)]
    out = sched2.run()
    for r2, r in zip(rids2, rids):
        toks = out[r2].tolist()
        full = ref[r].tolist()
        eos = full[2]
        first = full.index(eos)  # eos may legitimately appear earlier
        assert toks == full[:first + 1]


def test_preemption_resumes_exactly_on_dry_pool():
    """Oversubscribed pool: growth failures preempt the youngest slot; the
    preempted request resumes by re-prefill and its tokens are unchanged."""
    run, params, mesh = _make()
    # 2 slots x max_len 32 would need 16 blocks; give 9 usable -> pressure
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                      prefill_len=24, block_size=4, num_blocks=10)
    prompts = _prompts(3, run.model.vocab_size, lo=8, hi=14, seed=7)
    rids = [sched.submit(p, max_new=10) for p in prompts]
    out = sched.run()
    assert sum(r.preemptions for r in sched.finished.values()) > 0
    assert sched.decode_compiles == 1  # preemption re-uses the same step
    eng = ServeEngine(run, params, mesh, max_len=32)
    for r, p in zip(rids, prompts):
        ref = eng.generate(p[None, :], max_new=10)
        assert out[r].tolist() == ref[0].tolist()


def test_unservable_request_raises_instead_of_spinning():
    """A head-of-queue request needing more blocks than the whole pool can
    ever free must fail loudly, not busy-loop run() forever."""
    run, params, mesh = _make()
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                      prefill_len=24, block_size=8, num_blocks=3)
    sched.submit(np.arange(1, 21, dtype=np.int32), max_new=4)  # needs 3 blk
    with pytest.raises(RuntimeError, match="raise num_blocks"):
        sched.run()


def test_mla_falls_back_to_contiguous_slot_layout():
    run, params, mesh = _make("deepseek-v3-671b")
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=24,
                      prefill_len=12)
    assert sched.layout == "slots"
    prompts = _prompts(3, run.model.vocab_size, lo=4, hi=10, seed=9)
    rids = [sched.submit(p, max_new=4) for p in prompts]
    out = sched.run()
    assert sched.decode_compiles == 1
    eng = ServeEngine(run, params, mesh, max_len=24)
    for r, p in zip(rids, prompts):
        ref = eng.generate(p[None, :], max_new=4)
        assert out[r].tolist() == ref[0].tolist()


def test_int8_paged_scheduler_serves():
    run, params, mesh = _make(kv_dtype="int8")
    sched = Scheduler(run, params, mesh, num_slots=2, max_len=32,
                      prefill_len=16, block_size=4)
    assert sched.layout == "paged"
    assert "kv_scale" in sched.cache["stack"]  # quantized pool + scales
    rids = [sched.submit(p, max_new=5)
            for p in _prompts(3, run.model.vocab_size, seed=11)]
    out = sched.run()
    assert sched.decode_compiles == 1
    for r in rids:
        toks = out[r]
        assert toks.shape == (5,)
        assert (toks >= 0).all() and (toks < run.model.vocab_padded).all()


# --------------------------------------------------------------------------
# ServeEngine facade + legacy-path regressions
# --------------------------------------------------------------------------

def test_engine_generate_routes_through_scheduler():
    run, params, mesh = _make()
    eng = ServeEngine(run, params, mesh, max_len=32, num_slots=2,
                      prefill_len=16, block_size=4)
    legacy = ServeEngine(run, params, mesh, max_len=32)
    prompts = np.stack([p[:6] for p in
                        _prompts(3, run.model.vocab_size, lo=6, hi=7)])
    out = eng.generate(prompts, max_new=5)
    ref = legacy.generate(prompts, max_new=5)
    np.testing.assert_array_equal(out, ref)
    assert eng.scheduler.decode_compiles == 1


def test_generate_falls_back_for_oversized_prompts():
    """Prompts that don't fit the scheduler's fixed prefill/window shapes
    keep the legacy fixed-batch behaviour instead of raising."""
    run, params, mesh = _make()
    eng = ServeEngine(run, params, mesh, max_len=64, num_slots=2,
                      prefill_len=8, block_size=4)
    legacy = ServeEngine(run, params, mesh, max_len=64)
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, run.model.vocab_size, (2, 20), dtype=np.int32)
    out = eng.generate(prompts, max_new=4)  # 20 > prefill_len 8
    np.testing.assert_array_equal(out, legacy.generate(prompts, max_new=4))


def test_generate_masks_finished_rows_to_eos():
    """Satellite regression: rows that emitted eos must read eos from then
    on, even while the fixed batch keeps stepping for the others."""
    run, params, mesh = _make()
    eng = ServeEngine(run, params, mesh, max_len=32)  # legacy path
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, run.model.vocab_size, (3, 8), dtype=np.int32)
    ref = eng.generate(prompts, max_new=8)
    eos = int(ref[0, 1])  # row 0 finishes at step 1 (or wherever eos hits)
    out = eng.generate(prompts, max_new=8, eos_id=eos)
    for row_ref, row in zip(ref, out):
        hits = np.flatnonzero(row_ref[:len(row)] == eos)
        if hits.size:  # before eos: unchanged; at/after: all eos
            k = hits[0]
            np.testing.assert_array_equal(row[:k + 1], row_ref[:k + 1])
            assert (row[k:] == eos).all()
        else:
            np.testing.assert_array_equal(row, row_ref[:len(row)])


def test_pad_cache_pads_quantized_scale_leaves():
    """Satellite regression: int8 caches must pad k_scale/v_scale along the
    kv_seq axis with k/v, or value/scale lengths desynchronize."""
    from repro.models.kvcache import init_quantized_kv
    from repro.serving import pad_cache

    cache = {"stack": init_quantized_kv((2,), batch=3, length=5, kv_heads=2,
                                        head_dim=8)}
    padded = pad_cache(cache, 12)
    for name, leaf in padded["stack"].items():
        assert leaf.shape[-3] == 12, name
    # values and scales stay consistent after a write at a padded position
    np.testing.assert_array_equal(
        np.asarray(padded["stack"]["k_scale"][..., 5:, :, :], np.float32), 0)
