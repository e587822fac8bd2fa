"""Autotuner contract tests: table persistence + keying, fallback-demotion,
tuned-config parity in interpret mode, roofline candidate ordering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import roofline
from repro.kernels import autotune, ops, ref
from repro.kernels.autotune import TuneEntry, TuningTable


def _entry(bm=128, bk=256, bn=128, source="measured", kind=None, **kw):
    return TuneEntry(block_m=bm, block_k=bk, block_n=bn, us=12.5,
                     source=source, device_kind=kind or autotune.device_kind(),
                     **kw)


@pytest.fixture
def no_active_table():
    """Isolate the process-wide active table around each test."""
    prev = autotune.set_table(None)
    yield
    autotune.set_table(prev)


# --------------------------------------------------------------------------
# persistence + keying
# --------------------------------------------------------------------------

def test_table_disk_roundtrip(tmp_path):
    t = TuningTable()
    t.put("lowrank_fwd", 256, 512, 64, 256, jnp.float32, _entry())
    t.put("lowrank_dx", 512, 1024, 128, 512, jnp.bfloat16,
          _entry(source="analytic", fallback_reason="platform"),
          freeze_phase=1)
    path = t.save(str(tmp_path / "tune.json"))
    t2 = TuningTable.load(path)
    assert t2.entries == t.entries
    e = t2.lookup("lowrank_dx", 512, 1024, 128, 512, jnp.bfloat16,
                  freeze_phase=1)
    assert e is not None and e.fallback_reason == "platform"


def test_shape_bucket_keying():
    t = TuningTable()
    t.put("lowrank_fwd", 300, 512, 64, 256, jnp.float32, _entry())
    # every m in the same power-of-two bucket (256, 512] hits the same row
    for m in (257, 300, 400, 512):
        assert t.lookup("lowrank_fwd", m, 512, 64, 256, jnp.float32) is not None
    assert t.lookup("lowrank_fwd", 256, 512, 64, 256, jnp.float32) is None
    assert t.lookup("lowrank_fwd", 513, 512, 64, 256, jnp.float32) is None
    # weight geometry keys exactly — a different c is a different row
    assert t.lookup("lowrank_fwd", 300, 1024, 64, 256, jnp.float32) is None
    assert len(t) == 1


def test_search_does_not_mint_rows_per_batch_size(no_active_table):
    # distinct m values inside one bucket -> ONE table row, not three
    table = autotune.search([(260, 512, 64, 256), (300, 512, 64, 256),
                             (500, 512, 64, 256)],
                            ops_list=("lowrank_fwd",), measure=False)
    assert len(table) == 1


def test_stale_device_kind_is_a_miss():
    t = TuningTable()
    kind = autotune.device_kind()
    t.put("lowrank_fwd", 256, 512, 64, 256, jnp.float32, _entry())
    # foreign-chip key never matches this host's lookups
    t.put("lowrank_fwd", 256, 512, 64, 256, jnp.float32,
          _entry(kind="tpu-v9999"))
    assert t.lookup("lowrank_fwd", 256, 512, 64, 256, jnp.float32,
                    kind="tpu-v9999") is not None
    got = t.lookup("lowrank_fwd", 256, 512, 64, 256, jnp.float32, kind=kind)
    assert got is not None and got.device_kind == kind
    # a corrupted row (key kind != entry kind) is treated as a miss, not served
    key = autotune._key("lowrank_fwd", 256, 512, 64, 256, jnp.float32,
                        kind, None)
    t.entries[key] = _entry(kind="tpu-v9999")
    assert t.lookup("lowrank_fwd", 256, 512, 64, 256, jnp.float32,
                    kind=kind) is None


# --------------------------------------------------------------------------
# search: fallback demotion + measured interpret entries
# --------------------------------------------------------------------------

def test_no_measured_entry_from_fallback_timing(no_active_table):
    """On a host where the kernels cannot run, forcing measurement times the
    jnp fallback — the recorded entry must be analytic with the reason."""
    if ops.kernel_available():
        pytest.skip("kernels really run here; fallback cannot be forced")
    table = autotune.search([(256, 512, 64, 256)], ops_list=("lowrank_fwd",),
                            measure=True, interpret=False, iters=1, warmup=0)
    e = table.lookup("lowrank_fwd", 256, 512, 64, 256, jnp.float32)
    assert e is not None
    assert e.source == "analytic"
    assert e.fallback_reason == "platform"


def test_search_interpret_records_measured(no_active_table):
    table = autotune.search([(128, 256, 32, 128)], ops_list=("lowrank_fwd",),
                            budget=2, interpret=True, iters=1, warmup=0)
    e = table.lookup("lowrank_fwd", 128, 256, 32, 128, jnp.float32)
    assert e is not None
    assert e.source == "measured"
    assert e.fallback_reason == ""
    assert e.us > 0
    # second search over the same key is a pure cache hit: nothing re-measured
    n = len(table)
    autotune.search([(128, 256, 32, 128)], ops_list=("lowrank_fwd",),
                    budget=2, interpret=True, iters=1, warmup=0)
    assert len(table) == n


# --------------------------------------------------------------------------
# dispatcher consult (trace-time)
# --------------------------------------------------------------------------

def test_tuned_blocks_consult(no_active_table):
    req = (256, 512, 256)
    # no active table -> requested blocks stand
    assert ops._tuned_blocks("lowrank_fwd", 512, 1024, 128, 512, jnp.float32,
                             None, req) == req
    t = TuningTable()
    autotune.set_table(t)
    # miss -> requested blocks stand
    assert ops._tuned_blocks("lowrank_fwd", 512, 1024, 128, 512, jnp.float32,
                             None, req) == req
    t.put("lowrank_fwd", 512, 1024, 128, 512, jnp.float32,
          _entry(bm=128, bk=128, bn=128))
    assert ops._tuned_blocks("lowrank_fwd", 512, 1024, 128, 512, jnp.float32,
                             None, req) == (128, 128, 128)
    # bucketed hit whose blocks don't divide the actual m -> requested stand
    assert ops._tuned_blocks("lowrank_fwd", 320, 1024, 128, 512, jnp.float32,
                             None, req) == req


def test_autotuned_apply_matches_ref(no_active_table):
    m, c, r, s = 256, 512, 64, 256
    t = TuningTable()
    t.put("lowrank_fwd", m, c, r, s, jnp.float32, _entry(bm=128, bk=128, bn=128))
    autotune.set_table(t)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, (m, c), jnp.float32)
    u = jax.random.normal(k2, (c, r), jnp.float32) / np.sqrt(c)
    v = jax.random.normal(k3, (r, s), jnp.float32) / np.sqrt(r)
    got = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True,
                            autotune=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.lowrank_matmul_ref(x, u, v)),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# interpret parity of sweep-selected configs + roofline ordering
# --------------------------------------------------------------------------

SMOKE_SHAPES = [(256, 512, 64, 256), (512, 1024, 128, 512)]


@pytest.mark.parametrize("m,c,r,s", SMOKE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selected_config_interpret_parity(m, c, r, s, dtype):
    """The analytically-best pruned candidate must stay numerically faithful:
    f32 <= 1e-4 abs, bf16 <= one bf16 ulp (the k-block accumulation split
    can flip the final rounding), int8 exact (see test_int8_decode)."""
    bm, bk, bn = autotune.candidate_blocks("lowrank_fwd", m, c, r, s, dtype)[0]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(m + s), 3)
    x = jax.random.normal(k1, (m, c), jnp.float32).astype(dtype)
    u = (jax.random.normal(k2, (c, r), jnp.float32) / np.sqrt(c)).astype(dtype)
    v = (jax.random.normal(k3, (r, s), jnp.float32) / np.sqrt(r)).astype(dtype)
    got = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True,
                            block_m=bm, block_k=bk, block_n=bn)
    want = ref.lowrank_matmul_ref(x, u, v)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("m,c,r,s", SMOKE_SHAPES)
def test_predicted_vs_measured_candidate_ordering(m, c, r, s):
    """prune_candidates orders by the roofline prediction; the predicted
    winner's measured (interpret) time must not be badly beaten by a
    candidate the model ranked lower — the pruned ordering is what bounds
    the search budget, so a grossly wrong #1 would poison every table."""
    cands = autotune.candidate_blocks("lowrank_fwd", m, c, r, s, jnp.float32)
    assert cands, "pruning must keep at least one candidate"
    pred = [roofline.kernel_candidate_time("lowrank_fwd", m, c, r, s,
                                           *cand, jnp.float32)
            for cand in cands]
    assert pred == sorted(pred)  # ordered best-predicted-first
    top = cands[:3]
    # Rounds interleave the candidates, so a burst of other work on the
    # host lands on all of them alike, and each keeps its quickest round.
    meas = [float("inf")] * len(top)
    for _ in range(3):
        for i, cand in enumerate(top):
            sec, fb = autotune.measure_candidate(
                "lowrank_fwd", m, c, r, s, jnp.float32, cand,
                interpret=True, iters=2, warmup=1)
            assert not fb  # interpret mode really ran the kernel
            meas[i] = min(meas[i], sec)
    assert meas[0] <= 3.0 * min(meas)
