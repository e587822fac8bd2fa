"""Paged decode attention: the Pallas kernel (interpret mode) against the
gather path on the same pool, and the decode step that writes the pool in
place (DESIGN.md §8)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DistConfig, LRDConfig, RunConfig, ShapeConfig
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.models import kvcache as kvq
from repro.models import lm as lm_mod
from repro.models.attention import _paged_gather, dense_attention
from repro.obs import default_registry
from repro.serving import paged_cache as pc
from repro.serving.scheduler import Scheduler

BS, MAX_BLOCKS = 16, 40  # a fetch covers 16 blocks at hd 64, 20 at 128
KV, GROUP = 5, 3  # GQA: 3 query heads per KV head
MAX_LEN = MAX_BLOCKS * BS
SLOTS = 20  # two grid steps of 10 slots
RETIRED = (0, 13)  # rows at the sink block 0


def _table(rng, share_prefix: bool):
    """Distinct random blocks per slot; retired slots at the sink block 0;
    with ``share_prefix`` slots 1 and 2 share their first two blocks."""
    ids = rng.permutation(np.arange(1, 1 + SLOTS * MAX_BLOCKS))
    table = ids.reshape(SLOTS, MAX_BLOCKS).astype(np.int32)
    table[list(RETIRED)] = 0
    if share_prefix:
        table[2, :2] = table[1, :2]
    return table


@pytest.mark.parametrize("length,share_prefix,hd", [
    (1, False, 64), (15, False, 64), (16, False, 64), (17, False, 64),
    (40, False, 64),  # inside the first fetch
    (300, False, 64),  # inside the second fetch
    (MAX_LEN, False, 64),
    (37, True, 64),  # two slots read the same prefix blocks
    (330, False, 128),  # keys and values read apart
    (MAX_LEN, False, 128),
])
def test_kernel_matches_gather_and_dense(length, share_prefix, hd):
    rng = np.random.default_rng(length)
    lengths = rng.integers(1, MAX_LEN + 1, SLOTS).astype(np.int32)
    lengths[[1, 2, 3, 11]] = length, max(length - 3, 1), MAX_LEN, length
    lengths[list(RETIRED)] = 1
    table = _table(rng, share_prefix)
    num_blocks = 1 + SLOTS * MAX_BLOCKS
    pool = jnp.asarray(rng.standard_normal((2, num_blocks, BS, KV * 2 * hd)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((SLOTS, KV * GROUP, hd)) / 8,
                    jnp.bfloat16)
    got = paged_attention(q, pool, jnp.int32(1), jnp.asarray(table),
                          jnp.asarray(lengths), interpret=True)
    k, v = kvq.split_kv(_paged_gather(pool, 1, jnp.asarray(table)), KV)
    want = dense_attention(q[:, None], k, v, causal=False,
                           kv_len=jnp.asarray(lengths))[:, 0]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # retired slots are skipped: zeros, read by no one
    want[list(RETIRED)] = 0
    # both round to bfloat16 once, from float32 softmax and accumulation
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2 ** -7 * np.abs(want).max())


def _run(kv_dtype=None):
    cfg = get_smoke_config("smollm-360m")
    if kv_dtype:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    return RunConfig(model=cfg, shape=ShapeConfig("s", 32, 2, "decode"),
                     lrd=LRDConfig(enabled=False, pallas_interpret=True),
                     dist=DistConfig(fsdp=False, remat="none"))


def _paged(run, num_slots=2, max_len=32, bs=4, seed=1):
    """A pool filled with noise, so any row the step rewrites shows."""
    mb = pc.blocks_for(max_len, bs)
    pages = pc.PageTableManager(num_slots, mb, 1 + num_slots * mb, bs)
    for slot in range(num_slots):
        assert pages.admit(slot, max_len)
    cache = pc.init_paged_cache(run.model, num_slots, 1 + num_slots * mb,
                                bs, mb)
    rng = np.random.default_rng(seed)
    cache = {name: dict(stack, kv=jnp.asarray(
        rng.standard_normal(stack["kv"].shape), stack["kv"].dtype))
        for name, stack in cache.items()}
    return pc.with_page_table(cache, pages.table), pages.table


def test_decode_step_rewrites_only_its_rows():
    run = _run()
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    cache, table = _paged(run)
    pos = np.asarray([5, 13], np.int32)
    tokens = jnp.asarray([[3], [7]], jnp.int32)
    _, new, _ = lm_mod.lm_apply(params, tokens, run.model, mode="decode",
                                cache=cache, pos=jnp.asarray(pos),
                                use_pallas=steps.kernel_policy(run))
    before = np.asarray(cache["stack"]["kv"], np.float32)
    after = np.asarray(new["stack"]["kv"], np.float32)
    bs = before.shape[2]
    written = np.zeros(before.shape[:3], bool)
    for slot, p in enumerate(pos):
        written[:, table[slot, p // bs], p % bs] = True
    assert np.array_equal(before[~written], after[~written])
    assert not np.array_equal(before[written], after[written])
    np.testing.assert_array_equal(new["stack"]["page_table"],
                                  cache["stack"]["page_table"])


def test_serve_step_aliases_the_pool():
    run = _run()
    mesh = make_host_mesh(1, 1)
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    cache, _ = _paged(run)
    step = jax.jit(steps.build_serve_step(run, mesh), donate_argnums=(1,))
    args = (params, cache, jnp.zeros((2, 1), jnp.int32),
            jnp.asarray([5, 13], jnp.int32), None)
    hlo = step.lower(*args).compile().as_text()
    pool = cache["stack"]["kv"]
    shape = "[" + ",".join(map(str, pool.shape)) + "]"
    params_n = len(jax.tree_util.tree_leaves(params))
    # the pool is the first leaf of the donated cache argument
    assert f"{params_n}, {{}}, may-alias" in hlo \
        or f"{params_n}, {{}}, must-alias" in hlo
    copies = [line for line in hlo.splitlines()
              if " copy(" in line and shape in line.split("=")[1][:60]]
    assert not copies, copies


def test_decode_compiles_once_across_block_counts():
    run = _run()
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    sched = Scheduler(run, params, make_host_mesh(1, 1), num_slots=2,
                      max_len=32, prefill_len=8, block_size=4)
    rng = np.random.default_rng(3)
    for n, new in ((3, 20), (7, 2), (5, 13)):
        sched.submit(rng.integers(0, run.model.vocab_size, n, np.int32),
                     max_new=new)
    held = set()
    while sched.has_work():
        sched.step()
        held.update(sched.pages.allocated(i) for i in range(2))
    assert len(held - {0}) >= 4  # slots held 1, 2, ... 6 blocks
    assert sched.decode_compiles == 1


def _fallbacks(reason):
    return default_registry().counter("kernel_fallbacks", "").value(
        op="paged_attention", reason=reason)


@pytest.mark.parametrize("kv_dtype,chunk,reason", [
    (None, 1, None), ("int8", 1, "int8_pool"), (None, 3, "chunk")])
def test_fallbacks_counted_where_the_gather_path_runs(kv_dtype, chunk,
                                                      reason):
    run = _run(kv_dtype)
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    cache, _ = _paged(run)
    before = {r: _fallbacks(r) for r in ("int8_pool", "chunk", "platform")}
    with ops.capture_fallbacks() as seen:
        jax.eval_shape(
            lambda c: lm_mod.lm_apply(
                params, jnp.zeros((2, chunk), jnp.int32), run.model,
                mode="decode", cache=c, pos=jnp.asarray([5, 13], jnp.int32),
                use_pallas=steps.kernel_policy(run)), cache)
    ours = [f for f in seen if f.op == "paged_attention"]
    assert [f.reason for f in ours] == ([reason] if reason else [])
    for r, n in before.items():
        assert _fallbacks(r) == n + (r == reason)
