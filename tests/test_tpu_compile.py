"""The fused low-rank kernels compile for a TPU v5e at smollm-360m's widths.

Every test compiles one kernel for one described (not attached) v5e chip —
the TPU compiler is installed without a chip — at the ranks the model's
rank quantization picks, with the blocks ``ops.resolve_blocks`` derives
from the default request.  A block rule the chip's compiler would refuse
(an unaligned slice, more VMEM than a kernel may use) fails here, on the
CPU.  The topology is described inside a fixture only: describing it loads
the TPU library, which one process at a time may hold.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import LRDConfig, RunConfig, ShapeConfig
from repro.kernels import ops
from repro.kernels.lowrank_bwd import (lowrank_matmul_du, lowrank_matmul_dv,
                                       lowrank_matmul_dx)
from repro.kernels.lowrank_ffn import lowrank_gated_ffn
from repro.kernels.lowrank_matmul import lowrank_matmul

TRAIN_M = 8 * 1024  # tokens of one train step at chip_smoke's batch
DECODE_M = 8  # one decode step of eight serving slots
# (C, S) of every projection: q/o, k/v, gate/up, down
WIDTHS = [(960, 960), (960, 320), (960, 2560), (2560, 960)]
_LRD = LRDConfig()
REQUESTED = (_LRD.pallas_block_m, _LRD.pallas_block_k, _LRD.pallas_block_n)


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # the compiler logs nowhere
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def ranks():
    """(C, S) -> the rank smollm-360m's LRD init gives that projection."""
    run = RunConfig(model=get_config("smollm-360m"),
                    shape=ShapeConfig("t", 1024, 8, "train"),
                    lrd=LRDConfig(enabled=True))
    from repro.launch import steps
    layers = jax.eval_shape(lambda: steps.init_params(run)[0])["stack"]
    out = {}
    for group in (layers["attn"], layers["ffn"]):
        for f in group.values():
            _, c, r = f["u"].shape
            out[(c, f["v"].shape[-1])] = r
    return out


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _blocks(m, c, s, r):
    blocks = ops.resolve_blocks(m, c, s, r, jnp.bfloat16, REQUESTED)
    assert blocks is not None, (m, c, s, r)
    return dict(zip(("block_m", "block_k", "block_n"), blocks))


@pytest.mark.parametrize("kernel", ["fwd", "dx", "du", "dv"])
@pytest.mark.parametrize("c,s", WIDTHS)
def test_lowrank_kernel_compiles(kernel, c, s, one_chip, no_compile_cache,
                                 ranks):
    m, r = TRAIN_M, ranks[(c, s)]
    kw = _blocks(m, c, s, r)
    fn, shapes = {
        "fwd": (lambda x, u, v: lowrank_matmul(x, u, v, **kw),
                ((m, c), (c, r), (r, s))),
        "dx": (lambda dy, u, v: lowrank_matmul_dx(dy, u, v, **kw),
               ((m, s), (c, r), (r, s))),
        "du": (lambda x, dy, v: lowrank_matmul_du(x, dy, v, **kw),
               ((m, c), (m, s), (r, s))),
        "dv": (lambda x, u, dy: lowrank_matmul_dv(x, u, dy, **kw),
               ((m, c), (c, r), (m, s))),
    }[kernel]
    assert "tpu_custom_call" in _compile(fn, *shapes, sharding=one_chip)


@pytest.mark.parametrize("c,s", WIDTHS)
def test_lowrank_fwd_compiles_at_decode_batch(c, s, one_chip,
                                              no_compile_cache, ranks):
    m, r = DECODE_M, ranks[(c, s)]
    kw = _blocks(m, c, s, r)
    assert kw["block_m"] == m  # whole-dimension block: 8 rows, no tiling
    hlo = _compile(lambda x, u, v: lowrank_matmul(x, u, v, **kw),
                   (m, c), (c, r), (r, s), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_gated_ffn_compiles(one_chip, no_compile_cache, ranks):
    m, (c, f) = TRAIN_M, WIDTHS[2]
    r = ranks[(c, f)]
    kw = _blocks(m, c, f, 2 * r)  # both branches' accumulators per tile
    hlo = _compile(lambda x, gu, gv, uu, uv: lowrank_gated_ffn(
        x, gu, gv, uu, uv, **kw),
        (m, c), (c, r), (r, f), (c, r), (r, f), sharding=one_chip)
    assert "tpu_custom_call" in hlo


# the serving cells' paged pool: 64 slots x 1280 positions in 16-row blocks
SLOTS, BLOCK, MAX_BLOCKS = 64, 16, 80
NUM_BLOCKS = 1 + SLOTS * MAX_BLOCKS


def _pool_copies(hlo: str, shape) -> list:
    dims = "[" + ",".join(map(str, shape)) + "]"
    return [line for line in hlo.splitlines()
            if " copy(" in line and dims in line.split("=")[1][:80]]


# (query heads, KV heads, head dim) of archs a paged pool serves, and slots:
# smollm-360m, deepseek-coder-33b, olmoe-1b-7b (MHA), qwen2-72b
PAGED_HEADS = [(15, 5, 64, SLOTS), (56, 8, 128, SLOTS), (16, 16, 128, SLOTS),
               (64, 8, 128, 512)]


@pytest.mark.parametrize("heads,kv,hd,slots", PAGED_HEADS)
def test_paged_attention_reads_the_pool_as_it_lies(one_chip,
                                                   no_compile_cache, heads,
                                                   kv, hd, slots):
    """The pool layout (KV heads' key and value lanes side by side) is one
    the chip stores row-major and unpadded, so the kernel takes it with no
    relayout copy.  Its VMEM does not grow with the slots or the heads, so
    it compiles for every paged arch's widths, at 512 slots too (holding
    every slot's queries and outputs at once, 512 x 64 heads of 128, would
    take 2x its 128 MiB)."""
    from repro.kernels.paged_attention import paged_attention
    pool = (2, NUM_BLOCKS, BLOCK, kv * 2 * hd)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((slots, heads, hd), jnp.bfloat16), (pool, jnp.bfloat16),
        ((), jnp.int32), ((slots, MAX_BLOCKS), jnp.int32),
        ((slots,), jnp.int32))]
    compiled = jax.jit(paged_attention).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and not _pool_copies(hlo, pool)
    pool_bytes = 2 * NUM_BLOCKS * BLOCK * kv * 2 * hd * 2
    rest = slots * (heads * hd * 2 + MAX_BLOCKS * 4 + 4)
    assert compiled.memory_analysis().argument_size_in_bytes \
        < pool_bytes * 1.01 + rest  # no padding


@pytest.mark.parametrize("arch", ["smollm-360m", "deepseek-coder-33b"])
def test_paged_decode_step_writes_the_pool_in_place(one_chip,
                                                    no_compile_cache,
                                                    monkeypatch, arch):
    """Two layers, the serving cells' pool: the donated pool is aliased to
    the step's output and no copy has its shape."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch import steps
    from repro.launch.mesh import make_mesh
    from repro.serving import paged_cache as pc
    monkeypatch.setattr(ops, "kernel_available", lambda *a: True)
    model = dataclasses.replace(get_config(arch), num_layers=2)
    run = RunConfig(model=model, shape=ShapeConfig("s", 1280, 1, "decode"),
                    lrd=LRDConfig(enabled=True, use_pallas_kernel=True))
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=list(one_chip.device_set))
    rep = NamedSharding(mesh, PartitionSpec())
    spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), t)
    params = spec(jax.eval_shape(lambda: steps.init_params(run)[0]))
    cache = spec(jax.eval_shape(lambda: pc.init_paged_cache(
        model, SLOTS, NUM_BLOCKS, BLOCK, MAX_BLOCKS)))
    step = jax.jit(steps.build_serve_step(run, mesh), donate_argnums=(1,))
    compiled = step.lower(
        params, cache, jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32,
                                            sharding=rep),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rep),
        None).compile()
    hlo = compiled.as_text()
    pool = cache["stack"]["kv"].shape
    assert "paged_attention" in hlo and not _pool_copies(hlo, pool)
    pool_bytes = 2 * NUM_BLOCKS * BLOCK * pool[-1] * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_prefill_insert_writes_the_pool_in_place(one_chip, no_compile_cache):
    """A batch-1 prefill of the chat cell's 1024 positions lands in the
    donated pool in place: no copy of the pool, no pool-sized temporary."""
    from repro.serving import paged_cache as pc
    model = dataclasses.replace(get_config("smollm-360m"), num_layers=2)
    pool = jax.eval_shape(lambda: pc.init_paged_cache(
        model, SLOTS, NUM_BLOCKS, BLOCK, MAX_BLOCKS))
    spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    prefill = {"stack": {n: jax.ShapeDtypeStruct((2, 1, 1024, 5, 64),
                                                 jnp.bfloat16)
                         for n in ("k", "v")}}
    insert = jax.jit(pc.insert_prefill_paged, donate_argnums=(0,))
    compiled = insert.lower(spec(pool), spec(prefill), jax.ShapeDtypeStruct(
        (MAX_BLOCKS,), jnp.int32, sharding=one_chip)).compile()
    hlo = compiled.as_text()
    assert not _pool_copies(hlo, pool["stack"]["kv"].shape)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * NUM_BLOCKS * BLOCK * 640 * 2 / 100
