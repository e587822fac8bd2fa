"""The fused low-rank kernels compile for a TPU v5e at smollm-360m's widths.

Every test compiles one kernel for one described (not attached) v5e chip —
the TPU compiler is installed without a chip — at the ranks the model's
rank quantization picks, with the blocks ``ops.resolve_blocks`` derives
from the default request.  A block rule the chip's compiler would refuse
(an unaligned slice, more VMEM than a kernel may use) fails here, on the
CPU.  The topology is described inside a fixture only: describing it loads
the TPU library, which one process at a time may hold.
"""

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
