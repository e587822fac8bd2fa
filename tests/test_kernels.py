"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs the jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.lowrank_matmul import lowrank_matmul


def _mats(key, m, c, r, s, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (m, c), jnp.float32).astype(dtype)
    u = (jax.random.normal(k2, (c, r), jnp.float32) / np.sqrt(c)).astype(dtype)
    v = (jax.random.normal(k3, (r, s), jnp.float32) / np.sqrt(r)).astype(dtype)
    return x, u, v


SHAPES = [
    # (m, c, r, s, bm, bk, bn)
    (256, 512, 64, 256, 128, 256, 128),
    (512, 1024, 128, 512, 256, 512, 256),
    (256, 512, 128, 512, 256, 512, 256),
    (128, 256, 32, 128, 128, 256, 128),
    (512, 512, 256, 1024, 256, 512, 512),
]


@pytest.mark.parametrize("m,c,r,s,bm,bk,bn", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lowrank_matmul_matches_ref(m, c, r, s, bm, bk, bn, dtype):
    x, u, v = _mats(jax.random.PRNGKey(m + c + r + s), m, c, r, s, dtype)
    got = lowrank_matmul(x, u, v, block_m=bm, block_k=bk, block_n=bn,
                         interpret=True)
    want = ref.lowrank_matmul_ref(x, u, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_lowrank_apply_batched_and_fallback():
    # 3-D input routes through reshape; dims no requested block divides run
    # as whole-dimension blocks
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 100, 130), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (130, 16), jnp.float32) * 0.1
    v = jax.random.normal(jax.random.PRNGKey(2), (16, 70), jnp.float32) * 0.2
    got = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True)
    want = ref.lowrank_matmul_ref(x.reshape(-1, 130), u, v).reshape(2, 100, 70)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_lowrank_apply_divisible_uses_kernel_path():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 512), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (512, 64), jnp.float32) * 0.05
    v = jax.random.normal(jax.random.PRNGKey(5), (64, 256), jnp.float32) * 0.1
    got = ops.lowrank_apply(x, u, v, use_kernel=True, interpret=True)
    want = ref.lowrank_matmul_ref(x.reshape(-1, 512), u, v).reshape(2, 256, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_kernel_grad_matches_ref_grad():
    # the fused kernel sits on the forward path; training differentiates it
    # through the custom VJP (fused fwd kernel + composed jnp bwd).
    x, u, v = _mats(jax.random.PRNGKey(9), 128, 256, 32, 128, jnp.float32)

    def f_kernel(u, v):
        return jnp.sum(ops.lowrank_apply(x, u, v, use_kernel=True,
                                         block_m=128, block_k=256,
                                         block_n=128, interpret=True) ** 2)

    def f_ref(u, v):
        return jnp.sum(ref.lowrank_matmul_ref(x, u, v) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1))(u, v)
    gr = jax.grad(f_ref, argnums=(0, 1))(u, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_flash_attention_ref_blockwise_consistency():
    from repro.models.attention import blockwise_attention, dense_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(k1, (2, 128, 4, 16), jnp.float32) * 0.3
    k = jax.random.normal(k2, (2, 128, 2, 16), jnp.float32) * 0.3
    v = jax.random.normal(k3, (2, 128, 2, 16), jnp.float32)
    got = blockwise_attention(q, k, v, causal=True, block_q=32, block_kv=64)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    got_nc = blockwise_attention(q, k, v, causal=False, block_q=32, block_kv=32)
    want_nc = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got_nc), np.asarray(want_nc),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,c,rg,ru,f", [
    (256, 512, 64, 64, 256),
    (512, 1024, 128, 64, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lowrank_gated_ffn_matches_ref(m, c, rg, ru, f, dtype):
    from repro.kernels.lowrank_ffn import lowrank_gated_ffn

    ks = jax.random.split(jax.random.PRNGKey(m + f), 5)
    x = jax.random.normal(ks[0], (m, c), jnp.float32).astype(dtype)
    gu = (jax.random.normal(ks[1], (c, rg)) / np.sqrt(c)).astype(dtype)
    gv = (jax.random.normal(ks[2], (rg, f)) / np.sqrt(rg)).astype(dtype)
    uu = (jax.random.normal(ks[3], (c, ru)) / np.sqrt(c)).astype(dtype)
    uv = (jax.random.normal(ks[4], (ru, f)) / np.sqrt(ru)).astype(dtype)
    got = lowrank_gated_ffn(x, gu, gv, uu, uv, block_m=128, block_k=256,
                            block_n=128, interpret=True)
    want = ref.lowrank_gated_ffn_ref(x, gu, gv, uu, uv)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_double_buffered_fwd_matches_standard(dtype):
    """The explicit two-slot DMA pipeline variant is bit-identical to the
    standard fwd kernel: same blocks, same accumulation order — only the
    U-tile staging differs (interpret mode executes the async copies)."""
    m, c, r, s = 256, 1024, 64, 256
    x, u, v = _mats(jax.random.PRNGKey(7), m, c, r, s, dtype)
    std = lowrank_matmul(x, u, v, block_m=128, block_k=256, block_n=128,
                         interpret=True)
    db = lowrank_matmul(x, u, v, block_m=128, block_k=256, block_n=128,
                        interpret=True, double_buffer=True)
    np.testing.assert_array_equal(np.asarray(std), np.asarray(db))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_double_buffered_dx_matches_standard(dtype):
    from repro.kernels.lowrank_bwd import lowrank_matmul_dx

    m, c, r, s = 256, 512, 64, 512
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(8), 3)
    dy = jax.random.normal(k1, (m, s), jnp.float32).astype(dtype)
    u = (jax.random.normal(k2, (c, r), jnp.float32) / np.sqrt(c)).astype(dtype)
    v = (jax.random.normal(k3, (r, s), jnp.float32) / np.sqrt(r)).astype(dtype)
    std = lowrank_matmul_dx(dy, u, v, block_m=128, block_k=256, block_n=128,
                            interpret=True)
    db = lowrank_matmul_dx(dy, u, v, block_m=128, block_k=256, block_n=128,
                           interpret=True, double_buffer=True)
    np.testing.assert_array_equal(np.asarray(std), np.asarray(db))


@pytest.mark.parametrize("m,c,s,r,dtype,want", [
    # smollm-360m train step: q/o keep block_m, take whole 960 for k and n
    (8192, 960, 960, 239, jnp.bfloat16, (256, 960, 960)),
    # down projection: block_k 512 divides 2560, block_n whole 960
    (8192, 2560, 960, 256, jnp.bfloat16, (256, 512, 960)),
    # decode batch of 8 rows against the 320-wide k/v projection
    (8, 960, 320, 80, jnp.bfloat16, (8, 960, 320)),
    # no requested block divides and whole-dimension tiles overflow VMEM
    (2000, 1100, 6, 3, jnp.float32, None),
])
def test_resolve_blocks_rule(m, c, s, r, dtype, want):
    """Requested blocks stay where they divide; otherwise the whole
    dimension is the block, unless its tiles overflow the VMEM budget."""
    assert ops.resolve_blocks(m, c, s, r, dtype, (256, 512, 256)) == want
