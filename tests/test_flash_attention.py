"""Flash-attention Pallas kernel: shape/dtype sweep vs the jnp oracle
(interpret mode), incl. causal masking and rectangular q/kv."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref

CASES = [
    # (bh, sq, sk, d, causal, bq, bkv)
    (4, 512, 512, 64, True, 128, 128),
    (2, 256, 512, 128, False, 128, 256),
    (6, 512, 512, 128, True, 256, 512),
    (1, 1024, 1024, 64, True, 256, 256),
    (3, 128, 384, 64, False, 128, 128),
]


@pytest.mark.parametrize("bh,sq,sk,d,causal,bq,bkv", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(bh, sq, sk, d, causal, bq, bkv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(bh * sq + sk), 3)
    q = (jax.random.normal(ks[0], (bh, sq, d), jnp.float32) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (bh, sk, d), jnp.float32) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (bh, sk, d), jnp.float32).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_kv=bkv,
                          interpret=True)
    ref = flash_attention_ref(q[:, :, None], k[:, :, None], v[:, :, None],
                              causal=causal)[:, :, 0]
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_skips_future_blocks_exactly():
    """Causal output must be invariant to the content of future positions."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 64), jnp.float32)
    base = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                           interpret=True)
    k2 = k.at[:, 128:].set(999.0)  # poison strictly-future kv for q block 0
    v2 = v.at[:, 128:].set(-999.0)
    poisoned = flash_attention(q, k2, v2, causal=True, block_q=128,
                               block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(base[:, :128]),
                               np.asarray(poisoned[:, :128]), rtol=1e-5,
                               atol=1e-5)


def test_flash_impl_matches_blockwise_in_model():
    """attention_impl='flash' (Pallas, interpret mode by the kernel
    policy) must equal the blockwise jnp path end-to-end through a GQA
    model forward."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.configs.base import DistConfig, LRDConfig, RunConfig, ShapeConfig
    from repro.kernels.ops import KernelPolicy
    from repro.launch import steps
    from repro.models import lm as lm_mod

    cfg = get_smoke_config("qwen2-72b")
    cfg_b = dataclasses.replace(cfg, attention_impl="blockwise",
                                attention_block_q=16, attention_block_kv=16)
    cfg_f = dataclasses.replace(cfg, attention_impl="flash",
                                attention_block_q=16, attention_block_kv=16)
    run = RunConfig(model=cfg_b, shape=ShapeConfig("t", 64, 2, "train"),
                    lrd=LRDConfig(enabled=False),
                    dist=DistConfig(fsdp=False, remat="none"))
    params, _ = steps.init_params(run, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    lb, _, _ = lm_mod.lm_apply(params, toks, cfg_b, mode="full")
    lf, _, _ = lm_mod.lm_apply(params, toks, cfg_f, mode="full",
                               use_pallas=KernelPolicy(interpret=True))
    np.testing.assert_allclose(np.asarray(lb), np.asarray(lf), rtol=1e-4,
                               atol=1e-4)
