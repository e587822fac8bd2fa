"""FLOP and byte counts from shapes, and the peak table."""

import json

import jax
import jax.numpy as jnp
import pytest

from harness import core, flops


def sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def tree(tie=True):
    t = {"embed": {"embedding": sds(100, 8)},
         "final_norm": {"scale": sds(8)},
         "stack": {"attn": {"wq": {"u": sds(2, 8, 3), "v": sds(2, 3, 8)},
                            "wk": {"u": sds(2, 8, 2), "v": sds(2, 2, 4)},
                            "wv": {"u": sds(2, 8, 2), "v": sds(2, 2, 4)},
                            "wo": {"kernel": sds(2, 8, 8)}},
                   "ffn": {"gate": {"u": sds(2, 8, 4), "v": sds(2, 4, 16)},
                           "up": {"u": sds(2, 8, 4), "v": sds(2, 4, 16)},
                           "down": {"u": sds(2, 16, 4), "v": sds(2, 4, 8)}}}}
    if not tie:
        t["unembed"] = {"kernel": sds(8, 100)}
    return t


CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "num_hidden_layers": 2, "tie_word_embeddings": True}


def test_linears():
    got = {ln.path: (ln.c, ln.r, ln.s, ln.count) for ln in flops.linears(tree())}
    assert got["stack/attn/wq"] == (8, 3, 8, 2)
    assert got["stack/attn/wo"] == (8, None, 8, 2)
    assert got["stack/ffn/down"] == (16, 4, 8, 2)


def test_train_flops_per_token():
    s = 7
    per_phase = {}
    for phase in (0, 1):
        want = 0.0
        for c, r, n in ((8, 3, 8), (8, 2, 4), (8, 2, 4), (8, 4, 16),
                        (8, 4, 16), (16, 4, 8)):
            w = 2 * r * n if phase == 0 else 2 * c * r
            want += 2 * (2 * 2 * r * (c + n) + w)
        want += 2 * 3 * 2 * 8 * 8  # dense wo: fwd, dx, dW
        want += 3 * 2 * 8 * 100  # tied head: fwd, dx, dE
        want += 2 * 3 * 2 * 2 * 2 * 4 * (s + 1) / 2  # causal attention
        per_phase[phase] = flops.train_flops_per_token(tree(), CFG, s, phase)
        assert per_phase[phase] == pytest.approx(want)
    # untied: the head is a dense kernel counted with the linears
    assert flops.train_flops_per_token(
        tree(tie=False), dict(CFG, tie_word_embeddings=False), s, 0) == \
        pytest.approx(per_phase[0])


def test_kernel_calls_follow_dispatch():
    m = 64
    calls = flops.lowrank_kernel_calls(tree(), m, phase=0)
    names = sorted((c.name, c.count) for c in calls)
    # wq, wk, wv, down: fwd, dx, dv; gate+up fused: ffn_fwd, then per
    # branch a recomputed fwd, dx and dv
    assert names.count(("lowrank_fwd", 2)) == 6
    assert names.count(("lowrank_dx", 2)) == 6
    assert names.count(("lowrank_dv", 2)) == 6
    assert names.count(("lowrank_ffn_fwd", 2)) == 1
    assert not [c for c in calls if c.name == "lowrank_du"]
    wq = [c for c in calls if c.name == "lowrank_fwd" and c.bytes ==
          2 * (m * 8 + 8 * 3 + 3 * 8 + m * 8)]
    assert wq and wq[0].flops == 2 * m * 3 * (8 + 8)
    remat = flops.lowrank_kernel_calls(tree(), m, 1, remat=True)
    assert sum(c.count for c in remat if c.name == "lowrank_ffn_fwd") == 4
    fell = flops.lowrank_kernel_calls(
        tree(), m, 0, fallback_shapes=[("lowrank_ffn", (m, 8, 16)),
                                       ("lowrank_fwd", (m, 16, 8))])
    assert sum(c.count for c in fell) == 3 * 3 * 2  # q, k, v only


def test_least_time_and_bound():
    c = flops.KernelCall("k", flops=2e12, bytes=1e9, count=1)
    assert c.least_s(1e12, 1e9) == pytest.approx(2.0)
    assert c.bound(1e12, 1e9) == "compute"
    assert c.bound(1e12, 1e8) == "memory"


def test_decode_terms():
    t = flops.decode_terms(tree(), CFG)
    f, b = t.work([3, 5])
    per_row = 2 * (3 * 16 + 2 * 12 + 2 * 12 + 64 + 4 * 24 + 4 * 24 + 4 * 24) * 2
    per_row += 2 * 8 * 100
    assert f == pytest.approx(2 * per_row + 4 * 2 * 4 * 2 * 8)
    weights = flops.served_weight_bytes(tree())
    assert b == pytest.approx(weights + 8 * 2 * 1 * 4 * 2 * 2)


def test_peaks_table():
    p = core.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        core.peaks("TPU v4")
    table = json.load(open(core.BENCH / "peaks.json"))
    assert "TPU v5e" in table["source"]
