"""Mixture-of-Experts with three dispatch paths:

* ``ep``      — production expert parallelism: shard_map over the ``model``
                axis, two-stage capacity-bounded scatter + ``all_to_all``
                (GShard/DeepSpeed-MoE style).  Tokens are owned 1:1 by devices
                (batch over data, seq over model); experts live model-sharded.
                Used for train/prefill shapes.
* ``gshard``  — one-hot dispatch einsum with capacity (T, E, C) tensors; used
                for decode shapes where the token count is tiny and an
                all_to_all over 256 devices would be degenerate.
* ``dense``   — compute every expert (tiny smoke tests only).

All expert projections route through ``common.linear`` param groups and are
therefore LRD-decomposable like any other matrix (the paper's technique is
*most* profitable here: 256 experts x 3 matrices per layer in deepseek-v3).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.configs.base import ModelConfig
from repro.distributed import current_mesh, shard
from repro.models import common
from repro.models.common import Params, linear


def moe_init(dec, key, path: str, cfg: ModelConfig, stack: Tuple[int, ...] = ()) -> Params:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": {"kernel": (jax.random.normal(ks[0], stack + (d, e), jnp.float32)
                              * (d ** -0.5)).astype(jnp.float32)},
        "experts": {
            "gate": dec.linear(ks[1], f"{path}/experts/gate", d, f, stack=stack + (e,)),
            "up": dec.linear(ks[2], f"{path}/experts/up", d, f, stack=stack + (e,)),
            "down": dec.linear(ks[3], f"{path}/experts/down", f, d, stack=stack + (e,)),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = common.ffn_init(
            dec, ks[4], f"{path}/shared", d, f * cfg.num_shared_experts,
            "swiglu", cfg.pdtype, stack=stack)
    return p


def _router(p: Params, xf: jax.Array, cfg: ModelConfig):
    """Softmax router with top-k; returns (weights (t,k), ids (t,k), aux)."""
    logits = jnp.dot(xf.astype(jnp.float32), p["router"]["kernel"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum(frac_tokens * frac_probs).
    e = cfg.num_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
        / jnp.maximum(xf.shape[0], 1), axis=0)
    aux = e * jnp.sum(me * ce)
    return topw, topi, aux


def _expert_ffn(experts: Params, x_e: jax.Array) -> jax.Array:
    """x_e: (E_local, C, d) -> (E_local, C, d), per-expert SwiGLU.

    Expert weights may be dense (E,d,f) or LRD pairs (E,d,r)+(E,r,f); both are
    einsum-batched over the expert dim.
    """

    def mat(p, t):  # t: (E, C, a) @ (E, a, b)
        if "kernel" in p:
            return jnp.einsum("ecd,edf->ecf", t, p["kernel"],
                              preferred_element_type=jnp.float32).astype(t.dtype)
        tt = jnp.einsum("ecd,edr->ecr", t, p["u"],
                        preferred_element_type=jnp.float32).astype(t.dtype)
        return jnp.einsum("ecr,erf->ecf", tt, p["v"],
                          preferred_element_type=jnp.float32).astype(t.dtype)

    g = mat(experts["gate"], x_e)
    u = mat(experts["up"], x_e)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x_e.dtype)
    return mat(experts["down"], h)


# --------------------------------------------------------------------------
# gshard one-hot dispatch (decode / small token counts)
# --------------------------------------------------------------------------

def _moe_gshard(p: Params, x: jax.Array, cfg: ModelConfig):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    topw, topi, aux = _router(p, xf, cfg)
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cap = max(1, int(t * k / e * cfg.capacity_factor))

    oh = jax.nn.one_hot(topi, e, dtype=jnp.int32)  # (t, k, e)
    gate = jnp.einsum("tk,tke->te", topw, oh.astype(jnp.float32))
    mask = jnp.sum(oh, axis=1)  # (t, e) 0/1
    pos = jnp.cumsum(mask, axis=0) - 1  # position within expert
    keep = (pos < cap) & (mask > 0)
    dispatch = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                              dtype=x.dtype)[..., :cap]  # (t, e, cap)
    dispatch = dispatch * keep[..., None].astype(x.dtype)
    x_e = jnp.einsum("tec,td->ecd", dispatch, xf)  # (e, cap, d)
    x_e = shard(x_e, "expert", None, None)
    y_e = _expert_ffn(p["experts"], x_e)
    combine = dispatch * gate[..., None].astype(x.dtype)
    y = jnp.einsum("tec,ecd->td", combine, y_e)
    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# dense (tiny smoke tests)
# --------------------------------------------------------------------------

def _moe_dense(p: Params, x: jax.Array, cfg: ModelConfig):
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    topw, topi, aux = _router(p, xf, cfg)
    gate = jnp.zeros((b * s, cfg.num_experts), jnp.float32)
    gate = gate.at[jnp.arange(b * s)[:, None], topi].set(topw)
    x_all = jnp.broadcast_to(xf[None], (cfg.num_experts,) + xf.shape)
    y_all = _expert_ffn(p["experts"], x_all)  # (e, t, d)
    y = jnp.einsum("te,etd->td", gate.astype(x.dtype), y_all)
    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# ep: shard_map + all_to_all expert parallelism
# --------------------------------------------------------------------------

def _moe_ep_local(xl, router_w, gate_w, up_w, down_w, cfg: ModelConfig,
                  ep_size: int, dtype):
    """Per-device function under shard_map.

    xl: (b_l, s_l, d) local token block.  Expert weights are the local slice
    (E_local, ...).  Two capacity-bounded scatters around a pair of
    all_to_alls; gradients flow through scatter/gather/all_to_all natively.
    """
    b_l, s_l, d = xl.shape
    t = b_l * s_l
    xf = xl.reshape(t, d)
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    e_local = e // ep_size

    topw, topi, aux = _router({"router": {"kernel": router_w}}, xf, cfg)
    ft = t * k
    fe = topi.reshape(ft)
    fw = topw.reshape(ft)
    tok = jnp.repeat(jnp.arange(t), k)
    dest = fe // e_local  # destination shard on the model axis

    # Stage 1: scatter pairs into per-destination send slots.  pos1 is unique
    # per (dest, slot) by cumsum construction; overflow slots (pos1 >= cap1)
    # are out-of-bounds and silently dropped (capacity-based token dropping,
    # GShard semantics).
    cap1 = max(1, int(ft / ep_size * cfg.capacity_factor))
    oh1 = jax.nn.one_hot(dest, ep_size, dtype=jnp.int32)
    pos1 = jnp.take_along_axis(jnp.cumsum(oh1, axis=0) - 1, dest[:, None], axis=1)[:, 0]
    send_x = jnp.zeros((ep_size, cap1, d), dtype).at[dest, pos1].set(
        xf[tok].astype(dtype), mode="drop")
    send_e = jnp.zeros((ep_size, cap1), jnp.int32).at[dest, pos1].set(
        fe % e_local + 1, mode="drop")  # +1: 0 marks an empty slot

    recv_x = jax.lax.all_to_all(send_x, "model", split_axis=0, concat_axis=0)
    recv_e = jax.lax.all_to_all(send_e[..., None], "model", split_axis=0,
                                concat_axis=0)[..., 0]

    # Stage 2: regroup received tokens by local expert id.
    rt = ep_size * cap1
    fe2 = recv_e.reshape(rt) - 1  # -1 = empty slot
    valid = fe2 >= 0
    rx = recv_x.reshape(rt, d)
    cap2 = max(1, int(rt / e_local * cfg.capacity_factor))
    oh2 = jnp.where(valid[:, None], jax.nn.one_hot(jnp.where(valid, fe2, 0),
                                                   e_local, dtype=jnp.int32), 0)
    pos2 = jnp.take_along_axis(jnp.cumsum(oh2, axis=0) - 1,
                               jnp.where(valid, fe2, 0)[:, None], axis=1)[:, 0]
    idx_e = jnp.where(valid, fe2, e_local)  # e_local is OOB -> dropped
    ex_in = jnp.zeros((e_local, cap2, d), dtype).at[idx_e, pos2].set(rx, mode="drop")

    ex_out = _expert_ffn({"gate": gate_w, "up": up_w, "down": down_w}, ex_in)

    # Reverse stage 2 (gather with fill 0 for empty/overflow), then stage 1.
    y2 = ex_out.at[idx_e, pos2].get(mode="fill", fill_value=0)
    back = jax.lax.all_to_all(y2.reshape(ep_size, cap1, d), "model",
                              split_axis=0, concat_axis=0)
    contrib = back.at[dest, pos1].get(mode="fill", fill_value=0)
    y = jnp.zeros((t, d), dtype).at[tok].add(contrib * fw[:, None].astype(dtype))

    mesh_axes = tuple(n for n in ("pod", "data", "model")
                      if n in (current_mesh().axis_names if current_mesh() else ()))
    return y.reshape(b_l, s_l, d), jax.lax.pmean(aux, mesh_axes)


def _moe_ep(p: Params, x: jax.Array, cfg: ModelConfig):
    mesh = current_mesh()
    assert mesh is not None, "ep MoE requires an active mesh (axis_rules)"
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep_size = sizes.get("model", 1)
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    b, s, _ = x.shape
    dp = 1
    for a in batch_axes:
        dp *= sizes[a]

    if (s % max(ep_size, 1) or cfg.num_experts % max(ep_size, 1)
            or b % max(dp, 1)):
        return _moe_gshard(p, x, cfg)

    ex = p["experts"]
    gate_w, up_w, down_w = ex["gate"], ex["up"], ex["down"]
    if "kernel" in gate_w:
        wrapped = shard_map(
            functools.partial(_moe_ep_kernels, cfg=cfg, ep_size=ep_size, dtype=x.dtype),
            mesh=mesh,
            in_specs=(
                P(batch_axes or None, "model", None),  # batch over data, seq over model
                P(None, None),  # router (replicated)
                P("model", None, None), P("model", None, None), P("model", None, None),
            ),
            out_specs=(P(batch_axes or None, "model", None), P()),
            check_vma=False,
        )
        y, aux = wrapped(x, p["router"]["kernel"], gate_w["kernel"],
                         up_w["kernel"], down_w["kernel"])
        return y, aux
    # LRD experts: same wiring with (u, v) factor pairs per matrix.
    wrapped_lrd = shard_map(
        functools.partial(_moe_ep_lrd, cfg=cfg, ep_size=ep_size, dtype=x.dtype),
        mesh=mesh,
        in_specs=(
            P(batch_axes or None, "model", None),
            P(None, None),
            P("model", None, None), P("model", None, None),
            P("model", None, None), P("model", None, None),
            P("model", None, None), P("model", None, None),
        ),
        out_specs=(P(batch_axes or None, "model", None), P()),
        check_vma=False,
    )
    y, aux = wrapped_lrd(x, p["router"]["kernel"], gate_w["u"], gate_w["v"],
                         up_w["u"], up_w["v"], down_w["u"], down_w["v"])
    return y, aux


def _moe_ep_kernels(xl, router_w, gw, uw, dw, cfg, ep_size, dtype):
    return _moe_ep_local(xl, router_w, {"kernel": gw}, {"kernel": uw},
                         {"kernel": dw}, cfg=cfg, ep_size=ep_size, dtype=dtype)


def _moe_ep_lrd(xl, router_w, gu, gv, uu, uv, du, dv, cfg, ep_size, dtype):
    return _moe_ep_local(
        xl, router_w, {"u": gu, "v": gv}, {"u": uu, "v": uv}, {"u": du, "v": dv},
        cfg=cfg, ep_size=ep_size, dtype=dtype)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig,
              *, use_pallas: bool = False) -> Tuple[jax.Array, jax.Array]:
    impl = cfg.moe_impl
    if impl == "ep" and current_mesh() is None:
        impl = "dense" if x.shape[0] * x.shape[1] <= 4096 else "gshard"
    if impl == "ep":
        y, aux = _moe_ep(p, x, cfg)
    elif impl == "gshard":
        y, aux = _moe_gshard(p, x, cfg)
    else:
        y, aux = _moe_dense(p, x, cfg)
    if "shared" in p:
        y = y + common.ffn(p["shared"], x, use_pallas=use_pallas)
    return y, aux
