"""Decoder-LM assembly for every assigned architecture family.

One ``init`` / ``apply`` pair covers dense (llama/qwen), MoE (olmoe /
deepseek-v3 incl. MLA + MTP), VLM (llama-3.2-vision: cross-attn every 5th
layer), hybrid (zamba2: Mamba2 backbone + shared attention block) and SSM
(xlstm: mLSTM stack).  Layers are stacked (params carry a leading L dim,
built directly by ``Decomposer(..., stack=(L,))``) and applied with
``lax.scan`` so the HLO stays one-layer-sized (DESIGN.md §3).

``mode``: "full" (train / prefill — returns per-layer caches) or "decode"
(single token against caches).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.decompose import Decomposer
from repro.distributed import shard
from repro.kernels.ops import KernelPolicy
from repro.models import attention, moe as moe_mod, ssm
from repro.models.attention import gqa_apply, gqa_init, mla_apply, mla_init
from repro.models.common import (Params, cross_entropy, embed, embedding_init,
                                 ffn, ffn_init, linear, mask_vocab, rmsnorm,
                                 rmsnorm_init, rope_table)
from repro.obs import scope


def _bc(p: Params, stack: Tuple[int, ...]) -> Params:
    if not stack:
        return p
    return {k: jnp.broadcast_to(v, stack + v.shape) for k, v in p.items()}


# --------------------------------------------------------------------------
# Decoder layer (dense / moe / mla)
# --------------------------------------------------------------------------

def decoder_layer_init(dec: Decomposer, key, path: str, cfg: ModelConfig,
                       *, moe_layer: bool, stack: Tuple[int, ...] = ()) -> Params:
    ks = jax.random.split(key, 2)
    attn = (mla_init if cfg.use_mla else gqa_init)(dec, ks[0], f"{path}/attn", cfg, stack=stack)
    p: Params = {
        "norm1": _bc(rmsnorm_init(cfg.d_model, cfg.pdtype), stack),
        "attn": attn,
        "norm2": _bc(rmsnorm_init(cfg.d_model, cfg.pdtype), stack),
    }
    if moe_layer:
        p["moe"] = moe_mod.moe_init(dec, ks[1], f"{path}/moe", cfg, stack=stack)
    else:
        f = cfg.dense_d_ff or cfg.d_ff
        p["ffn"] = ffn_init(dec, ks[1], f"{path}/ffn", cfg.d_model, f,
                            cfg.ffn_activation, cfg.pdtype, stack=stack)
    return p


def decoder_layer_apply(lp: Params, h: jax.Array, cfg: ModelConfig, *, rope,
                        mode: str, cache: Optional[Params], pos,
                        moe_layer: bool, use_pallas: bool = False,
                        kv_src: Optional[jax.Array] = None):
    # "train" == "full" without materializing KV caches through scan ys.
    attn_mode = "full" if mode == "train" else mode
    with scope("norm"):
        a_in = rmsnorm(lp["norm1"], h, cfg.norm_eps)
    if cfg.use_mla:
        a_out, new_cache = mla_apply(lp["attn"], a_in, cfg, rope_q=rope, rope_k=rope,
                                     mode=attn_mode, cache=cache, pos=pos,
                                     use_pallas=use_pallas)
    else:
        rope4 = (rope[0], rope[1], rope[0], rope[1]) if rope is not None else None
        a_out, new_cache = gqa_apply(lp["attn"], a_in, cfg, rope=rope4, mode=attn_mode,
                                     cache=cache, pos=pos, kv_src=kv_src,
                                     use_pallas=use_pallas)
    if mode == "train":
        new_cache = None
    h = h + a_out
    with scope("norm"):
        f_in = rmsnorm(lp["norm2"], h, cfg.norm_eps)
    if moe_layer:
        with scope("ffn"):
            f_out, aux = moe_mod.moe_apply(lp["moe"], f_in, cfg,
                                           use_pallas=use_pallas)
    else:
        f_out, aux = ffn(lp["ffn"], f_in, use_pallas=use_pallas), jnp.zeros((), jnp.float32)
    h = h + f_out
    h = shard(h, "batch", "seq", "embed")
    return h, new_cache, aux


def _best_divisor(n: int) -> int:
    """Divisor of n closest to sqrt(n) (group count for two-level remat)."""
    best = 1
    for g in range(2, int(n ** 0.5) + 1):
        if n % g == 0:
            best = g
    return best


def _scan_stack(stacked: Params, h: jax.Array, body, cache: Optional[Params],
                remat: str = "none"):
    """scan over the layer dim of ``stacked`` (+ optional stacked cache).

    remat="full": checkpoint each layer (stash = L layer-inputs).
    remat="sqrt": two-level checkpointed scan over (G, L/G) groups — stash =
    G + L/G layer-inputs, the classic sqrt(L) memory trade (~4.5x less for
    an 80-layer model at ~1 extra forward recompute).
    """

    def scan_body(carry, xs):
        lp, lc = xs
        # Barrier keeps the remat stash in the carry's own dtype (bf16):
        # without it XLA's convert-sinking stores an extra fp32 copy of
        # every layer input (measured 2x stash memory on the dry-run).
        carry = jax.lax.optimization_barrier(carry)
        h_new, new_lc, aux = body(lp, carry, lc)
        return h_new, (new_lc, aux)

    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if remat == "sqrt":
        g = _best_divisor(n_layers)
        if g == 1:
            remat = "full"  # prime layer count: flat per-layer checkpointing
        else:
            per = n_layers // g
            regroup = lambda t: jax.tree_util.tree_map(
                lambda x: x.reshape((g, per) + x.shape[1:]), t)
            inner_body = jax.checkpoint(scan_body, prevent_cse=False)

            @functools.partial(jax.checkpoint, prevent_cse=False)
            def group_body(carry, xs):
                gp, gc = xs
                carry = jax.lax.optimization_barrier(carry)
                h_new, ys = jax.lax.scan(inner_body, carry, (gp, gc))
                return h_new, ys

            h, (new_cache, auxs) = jax.lax.scan(
                group_body, h, (regroup(stacked), regroup(cache)))
            flat = lambda t: jax.tree_util.tree_map(
                lambda x: x.reshape((n_layers,) + x.shape[2:]), t)
            return h, flat(new_cache), jnp.sum(auxs)

    if remat == "full":
        scan_body = jax.checkpoint(scan_body, prevent_cse=False)
    elif remat == "dots":
        scan_body = jax.checkpoint(
            scan_body, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    h, (new_cache, auxs) = jax.lax.scan(scan_body, h, (stacked, cache))
    return h, new_cache, jnp.sum(auxs)


def _scan_paged(stacked: Params, h: jax.Array, body, cache: Params):
    """Decode over a paged pool (serving/paged_cache.py): scan the layers
    with the stack's pool leaves in the carry.

    Each layer gets the whole pool, its layer index and the page table,
    writes its new rows in place and reads its own layer of the pool, so
    no operation moves bytes in proportion to the pool: scanning the pool
    as ``xs`` and restacking it as ``ys`` would copy all of it every step.
    """
    pools = {k: v for k, v in cache.items() if k != "page_table"}
    table = cache["page_table"]
    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def scan_body(carry, xs):
        hh, pool = carry
        lp, layer = xs
        hh, new, aux = body(lp, hh, dict(pool, layer=layer, page_table=table))
        return (hh, {k: new[k] for k in pool}), aux

    (h, pools), auxs = jax.lax.scan(
        scan_body, (h, pools), (stacked, jnp.arange(n_layers, dtype=jnp.int32)))
    return h, dict(pools, page_table=table), jnp.sum(auxs)


# --------------------------------------------------------------------------
# Model init
# --------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig, dec: Decomposer) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {"embed": embedding_init(ks[0], cfg.vocab_padded, cfg.d_model, cfg.pdtype)}
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        if fam == "vlm":
            every = cfg.cross_attn_every
            n_groups = cfg.num_layers // (every + 1)
            p["self_stack"] = decoder_layer_init(
                dec, ks[1], "layers/self", cfg, moe_layer=False, stack=(n_groups, every))
            p["cross_stack"] = _vlm_cross_init(dec, ks[2], cfg, stack=(n_groups,))
        elif cfg.num_experts and cfg.first_k_dense:
            p["dense_stack"] = decoder_layer_init(
                dec, ks[1], "layers/dense", cfg, moe_layer=False, stack=(cfg.first_k_dense,))
            p["moe_stack"] = decoder_layer_init(
                dec, ks[2], "layers/moe", cfg, moe_layer=True,
                stack=(cfg.num_layers - cfg.first_k_dense,))
        elif cfg.num_experts:
            p["moe_stack"] = decoder_layer_init(
                dec, ks[1], "layers/moe", cfg, moe_layer=True, stack=(cfg.num_layers,))
        else:
            p["stack"] = decoder_layer_init(
                dec, ks[1], "layers", cfg, moe_layer=False, stack=(cfg.num_layers,))
        if cfg.use_mtp:
            p["mtp"] = {
                "proj": dec.linear(ks[3], "mtp/proj", 2 * cfg.d_model, cfg.d_model),
                "layer": decoder_layer_init(dec, ks[4], "mtp/layer", cfg,
                                            moe_layer=bool(cfg.num_experts)),
                "norm_h": rmsnorm_init(cfg.d_model, cfg.pdtype),
                "norm_e": rmsnorm_init(cfg.d_model, cfg.pdtype),
            }
    elif fam == "hybrid":
        n_grp, per, tail = _hybrid_split(cfg)
        p["mamba_groups"] = ssm.mamba2_init(dec, ks[1], "layers/mamba", cfg,
                                            stack=(n_grp, per))
        if tail:
            p["mamba_tail"] = ssm.mamba2_init(dec, ks[2], "layers/mamba_tail", cfg,
                                              stack=(tail,))
        p["shared_attn"] = _zamba_shared_init(dec, ks[3], cfg)
    elif fam == "ssm":
        p["stack"] = ssm.mlstm_init(dec, ks[1], "layers/mlstm", cfg,
                                    stack=(cfg.num_layers,))
    else:
        raise ValueError(f"lm_init: unsupported family {fam!r} (enc-dec lives in encdec.py)")

    p["final_norm"] = rmsnorm_init(cfg.d_model, cfg.pdtype)
    if not cfg.tie_embeddings:
        p["unembed"] = dec.linear(ks[5], "unembed", cfg.d_model, cfg.vocab_padded)
    return p


def _vlm_cross_init(dec, key, cfg: ModelConfig, stack) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "norm1": _bc(rmsnorm_init(cfg.d_model, cfg.pdtype), stack),
        "attn": gqa_init(dec, ks[0], "layers/cross/attn", cfg, cross=True, stack=stack),
        "norm2": _bc(rmsnorm_init(cfg.d_model, cfg.pdtype), stack),
        "ffn": ffn_init(dec, ks[1], "layers/cross/ffn", cfg.d_model, cfg.d_ff,
                        cfg.ffn_activation, cfg.pdtype, stack=stack),
    }


def _hybrid_split(cfg: ModelConfig) -> Tuple[int, int, int]:
    per = cfg.attn_every
    n_grp = cfg.num_layers // per
    tail = cfg.num_layers - n_grp * per
    return n_grp, per, tail


def _zamba_shared_init(dec, key, cfg: ModelConfig) -> Params:
    """Zamba2 shared transformer block: runs at 2*d on concat(h, x0)."""
    d2 = 2 * cfg.d_model
    wide = _zamba_wide_cfg(cfg)
    ks = jax.random.split(key, 3)
    return {
        "norm1": rmsnorm_init(d2, cfg.pdtype),
        "attn": gqa_init(dec, ks[0], "shared/attn", wide),
        "norm2": rmsnorm_init(d2, cfg.pdtype),
        "ffn": ffn_init(dec, ks[1], "shared/ffn", d2, cfg.d_ff, "gelu", cfg.pdtype),
        "down": dec.linear(ks[2], "shared/down_proj", d2, cfg.d_model),
    }


def _zamba_wide_cfg(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(cfg, d_model=2 * cfg.d_model, use_mla=False,
                               qk_norm=False, qkv_bias=False)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def lm_apply(
    p: Params,
    tokens: jax.Array,  # (B, S) int32
    cfg: ModelConfig,
    *,
    mode: str = "full",
    cache: Optional[Params] = None,
    pos=None,
    vision_embeddings: Optional[jax.Array] = None,
    remat: str = "none",
    use_pallas: "bool | KernelPolicy" = False,
    return_hidden: bool = False,
):
    """Returns (logits, new_cache, aux[, hidden]).

    ``use_pallas`` (bool or :class:`repro.kernels.ops.KernelPolicy`) is
    forwarded verbatim through every layer body down to
    ``models.common.linear``/``ffn`` — the launch layer uses the policy form
    to carry the static sequential-freezing group into the fused-kernel VJPs
    without per-layer plumbing.
    """
    b, s = tokens.shape
    hd = cfg.resolved_head_dim
    with scope("embed"):
        h = embed(p["embed"], tokens).astype(cfg.cdtype)
        h = shard(h, "batch", "seq", "embed")

    with scope("attn_proj"):  # the RoPE tables the projections apply
        rope = _make_rope(cfg, s, "full" if mode == "train" else mode, pos)
    fam = cfg.family
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    # the layer scans, their stacking and their carries
    with scope("layers"):
        if fam in ("dense", "moe"):
            for name, moe_layer in (("stack", False), ("dense_stack", False),
                                    ("moe_stack", True)):
                if name not in p:
                    continue
                body = functools.partial(
                    _decoder_body, cfg=cfg, rope=rope, mode=mode, pos=pos,
                    moe_layer=moe_layer, use_pallas=use_pallas)
                stack_cache = cache.get(name) if cache else None
                if mode == "decode" and stack_cache is not None \
                        and "page_table" in stack_cache:
                    h, nc, aux = _scan_paged(p[name], h, body, stack_cache)
                else:
                    h, nc, aux = _scan_stack(p[name], h, body, stack_cache,
                                             remat)
                new_cache[name] = nc
                aux_total += aux
        elif fam == "vlm":
            h, new_cache, aux_total = _vlm_forward(
                p, h, cfg, rope, mode, cache, pos, vision_embeddings, remat,
                use_pallas)
        elif fam == "hybrid":
            h, new_cache, aux_total = _hybrid_forward(
                p, h, cfg, rope, mode, cache, pos, remat, use_pallas)
        elif fam == "ssm":
            body = functools.partial(_mlstm_body, cfg=cfg, mode=mode,
                                     use_pallas=use_pallas)
            h, nc, aux_total = _scan_stack(
                p["stack"], h, body, cache.get("stack") if cache else None,
                remat)
            new_cache["stack"] = nc

    with scope("head"):
        h = rmsnorm(p["final_norm"], h, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.dot(h, p["embed"]["embedding"].T,
                             preferred_element_type=jnp.float32)
        else:
            logits = linear(p["unembed"], h,
                            use_pallas=use_pallas).astype(jnp.float32)
        logits = mask_vocab(logits, cfg.vocab_size)
        logits = shard(logits, "batch", "seq", "vocab")
    if return_hidden:
        return logits, new_cache, aux_total, h
    return logits, new_cache, aux_total


def _make_rope(cfg: ModelConfig, s: int, mode: str, pos):
    if cfg.family == "ssm":
        return None
    if cfg.use_mla:
        hd = cfg.qk_rope_head_dim
    elif cfg.family == "hybrid":
        hd = 2 * cfg.d_model // cfg.num_heads  # zamba2 shared block runs at 2*d
    else:
        hd = cfg.resolved_head_dim
    if mode == "full":
        cos, sin = rope_table(s, hd, cfg.rope_theta)
    else:
        pos_arr = jnp.asarray(pos).reshape(-1)
        if pos_arr.size > 1:
            # Slot-indexed decode: each batch row sits at its own position,
            # so the tables are (B, s, hd/2) — apply_rope broadcasts per
            # row.  s > 1 is the speculative verify chunk: row b's chunk
            # positions are pos[b] .. pos[b]+s-1.
            cos, sin = rope_table(s, hd, cfg.rope_theta,
                                  positions=pos_arr[:, None]
                                  + jnp.arange(s)[None, :])
        else:
            cos, sin = rope_table(s, hd, cfg.rope_theta,
                                  positions=pos_arr[:1] + jnp.arange(s))
    return (cos, sin)


def _decoder_body(lp, h, lc, *, cfg, rope, mode, pos, moe_layer, use_pallas):
    return decoder_layer_apply(lp, h, cfg, rope=rope, mode=mode, cache=lc,
                               pos=pos, moe_layer=moe_layer, use_pallas=use_pallas)


def _mlstm_body(lp, h, lc, *, cfg, mode, use_pallas):
    out, new_state = ssm.mlstm_apply(lp, h, cfg,
                                     mode="full" if mode == "train" else mode,
                                     state=lc, use_pallas=use_pallas)
    return h + out, None if mode == "train" else new_state, jnp.zeros((), jnp.float32)


def _mamba_body(lp, h, lc, *, cfg, mode, use_pallas):
    out, new_state = ssm.mamba2_apply(lp, h, cfg,
                                      mode="full" if mode == "train" else mode,
                                      state=lc, use_pallas=use_pallas)
    return h + out, None if mode == "train" else new_state, jnp.zeros((), jnp.float32)


def _vlm_forward(p, h, cfg, rope, mode, cache, pos, vision_embeddings, remat,
                 use_pallas):
    """Outer scan over groups: (cross_attn_every self layers) + 1 cross layer."""
    self_body = functools.partial(_decoder_body, cfg=cfg, rope=rope, mode=mode,
                                  pos=pos, moe_layer=False, use_pallas=use_pallas)
    # inner layers need their own remat: the group-level checkpoint alone
    # leaves every inner-layer activation saved (measured 119 GiB/device for
    # the 100-layer llama-3.2-vision train cell).
    inner_remat = "full" if remat in ("full", "sqrt") else "none"

    def group_body(carry, xs):
        hh = carry
        (self_lp, cross_lp), (self_lc, cross_lc) = xs
        hh, self_nc, _ = _scan_stack(self_lp, hh, self_body, self_lc,
                                     remat=inner_remat)
        hh, cross_nc = _vlm_cross_apply(cross_lp, hh, cfg, mode, cross_lc,
                                        vision_embeddings, use_pallas)
        if mode == "train":
            self_nc, cross_nc = None, None
        return hh, (self_nc, cross_nc)

    if remat in ("full", "sqrt"):  # groups ARE the outer sqrt level
        group_body = jax.checkpoint(group_body, prevent_cse=False)
    cache_groups = (cache.get("self"), cache.get("cross")) if cache else (None, None)
    h, (self_nc, cross_nc) = jax.lax.scan(
        group_body, h, ((p["self_stack"], p["cross_stack"]), cache_groups))
    return h, {"self": self_nc, "cross": cross_nc}, jnp.zeros((), jnp.float32)


def _vlm_cross_apply(lp, h, cfg, mode, lc, vision_embeddings, use_pallas):
    a_in = rmsnorm(lp["norm1"], h, cfg.norm_eps)
    if mode in ("full", "train"):
        a_out, nc = gqa_apply(lp["attn"], a_in, cfg, rope=None, mode="full",
                              kv_src=vision_embeddings, use_pallas=use_pallas)
    else:
        a_out, nc = gqa_apply(lp["attn"], a_in, cfg, rope=None, mode="decode",
                              cache=lc, pos=jnp.zeros((), jnp.int32),
                              kv_src=vision_embeddings, use_pallas=use_pallas)
    h = h + a_out
    f_in = rmsnorm(lp["norm2"], h, cfg.norm_eps)
    h = h + ffn(lp["ffn"], f_in, use_pallas=use_pallas)
    return h, nc


def _hybrid_forward(p, h, cfg, rope, mode, cache, pos, remat, use_pallas):
    """Zamba2: groups of mamba layers, shared attention block between groups."""
    x0 = h  # original embedding, re-fed to the shared block (zamba design)
    mamba_body = functools.partial(_mamba_body, cfg=cfg, mode=mode,
                                   use_pallas=use_pallas)
    shared = p["shared_attn"]

    def group_body(carry, xs):
        hh = carry
        grp_lp, (grp_state, attn_lc) = xs
        hh, grp_ns, _ = _scan_stack(grp_lp, hh, mamba_body, grp_state, remat="none")
        hh, attn_nc = _zamba_shared_apply(shared, hh, x0, cfg, rope, mode,
                                          attn_lc, pos, use_pallas)
        if mode == "train":
            grp_ns, attn_nc = None, None
        return hh, (grp_ns, attn_nc)

    if remat in ("full", "sqrt"):  # groups ARE the outer sqrt level
        group_body = jax.checkpoint(group_body, prevent_cse=False)
    cache_groups = ((cache.get("mamba_groups"), cache.get("shared_attn"))
                    if cache else (None, None))
    h, (grp_ns, attn_nc) = jax.lax.scan(group_body, h,
                                        (p["mamba_groups"], cache_groups))
    new_cache = {"mamba_groups": grp_ns, "shared_attn": attn_nc}
    if "mamba_tail" in p:
        h, tail_ns, _ = _scan_stack(p["mamba_tail"], h, mamba_body,
                                    cache.get("mamba_tail") if cache else None, remat)
        new_cache["mamba_tail"] = tail_ns
    return h, new_cache, jnp.zeros((), jnp.float32)


def _zamba_shared_apply(sp, h, x0, cfg, rope, mode, lc, pos, use_pallas):
    wide = _zamba_wide_cfg(cfg)
    z = jnp.concatenate([h, x0], axis=-1)
    a_in = rmsnorm(sp["norm1"], z, cfg.norm_eps)
    rope4 = (rope[0], rope[1], rope[0], rope[1]) if rope is not None else None
    a_out, nc = gqa_apply(sp["attn"], a_in, wide, rope=rope4,
                          mode="full" if mode == "train" else mode,
                          cache=lc, pos=pos, use_pallas=use_pallas)
    z = z + a_out
    f_in = rmsnorm(sp["norm2"], z, cfg.norm_eps)
    z = z + ffn(sp["ffn"], f_in, use_pallas=use_pallas)
    return h + linear(sp["down"], z, use_pallas=use_pallas), nc


# --------------------------------------------------------------------------
# MTP head (deepseek-v3)
# --------------------------------------------------------------------------

def mtp_logits(p: Params, h: jax.Array, tokens: jax.Array, cfg: ModelConfig,
               *, use_pallas: "bool | KernelPolicy" = False) -> jax.Array:
    """Depth-1 multi-token prediction: predict t+2 from (h_t, emb(t+1))."""
    mtp = p["mtp"]
    # shift-by-one, padded back to S so seq stays divisible for the MoE EP
    # path (an S-1 tail would force the gshard fallback at 4095 tokens).
    emb_next = embed(p["embed"], jnp.roll(tokens, -1, axis=1)).astype(h.dtype)
    h_in = jnp.concatenate([
        rmsnorm(mtp["norm_h"], h, cfg.norm_eps),
        rmsnorm(mtp["norm_e"], emb_next, cfg.norm_eps)], axis=-1)
    hm = linear(mtp["proj"], h_in, use_pallas=use_pallas)
    s = hm.shape[1]
    rope = (rope_table(s, cfg.qk_rope_head_dim if cfg.use_mla else cfg.resolved_head_dim,
                       cfg.rope_theta))
    hm, _, _ = decoder_layer_apply(mtp["layer"], hm, cfg, rope=rope, mode="train",
                                   cache=None, pos=None,
                                   moe_layer=bool(cfg.num_experts),
                                   use_pallas=use_pallas)
    hm = rmsnorm(p["final_norm"], hm, cfg.norm_eps)
    if cfg.tie_embeddings:
        lg = jnp.dot(hm, p["embed"]["embedding"].T, preferred_element_type=jnp.float32)
    else:
        lg = linear(p["unembed"], hm, use_pallas=use_pallas).astype(jnp.float32)
    from repro.distributed import shard as _shard
    return _shard(mask_vocab(lg, cfg.vocab_size), "batch", "seq", "vocab")


def mtp_loss_mask(tokens: jax.Array) -> jax.Array:
    """Valid positions for the padded depth-1 MTP loss (last 2 invalid)."""
    b, s = tokens.shape
    idx = jnp.arange(s)
    return jnp.broadcast_to((idx < s - 2).astype(jnp.float32), (b, s))


# --------------------------------------------------------------------------
# Cache init
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Params:
    dtype = dtype or cfg.cdtype
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads

    def kv_cache(stack: Tuple[int, ...], length: int, heads: int, head_d: int):
        if cfg.kv_cache_dtype == "int8":
            from repro.models.kvcache import init_quantized_kv
            return init_quantized_kv(stack, batch, length, heads, head_d)
        return {"k": jnp.zeros(stack + (batch, length, heads, head_d), dtype),
                "v": jnp.zeros(stack + (batch, length, heads, head_d), dtype)}

    fam = cfg.family
    if fam in ("dense", "moe"):
        if cfg.use_mla:
            def mla_cache(n):
                return {"ckv": jnp.zeros((n, batch, max_len, cfg.kv_lora_rank), dtype),
                        "kr": jnp.zeros((n, batch, max_len, cfg.qk_rope_head_dim), dtype)}
            out = {}
            if cfg.num_experts and cfg.first_k_dense:
                out["dense_stack"] = mla_cache(cfg.first_k_dense)
                out["moe_stack"] = mla_cache(cfg.num_layers - cfg.first_k_dense)
            elif cfg.num_experts:
                out["moe_stack"] = mla_cache(cfg.num_layers)
            else:
                out["stack"] = mla_cache(cfg.num_layers)
            return out
        out = {}
        if cfg.num_experts and cfg.first_k_dense:
            out["dense_stack"] = kv_cache((cfg.first_k_dense,), max_len, kv, hd)
            out["moe_stack"] = kv_cache((cfg.num_layers - cfg.first_k_dense,), max_len, kv, hd)
        elif cfg.num_experts:
            out["moe_stack"] = kv_cache((cfg.num_layers,), max_len, kv, hd)
        else:
            out["stack"] = kv_cache((cfg.num_layers,), max_len, kv, hd)
        return out
    if fam == "vlm":
        every = cfg.cross_attn_every
        n_groups = cfg.num_layers // (every + 1)
        return {"self": kv_cache((n_groups, every), max_len, kv, hd),
                "cross": kv_cache((n_groups,), cfg.num_image_tokens, kv, hd)}
    if fam == "hybrid":
        n_grp, per, tail = _hybrid_split(cfg)
        d = cfg.d_model
        di = cfg.ssm_expand * d
        nh = di // cfg.ssm_head_dim
        conv_dim = di + 2 * cfg.ssm_state

        def mstate(stack):
            return {"ssm": jnp.zeros(stack + (batch, nh, cfg.ssm_state, cfg.ssm_head_dim), dtype),
                    "conv": jnp.zeros(stack + (batch, cfg.ssm_conv_width - 1, conv_dim), dtype)}

        wide_hd = 2 * d // cfg.num_heads
        out = {"mamba_groups": mstate((n_grp, per)),
               "shared_attn": kv_cache((n_grp,), max_len, cfg.num_kv_heads, wide_hd)}
        if tail:
            out["mamba_tail"] = mstate((tail,))
        return out
    if fam == "ssm":
        nh = cfg.xlstm_heads
        hd_x = cfg.d_model // nh
        return {"stack": {
            "c": jnp.zeros((cfg.num_layers, batch, nh, hd_x, hd_x), dtype),
            "n": jnp.zeros((cfg.num_layers, batch, nh, hd_x), dtype),
            "m": jnp.full((cfg.num_layers, batch, nh), -1e30, jnp.float32),
        }}
    raise ValueError(fam)
