"""Operations and bytes the cells' work requires, from the shapes alone.

Everything here is arithmetic on the param tree's shapes (the ranks the
run built) and the token counts of the work: no HLO, no trace.  Matmul
operands are counted at their stored width (2 bytes for bfloat16).

* :func:`linears` lists every projection of a dense decoder LM: low-rank
  ``(c, r, s)`` pairs and dense ``(c, s)`` kernels, with how many of each
  the stacked layers hold.
* :func:`train_flops_per_token` is the model FLOPs of one training token
  in one sequential-freezing phase: forward, the input gradient, and the
  weight gradient of the trainable group only.  Nothing recomputed counts.
* :func:`lowrank_kernel_calls` lists the fused low-rank kernels one
  training step launches, with each call's operations and bytes, for the
  roofline of the kernel layer.
* :func:`decode_terms` is one decode step's required operations and
  bytes: the served weights read once and the K/V of the live lengths.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Linear:
    path: str
    c: int
    s: int
    r: Optional[int]  # None: a dense kernel
    count: int  # how many (stacked layers)
    itemsize: int = BF16

    @property
    def params(self) -> int:
        per = self.c * self.s if self.r is None else self.r * (self.c + self.s)
        return per * self.count


def _shape(x):
    return tuple(x.shape)


def linears(abstract: Dict) -> List[Linear]:
    """Every projection group of a param tree (abstract or concrete)."""
    out: List[Linear] = []

    def walk(t, path):
        if not isinstance(t, dict):
            return
        if "u" in t and "v" in t and not isinstance(t["u"], dict):
            u, v = _shape(t["u"]), _shape(t["v"])
            count = 1
            for d in u[:-2]:
                count *= d
            out.append(Linear(path, u[-2], v[-1], u[-1], count,
                              t["u"].dtype.itemsize))
            return
        if "kernel" in t and not isinstance(t["kernel"], dict):
            k = _shape(t["kernel"])
            count = 1
            for d in k[:-2]:
                count *= d
            out.append(Linear(path, k[-2], k[-1], None, count,
                              t["kernel"].dtype.itemsize))
            return
        for key, sub in t.items():
            walk(sub, f"{path}/{key}" if path else key)

    walk(abstract, "")
    return out


@dataclasses.dataclass(frozen=True)
class Attn:
    layers: int
    heads: int
    head_dim: int
    kv_heads: int


def attention_dims(cfg: dict) -> Attn:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Attn(cfg["num_hidden_layers"], h, cfg.get("head_dim") or d // h,
                cfg["num_key_value_heads"])


def _head(abstract: Dict, tie: bool) -> Optional[Tuple[int, int]]:
    if not tie:
        return None  # the untied head is a dense Linear in linears()
    v, d = _shape(abstract["embed"]["embedding"])
    return d, v


def train_flops_per_token(abstract: Dict, cfg: dict, seq_len: int,
                          phase: int) -> float:
    """Model FLOPs per training token in ``phase`` (0: u frozen, 1: v
    frozen): forward 2N, input gradient 2N, the trainable factor's weight
    gradient, and causal attention forward and backward."""
    total = 0.0
    for ln in linears(abstract):
        if ln.r is None:
            total += 3 * 2 * ln.c * ln.s * ln.count
            continue
        fwd = 2 * ln.r * (ln.c + ln.s)
        wgrad = 2 * ln.r * ln.s if phase == 0 else 2 * ln.c * ln.r
        total += (2 * fwd + wgrad) * ln.count
    head = _head(abstract, cfg["tie_word_embeddings"])
    if head is not None:
        d, v = head
        total += 3 * 2 * d * v
    a = attention_dims(cfg)
    # scores and values over (seq_len + 1) / 2 keys on average, twice
    fwd_attn = 2 * 2 * a.heads * a.head_dim * (seq_len + 1) / 2
    total += 3 * fwd_attn * a.layers
    return total


@dataclasses.dataclass(frozen=True)
class KernelCall:
    name: str  # the pallas_call's name
    flops: float
    bytes: float
    count: int

    def least_s(self, peak_flops: float, peak_bw: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bw)

    def bound(self, peak_flops: float, peak_bw: float) -> str:
        return ("compute" if self.flops / peak_flops >= self.bytes / peak_bw
                else "memory")


def _lr_call(name, m, c, r, s, count, item=BF16) -> KernelCall:
    """fwd / dx / du / dv: two chained matmuls through rank r; each reads
    the four (m, c), (c, r), (r, s), (m, s) operands once and writes one."""
    flops = 2.0 * m * r * (c + s)
    byts = float(item) * (m * c + c * r + r * s + m * s)
    return KernelCall(name, flops, byts, count)


def lowrank_kernel_calls(abstract: Dict, m: int, phase: int,
                         fallback_shapes: Iterable[Tuple[str, Tuple]] = (),
                         remat: bool = False) -> List[KernelCall]:
    """The fused low-rank kernel launches of one training step of ``m``
    tokens, per the program's dispatch: each factorized projection runs
    ``lowrank_fwd``, ``lowrank_dx`` and the trainable factor's
    ``lowrank_du``/``lowrank_dv``; a gated FFN whose gate and up are both
    factorized runs ``lowrank_ffn_fwd`` forward and, backward, recomputes
    both branches with ``lowrank_fwd``.  Under per-layer remat each
    forward kernel runs twice.  ``fallback_shapes`` are the ``(op,
    (m, c, s))`` the dispatcher sent to jnp instead: those calls are not
    kernels and are left out."""
    fell = {(op, tuple(shape)) for op, shape in fallback_shapes}
    wname = "lowrank_dv" if phase == 0 else "lowrank_du"
    fwd_runs = 2 if remat else 1
    calls: List[KernelCall] = []
    groups: Dict[str, Dict[str, Linear]] = {}
    for ln in linears(abstract):
        if ln.r is None:
            continue
        parent, leaf = ln.path.rsplit("/", 1) if "/" in ln.path else ("", ln.path)
        groups.setdefault(parent, {})[leaf] = ln
    for parent, members in groups.items():
        fused = ("gate" in members and "up" in members)
        for leaf, ln in members.items():
            if fused and leaf in ("gate", "up"):
                continue
            if ("lowrank_fwd", (m, ln.c, ln.s)) in fell:
                continue
            calls.append(_lr_call("lowrank_fwd", m, ln.c, ln.r, ln.s,
                                  fwd_runs * ln.count, ln.itemsize))
            calls.append(_lr_call("lowrank_dx", m, ln.c, ln.r, ln.s,
                                  ln.count, ln.itemsize))
            calls.append(_lr_call(wname, m, ln.c, ln.r, ln.s, ln.count,
                                  ln.itemsize))
        if fused:
            g, u = members["gate"], members["up"]
            if ("lowrank_ffn", (m, g.c, g.s)) in fell:
                continue
            item = g.itemsize
            flops = 2.0 * m * (g.r * (g.c + g.s) + u.r * (u.c + u.s))
            byts = float(item) * (m * g.c + g.c * g.r + g.r * g.s
                                  + u.c * u.r + u.r * u.s + m * g.s)
            calls.append(KernelCall("lowrank_ffn_fwd", flops, byts,
                                    fwd_runs * g.count))
            for ln in (g, u):
                calls.append(_lr_call("lowrank_fwd", m, ln.c, ln.r, ln.s,
                                      ln.count, item))
                calls.append(_lr_call("lowrank_dx", m, ln.c, ln.r, ln.s,
                                      ln.count, item))
                calls.append(_lr_call(wname, m, ln.c, ln.r, ln.s, ln.count,
                                      item))
    return calls


def served_weight_bytes(params) -> float:
    """Bytes of every leaf of a served param tree."""
    import jax
    return float(sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(params)))


@dataclasses.dataclass(frozen=True)
class DecodeTerms:
    """One decode step's work as a function of its live KV lengths."""
    flops_per_row: float  # every weight once, 2 FLOPs each
    weight_bytes: float  # the served weights, read once per step
    flops_per_key: float  # attention scores and values, all layers
    bytes_per_key: float  # one position's K and V, all layers

    def work(self, live_lengths: Sequence[int]) -> Tuple[float, float]:
        keys = float(sum(live_lengths))
        return (self.flops_per_row * len(live_lengths)
                + self.flops_per_key * keys,
                self.weight_bytes + self.bytes_per_key * keys)


def decode_terms(params, cfg: dict, kv_itemsize: int = BF16) -> DecodeTerms:
    """The required work of a decode step of the served ``params``: a row
    whose KV length (the new token's included) is L costs every weight
    once and attention over L keys; the step reads the weights once and
    each live row's L positions of K and V."""
    per_row = 0.0
    for ln in linears(params):
        per_row += 2.0 * (ln.c * ln.s if ln.r is None
                          else ln.r * (ln.c + ln.s)) * ln.count
    head = _head(params, cfg["tie_word_embeddings"])
    if head is not None:
        per_row += 2.0 * head[0] * head[1]
    a = attention_dims(cfg)
    return DecodeTerms(per_row, served_weight_bytes(params),
                       4.0 * a.heads * a.head_dim * a.layers,
                       2.0 * a.kv_heads * a.head_dim * kv_itemsize * a.layers)
