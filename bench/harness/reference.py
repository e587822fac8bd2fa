"""Plain float32 reference of the dense decoder LM the cells run.

Written from the published architecture (Llama-style: RMSNorm, RoPE on
split halves, grouped-query causal attention, SwiGLU feed-forward, tied or
untied head), not from the program: it imports nothing of ``repro``.  A
linear layer is whatever the param group holds: ``kernel`` (dense) or the
low-rank pair ``u``, ``v`` (``x @ u @ v``).  Every matmul runs at
``Precision.HIGHEST`` on float32 operands.

``mode="fp8"`` is the control of the correctness check: the same
reference with every matmul operand (forward and backward) rounded to
float8 e4m3 under a per-tensor scale, the lower precision a later change
would be tempted by.

Attention is exact softmax over all keys taken in blocks of queries, the
loss is taken in blocks of rows, training backpropagates layer by layer
and serving's forward rematerializes each layer, so the reference fits on
one chip at the cells' sizes once the program's state is freed.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes the reference needs, from the configuration file."""
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    norm_eps: float
    rope_theta: float
    tie_embeddings: bool
    mode: str = "f32"  # "f32" | "fp8" (the control)

    @classmethod
    def from_config(cls, cfg: dict, mode: str = "f32") -> "Spec":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d_model=d, num_heads=h,
                   num_kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // h,
                   vocab_size=cfg["vocab_size"],
                   norm_eps=cfg["rms_norm_eps"],
                   rope_theta=cfg["rope_theta"],
                   tie_embeddings=cfg["tie_word_embeddings"], mode=mode)


# --------------------------------------------------------------------------
# matmuls
# --------------------------------------------------------------------------

def _round8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale (as float32)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = FP8_MAX / amax
    return (x * s).astype(FP8).astype(jnp.float32) / s


@jax.custom_vjp
def _q8(x):
    """A matmul operand in float8: rounded forward, and its cotangent
    rounded the same way (each under its own scale) backward."""
    return _round8(x)


_q8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def mm(spec: Spec, a, b):
    """``a @ b`` for a (..., k) activation and a (k, n) weight."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if spec.mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def einsum(spec: Spec, eq, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if spec.mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def linear(spec: Spec, g: Dict, x):
    if "kernel" in g:
        y = mm(spec, x, g["kernel"])
    else:
        y = mm(spec, mm(spec, x, g["u"]), g["v"])
    if "bias" in g:
        y = y + g["bias"].astype(jnp.float32)
    return y


def rmsnorm(spec: Spec, scale, x):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + spec.norm_eps) * scale.astype(jnp.float32)


def rope(spec: Spec, x, positions):
    """x (B, S, H, D); positions (B, S) -> rotated by split halves."""
    half = x.shape[-1] // 2
    inv = 1.0 / (spec.rope_theta
                 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, half)
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(spec: Spec, q, k, v, block: int = 256):
    """Causal GQA softmax attention, exact, in blocks of queries."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    block = min(block, s)
    nb = -(-s // block)
    pad = nb * block - s
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qp.reshape(b, nb, block, kvh, g, d)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        qi = jax.lax.dynamic_index_in_dim(qb, i, axis=1, keepdims=False)
        sc = einsum(spec, "bqkgd,btkd->bkgqt", qi, k) * (d ** -0.5)
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return einsum(spec, "bkgqt,btkd->bqkgd", p, v)

    out = jax.lax.map(one, jnp.arange(nb))  # (nb, b, block, kvh, g, d)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nb * block, h, d)
    return out[:, :s]


def layer(spec: Spec, lp: Dict, h, positions):
    b, s, _ = h.shape
    hd, nh, kvh = spec.head_dim, spec.num_heads, spec.num_kv_heads
    a = rmsnorm(spec, lp["norm1"]["scale"], h)
    att = lp["attn"]
    q = linear(spec, att["wq"], a).reshape(b, s, nh, hd)
    k = linear(spec, att["wk"], a).reshape(b, s, kvh, hd)
    v = linear(spec, att["wv"], a).reshape(b, s, kvh, hd)
    q, k = rope(spec, q, positions), rope(spec, k, positions)
    o = attention(spec, q, k, v).reshape(b, s, nh * hd)
    h = h + linear(spec, att["wo"], o)
    f = rmsnorm(spec, lp["norm2"]["scale"], h)
    ffn = lp["ffn"]
    gated = jax.nn.silu(linear(spec, ffn["gate"], f)) * linear(spec, ffn["up"], f)
    return h + linear(spec, ffn["down"], gated)


def hidden(spec: Spec, params: Dict, tokens):
    """Final-norm hidden states (B, S, D) of a causal forward."""
    b, s = tokens.shape
    h = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    h = h.astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(h, lp):
        return jax.checkpoint(functools.partial(layer, spec))(
            lp, h, positions), None

    h, _ = jax.lax.scan(body, h, params["stack"])
    return rmsnorm(spec, params["final_norm"]["scale"], h)


def head(spec: Spec, params: Dict, h):
    """Logits (..., vocab) of final-norm hidden states."""
    if spec.tie_embeddings:
        return mm(spec, h, params["embed"]["embedding"].T)
    return linear(spec, params["unembed"], h)


# --------------------------------------------------------------------------
# training: the first steps of sequential freezing under SGD + momentum
# --------------------------------------------------------------------------

def frozen_names(phase: int) -> tuple:
    """Algorithm 2's two factor groups: ``u`` frozen in phase 0, ``v`` in
    phase 1."""
    return ("u",) if phase == 0 else ("v",)


def learning_rate(opt: dict, step: int) -> float:
    """Warmup then cosine decay, ``step`` counted from 0."""
    base, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    t = step + 1.0
    if t < warm:
        return base * t / max(warm, 1)
    frac = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * 0.5 * (1.0 + np.cos(np.pi * frac))


def flatten(tree, prefix: str = "") -> Dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def unflatten(flat: Dict) -> Dict:
    out: Dict = {}
    for path, x in flat.items():
        node = out
        *head_, last = path.split("/")
        for k in head_:
            node = node.setdefault(k, {})
        node[last] = x
    return out


def _frozen(path: str, phase: int) -> bool:
    return path.rsplit("/", 1)[-1] in frozen_names(phase)


def _f32(t: Dict) -> Dict:
    return {k: v.astype(jnp.float32) for k, v in t.items()}


@functools.lru_cache(maxsize=None)
def _train_fns(spec: Spec, rows: int):
    """Jitted pieces of one layer-by-layer training step: the embedding,
    one layer forward, the head's loss and gradient, one layer's
    gradient (its forward recomputed), the embedding's gradient."""

    def embed_fn(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    def layer_fn(lp, h):
        s = h.shape[1]
        pos = jnp.broadcast_to(jnp.arange(s), h.shape[:2])
        return layer(spec, unflatten(lp), h, pos)

    def head_loss(tr, fr, h, labels):
        params = unflatten({**tr, **fr})
        hf = rmsnorm(spec, params["final_norm"]["scale"], h)
        hf = hf.reshape(-1, hf.shape[-1])
        lf = labels.reshape(-1)
        n = hf.shape[0]
        r = min(rows, n)

        @jax.checkpoint
        def chunk(i):
            hi = jax.lax.dynamic_slice_in_dim(hf, i * r, r)
            li = jax.lax.dynamic_slice_in_dim(lf, i * r, r)
            lg = head(spec, params, hi)[:, :spec.vocab_size]
            gold = jnp.take_along_axis(lg, li[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

        return jnp.sum(jax.lax.map(chunk, jnp.arange(n // r))) / n

    def layer_grad(tr, fr, h, dy):
        def f(tr, h):
            return layer_fn({**tr, **fr}, h)
        _, vjp = jax.vjp(f, tr, h)
        return vjp(dy)

    def embed_grad(shape, tokens, dh):
        return jnp.zeros(shape, jnp.float32).at[tokens.reshape(-1)].add(
            dh.reshape(-1, dh.shape[-1]))

    return (jax.jit(embed_fn), jax.jit(layer_fn),
            jax.jit(jax.value_and_grad(head_loss, argnums=(0, 2))),
            jax.jit(layer_grad),
            jax.jit(embed_grad, static_argnums=(0,)))


@functools.partial(jax.jit, static_argnums=(6,))
def _update(p, mu, g, lr, momentum, wd, dtype):
    mu = momentum * mu + g
    p = p.astype(jnp.float32)
    return (p - lr * (mu + wd * p)).astype(dtype), mu


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_row(x, i, row):
    return x.at[i].set(row)


def _sq(x) -> float:
    return float(jnp.sum(jnp.square(x.astype(jnp.float32))))


def train_steps(spec: Spec, params: Dict, batches: Sequence[Dict],
                phases: Sequence[int], opt: dict, rows: int = 1024) -> Dict:
    """Run ``len(batches)`` SGD-momentum steps of sequential freezing.

    Backpropagation goes layer by layer: the layer inputs of the step are
    kept, then each layer's gradient is taken with its forward recomputed
    and its weights updated at once, so only one layer's gradient is ever
    on the device.  Gradients, momentum and updates are float32; each
    updated weight is stored back in its leaf's dtype, the bfloat16 the
    configuration states, as a bfloat16 checkpoint holds it.  The momentum
    lives on the host.  Returns the loss of each step, the squared norm of
    each leaf's gradient at the first step (``first_grad_sq``) and at the
    first step that trained it (``trained_grad_sq``), and the final flat
    params.  The stacked leaves of ``params`` are updated in place (their
    buffers are donated): pass weights made for the reference alone.
    """
    flat = flatten(params)
    stacked = sorted(p for p in flat if p.startswith("stack/"))
    top = sorted(p for p in flat if not p.startswith("stack/"))
    n_layers = flat[stacked[0]].shape[0]
    mu = {p: np.zeros(x.shape, np.float32) for p, x in flat.items()}
    embed_fn, layer_fn, head_grad, layer_grad, embed_grad = _train_fns(
        spec, rows)
    losses: List[float] = []
    first_grad_sq = None
    trained_grad_sq: Dict[str, float] = {}

    def update(path, g, lr, row=None):
        x = flat[path] if row is None else flat[path][row]
        m = mu[path] if row is None else mu[path][row]
        new, new_mu = _update(x, jnp.asarray(m), g, lr, opt["momentum"],
                              opt["weight_decay"], jnp.dtype(x.dtype))
        if row is None:
            flat[path], mu[path] = new, np.asarray(new_mu)
        else:
            flat[path] = _set_row(flat[path], row, new)
            mu[path][row] = np.asarray(new_mu)

    for step, (batch, phase) in enumerate(zip(batches, phases)):
        lr = learning_rate(opt, step)
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        hs = [embed_fn(flat["embed/embedding"], tokens)]
        for i in range(n_layers):
            lp = {p[len("stack/"):]: flat[p][i] for p in stacked}
            hs.append(layer_fn(lp, hs[-1]))
        tr = {p: flat[p] for p in top if not _frozen(p, phase)}
        fr = {p: flat[p] for p in top if _frozen(p, phase)}
        val, (g_top, dh) = head_grad(_f32(tr), fr, hs.pop(), labels)
        losses.append(float(val))
        sq = {p: 0.0 for p in stacked + top if not _frozen(p, phase)}
        for i in reversed(range(n_layers)):
            lp = {p[len("stack/"):]: flat[p][i] for p in stacked}
            ltr = {k: v for k, v in lp.items() if not _frozen(k, phase)}
            lfr = {k: v for k, v in lp.items() if _frozen(k, phase)}
            g_layer, dh = layer_grad(_f32(ltr), lfr, hs.pop(), dh)
            for k, g in g_layer.items():
                sq["stack/" + k] += _sq(g)
                update("stack/" + k, g, lr, row=i)
            del g_layer
        g_emb = embed_grad(flat["embed/embedding"].shape, tokens, dh)
        g_top = dict(g_top)
        g_top["embed/embedding"] = g_top.get("embed/embedding", 0.0) + g_emb
        for p, g in g_top.items():
            sq[p] = _sq(g)
            update(p, g, lr)
        del g_top, g_emb, dh
        if first_grad_sq is None:
            first_grad_sq = dict(sq)
        for p, v in sq.items():
            trained_grad_sq.setdefault(p, v)
    return {"losses": losses, "first_grad_sq": first_grad_sq,
            "trained_grad_sq": trained_grad_sq, "params": flat}


# --------------------------------------------------------------------------
# serving: the served model, and logits of prompts with their served tokens
# --------------------------------------------------------------------------

def truncate(u, v, rank: int):
    """The best rank-``rank`` approximation of ``u @ v`` as float32
    factors (QR of each factor, SVD of the small core), over any leading
    stack dimensions."""
    def one(u, v):
        qu, ru = jnp.linalg.qr(u)
        qv, rv = jnp.linalg.qr(v.T)
        uu, sv, vt = jnp.linalg.svd(jnp.matmul(ru, rv.T, precision=HIGHEST),
                                    full_matrices=False)
        a = jnp.matmul(qu, uu[:, :rank] * sv[:rank], precision=HIGHEST)
        b = jnp.matmul(vt[:rank], qv.T, precision=HIGHEST)
        return a, b

    u, v = u.astype(jnp.float32), v.astype(jnp.float32)
    lead = u.shape[:-2]
    uf = u.reshape((-1,) + u.shape[-2:])
    vf = v.reshape((-1,) + v.shape[-2:])
    a, b = jax.jit(jax.vmap(one))(uf, vf)
    return a.reshape(lead + a.shape[-2:]), b.reshape(lead + b.shape[-2:])


def served_params(params: Dict, served_shapes: Dict) -> Dict:
    """The served model from the trained-layout weights, in float32, as
    the serving artifact's shapes say it is cut: a factor pair served as
    one kernel is multiplied out, one served at a lower rank is truncated
    to it, the rest is served as it is."""
    def walk(p, s):
        if isinstance(p, dict) and "u" in p and "v" in p and \
                not isinstance(p["u"], dict):
            out = {k: x for k, x in p.items() if k not in ("u", "v")}
            u, v = p["u"].astype(jnp.float32), p["v"].astype(jnp.float32)
            if "kernel" in s:
                out["kernel"] = jnp.matmul(u, v, precision=HIGHEST)
            elif s["u"].shape[-1] < u.shape[-1]:
                out["u"], out["v"] = truncate(u, v, s["u"].shape[-1])
            else:
                out["u"], out["v"] = u, v
            return out
        if isinstance(p, dict):
            return {k: walk(x, s[k]) for k, x in p.items()}
        return p.astype(jnp.float32)

    return walk(params, served_shapes)


@functools.lru_cache(maxsize=None)
def _served_fn(spec: Spec, control: "Spec | None"):
    def rows_logits(sp, params, tokens, rows):
        h = hidden(sp, params, tokens)
        h = h.reshape(-1, h.shape[-1])[rows]
        return head(sp, params, h)[:, :sp.vocab_size]

    def f(params, tokens, rows, gold):
        lg = rows_logits(spec, params, tokens, rows)
        best = jnp.max(lg, axis=-1)
        gap = best - jnp.take_along_axis(lg, gold[:, None], axis=-1)[:, 0]
        if control is None:
            return gap, gap
        pick = jnp.argmax(rows_logits(control, params, tokens, rows), axis=-1)
        cgap = best - jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return gap, cgap

    return jax.jit(f)


def served_gaps(spec: Spec, params: Dict, seqs, pad_len: int, n_seqs: int,
                n_rows: int, control: "Spec | None" = None):
    """For each (prompt, served tokens) of ``seqs``, at every served
    position: the gap by which the served token's logit lies below the
    reference's best, and, with ``control``, the gap of the token the
    control puts first.  Sequences are padded at the end to ``pad_len``
    (causal: the padding changes no position read), and the batch to
    ``n_seqs`` sequences and ``n_rows`` served positions, so one program
    serves every run."""
    if len(seqs) > n_seqs:
        raise ValueError(f"{len(seqs)} sequences, room for {n_seqs}")
    tokens = np.zeros((n_seqs, pad_len), np.int32)
    rows, gold = [], []
    for j, (prompt, served) in enumerate(seqs):
        seq = np.concatenate([prompt, served[:-1]])
        tokens[j, :seq.size] = seq
        start = j * pad_len + prompt.size - 1
        rows.extend(range(start, start + served.size))
        gold.extend(served.tolist())
    n = len(rows)
    if n > n_rows:
        raise ValueError(f"{n} served positions, room for {n_rows}")
    rows += [0] * (n_rows - n)
    gold += [0] * (n_rows - n)
    gap, cgap = _served_fn(spec, control)(
        params, jnp.asarray(tokens), jnp.asarray(rows, jnp.int32),
        jnp.asarray(gold, jnp.int32))
    return np.asarray(gap)[:n], (np.asarray(cgap)[:n]
                                 if control is not None else None)
