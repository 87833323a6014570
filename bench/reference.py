"""Plain float32 reference of the benchmark's training job: a Qwen3-style
decoder (RMSNorm, rotary GQA attention, SwiGLU MLP) with LoRA adapters on a
frozen base, its token-mean cross-entropy, and AdamW with global-norm
clipping.

It imports nothing of the program.  From the seed it makes the same bf16
weights and adapters as the program's initializer (the same
``jax.random`` calls, copied), puts the frozen layers through the pool's
blockwise-absmax codec where the job streams a quantized pool (copied from
the program's ``quantize_rows`` / dequant kernel: one row per layer, the
layer's leaves concatenated in pytree order), and then computes in float32
at ``highest`` matmul precision, one layer at a time under ``jax.checkpoint``
and the head in token blocks, so that it fits beside nothing on a 16 GB chip.

Departures from the published Qwen3 layer, shared with the program: no
per-head RMSNorm of queries and keys (QK-norm), and random weights.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
QUANT_BLOCK = 256
QMAX = {"int8": 127.0, "int4": 7.0}
XENT_BLOCK = 256
LORA_KEY_SALT = 0x10A


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model as run, read from a configuration file's published keys."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=bool(c["tie_word_embeddings"]),
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]))


@dataclasses.dataclass(frozen=True)
class Job:
    """LoRA and optimizer settings of a traffic mix."""
    rank: int
    alpha: float
    targets: tuple
    pool_dtype: str
    lr: float
    b1: float
    b2: float
    eps: float
    grad_clip: float
    weight_decay: float

    @classmethod
    def from_mix(cls, mix: dict) -> "Job":
        lora, opt = mix["lora"], mix["optimizer"]
        return cls(rank=lora["rank"], alpha=float(lora["alpha"]),
                   targets=tuple(lora["targets"]), pool_dtype=mix["pool_dtype"],
                   lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                   grad_clip=opt["grad_clip"],
                   weight_decay=opt["weight_decay"])


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _normal(key, shape):
    return jax.random.normal(key, shape, BF16)


def _init_layer(key, s: Shape):
    d, qd, kvd = s.d_model, s.n_heads * s.d_head, s.n_kv_heads * s.d_head
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 6)
    sc = 1.0 / math.sqrt(d)
    attn = {"w_q": _normal(ka[0], (d, qd)) * sc,
            "w_k": _normal(ka[1], (d, kvd)) * sc,
            "w_v": _normal(ka[2], (d, kvd)) * sc,
            "w_o": _normal(ka[3], (qd, d)) / math.sqrt(qd)}
    km = jax.random.split(ks[2], 3)
    mlp = {"w_up": _normal(km[0], (d, s.d_ff)) * (1.0 / math.sqrt(d)),
           "w_down": _normal(km[1], (s.d_ff, d)) * (1.0 / math.sqrt(s.d_ff)),
           "w_gate": _normal(km[2], (d, s.d_ff)) * (1.0 / math.sqrt(d))}
    return {"attn": attn, "mlp": mlp,
            "norm1": {"scale": jnp.ones((d,), BF16)},
            "norm2": {"scale": jnp.ones((d,), BF16)}}


def _dotted(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def target_paths(layers, targets) -> list:
    """Dotted paths of the stacked matrices the adapters decorate."""
    return [_dotted(p) for p, leaf in
            jax.tree_util.tree_flatten_with_path(layers)[0]
            if leaf.ndim == 3 and any(_dotted(p) == t or
                                      _dotted(p).startswith(t + ".")
                                      for t in targets)]


def _codec(layers, bits_name: str):
    """Each layer's leaves as one row, blockwise-absmax quantized and
    dequantized back to the leaves' dtype, one layer at a time."""
    qmax = QMAX[bits_name]
    leaves, treedef = jax.tree_util.tree_flatten(layers)

    def one(row_leaves):
        flat = jnp.concatenate([l.reshape(-1) for l in row_leaves])
        n = flat.shape[0]
        nb = -(-n // QUANT_BLOCK)
        blocks = jnp.pad(flat.astype(F32), (0, nb * QUANT_BLOCK - n)).reshape(
            nb, QUANT_BLOCK)
        scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1) / qmax, 1e-12)
        codes = jnp.clip(jnp.round(blocks / scale[:, None]), -qmax, qmax)
        deq = (codes * scale[:, None]).reshape(-1)
        out, off = [], 0
        for l in row_leaves:
            out.append(deq[off:off + l.size].reshape(l.shape).astype(l.dtype))
            off += l.size
        return out

    rows = jax.lax.map(one, leaves)
    return jax.tree_util.tree_unflatten(treedef, rows)


def make_weights(key, s: Shape, job: Job):
    """(frozen base, adapters): the program's initializer, copied, with the
    pool's codec applied to the layers when the job quantizes the pool."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layers = jax.vmap(lambda k: _init_layer(k, s))(
        jax.random.split(k_layers, s.n_layers))
    base = {"embed": _normal(k_emb, (s.vocab, s.d_model))
            * (1.0 / math.sqrt(s.d_model)),
            "final_norm": {"scale": jnp.ones((s.d_model,), BF16)}}
    if not s.tied:
        base["lm_head"] = _normal(k_head, (s.d_model, s.vocab)) \
            * (1.0 / math.sqrt(s.d_model))
    key_a = jax.random.fold_in(key, LORA_KEY_SALT)
    targets = set(target_paths(layers, job.targets))
    adapters: dict = {}
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(layers)[0]):
        dotted = _dotted(path)
        if dotted not in targets:
            continue
        n, din, dout = leaf.shape
        a = _normal(jax.random.fold_in(key_a, i), (n, job.rank, dout)) \
            * (1.0 / math.sqrt(din))
        node = adapters
        parts = dotted.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = {"A": a, "B": jnp.zeros((n, din, job.rank), BF16)}
    if job.pool_dtype != "none":
        layers = _codec(layers, job.pool_dtype)
    base["layers"] = layers
    return base, adapters


# ---------------------------------------------------------------------------
# Forward and loss, float32
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """Rotary embedding, halves rotated (x: (B, S, H, D))."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lin(h, w, pair, scale):
    y = h @ w.astype(F32)
    if pair is not None:
        y = y + scale * ((h @ pair["B"]) @ pair["A"])
    return y


def _layer(x, w, ad, s: Shape, scale):
    b, n, _ = x.shape
    get = lambda grp, k: ad.get(grp, {}).get(k)
    h = _rms(x, w["norm1"]["scale"], s.eps)
    a = w["attn"]
    q = _lin(h, a["w_q"], get("attn", "w_q"), scale).reshape(
        b, n, s.n_heads, s.d_head)
    k = _lin(h, a["w_k"], get("attn", "w_k"), scale).reshape(
        b, n, s.n_kv_heads, s.d_head)
    v = _lin(h, a["w_v"], get("attn", "w_v"), scale).reshape(
        b, n, s.n_kv_heads, s.d_head)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    g = s.n_heads // s.n_kv_heads
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(s.d_head)
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, n, -1)
    x = x + _lin(o, a["w_o"], get("attn", "w_o"), scale)
    h = _rms(x, w["norm2"]["scale"], s.eps)
    m = w["mlp"]
    up = jax.nn.silu(_lin(h, m["w_gate"], get("mlp", "w_gate"), scale)) \
        * _lin(h, m["w_up"], get("mlp", "w_up"), scale)
    return x + _lin(up, m["w_down"], get("mlp", "w_down"), scale)


def loss_fn(adapters, base, batch, s: Shape, job: Job):
    """Mean next-token cross-entropy over the labels that are not ignored
    (``-100``); ``adapters`` are float32."""
    scale = job.alpha / job.rank
    x = jnp.take(base["embed"], batch["tokens"], axis=0).astype(F32)

    @jax.checkpoint
    def body(x, wa):
        w, ad = wa
        return _layer(x, w, ad, s, scale), None

    x, _ = jax.lax.scan(body, x, (base["layers"], adapters))
    x = _rms(x, base["final_norm"]["scale"], s.eps)
    head = base["embed"].T if s.tied else base["lm_head"]
    b, n, d = x.shape
    blocks = -(-n // XENT_BLOCK)
    pad = blocks * XENT_BLOCK - n
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(
        b, blocks, XENT_BLOCK, d).transpose(1, 0, 2, 3)
    ls = jnp.pad(batch["labels"], ((0, 0), (0, pad)),
                 constant_values=-100).reshape(b, blocks, XENT_BLOCK
                                               ).transpose(1, 0, 2)

    @jax.checkpoint
    def xent(tot, xl):
        xc, lc = xl
        logits = xc @ head.astype(F32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                   axis=-1)[..., 0]
        return tot + jnp.where(lc != -100, lse - gold, 0.0).sum(), None

    tot, _ = jax.lax.scan(xent, jnp.zeros((), F32), (xs, ls))
    cnt = jnp.sum(batch["labels"] != -100)
    return tot / jnp.maximum(cnt, 1).astype(F32)


# ---------------------------------------------------------------------------
# Training steps
# ---------------------------------------------------------------------------

def _step(opt, base, batch, s: Shape, job: Job, store_dtype):
    """One AdamW step on the adapters: the forward sees the adapters as the
    job stores them (``store_dtype``), the update is float32."""
    used = jax.tree.map(lambda m: m.astype(store_dtype).astype(F32),
                        opt["master"])
    loss, g = jax.value_and_grad(loss_fn)(used, base, batch, s, job)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, job.grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if job.grad_clip else 1.0
    g = jax.tree.map(lambda x: x * clip, g)
    t = opt["step"] + 1
    tf = t.astype(F32)
    m = jax.tree.map(lambda m_, g_: job.b1 * m_ + (1 - job.b1) * g_,
                     opt["m"], g)
    v = jax.tree.map(lambda v_, g_: job.b2 * v_ + (1 - job.b2) * g_ * g_,
                     opt["v"], g)
    bc1, bc2 = 1.0 - job.b1 ** tf, 1.0 - job.b2 ** tf
    master = jax.tree.map(
        lambda w, m_, v_: w - job.lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                      + job.eps)
                                        + job.weight_decay * w),
        opt["master"], m, v)
    return {"master": master, "m": m, "v": v, "step": t}, loss, g


def layer_rows(tree, n_layers: int) -> dict:
    """``{"<path>[<layer>]": flat float64 row}`` over the stacked adapter
    leaves: each layer's part of each leaf counts as one leaf."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf, np.float64)[:n_layers].reshape(n_layers, -1)
        for i, row in enumerate(arr):
            out[f"{_dotted(path)}[{i}]"] = row
    return out


def run(key, batches: list, s: Shape, job: Job, store_dtype=BF16) -> dict:
    """The first ``len(batches)`` steps from the seed's weights.

    Returns host values: ``losses``; ``grad1`` (per layer and leaf, the
    first gradient as the optimizer takes it, i.e. clipped); ``change``
    (master adapters after the last step minus their initial values);
    ``grad_max`` (per layer and leaf, the largest norm of any step's
    gradient)."""
    with jax.default_matmul_precision("highest"):
        base, adapters = jax.jit(functools.partial(
            make_weights, s=s, job=job))(key)
        init = jax.tree.map(lambda a: a.astype(F32), adapters)
        opt = {"master": init,
               "m": jax.tree.map(jnp.zeros_like, init),
               "v": jax.tree.map(jnp.zeros_like, init),
               "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(functools.partial(_step, s=s, job=job,
                                         store_dtype=store_dtype))
        losses, grad_max, grad1 = [], None, None
        for b in batches:
            opt, loss, g = step(opt, base, {k: jnp.asarray(v)
                                            for k, v in b.items()})
            rows = layer_rows(g, s.n_layers)
            grad1 = grad1 or rows
            norms = {k: float(np.linalg.norm(v)) for k, v in rows.items()}
            grad_max = norms if grad_max is None else {
                k: max(v, grad_max[k]) for k, v in norms.items()}
            losses.append(float(loss))
        change = jax.tree.map(lambda a, b: a - b, opt["master"], init)
        out = {"losses": losses, "grad1": grad1,
               "change": layer_rows(change, s.n_layers),
               "grad_max": grad_max}
    del base, opt, init, adapters
    return out
