"""Plain float32 reference of a dense Qwen3-style decoder (RMSNorm, rotary
GQA attention, SwiGLU MLP) with LoRA adapters on a frozen base, and its
operations and bytes counted from shapes.

A configuration names its reference module with ``"reference": "<name>"``
and the harness loads ``bench/references/<name>.py`` (``harness.
load_reference``).  Every reference module provides:

  Shape.from_config(spec)             the model as run, read from the
                                      configuration file; ``n_layers`` is the
                                      depth of the stacked layers
  make_weights(key, s, job)           (frozen base, adapters) from the seed,
                                      the program's initializer copied, the
                                      pool's codec applied where ``job``
                                      quantizes it; the adapters' leaves are
                                      stacked over the ``n_layers`` layers
  loss_fn(adapters, base, batch, s, job)
                                      the token-mean cross-entropy in float32
  step_flops_per_token(s, job, seq)   required operations of one training
                                      step per token (``mfu``)
  layer_elems(s)                      elements of one pool row (the dequant
                                      kernel's bytes)

``bench/reference.py`` holds what no architecture changes (the job, the
pool's codec, AdamW, the run of the first steps at ``highest`` precision).
The run's record (``harness.run_cell``) carries the module, its ``Shape``,
the ``Job`` and the program's plan and model, so a metric reader can count
a kernel of its own architecture from the same module.

This module imports nothing of the program.  From the seed it makes the same
bf16 weights and adapters as the program's initializer (the same
``jax.random`` calls, copied), and computes one layer at a time under
``jax.checkpoint`` and the head in token blocks, so that it fits beside
nothing on a 16 GB chip.

Departures from the published Qwen3 layer, shared with the program: no
per-head RMSNorm of queries and keys (QK-norm), and random weights.

Required operations of one LoRA training step, per token (multiply-add = 2):

  forward        every base matrix once, the adapters' two thin matrices,
                 attention's two score/value products over the causal
                 context (on average (seq + 1) / 2 keys), and the LM head;
  backward       activation gradients only through the frozen base and the
                 head (one product per matrix), attention's four products,
                 and the adapters' weight and activation gradients (two
                 products per thin matrix);
  not counted    recomputation, the per-step merge of the adapters into the
                 base, the codec, the optimizer and elementwise work.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.reference import BF16, F32, Job, codec, dotted

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


def target_paths(layers, targets) -> list:
    """Dotted paths of the stacked matrices the adapters decorate."""
    return [dotted(p) for p, leaf in
            jax.tree_util.tree_flatten_with_path(layers)[0]
            if leaf.ndim == 3 and any(dotted(p) == t or
                                      dotted(p).startswith(t + ".")
                                      for t in targets)]


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
        name = dotted(path)
        if name not in targets:
            continue
        n, din, dout = leaf.shape
        a = _normal(jax.random.fold_in(key_a, i), (n, job.rank, dout)) \
            * (1.0 / math.sqrt(din))
        node = adapters
        parts = name.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = {"A": a, "B": jnp.zeros((n, din, job.rank), BF16)}
    if job.pool_dtype != "none":
        layers = codec(layers, job.pool_dtype)
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
# Operations and bytes from shapes
# ---------------------------------------------------------------------------

def layer_matrices(s) -> dict:
    """``{"attn.w_q": (din, dout), ...}`` of one decoder layer."""
    d, qd, kvd = s.d_model, s.n_heads * s.d_head, s.n_kv_heads * s.d_head
    return {"attn.w_q": (d, qd), "attn.w_k": (d, kvd), "attn.w_v": (d, kvd),
            "attn.w_o": (qd, d), "mlp.w_gate": (d, s.d_ff),
            "mlp.w_up": (d, s.d_ff), "mlp.w_down": (s.d_ff, d)}


def layer_elems(s) -> int:
    """Elements of one layer of the pool: its matrices and two norms."""
    return sum(a * b for a, b in layer_matrices(s).values()) + 2 * s.d_model


def step_flops_per_token(s, job, seq: int, causal: bool = True) -> float:
    mats = layer_matrices(s)
    dense = sum(a * b for a, b in mats.values())
    adapted = [k for k in mats
               if any(k == t or k.startswith(t + ".") for t in job.targets)]
    lora = sum(2 * job.rank * (mats[k][0] + mats[k][1]) for k in adapted)
    ctx = (seq + 1) / 2 if causal else seq
    attn = 2 * 2 * s.n_heads * s.d_head * ctx
    head = 2 * s.d_model * s.vocab
    forward = s.n_layers * (2 * dense + attn + lora) + head
    backward = s.n_layers * (2 * dense + 2 * attn + 2 * lora) + head
    return float(forward + backward)
