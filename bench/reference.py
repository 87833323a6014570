"""Plain float32 reference of the benchmark's training job: what no
architecture changes.  The architecture itself (its ``Shape``, weights from
the seed, float32 loss, and operations and bytes per token) is a module of
its own under ``bench/references/``, named by the configuration
(``"reference": "<name>"``); ``bench/references/qwen3_dense.py`` states the
interface every such module provides.

Here: the job (LoRA and optimizer settings of a traffic mix); the pool's
blockwise-absmax codec (copied from the program's ``quantize_rows`` /
dequant kernel: one row per layer, the layer's leaves concatenated in pytree
order), which a module's ``make_weights`` puts the frozen layers through
where the job streams a quantized pool; AdamW with global-norm clipping; and
:func:`run`, the first steps of a module's model at ``highest`` matmul
precision.  It imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
QUANT_BLOCK = 256
QMAX = {"int8": 127.0, "int4": 7.0}


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


def dotted(path) -> str:
    """A pytree path as ``"attn.w_q"``."""
    return ".".join(str(getattr(p, "key", p)) for p in path)


# ---------------------------------------------------------------------------
# The pool's codec
# ---------------------------------------------------------------------------

def codec(layers, bits_name: str):
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


# ---------------------------------------------------------------------------
# Training steps
# ---------------------------------------------------------------------------

def _step(opt, base, batch, module, s, job: Job, store_dtype):
    """One AdamW step on the adapters of ``module``'s model: the forward sees
    the adapters as the job stores them (``store_dtype``), the update is
    float32."""
    used = jax.tree.map(lambda m: m.astype(store_dtype).astype(F32),
                        opt["master"])
    loss, g = jax.value_and_grad(module.loss_fn)(used, base, batch, s, job)
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
            out[f"{dotted(path)}[{i}]"] = row
    return out


def run(key, batches: list, module, s, job: Job, store_dtype=BF16) -> dict:
    """The first ``len(batches)`` steps of the reference ``module``'s model
    of shape ``s`` from the seed's weights.

    Returns host values: ``losses``; ``grad1`` (per layer and leaf, the
    first gradient as the optimizer takes it, i.e. clipped); ``change``
    (master adapters after the last step minus their initial values);
    ``grad_max`` (per layer and leaf, the largest norm of any step's
    gradient)."""
    with jax.default_matmul_precision("highest"):
        base, adapters = jax.jit(functools.partial(
            module.make_weights, s=s, job=job))(key)
        init = jax.tree.map(lambda a: a.astype(F32), adapters)
        opt = {"master": init,
               "m": jax.tree.map(jnp.zeros_like, init),
               "v": jax.tree.map(jnp.zeros_like, init),
               "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(functools.partial(_step, module=module, s=s, job=job,
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
