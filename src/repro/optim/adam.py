"""Mixed-precision optimizer: AdamW + Adafactor-factored variant.

Placement policy (DESIGN.md §2): the fp32 master + moments are the paper's
"host-resident optimizer copy".  On the TPU target they sit either fully
sharded across every mesh axis (the pooled-HBM analogue; default — the only
mode XLA:CPU compiles under SPMD) or in ``pinned_host`` memory
(``placement='host'``, real-TPU/off-SPMD path).  ``mode='adafactor'`` factors
the second moment for the ≥100B configs so the states fit a 16 GB v5e chip
even single-pod.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime import spans


@dataclasses.dataclass(frozen=True)
class OptConfig:
    mode: str = "adamw"            # adamw | adafactor
    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    master_dtype: Any = jnp.float32
    placement: str = "device"      # device | host (pinned_host, TPU target)


def _factored(shape) -> bool:
    return len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1


# ---------------------------------------------------------------------------
# Frozen-base masking (LoRA / adapter fine-tuning)
# ---------------------------------------------------------------------------
# The optimizer state (fp32 master + moments) is the paper's host-resident
# copy — ~6 bytes/param that dominate host DRAM and the §4.3 download
# traffic.  Under a frozen base only the mask-True leaves (the adapters)
# need any of it, so the state is built over the *pruned* trainable subtree
# rather than carrying dead full-size moments for frozen weights.

def trainable_leaves(tree, mask):
    """Prune ``tree`` to the ``mask``-True leaves.

    ``mask`` is a boolean pytree with ``tree``'s structure (e.g.
    ``repro.models.lora.param_mask``).  Dict nodes whose every leaf is
    frozen are dropped entirely, so the result's pytree structure is
    exactly the trainable substructure — the same structure the frozen-base
    dispatch deposits gradients in.  Feed the result to
    :func:`init_opt_state` / :func:`opt_state_specs`.
    """
    if isinstance(tree, dict):
        out = {}
        for k in tree:
            sub = trainable_leaves(tree[k], mask[k])
            if sub is not None:
                out[k] = sub
        return out or None
    return tree if mask else None


@spans.scope(spans.OPTIMIZER)
def merge_trainable(full, trainable, mask):
    """Inverse of :func:`trainable_leaves`: graft updated trainable leaves
    back into the full tree; mask-False leaves pass through untouched."""
    if isinstance(full, dict):
        sub = trainable or {}
        return {k: merge_trainable(full[k], sub.get(k), mask[k])
                for k in full}
    if mask:
        if trainable is None:
            raise ValueError("mask marks a leaf trainable but the updated "
                             "subtree does not provide it")
        return trainable
    return full


def init_opt_state(params, cfg: OptConfig):
    """master (fp32) + first/second moments (+ step counter)."""
    # explicit copy: fp32 param leaves would otherwise ALIAS the master
    # (astype is a no-op view) and break buffer donation
    master = jax.tree.map(
        lambda p: jnp.array(p, dtype=cfg.master_dtype, copy=True), params)
    if cfg.mode == "adamw":
        m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return {"master": master, "m": m, "v": v, "step": jnp.int32(0)}
    # adafactor: factored second moment for >=2D leaves, bf16 first moment
    def vrow(p):
        return jnp.zeros(p.shape[:-1], jnp.float32) if _factored(p.shape) \
            else jnp.zeros(p.shape, jnp.float32)

    def vcol(p):
        return jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32) \
            if _factored(p.shape) else jnp.zeros((1,), jnp.float32)

    return {"master": master,
            "m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16), params),
            "vr": jax.tree.map(vrow, params),
            "vc": jax.tree.map(vcol, params),
            "step": jnp.int32(0)}


def global_grad_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


@spans.scope(spans.OPTIMIZER)
def apply_updates(opt_state, grads, cfg: OptConfig, param_like=None,
                  grad_norm=None):
    """Returns (new_params, new_opt_state, metrics).

    ``param_like`` (a params pytree) fixes the per-leaf compute dtype of the
    returned params; defaults to bfloat16 everywhere.  ``grad_norm``
    overrides the clipping norm — required inside ``shard_map`` regions
    where ``grads`` leaves are local shards and the GLOBAL norm needs a
    ``psum`` the caller must supply (the dispatch runtime's in-program
    async optimizer does exactly this)."""
    step = opt_state["step"] + 1
    gnorm = global_grad_norm(grads) if grad_norm is None else grad_norm
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if cfg.grad_clip else 1.0
    t = step.astype(jnp.float32)
    if cfg.mode == "adamw":
        bc1 = 1.0 - cfg.b1 ** t
        bc2 = 1.0 - cfg.b2 ** t

        def upd(master, g, m, v):
            g = g.astype(jnp.float32) * scale
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
            master = master - cfg.lr * (u + cfg.weight_decay * master)
            return master, m, v

        new = jax.tree.map(upd, opt_state["master"], grads,
                           opt_state["m"], opt_state["v"])
        master = jax.tree.map(lambda x: x[0], new, is_leaf=lambda x: isinstance(x, tuple))
        m = jax.tree.map(lambda x: x[1], new, is_leaf=lambda x: isinstance(x, tuple))
        v = jax.tree.map(lambda x: x[2], new, is_leaf=lambda x: isinstance(x, tuple))
        state = {"master": master, "m": m, "v": v, "step": step}
    else:
        def upd(master, g, m, vr, vc):
            g = g.astype(jnp.float32) * scale
            m = (cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g)
            g2 = jnp.square(g) + 1e-30
            if _factored(g.shape):
                vr = cfg.b2 * vr + (1 - cfg.b2) * g2.mean(axis=-1)
                vc = cfg.b2 * vc + (1 - cfg.b2) * g2.mean(axis=-2)
                denom = jnp.sqrt(vr[..., None] * vc[..., None, :]
                                 / jnp.maximum(vr.mean(axis=-1, keepdims=True)[..., None], 1e-30)) \
                    + cfg.eps
            else:
                vr = cfg.b2 * vr + (1 - cfg.b2) * g2
                denom = jnp.sqrt(vr) + cfg.eps
            master = master - cfg.lr * (m / denom + cfg.weight_decay * master)
            return master, m.astype(jnp.bfloat16), vr, vc

        new = jax.tree.map(upd, opt_state["master"], grads, opt_state["m"],
                           opt_state["vr"], opt_state["vc"])
        pick = lambda i: jax.tree.map(lambda x: x[i], new,
                                      is_leaf=lambda x: isinstance(x, tuple))
        state = {"master": pick(0), "m": pick(1), "vr": pick(2), "vc": pick(3),
                 "step": step}
    if param_like is not None:
        params = jax.tree.map(lambda x, p: x.astype(p.dtype),
                              state["master"], param_like)
    else:
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), state["master"])
    return params, state, {"grad_norm": gnorm, "step": step}


def opt_state_specs(param_spec_tree, cfg: OptConfig):
    """PartitionSpecs for the opt state, mirroring the param specs.

    Factored Adafactor stats drop the last (vr) / second-to-last (vc) dim of
    the param spec."""
    master = param_spec_tree
    if cfg.mode == "adamw":
        return {"master": master, "m": master, "v": master, "step": P()}

    def vr_spec(s):
        parts = list(s)
        return P(*parts[:-1]) if len(parts) >= 2 else s

    def vc_spec(s):
        parts = list(s)
        return P(*(parts[:-2] + parts[-1:])) if len(parts) >= 2 else P(None)

    is_spec = lambda x: isinstance(x, P)
    return {"master": master, "m": master,
            "vr": jax.tree.map(vr_spec, master, is_leaf=is_spec),
            "vc": jax.tree.map(vc_spec, master, is_leaf=is_spec),
            "step": P()}
