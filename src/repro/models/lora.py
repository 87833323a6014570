"""Low-rank adapters (LoRA) over the stacked layer pool.

The paper's headline fine-tuning claim (Qwen3-235B LoRA at 31K tokens on one
server) rests on the base model being *frozen*: only rank-``r`` adapter
factors train, so the traveling gradient buffer, the end-of-ring gradient
deposit, and the §4.3 host-resident optimizer copies all shrink from
parameter size to adapter size.  This module owns the adapter math; the
frozen-base ring execution lives in :mod:`repro.core.dispatch`.

Representation
--------------
Adapters mirror the stacked layer pool: ``params["layers"]`` leaves are
``(L, din, dout)`` (a leading layer axis over per-layer matrices), and the
adapter tree replaces each *targeted* leaf with ``{"A": (L, r, dout),
"B": (L, din, r)}``.  The adapted weight is

    W_eff = W + (alpha / r) * B @ A

with ``B`` zero-initialised (so a fresh adapter is a bit-exact no-op) and
``A`` Gaussian — the standard LoRA parameterisation.  Because adapters keep
the leading layer axis they shard, pad, ring-ship and deposit exactly like
the dense pool (``P("model", ...)`` over the layer dim), just ~100-1000x
smaller.

Only plain projection matrices — stacked rank-3 leaves — are adaptable:
norm scales (rank-2 stacked) and per-expert / per-head factor tensors
(rank-4+ stacked: MoE experts, MLA ``w_q``/``w_uk``/``w_uv``) stay frozen.
``target_modules`` selects among the adaptable leaves by dotted path
(``"attn"`` matches every ``attn.*`` matrix, ``"attn.w_q"`` exactly one).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime import spans

DEFAULT_TARGETS = ("attn", "mlp")


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    rank: int = 8
    alpha: float = 16.0
    target_modules: tuple = DEFAULT_TARGETS

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")
        object.__setattr__(self, "target_modules",
                           tuple(self.target_modules))

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def _dotted(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def _matches(dotted: str, targets) -> bool:
    return any(dotted == t or dotted.startswith(t + ".") for t in targets)


def target_leaf_paths(layers, cfg: LoraConfig) -> list[str]:
    """Dotted paths (within one layer) of the leaves ``cfg`` adapts, in the
    pool's deterministic flatten order.  ``layers`` is the stacked
    ``params["layers"]`` tree (arrays or ShapeDtypeStructs).

    Raises ValueError for any target that matches nothing — a typo'd or
    arch-inapplicable module (e.g. ``"mlp"`` on a pure-MoE layer) must not
    silently train fewer adapters than the user asked for."""
    out = []
    adaptable = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(layers)[0]:
        dotted = _dotted(path)
        if leaf.ndim != 3:
            continue
        adaptable.append(dotted)
        if _matches(dotted, cfg.target_modules):
            out.append(dotted)
    unmatched = [t for t in cfg.target_modules
                 if not any(d == t or d.startswith(t + ".")
                            for d in adaptable)]
    if unmatched:
        raise ValueError(
            f"target_modules {unmatched} match no stacked rank-3 leaf of "
            f"the layer pool (adaptable: {adaptable})")
    return out


def applicable_targets(model_cfg, targets=("attn", "mlp")) -> tuple:
    """The subset of ``targets`` that matches at least one adaptable
    (stacked rank-3) leaf of ``model_cfg``'s layer pool — lets generic
    tooling (benchmarks, sweeps) build a :class:`LoraConfig` that is valid
    across architectures (a pure-MoE layer has no ``"mlp"`` leaf, an
    attention-free one no ``"attn"``).  Raises if NOTHING matches, so a
    fully inapplicable request still fails loudly like
    ``target_leaf_paths``."""
    from . import transformer as T

    layers = T.abstract_params(model_cfg)["layers"]
    adaptable = [_dotted(p) for p, leaf
                 in jax.tree_util.tree_flatten_with_path(layers)[0]
                 if leaf.ndim == 3]
    out = tuple(t for t in targets
                if any(d == t or d.startswith(t + ".") for d in adaptable))
    if not out:
        raise ValueError(
            f"none of {list(targets)} matches an adaptable stacked rank-3 "
            f"leaf of the layer pool (adaptable: {adaptable})")
    return out


def _is_pair(node) -> bool:
    return isinstance(node, dict) and set(node) == {"A", "B"}


def init_adapters(key, layers, cfg: LoraConfig, dtype=None):
    """Fresh adapters for the stacked ``layers`` pool: a nested dict holding
    ``{"A", "B"}`` pairs at each targeted leaf position.  ``B`` is zeros
    (adapted forward == base forward until the first update); ``A`` is
    Gaussian scaled by ``1/sqrt(din)``.  ``dtype=None`` follows each base
    leaf's dtype."""
    flat = jax.tree_util.tree_flatten_with_path(layers)[0]
    targets = set(target_leaf_paths(layers, cfg))   # raises on dead targets
    adapters: dict = {}
    for i, (path, leaf) in enumerate(flat):
        dotted = _dotted(path)
        if dotted not in targets:
            continue
        l, din, dout = leaf.shape
        dt = dtype or leaf.dtype
        a = jax.random.normal(jax.random.fold_in(key, i), (l, cfg.rank, dout),
                              dt) * (1.0 / math.sqrt(din))
        b = jnp.zeros((l, din, cfg.rank), dt)
        node = adapters
        keys = dotted.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = {"A": a, "B": b}
    return adapters


def adapter_abstract(model_cfg, cfg: LoraConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree of ``init_adapters`` without allocating."""
    from . import transformer as T

    layers = T.abstract_params(model_cfg)["layers"]
    return jax.eval_shape(
        lambda: init_adapters(jax.random.PRNGKey(0), layers, cfg, dtype))


def adapter_params_per_layer(model_cfg, cfg: LoraConfig) -> int:
    """Trainable parameters ONE layer's adapters hold: ``r * (din + dout)``
    summed over the targeted leaves — what the §4.3 download/optimizer byte
    accounting (``LayerCost.trainable_bytes``) is built from."""
    from . import transformer as T

    layers = T.abstract_params(model_cfg)["layers"]
    targets = set(target_leaf_paths(layers, cfg))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(layers)[0]:
        if _dotted(path) in targets:
            _, din, dout = leaf.shape
            total += cfg.rank * (din + dout)
    return total


# ---------------------------------------------------------------------------
# Merge / unmerge
# ---------------------------------------------------------------------------

def _delta(pair, w, scale):
    d = jnp.matmul(pair["B"].astype(jnp.float32),
                   pair["A"].astype(jnp.float32)) * scale
    return d.reshape(w.shape).astype(w.dtype)


@spans.scope(spans.MERGE)
def merge_layers(layers, adapters, cfg: LoraConfig, *, sign: float = 1.0):
    """``W + sign * (alpha/r) * B @ A`` leafwise.  Works on any tree with the
    pool's structure and a shared leading axis — the full stacked pool, a
    local pool shard, or a ``(kmax, ...)`` ring block — since the matmul
    batches over leading dims."""
    if not isinstance(layers, dict):
        return layers

    def walk(base, ad):
        out = dict(base)
        for k, v in ad.items():
            if _is_pair(v):
                out[k] = base[k] + _delta(v, base[k], sign * cfg.scale)
            else:
                out[k] = walk(base[k], v)
        return out

    return walk(layers, adapters)


def merge_params(params, adapters, cfg: LoraConfig):
    """Dense single-program view: base params with every adapter folded in
    (``W + (alpha/r) B@A``) — the merged-dense reference the equivalence
    harness differentiates, and what a serving path would export."""
    out = {k: v for k, v in params.items() if k != "lora"}
    out["layers"] = merge_layers(params["layers"], adapters, cfg)
    return out


def unmerge_params(params, adapters, cfg: LoraConfig):
    """Inverse of :func:`merge_params`: subtract the adapter deltas."""
    out = {k: v for k, v in params.items() if k != "lora"}
    out["layers"] = merge_layers(params["layers"], adapters, cfg, sign=-1.0)
    return out


# ---------------------------------------------------------------------------
# Optimizer mask
# ---------------------------------------------------------------------------

def opt_mask(adapters):
    """All-True boolean tree over the adapters — by construction the exact
    pytree structure of the gradients the frozen-base ring deposits."""
    return jax.tree.map(lambda _: True, adapters)


def param_mask(params) -> dict:
    """Boolean tree over a full roundpipe param dict: True exactly on the
    adapter leaves (the ``"lora"`` subtree), False on every frozen base
    leaf.  Feed to :func:`repro.optim.trainable_leaves` to build the
    adapter-only optimizer state.  Structural over dict nodes (anything
    else is a leaf) so it works on arrays, ShapeDtypeStructs and
    PartitionSpec trees alike."""
    def fill(node, v):
        if isinstance(node, dict):
            return {k: fill(sub, v) for k, sub in node.items()}
        return v

    return {k: fill(sub, k == "lora") for k, sub in params.items()}
