"""LoRA adapter properties (repro.models.lora).

Hypothesis drives the shape/rank space where available (the offline
container stubs it out — see conftest); every core property also has a
deterministic twin so the fast tier exercises the real math either way.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import smoke_config
from repro.models import lora
from repro.models import transformer as T
from repro.models.config import get_config
from repro.optim import merge_trainable, trainable_leaves

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_multiround_lora_matches_merged_dense():
    """The same R in {1, 2, 3} sweep with a frozen base: the adapter ring
    re-injects per round and the adapter-shaped deposit accumulates across
    rounds; grads must allclose the merged-dense full-batch reference."""
    run_mode("qwen3-1.7b", "rounds-lora", n_layers=7)


@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_lora_matches_merged_dense():
    """Frozen-base LoRA equivalence (headline): one adapter fine-tuning step
    through the ring on the uneven 7-layer/4-worker auto plan vs a
    single-program merged-dense reference (W + (alpha/r)·B@A folded in).
    Loss and every adapter-grad leaf must allclose, the deposited pytree
    must hold ONLY adapter leaves, and the compiled LoRA plan's download
    bytes must be strictly below the full-fine-tune plan's."""
    run_mode("qwen3-1.7b", "lora", n_layers=7)


def _cfg(n_layers=2):
    cfg = smoke_config(get_config("qwen3-1.7b"))
    return dataclasses.replace(cfg, n_layers=n_layers,
                               name=f"{cfg.name}-lora{n_layers}")


def _params(cfg, dtype=jnp.float32):
    return T.init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)


def _random_adapters(cfg, lcfg, seed=1, scale=0.1):
    """Adapters with BOTH factors nonzero (B away from its zero init)."""
    p = _params(cfg)
    ad = lora.init_adapters(jax.random.PRNGKey(seed), p["layers"], lcfg,
                            dtype=jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten(ad)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [jax.random.normal(k, l.shape, l.dtype) * scale
              for k, l in zip(keys, leaves)]
    return p, jax.tree_util.tree_unflatten(treedef, leaves)


class TestTargets:
    def test_default_targets_cover_attn_and_mlp(self):
        cfg = _cfg()
        paths = lora.target_leaf_paths(T.abstract_params(cfg)["layers"],
                                       lora.LoraConfig(rank=4))
        assert any(p.startswith("attn.") for p in paths)
        assert any(p.startswith("mlp.") for p in paths)
        assert all("norm" not in p for p in paths)

    def test_exact_path_target(self):
        cfg = _cfg()
        paths = lora.target_leaf_paths(
            T.abstract_params(cfg)["layers"],
            lora.LoraConfig(rank=4, target_modules=("attn.w_q",)))
        assert paths == ["attn.w_q"]

    def test_no_match_raises(self):
        cfg = _cfg()
        p = _params(cfg)
        with pytest.raises(ValueError, match="match no"):
            lora.init_adapters(jax.random.PRNGKey(0), p["layers"],
                               lora.LoraConfig(rank=4,
                                               target_modules=("nope",)))

    def test_partially_dead_targets_raise(self):
        """A typo'd target must not silently train fewer adapters than
        asked: ('attn', 'mpl') raises even though 'attn' matches."""
        cfg = _cfg()
        p = _params(cfg)
        with pytest.raises(ValueError, match="mpl"):
            lora.init_adapters(
                jax.random.PRNGKey(0), p["layers"],
                lora.LoraConfig(rank=4, target_modules=("attn", "mpl")))

    def test_bad_rank_rejected(self):
        with pytest.raises(ValueError):
            lora.LoraConfig(rank=0)

    def test_adapter_params_per_layer_counts_rank(self):
        cfg = _cfg()
        n1 = lora.adapter_params_per_layer(cfg, lora.LoraConfig(rank=2))
        n2 = lora.adapter_params_per_layer(cfg, lora.LoraConfig(rank=4))
        assert n2 == 2 * n1 > 0


class TestZeroInitB:
    def test_fresh_adapters_are_a_bitwise_noop(self):
        """Zero-init B => merged weights (and thus the adapted forward) are
        bit-identical to the base."""
        cfg = _cfg()
        p = _params(cfg)
        lcfg = lora.LoraConfig(rank=4)
        ad = lora.init_adapters(jax.random.PRNGKey(1), p["layers"], lcfg,
                                dtype=jnp.float32)
        merged = lora.merge_params(p, ad, lcfg)
        for (ka, va), (_, vb) in zip(
                jax.tree_util.tree_flatten_with_path(p["layers"])[0],
                jax.tree_util.tree_flatten_with_path(merged["layers"])[0]):
            assert np.array_equal(np.asarray(va), np.asarray(vb)), ka

    def test_fresh_adapter_forward_bit_identical(self):
        cfg = _cfg()
        p = _params(cfg)
        lcfg = lora.LoraConfig(rank=4)
        ad = lora.init_adapters(jax.random.PRNGKey(1), p["layers"], lcfg,
                                dtype=jnp.float32)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 8),
                                              0, cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.PRNGKey(3), (2, 8),
                                              0, cfg.vocab_size)}
        base = T.loss_fn(p, batch, cfg, remat=False, xent_chunk=8, kv_chunk=8)
        adapted = T.loss_fn(lora.merge_params(p, ad, lcfg), batch, cfg,
                            remat=False, xent_chunk=8, kv_chunk=8)
        assert float(base) == float(adapted)


class TestMergeUnmerge:
    def test_merge_unmerge_roundtrip(self):
        cfg = _cfg()
        lcfg = lora.LoraConfig(rank=4, alpha=8.0)
        p, ad = _random_adapters(cfg, lcfg)
        merged = lora.merge_params(p, ad, lcfg)
        back = lora.unmerge_params(merged, ad, lcfg)
        for (ka, va), (_, vb) in zip(
                jax.tree_util.tree_flatten_with_path(p["layers"])[0],
                jax.tree_util.tree_flatten_with_path(back["layers"])[0]):
            np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                       rtol=1e-6, atol=1e-6,
                                       err_msg=jax.tree_util.keystr(ka))

    def test_merge_actually_changes_targets_only(self):
        cfg = _cfg()
        lcfg = lora.LoraConfig(rank=4, target_modules=("attn.w_q",))
        p, ad = _random_adapters(cfg, lcfg)
        merged = lora.merge_params(p, ad, lcfg)
        for (ka, va), (_, vb) in zip(
                jax.tree_util.tree_flatten_with_path(p["layers"])[0],
                jax.tree_util.tree_flatten_with_path(merged["layers"])[0]):
            path = jax.tree_util.keystr(ka)
            if "w_q" in path and "attn" in path:
                assert not np.array_equal(np.asarray(va), np.asarray(vb))
            else:
                assert np.array_equal(np.asarray(va), np.asarray(vb)), path


TARGET_SUBSETS = [("attn",), ("mlp",), ("attn", "mlp"),
                  ("attn.w_q", "mlp.w_down"), ("attn.w_o",)]


class TestGradStructureEqualsOptimizerMask:
    @pytest.mark.parametrize("targets", TARGET_SUBSETS)
    def test_adapter_grads_match_mask_structure(self, targets):
        """For any target_modules subset: the adapter-grad pytree of the
        merged-dense loss has EXACTLY the optimizer mask's structure — what
        guarantees the ring deposit feeds the masked optimizer 1:1."""
        cfg = _cfg()
        lcfg = lora.LoraConfig(rank=2, target_modules=targets)
        p, ad = _random_adapters(cfg, lcfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 8),
                                              0, cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.PRNGKey(6), (2, 8),
                                              0, cfg.vocab_size)}
        grads = jax.grad(lambda a: T.loss_fn(
            lora.merge_params(p, a, lcfg), batch, cfg, remat=False,
            xent_chunk=8, kv_chunk=8))(ad)
        mask = lora.opt_mask(ad)
        assert jax.tree_util.tree_structure(grads) == \
            jax.tree_util.tree_structure(mask)
        assert all(jax.tree_util.tree_leaves(mask))

    @pytest.mark.parametrize("targets", TARGET_SUBSETS)
    def test_param_mask_prunes_to_adapters(self, targets):
        """trainable_leaves(params, param_mask) == {"lora": adapters}: the
        masked optimizer state covers the adapter leaves and nothing else."""
        cfg = _cfg()
        lcfg = lora.LoraConfig(rank=2, target_modules=targets)
        p, ad = _random_adapters(cfg, lcfg)
        full = dict(p, lora=ad)
        mask = lora.param_mask(full)
        tr = trainable_leaves(full, mask)
        assert set(tr) == {"lora"}
        assert jax.tree_util.tree_structure(tr["lora"]) == \
            jax.tree_util.tree_structure(ad)
        # merge_trainable grafts updates back and leaves the base untouched
        bumped = jax.tree.map(lambda a: a + 1.0, tr)
        merged = merge_trainable(full, bumped, mask)
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                   zip(jax.tree.leaves(full["layers"]),
                       jax.tree.leaves(merged["layers"])))
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(merged["lora"])[0]),
            np.asarray(jax.tree.leaves(full["lora"])[0]) + 1.0)


# ---------------------------------------------------------------------------
# Hypothesis sweeps (skipped when hypothesis is stubbed out)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(rank=st.integers(1, 8), alpha=st.floats(0.5, 32.0),
       din=st.integers(2, 12), dout=st.integers(2, 12),
       n_layers=st.integers(1, 4))
def test_merge_unmerge_roundtrip_property(rank, alpha, din, dout, n_layers):
    """merge(unmerge(p)) == p for arbitrary shapes/ranks (fp32 tolerance)."""
    lcfg = lora.LoraConfig(rank=rank, alpha=alpha,
                           target_modules=("attn.w_q",))
    key = jax.random.PRNGKey(rank * 131 + din)
    w = jax.random.normal(key, (n_layers, din, dout), jnp.float32)
    layers = {"attn": {"w_q": w}}
    ad = {"attn": {"w_q": {
        "A": jax.random.normal(jax.random.fold_in(key, 1),
                               (n_layers, rank, dout), jnp.float32),
        "B": jax.random.normal(jax.random.fold_in(key, 2),
                               (n_layers, din, rank), jnp.float32)}}}
    merged = lora.merge_layers(layers, ad, lcfg)
    back = lora.merge_layers(merged, ad, lcfg, sign=-1.0)
    np.testing.assert_allclose(np.asarray(back["attn"]["w_q"]),
                               np.asarray(w), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(rank=st.integers(1, 6), seed=st.integers(0, 99))
def test_zero_b_noop_property(rank, seed):
    """Zero-init B: merged == base bit-exactly, any rank/seed."""
    cfg = _cfg()
    p = _params(cfg)
    ad = lora.init_adapters(jax.random.PRNGKey(seed), p["layers"],
                            lora.LoraConfig(rank=rank), dtype=jnp.float32)
    merged = lora.merge_params(p, ad, lora.LoraConfig(rank=rank))
    for a, b in zip(jax.tree.leaves(p["layers"]),
                    jax.tree.leaves(merged["layers"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=10, deadline=None)
@given(subset=st.sets(st.sampled_from(
    ["attn.w_q", "attn.w_k", "attn.w_v", "attn.w_o",
     "mlp.w_up", "mlp.w_down", "mlp.w_gate"]), min_size=1, max_size=4))
def test_mask_structure_property(subset):
    """Adapter structure == optimizer mask structure for ANY target subset."""
    cfg = _cfg()
    p = _params(cfg)
    lcfg = lora.LoraConfig(rank=2, target_modules=tuple(sorted(subset)))
    ad = lora.init_adapters(jax.random.PRNGKey(0), p["layers"], lcfg)
    mask = lora.opt_mask(ad)
    assert jax.tree_util.tree_structure(ad) == \
        jax.tree_util.tree_structure(mask)
    assert len(jax.tree.leaves(ad)) == 2 * len(
        lora.target_leaf_paths(p["layers"], lcfg))
