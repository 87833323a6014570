"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs (task spec f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow     # subprocess XLA compiles, minutes per case

from repro.configs import ASSIGNED, PAPER_MODELS, smoke_config
from repro.models import transformer as T
from repro.models.config import get_config
from roundpipe_proof import run_mode

BATCH, SEQ = 2, 16


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x7b", "rwkv6-7b",
                                  "starcoder2-7b", "internvl2-76b"])
def test_dispatch_matches_reference(arch):
    run_mode(arch, "uniform")


def make_batch(cfg, key):
    kt, kl, ke = jax.random.split(key, 3)
    if cfg.frontend:
        batch = {"embeds": jax.random.normal(ke, (BATCH, SEQ, cfg.d_model), jnp.bfloat16)}
    else:
        batch = {"tokens": jax.random.randint(kt, (BATCH, SEQ), 0, cfg.vocab_size)}
    batch["labels"] = jax.random.randint(kl, (BATCH, SEQ), 0, cfg.vocab_size)
    return batch


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ASSIGNED + PAPER_MODELS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch, key):
        cfg = smoke_config(get_config(arch))
        params = T.init_params(key, cfg)
        batch = make_batch(cfg, key)
        out = jax.jit(lambda p, b: T.forward(p, b, cfg))(params, batch)
        assert out.shape == (BATCH, SEQ, cfg.d_model)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def test_train_step_loss_finite_and_grads_nonzero(self, arch, key):
        cfg = smoke_config(get_config(arch))
        params = T.init_params(key, cfg)
        batch = make_batch(cfg, key)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, b: T.loss_fn(p, b, cfg)))(params, batch)
        assert np.isfinite(float(loss))
        # loss near ln(vocab) at init
        assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 3 * np.log(cfg.vocab_size)
        norms = [float(jnp.linalg.norm(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads)]
        assert all(np.isfinite(norms))
        assert sum(n > 0 for n in norms) > len(norms) * 0.5

    def test_decode_step(self, arch, key):
        cfg = smoke_config(get_config(arch))
        if cfg.encoder_only:
            pytest.skip("encoder-only arch has no decode step")
        params = T.init_params(key, cfg)
        cache = T.zero_cache(cfg, BATCH, max_len=SEQ)
        tok = jnp.zeros((BATCH,), jnp.int32)
        logits, cache2 = jax.jit(
            lambda p, c, t: T.decode_step(p, c, t, cfg))(params, cache, tok)
        assert logits.shape == (BATCH, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()
        assert int(cache2["len"]) == 1


class TestPrefillDecodeConsistency:
    """prefill(tokens) then decode must agree with teacher-forced forward."""

    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x7b", "rwkv6-7b",
                                      "hymba-1.5b", "deepseek-v2-236b"])
    def test_incremental_matches_full(self, arch, key):
        cfg = smoke_config(get_config(arch))
        params = T.init_params(key, cfg, dtype=jnp.float32)
        toks = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
        max_len = 16

        # full forward logits at every position
        h = T.forward(params, {"tokens": toks}, cfg, remat=False)
        full_logits = (h @ T.lm_head_weights(params, cfg)).astype(jnp.float32)

        # incremental: decode tokens one by one from an empty cache
        cache = T.zero_cache(cfg, 1, max_len, dtype=jnp.float32)
        step = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg))
        for i in range(8):
            logits, cache = step(params, cache, toks[:, i])
            np.testing.assert_allclose(
                np.asarray(logits[0]), np.asarray(full_logits[0, i]),
                rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "hymba-1.5b", "rwkv6-7b"])
    def test_prefill_then_decode(self, arch, key):
        cfg = smoke_config(get_config(arch))
        params = T.init_params(key, cfg, dtype=jnp.float32)
        toks = jax.random.randint(key, (1, 9), 0, cfg.vocab_size)
        max_len = 16

        h = T.forward(params, {"tokens": toks}, cfg, remat=False)
        full_logits = (h @ T.lm_head_weights(params, cfg)).astype(jnp.float32)

        _, cache = jax.jit(lambda p, b: T.prefill(p, b, cfg, max_len,
                                                  dtype=jnp.float32))(
            params, {"tokens": toks[:, :8]})
        assert int(cache["len"]) == 8
        logits, _ = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg))(
            params, cache, toks[:, 8])
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full_logits[0, 8]),
                                   rtol=2e-2, atol=2e-2)


class TestSlidingWindowRing:
    def test_ring_cache_matches_full_attention_within_window(self, key):
        """With window w, decoding past w positions must equal a model that
        sees only the last w tokens."""
        cfg = smoke_config(get_config("mixtral-8x7b"))
        assert cfg.sliding_window == 8
        params = T.init_params(key, cfg, dtype=jnp.float32)
        toks = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
        cache = T.zero_cache(cfg, 1, max_len=32, dtype=jnp.float32)
        assert cache["k"].shape[2] == 8  # physical cache is window-bounded
        step = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg))
        for i in range(12):
            logits, cache = step(params, cache, toks[:, i])
        h = T.forward(params, {"tokens": toks}, cfg, remat=False)
        full = (h @ T.lm_head_weights(params, cfg)).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full[0, 11]),
                                   rtol=2e-2, atol=2e-2)


class TestChunkedXent:
    def test_matches_dense_xent(self, key):
        b, s, d, v = 2, 12, 16, 37
        x = jax.random.normal(key, (b, s, d))
        w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.1
        labels = jax.random.randint(key, (b, s), 0, v)
        tot, cnt = T.chunked_softmax_xent(x, w, labels, chunk=5)
        logits = (x @ w).astype(jnp.float32)
        ref = -jax.nn.log_softmax(logits)[
            jnp.arange(b)[:, None], jnp.arange(s)[None], labels].sum()
        np.testing.assert_allclose(float(tot), float(ref), rtol=1e-5)
        assert int(cnt) == b * s

    def test_ignore_index(self, key):
        x = jax.random.normal(key, (1, 8, 16))
        w = jax.random.normal(key, (16, 11))
        labels = jnp.array([[0, 1, -100, 3, -100, 5, 6, 7]])
        _, cnt = T.chunked_softmax_xent(x, w, labels, chunk=3)
        assert int(cnt) == 6

    def test_grads_flow(self, key):
        x = jax.random.normal(key, (1, 8, 16))
        w = jax.random.normal(key, (16, 11))
        labels = jnp.zeros((1, 8), jnp.int32)
        g = jax.grad(lambda ww: T.chunked_softmax_xent(x, ww, labels, chunk=4)[0])(w)
        assert float(jnp.abs(g).sum()) > 0
