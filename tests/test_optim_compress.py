"""optim/compress.py: int8 blockwise codec with error feedback.

Covers the three contracts the quantized-deposit path (ISSUE 6) leans on:
round-trip shape/tolerance, error-feedback residual telescoping across
steps, and ``psum_compressed``'s shared-scale linearity over an axis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.optim.compress import (BLOCK, compress_int8, decompress_int8,
                                  psum_compressed)

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_async_quant_matches_staleness1():
    """Quantized pool + compressed deposits under the chained async program
    (--pool-dtype/--grad-compress compose with --async-opt): the int8 ring — requantizing
    the pool in-program at every update tick — must land on the
    staleness-1 oracle taken at the int8-dequantized pool, separate from
    staleness-0, and grad_compress='int8' must thread the error-feedback
    residual through state['opt']['grad_residual'] across the chain while
    staying within codec tolerance of the uncompressed chain."""
    run_mode("qwen3-1.7b", "async-quant", n_layers=7)


def _rand(shape, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


# ---------------------------------------------------------------------------
# round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(BLOCK,), (3, 100), (7, 129), (1, 1),
                                   (2, BLOCK, 3)])
def test_roundtrip_shapes_and_tolerance(shape):
    g = _rand(shape, seed=1)
    codes, scale, residual = compress_int8(g)
    nblocks = -(-g.size // BLOCK)
    assert codes.shape == (nblocks, BLOCK) and codes.dtype == jnp.int8
    assert scale.shape == (nblocks,) and scale.dtype == jnp.float32
    assert residual.shape == g.shape

    deq = decompress_int8(codes, scale, shape)
    assert deq.shape == shape
    # per-element error is bounded by half the block's quantization step
    flat_err = np.abs(np.asarray(deq - g)).reshape(-1)
    step = np.repeat(np.asarray(scale), BLOCK)[: g.size]
    assert (flat_err <= step / 2 + 1e-7).all()
    # and the residual IS that error, exactly
    np.testing.assert_allclose(np.asarray(residual), np.asarray(g - deq),
                               rtol=0, atol=0)


def test_roundtrip_zero_input():
    codes, scale, residual = compress_int8(jnp.zeros((5, 7)))
    assert not np.asarray(codes).any()
    assert not np.asarray(residual).any()
    deq = decompress_int8(codes, scale, (5, 7))
    assert not np.asarray(deq).any()


def test_roundtrip_under_jit():
    g = _rand((3, 200), seed=2)

    @jax.jit
    def f(x):
        codes, scale, res = compress_int8(x)
        return decompress_int8(codes, scale, x.shape), res

    deq, res = f(g)
    np.testing.assert_allclose(np.asarray(deq + res), np.asarray(g),
                               rtol=0, atol=1e-6)


def test_codes_saturate_at_127():
    # one outlier per block pins the scale; everything else quantizes fine
    g = jnp.ones((BLOCK,)).at[0].set(1270.0)
    codes, scale, _ = compress_int8(g)
    assert int(codes[0, 0]) == 127
    np.testing.assert_allclose(float(scale[0]), 10.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def test_residual_telescopes_over_steps():
    """With a constant gradient, K error-feedback deposits sum to K*g - r_K:
    the MEAN deposit converges to g at rate |r_K|/K while the single-shot
    error stays put — the property the dispatch deposit path inherits."""
    g = _rand((4, 300), seed=3)
    k_steps, residual, total = 8, None, jnp.zeros_like(g)
    for _ in range(k_steps):
        codes, scale, residual = compress_int8(g, residual)
        total = total + decompress_int8(codes, scale, g.shape)
    # exact telescoping: sum of deposits == K*g - final residual
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(k_steps * g - residual),
                               rtol=1e-5, atol=1e-5)
    single_err = np.abs(np.asarray(
        decompress_int8(*compress_int8(g)[:2], g.shape) - g)).max()
    mean_err = np.abs(np.asarray(total / k_steps - g)).max()
    assert mean_err < single_err / 2, (mean_err, single_err)


def test_residual_feeds_next_compression():
    # a residual large enough to flip codes must change the next deposit
    g = _rand((BLOCK,), seed=4)
    codes0, scale0, _ = compress_int8(g)
    big = jnp.full_like(g, float(scale0[0]) * 3)
    codes1, _, _ = compress_int8(g, big)
    assert np.abs(np.asarray(codes1, np.int32)
                  - np.asarray(codes0, np.int32)).max() >= 2


# ---------------------------------------------------------------------------
# psum_compressed: shared-scale linearity over an axis
# ---------------------------------------------------------------------------

def test_psum_compressed_matches_sum():
    n = 4
    gs = _rand((n, 3, 170), seed=5)
    out, res = jax.vmap(lambda g: psum_compressed(g, "i"),
                        axis_name="i")(gs)
    want = np.asarray(gs.sum(0))
    # every participant reconstructs the SAME total (shared-scale grid)
    for i in range(1, n):
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[i]))
    # error bound: local quantization + shared-grid requantization, n terms
    codes, scale, _ = jax.vmap(compress_int8)(gs)
    shared = np.asarray(scale).max(axis=0)
    step = np.repeat(shared, BLOCK)[: gs[0].size].reshape(gs[0].shape)
    assert (np.abs(np.asarray(out[0]) - want) <= n * step + 1e-6).all()
    # per-participant residual is the LOCAL round-trip error
    for i in range(n):
        deq = decompress_int8(codes[i], scale[i], gs[i].shape)
        np.testing.assert_allclose(np.asarray(res[i]),
                                   np.asarray(gs[i] - deq),
                                   rtol=1e-5, atol=1e-6)


def test_psum_compressed_scale_invariance():
    # doubling every input doubles the reconstruction (shared grid scales)
    g = _rand((2, BLOCK), seed=6)
    gs = jnp.stack([g, -g])
    out, _ = jax.vmap(lambda x: psum_compressed(x, "i"), axis_name="i")(gs)
    # +g and -g cancel on the shared grid exactly (symmetric codes)
    assert np.abs(np.asarray(out[0])).max() <= float(
        np.asarray(jax.vmap(compress_int8)(gs)[1]).max()) + 1e-6


# ---------------------------------------------------------------------------
# hypothesis-backed properties (skipped when hypothesis is stubbed)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=900),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_roundtrip_property(n_elems, seed):
    g = _rand((n_elems,), seed=seed % 1000, scale=1.0 + seed % 7)
    codes, scale, residual = compress_int8(g)
    deq = decompress_int8(codes, scale, g.shape)
    step = np.repeat(np.asarray(scale), BLOCK)[:n_elems]
    assert (np.abs(np.asarray(deq - g)) <= step / 2 + 1e-6).all()
    np.testing.assert_allclose(np.asarray(deq + residual), np.asarray(g),
                               rtol=0, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=6))
def test_error_feedback_mean_converges_property(k_steps):
    g = _rand((500,), seed=7)
    residual, total = None, jnp.zeros_like(g)
    for _ in range(k_steps):
        codes, scale, residual = compress_int8(g, residual)
        total = total + decompress_int8(codes, scale, g.shape)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(k_steps * g - residual),
                               rtol=1e-5, atol=1e-5)
