"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (task spec c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_xent import fused_xent
from repro.kernels.rwkv_scan import rwkv_scan
from repro.kernels.ssm_scan import ssm_scan

KEY = jax.random.PRNGKey(42)
TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,h,kh,d,bq,bk", [
        (128, 4, 4, 32, 64, 64),    # MHA
        (256, 8, 2, 16, 64, 128),   # GQA
        (192, 4, 1, 64, 64, 64),    # MQA, ragged seq/block
        (128, 2, 2, 48, 32, 32),    # small blocks
    ])
    def test_causal_sweep(self, dtype, s, h, kh, d, bq, bk):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, s, h, d), dtype)
        k = rand(ks[1], (2, s, kh, d), dtype)
        v = rand(ks[2], (2, s, kh, d), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    @pytest.mark.parametrize("window", [32, 100, 1000])
    def test_sliding_window(self, window):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 256, 4, 32), jnp.float32)
        k = rand(ks[1], (1, 256, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 256, 2, 32), jnp.float32)
        out = flash_attention(q, k, v, causal=True, sliding_window=window,
                              block_q=64, block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bidirectional_encoder(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, 128, 4, 32), jnp.float32)
        k = rand(ks[1], (2, 128, 4, 32), jnp.float32)
        v = rand(ks[2], (2, 128, 4, 32), jnp.float32)
        out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_mla_asymmetric_value_dim(self):
        """dh_qk=40, dv=32 (MLA-style)."""
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 128, 4, 40), jnp.float32)
        k = rand(ks[1], (1, 128, 4, 40), jnp.float32)
        v = rand(ks[2], (1, 128, 4, 32), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,h,kh,d,bk", [
        (512, 8, 2, 32, 128), (1024, 4, 4, 64, 256), (384, 8, 1, 16, 128)])
    def test_sweep(self, dtype, s, h, kh, d, bk):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, h, d), dtype)
        k = rand(ks[1], (2, s, kh, d), dtype)
        v = rand(ks[2], (2, s, kh, d), dtype)
        nv = jnp.array([s, s // 3], jnp.int32)
        out = decode_attention(q, k, v, nv, block_k=bk, interpret=True)
        want = ref.decode_attention_ref(q, k, v, nv)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_single_valid_token(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 4, 32), jnp.float32)
        k = rand(ks[1], (1, 256, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 256, 2, 32), jnp.float32)
        out = decode_attention(q, k, v, jnp.int32(1), block_k=64, interpret=True)
        want = ref.decode_attention_ref(q, k, v, jnp.int32(1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestFusedXent:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t,d,v,bt,bv", [
        (128, 32, 512, 64, 128), (256, 16, 1024, 256, 256), (64, 64, 256, 32, 64),
        (64, 32, 300, 32, 128)])     # vocab not a multiple of block_v
    def test_forward_sweep(self, dtype, t, d, v, bt, bv):
        ks = jax.random.split(KEY, 2)
        x = rand(ks[0], (t, d), dtype)
        w = (rand(ks[1], (d, v), dtype) * 0.1).astype(dtype)
        labels = jax.random.randint(KEY, (t,), 0, v)
        out = fused_xent(x, w, labels, bt, bv, True)
        want = ref.fused_xent_ref(x, w, labels)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_gradients_match_reference(self):
        self._check_gradients(v=128, bv=32)

    def test_gradients_ragged_vocab(self):
        """Vocab not a multiple of block_v: padded columns must carry no
        probability mass and no weight gradient."""
        self._check_gradients(v=150, bv=64)

    @staticmethod
    def _check_gradients(v, bv):
        t, d = 64, 16
        ks = jax.random.split(KEY, 2)
        x = rand(ks[0], (t, d), jnp.float32)
        w = rand(ks[1], (d, v), jnp.float32) * 0.1
        labels = jax.random.randint(KEY, (t,), 0, v)
        gx, gw = jax.grad(lambda a, b: fused_xent(a, b, labels, 32, bv, True).sum(),
                          argnums=(0, 1))(x, w)
        rx, rw = jax.grad(lambda a, b: ref.fused_xent_ref(a, b, labels).sum(),
                          argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-4, atol=1e-4)


class TestRwkvScan:
    @pytest.mark.parametrize("s,h,n,chunk", [(64, 2, 16, 16), (128, 4, 32, 64),
                                             (96, 1, 64, 32)])
    def test_sweep(self, s, h, n, chunk):
        ks = jax.random.split(KEY, 5)
        shape = (2, s, h, n)
        r, k, v = (rand(ks[i], shape, jnp.float32) for i in range(3))
        w = jax.nn.sigmoid(rand(ks[3], shape, jnp.float32))  # decay in (0,1)
        u = rand(ks[4], (h, n), jnp.float32)
        s0 = jnp.zeros((2, h, n, n), jnp.float32)
        y, sT = rwkv_scan(r, k, v, w, u, s0, chunk=chunk, interpret=True)
        ry, rsT = ref.rwkv_scan_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(sT), np.asarray(rsT), rtol=1e-4, atol=1e-4)

    def test_state_carries_across_chunks(self):
        """Same input split into chunks must equal one big chunk."""
        ks = jax.random.split(KEY, 5)
        shape = (1, 64, 2, 16)
        r, k, v = (rand(ks[i], shape, jnp.float32) for i in range(3))
        w = jax.nn.sigmoid(rand(ks[3], shape, jnp.float32))
        u = rand(ks[4], (2, 16), jnp.float32)
        s0 = rand(ks[0], (1, 2, 16, 16), jnp.float32)
        y1, s1 = rwkv_scan(r, k, v, w, u, s0, chunk=64, interpret=True)
        y2, s2 = rwkv_scan(r, k, v, w, u, s0, chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)


class TestSsmScan:
    @pytest.mark.parametrize("s,di,n,bd,chunk", [
        (64, 32, 8, 32, 16), (128, 64, 16, 32, 64), (96, 128, 8, 64, 32)])
    def test_sweep(self, s, di, n, bd, chunk):
        ks = jax.random.split(KEY, 5)
        x = rand(ks[0], (2, s, di), jnp.float32)
        dt = jax.nn.softplus(rand(ks[1], (2, s, 1), jnp.float32))
        bm = rand(ks[2], (2, s, n), jnp.float32)
        cm = rand(ks[3], (2, s, n), jnp.float32)
        a = -jnp.exp(rand(ks[4], (di, n), jnp.float32) * 0.5)
        h0 = jnp.zeros((2, di, n), jnp.float32)
        y, hT = ssm_scan(x, dt, bm, cm, a, h0, block_d=bd, chunk=chunk,
                         interpret=True)
        ry, rhT = ref.ssm_scan_ref(x, dt, bm, cm, a, h0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(rhT), rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(
    s=st.sampled_from([64, 128, 192]),
    h=st.sampled_from([2, 4]),
    g=st.sampled_from([1, 2]),
    d=st.sampled_from([16, 32]),
)
def test_flash_attention_property(s, h, g, d):
    """Property sweep: kernel == oracle for arbitrary GQA geometry."""
    kh = max(1, h // g)
    ks = jax.random.split(jax.random.PRNGKey(s * h * d), 3)
    q = rand(ks[0], (1, s, h, d), jnp.float32)
    k = rand(ks[1], (1, s, kh, d), jnp.float32)
    v = rand(ks[2], (1, s, kh, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
