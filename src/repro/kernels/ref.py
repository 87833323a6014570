"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are deliberately naive (materialise everything, fp32 math) — they define
correctness, not performance.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, sliding_window=None,
                        logit_scale=None):
    """q: (B,Sq,H,Dh); k,v: (B,Skv,KH,Dh|Dv) -> (B,Sq,H,Dv).  fp32 softmax."""
    b, sq, h, dh = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = logit_scale if logit_scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, sq, kh, g, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq)[:, None]
    kv_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kv_pos <= q_pos
    if sliding_window is not None:
        mask &= kv_pos > q_pos - sliding_window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, n_valid, *, logit_scale=None):
    """q: (B,H,Dh); caches: (B,S,KH,Dh); n_valid: scalar or (B,)."""
    b, h, dh = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = logit_scale if logit_scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, kh, g, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] < (
        n_valid[:, None] if jnp.ndim(n_valid) else jnp.full((b, 1), n_valid))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, h, dh).astype(q.dtype)


def fused_xent_ref(x, w, labels):
    """x: (T,D); w: (D,V); labels: (T,) -> per-token loss (T,) fp32."""
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


def dequant_rows_ref(codes, scales, *, block=256):
    """(R, nb*block) int8 codes or (R, nb*block//2) uint8 nibble pairs (low
    nibbles hold the first half of each block, high nibbles the second),
    with (R, nb) fp32 per-block scales -> (R, nb*block) fp32 rows."""
    r, nb = scales.shape
    if codes.dtype == jnp.uint8:
        p = codes.astype(jnp.int32).reshape(r, nb, block // 2)
        lo, hi = p & 0xF, (p >> 4) & 0xF
        lo, hi = lo - 16 * (lo >> 3), hi - 16 * (hi >> 3)
        codes = jnp.concatenate([lo, hi], axis=-1)
    blocks = codes.reshape(r, nb, block).astype(jnp.float32)
    return (blocks * scales[..., None]).reshape(r, nb * block)


def rwkv_scan_ref(r, k, v, w, u, s0):
    """r,k,v,w: (B,S,H,N) fp32; u: (H,N); s0: (B,H,N,N).
    y_t = r_t · (diag(u) k_t v_t^T + S_{t-1});  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    Returns (y (B,S,H,N), s_T)."""
    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhn,bhnm->bhm", rt, u[..., None] * kv + s)
        s = wt[..., None] * s + kv
        return s, y

    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w))
    s_t, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3), s_t


def ssm_scan_ref(x, dt, bmat, cmat, a, h0):
    """Mamba selective scan.  x,dt: (B,S,Di),(B,S,1); bmat,cmat: (B,S,N);
    a: (Di,N); h0: (B,Di,N).  Returns (y (B,S,Di), h_T)."""
    def step(h, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt[..., None] * a)
        h = decay * h + (dtt * xt)[..., None] * bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    xs = (x.transpose(1, 0, 2), dt.transpose(1, 0, 2),
          bmat.transpose(1, 0, 2), cmat.transpose(1, 0, 2))
    h_t, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2), h_t
