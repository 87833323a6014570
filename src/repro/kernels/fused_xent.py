"""Fused LM-head + softmax cross-entropy (Pallas): never materialise (T, V).

The LM head is the paper's canonical heavy stage (Fig. 1 "layer 13"); fusing
the头 projection with the loss removes the (T, V) logits round-trip to HBM —
for nemotron's 256k vocab that is 2·T·256000 bytes per micro-batch.  The
kernel streams vocab blocks through VMEM keeping an online logsumexp and the
gold-label logit; a custom VJP recomputes per-block softmax for the backward
(so backward memory is also O(T * block_v)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _xent_kernel(labels_ref, x_ref, w_ref, loss_ref, m_scr, l_scr, gold_scr,
                 *, block_v, n_v_blocks, vocab):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        gold_scr[...] = jnp.zeros_like(gold_scr)

    logits = jax.lax.dot_general(x_ref[...], w_ref[...],  # (bt, d) @ (d, bv)
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    labels = labels_ref[...]                               # (bt, 1)
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab, logits, NEG_INF)       # vocab padding
    gold_scr[...] += jnp.sum(jnp.where(col == labels, logits, 0.0), axis=1,
                             keepdims=True)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    l_scr[...] = l_scr[...] * jnp.exp(m_prev - m_new) \
        + jnp.exp(logits - m_new).sum(axis=1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(vi == n_v_blocks - 1)
    def _final():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        loss_ref[...] = lse - gold_scr[...]


def _pad_vocab(w, block_v):
    """Zero-pad the vocab dim of ``w`` up to a multiple of ``block_v``; the
    padded columns are masked to -inf wherever logits are formed."""
    v = w.shape[1]
    return jnp.pad(w, ((0, 0), (0, -v % block_v)))


def _xent_forward(x, w, labels, *, block_t, block_v, interpret):
    t, d = x.shape
    v = w.shape[1]
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    if t % block_t:
        raise ValueError("fused_xent requires T divisible by block_t")
    w = _pad_vocab(w, block_v)
    nt, nv = t // block_t, w.shape[1] // block_v
    kernel = functools.partial(_xent_kernel, block_v=block_v, n_v_blocks=nv,
                               vocab=v)
    loss = pl.pallas_call(
        kernel,
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda ti, vi: (0, vi)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(labels.reshape(t, 1), x, w)
    return loss[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_xent(x, w, labels, block_t=256, block_v=512, interpret=False):
    """Per-token loss (T,) fp32 for x: (T,D), w: (D,V), labels: (T,).  V need
    not be a multiple of ``block_v``: the last vocab block is padded and
    masked."""
    return _xent_forward(x, w, labels, block_t=block_t, block_v=block_v,
                         interpret=interpret)


def _fwd(x, w, labels, block_t, block_v, interpret):
    loss = _xent_forward(x, w, labels, block_t=block_t, block_v=block_v,
                         interpret=interpret)
    return loss, (x, w, labels)


def _bwd(block_t, block_v, interpret, res, g):
    """dL/dx = (p - onehot) @ w^T ; dL/dw = x^T (p - onehot), streamed over
    vocab blocks with rematerialised block logits (never (T,V) at once)."""
    x, w, labels = res
    t, d = x.shape
    v = w.shape[1]
    block_v = min(block_v, v)
    wp = _pad_vocab(w, block_v)
    xf = x.astype(jnp.float32)
    n_blocks = wp.shape[1] // block_v

    def block_logits(vi):
        wb = jax.lax.dynamic_slice(wp, (0, vi * block_v), (d, block_v))
        col = vi * block_v + jnp.arange(block_v)
        logits = jnp.where(col[None, :] < v, xf @ wb.astype(jnp.float32),
                           NEG_INF)
        return wb, col, logits

    # pass 1: global logsumexp per token (streamed)
    def lse_body(carry, vi):
        m, l = carry
        _, _, logits = block_logits(vi)
        m_new = jnp.maximum(m, logits.max(axis=1))
        l = l * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(axis=1)
        return (m_new, l), None

    (m, l), _ = jax.lax.scan(
        lse_body, (jnp.full((t,), NEG_INF, jnp.float32), jnp.zeros((t,), jnp.float32)),
        jnp.arange(n_blocks))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))

    # pass 2: accumulate grads block by block
    def grad_body(carry, vi):
        dx, dw = carry
        wb, col, logits = block_logits(vi)
        p = jnp.exp(logits - lse[:, None])
        p = p - (col[None, :] == labels[:, None]).astype(jnp.float32)
        p = p * g[:, None]
        dx = dx + p @ wb.astype(jnp.float32).T
        dwb = xf.T @ p
        dw = jax.lax.dynamic_update_slice(dw, dwb.astype(w.dtype), (0, vi * block_v))
        return (dx, dw), None

    (dx, dw), _ = jax.lax.scan(
        grad_body, (jnp.zeros((t, d), jnp.float32), jnp.zeros_like(wp)),
        jnp.arange(n_blocks))
    return dx.astype(x.dtype), dw[:, :v], None


fused_xent.defvjp(_fwd, _bwd)
