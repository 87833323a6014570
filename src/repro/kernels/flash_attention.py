"""Flash attention for TPU (Pallas): blocked online-softmax, causal/SWA/GQA.

TPU adaptation of the FlashAttention tiling (paper's workloads train with full
activation recomputation; attention is the dominant recompute cost).  Blocks
are sized for VMEM (q/k/v tiles) and MXU alignment (block_q, block_k multiples
of 128 at full size; tests sweep smaller interpret-mode blocks).  The kv-block
grid axis is innermost: TPU grid execution is sequential over it, so the
running (m, l, acc) state lives in VMEM scratch across iterations, and causal
block skipping uses ``pl.when`` (no wasted MXU work above the diagonal —
unlike the jnp reference path, which masks).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_k, n_kv_blocks, causal, sliding_window,
                  seq_kv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # causal / SWA block-level skip: is any (q,k) pair in this tile live?
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if sliding_window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - sliding_window)

    @pl.when(live if not isinstance(live, bool) else True)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (bq, d)
        k = k_ref[...].astype(jnp.float32)                 # (bk, d)
        v = v_ref[...].astype(jnp.float32)                 # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_kv
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if sliding_window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - sliding_window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _final():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, sliding_window=None,
                    logit_scale=None, block_q=128, block_k=128,
                    interpret=False):
    """q: (B,Sq,H,Dh); k,v: (B,Skv,KH,Dh|Dv) -> (B,Sq,H,Dv).

    GQA is handled by mapping query head h to kv head h // (H // KH) in the
    BlockSpec index maps (no materialised KV broadcast).  The kernel runs
    head-major, (B, H, S, D), so each block's last two dims are a
    (sequence, head_dim) tile as the TPU's tiling requires.
    """
    b, sq, h, dh = q.shape
    skv, kh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kh
    scale = logit_scale if logit_scale is not None else 1.0 / math.sqrt(dh)

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq = -(-sq // block_q)
    nk = -(-skv // block_k)
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv_blocks=nk, causal=causal, sliding_window=sliding_window,
        seq_kv=skv)

    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((None, None, block_k, dv),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, dv),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum
            pltpu.VMEM((block_q, dv), jnp.float32),   # output acc
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)
