"""Jit'd public wrappers for the Pallas kernels.

Each wrapper runs its Pallas kernel when the default backend is a TPU and
the pure-jnp reference otherwise.  The choice is made when the wrapper is
traced, so importing this module touches no backend.  ``interpret=True``
runs the Pallas kernel in interpret mode on any backend; only tests ask
for it.  Models call these entry points only — nothing else imports
kernels directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .dequant import dequant_rows as _dequant_pallas
from .flash_attention import flash_attention as _flash_pallas
from .fused_xent import fused_xent as _xent_pallas
from .rwkv_scan import rwkv_scan as _rwkv_pallas
from .ssm_scan import ssm_scan as _ssm_pallas


def _pallas(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


def _per_device(kernel, *args):
    """Call a Pallas ``kernel`` manual over every Auto axis of the ambient
    mesh (e.g. ``data`` inside the ring's shard_map over ``model``): GSPMD
    cannot partition a Mosaic kernel, so each device runs it on whole,
    replicated operands."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = {name for name, kind in zip(mesh.axis_names, mesh.axis_types)
            if kind == AxisType.Auto}
    if not auto:
        return kernel(*args)
    return jax.shard_map(kernel, in_specs=P(), out_specs=P(),
                         axis_names=auto, check_vma=False)(*args)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "interpret", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal=True, sliding_window=None,
                    interpret=False, block_q=128, block_k=128):
    if _pallas(interpret):
        return _per_device(functools.partial(
            _flash_pallas, causal=causal, sliding_window=sliding_window,
            block_q=block_q, block_k=block_k, interpret=interpret), q, k, v)
    return ref.flash_attention_ref(q, k, v, causal=causal,
                                   sliding_window=sliding_window)


@functools.partial(jax.jit, static_argnames=("interpret", "block_k"))
def decode_attention(q, k_cache, v_cache, n_valid, *, interpret=False,
                     block_k=512):
    if _pallas(interpret):
        return _per_device(functools.partial(
            _decode_pallas, block_k=block_k, interpret=interpret),
            q, k_cache, v_cache, n_valid)
    return ref.decode_attention_ref(q, k_cache, v_cache, n_valid)


@functools.partial(jax.jit, static_argnames=("interpret", "block_t",
                                             "block_v"))
def fused_xent(x, w, labels, *, interpret=False, block_t=256, block_v=512):
    if _pallas(interpret):
        return _per_device(
            lambda x, w, labels: _xent_pallas(x, w, labels, block_t, block_v,
                                              interpret), x, w, labels)
    return ref.fused_xent_ref(x, w, labels)


@functools.partial(jax.jit, static_argnames=("block", "out_dtype",
                                             "interpret"))
def dequant_rows(codes, scales, *, block=256, out_dtype=jnp.float32,
                 interpret=False):
    """Fused dequant-on-upload: blockwise-absmax codes + scales -> rows.

    ``codes.dtype`` tags the format: int8 = one code per element, uint8 = two
    int4 nibbles per byte (the frozen-base LoRA pool).  Output is the standby
    row in ``out_dtype`` — no intermediate fp32 materialization pass."""
    if _pallas(interpret):
        return _per_device(functools.partial(
            _dequant_pallas, block=block, out_dtype=out_dtype,
            interpret=interpret), codes, scales)
    return ref.dequant_rows_ref(codes, scales, block=block).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def rwkv_scan(r, k, v, w, u, s0, *, interpret=False, chunk=128):
    if _pallas(interpret):
        return _per_device(functools.partial(
            _rwkv_pallas, chunk=chunk, interpret=interpret),
            r, k, v, w, u, s0)
    return ref.rwkv_scan_ref(r, k, v, w, u, s0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_d",
                                             "chunk"))
def ssm_scan(x, dt, bmat, cmat, a, h0, *, interpret=False, block_d=256,
             chunk=128):
    if _pallas(interpret):
        return _per_device(functools.partial(
            _ssm_pallas, block_d=block_d, chunk=chunk, interpret=interpret),
            x, dt, bmat, cmat, a, h0)
    return ref.ssm_scan_ref(x, dt, bmat, cmat, a, h0)
