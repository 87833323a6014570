"""Fused dequant-on-upload (Pallas): rebuild the standby buffer in compute
precision straight from the quantized stream.

The resident pool crosses PCIe as blockwise-absmax codes (int8, or two int4
nibbles per byte for the frozen-base LoRA path) plus one fp32 scale per
``QUANT_BLOCK`` elements.  The kernel fuses the widen-and-rescale into the
standby promote, so the quantized payload never round-trips through a
separately materialised fp32 copy: codes stream VMEM-block by VMEM-block and
leave as compute-precision rows.

Quantization itself (host master -> codes) happens once per step on the pool
shard and is pure jnp — it is not on the per-tick critical path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QUANT_BLOCK = 256      # elements per scale (matches optim.compress.BLOCK)
INT8_QMAX = 127.0
INT4_QMAX = 7.0        # symmetric signed nibbles in [-7, 7]


# ---------------------------------------------------------------------------
# Quantize (pure jnp — once per step, off the tick loop)
# ---------------------------------------------------------------------------

def quantize_rows(rows, *, bits: int = 8, block: int = QUANT_BLOCK):
    """rows: (R, E) float -> (codes, scales).

    codes: (R, ceil(E/block)*block) int8 for ``bits=8``, or the int4-packed
    (R, ceil(E/block)*block // 2) uint8 pair-of-nibbles layout for ``bits=4``.
    scales: (R, ceil(E/block)) fp32, per-block absmax / qmax, clamped >=1e-12
    so all-zero blocks stay exact.
    """
    if bits not in (8, 4):
        raise ValueError(f"unsupported pool quantization bits: {bits}")
    r, e = rows.shape
    nb = -(-e // block)
    flat = jnp.pad(rows.astype(jnp.float32), ((0, 0), (0, nb * block - e)))
    blocks = flat.reshape(r, nb, block)
    qmax = INT8_QMAX if bits == 8 else INT4_QMAX
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=2) / qmax, 1e-12)
    codes = jnp.clip(jnp.round(blocks / scale[..., None]),
                     -qmax, qmax).astype(jnp.int8)
    codes = codes.reshape(r, nb * block)
    if bits == 4:
        codes = pack_int4(codes, block)
    return codes, scale


def pack_int4(codes, block: int = QUANT_BLOCK):
    """int8 codes in [-8, 7] -> uint8 nibble pairs, half the bytes.

    Within each run of ``block`` codes, code ``i`` lands in the low nibble of
    byte ``i`` and code ``i + block // 2`` in the high nibble, so the kernel
    widens a byte tile into two lane-aligned half-blocks (no interleave).
    The last dim must be a multiple of ``block``."""
    *lead, n = codes.shape
    half = (codes.astype(jnp.int32) & 0xF).astype(jnp.uint8).reshape(
        *lead, n // block, 2, block // 2)
    return (half[..., 0, :] | (half[..., 1, :] << 4)).reshape(*lead, n // 2)


def _widen_nibble(n):
    """[0, 15] nibble -> signed int32 in [-8, 7] (two's complement)."""
    n = n.astype(jnp.int32)
    return n - 16 * (n >> 3)


def unpack_int4(packed, block: int = QUANT_BLOCK):
    """uint8 nibble pairs -> int8 codes, inverse of :func:`pack_int4`."""
    *lead, n = packed.shape
    p = packed.astype(jnp.int32).reshape(*lead, 2 * n // block, block // 2)
    lo, hi = _widen_nibble(p & 0xF), _widen_nibble((p >> 4) & 0xF)
    return jnp.concatenate([lo, hi], axis=-1).reshape(
        *lead, 2 * n).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Pallas kernels: codes + scales -> compute-precision rows
# ---------------------------------------------------------------------------

ROW_TILE = 8         # pool rows per program (f32 sublane tile)
SCALE_TILE = 128     # scale blocks per program: one lane-width of scales


def _dequant_kernel(codes_ref, scale_ref, out_ref, *, block, packed,
                    n_blocks):
    # codes (rows, n_blocks * code_cols), scales (rows, n_blocks): widen,
    # rescale and cast one scale block at a time, all in one pass
    s = scale_ref[...].astype(jnp.float32)
    half = block // 2
    for j in range(n_blocks):
        sj = s[:, j:j + 1]
        if packed:
            p = codes_ref[:, j * half:(j + 1) * half].astype(jnp.int32)
            for k, nib in enumerate((p & 0xF, (p >> 4) & 0xF)):
                lo = j * block + k * half
                out_ref[:, lo:lo + half] = (
                    _widen_nibble(nib).astype(jnp.float32) * sj
                ).astype(out_ref.dtype)
        else:
            c = codes_ref[:, j * block:(j + 1) * block].astype(jnp.float32)
            out_ref[:, j * block:(j + 1) * block] = (c * sj).astype(
                out_ref.dtype)


def dequant_rows(codes, scales, *, block: int = QUANT_BLOCK,
                 out_dtype=jnp.float32, interpret: bool = False):
    """(codes, scales) from :func:`quantize_rows` -> (R, nb*block) rows.

    codes int8 selects 8-bit codes; uint8 the packed int4 pairs (the storage
    dtype IS the format tag).  Each program dequantizes a tile of
    ``ROW_TILE`` rows by ``SCALE_TILE`` scale blocks, so every block's last
    two dims meet the TPU's (8, 128) tiling; edge tiles are partial.  The
    kernel's ``name`` names its HLO instruction (``dequant_rows.N``) whatever
    scope or function calls it; the benchmark finds its events by it.
    """
    r, nb = scales.shape
    packed = codes.dtype == jnp.uint8
    code_cols = block // 2 if packed else block
    if codes.shape != (r, nb * code_cols):
        raise ValueError(f"codes {codes.shape} do not match scales {scales.shape} "
                         f"with block={block}")
    tr = ROW_TILE if r > ROW_TILE else r
    tb = SCALE_TILE if nb > SCALE_TILE else nb
    kernel = functools.partial(_dequant_kernel, block=block, packed=packed,
                               n_blocks=tb)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, tr), pl.cdiv(nb, tb)),
        in_specs=[
            pl.BlockSpec((tr, tb * code_cols), lambda ri, bi: (ri, bi)),
            pl.BlockSpec((tr, tb), lambda ri, bi: (ri, bi)),
        ],
        out_specs=pl.BlockSpec((tr, tb * block), lambda ri, bi: (ri, bi)),
        out_shape=jax.ShapeDtypeStruct((r, nb * block), out_dtype),
        interpret=interpret,
        name="dequant_rows",
    )(codes, scales)
