"""Bytes from shapes that no architecture changes: the yardstick of the pool's
dequant kernel's roofline share.  An architecture's operations per token and
the elements of its pool row are its reference module's
(``bench/references/<name>.py``: ``step_flops_per_token``,
``layer_elems``).
"""
from __future__ import annotations

QUANT_BLOCK = 256
CODE_BITS = {"int8": 8, "int4": 4}


def dequant_bytes(rows: int, row_elems: int, pool_dtype: str,
                  out_bytes: int = 2) -> int:
    """HBM bytes of one ``dequant_rows`` call over ``rows`` pool rows:
    codes and float32 scales read, ``out_bytes``-wide rows written (the
    kernel writes whole blocks, padding included)."""
    blocks = -(-row_elems // QUANT_BLOCK)
    codes = rows * blocks * QUANT_BLOCK * CODE_BITS[pool_dtype] // 8
    scales = rows * blocks * 4
    out = rows * blocks * QUANT_BLOCK * out_bytes
    return codes + scales + out
