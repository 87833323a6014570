"""Operations and bytes from shapes: the yardstick of ``mfu`` and of the
dequant kernel's roofline share.

Required operations of one LoRA training step, per token (multiply-add = 2):

  forward        every base matrix once, the adapters' two thin matrices,
                 attention's two score/value products over the causal
                 context (on average (seq + 1) / 2 keys), and the LM head;
  backward       activation gradients only through the frozen base and the
                 head (one product per matrix), attention's four products,
                 and the adapters' weight and activation gradients (two
                 products per thin matrix);
  not counted    recomputation, the per-step merge of the adapters into the
                 base, the codec, the optimizer and elementwise work.
"""
from __future__ import annotations

QUANT_BLOCK = 256
CODE_BITS = {"int8": 8, "int4": 4}


def layer_matrices(s) -> dict:
    """``{"attn.w_q": (din, dout), ...}`` of one decoder layer."""
    d, qd, kvd = s.d_model, s.n_heads * s.d_head, s.n_kv_heads * s.d_head
    return {"attn.w_q": (d, qd), "attn.w_k": (d, kvd), "attn.w_v": (d, kvd),
            "attn.w_o": (qd, d), "mlp.w_gate": (d, s.d_ff),
            "mlp.w_up": (d, s.d_ff), "mlp.w_down": (s.d_ff, d)}


def layer_elems(s) -> int:
    """Elements of one layer of the pool: its matrices and two norms."""
    return sum(a * b for a, b in layer_matrices(s).values()) + 2 * s.d_model


def step_flops_per_token(s, job, seq: int, causal: bool = True) -> float:
    mats = layer_matrices(s)
    dense = sum(a * b for a, b in mats.values())
    adapted = [k for k in mats
               if any(k == t or k.startswith(t + ".") for t in job.targets)]
    lora = sum(2 * job.rank * (mats[k][0] + mats[k][1]) for k in adapted)
    ctx = (seq + 1) / 2 if causal else seq
    attn = 2 * 2 * s.n_heads * s.d_head * ctx
    head = 2 * s.d_model * s.vocab
    forward = s.n_layers * (2 * dense + attn + lora) + head
    backward = s.n_layers * (2 * dense + 2 * attn + 2 * lora) + head
    return float(forward + backward)


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
