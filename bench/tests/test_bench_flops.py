"""Operations and bytes from shapes: the ``qwen3_dense`` reference module's
counts by hand for both configurations and against XLA's own count on a
tiny model, and ``bench/flops.py``'s dequant kernel bytes."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, reference as R

D = harness.load_reference("qwen3_dense")


def _shape_job(cell):
    c = harness.load_cell(cell)
    assert c["reference"].__file__ == D.__file__
    return D.Shape.from_config(c["config_spec"]), R.Job.from_mix(c["mix"]), c


def _hand(L, d, qd, kvd, ff, V, r, seq):
    """Per token, written out: forward 2*params + attention + adapters +
    head; backward the same with attention and adapters doubled."""
    dense = d * qd + 2 * d * kvd + qd * d + 3 * d * ff
    lora = r * ((d + qd) + 2 * (d + kvd) + (qd + d) + 2 * (d + ff) + (ff + d))
    attn = 4 * qd * (seq + 1) / 2
    return 3 * L * (2 * lora) + L * (4 * dense + 3 * attn) + 4 * d * V


@pytest.mark.parametrize("cell,expect", [
    # Qwen3-1.7B, 28 layers, 1 x 512: about 7.2 GFLOP per token
    ("qwen3-1.7b.lora-bf16", _hand(28, 2048, 2048, 1024, 6144, 151936, 16,
                                   512)),
    # Qwen3-32B widths, 2 layers, 1 x 1024: about 7.1 GFLOP per token
    ("qwen3-32b-2l.lora-int8", _hand(2, 5120, 8192, 1024, 25600, 151936, 16,
                                     1024)),
])
def test_step_flops_match_a_hand_count(cell, expect):
    s, job, c = _shape_job(cell)
    got = D.step_flops_per_token(s, job, c["mix"]["seq"])
    assert got == pytest.approx(expect, rel=1e-12)
    assert 6.5e9 < got < 7.5e9


def test_head_share_of_the_32b_cut():
    s, job, c = _shape_job("qwen3-32b-2l.lora-int8")
    head = 4 * s.d_model * s.vocab
    share = head / D.step_flops_per_token(s, job, c["mix"]["seq"])
    assert 0.40 < share < 0.50


def _unrolled_loss(ad, base, batch, s, job):
    """The reference's layers unrolled, the head in one block: every
    matmul appears once in the HLO, so XLA's count is the whole step's."""
    x = jnp.take(base["embed"], batch["tokens"], axis=0).astype(jnp.float32)
    for i in range(s.n_layers):
        x = D._layer(x, jax.tree.map(lambda a: a[i], base["layers"]),
                     jax.tree.map(lambda a: a[i], ad), s,
                     job.alpha / job.rank)
    x = D._rms(x, base["final_norm"]["scale"], s.eps)
    logits = x @ base["lm_head"].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return (lse - gold).mean()


def test_step_flops_match_xla_cost_analysis_on_a_tiny_model():
    """XLA counts full (non-causal) attention products, recomputes nothing
    here and skips the first layer's input gradient: within 2%."""
    s = D.Shape(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, d_head=64,
                d_ff=768, vocab=1024, tied=False, rope_theta=1e6, eps=1e-6)
    _, job, _ = _shape_job("qwen3-1.7b.lora-bf16")
    seq = 128
    base, ad = jax.eval_shape(functools.partial(D.make_weights, s=s, job=job),
                              jax.random.PRNGKey(0))
    ad = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), ad)
    batch = {k: jax.ShapeDtypeStruct((1, seq), jnp.int32)
             for k in ("tokens", "labels")}
    grad = jax.jit(jax.grad(functools.partial(_unrolled_loss, s=s, job=job)))
    cost = grad.lower(ad, base, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    want = D.step_flops_per_token(s, job, seq, causal=False) * seq
    assert cost["flops"] == pytest.approx(want, rel=0.02)


def test_layer_elems_and_dequant_bytes_come_from_shapes():
    s, _, _ = _shape_job("qwen3-32b-2l.lora-int8")
    elems = D.layer_elems(s)
    assert elems == 5120 * (8192 + 2 * 1024) + 8192 * 5120 \
        + 3 * 5120 * 25600 + 2 * 5120
    blocks = -(-elems // 256)
    # 2 rows: int8 codes + f32 scales read, bf16 rows written
    assert flops.dequant_bytes(2, elems, "int8") == \
        2 * blocks * (256 + 4 + 512)
    assert flops.dequant_bytes(2, elems, "int4") == \
        2 * blocks * (128 + 4 + 512)
