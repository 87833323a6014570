"""Quantized resident pool (ISSUE 6): codec round-trips, the fused
dequant-on-upload kernel vs its reference, plan-level byte accounting, and
the standby-cache / quantized-upload behavior of the transfer simulator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.partition import QUANT_BLOCK, quant_upload_bytes
from repro.core.plan import plan_from_config
from repro.core.simulator import simulate_plan
from repro.kernels import dequant as dq
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models.config import get_config

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_quant_pool_matches_reference():
    """Quantized resident pool: int8 per-block-absmax
    streaming with fused dequant-on-upload must track dequantize(quantize(W))
    run dense to ~float tolerance (the codec IS the only perturbation), the
    chunked code+scale prefetch must be BIT-identical to the whole-block
    quant gather, the 4-bit packed frozen base must track its dequantized
    reference under LoRA, plan byte accounting must match
    quant_upload_bytes exactly, and the error-feedback int8 deposit must
    telescope (mean error halves vs single-shot over K=4 repeats)."""
    run_mode("qwen3-1.7b", "quant", n_layers=7)


def _rows(r, e, seed=0, scale=3.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (r, e),
                                     jnp.float32)


# ---------------------------------------------------------------------------
# codec round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,qmax", [(8, 127.0), (4, 7.0)])
@pytest.mark.parametrize("e", [QUANT_BLOCK, 3 * QUANT_BLOCK, 1000])
def test_quantize_dequant_roundtrip(bits, qmax, e):
    rows = _rows(4, e, seed=bits)
    codes, scales = dq.quantize_rows(rows, bits=bits)
    nb = -(-e // QUANT_BLOCK)
    assert scales.shape == (4, nb) and scales.dtype == jnp.float32
    if bits == 8:
        assert codes.dtype == jnp.int8 and codes.shape == (4, nb * QUANT_BLOCK)
    else:  # storage dtype is the format tag
        assert codes.dtype == jnp.uint8
        assert codes.shape == (4, nb * QUANT_BLOCK // 2)
    deq = np.asarray(kref.dequant_rows_ref(codes, scales))[:, :e]
    # per-element error bounded by half a quantization step
    step = np.repeat(np.asarray(scales), QUANT_BLOCK, axis=1)[:, :e]
    assert (np.abs(deq - np.asarray(rows)) <= step / 2 + 1e-6).all()


def test_pack_unpack_int4_inverse():
    codes = jnp.arange(-8, 8, dtype=jnp.int8).reshape(1, 16)
    packed = dq.pack_int4(codes, block=8)
    assert packed.dtype == jnp.uint8 and packed.shape == (1, 8)
    # low nibbles carry the first half of each block, high nibbles the second
    np.testing.assert_array_equal(np.asarray(packed[0, :4]) & 0xF,
                                  np.arange(-8, -4) & 0xF)
    np.testing.assert_array_equal(np.asarray(packed[0, :4]) >> 4,
                                  np.arange(-4, 0) & 0xF)
    np.testing.assert_array_equal(np.asarray(dq.unpack_int4(packed, block=8)),
                                  np.asarray(codes))


def test_zero_rows_stay_exact():
    codes, scales = dq.quantize_rows(jnp.zeros((2, QUANT_BLOCK)))
    assert not np.asarray(kref.dequant_rows_ref(codes, scales)).any()


# ---------------------------------------------------------------------------
# fused kernel vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_pallas_dequant_matches_ref(bits):
    rows = _rows(3, 2 * QUANT_BLOCK, seed=9)
    codes, scales = dq.quantize_rows(rows, bits=bits)
    out = dq.dequant_rows(codes, scales, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.dequant_rows_ref(codes, scales)),
                               rtol=0, atol=0)


@pytest.mark.parametrize("bits", [8, 4])
def test_pallas_dequant_edge_tiles_match_ref(bits):
    """Rows and scale blocks that the kernel's tiles do not divide: the
    partial edge tiles must still reproduce the reference exactly."""
    rows = _rows(dq.ROW_TILE + 3, (dq.SCALE_TILE + 3) * QUANT_BLOCK - 5,
                 seed=10)
    codes, scales = dq.quantize_rows(rows, bits=bits)
    out = dq.dequant_rows(codes, scales, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(kref.dequant_rows_ref(codes, scales)))


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_ops_dequant_rows_dispatch(out_dtype):
    """kernels.ops.dequant_rows is the dispatch entry point: jit-safe and
    cast to the requested compute precision."""
    rows = _rows(2, QUANT_BLOCK, seed=11)
    codes, scales = dq.quantize_rows(rows)
    out = jax.jit(lambda c, s: kops.dequant_rows(c, s, out_dtype=out_dtype))(
        codes, scales)
    assert out.dtype == out_dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(kref.dequant_rows_ref(codes, scales)),
        rtol=1e-2 if out_dtype == jnp.bfloat16 else 0,
        atol=1e-2 if out_dtype == jnp.bfloat16 else 0)


# ---------------------------------------------------------------------------
# plan byte accounting
# ---------------------------------------------------------------------------

def test_quant_upload_bytes_formula():
    n = 5 * QUANT_BLOCK + 17          # forces block padding
    nb = -(-n // QUANT_BLOCK)
    assert quant_upload_bytes(n, "none") is None             # dense streaming
    assert quant_upload_bytes(n, "int8") == nb * QUANT_BLOCK + 4 * nb
    assert quant_upload_bytes(n, "int4") == nb * QUANT_BLOCK // 2 + 4 * nb
    with pytest.raises(ValueError):
        quant_upload_bytes(n, "fp8")


@pytest.mark.parametrize("dtype,hi", [("int8", 0.60), ("int4", 0.40)])
def test_plan_quant_upload_ratio(dtype, hi):
    """Quantized plans cut per-step upload bytes roughly in proportion to
    the code width; the replicated head stays dense, so the ratio sits a
    little above bits/16."""
    cfg = smoke_config(get_config("qwen3-1.7b"))
    dense = plan_from_config(cfg, 4)
    quant = plan_from_config(cfg, 4, pool_dtype=dtype)
    d_up = sum(c.upload_stream_bytes for c in dense.layer_costs)
    q_up = sum(c.upload_stream_bytes for c in quant.layer_costs)
    assert 0 < q_up < d_up
    lo = {"int8": 8, "int4": 4}[dtype] / 16 * 0.95
    assert lo < q_up / d_up < hi, q_up / d_up
    # head cost identical: quantization only touches the streamed body
    assert quant.layer_costs[-1].upload_bytes is None
    assert dense.layer_costs[-1].upload_stream_bytes == \
        quant.layer_costs[-1].upload_stream_bytes
    # compute/download untouched — only the up lane narrows
    for dc, qc in zip(dense.layer_costs, quant.layer_costs):
        assert dc.download_bytes == qc.download_bytes
        assert dc.fwd == qc.fwd and dc.grad == qc.grad


# ---------------------------------------------------------------------------
# simulator: quantized uploads + standby cache
# ---------------------------------------------------------------------------

def _plan(pool_dtype="none"):
    cfg = smoke_config(get_config("qwen3-1.7b"))
    return plan_from_config(cfg, 4, pool_dtype=pool_dtype)


def test_simulator_charges_quantized_bytes():
    bw = 1e6     # slow lane: makespan is upload-bound, so bytes dominate
    dense = simulate_plan(_plan(), bandwidth=bw)
    quant = simulate_plan(_plan("int8"), bandwidth=bw)
    assert quant.makespan < dense.makespan
    assert sum(quant.transfer_busy) < sum(dense.transfer_busy)


def test_standby_cache_pays_only_after_ring_wrap():
    """The ring rotates a fresh slot onto each worker every round/iteration,
    so a worker only REVISITS a slot once it has swept all of them —
    standby_cache is a no-op until the ring wraps (rounds + iterations >
    n_workers), then caps total upload traffic at one full sweep."""
    import dataclasses

    from repro.models.config import get_config as _get
    cfg = dataclasses.replace(smoke_config(_get("qwen3-1.7b")), n_layers=7)
    plan = plan_from_config(cfg, 4)
    bw = 1e4    # upload-bound lane so cached bytes move the makespan
    runs = {it: (simulate_plan(plan, bandwidth=bw, iterations=it),
                 simulate_plan(plan, bandwidth=bw, iterations=it,
                               standby_cache=True))
            for it in (1, 4, 5, 8)}
    for it in (1, 4):     # ring has not wrapped: nothing is revisited
        a, b = runs[it]
        assert b.makespan == a.makespan
        assert sum(b.transfer_busy) == sum(a.transfer_busy)
    for it in (5, 8):     # past the wrap: strictly cheaper
        a, b = runs[it]
        assert b.makespan < a.makespan
        assert sum(b.transfer_busy) < sum(a.transfer_busy)
    # cached upload traffic saturates at ONE full sweep of the slots
    assert sum(runs[5][1].transfer_busy) == sum(runs[8][1].transfer_busy) \
        == sum(runs[4][0].transfer_busy)
