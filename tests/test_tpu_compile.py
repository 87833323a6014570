"""The Pallas kernels compile for a TPU v5e chip at Qwen3-1.7B's widths.

Nothing runs: each test lowers one kernel for a described (not attached)
v5e device and lets the chip's compiler accept or refuse it, which catches
block shapes that break the TPU tiling and kernels that overflow VMEM.
The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dequant, flash_attention, fused_xent
from repro.models import transformer as T
from repro.models.config import get_config

CFG = get_config("qwen3-1.7b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache entirely
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool_row_elems():
    """Elements of one flattened transformer layer: one resident pool row."""
    layers = T.abstract_params(CFG)["layers"]
    return sum(math.prod(leaf.shape[1:]) for leaf in jax.tree.leaves(layers))


@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_rows_compiles_at_pool_width(one_chip, bits):
    rows = CFG.n_layers                      # the one-chip ring's block depth
    nb = -(-_pool_row_elems() // dequant.QUANT_BLOCK)
    cols = dequant.QUANT_BLOCK * nb // (8 // bits)
    codes = jax.ShapeDtypeStruct(
        (rows, cols), jnp.int8 if bits == 8 else jnp.uint8, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((rows, nb), jnp.float32, sharding=one_chip)
    _compile(lambda c, s: dequant.dequant_rows(c, s, out_dtype=jnp.bfloat16),
             codes, scales)


def test_dequant_rows_instruction_keeps_its_name(one_chip):
    """The kernel's HLO instruction is ``dequant_rows.N`` (what the
    benchmark's ``dequant_rows_roofline`` finds in the trace) even when it
    is called, as the ring calls it, from another function inside an
    ``rp.*`` scope: without the kernel's own ``name`` the instruction takes
    the innermost scope's name."""
    codes = jax.ShapeDtypeStruct((8, 2048), jnp.int8, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=one_chip)

    def promote(c, s):
        with jax.named_scope("rp.promote"):
            return dequant.dequant_rows(c, s, out_dtype=jnp.bfloat16)

    text = _compile(promote, codes, scales).as_text()
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(", text)
    assert calls and all(re.fullmatch(r"dequant_rows(\.\d+)?", c)
                         for c in calls), calls


def test_flash_attention_compiles_at_model_width(one_chip):
    b, s = 1, 1024
    q = jax.ShapeDtypeStruct((b, s, CFG.n_heads, CFG.d_head), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, CFG.n_kv_heads, CFG.d_head),
                              jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention.flash_attention(q, k, v),
             q, kv, kv)


def test_fused_xent_compiles_at_model_vocab(one_chip):
    t = 512
    assert CFG.vocab_size % 512, "the test must exercise vocab padding"
    x = jax.ShapeDtypeStruct((t, CFG.d_model), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((CFG.d_model, CFG.vocab_size), jnp.bfloat16,
                             sharding=one_chip)
    labels = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip)

    def loss_and_grads(x, w, labels):
        return jax.value_and_grad(
            lambda x, w: fused_xent.fused_xent(x, w, labels).sum(),
            argnums=(0, 1))(x, w)

    _compile(loss_and_grads, x, w, labels)
