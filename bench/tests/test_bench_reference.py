"""``bench/reference.py`` and the configurations' reference module
``bench/references/qwen3_dense.py`` stand apart from the program and agree
with it: the same weights from the seed, bit for bit; the same float32 loss
and adapter gradients as the model code run in float32; the pool's
codec."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference as R
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.write_cell(tmp_path_factory.mktemp("cell"))
    return harness.load_cell("tiny", root)


@pytest.fixture(scope="module")
def D(cell):
    """The tiny configuration's reference module: ``qwen3_dense``."""
    assert cell["config_spec"]["reference"] == "qwen3_dense"
    return cell["reference"]


def _program_state(cell, key):
    from repro.core.dispatch import init_roundpipe_state
    from repro.launch.steps import StepConfig
    from repro.models.lora import LoraConfig
    lora = cell["mix"]["lora"]
    step_cfg = StepConfig(strategy="roundpipe", lora=LoraConfig(
        rank=lora["rank"], alpha=lora["alpha"],
        target_modules=tuple(lora["targets"])))
    return init_roundpipe_state(key, harness.model_config(cell["config_spec"]),
                                step_cfg, n_workers=1)


def test_reference_imports_nothing_of_the_program():
    for path in [R.__file__, *(harness.BENCH / "references").glob("*.py")]:
        text = open(path).read()
        assert "repro" not in text, path


def test_same_weights_from_the_seed(cell, D):
    key = harness.seed_key(2 ** 33 + 7)
    s = D.Shape.from_config(cell["config_spec"])
    job = R.Job.from_mix(cell["mix"])
    base, adapters = D.make_weights(key, s, job)
    params = _program_state(cell, key)["params"]
    want = {k: v for k, v in params.items() if k != "lora"}
    assert jax.tree.structure(base) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(adapters),
                    jax.tree.leaves(params["lora"])):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_float32_loss_and_grads_match_the_model_code(cell, D):
    """With every weight in float32 and the adapters' B non-zero, the
    reference's unmerged LoRA layer equals the model code's merged one."""
    from repro.models import lora as lora_mod
    from repro.models import transformer as T
    from repro.models.lora import LoraConfig
    s = D.Shape.from_config(cell["config_spec"])
    job = R.Job.from_mix(cell["mix"])
    cfg = harness.model_config(cell["config_spec"])
    base, adapters = D.make_weights(jax.random.PRNGKey(3), s, job)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    base = f32(base)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    adapters = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(
        next(keys), a.shape), f32(adapters))
    from bench import traffic
    batch = {k: jnp.asarray(v) for k, v in traffic.Batches(
        cell["mix"], s.vocab, 11).batch(0).items()}
    lcfg = LoraConfig(rank=job.rank, alpha=job.alpha,
                      target_modules=job.targets)

    def program_loss(ad):
        return T.loss_fn(lora_mod.merge_params(base, ad, lcfg), batch, cfg,
                         kv_chunk=16, xent_chunk=16)

    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(program_loss)(adapters)
        got, gg = jax.value_and_grad(D.loss_fn)(adapters, base, batch, s,
                                                 job)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6 * float(
            jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("pool", ["int8", "int4"])
def test_codec_matches_the_program_quantizer(cell, D, pool):
    from repro.kernels.dequant import quantize_rows
    from repro.kernels.ref import dequant_rows_ref
    s = D.Shape.from_config(cell["config_spec"])
    job = R.Job.from_mix(cell["mix"])
    base, _ = D.make_weights(jax.random.PRNGKey(5), s, job)
    got = R.codec(base["layers"], pool)
    leaves = jax.tree.leaves(base["layers"])
    rows = jnp.concatenate([l.reshape(s.n_layers, -1) for l in leaves], 1)
    # jitted, as inside the step: eager division rounds some ties apart
    deq = jax.jit(lambda r: dequant_rows_ref(
        *quantize_rows(r, bits=int(pool[3:]))))(rows)
    off = 0
    for g, l in zip(jax.tree.leaves(got), leaves):
        n = l[0].size
        want = deq[:, off:off + n].reshape(l.shape).astype(l.dtype)
        off += n
        assert np.array_equal(g, want)
