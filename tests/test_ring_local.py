"""Local-row injection on a one-worker ring (``RingMachine.local_slot``).

At N=1 every pool row already sits on the injecting worker, so each slot's
injection block is read in place from the pool: no standby, no chunk copy,
no self-``ppermute``.  These tests hold the local block to the standby path
bit for bit, the N=1 steps to float32 references, the lowered step to no
``rp.upload`` op, and the trace-time injection counter to all-local at N=1
and none at N=4.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.core.consistency import reference_staleness1
from repro.core.dispatch import (build_roundpipe_async_train_step,
                                 build_roundpipe_grads_fn,
                                 build_roundpipe_train_step,
                                 init_roundpipe_state)
from repro.core.partition import LayerCost, Partition
from repro.core.plan import compile_plan, plan_from_config
from repro.core.ring import AXIS, RingMachine
from repro.launch.mesh import make_mesh
from repro.launch.steps import StepConfig
from repro.models import lora as lora_mod
from repro.models import transformer as T
from repro.models.lora import LoraConfig
from repro.optim import OptConfig, init_opt_state, trainable_leaves
from repro.optim.adam import apply_updates

CFG = dataclasses.replace(smoke_config("qwen3-1.7b"), n_layers=4)
LORA = LoraConfig(rank=4, alpha=8.0)
B, S = 8, 16
POOL_DTYPES = ("none", "int8", "int4")


def uneven_plan():
    """N=1, slots F[0..2] FB[3+head] B[1..2] B[0]: kmax 3, so every slot
    but F pads, B[1..2] with layer 1 and not its last row."""
    part = Partition(fwd_stages=((0, 1, 2),),
                     bwd_stages=((3, 4), (1, 2), (0,)),
                     t_max=3.0, objective=0.0, n_stages=4)
    costs = [LayerCost(1.0, 2.0)] * 4 + [LayerCost(2.0, 4.0)]
    return compile_plan(part, costs, n_workers=1, n_body_layers=4)


PLANS = {"one-slot": lambda: plan_from_config(CFG, 1), "uneven": uneven_plan}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def weights():
    """Float32 weights and adapters, with B away from its zero init so both
    factors get gradients (one compiled call: eager init is slow on CPU)."""
    @jax.jit
    def make(key):
        params = T.init_params(key, CFG, dtype=jnp.float32)
        ad = lora_mod.init_adapters(jax.random.fold_in(key, 3),
                                    params["layers"], LORA,
                                    dtype=jnp.float32)
        keys = iter(jax.random.split(jax.random.fold_in(key, 4), 64))
        return params, jax.tree.map(
            lambda a: 0.05 * jax.random.normal(next(keys), a.shape, a.dtype),
            ad)
    return make(jax.random.PRNGKey(0))


def worst_rel(ref, got):
    """max over leaves of |got - ref|_inf / |ref|_inf."""
    return max(float(np.abs(np.asarray(g) - np.asarray(r)).max()
                     / (np.abs(np.asarray(r)).max() + 1e-6))
               for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(got)))


@pytest.fixture(scope="module")
def blocks(mesh, weights):
    """``{(plan, pool dtype): (plan, local blocks, promoted standbys)}``,
    one compiled call per plan over a bf16 pool."""
    pool = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                        weights[0]["layers"])
    out = {}
    for name, make_plan in PLANS.items():
        plan = make_plan()

        def body(pool, worker_id):
            res = {}
            for pool_dtype in POOL_DTYPES:
                rm = RingMachine(cfg=CFG, plan=plan, n_workers=1,
                                 l_pad=CFG.n_layers, worker_id=worker_id,
                                 pool_template=pool,
                                 prefetch_program=plan.prefetch_program(),
                                 pool_dtype=pool_dtype)
                quant = pool_dtype != "none"
                src = rm.quantize_pool(pool) if quant else pool
                got, want = [], []
                for spec in plan.stages:
                    if not spec.layers:
                        continue
                    assert rm.local_slot(spec)
                    assert not rm.uses_standby(spec.slot)
                    got.append(rm.local_block(spec, src, quantized=quant))
                    if quant:
                        stand = rm.upload_slot_q(rm.zeros_standby_q(src),
                                                 spec.slot, src)
                        want.append(rm.dequant_block(*stand, spec))
                    else:
                        stand = rm.upload_slot(rm.zeros_standby(), spec.slot,
                                               jax.tree.leaves(pool))
                        want.append(rm.promote_standby(stand, spec))
                res[pool_dtype] = (got, want)
            return res

        f = jax.shard_map(body, mesh=mesh, axis_names={AXIS},
                          in_specs=(P(AXIS), P(AXIS)), out_specs=P(),
                          check_vma=False)
        res = jax.jit(f)(pool, jnp.arange(1, dtype=jnp.int32))
        for pool_dtype, (got, want) in res.items():
            out[name, pool_dtype] = (plan, got, want)
    return out


@pytest.mark.parametrize("plan_name", sorted(PLANS))
@pytest.mark.parametrize("pool_dtype", POOL_DTYPES)
def test_local_block_equals_promoted_standby(blocks, plan_name, pool_dtype):
    plan, got, want = blocks[plan_name, pool_dtype]
    assert len(got) == sum(1 for s in plan.stages if s.layers)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture(scope="module")
def job(weights):
    """The cells' shape at smoke widths: one fused slot, float32 weights,
    adapters, two batches, and the merged-dense float32 loss and adapter
    gradients as the reference."""
    params, adapters = weights
    key = jax.random.PRNGKey(5)
    shape = (2, B, S)
    bs = {"tokens": jax.random.randint(key, shape, 0, CFG.vocab_size),
          "labels": jax.random.randint(jax.random.fold_in(key, 1), shape, 0,
                                       CFG.vocab_size)}

    @jax.jit
    def reference(adapters, batch):
        def loss(ad):
            merged = lora_mod.merge_params(params, ad, LORA)
            return T.loss_fn(merged, batch, CFG, remat=False, xent_chunk=S,
                             kv_chunk=S)
        return jax.value_and_grad(loss)(adapters)

    return dict(cfg=CFG, params=params, adapters=adapters, batches=bs,
                reference=reference, plan=plan_from_config(CFG, 1, lora=LORA))


def step_batch(job, t):
    return jax.tree.map(lambda x: x[t], job["batches"])


def test_sync_lora_step_matches_float32_reference(mesh, job):
    """Loss and adapter grads of the N=1 prefetch-path step against the
    float32 reference, at the subprocess harness's bars."""
    cfg, plan = job["cfg"], job["plan"]
    batch = step_batch(job, 0)
    ref_l, ref_g = job["reference"](job["adapters"], batch)
    grads_fn = build_roundpipe_grads_fn(
        cfg, mesh, plan, xent_chunk=S, kv_chunk=S, lora=LORA,
        prefetch_program=plan.prefetch_program())
    with mesh:
        got_g, loss, tokens = jax.jit(grads_fn)(
            dict(job["params"], lora=job["adapters"]), batch)
    assert int(tokens) == B * S
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-4)
    assert worst_rel(ref_g, got_g["lora"]) < 5e-3


def test_async_chain_matches_staleness1(mesh, job):
    """Two chained LoRA steps at N=1: each local block reads the adapter
    version its step injects (staleness 1), against the float32 oracle."""
    cfg, plan, adapters = job["cfg"], job["plan"], job["adapters"]
    ocfg = OptConfig(lr=1e-2)
    steps = 2
    ref_losses, opt = [], {"opt": init_opt_state(adapters, ocfg)}

    def device_fn(weights, t):
        loss, grads = job["reference"](weights[0], step_batch(job, t))
        ref_losses.append(float(loss))
        return [grads]

    def optimizer_fn(_, staged, t):
        new, opt["opt"], _ = apply_updates(opt["opt"], staged[0], ocfg,
                                           param_like=adapters)
        return [new]

    ref = reference_staleness1(1, device_fn, optimizer_fn, [adapters],
                               steps)[0]
    step_cfg = StepConfig(strategy="roundpipe", grad_accum=1, partition=plan,
                          n_microbatches=1, prefetch=True, kv_chunk=S,
                          xent_chunk=S, lora=LORA, opt=ocfg)
    multi, state_sh, _, _ = build_roundpipe_async_train_step(
        cfg, mesh, step_cfg, B, S, steps_per_call=steps, plan=plan)
    params = dict(job["params"], lora=adapters)
    opt0 = init_opt_state(
        trainable_leaves(params, lora_mod.param_mask(params)), ocfg)
    # the step donates its state: hand it copies, not the fixture's arrays
    state = jax.device_put(jax.tree.map(lambda a: jnp.array(a, copy=True),
                                        {"params": params, "opt": opt0}),
                           state_sh)
    with mesh:
        state, metrics = multi(state, job["batches"])
    np.testing.assert_allclose(np.asarray(metrics["loss"]), ref_losses,
                               rtol=1e-4)
    assert worst_rel(ref, state["params"]["lora"]) < 5e-3
    # per step: the weight ring's block and the adapter ring's
    assert multi.injections == {"local": 2 * steps, "streamed": 0}


SCOPE = re.compile(r"rp\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
LOC = re.compile(r'loc\("([^"]*)"')


def innermost_scopes(text):
    """The innermost ``rp.*`` scope of every named location."""
    return {found[-1] for name in LOC.findall(text)
            if (found := SCOPE.findall(name))}


def lora_step_cfg(plan, pool_dtype):
    return StepConfig(strategy="roundpipe", grad_accum=1, partition=plan,
                      kv_chunk=S, xent_chunk=S, lora=LORA,
                      pool_dtype=pool_dtype)


def test_lowered_one_worker_step_streams_nothing(mesh, job):
    """The one-slot int8 LoRA step the benchmark's cells run, at smoke
    widths: its ops carry ``rp.promote`` (the local dequant), none
    ``rp.upload``, and the counter reads both rings' injections local."""
    cfg = job["cfg"]
    plan = plan_from_config(cfg, 1, lora=LORA, pool_dtype="int8")
    step_cfg = lora_step_cfg(plan, "int8")
    step, _, _, _ = build_roundpipe_train_step(cfg, mesh, step_cfg, B, S)
    state = jax.eval_shape(functools.partial(
        init_roundpipe_state, cfg=cfg, step_cfg=step_cfg, n_workers=1),
        jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "labels")}
    with mesh:
        text = step.lower(state, batch).as_text(debug_info=True)
    scopes = innermost_scopes(text)
    assert "rp.promote" in scopes and "rp.upload" not in scopes
    assert step.injections == {"local": 2, "streamed": 0}


def test_four_worker_ring_streams_every_injection():
    """N=4 (traced on an abstract mesh, no devices): no slot is local."""
    cfg = dataclasses.replace(CFG, n_layers=2)
    plan = plan_from_config(cfg, 4, lora=LORA)
    step_cfg = lora_step_cfg(plan, "none")
    mesh4 = AbstractMesh((1, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    step, _, _, _ = build_roundpipe_train_step(cfg, mesh4, step_cfg, 4, S)
    state = jax.eval_shape(functools.partial(
        init_roundpipe_state, cfg=cfg, step_cfg=step_cfg, n_workers=4),
        jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((4, S), jnp.int32)
             for k in ("tokens", "labels")}
    step.trace(state, batch)
    n_inj = sum(1 for s in plan.stages if s.layers)
    assert step.injections == {"local": 0, "streamed": 2 * n_inj}
