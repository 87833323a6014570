"""Subprocess body for RoundPipe dispatch correctness (needs 8 host devices
set BEFORE jax init, so it cannot run in the main pytest process).

Compares the plan-driven shard_map ring pipeline's loss and gradients against
the plain single-program reference on identical fp32 parameters.

Usage:  python roundpipe_subprocess.py <arch> [mode] [n_layers]

mode:
  uniform  — 1-layer-per-stage plan (the seed runtime's only shape)
  auto     — cost-model auto_partition (paper §4.4), incl. LM-head stage
  uneven   — hand-built non-uniform partition with an LM-head pseudo-layer,
             n_layers % n_workers != 0
  prefetch — the uneven-auto plan executed twice: whole-block injection vs
             the chunked double-buffered PrefetchProgram path (forced chunk
             splits); gradients must match bit-tightly AND the reference
  lora     — frozen-base adapter fine-tuning on the uneven auto plan
             (n_layers % N != 0): one LoRA RoundPipe step vs a single-program
             merged-dense reference (base weights with W + (alpha/r)·B@A
             folded in); per-leaf allclose on loss and adapter grads, and the
             deposited pytree must hold ONLY adapter leaves (no base grads)
  rounds   — multi-round steady state on the uneven auto plan: for
             R in {1, 2, 3}, an R-round gradient-accumulated step
             (n_microbatches = R*N, rounds stitched back-to-back in
             R*S + N - 1 ticks) must per-leaf allclose the single-program
             full-batch reference over all M micro-batches; R = 1 must be
             BIT-identical to the legacy single-round path
  rounds-lora — the same R-sweep with a frozen base: R-round accumulated
             adapter grads vs the merged-dense full-batch reference
  quant    — quantized resident pool with fused dequant-on-upload on the
             uneven 7-layer/4-worker auto plan: the int8 ring must match a
             single-program reference on the int8-DEQUANTIZED weights
             near-exactly (and the fp32 reference within quantization
             tolerance), the chunked code+scale prefetch path must be
             BIT-identical to the whole-block quant gather, the int4
             frozen-base LoRA ring must match merged-dense references on
             the dequantized and fp32 bases, and error-feedback int8
             deposits (grad_compress) must converge to the exact grads as
             the residual telescopes over repeated steps
  async-quant — quantized pool AND compressed deposits under the cross-step
             staleness-1 chained program (the combination the launcher
             refused before the schedule-IR refactor): the int8 ring must
             land on the staleness-1 oracle taken at the int8-DEQUANTIZED
             pool (requantized in-program at every update tick), separate
             from the staleness-0 trajectory, and grad_compress="int8"
             must thread the error-feedback residual through
             state["opt"]["grad_residual"] across the chain
  async-lora — cross-step staleness-1 chained program with a FROZEN base:
             the dense pool is read-only (bit-identical across the chain)
             while the adapter ring versions staleness-1; the final
             adapter pool must allclose reference_staleness1 restricted to
             the adapters (and separate from the staleness-0 trajectory)
  chaos    — the goodput supervisor driving the REAL compiled step through
             the full detect→mitigate state machine on the uneven
             7-layer/4-worker auto plan: a 5x-slowed worker mid-run must
             trigger the straggler streak → schedule re-score → g0
             rotation rebuild, a killed worker must trigger the elastic
             re-plan to N-1 + restore from the (async-written) newest
             checkpoint; the final params must land within the harness
             tolerance of the UNINTERRUPTED N=4 reference trajectory
             (deterministic replay: the replayed step's loss matches its
             pre-fault run), and the goodput ledger must charge the
             replay/replan overhead
  async    — cross-step staleness-1 chained program (paper §4.3) on the
             uneven 7-layer/4-worker auto plan: I optimizer steps executed
             back-to-back in ONE ring program (fill/drain paid once per
             chain, step T+1 injecting while step T drains into the
             in-program host optimizer) must per-leaf allclose
             reference_staleness1; with overlap disabled the multi-step
             driver must be BIT-identical to looping PR 4's synchronous
             step; and the threaded HostAsyncRoundPipe worker (the five
             per-layer ConsistencyProtocol constraints around the real
             dispatch grads_fn) must land on the same trajectory
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import smoke_config  # noqa: E402
from repro.core.dispatch import build_roundpipe_grads_fn  # noqa: E402
from repro.core.partition import LayerCost, Partition  # noqa: E402
from repro.core.plan import (compile_plan, plan_from_config,  # noqa: E402
                             uniform_partition)
from repro.core.simulator import simulate_plan  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import get_config  # noqa: E402
import dataclasses  # noqa: E402


LORA_CFG = None  # set in main() for mode == "lora"


def make_plan(mode: str, cfg, n_workers: int):
    if mode in ("prefetch", "rounds", "async", "quant", "async-quant",
                "chaos"):
        return plan_from_config(cfg, n_workers)
    if mode in ("lora", "rounds-lora", "async-lora"):
        return plan_from_config(cfg, n_workers, lora=LORA_CFG)
    if mode == "uniform":
        part = uniform_partition(cfg.n_layers)
        costs = [LayerCost(1.0, 2.0) for _ in range(cfg.n_layers)]
        return compile_plan(part, costs, n_workers=n_workers,
                            n_body_layers=cfg.n_layers)
    if mode == "auto":
        return plan_from_config(cfg, n_workers)
    if mode == "uneven":
        # 6 body layers + head pseudo-layer on 4 workers (6 % 4 != 0):
        # fwd blocks of 2, fused = layers 4,5 + head, uneven backward blocks.
        assert cfg.n_layers == 6, "uneven mode expects n_layers=6"
        part = Partition(fwd_stages=((0, 1), (2, 3)),
                         bwd_stages=((4, 5, 6), (3,), (0, 1, 2)),
                         t_max=9.0, objective=0.0, n_stages=5)
        costs = [LayerCost(1.0, 2.0) for _ in range(6)] + [LayerCost(2.0, 4.0)]
        return compile_plan(part, costs, n_workers=n_workers,
                            n_body_layers=cfg.n_layers)
    raise SystemExit(f"unknown mode {mode}")


# ---------------------------------------------------------------------------
# shared fixture builders — every mode parametrizes these instead of
# re-implementing its own batch / adapter / state / comparison setup
# ---------------------------------------------------------------------------

def make_batch(key, cfg, b, s, steps=None):
    """One (b, s) batch, or a stacked (steps, b, s) multi-step batch."""
    shape = (b, s) if steps is None else (steps, b, s)
    out = {}
    if cfg.frontend:
        out["embeds"] = jax.random.normal(key, shape + (cfg.d_model,),
                                          jnp.float32)
    else:
        out["tokens"] = jax.random.randint(key, shape, 0, cfg.vocab_size)
    out["labels"] = jax.random.randint(jax.random.fold_in(key, 1), shape, 0,
                                       cfg.vocab_size)
    return out


def make_adapters(params):
    """Frozen-base adapter pool, randomized away from the zero-B init so
    BOTH factors carry nonzero gradients (zero B would make every A-grad
    trivially zero)."""
    from repro.models import lora as lora_mod
    adapters = lora_mod.init_adapters(jax.random.PRNGKey(3),
                                      params["layers"], LORA_CFG,
                                      dtype=jnp.float32)
    return jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(4), a.shape, a.dtype)
        * 0.05, adapters)


def fresh_train_state(params, cfg, n, sh, ocfg, *, lora=False):
    """A donation-safe padded train state: the steps donate their input, so
    every run gets its own copy of the padded params/opt buffers.  With
    ``lora`` the optimizer state covers the adapter leaves only."""
    from repro.core.dispatch import pad_pool
    from repro.optim import init_opt_state, trainable_leaves

    padded = jax.tree.map(lambda x: jnp.array(x, copy=True),
                          pad_pool(params, cfg, n))
    if lora:
        from repro.models import lora as lora_mod
        opt = init_opt_state(
            trainable_leaves(padded, lora_mod.param_mask(padded)), ocfg)
    else:
        opt = init_opt_state(padded, ocfg)
    return jax.device_put({"params": padded, "opt": opt}, sh)


def tree_items(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def assert_trees_equal(a_tree, b_tree, msg):
    """Per-leaf BIT equality (same paths, same bytes)."""
    for (ka, va), (kb, vb) in zip(tree_items(a_tree), tree_items(b_tree)):
        assert ka == kb
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb),
            err_msg=f"{msg} at {jax.tree_util.keystr(ka)}")


def assert_trees_close(a_tree, b_tree, msg, rtol=1e-5, atol=1e-7):
    for (ka, va), (kb, vb) in zip(tree_items(a_tree), tree_items(b_tree)):
        assert ka == kb
        np.testing.assert_allclose(np.asarray(vb, np.float32),
                                   np.asarray(va, np.float32),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"{msg} at "
                                           f"{jax.tree_util.keystr(ka)}")


def worst_rel_tree(ref_tree, got_tree, label=""):
    """max over leaves of |got - ref|_inf / |ref|_inf (the harness bar)."""
    worst = 0.0
    for (ka, va), (kb, vb) in zip(tree_items(ref_tree), tree_items(got_tree)):
        assert ka == kb
        rv = np.asarray(va, np.float32)
        gv = np.asarray(vb, np.float32)
        err = np.abs(gv - rv).max() / (np.abs(rv).max() + 1e-6)
        if err > worst:
            worst = err
        if label and err > 5e-3:
            print("MISMATCH", label, jax.tree_util.keystr(ka), err)
    return worst


def check_tick_order(plan, rounds, iterations=1):
    """The runtime's injection order IS the round-stitched tick table, the
    schedule generator dispatches slots in the same order, and the
    generated TickProgram IR agrees record-for-record (and round-trips
    through its JSON serialization)."""
    from repro.core.schedule import TickProgram, dispatch_slot_order
    from repro.core.schedule import validate as validate_schedule

    n = plan.n_workers
    table = plan.tick_table(rounds, iterations)
    assert len(table) == iterations * rounds * plan.n_slots + n - 1
    sched = plan.schedule(rounds * n, round_size=n, iterations=iterations)
    validate_schedule(sched)
    if iterations == 1:
        order = dispatch_slot_order(sched, n)
    else:
        order = dispatch_slot_order(sched, n, rounds_per_iteration=rounds)
    assert order == [e for e in table if e is not None], (rounds, iterations)
    prog = plan.tick_program(rounds, iterations)
    assert prog.entries == tuple(table)
    assert TickProgram.from_json(prog.to_json()) == prog


def build_grads_fn(cfg, mesh, plan, **kw):
    """Build the grads_fn in BOTH driver shapes: the legacy-shaped call
    (the driver generates its tick program internally) and the unified
    ring machine handed the generated schedule IR explicitly.  On first
    call the two must trace to the IDENTICAL jaxpr — the refactor
    guarantee that a schedule is plan-layer data, not a second code path —
    then the legacy-shaped jitted callable serves the mode's comparisons."""
    m = kw.get("n_microbatches")
    rounds = plan.rounds_for(m) if m else 1
    legacy = build_roundpipe_grads_fn(cfg, mesh, plan, **kw)
    explicit = build_roundpipe_grads_fn(
        cfg, mesh, plan, tick_program=plan.tick_program(rounds), **kw)
    jitted = jax.jit(legacy)
    checked = []

    def fn(*args):
        if not checked:
            ja = jax.make_jaxpr(legacy)(*args)
            jb = jax.make_jaxpr(explicit)(*args)
            assert str(ja) == str(jb), \
                "explicit tick_program traced a DIFFERENT program than the " \
                "legacy-shaped driver call"
            checked.append(True)
        return jitted(*args)

    return fn


def main():
    global LORA_CFG
    arch = sys.argv[1] if len(sys.argv) > 1 else "qwen3-1.7b"
    mode = sys.argv[2] if len(sys.argv) > 2 else "uniform"
    n_layers = int(sys.argv[3]) if len(sys.argv) > 3 else \
        (6 if mode == "uneven" else
         7 if mode in ("quant", "async-lora", "async-quant", "chaos") else 8)
    cfg = smoke_config(get_config(arch))
    cfg = dataclasses.replace(cfg, n_layers=n_layers, name=cfg.name + "-rp")
    n_model = 4
    mesh = make_mesh((2, n_model), ("data", "model"))
    if mode in ("lora", "rounds-lora", "quant", "async-lora"):
        from repro.models.lora import LoraConfig
        LORA_CFG = LoraConfig(rank=4, alpha=8.0)

    plan = make_plan(mode, cfg, n_model)
    plan.validate()
    sim = simulate_plan(plan)            # same object the runtime executes
    print(plan.describe())
    print(f"simulated bubble ratio: {sim.bubble_ratio:.4f}")

    key = jax.random.PRNGKey(0)
    # fp32 params for tight comparison
    params = T.init_params(key, cfg, dtype=jnp.float32)
    b, s = 8, 16
    if mode in ("rounds", "rounds-lora"):
        run_rounds(cfg, mesh, plan, params, s, lora=mode == "rounds-lora")
        return
    if mode == "chaos":
        run_chaos(cfg, mesh, plan, params, s)
        return
    if mode == "async":
        run_async(cfg, mesh, plan, params, b, s)
        return
    if mode == "async-lora":
        run_async_lora(cfg, mesh, plan, params, b, s)
        return
    batch = make_batch(key, cfg, b, s)

    if mode == "lora":
        run_lora(cfg, mesh, plan, params, batch, b, s)
        return
    if mode == "quant":
        run_quant(cfg, mesh, plan, params, batch, b, s)
        return
    if mode == "async-quant":
        run_async_quant(cfg, mesh, plan, params, b, s)
        return

    # ---- reference loss & grads (single program, no pipeline) ---------------
    def ref_loss(p):
        return T.loss_fn(p, batch, cfg, remat=False, xent_chunk=8, kv_chunk=8)

    ref_l, ref_g = jax.value_and_grad(ref_loss)(params)

    # ---- roundpipe ----------------------------------------------------------
    check_tick_order(plan, 1)
    grads_fn = build_grads_fn(cfg, mesh, plan, xent_chunk=8, kv_chunk=8)
    with mesh:
        rp_g, rp_loss, rp_tokens = grads_fn(params, batch)

    if mode == "prefetch":
        # chunk_limit = 1/3 of the largest BODY layer's planned bytes: every
        # ring row is split into >= 3 partial-row uploads spread across LPT
        # windows (head chunks are budget-only, row == -1, so they must not
        # count toward the splitting guard)
        biggest = max(int(c.weight_bytes)
                      for c in plan.layer_costs[:plan.n_layers])
        program = plan.prefetch_program(chunk_limit=max(1, biggest // 3))
        n_chunks = sum(1 for t in program.uploads for cu in t if cu.row >= 0)
        assert n_chunks > plan.n_layers, "row chunk splitting did not engage"
        pf_fn = build_grads_fn(cfg, mesh, plan, xent_chunk=8, kv_chunk=8,
                               prefetch_program=program)
        with mesh:
            pf_g, pf_loss, _ = pf_fn(params, batch)
        np.testing.assert_allclose(float(pf_loss), float(rp_loss), rtol=1e-6)
        assert_trees_close(rp_g, pf_g, "prefetch vs whole-block")
        print(f"prefetch path matches whole-block "
              f"({n_chunks} row chunk uploads)")

    print("ref loss", float(ref_l), "rp loss", float(rp_loss))
    np.testing.assert_allclose(float(rp_loss), float(ref_l), rtol=1e-4)
    assert int(rp_tokens) == b * s

    flat_ref = jax.tree_util.tree_flatten_with_path(ref_g)[0]
    flat_rp = jax.tree_util.tree_flatten_with_path(rp_g)[0]
    ref_map = {jax.tree_util.keystr(k): v for k, v in flat_ref}
    rp_map = {jax.tree_util.keystr(k): v for k, v in flat_rp}
    assert set(ref_map) == set(rp_map), (set(ref_map) ^ set(rp_map))
    worst = worst_rel_tree(ref_g, rp_g, label="grads")
    print("worst rel grad err:", worst)
    assert worst < 5e-3, worst
    print("ROUNDPIPE_DISPATCH_OK")


def run_rounds(cfg, mesh, plan, params, s, *, lora=False):
    """Multi-round steady-state equivalence (ISSUE 4 tentpole): for each
    R in {1, 2, 3} an R-round gradient-accumulated RoundPipe step over
    M = R*N micro-batches must per-leaf allclose the single-program
    full-batch reference on the SAME M-micro-batch batch; R = 1 must be
    bit-identical to the legacy (no round axis) path.  ``lora`` runs the
    frozen-base variant against the merged-dense reference."""
    n = plan.n_workers
    b_round = 8                          # samples per round (2 per worker)
    key = jax.random.PRNGKey(0)

    adapters = make_adapters(params) if lora else None

    for r in (1, 2, 3):
        m = r * n
        g = r * b_round
        kb = jax.random.fold_in(key, r)
        batch = {"tokens": jax.random.randint(kb, (g, s), 0, cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.fold_in(kb, 1),
                                              (g, s), 0, cfg.vocab_size)}

        # the runtime's injection order IS the round-stitched tick table,
        # the schedule generator dispatches slots in the same order, and
        # the generated IR round-trips
        check_tick_order(plan, r)

        if lora:
            from repro.models import lora as lora_mod

            def ref_loss(ad):
                merged = lora_mod.merge_params(params, ad, LORA_CFG)
                return T.loss_fn(merged, batch, cfg, remat=False,
                                 xent_chunk=8, kv_chunk=8)

            ref_l, ref_g = jax.value_and_grad(ref_loss)(adapters)
            rp_params = dict(params, lora=adapters)
        else:
            def ref_loss(p):
                return T.loss_fn(p, batch, cfg, remat=False, xent_chunk=8,
                                 kv_chunk=8)

            ref_l, ref_g = jax.value_and_grad(ref_loss)(params)
            rp_params = params

        fn = build_grads_fn(
            cfg, mesh, plan, xent_chunk=8, kv_chunk=8,
            lora=LORA_CFG if lora else None, n_microbatches=m)
        with mesh:
            rp_g, rp_loss, rp_tokens = fn(rp_params, batch)
        assert int(rp_tokens) == g * s, (int(rp_tokens), g * s)

        if lora:
            assert set(rp_g) == {"lora"}, set(rp_g)
            rp_cmp, ref_cmp = rp_g["lora"], ref_g
        else:
            rp_cmp, ref_cmp = rp_g, ref_g

        if r == 2 and not lora:
            # multi-round prefetch: the per-slot ChunkUpload tables are
            # replayed modulo S — round 2's standby uploads stream while
            # round 1 drains — and must stay bit-equivalent to the
            # whole-block gather (forced row chunk splitting, as in the
            # single-round prefetch mode)
            biggest = max(int(c.weight_bytes)
                          for c in plan.layer_costs[:plan.n_layers])
            program = plan.prefetch_program(chunk_limit=max(1, biggest // 3))
            pf_fn = build_roundpipe_grads_fn(
                cfg, mesh, plan, xent_chunk=8, kv_chunk=8,
                prefetch_program=program, n_microbatches=m)
            with mesh:
                pf_g, pf_loss, _ = jax.jit(pf_fn)(rp_params, batch)
            np.testing.assert_allclose(float(pf_loss), float(rp_loss),
                                       rtol=1e-6)
            assert_trees_close(rp_g, pf_g, "R=2 prefetch vs whole-block")
            print("R=2 prefetch path matches whole-block injection")

        if r == 1:
            # legacy single-round path (no round axis): the generalized
            # machinery at R=1 must be BIT-identical, not just close
            legacy_fn = build_roundpipe_grads_fn(
                cfg, mesh, plan, xent_chunk=8, kv_chunk=8,
                lora=LORA_CFG if lora else None)
            with mesh:
                lg, ll, _ = jax.jit(legacy_fn)(rp_params, batch)
            assert np.asarray(ll).tobytes() == np.asarray(rp_loss).tobytes()
            assert_trees_equal(lg, rp_g,
                               "R=1 not bit-identical to legacy path")
            print("R=1 bit-identical to the legacy single-round path")

        print(f"R={r}: ref loss {float(ref_l)} rp loss {float(rp_loss)}")
        np.testing.assert_allclose(float(rp_loss), float(ref_l), rtol=1e-4)
        worst = worst_rel_tree(ref_cmp, rp_cmp, label=f"R={r}")
        print(f"R={r}: worst rel grad err: {worst}")
        assert worst < 5e-3, (r, worst)
    print("ROUNDPIPE_DISPATCH_OK")


def run_chaos(cfg, mesh, plan, params, s):
    """Chaos harness for the goodput supervisor (ISSUE 10 tentpole): the
    REAL compiled RoundPipe step driven through the full detect→mitigate
    state machine on the uneven 7-layer/4-worker auto plan.

    Injected faults: worker 2 reports 5x-slow step times from step 2 (while
    the schedule is unrotated) — the straggler streak must re-score the
    rotation family under the measured ``device_scale`` and rebuild the
    step with the winning ``g0=3``; worker 1 dies at step 5 — the
    supervisor must re-plan for the N-1=3 survivors (fresh auto partition,
    M' floored to 3), restore the newest ASYNC-written checkpoint through
    the elastic re-shard path onto the (2,3) mesh, and replay
    deterministically.  Bars: the final params match the uninterrupted
    N=4 reference trajectory within the harness tolerance, the replayed
    step's loss matches its pre-fault value (deterministic data replay),
    and the goodput ledger charges nonzero replay + replan overhead."""
    import shutil
    import tempfile

    from repro.core.dispatch import (build_roundpipe_train_step,
                                     reshape_pooled_state)
    from repro.core.plan import replan_for_survivors
    from repro.core.simulator import search_schedule
    from repro.launch.steps import StepConfig
    from repro.optim import OptConfig
    from repro.runtime.fault_tolerance import StragglerPolicy
    from repro.runtime.supervisor import Supervisor, WorkerFault

    n0 = plan.n_workers
    b = 12                       # divisible by M at N=4 (M=4) and N=3 (M=3)
    n_steps = 8
    kill_at, slow_from = 5, 2
    ocfg = OptConfig(lr=1e-2)
    key = jax.random.PRNGKey(11)
    losses = {}                  # step -> [loss, ...]; replays append
    killed = []
    compiled = {}                # (n_workers, g0) -> built step bundle

    def data_for(step):
        return make_batch(jax.random.fold_in(key, 1000 + step), cfg, b, s)

    def build(n_workers, g0, replan):
        if (n_workers, g0) not in compiled:
            if n_workers == n0:
                sub_mesh, rt_plan, m = mesh, plan, n0
            else:
                sub_mesh = make_mesh((2, n_workers), ("data", "model"),
                                     devices=jax.devices()[:2 * n_workers])
                rt_plan, m = replan.plan, replan.n_microbatches
            scfg = StepConfig(strategy="roundpipe", grad_accum=1,
                              partition=rt_plan, n_microbatches=m,
                              kv_chunk=8, xent_chunk=8, opt=ocfg, g0=g0)
            step, state_sh, _, _ = build_roundpipe_train_step(
                cfg, sub_mesh, scfg, b, s, plan=rt_plan)
            compiled[(n_workers, g0)] = (step, state_sh, rt_plan, m,
                                         sub_mesh)
        return compiled[(n_workers, g0)]

    def make_runtime(*, n_workers, g0, use_async, replan=None):
        del use_async
        step_c, state_sh, rt_plan, m, sub_mesh = build(n_workers, g0, replan)
        ticks = []               # steps THIS runtime has completed

        class RT:
            shardings = state_sh
            like = state_sh      # loader only needs the tree structure

            @staticmethod
            def init_state():
                return fresh_train_state(params, cfg, n_workers, state_sh,
                                         ocfg)

            @staticmethod
            def batch_for(step):
                return step, data_for(step)

            @staticmethod
            def step_fn(state, step_batch):
                t, batch = step_batch
                if t == kill_at and not killed:
                    killed.append(t)
                    raise WorkerFault(1, "chaos: injected device loss")
                with sub_mesh:
                    new_state, metrics = step_c(state, batch)
                losses.setdefault(t, []).append(float(metrics["loss"]))
                return new_state, metrics

            @staticmethod
            def adapt_state(host_state):
                # elastic restore: re-pad the pool for THIS worker count,
                # then re-place under this mesh's shardings
                return jax.device_put(
                    reshape_pooled_state(host_state, cfg, n_workers),
                    state_sh)

            @staticmethod
            def worker_times(metrics):
                ticks.append(1)
                if n_workers == n0 and g0 == 0 and len(ticks) > slow_from:
                    return [1.0, 1.0, 5.0, 1.0]   # worker 2 is 5x slow
                return [1.0] * n_workers

            @staticmethod
            def rescore(scales):
                sr = search_schedule(rt_plan, m, round_size=n_workers,
                                     device_scale=list(scales))
                return sr.choice.g0

        return RT

    ckpt_dir = tempfile.mkdtemp(prefix="chaos-ckpt-")
    try:
        sup = Supervisor(
            make_runtime, ckpt_dir, n_workers=n0,
            replan_fn=lambda n: replan_for_survivors(
                cfg, n, n_microbatches=n0, async_steps=1),
            straggler=StragglerPolicy(factor=2.0, min_samples=2),
            save_every=2, async_ckpt=True, use_async=False)
        state, end = sup.run(n_steps)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    assert end == n_steps and sup.n_workers == n0 - 1
    print("events:", [(e.step, e.kind) for e in sup.events])

    # straggler streak -> re-scored rotation past the slow worker
    stragglers = sup.events_of("straggler")
    assert stragglers and stragglers[0].detail["worker"] == 2
    rotations = sup.events_of("rotate")
    assert len(rotations) == 1, rotations
    assert rotations[0].detail["g0"] == 3
    assert rotations[0].detail["worker"] == 2

    # dead worker -> elastic re-plan to the survivors + restore
    replans = sup.events_of("replan")
    assert len(replans) == 1 and replans[0].detail["n_workers"] == 3
    assert replans[0].detail["n_microbatches"] == 3
    assert replans[0].detail["async_ok"]
    restores = sup.events_of("restore")
    assert len(restores) == 1, restores
    assert restores[0].detail["resumed_at"] == 4, restores

    # deterministic replay: step 4 ran twice (N=4 pre-fault, N=3 replay)
    # on the SAME (seed, step)-pure batch — the losses must agree
    assert len(losses[4]) == 2, {t: len(v) for t, v in losses.items()}
    np.testing.assert_allclose(losses[4][1], losses[4][0], rtol=1e-4)

    # goodput ledger: overhead charged, productive time dominates
    rep = sup.meter.report()
    print("goodput ledger:", {k: round(v, 4) for k, v in rep.items()})
    assert 0.0 < rep["goodput"] < 1.0, rep
    assert rep["replay_s"] > 0.0 and rep["replan_s"] > 0.0, rep

    # final params vs the UNINTERRUPTED N=4 reference trajectory: the
    # whole chaos sequence (rotation rebuild, topology change, elastic
    # re-pad, replay) must land on the same training trajectory
    ref_step, ref_sh, _, _, _ = build(n0, 0, None)
    ref_state = fresh_train_state(params, cfg, n0, ref_sh, ocfg)
    with mesh:
        for t in range(n_steps):
            ref_state, _ = ref_step(ref_state, data_for(t))

    def real_params(st):
        return {k: (jax.tree.map(lambda a: a[:cfg.n_layers], v)
                    if k == "layers" else v)
                for k, v in st["params"].items()}

    worst = worst_rel_tree(real_params(ref_state), real_params(state),
                           label="chaos")
    print("worst rel param err vs uninterrupted N=4 reference:", worst)
    assert worst < 5e-3, worst
    print("ROUNDPIPE_DISPATCH_OK")


def run_async(cfg, mesh, plan, params, b, s):
    """Cross-step staleness-1 equivalence (ISSUE 5 tentpole).

    For (rounds, steps, prefetch) in {(1, 3, off), (2, 2, on)}: the chained
    ring program of ``build_roundpipe_async_train_step`` — I optimizer
    steps in ``I*R*S + N - 1`` ticks, in-program updates at each step's
    deposit-complete tick — must land per-leaf allclose on
    ``reference_staleness1``'s final weights and per-step losses, and must
    be DISTINGUISHABLE from the synchronous (staleness-0) trajectory.
    ``overlap=False`` must be bit-identical to looping the PR-4
    synchronous step.  The threaded ``HostAsyncRoundPipe`` worker (the
    five per-layer §4.3 constraints around the real dispatch grads_fn)
    must reproduce the same staleness-1 trajectory.
    """
    import functools

    from repro.core.consistency import reference_staleness1
    from repro.core.dispatch import (build_roundpipe_async_train_step,
                                     build_roundpipe_train_step)
    from repro.launch.steps import StepConfig
    from repro.optim import OptConfig, init_opt_state
    from repro.optim.adam import apply_updates
    from repro.optim.async_opt import HostAsyncRoundPipe

    n = plan.n_workers
    ocfg = OptConfig(lr=1e-2)            # big enough that staleness shows
    key = jax.random.PRNGKey(7)

    # shallow plans (sf < N-1) overlap step k+1's fused work with step k's
    # drain — the regime the parity-paired accumulators exist for; the
    # full extras (overlap=False bit-identity, threaded worker) only run
    # on the deep plan to bound compile time
    shallow = plan.n_fwd < n - 1
    configs = ((1, 3, True),) if shallow else ((1, 3, False), (2, 2, True))
    for rounds, steps, prefetch in configs:
        m = rounds * n
        kb = jax.random.fold_in(key, rounds)
        batches = {
            "tokens": jax.random.randint(kb, (steps, b, s), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.fold_in(kb, 1),
                                         (steps, b, s), 0, cfg.vocab_size)}

        # the chained order IS the cross-step tick table, the schedule
        # generator dispatches it identically (iterations > 1, g0
        # advancing), and the generated IR round-trips
        check_tick_order(plan, rounds, iterations=steps)

        # ---- staleness-1 oracle (the whole net as one protocol layer) ------
        def batch_of(t):
            return jax.tree.map(lambda x: x[t], batches)

        loss_of = functools.partial(T.loss_fn, cfg=cfg, remat=False,
                                    xent_chunk=8, kv_chunk=8)
        ref_losses = []
        opt_cell = {"opt": init_opt_state(params, ocfg)}

        def device_fn(weights, t):
            loss, grads = jax.value_and_grad(
                lambda p: loss_of(p, batch_of(t)))(weights[0])
            ref_losses.append(float(loss))
            return [grads]

        def optimizer_fn(opt_w, staged, t):
            new_p, opt_cell["opt"], _ = apply_updates(
                opt_cell["opt"], staged[0], ocfg, param_like=params)
            return [new_p]

        ref_final = reference_staleness1(1, device_fn, optimizer_fn,
                                         [params], steps)[0]

        # staleness-0 (synchronous) oracle, for distinguishability
        sync_losses = []
        p_sync, opt_sync = params, init_opt_state(params, ocfg)
        for t in range(steps):
            loss, grads = jax.value_and_grad(
                lambda p: loss_of(p, batch_of(t)))(p_sync)
            sync_losses.append(float(loss))
            p_sync, opt_sync, _ = apply_updates(opt_sync, grads, ocfg,
                                                param_like=params)

        # ---- the chained program -------------------------------------------
        step_cfg = StepConfig(strategy="roundpipe", grad_accum=1,
                              partition=plan, n_microbatches=m,
                              prefetch=prefetch, kv_chunk=8, xent_chunk=8,
                              opt=ocfg)
        multi, state_sh, _, _ = build_roundpipe_async_train_step(
            cfg, mesh, step_cfg, b, s, steps_per_call=steps, plan=plan)
        state0 = fresh_train_state(params, cfg, n, state_sh, ocfg)
        with mesh:
            state1, metrics = multi(state0, batches)
        got = {k: (jax.tree.map(lambda a: a[:cfg.n_layers], v)
                   if k == "layers" else v)
               for k, v in state1["params"].items()}

        err_s1 = worst_rel_tree(ref_final, got)
        err_s0 = worst_rel_tree(p_sync, got)
        sep = worst_rel_tree(p_sync, ref_final)
        print(f"R={rounds} I={steps} prefetch={prefetch}: "
              f"err vs staleness-1 {err_s1:.2e}, vs staleness-0 {err_s0:.2e} "
              f"(oracle separation {sep:.2e})")
        np.testing.assert_allclose(np.asarray(metrics["loss"]),
                                   np.asarray(ref_losses), rtol=1e-4)
        assert err_s1 < 5e-3, err_s1
        assert sep > 10 * max(err_s1, 1e-9), (sep, err_s1)
        assert err_s0 > 5 * err_s1, (err_s0, err_s1)
        assert int(metrics["step"]) == steps

        # ---- overlap disabled == PR-4 synchronous loop, bitwise -------------
        if rounds == 1 and not shallow:
            nool, state_sh2, _, _ = build_roundpipe_async_train_step(
                cfg, mesh, step_cfg, b, s, steps_per_call=steps, plan=plan,
                overlap=False)
            s_a = fresh_train_state(params, cfg, n, state_sh2, ocfg)
            with mesh:
                s_a, m_a = nool(s_a, batches)
            sync_step, state_sh3, _, _ = build_roundpipe_train_step(
                cfg, mesh, step_cfg, b, s, plan=plan)
            s_b = fresh_train_state(params, cfg, n, state_sh3, ocfg)
            with mesh:
                for t in range(steps):
                    s_b, _ = sync_step(s_b, batch_of(t))
            assert_trees_equal(s_a["params"], s_b["params"],
                               "overlap=False not bit-identical to the "
                               "synchronous loop")
            print("overlap=False bit-identical to the synchronous PR-4 loop")

        # ---- threaded host worker: the five per-layer constraints ----------
        if rounds == 1 and not shallow:
            from repro.core.dispatch import build_roundpipe_grads_fn
            grads_fn = build_roundpipe_grads_fn(cfg, mesh, plan, xent_chunk=8,
                                                kv_chunk=8)
            with mesh:
                jfn = jax.jit(grads_fn)
                jfn(params, batch_of(0))     # compile on the main thread
            host = HostAsyncRoundPipe(
                lambda p, bt: jfn(p, bt), params, ocfg,
                [batch_of(t) for t in range(steps)], mesh=mesh)
            host_final = host.train(steps)
            err_host = worst_rel_tree(ref_final, host_final)
            print(f"threaded host worker err vs staleness-1: {err_host:.2e}")
            assert err_host < 5e-3, err_host
            np.testing.assert_allclose(np.asarray(host.losses),
                                       np.asarray(ref_losses), rtol=1e-4)
    print("ROUNDPIPE_DISPATCH_OK")


def _dequantize_pool(layers_tree, bits):
    """What the dispatch runtime's quantize->ship->dequant round trip does
    to the pool, replicated host-side: per layer row, flatten + concat the
    leaves (dispatch's pool_cat layout — blocks SPAN leaf boundaries),
    blockwise-absmax quantize, fused dequant, split back."""
    from repro.kernels import ops as kops
    from repro.kernels.dequant import quantize_rows

    leaves, tdef = jax.tree_util.tree_flatten(layers_tree)
    rows = leaves[0].shape[0]
    cat = jnp.concatenate(
        [l.reshape(rows, -1).astype(jnp.float32) for l in leaves], axis=1)
    codes, scales = quantize_rows(cat, bits=bits)
    flat = kops.dequant_rows(codes, scales)[:, :cat.shape[1]]
    out, off = [], 0
    for l in leaves:
        ne = int(np.prod(l.shape[1:]))
        out.append(flat[:, off:off + ne].reshape(l.shape).astype(l.dtype))
        off += ne
    return jax.tree_util.tree_unflatten(tdef, out)


def run_quant(cfg, mesh, plan, params, batch, b, s):
    """Quantized resident pool + error-feedback deposits (ISSUE 6 tentpole).

    * byte accounting: the int8 / int4 plans' stage upload budgets shrink
      to the code+scale payload (~0.508x / ~0.258x of the dense bf16
      bytes on body stages; the replicated LM head stays dense)
    * int8 ring vs the single-program reference on the int8-DEQUANTIZED
      weights: tight (the ring is bit-faithful to deq(quant(W))), plus a
      quantization-tolerance check against the fp32 reference
    * chunked code+scale prefetch (forced row splits) vs the whole-block
      quant gather: BIT-identical standby reassembly
    * int4 frozen-base LoRA vs merged-dense references on the dequantized
      base (tight) and the fp32 base (tolerance)
    * grad_compress="int8": single-shot deposits stay within the codec's
      worst-case bar, and the K-step mean with the carried residual
      converges BELOW the single-shot error (the error-feedback property)
    """
    from repro.core.partition import quant_upload_bytes
    from repro.models import lora

    n = plan.n_workers

    # ---- plan byte accounting ----------------------------------------------
    q8_plan = plan_from_config(cfg, n, pool_dtype="int8")
    q4_plan = plan_from_config(cfg, n, lora=LORA_CFG, pool_dtype="int4")
    dense_up = sum(plan.stage_bytes)
    q8_up = sum(q8_plan.stage_bytes)
    q4_up = sum(q4_plan.stage_bytes)
    assert 0 < q4_up < q8_up < dense_up, (q4_up, q8_up, dense_up)
    body = int(plan.layer_costs[0].weight_bytes)
    assert int(q8_plan.layer_costs[0].upload_stream_bytes) == \
        quant_upload_bytes(body // 2, "int8")
    print(f"upload bytes/step: dense {dense_up}  int8 {q8_up} "
          f"({q8_up / dense_up:.3f}x)  int4 {q4_up} ({q4_up / dense_up:.3f}x)")

    # ---- int8 ring vs dequantized-weights reference (tight) ----------------
    params_dq8 = dict(params, layers=_dequantize_pool(params["layers"], 8))

    def ref_loss8(p):
        return T.loss_fn(p, batch, cfg, remat=False, xent_chunk=8, kv_chunk=8)

    dq_l, dq_g = jax.value_and_grad(ref_loss8)(params_dq8)
    fp_l, fp_g = jax.value_and_grad(ref_loss8)(params)

    qfn = build_roundpipe_grads_fn(cfg, mesh, q8_plan, xent_chunk=8,
                                   kv_chunk=8, pool_dtype="int8")
    with mesh:
        q_g, q_loss, q_tokens = jax.jit(qfn)(params, batch)
    assert int(q_tokens) == b * s
    np.testing.assert_allclose(float(q_loss), float(dq_l), rtol=1e-4)
    tight = worst_rel_tree(dq_g, q_g)
    print(f"int8 ring vs dequantized-weights reference: worst rel {tight:.2e}")
    assert tight < 5e-3, tight
    # quantization-tolerance bar vs the fp32 reference (DESIGN.md §7)
    loose = worst_rel_tree(fp_g, q_g)
    print(f"int8 ring vs fp32 reference: worst rel {loose:.2e} "
          f"(loss {float(q_loss):.6f} vs {float(fp_l):.6f})")
    np.testing.assert_allclose(float(q_loss), float(fp_l), rtol=5e-2)
    assert loose < 0.25, loose

    # ---- chunked code+scale prefetch == whole-block quant gather, bitwise --
    biggest = max(int(c.upload_stream_bytes)
                  for c in q8_plan.layer_costs[:q8_plan.n_layers])
    program = q8_plan.prefetch_program(chunk_limit=max(1, biggest // 3))
    n_chunks = sum(1 for t in program.uploads for cu in t if cu.row >= 0)
    assert n_chunks > q8_plan.n_layers, "row chunk splitting did not engage"
    pf_fn = build_roundpipe_grads_fn(cfg, mesh, q8_plan, xent_chunk=8,
                                     kv_chunk=8, pool_dtype="int8",
                                     prefetch_program=program)
    with mesh:
        pf_g, pf_loss, _ = jax.jit(pf_fn)(params, batch)
    assert np.asarray(pf_loss).tobytes() == np.asarray(q_loss).tobytes()
    for (ka, va), (kb, vb) in zip(
            jax.tree_util.tree_flatten_with_path(q_g)[0],
            jax.tree_util.tree_flatten_with_path(pf_g)[0]):
        assert ka == kb
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb),
            err_msg=f"quant prefetch not bit-identical to whole-block at "
                    f"{jax.tree_util.keystr(ka)}")
    print(f"quant prefetch bit-identical to whole-block "
          f"({n_chunks} code-chunk uploads)")

    # ---- int4 frozen-base LoRA ---------------------------------------------
    adapters = lora.init_adapters(jax.random.PRNGKey(3), params["layers"],
                                  LORA_CFG, dtype=jnp.float32)
    adapters = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(4), a.shape, a.dtype)
        * 0.05, adapters)
    params_dq4 = dict(params, layers=_dequantize_pool(params["layers"], 4))

    def lora_ref(base):
        def f(ad):
            merged = lora.merge_params(base, ad, LORA_CFG)
            return T.loss_fn(merged, batch, cfg, remat=False, xent_chunk=8,
                             kv_chunk=8)
        return jax.value_and_grad(f)(adapters)

    dq4_l, dq4_g = lora_ref(params_dq4)
    fp4_l, fp4_g = lora_ref(params)
    l4fn = build_roundpipe_grads_fn(cfg, mesh, q4_plan, xent_chunk=8,
                                    kv_chunk=8, lora=LORA_CFG,
                                    pool_dtype="int4")
    with mesh:
        l4_g, l4_loss, _ = jax.jit(l4fn)(dict(params, lora=adapters), batch)
    assert set(l4_g) == {"lora"}, set(l4_g)
    np.testing.assert_allclose(float(l4_loss), float(dq4_l), rtol=1e-4)
    tight4 = worst_rel_tree(dq4_g, l4_g["lora"])
    print(f"int4 LoRA ring vs dequantized-base reference: "
          f"worst rel {tight4:.2e}")
    assert tight4 < 5e-3, tight4
    # tolerance vs the fp32 base is dominated by how well the BASE weights
    # quantize (random smoke init is the worst case — real checkpoints are
    # far smoother): the binding check is the loss bar; the adapter-grad
    # gap is printed for the record with only a sanity ceiling
    loose4 = worst_rel_tree(fp4_g, l4_g["lora"])
    print(f"int4 LoRA ring vs fp32-base reference: worst rel {loose4:.2e} "
          f"(loss {float(l4_loss):.6f} vs {float(fp4_l):.6f})")
    np.testing.assert_allclose(float(l4_loss), float(fp4_l), rtol=1e-1)
    assert loose4 < 2.5, loose4

    # ---- error-feedback compressed deposits --------------------------------
    exact_fn = build_roundpipe_grads_fn(cfg, mesh, plan, xent_chunk=8,
                                        kv_chunk=8)
    cfn = build_roundpipe_grads_fn(cfg, mesh, plan, xent_chunk=8, kv_chunk=8,
                                   grad_compress="int8")
    with mesh:
        ex_g, ex_loss, _ = jax.jit(exact_fn)(params, batch)
        jcfn = jax.jit(cfn)
        residual = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params["layers"])
        sums, k_steps = None, 4
        for _ in range(k_steps):
            c_g, c_loss, _, residual = jcfn(params, batch, residual)
            sums = c_g if sums is None else jax.tree.map(
                jnp.add, sums, c_g)
            if sums is c_g:
                first_err = worst_rel_tree(ex_g["layers"], c_g["layers"])
    mean_g = jax.tree.map(lambda a: a / k_steps, sums)
    mean_err = worst_rel_tree(ex_g["layers"], mean_g["layers"])
    # forward compute is untouched: deposits happen after the loss
    assert np.asarray(c_loss).tobytes() == np.asarray(ex_loss).tobytes()
    # replicated grads never cross the down lane, so they see no codec
    # error — but the compressed build is a structurally different XLA
    # program (extra residual I/O, three deposit hops, quantize ops), so
    # fusion/scheduling may reorder their independent float math by last
    # bits.  Hold them to reassociation-level tolerance, not bit equality.
    rep_err = max(worst_rel_tree(ex_g[k], c_g[k])
                  for k in ("embed", "final_norm"))
    assert rep_err < 1e-5, rep_err
    res_norm = float(sum(
        jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(residual)))
    print(f"compressed deposits: single-shot worst rel {first_err:.2e}, "
          f"{k_steps}-step mean {mean_err:.2e}, residual L1 {res_norm:.3e}")
    assert first_err < 8e-3, first_err           # int8 codec worst case
    assert mean_err < first_err / 2, (mean_err, first_err)
    assert res_norm > 0.0

    # ---- quant pool + compressed deposits compose --------------------------
    qc_fn = build_roundpipe_grads_fn(cfg, mesh, q8_plan, xent_chunk=8,
                                     kv_chunk=8, pool_dtype="int8",
                                     grad_compress="int8")
    residual = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params["layers"])
    with mesh:
        qc_g, qc_loss, _, residual = jax.jit(qc_fn)(params, batch, residual)
    assert np.asarray(qc_loss).tobytes() == np.asarray(q_loss).tobytes()
    both = worst_rel_tree(dq_g["layers"], qc_g["layers"])
    print(f"int8 pool + int8 deposits vs dequantized reference: "
          f"worst rel {both:.2e}")
    assert both < 1.5e-2, both
    print("ROUNDPIPE_DISPATCH_OK")


def run_async_quant(cfg, mesh, plan, params, b, s):
    """Quantized pool + compressed deposits UNDER the cross-step chained
    program (the satellite that lifts the launcher's sync-only refusal).

    * int8 resident pool, staleness-1 chain: every injection dequantizes
      the CURRENT pool version (requantized in-program at each step's
      update tick), so the chain must land tightly on the staleness-1
      oracle whose device grads are taken at the int8-DEQUANTIZED pool —
      a runtime that skipped requantization (or injected the exact fp32
      pool) would miss this bar by the quantization noise (~0.25 here)
    * the trajectory must separate from the staleness-0 (synchronous)
      dequantized oracle, same distinguishability bars as ``async``
    * grad_compress="int8" threads the error-feedback residual through
      ``state["opt"]["grad_residual"]`` ACROSS the chained steps: step-0
      loss matches the uncompressed chain (forward untouched), the
      returned residual is nonzero, and the final weights stay within
      codec tolerance of the uncompressed chain
    """
    import functools

    from repro.core.consistency import reference_staleness1
    from repro.core.dispatch import (build_roundpipe_async_train_step,
                                     pad_pool)
    from repro.launch.steps import StepConfig
    from repro.optim import OptConfig, init_opt_state
    from repro.optim.adam import apply_updates

    n = plan.n_workers
    ocfg = OptConfig(lr=1e-2)            # big enough that staleness shows
    rounds, steps, prefetch = 1, 3, True
    m = rounds * n
    q8_plan = plan_from_config(cfg, n, pool_dtype="int8")
    check_tick_order(q8_plan, rounds, iterations=steps)

    kb = jax.random.fold_in(jax.random.PRNGKey(7), 1)
    batches = make_batch(kb, cfg, b, s, steps=steps)

    def batch_of(t):
        return jax.tree.map(lambda x: x[t], batches)

    loss_of = functools.partial(T.loss_fn, cfg=cfg, remat=False,
                                xent_chunk=8, kv_chunk=8)

    def dq(p):
        return dict(p, layers=_dequantize_pool(p["layers"], 8))

    # ---- staleness-1 oracle at the dequantized pool ------------------------
    ref_losses = []
    opt_cell = {"opt": init_opt_state(params, ocfg)}

    def device_fn(weights, t):
        loss, grads = jax.value_and_grad(
            lambda p: loss_of(p, batch_of(t)))(dq(weights[0]))
        ref_losses.append(float(loss))
        return [grads]

    def optimizer_fn(opt_w, staged, t):
        new_p, opt_cell["opt"], _ = apply_updates(
            opt_cell["opt"], staged[0], ocfg, param_like=params)
        return [new_p]

    ref_final = reference_staleness1(1, device_fn, optimizer_fn,
                                     [params], steps)[0]

    # staleness-0 oracle (same dequantized device grads), for separation
    p_sync, opt_sync = params, init_opt_state(params, ocfg)
    for t in range(steps):
        _, grads = jax.value_and_grad(
            lambda p: loss_of(p, batch_of(t)))(dq(p_sync))
        p_sync, opt_sync, _ = apply_updates(opt_sync, grads, ocfg,
                                            param_like=params)

    # ---- the int8-pool chained program -------------------------------------
    step_cfg = StepConfig(strategy="roundpipe", grad_accum=1,
                          partition=q8_plan, n_microbatches=m,
                          prefetch=prefetch, kv_chunk=8, xent_chunk=8,
                          pool_dtype="int8", opt=ocfg)
    multi, state_sh, _, _ = build_roundpipe_async_train_step(
        cfg, mesh, step_cfg, b, s, steps_per_call=steps, plan=q8_plan)
    state0 = fresh_train_state(params, cfg, n, state_sh, ocfg)
    with mesh:
        state1, metrics = multi(state0, batches)
    got = {k: (jax.tree.map(lambda a: a[:cfg.n_layers], v)
               if k == "layers" else v)
           for k, v in state1["params"].items()}

    err_s1 = worst_rel_tree(ref_final, got)
    err_s0 = worst_rel_tree(p_sync, got)
    sep = worst_rel_tree(p_sync, ref_final)
    print(f"int8 pool R={rounds} I={steps} prefetch={prefetch}: err vs "
          f"dequantized staleness-1 {err_s1:.2e}, vs staleness-0 "
          f"{err_s0:.2e} (oracle separation {sep:.2e})")
    np.testing.assert_allclose(np.asarray(metrics["loss"]),
                               np.asarray(ref_losses), rtol=1e-4)
    assert err_s1 < 5e-3, err_s1
    assert sep > 10 * max(err_s1, 1e-9), (sep, err_s1)
    assert err_s0 > 5 * err_s1, (err_s0, err_s1)
    assert int(metrics["step"]) == steps

    # ---- + error-feedback compressed deposits across the chain -------------
    step_cfg_c = dataclasses.replace(step_cfg, grad_compress="int8")
    multi_c, state_sh_c, _, _ = build_roundpipe_async_train_step(
        cfg, mesh, step_cfg_c, b, s, steps_per_call=steps, plan=q8_plan)
    padded = jax.tree.map(lambda x: jnp.array(x, copy=True),
                          pad_pool(params, cfg, n))
    opt_c = dict(init_opt_state(padded, ocfg),
                 grad_residual=jax.tree.map(
                     lambda a: jnp.zeros(a.shape, jnp.float32),
                     padded["layers"]))
    state_c0 = jax.device_put({"params": padded, "opt": opt_c}, state_sh_c)
    with mesh:
        state_c1, metrics_c = multi_c(state_c0, batches)

    # forward compute untouched at step 0: deposits land after the loss
    np.testing.assert_allclose(float(np.asarray(metrics_c["loss"])[0]),
                               float(np.asarray(metrics["loss"])[0]),
                               rtol=1e-6)
    res_norm = float(sum(
        jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(
            state_c1["opt"]["grad_residual"])))
    assert res_norm > 0.0, "error-feedback residual never accumulated"
    got_c = {k: (jax.tree.map(lambda a: a[:cfg.n_layers], v)
                 if k == "layers" else v)
             for k, v in state_c1["params"].items()}
    codec_drift = worst_rel_tree(got, got_c)
    print(f"int8 pool + int8 deposits: final-weight drift vs uncompressed "
          f"chain {codec_drift:.2e}, residual L1 {res_norm:.3e}")
    assert codec_drift < 2e-2, codec_drift
    err_c = worst_rel_tree(ref_final, got_c)
    print(f"compressed chain err vs dequantized staleness-1: {err_c:.2e}")
    assert err_c < 2.5e-2, err_c
    print("ROUNDPIPE_DISPATCH_OK")


def run_async_lora(cfg, mesh, plan, params, b, s):
    """Cross-step staleness-1 async optimizer with a FROZEN base (satellite
    of ISSUE 6): the dense pool is read-only for the whole chained program
    — there is no cross-step dense-weight staleness, which is exactly why
    the launcher's --async-opt + --lora-rank refusal could be lifted — and
    only the adapter ring versions staleness-1.  The final adapter pool
    must allclose ``reference_staleness1`` restricted to the adapters, the
    dense pool must come back BIT-identical, and the trajectory must
    separate from the staleness-0 (synchronous) oracle."""
    import functools

    from repro.core.consistency import reference_staleness1
    from repro.core.dispatch import (build_roundpipe_async_train_step,
                                     pad_pool)
    from repro.launch.steps import StepConfig
    from repro.models import lora as lora_mod
    from repro.optim import OptConfig, init_opt_state
    from repro.optim.adam import apply_updates

    n = plan.n_workers
    ocfg = OptConfig(lr=1e-2)            # big enough that staleness shows
    key = jax.random.PRNGKey(7)
    lcfg = LORA_CFG

    adapters = make_adapters(params)
    params_l = dict(params, lora=adapters)

    for rounds, steps, prefetch in ((1, 3, False), (2, 2, True)):
        m = rounds * n
        kb = jax.random.fold_in(key, rounds)
        batches = {
            "tokens": jax.random.randint(kb, (steps, b, s), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.fold_in(kb, 1),
                                         (steps, b, s), 0, cfg.vocab_size)}

        def batch_of(t):
            return jax.tree.map(lambda x: x[t], batches)

        def loss_of(ad, t):
            merged = lora_mod.merge_params(params, ad, lcfg)
            return T.loss_fn(merged, batch_of(t), cfg, remat=False,
                             xent_chunk=8, kv_chunk=8)

        # ---- staleness-1 oracle over the adapters only ---------------------
        ref_losses = []
        opt_cell = {"opt": init_opt_state(adapters, ocfg)}

        def device_fn(weights, t):
            loss, grads = jax.value_and_grad(
                functools.partial(loss_of, t=t))(weights[0])
            ref_losses.append(float(loss))
            return [grads]

        def optimizer_fn(opt_w, staged, t):
            new_a, opt_cell["opt"], _ = apply_updates(
                opt_cell["opt"], staged[0], ocfg, param_like=adapters)
            return [new_a]

        ref_final = reference_staleness1(1, device_fn, optimizer_fn,
                                         [adapters], steps)[0]

        # staleness-0 oracle, for distinguishability
        a_sync, opt_sync = adapters, init_opt_state(adapters, ocfg)
        for t in range(steps):
            _, grads = jax.value_and_grad(
                functools.partial(loss_of, t=t))(a_sync)
            a_sync, opt_sync, _ = apply_updates(opt_sync, grads, ocfg,
                                                param_like=adapters)

        # ---- the chained frozen-base program -------------------------------
        step_cfg = StepConfig(strategy="roundpipe", grad_accum=1,
                              partition=plan, n_microbatches=m,
                              prefetch=prefetch, kv_chunk=8, xent_chunk=8,
                              lora=lcfg, opt=ocfg)
        multi, state_sh, _, _ = build_roundpipe_async_train_step(
            cfg, mesh, step_cfg, b, s, steps_per_call=steps, plan=plan)
        state0 = fresh_train_state(params_l, cfg, n, state_sh, ocfg,
                                   lora=True)
        with mesh:
            state1, metrics = multi(state0, batches)

        # frozen base: the dense pool and replicated params are READ-ONLY
        p0 = pad_pool(params_l, cfg, n)
        for name in ("layers", "embed", "final_norm"):
            if name not in state1["params"]:
                continue
            assert_trees_equal(p0[name], state1["params"][name],
                               f"frozen {name} mutated")

        got = jax.tree.map(lambda a: a[:cfg.n_layers],
                           state1["params"]["lora"])
        err_s1 = worst_rel_tree(ref_final, got)
        err_s0 = worst_rel_tree(a_sync, got)
        sep = worst_rel_tree(a_sync, ref_final)
        print(f"R={rounds} I={steps} prefetch={prefetch}: adapter err vs "
              f"staleness-1 {err_s1:.2e}, vs staleness-0 {err_s0:.2e} "
              f"(oracle separation {sep:.2e})")
        np.testing.assert_allclose(np.asarray(metrics["loss"]),
                                   np.asarray(ref_losses), rtol=1e-4)
        assert err_s1 < 5e-3, err_s1
        assert sep > 10 * max(err_s1, 1e-9), (sep, err_s1)
        assert err_s0 > 5 * err_s1, (err_s0, err_s1)
        assert int(metrics["step"]) == steps
    print("ROUNDPIPE_DISPATCH_OK")


def run_lora(cfg, mesh, plan, params, batch, b, s):
    """Frozen-base equivalence: one LoRA RoundPipe step vs the merged-dense
    single-program reference differentiated through the adapters only."""
    from repro.models import lora

    lcfg = LORA_CFG
    adapters = lora.init_adapters(jax.random.PRNGKey(3), params["layers"],
                                  lcfg, dtype=jnp.float32)
    # randomize B away from its zero init so BOTH factors carry nonzero
    # gradients (zero B would make every A-grad trivially zero)
    adapters = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(4), a.shape, a.dtype)
        * 0.05, adapters)

    # split byte accounting: the LoRA plan downloads strictly less than the
    # full-fine-tune plan built from the same architecture
    full_plan = plan_from_config(cfg, plan.n_workers)
    lora_down = sum(plan.stage_download_bytes)
    full_down = sum(full_plan.stage_download_bytes)
    assert 0 < lora_down < full_down, (lora_down, full_down)
    assert plan.stage_bytes == full_plan.stage_bytes  # uploads stay dense
    print(f"download bytes/step: lora {lora_down} < full {full_down}")

    # ---- merged-dense reference: W + (alpha/r) B@A folded in ---------------
    def ref_loss(ad):
        merged = lora.merge_params(params, ad, lcfg)
        return T.loss_fn(merged, batch, cfg, remat=False, xent_chunk=8,
                         kv_chunk=8)

    ref_l, ref_g = jax.value_and_grad(ref_loss)(adapters)

    # ---- frozen-base ring ---------------------------------------------------
    grads_fn = build_roundpipe_grads_fn(cfg, mesh, plan, xent_chunk=8,
                                        kv_chunk=8, lora=lcfg)
    with mesh:
        rp_g, rp_loss, rp_tokens = jax.jit(grads_fn)(
            dict(params, lora=adapters), batch)

    # base grads are ABSENT from the deposited pytree: adapter leaves only
    assert set(rp_g) == {"lora"}, set(rp_g)
    assert jax.tree_util.tree_structure(rp_g["lora"]) == \
        jax.tree_util.tree_structure(adapters)

    print("ref loss", float(ref_l), "rp loss", float(rp_loss))
    np.testing.assert_allclose(float(rp_loss), float(ref_l), rtol=1e-4)
    assert int(rp_tokens) == b * s

    worst = 0.0
    for (ka, va), (kb, vb) in zip(
            jax.tree_util.tree_flatten_with_path(ref_g)[0],
            jax.tree_util.tree_flatten_with_path(rp_g["lora"])[0]):
        assert ka == kb
        rv = np.asarray(va, np.float32)
        gv = np.asarray(vb, np.float32)
        assert np.abs(rv).max() > 0, ("degenerate zero reference grad",
                                      jax.tree_util.keystr(ka))
        err = np.abs(gv - rv).max() / (np.abs(rv).max() + 1e-6)
        worst = max(worst, err)
        if err > 5e-3:
            print("MISMATCH", jax.tree_util.keystr(ka), err)
    print("worst rel adapter grad err:", worst)
    assert worst < 5e-3, worst
    print("ROUNDPIPE_DISPATCH_OK")


if __name__ == "__main__":
    main()
