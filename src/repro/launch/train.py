"""Training launcher: config-driven, fault-tolerant, checkpointed.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \\
      --steps 300 --batch 16 --seq 128 --mesh 1x1 [--strategy roundpipe] \\
      [--microbatches 16] [--ckpt-dir /tmp/ckpt --ckpt-every 50]

(--microbatches M requires --batch divisible by M: each of the R = M/N
rounds feeds micro-batches of global_batch/M samples.)

On a real pod this runs under ``jax.distributed.initialize`` with the
production mesh; on this host it runs any reduced config end-to-end.

Checkpointing goes through the atomic writer in ``repro.checkpoint``
(write-to-tmp + manifest-last rename): ``--ckpt-every`` steps the live
state is saved under ``--ckpt-dir``, and on startup the newest manifest
is restored — step counter included — so an interrupted run resumes
bit-identically to an uninterrupted one (``tests/test_train_resume.py``).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
    else ``<checkout>/.jax_cache``.  Call before the first compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--strategy", default="gspmd",
                    choices=["gspmd", "roundpipe"])
    ap.add_argument("--partition", default="auto",
                    choices=["auto", "uniform"],
                    help="roundpipe stage split: cost-model auto-partition "
                         "(paper §4.4, uneven stages + LM-head stage) or the "
                         "degenerate 1-layer-per-stage split")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="roundpipe only: micro-batches per step M = R*N; "
                         "R > 1 stitches R rounds back-to-back per optimizer "
                         "step (paper §3.2 steady state), accumulating "
                         "gradients across rounds.  0 -> one round (M = N)")
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="roundpipe only: >0 enables frozen-base LoRA "
                         "fine-tuning at this adapter rank")
    ap.add_argument("--lora-alpha", type=float, default=16.0)
    ap.add_argument("--lora-targets", default="attn,mlp",
                    help="comma-separated module paths the adapters decorate")
    ap.add_argument("--pool-dtype", default="none",
                    choices=["none", "int8", "int4"],
                    help="roundpipe only: stream the resident pool QUANTIZED "
                         "(blockwise-absmax codes + fp32 scales, fused "
                         "dequant-on-upload at promote time).  Host master "
                         "weights stay full precision; int4 targets the "
                         "frozen-base LoRA pool.  Composes with --async-opt "
                         "(each staleness-1 version requantizes at its "
                         "update tick)")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8"],
                    help="roundpipe only: int8 error-feedback compressed "
                         "gradient deposits (optim/compress.py); the "
                         "residual rides in the optimizer state.  Composes "
                         "with --async-opt (the residual threads across "
                         "the chained steps)")
    ap.add_argument("--schedule", default="hand",
                    choices=["hand", "searched"],
                    help="roundpipe only: tick-program selector.  'hand' "
                         "executes the canonical generated plan.tick_program;"
                         " 'searched' scores the schedule family (injection "
                         "rotation, lane policy, standby residency) with "
                         "simulate_plan and executes the certified winner — "
                         "never a higher simulated bubble than 'hand'")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", "--save-every", type=int, default=50,
                    dest="ckpt_every",
                    help="save an atomic checkpoint every K steps; startup "
                         "always resumes from the newest one in --ckpt-dir")
    ap.add_argument("--async-opt", action="store_true",
                    help="staleness-1 host optimizer (paper §4.3): under "
                         "gspmd the update of the PENDING grads overlaps the "
                         "current step inside one program; under roundpipe "
                         "--async-steps optimizer steps chain back-to-back "
                         "in one ring program (fill/drain paid once per "
                         "chain).  Errors for strategies that cannot "
                         "support it.  Combines with --lora-rank: the "
                         "frozen base makes the dense pool read-only, so "
                         "only the adapter ring versions staleness-1")
    ap.add_argument("--async-steps", type=int, default=4,
                    help="roundpipe + --async-opt only: optimizer steps "
                         "chained per program call (the I of the "
                         "(N-1)/(I*R*S+N-1) cross-step bubble); must "
                         "divide --steps")
    ap.add_argument("--elastic", action="store_true",
                    help="roundpipe only: run under the goodput supervisor "
                         "(runtime/supervisor.py).  A dead worker triggers a "
                         "re-plan onto the surviving N-1 (fresh "
                         "auto_partition, R = rounds_for(M')) and an elastic "
                         "restore from the newest checkpoint onto the "
                         "smaller mesh; a persistent straggler rotates the "
                         "schedule (g0) past the slow device.  Drives the "
                         "synchronous step (drop --async-opt)")
    ap.add_argument("--async-ckpt", action="store_true",
                    help="write checkpoints on a background thread: the "
                         "training loop pays only the device→host snapshot, "
                         "serialization + the atomic rename overlap the next "
                         "steps.  Crash-safe via the same manifest-last "
                         "protocol as the sync writer")
    ap.add_argument("--straggler-factor", type=float, default=2.0,
                    help="supervisor straggler threshold: a worker (or step) "
                         "slower than FACTOR x the median is flagged; under "
                         "--elastic a persistent per-worker straggler "
                         "triggers the g0 rotation mitigation")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--profile-dir", default=None,
                    help="trace the --profile-steps steps with jax.profiler "
                         "into DIR (TensorBoard / Perfetto): each step is an "
                         "rp.train_step, the device ops carry the step's "
                         "rp.* scopes, GC pauses show as rp.host.gc")
    ap.add_argument("--profile-steps", default="2:5",
                    help="A:B — the loop steps --profile-dir traces, A to "
                         "B-1 (chained calls under --async-opt)")
    return ap


def run_training(args, *, max_restarts: int = 3) -> dict:
    """The launcher body: build everything from ``args`` and train.

    Returns ``{"state", "losses", "steps", "resumed_from", "durations"}``
    so tests can drive the exact production wiring (checkpoint resume
    included) in-process; ``durations`` holds each step's wall seconds up
    to its finished state.  ``max_restarts`` bounds the fault-tolerant
    loop's checkpoint restarts (0: the first error propagates).
    """
    from repro.launch.mesh import request_host_devices
    n_data, n_model = (int(x) for x in args.mesh.split("x"))
    if n_data * n_model > 1:
        request_host_devices(n_data * n_model)

    import jax

    use_compile_cache()

    from repro.checkpoint import CheckpointManager, latest_step
    from repro.configs import smoke_config
    from repro.data import DataConfig, SyntheticLMDataset
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import (StepConfig, build_train_step,
                                    init_train_state)
    from repro.models.config import get_config
    from repro.optim import OptConfig
    from repro.runtime import FaultTolerantLoop, spans

    profile = None
    if args.profile_dir:
        try:
            profile = spans.ProfileWindow.parse(args.profile_dir,
                                                args.profile_steps)
        except ValueError as e:
            raise SystemExit(str(e)) from None
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = make_mesh((n_data, n_model), ("data", "model"))
    lora_cfg = None
    if args.lora_rank > 0:
        if args.strategy != "roundpipe":
            raise SystemExit("--lora-rank requires --strategy roundpipe")
        from repro.models.lora import LoraConfig
        lora_cfg = LoraConfig(
            rank=args.lora_rank, alpha=args.lora_alpha,
            target_modules=tuple(t.strip()
                                 for t in args.lora_targets.split(",")
                                 if t.strip()))
    microbatches = args.microbatches or None
    if microbatches is not None and args.strategy != "roundpipe":
        raise SystemExit("--microbatches requires --strategy roundpipe")
    # --async-opt routing (no more silent drop): every strategy either
    # supports the staleness-1 update or refuses it loudly
    async_rp = args.async_opt and args.strategy == "roundpipe"
    if args.async_opt and args.strategy not in ("gspmd", "roundpipe"):
        raise SystemExit(
            f"--async-opt is not supported under --strategy {args.strategy}: "
            f"the staleness-1 update needs either the gspmd in-step pending-"
            f"grad path or the roundpipe cross-step chained program")
    # --async-opt + --lora-rank is allowed: the frozen base never updates,
    # so the dense pool is read-only across the chain and only the adapter
    # ring needs staleness-1 versioning (proven against the staleness-1
    # LoRA oracle in roundpipe_subprocess.py async-lora)
    if args.pool_dtype != "none" and args.strategy != "roundpipe":
        raise SystemExit("--pool-dtype requires --strategy roundpipe")
    if args.grad_compress != "none" and args.strategy != "roundpipe":
        raise SystemExit("--grad-compress requires --strategy roundpipe")
    # --pool-dtype / --grad-compress compose with --async-opt: the chained
    # program requantizes each staleness-1 version at its D_T update tick
    # and threads the error-feedback residual across the whole chain
    # (proven in roundpipe_subprocess.py async-quant)
    if args.schedule != "hand" and args.strategy != "roundpipe":
        raise SystemExit("--schedule requires --strategy roundpipe")
    use_supervisor = args.elastic or args.async_ckpt
    if args.elastic and args.strategy != "roundpipe":
        raise SystemExit("--elastic requires --strategy roundpipe: elastic "
                         "re-planning re-runs the plan compiler for the "
                         "surviving workers")
    if use_supervisor and args.async_opt:
        # the supervisor tears the async chain down on every elastic replan
        # anyway (R*S < N-1 forces the sync fallback — DESIGN.md §9), so the
        # launcher wires it to the synchronous step only; the call-unit /
        # optimizer-unit checkpoint interplay of the chained program does
        # not survive a mid-run topology change
        raise SystemExit("--elastic/--async-ckpt drive the synchronous "
                         "step: drop --async-opt")
    if async_rp and args.async_steps < 1:
        raise SystemExit("--async-steps must be >= 1")
    if async_rp and args.steps % args.async_steps:
        lo = args.steps - args.steps % args.async_steps or args.async_steps
        hi = (args.steps // args.async_steps + 1) * args.async_steps
        raise SystemExit(
            f"--steps {args.steps} must be a multiple of --async-steps "
            f"{args.async_steps}: the chained program executes whole "
            f"chains — choose e.g. {lo} or {hi}")
    plan = None
    if args.strategy == "roundpipe":
        # compile the plan up front: the train step executes this exact
        # object, and the simulator reports its bubble before we spend flops
        from repro.core.plan import plan_from_config, uniform_partition
        from repro.core.simulator import simulate_plan
        if args.partition == "uniform":
            plan = plan_from_config(
                cfg, n_model, partition=uniform_partition(cfg.n_layers),
                lora=lora_cfg, pool_dtype=args.pool_dtype)
        else:
            plan = plan_from_config(cfg, n_model, lora=lora_cfg,
                                    pool_dtype=args.pool_dtype)
        m_sim = microbatches or n_model
        r_sim = plan.rounds_for(m_sim)
        sim = simulate_plan(plan, m_sim, round_size=n_model)
        print(plan.describe())
        print(f"simulated bubble ratio ({r_sim} round"
              f"{'s' if r_sim != 1 else ''}, M={m_sim}): "
              f"{sim.bubble_ratio:.4f}")
        if args.schedule == "searched":
            from repro.core.simulator import search_schedule
            sr = search_schedule(
                plan, m_sim, round_size=n_model,
                iterations=args.async_steps if async_rp else 1)
            print(f"searched schedule: '{sr.choice.name}' over "
                  f"{len(sr.scored)} candidates — simulated bubble "
                  f"{sr.bubble:.4f} (hand {sr.hand_bubble:.4f})")
        if async_rp:
            sim_async = simulate_plan(plan, m_sim, round_size=n_model,
                                      iterations=args.async_steps)
            print(f"simulated cross-step bubble "
                  f"({args.async_steps} chained steps, staleness-1): "
                  f"{sim_async.bubble_ratio:.4f}")
        if lora_cfg is not None:
            full = plan_from_config(cfg, n_model, partition=plan.partition)
            up = sum(plan.stage_bytes) * r_sim
            down = sum(plan.stage_download_bytes) * r_sim
            full_down = sum(full.stage_download_bytes) * r_sim
            print(f"LoRA r={lora_cfg.rank}: upload {up / 2**20:.1f} MiB/step, "
                  f"grad download {down / 2**20:.3f} MiB/step "
                  f"(full fine-tune would download {full_down / 2**20:.1f} MiB)")
        if args.pool_dtype != "none":
            dense = plan_from_config(cfg, n_model, partition=plan.partition,
                                     lora=lora_cfg)
            q_up = sum(plan.stage_bytes) * r_sim
            d_up = sum(dense.stage_bytes) * r_sim
            print(f"quantized pool ({args.pool_dtype}): upload "
                  f"{q_up / 2**20:.1f} MiB/step ({q_up / d_up:.3f}x of the "
                  f"dense {d_up / 2**20:.1f} MiB)")
    step_cfg = StepConfig(strategy=args.strategy, grad_accum=1,
                          async_optimizer=args.async_opt,
                          sequence_parallel=n_model > 1,
                          kv_chunk=min(1024, args.seq),
                          xent_chunk=min(256, args.seq),
                          partition=plan,
                          lora=lora_cfg,
                          n_microbatches=microbatches,
                          pool_dtype=args.pool_dtype,
                          grad_compress=args.grad_compress,
                          schedule=args.schedule,
                          opt=OptConfig(lr=args.lr))
    # round-major pipeline (DataConfig.rounds): multi-round synchronous
    # roundpipe consumes (R, G/R, ...) batches straight from the dataset —
    # the compiled step drops its in-step reshape (sample-identical split)
    rounds_data = 0
    if args.strategy == "roundpipe" and microbatches and not async_rp \
            and not use_supervisor:
        # the supervisor keeps the flat (G, ...) contract instead: batches
        # must be topology-independent so the deterministic replay after an
        # elastic re-plan feeds the N-1 mesh the SAME samples per step
        rounds_data = plan.rounds_for(microbatches)
    data = SyntheticLMDataset(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                         rounds=rounds_data))

    resumed_from = latest_step(args.ckpt_dir)
    if resumed_from is not None:
        print(f"resuming from checkpoint step {resumed_from} in "
              f"{args.ckpt_dir}")

    if use_supervisor:
        try:
            return _run_supervised(args, cfg, step_cfg, data, plan, mesh,
                                   n_data, n_model, resumed_from, profile)
        finally:
            if profile is not None:
                profile.close()

    with mesh:
        if async_rp:
            # the tentpole: K steps chained in ONE ring program — step T+1's
            # injection streams while step T's grads drain into the
            # in-program staleness-1 optimizer (paper §4.3, DESIGN.md §6)
            from repro.core.dispatch import build_roundpipe_async_train_step
            step, state_sh, _, plan = build_roundpipe_async_train_step(
                cfg, mesh, step_cfg, args.batch, args.seq,
                steps_per_call=args.async_steps, plan=plan)
        else:
            step, state_sh, _ = build_train_step(
                cfg, mesh, step_cfg, args.batch, args.seq,
                round_major=rounds_data > 0)
        # built in place, already sharded: no device holds the whole state
        if args.strategy == "roundpipe":
            from repro.core.dispatch import init_roundpipe_state
            init = jax.jit(lambda: init_roundpipe_state(
                jax.random.PRNGKey(0), cfg, step_cfg, n_workers=n_model),
                out_shardings=state_sh)
        else:
            init = jax.jit(lambda: init_train_state(
                jax.random.PRNGKey(0), cfg, step_cfg), out_shardings=state_sh)
        like = jax.eval_shape(init)

        mgr = CheckpointManager(args.ckpt_dir, save_every=args.ckpt_every)
        losses = []
        steps_per_call = args.async_steps if async_rp else 1

        if async_rp:
            import numpy as np

            from repro.checkpoint import save_checkpoint

            class _ChainedBatches:
                """Stack --async-steps consecutive global batches along a
                leading step axis — one chained-program call each."""

                def batch(self, call):
                    bs = [data.batch(call * steps_per_call + j)
                          for j in range(steps_per_call)]
                    return jax.tree.map(lambda *xs: np.stack(xs), *bs)

            class _OptStepCkpt:
                """Keep the checkpoint manifest counter in OPTIMIZER-step
                units (the sync convention) while the loop counts chained
                program calls: call c's manifest step is its last completed
                optimizer step (c+1)*K - 1, so sync and async runs share a
                --ckpt-dir without mis-positioning the data stream."""

                def restore_or_init(self, init_fn, like, shardings=None):
                    state, start_opt = mgr.restore_or_init(init_fn, like,
                                                           shardings)
                    calls, rem = divmod(start_opt, steps_per_call)
                    if rem:
                        # flooring to a chain boundary would RE-APPLY the
                        # trailing rem updates (double-training, not a
                        # deterministic replay) — refuse, like the --steps
                        # multiple check above.  Synchronous runs save at
                        # manifest steps ≡ 0 (mod --ckpt-every), which a
                        # chain can never start from: the interchange is
                        # one-directional (async checkpoints resume
                        # synchronously; the reverse needs an aligned
                        # manifest)
                        raise SystemExit(
                            f"checkpoint in {args.ckpt_dir} holds "
                            f"{start_opt} optimizer steps, not a multiple "
                            f"of --async-steps {steps_per_call}: resuming "
                            f"the chained program here would double-apply "
                            f"{rem} update(s).  Resume synchronously (drop "
                            f"--async-opt) — sync-written checkpoints do "
                            f"not land on chain boundaries")
                    return state, calls

                def maybe_save(self, call, state) -> bool:
                    every = max(1, args.ckpt_every // steps_per_call)
                    if call % every:
                        return False
                    save_checkpoint(args.ckpt_dir,
                                    (call + 1) * steps_per_call - 1, state,
                                    keep=mgr.keep)
                    return True

            loop_mgr = _OptStepCkpt()
            loop_data = _ChainedBatches()
            n_calls = args.steps // steps_per_call
        else:
            loop_mgr = mgr
            loop_data = data
            n_calls = args.steps
        if args.strategy == "roundpipe":
            # the ring's injection counts exist once the step is traced; the
            # first call reuses this trace
            step.trace(like, loop_data.batch(0))
            print(f"ring injections per call: {step.injections['local']} "
                  f"local, {step.injections['streamed']} streamed")

        def metrics_cb(s, m, dt):
            import numpy as np
            ls = np.asarray(m["loss"]).reshape(-1)
            losses.extend(float(x) for x in ls)
            if s % args.log_every == 0:
                n_sub = ls.size
                tps = n_sub * args.batch * args.seq / dt
                gn = np.asarray(m.get("grad_norm", 0)).reshape(-1)[-1]
                # label the LAST optimizer step of the chain — the one whose
                # loss is printed — so async and sync loss curves line up
                step_no = s * n_sub + n_sub - 1
                print(f"step {step_no:5d} loss {float(ls[-1]):.4f} "
                      f"gnorm {float(gn):.3f} "
                      f"{dt * 1e3 / n_sub:7.1f} ms/step {tps:9.0f} tok/s",
                      flush=True)

        loop = FaultTolerantLoop(step, loop_mgr, loop_data,
                                 max_restarts=max_restarts,
                                 step_timeout_s=600.0, profile=profile)
        t0 = time.time()
        try:
            state, final = loop.run(init, like, n_calls, shardings=state_sh,
                                    metrics_cb=metrics_cb)
        finally:
            if profile is not None:
                profile.close()
        final *= steps_per_call
        dt = time.time() - t0
    if losses:
        print(f"done: {final} steps in {dt:.1f}s; "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
              f"stragglers={len(loop.stragglers)} restarts={loop.restarts}")
    else:
        print(f"done: {final} steps (all restored from checkpoint)")
    return {"state": state, "losses": losses, "steps": final,
            "resumed_from": resumed_from, "durations": loop.durations}


def _run_supervised(args, cfg, step_cfg, data, plan, mesh, n_data, n_model,
                    resumed_from, profile=None) -> dict:
    """The --elastic / --async-ckpt path: the goodput supervisor drives the
    compiled step through a runtime factory, so a worker death rebuilds the
    whole stack (plan, mesh, step, shardings) for the survivors and resumes
    through the elastic restore (``reshape_pooled_state``), while a
    persistent straggler only swaps the step for one compiled with the
    rotated ``g0``.  Checkpoints go through the background writer when
    ``--async-ckpt`` is set."""
    import dataclasses

    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.runtime.supervisor import Supervisor, StragglerPolicy

    losses = []

    def make_runtime(*, n_workers, g0, use_async, replan=None):
        del use_async          # launcher wires the synchronous step only
        if n_workers == n_model:
            sub_mesh, rt_plan, m = mesh, plan, step_cfg.n_microbatches
        else:
            sub_mesh = make_mesh((n_data, n_workers), ("data", "model"),
                                 devices=jax.devices()[:n_data * n_workers])
            rt_plan, m = replan.plan, replan.n_microbatches
            if args.batch % m:
                raise SystemExit(
                    f"elastic re-plan chose M={m} micro-batches for "
                    f"N={n_workers} survivors but --batch {args.batch} is "
                    f"not divisible by it: pick a global batch divisible "
                    f"by every worker count you intend to survive on")
        scfg = dataclasses.replace(step_cfg, partition=rt_plan,
                                   n_microbatches=m, g0=g0)
        with sub_mesh:
            from repro.launch.steps import build_train_step, init_train_state
            step, state_sh, _ = build_train_step(cfg, sub_mesh, scfg,
                                                 args.batch, args.seq)
            if args.strategy == "roundpipe":
                from repro.core.dispatch import init_roundpipe_state
                init = lambda: jax.device_put(
                    init_roundpipe_state(jax.random.PRNGKey(0), cfg, scfg,
                                         n_workers=n_workers), state_sh)
            else:
                init = lambda: jax.device_put(
                    init_train_state(jax.random.PRNGKey(0), cfg, scfg),
                    state_sh)

        class _Runtime:
            shardings = state_sh
            like = jax.eval_shape(init)
            init_state = staticmethod(init)
            batch_for = staticmethod(data.batch)

            @staticmethod
            def step_fn(state, batch):
                with sub_mesh:
                    st, metrics = step(state, batch)
                ls = np.asarray(metrics["loss"]).reshape(-1)
                losses.extend(float(x) for x in ls)
                return st, metrics

            @staticmethod
            def adapt_state(host_state):
                if args.strategy == "roundpipe":
                    from repro.core.dispatch import reshape_pooled_state
                    host_state = reshape_pooled_state(host_state, cfg,
                                                      n_workers)
                return jax.device_put(host_state, state_sh)

        if args.strategy == "roundpipe" and rt_plan is not None:
            def rescore(scales):
                # re-score the rotation family under the measured slowdown;
                # the winner's g0 becomes the next step's injection worker
                from repro.core.simulator import search_schedule
                sr = search_schedule(rt_plan, m or n_workers,
                                     round_size=n_workers,
                                     device_scale=list(scales))
                return sr.choice.g0
            _Runtime.rescore = staticmethod(rescore)
        return _Runtime()

    replan_fn = None
    if args.elastic:
        from repro.core.plan import replan_for_survivors

        def replan_fn(n_surviving):
            return replan_for_survivors(
                cfg, n_surviving, n_microbatches=step_cfg.n_microbatches,
                lora=step_cfg.lora, pool_dtype=args.pool_dtype)

    sup = Supervisor(make_runtime, args.ckpt_dir, n_workers=n_model,
                     replan_fn=replan_fn,
                     straggler=StragglerPolicy(factor=args.straggler_factor),
                     save_every=args.ckpt_every,
                     async_ckpt=args.async_ckpt, step_timeout_s=600.0,
                     profile=profile)
    t0 = time.time()
    state, final = sup.run(args.steps)
    dt = time.time() - t0
    rep = sup.meter.report()
    print(f"done: {final} steps in {dt:.1f}s on N={sup.n_workers}; "
          f"goodput {rep['goodput']:.3f} "
          f"(ckpt {rep['ckpt_s']:.2f}s replan {rep['replan_s']:.2f}s "
          f"replay {rep['replay_s']:.2f}s); "
          f"events={[e.kind for e in sup.events]}")
    return {"state": state, "losses": losses, "steps": final,
            "resumed_from": resumed_from, "goodput": rep,
            "events": sup.events, "n_workers": sup.n_workers}


def main() -> None:
    run_training(build_parser().parse_args())


if __name__ == "__main__":
    main()
