"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices build the production meshes; ``.lower().compile()`` must succeed
for the 16×16 single-pod AND the 2×16×16 multi-pod mesh for every cell.
``memory_analysis()`` proves the per-device footprint fits a v5e chip;
``cost_analysis()`` + the collective schedule parsed from the compiled HLO
feed the roofline (EXPERIMENTS.md §Roofline).

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all            # every cell, subprocess each
"""
import argparse
import json
import re
import subprocess
import sys
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective op in the compiled HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    # e.g.:  %all-gather.3 = bf16[2,1152,4608]{...} all-gather(...)
    pat = re.compile(
        r"=\s*(?:\()?\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\s(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")
    for m in pat.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        if op.endswith("-done)"):
            continue
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, strategy: str,
             variant: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.shapes import SHAPES, cell_status, input_specs
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import (StepConfig, abstract_train_state,
                                    build_decode_step, build_prefill_step,
                                    build_train_step)
    from repro.models import transformer as T
    from repro.models.config import get_config
    from repro.optim import OptConfig

    cfg = get_config(arch)
    spec = SHAPES[shape]
    runs, reason = cell_status(arch, shape)
    meta = {"arch": arch, "shape": shape, "strategy": strategy,
            "mesh": "2x16x16" if multi_pod else "16x16", "step": spec.step}
    if not runs:
        return dict(meta, status="skipped", reason=reason)

    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.launch.presets import step_config_for
    step_cfg = step_config_for(arch, shape, strategy=strategy)
    if variant:
        import dataclasses as _dc
        overrides = {}
        for kv in variant.split(","):
            k, _, v = kv.partition("=")
            if k == "accum_dtype":
                import jax.numpy as jnp
                overrides[k] = getattr(jnp, v)
            else:
                overrides[k] = {"true": True, "false": False}.get(
                    v.lower(), int(v) if v.isdigit() else v)
        step_cfg = _dc.replace(step_cfg, **overrides)
        meta["variant"] = variant

    t0 = time.time()
    with mesh:
        if spec.step == "train":
            if strategy == "roundpipe":
                # the dry run lowers the exact ExecutionPlan the runtime
                # would execute; record its simulated schedule alongside —
                # at the step's micro-batch count M (R = M/N stitched
                # rounds), so the recorded bubble is the one the lowered
                # program realizes
                import dataclasses as _dc
                from repro.core.dispatch import resolve_plan
                from repro.launch.mesh import axis_size
                from repro.core.simulator import simulate_plan
                n_model = axis_size(mesh, "model")
                plan = resolve_plan(cfg, step_cfg, n_model)
                step_cfg = _dc.replace(step_cfg, partition=plan)
                m_micro = step_cfg.n_microbatches or n_model
                meta["plan"] = plan.describe()
                meta["n_microbatches"] = m_micro
                meta["rounds"] = plan.rounds_for(m_micro)
                meta["simulated_bubble"] = round(
                    simulate_plan(plan, m_micro,
                                  round_size=n_model).bubble_ratio, 4)
                # the §4.3 cross-step regime this plan WOULD reach with the
                # staleness-1 chained program (4 steps per chain) — a
                # simulator projection only: the program lowered below is
                # always the synchronous per-step one
                meta["simulated_bubble_async4"] = round(
                    simulate_plan(plan, m_micro, round_size=n_model,
                                  iterations=4).bubble_ratio, 4)
                # the generated schedule IR this plan executes, serialized
                # (TickProgram.to_json round-trips by construction — the
                # property tests replay the record through from_json), plus
                # the search layer's verdict over the schedule family
                from repro.core.schedule import TickProgram
                from repro.core.simulator import search_schedule
                rounds = plan.rounds_for(m_micro)
                meta["tick_program"] = plan.tick_program(rounds).to_json()
                assert TickProgram.from_json(meta["tick_program"]) == \
                    plan.tick_program(rounds)
                sr = search_schedule(plan, m_micro, round_size=n_model)
                meta["searched_schedule"] = {
                    "choice": sr.choice.name,
                    "bubble": round(sr.bubble, 4),
                    "hand_bubble": round(sr.hand_bubble, 4),
                }
                # goodput projection (runtime/supervisor.py analytic model):
                # step seconds from the train FLOPs at the paper's nominal
                # per-GPU rate, degraded by the simulated bubble; checkpoint
                # cost from the full fp32-master + Adam-moment state over
                # nominal host/disk bandwidths; 1000-step MTBF, checkpoint
                # every 50 steps.  The async writer pays only the
                # device→host snapshot, so its goodput is strictly above
                # the sync baseline by construction.
                from repro.runtime.supervisor import (analytic_goodput,
                                                      checkpoint_cost_model)
                n_params = T.param_count(cfg)
                state_bytes = n_params * 14.0   # bf16 + fp32 master + m + v
                c_sync, c_async = checkpoint_cost_model(
                    state_bytes, host_bw=25e9, disk_bw=2e9)
                flops = 6 * T.active_param_count(cfg) \
                    * spec.seq_len * spec.global_batch
                step_s = flops / (n_model * 330e12
                                  * (1 - meta["simulated_bubble"]))
                meta["goodput"] = {
                    "mtbf_steps": 1000, "ckpt_every": 50,
                    "sync_ckpt": round(analytic_goodput(
                        step_s, mtbf_steps=1000, ckpt_every=50,
                        ckpt_cost_s=c_sync), 4),
                    "async_ckpt": round(analytic_goodput(
                        step_s, mtbf_steps=1000, ckpt_every=50,
                        ckpt_cost_s=c_async), 4),
                }
                assert meta["goodput"]["async_ckpt"] >= \
                    meta["goodput"]["sync_ckpt"]
            step, state_sh, batch_sh = build_train_step(
                cfg, mesh, step_cfg, spec.global_batch, spec.seq_len)
            if strategy == "roundpipe":
                import functools
                from repro.core.dispatch import init_roundpipe_state
                state_abs = jax.eval_shape(functools.partial(
                    init_roundpipe_state, cfg=cfg, step_cfg=step_cfg,
                    n_workers=axis_size(mesh, "model")),
                    jax.random.PRNGKey(0))
            else:
                state_abs = abstract_train_state(cfg, step_cfg)
            batch_abs = input_specs(arch, shape)
            lowered = step.lower(state_abs, batch_abs)
            if strategy == "roundpipe":
                # ring injections read in place from the local pool against
                # those streamed through the standby or a gather (counted
                # while tracing)
                meta["injections"] = dict(step.injections)
        elif spec.step == "prefill":
            step, psh, bsh, csh = build_prefill_step(
                cfg, mesh, step_cfg, spec.global_batch, spec.seq_len)
            lowered = step.lower(T.abstract_params(cfg), input_specs(arch, shape))
        else:  # decode
            step, psh, csh, tsh = build_decode_step(
                cfg, mesh, step_cfg, spec.global_batch, spec.seq_len)
            cache_abs = T.init_cache(cfg, spec.global_batch, spec.seq_len)
            lowered = step.lower(T.abstract_params(cfg), cache_abs,
                                 input_specs(arch, shape)["tokens"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    print("memory_analysis:", ma)                      # proves it fits
    cost = compiled.cost_analysis()
    print("cost_analysis flops:", cost.get("flops"),
          "bytes accessed:", cost.get("bytes accessed"))
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    n_chips = 512 if multi_pod else 256
    model_flops = 6 * T.active_param_count(cfg) * spec.seq_len * spec.global_batch \
        if spec.step == "train" else \
        (2 * T.active_param_count(cfg) * spec.seq_len * spec.global_batch
         if spec.step == "prefill"
         else 2 * T.active_param_count(cfg) * spec.global_batch)

    return dict(
        meta,
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
            peak_bytes=ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
        ),
        cost=dict(
            flops=cost.get("flops", 0.0),
            bytes_accessed=cost.get("bytes accessed", 0.0),
        ),
        collectives=coll,
        model_flops=model_flops,
        params=T.param_count(cfg),
        active_params=T.active_param_count(cfg),
    )


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="gspmd", choices=["gspmd", "roundpipe"])
    ap.add_argument("--variant", default="",
                    help="StepConfig overrides, e.g. 'pure_dp=true,grad_accum=4'")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell, one subprocess each")
    ap.add_argument("--skip-existing", action="store_true")
    return ap


def main() -> int:
    args = build_parser().parse_args()
    from repro.launch.mesh import request_host_devices
    request_host_devices(512)     # placeholder devices; before jax init
    RESULTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs import ASSIGNED  # light import (no jax dev init needed)
        from repro.configs.shapes import SHAPES
        failures = []
        for multi_pod in (False, True):
            for arch in ASSIGNED:
                for shape in SHAPES:
                    tag = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}__{args.strategy}"
                    out = RESULTS / f"{tag}.json"
                    if args.skip_existing and out.exists():
                        st = json.loads(out.read_text()).get("status")
                        if st in ("ok", "skipped"):
                            print(f"[skip existing] {tag} ({st})", flush=True)
                            continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--strategy", args.strategy]
                    if multi_pod:
                        cmd.append("--multi-pod")
                    print(f"[run] {tag}", flush=True)
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    if r.returncode != 0:
                        failures.append(tag)
                        out.write_text(json.dumps(
                            {"arch": arch, "shape": shape, "status": "error",
                             "stderr": r.stderr[-4000:]}, indent=1))
                        print(f"[FAIL] {tag}\n{r.stderr[-2000:]}", flush=True)
                    else:
                        print(r.stdout.splitlines()[-1] if r.stdout else "",
                              flush=True)
        print(f"done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    res = run_cell(args.arch, args.shape, args.multi_pod, args.strategy,
                   args.variant)
    tag = f"{args.arch}__{args.shape}__{res['mesh']}__{args.strategy}"
    if args.variant:
        tag += "__" + args.variant.replace("=", "-").replace(",", "+")
    out = RESULTS / f"{tag}.json"
    out.write_text(json.dumps(res, indent=1))
    print(json.dumps({k: res[k] for k in ("arch", "shape", "mesh", "status")
                      if k in res}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
