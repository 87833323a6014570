"""Bring-up smoke: the RoundPipe training path on a TPU, at Qwen3-1.7B's
published width (28 layers, d_model 2048, vocab 151936), through the normal
entry point ``repro.launch.train.run_training``.

    python chip_smoke.py              # one chip: bf16 pool, then int8 pool
    python chip_smoke.py --chips 4    # four chips: the N=4 ring only

Frozen-base LoRA (rank 16) on random weights from a fixed seed.  Each phase
trains a few steps in this one process and checks that

  * the device is a TPU (no CPU fallback: the script exits non-zero);
  * every loss is finite;
  * the step-0 loss equals the single-program reference ``transformer.
    loss_fn`` on the same params and batch (zero-``B`` adapters make step 0
    the base model exactly; the int8 phase's reference runs on the base
    weights after the pool's own quantize/dequantize codec);
  * the steps really ran: a fresh checkpoint directory, nothing resumed.

The timing lines are one run of a few steps, not a benchmark.  The last
line of standard output is the JSON result.  A failed phase prints its
traceback and the next phase still runs; any failure exits 1 and prints
no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import statistics
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".smoke"            # fresh checkpoint directories, gitignored
ARCH = "qwen3-1.7b"
STEPS = 4
LORA_RANK = 16
# The ring and the reference both compute in bf16 with fp32 softmax and
# loss sums; they differ only in op order and chunking.  A relative loss
# error of 5e-3 is below one bf16 ulp (7.8e-3) of the loss.
LOSS_RTOL = 5e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def dequantized_layers(layers, bits: int):
    """The layer pool as the quantized ring computes with it: each layer's
    leaves concatenated in pytree order, blockwise-absmax quantized, and
    dequantized back to the leaves' dtype (``RingMachine.quantize_pool`` /
    ``dequant_block``), one layer at a time to bound device memory."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dequant import quantize_rows
    from repro.kernels.ref import dequant_rows_ref

    leaves, treedef = jax.tree_util.tree_flatten(layers)

    @jax.jit
    def codec(row_leaves):
        flat = jnp.concatenate([l.reshape(1, -1) for l in row_leaves], axis=1)
        deq = dequant_rows_ref(*quantize_rows(flat, bits=bits))[0]
        out, off = [], 0
        for l in row_leaves:
            out.append(deq[off:off + l.size].reshape(l.shape).astype(l.dtype))
            off += l.size
        return out

    rows = [codec([l[i] for l in leaves]) for i in range(leaves[0].shape[0])]
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.stack(col) for col in zip(*rows)])


def dequant_kernel_is_compiled() -> bool:
    """``kernels.ops.dequant_rows`` — the call the quantized ring makes —
    lowers to a Mosaic custom call here, not to interpret mode or jnp."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    codes = jax.ShapeDtypeStruct((8, 256 * 128), jnp.int8)
    scales = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    text = jax.jit(lambda c, s: ops.dequant_rows(
        c, s, out_dtype=jnp.bfloat16)).lower(codes, scales).as_text()
    return "tpu_custom_call" in text


def run_phase(name: str, *, mesh: str, batch: int, seq: int,
              pool_dtype: str, compile_s: list) -> None:
    """Train ``STEPS`` steps through ``run_training`` and check them.
    ``compile_s`` collects every backend compile's seconds."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import latest_step
    from repro.data import DataConfig, SyntheticLMDataset
    from repro.launch.train import build_parser, run_training
    from repro.models import transformer as T
    from repro.models.config import get_config

    cfg = get_config(ARCH)
    tag = f"[{name}]"
    print(f"{tag} {ARCH} at published width: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}; batch {batch} x seq {seq}; "
          f"LoRA r={LORA_RANK}; pool {pool_dtype}; mesh {mesh}", flush=True)
    ckpt = OUT / name
    shutil.rmtree(ckpt, ignore_errors=True)
    argv = ["--arch", ARCH, "--strategy", "roundpipe", "--mesh", mesh,
            "--lora-rank", str(LORA_RANK), "--batch", str(batch),
            "--seq", str(seq), "--steps", str(STEPS), "--log-every", "1",
            "--ckpt-dir", str(ckpt), "--ckpt-every", str(10 * STEPS)]
    if pool_dtype != "bf16":
        argv += ["--pool-dtype", pool_dtype]

    n_compiled = len(compile_s)
    t0 = time.perf_counter()
    res = run_training(build_parser().parse_args(argv), max_restarts=0)
    wall = time.perf_counter() - t0

    losses = res["losses"]
    check(res["resumed_from"] is None and res["steps"] == STEPS
          and len(losses) == STEPS and latest_step(ckpt) == 0,
          f"{tag} the {STEPS} steps did not all run from a fresh start")
    check(all(math.isfinite(x) for x in losses),
          f"{tag} non-finite loss in {losses}")

    params = res["state"]["params"]
    base = {k: v for k, v in params.items() if k != "lora"}
    base["layers"] = jax.tree.map(lambda a: a[:cfg.n_layers], base["layers"])
    if pool_dtype != "bf16":
        base["layers"] = dequantized_layers(base["layers"],
                                            bits=int(pool_dtype[3:]))
    batch0 = SyntheticLMDataset(DataConfig(cfg.vocab_size, seq, batch)).batch(0)
    ref = float(jax.jit(lambda p, b: T.loss_fn(
        p, b, cfg, kv_chunk=seq, xent_chunk=256))(
            base, jax.tree.map(jnp.asarray, batch0)))
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"{tag} losses {' '.join(f'{x:.5f}' for x in losses)}", flush=True)
    print(f"{tag} step-0 loss {losses[0]:.6f} vs single-program reference "
          f"{ref:.6f}: relative error {rel:.2e} (tolerance {LOSS_RTOL:.0e})",
          flush=True)
    check(rel <= LOSS_RTOL, f"{tag} step-0 loss does not match the reference")

    durations = res["durations"]
    steady = statistics.median(durations[1:])
    print(f"{tag} one run, not a benchmark: first step (compile + run) "
          f"{durations[0]:.3f} s; steady-state median of {len(durations) - 1}"
          f" block_until_ready-timed steps {steady:.4f} s/step, "
          f"{batch * seq / steady:.1f} tokens/s; backend compile "
          f"{sum(compile_s[n_compiled:]):.1f} s in this phase; phase wall "
          f"{wall:.1f} s", flush=True)
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        print(f"{tag} {d} peak_bytes_in_use (process so far) "
              f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    if pool_dtype != "bf16":
        check(dequant_kernel_is_compiled(),
              f"{tag} dequant_rows did not lower to the compiled Pallas kernel")
        print(f"{tag} dequant_rows lowers to the compiled Pallas kernel "
              f"(tpu_custom_call)", flush=True)
    del res, params, base
    gc.collect()
    shutil.rmtree(ckpt, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: bf16 then int8 pool on one chip; 4: the N=4 "
                         "ring over four chips")
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.train import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    use_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    if args.chips == 1:
        # batch 1 x 512 is the largest one-chip size the compiler accepts:
        # every layer's vjp residuals sit in the one FB slot
        phases = [dict(name="bf16", mesh="1x1", batch=1, seq=512,
                       pool_dtype="bf16"),
                  dict(name="int8", mesh="1x1", batch=1, seq=512,
                       pool_dtype="int8")]
    else:
        # the N=4 auto plan holds 18-layer blocks on every chip; at seq 512
        # that needs 16.2 GB, at 256 it fits
        phases = [dict(name="ring4", mesh="1x4", batch=4, seq=256,
                       pool_dtype="bf16")]
    failed = []
    try:
        for phase in phases:
            # a failed phase is reported and the next one still runs, so
            # one run shows every phase's state; any failure exits 1
            try:
                run_phase(**phase, compile_s=compile_s)
            except Exception as e:           # noqa: BLE001 — reported
                traceback.print_exc()
                print(f"chip_smoke: phase {phase['name']} FAILED: {e}",
                      file=sys.stderr, flush=True)
                failed.append(phase["name"])
            gc.collect()       # the failed phase's arrays, once e is gone
    finally:
        shutil.rmtree(OUT, ignore_errors=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
