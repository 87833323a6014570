"""Serving launcher: prefill a batch of prompts, decode with batched steps.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \\
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    from repro.launch.mesh import request_host_devices
    n_data, n_model = (int(x) for x in args.mesh.split("x"))
    if n_data * n_model > 1:
        request_host_devices(n_data * n_model)

    import jax
    import jax.numpy as jnp

    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import StepConfig, build_decode_step, build_prefill_step
    from repro.models import transformer as T
    from repro.models.config import get_config

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.encoder_only:
        raise SystemExit("encoder-only arch has no decode path")
    mesh = make_mesh((n_data, n_model), ("data", "model"))
    max_len = args.prompt_len + args.gen
    step_cfg = StepConfig(kv_chunk=min(1024, args.prompt_len),
                          sequence_parallel=n_model > 1)

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    with mesh:
        prefill, psh, bsh, csh = build_prefill_step(
            cfg, mesh, step_cfg, args.batch, max_len)
        decode, _, _, tsh = build_decode_step(cfg, mesh, step_cfg,
                                              args.batch, max_len)
        t0 = time.time()
        batch = {"embeds": jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16)} \
            if cfg.frontend else {"tokens": prompts}
        logits, cache = prefill(params, batch)
        logits.block_until_ready()
        t_prefill = time.time() - t0
        out_tokens = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t0 = time.time()
        for _ in range(args.gen):
            out_tokens.append(tok)
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok.block_until_ready()
        t_decode = time.time() - t0
    gen = jnp.stack(out_tokens, axis=1)
    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill * 1e3:.0f} ms "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")
    print(f"decode: {args.gen} steps in {t_decode * 1e3:.0f} ms "
          f"({args.batch * args.gen / max(t_decode, 1e-9):.0f} tok/s)")
    print("generated token ids (first sequence):", gen[0].tolist())


if __name__ == "__main__":
    main()
