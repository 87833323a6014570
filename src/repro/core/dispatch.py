"""RoundPipe computation-dispatch runtime (shard_map over `model`).

SPMD realization of the paper's §3 paradigm, driven entirely by a compiled
:class:`~repro.core.plan.ExecutionPlan` (see DESIGN.md §2).  The weight pool
is layer-sharded across the N workers of the `model` axis (the "host DRAM"
analogue: the pool is the union of HBMs).  Stages are NOT bound to workers:
each tick one *slot* — a variable-size, possibly uneven block of layers
chosen by the auto-partitioner (paper §4.4) — is injected at worker 0 and
travels one hop per tick around a **weight ring** (`ppermute`), while each
worker's resident micro-batch group stays put.  Worker w executes slot
``t - w`` at tick t, so at any tick the N workers run N *different* slots
round-robin, exactly the paper's slot→worker map ``(g0 + i) mod N``.

Unified slot ring
-----------------
Unlike the v1 runtime (one layer per tick, ``n_layers % N == 0`` required),
there is a single ring of ``S = Sf + Sb`` slots in plan order:

  * slots ``0..Sf-1`` — plain forward stages; each worker folds the block's
    layers over its resident activations, stashing every layer-boundary
    input for later recompute (§2.1.1);
  * slot ``Sf`` — the fused FB stage (§3.2): forward of the deepest
    (possibly empty) body block + final norm + LM head + loss AND their
    backward, so those layers' forward is never paid twice;
  * slots ``Sf+1..S-1`` — backward stages, deepest-first: re-run the block
    forward from the stashed boundary under ``jax.vjp`` and emit block
    weight grads plus the activation gradient carried to the next slot.

Blocks are padded to the plan's ``max_block``; padding rows repeat the
block's first layer and are masked out of both activations and gradients,
so uneven stages (including an LM-head-only fused slot) cost one ring
buffer of fixed depth.  ``n_layers`` need not divide N: the pool is padded
to ``ceil(L/N)*N`` rows and the ring is staggered by *slot*, not by layer.

Beyond-paper: a gradient buffer travels in lockstep with the weight ring;
each worker adds its resident micro-batches' block gradients hop by hop, so
when a slot's weights exit the ring its gradient is already globally
reduced — the dispatch traffic doubles as the gradient ring-all-reduce
(recorded in EXPERIMENTS.md §Perf).

Frozen-base adapters (LoRA)
---------------------------
With a :class:`repro.models.lora.LoraConfig` the runtime switches to the
paper's fine-tuning regime (the Qwen3-235B-on-one-server claim): the dense
weight ring is READ-ONLY and a second, adapter-shaped ring travels beside
it carrying each slot's ``{"A", "B"}`` factors (the adapter pool shards,
pads and ships exactly like the layer pool — it is just ~100-1000x
smaller).  Every stage computes with the merged weights
``W + (alpha/r)·B@A`` but differentiates ONLY through the adapter operand:
the traveling gradient buffer, the hop-by-hop reduction and the
end-of-ring deposit all shrink from parameter size to adapter size, and
base/embed/head/norm gradients are never materialized — the deposited
pytree contains exactly the adapter leaves.

Chunked double-buffered injection (paper §4.2, DESIGN.md §3)
------------------------------------------------------------
With a compiled :class:`~repro.core.plan.PrefetchProgram`, slot ``t``'s
block is not gathered in one head-of-line burst at its injection tick.
Instead a *standby* buffer is filled during tick ``t-1`` (slot 0 during the
fill prologue): each :class:`~repro.core.plan.ChunkUpload` moves one
byte-range of one layer row from its pool owner to worker 0, in the LPT
window order the transfer planner assigned, and the finished standby block
is promoted into the ring at tick ``t``.  The chunk writes partition each
row exactly, so the path is bit-identical to the whole-block gather — it
only restructures the transfers so XLA can overlap them with the previous
slot's compute instead of serializing them at the tick boundary.

Multi-round steady state (paper §3.2, Fig. 15; DESIGN.md §5)
------------------------------------------------------------
The near-zero-bubble claim is a steady-state property: with ``M = R·N``
micro-batches per iteration, consecutive rounds interlock so the
``N-1``-tick fill/drain is paid once per STEP, not once per round —
bubble ``(N-1)/(R·S+N-1) -> 0`` as R grows.  ``StepConfig.n_microbatches``
(a multiple of N) runs ``R = M/N`` rounds back-to-back in ``R·S + N - 1``
ticks, driven by ``plan.tick_table(R)`` — the same round-stitched order
the schedule generator dispatches and the simulator times.  Batch leaves
carry a leading round axis ``(R, B, ...)``; round ``r+1``'s injection
(and its chunked standby prefetch, tables replayed modulo S) streams into
the ring while round ``r`` drains; gradient WAVES from successive rounds
deposit into the same pool rows (``.at[].add`` sums per-round
contributions) and the replicated embed/head/norm grads accumulate
locally across rounds before the single end-of-step ``psum`` — one
optimizer update per step covering all M micro-batches, normalized by
the step's total token count exactly like a single full-batch program.
LoRA composes: the adapter ring re-injects per round and the
adapter-shaped deposit accumulates across rounds identically.

Structural properties inherited from the paper: zero weight binding (§3.1);
fill/drain bubble of N-1 ticks each ≙ N(N-1)·t (§3.3); full activation
recomputation from per-worker stashed boundaries (§2.1.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.ring import (AXIS, ParityAccum, RingMachine, StepAccum,
                             block_row, gbuf_add, ring_add, zeros_block)
from repro.models import lora as lora_mod
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import (apply_updates, init_opt_state, merge_trainable,
                         opt_state_specs, trainable_leaves)
from repro.launch.mesh import axis_size
from repro.runtime import spans


def _check_program(program, plan, rounds: int, iterations: int):
    """Validate an externally supplied tick program against the plan before
    a driver unrolls it: shape fields must match and the injection order
    must be exactly the plan's round-stitched tick_table (the drivers
    contain no scheduling arithmetic — a wrong program would silently
    execute a wrong schedule)."""
    if (program.n_workers != plan.n_workers
            or program.n_slots != plan.n_slots
            or program.rounds != rounds
            or program.iterations != iterations):
        raise ValueError(
            f"tick program shaped (N={program.n_workers}, "
            f"S={program.n_slots}, R={program.rounds}, "
            f"I={program.iterations}) does not match plan "
            f"(N={plan.n_workers}, S={plan.n_slots}) at R={rounds}, "
            f"I={iterations}")
    if program.entries != plan.tick_table(rounds, iterations):
        raise ValueError("tick program injection order does not match the "
                         "plan's round-stitched tick_table")
    if not 0 <= program.g0 < plan.n_workers:
        raise ValueError(f"tick program g0={program.g0} out of range for "
                         f"{plan.n_workers} workers")
    return program


@spans.scope(spans.RING)
def roundpipe_forward_backward(params, batch, worker_id, grad_residual=None,
                               *, cfg: ModelConfig,
                               plan, n_workers: int, l_pad: int,
                               xent_chunk: int = 256, kv_chunk: int = 1024,
                               ring_grad_dtype=jnp.float32,
                               prefetch_program=None, lora=None,
                               rounds=None, pool_dtype: str = "none",
                               grad_compress: str = "none",
                               tick_program=None, g0: int = 0,
                               injections: dict | None = None):
    """Synchronous driver: unrolls a :class:`~repro.core.schedule.TickProgram`
    over the shared :class:`~repro.core.ring.RingMachine` (source pool = the
    live pool, accumulators = the per-step family) and returns
    (grads pytree, loss_sum, token_count).

    ``tick_program`` optionally supplies the generated schedule IR to
    execute (validated against the plan); ``None`` generates
    ``plan.tick_program(rounds or 1)`` — the same records either way.

    ``params['layers']`` leaves arrive LOCAL: (l_pad/N, ...) — this worker's
    pool shard (zero-padded rows beyond ``cfg.n_layers``).  ``batch`` arrives
    with the micro-batch group resident on this worker.  Everything else
    (embed/head/norm) is replicated over `model`.  ``plan`` supplies the
    static slot structure; all ring plumbing below is static per tick, only
    *which* slot a worker computes is traced.

    ``prefetch_program`` switches injection from the monolithic per-tick
    block gather to the chunked double-buffered uploader (module docstring);
    ``None`` is the whole-block fallback.

    ``lora`` (a :class:`repro.models.lora.LoraConfig`) selects the
    frozen-base mode: ``params['lora']`` (adapter pool, sharded/padded like
    the layer pool) rides a second ring, stages compute with merged weights
    but differentiate adapters only, and the returned grads pytree is
    ``{"lora": ...}`` — no base gradient is ever materialized.

    ``rounds`` selects the multi-round steady-state regime (paper §3.2,
    module docstring): batch leaves carry a leading round axis
    ``(R, B_w, ...)``, the loop runs ``plan.tick_table(R)`` — ``R``
    stitched rounds in ``R*S + N - 1`` ticks, one fill/drain per STEP —
    and gradients accumulate across rounds (pool deposits sum per-round
    waves; replicated embed/head/norm grads add locally before the single
    end-of-step psum).  ``None`` is the legacy single-round path with flat
    ``(B_w, ...)`` batch leaves (bit-identical to ``rounds=1`` up to the
    round axis).

    ``pool_dtype`` (``"none" | "int8" | "int4"``) streams the resident pool
    QUANTIZED (DESIGN.md §7): each worker quantizes its pool shard once per
    step into blockwise-absmax codes + fp32 scales, the standby uploads (or
    the whole-block gather) ship the code+scale payload instead of the
    dense rows, and the injection block is rebuilt in compute precision by
    the fused dequant-on-upload kernel (``kernels.ops.dequant_rows``) at
    promote time.  ``"none"`` keeps today's dense path bit-identical.

    ``grad_compress="int8"`` runs every gradient deposit through the
    error-feedback int8 codec (``optim.compress``): the down-lane payload
    becomes codes+scales, and the quantization error accumulates in
    ``grad_residual`` (a fp32 tree shaped like the deposited pool, living
    beside the Adam state) which is carried into the NEXT deposit of the
    same row.  With compression on, the body returns a 4-tuple ending in
    the updated residual.

    ``g0`` rotates the ring's physical endpoints (injection at physical
    worker ``g0``, drain tail at ``(g0+N-1) mod N`` — the straggler
    mitigation, DESIGN.md §9); a supplied ``tick_program``'s own ``g0``
    stamp takes precedence.  Gradient sums are mathematically identical
    across rotations (every worker still sweeps every slot with its own
    resident group); ``g0=0`` emits bit-identical programs to the legacy
    path.

    On a one-worker ring every slot is a ``RingMachine.local_slot``: its
    injection block is read in place from the local pool, with no standby
    and no gather.  ``injections`` (a dict) receives the trace's count of
    ``local`` and ``streamed`` injections.
    """
    n = n_workers
    frozen = lora is not None
    multi = rounds is not None
    r_total = rounds if multi else 1
    l_total = cfg.n_layers
    program = (_check_program(tick_program, plan, r_total, 1)
               if tick_program is not None
               else plan.tick_program(r_total, g0=g0))
    g0 = program.g0                        # the IR's rotation stamp governs
    # worker id from a P(AXIS)-sharded iota input (one entry per worker).
    # ``w`` is the LOGICAL ring position:
    # physical worker p sits at logical (p - g0) mod N (g0=0: identity).
    w = worker_id[0] if g0 == 0 else (worker_id[0] - g0) % n

    slots = plan.stages
    sf = plan.n_fwd
    s_total = plan.n_slots
    kmax = plan.max_block
    fused_spec = plan.fused
    live = r_total * s_total               # ticks with a slot on the ring

    pool = params["layers"]
    rm = RingMachine(cfg=cfg, plan=plan, n_workers=n, l_pad=l_pad,
                     worker_id=worker_id, pool_template=pool,
                     xent_chunk=xent_chunk, kv_chunk=kv_chunk,
                     prefetch_program=prefetch_program, pool_dtype=pool_dtype,
                     g0=g0, injections=injections)
    A = StepAccum                          # per-step accumulator family
    pslot = None                           # ignored by the per-step family
    head_w = T.lm_head_weights(params, cfg)
    tokens = batch.get("tokens")
    labels = batch["labels"]
    x_emb = T.embed_inputs(params, batch, cfg)
    bshape = x_emb.shape[1:] if multi else x_emb.shape     # (B_w, S, D)

    def round_leaf(leaf, ri):
        """Round ``ri``'s resident slice of a batch-derived leaf (identity
        on the legacy flat path)."""
        if not multi:
            return leaf
        return jax.lax.dynamic_index_in_dim(leaf, ri, 0, keepdims=False)

    # static per-slot lookup tables (indexed by the traced slot id)
    starts_arr = jnp.array([s.start for s in slots] + [0], jnp.int32)
    sizes_arr = jnp.array([s.size for s in slots] + [0], jnp.int32)

    # ---- tick-state ---------------------------------------------------------
    ring = zeros_block(pool, kmax)                         # traveling weights
    # traveling gradients: fp32 for exactness; bf16 (§Perf C1b) halves the
    # dominant dispatch traffic (hop count <= N keeps the error ~2^-8).
    # Frozen-base mode: the buffer is ADAPTER-shaped — the ring traffic and
    # the deposit shrink to trainable size, base grads never exist.
    grad_pool = params["lora"] if frozen else pool
    if frozen:
        a_ring = zeros_block(grad_pool, kmax)              # traveling adapters
    gbuf = jax.tree.map(lambda a: a.astype(ring_grad_dtype),
                        zeros_block(grad_pool, kmax))
    pool_grads = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              grad_pool)
    stash = jnp.zeros((l_total + 1,) + bshape, x_emb.dtype)  # row L = scratch
    act = jnp.zeros(bshape, x_emb.dtype)
    grad_carry = jnp.zeros(bshape, jnp.float32)
    loss_sum = A.zeros((), jnp.float32)
    tok_count = A.zeros((), jnp.int32)
    if not frozen:
        embed_grad = A.zeros(params["embed"].shape, jnp.float32)
        head_grad = A.zeros(head_w.shape, jnp.float32)
        fnorm_grad = A.tree_zeros(params["final_norm"], jnp.float32)

    # ---- codec selection (one quantization pass per step, qpair per call) ---
    quant = pool_dtype != "none"
    pool_leaves = jax.tree_util.tree_flatten(pool)[0]
    if quant:
        # the adapter pool (frozen-base mode) stays full-precision: it is
        # 100-1000x smaller and rides the whole-block path below
        qpair = rm.quantize_pool(pool)

    compress = grad_compress != "none"
    if compress and grad_compress != "int8":
        raise ValueError(f"unknown grad_compress {grad_compress!r}; "
                         f"expected none|int8")
    if compress and grad_residual is None:
        raise ValueError("grad_compress needs the grad_residual pytree "
                         "(init_roundpipe_state puts it beside the Adam "
                         "state)")

    # quant-aware indirection: "none" binds the dense machine methods so the
    # dense trace stays bit-identical to the pre-quantization runtime
    def _upload(stand, slot_idx):
        if quant:
            return rm.upload_slot_q(stand, slot_idx, qpair)
        return rm.upload_slot(stand, slot_idx, pool_leaves)

    def _zeros():
        return rm.zeros_standby_q(qpair) if quant else rm.zeros_standby()

    def _assemble(spec):
        if quant:
            return rm.assemble_block_q(spec, qpair)
        return rm.assemble_block(spec, pool)

    def _promote(stand, spec):
        if quant:
            return rm.dequant_block(stand[0], stand[1], spec)
        return rm.promote_standby(stand, spec)

    if rm.uses_standby(0):
        # fill prologue: slot 0 has no preceding compute window to hide in
        standby = _upload(_zeros(), 0)

    # The driver consumes the GENERATED schedule IR — the same round-stitched
    # injection order the schedule generator dispatches (program.entries ==
    # plan.tick_table, asserted in tests): tick t injects slot t % S of
    # round t // S; the N-1 drain ticks (None entries) are paid once per
    # step, not once per round.
    for rec in program.records:
        t, entry = rec.t, rec.entry
        # ---- ring plumbing (static per tick) --------------------------------
        shifted = rm.shift(ring)
        gbuf = rm.shift(gbuf)
        if frozen:
            a_shifted = rm.shift(a_ring)
        if entry is not None:
            spec = slots[entry[1]]
            if rm.uses_standby(entry[1]):
                if spec.size:
                    ring = ring_add(shifted, _promote(standby, spec))
                else:
                    ring = shifted
            else:
                inj = _assemble(spec)
                ring = ring_add(shifted, inj) if inj is not None else shifted
            # double-buffer swap: the next tick's slot streams into the
            # fresh standby across THIS tick's compute windows (XLA
            # overlaps the copies with the compute below — no tick-boundary
            # burst).  Round r+1's slot-0 upload therefore streams while
            # round r drains its deepest slots: the per-slot ChunkUpload
            # tables are replayed modulo S.
            if rec.upload is not None and rm.uses_standby(rec.upload[0]):
                standby = _upload(_zeros(), rec.upload[0])
            if frozen:
                # adapters are ~100-1000x smaller than the dense block: the
                # whole-block gather is already far below one chunk upload,
                # so they skip the standby machinery even under prefetch
                inj_a = rm.assemble_block(spec, params["lora"])
                a_ring = ring_add(a_shifted, inj_a) \
                    if inj_a is not None else a_shifted
        else:
            ring = shifted
            if frozen:
                a_ring = a_shifted

        # ---- compute: worker w holds stitched slot (t - w) ------------------
        fb = t - w                                          # traced
        if multi:
            on_ring = jnp.logical_and(fb >= 0, fb < live)
            slot_i = jnp.where(on_ring, jnp.mod(fb, s_total), s_total)
            ri = jnp.clip(jnp.floor_divide(fb, s_total), 0, r_total - 1)
            round_start = slot_i == 0
            plain_on = jnp.logical_and(on_ring, slot_i < sf)
            fused_on = jnp.logical_and(on_ring, slot_i == sf)
            bwd_on = jnp.logical_and(on_ring, slot_i > sf)
        else:
            slot_i = jnp.clip(fb, 0, s_total)
            ri = None
            round_start = fb == 0
            plain_on = jnp.logical_and(fb >= 0, fb < sf)
            fused_on = fb == sf
            bwd_on = jnp.logical_and(fb > sf, fb < s_total)
        start = starts_arr[slot_i]
        n_act = sizes_arr[slot_i]

        @spans.scope(spans.SLOT_F)
        def do_plain(op):
            act_, stash_ = op
            # frozen-base: forward compute runs on the merged weights; merged
            # INSIDE the cond branch so fused/backward ticks (which re-merge
            # within their own vjp closures) never pay for a dead dense block
            eff_ring = lora_mod.merge_layers(ring, a_ring, lora) \
                if frozen else ring
            x_in = jnp.where(round_start, round_leaf(x_emb, ri), act_)

            def step_one(xc, st_, k, lw):
                active = k < n_act
                lid = jnp.where(active, jnp.minimum(start + k, l_total),
                                l_total)                  # row L = scratch
                with jax.named_scope(spans.STASH):
                    st_ = jax.lax.dynamic_update_slice(
                        st_, xc[None].astype(st_.dtype),
                        (lid,) + (jnp.int32(0),) * len(bshape))
                y = T.layer_forward(xc, lw, cfg, kv_chunk=kv_chunk)
                return jnp.where(active, y, xc), st_

            if kmax == 1:
                return step_one(x_in, stash_, 0, block_row(eff_ring, 0))

            def body(carry, inp):
                xc, st_ = carry
                k, lw = inp
                return step_one(xc, st_, k, lw), None

            (y, stash_), _ = jax.lax.scan(body, (x_in, stash_),
                                          (jnp.arange(kmax), eff_ring))
            return y, stash_

        act, stash = jax.lax.cond(plain_on, do_plain,
                                  lambda op: op, (act, stash))

        if frozen:
            # frozen base: differentiate through the adapter operand only —
            # the vjp emits ADAPTER-shaped block grads; dense/head/norm/embed
            # cotangents are never formed
            @spans.scope(spans.SLOT_FB)
            def do_fused(op):
                act_, ls, tc, gcarry, gb_ = op
                x_in = jnp.where(round_start, round_leaf(x_emb, ri),
                                 act_)                      # Sf == 0 edge
                labels_cur = round_leaf(labels, ri)

                def floss(ablk, xx):
                    return rm.fused_loss(
                        lora_mod.merge_layers(ring, ablk, lora),
                        params["final_norm"], head_w, xx, labels_cur)

                tot, vjp, cnt = jax.vjp(floss, a_ring, x_in, has_aux=True)
                ga, gx = vjp(jnp.float32(1.0))
                gb_ = gbuf_add(gb_, ga)
                return (act_, A.add(ls, tot, pslot), A.add(tc, cnt, pslot),
                        gx.astype(jnp.float32), gb_)

            act, loss_sum, tok_count, grad_carry, gbuf = jax.lax.cond(
                fused_on, do_fused, lambda op: op,
                (act, loss_sum, tok_count, grad_carry, gbuf))

            @spans.scope(spans.SLOT_B)
            def do_bwd(op):
                gcarry, gb_ = op
                with jax.named_scope(spans.STASH):
                    x_in = jax.lax.dynamic_index_in_dim(
                        stash, jnp.minimum(start, l_total), 0,
                        keepdims=False)
                y, vjp = jax.vjp(
                    lambda ablk, xx: rm.stage_fwd(
                        lora_mod.merge_layers(ring, ablk, lora), n_act, xx),
                    a_ring, x_in)
                ga, gx = vjp(gcarry.astype(y.dtype))
                gb_ = gbuf_add(gb_, ga)
                return gx.astype(jnp.float32), gb_

            grad_carry, gbuf = jax.lax.cond(
                bwd_on, do_bwd, lambda op: op, (grad_carry, gbuf))
        else:
            @spans.scope(spans.SLOT_FB)
            def do_fused(op):
                act_, ls, tc, gcarry, hg, fg, gb_, eg = op
                x_in = jnp.where(round_start, round_leaf(x_emb, ri),
                                 act_)                      # Sf == 0 edge
                labels_cur = round_leaf(labels, ri)
                tot, vjp, cnt = jax.vjp(
                    lambda blk, fn, hw_, xx: rm.fused_loss(blk, fn, hw_, xx,
                                                           labels_cur),
                    ring, params["final_norm"], head_w, x_in, has_aux=True)
                gb, gf, gh, gx = vjp(jnp.float32(1.0))
                gb_ = gbuf_add(gb_, gb)
                if sf == 0 and fused_spec.layers and tokens is not None:
                    eg = A.token_add(eg, round_leaf(tokens, ri),
                                     gx.astype(jnp.float32), pslot)
                return (act_, A.add(ls, tot, pslot), A.add(tc, cnt, pslot),
                        gx.astype(jnp.float32),
                        A.add_f32(hg, gh, pslot),
                        A.tree_add_f32(fg, gf, pslot),
                        gb_, eg)

            (act, loss_sum, tok_count, grad_carry, head_grad, fnorm_grad,
             gbuf, embed_grad) = jax.lax.cond(
                fused_on, do_fused, lambda op: op,
                (act, loss_sum, tok_count, grad_carry, head_grad, fnorm_grad,
                 gbuf, embed_grad))

            @spans.scope(spans.SLOT_B)
            def do_bwd(op):
                gcarry, gb_, eg = op
                with jax.named_scope(spans.STASH):
                    x_in = jax.lax.dynamic_index_in_dim(
                        stash, jnp.minimum(start, l_total), 0,
                        keepdims=False)
                y, vjp = jax.vjp(lambda blk, xx: rm.stage_fwd(blk, n_act, xx),
                                 ring, x_in)
                gb, gx = vjp(gcarry.astype(y.dtype))
                gb_ = gbuf_add(gb_, gb)

                def embed_bwd(e):
                    if tokens is None:
                        return e                              # frontend stub
                    return A.token_add(e, round_leaf(tokens, ri),
                                       gx.astype(jnp.float32), pslot)

                eg = jax.lax.cond(jnp.logical_and(start == 0, n_act > 0),
                                  embed_bwd, lambda e: e, eg)
                return gx.astype(jnp.float32), gb_, eg

            grad_carry, gbuf, embed_grad = jax.lax.cond(
                bwd_on, do_bwd, lambda op: op, (grad_carry, gbuf, embed_grad))

        # ---- gradient deposit: slot exits the ring at worker N-1 -------------
        # Round r's wave for slot j exits at tick r*S + j + N - 1; the
        # .at[idx].add inside the machine SUMS successive rounds'
        # contributions into the same pool row — the cross-round gradient
        # accumulation.
        if rec.deposit is not None:
            for k, lid in enumerate(slots[rec.deposit].layers):
                owner, idx = divmod(lid, rm.per)
                row = block_row(gbuf, k)
                if compress:
                    pool_grads, grad_residual = rm.deposit_ef(
                        pool_grads, grad_residual, row, owner, idx)
                else:
                    pool_grads = rm.deposit_plain(pool_grads, row, owner, idx)

    # ---- finalize: reduce replicated-param grads ------------------------------
    loss_sum = jax.lax.psum(loss_sum, AXIS)
    tok_count = jax.lax.psum(tok_count, AXIS)
    scale = 1.0 / jnp.maximum(tok_count.astype(jnp.float32), 1.0)
    if frozen:
        # the deposited pytree holds EXACTLY the adapter leaves: the ring
        # all-reduce already summed them, so no psum and no base entries
        grads = jax.tree.map(lambda g: g * scale, {"lora": pool_grads})
        if compress:
            return grads, loss_sum * scale, tok_count, grad_residual
        return grads, loss_sum * scale, tok_count

    embed_grad = jax.lax.psum(embed_grad, AXIS)
    head_grad = jax.lax.psum(head_grad, AXIS)
    fnorm_grad = jax.tree.map(lambda g: jax.lax.psum(g, AXIS), fnorm_grad)

    grads = {"embed": embed_grad, "layers": pool_grads,
             "final_norm": fnorm_grad}
    if "lm_head" in params:
        grads["lm_head"] = head_grad
    else:                                                   # tied embeddings
        grads["embed"] = grads["embed"] + head_grad.T
    grads = jax.tree.map(lambda g: g * scale, grads)
    if compress:
        return grads, loss_sum * scale, tok_count, grad_residual
    return grads, loss_sum * scale, tok_count


@spans.scope(spans.RING)
def roundpipe_async_forward_backward(params, opt_state, batch, worker_id,
                                     cfg: ModelConfig, *, plan, n_workers: int,
                                     l_pad: int, steps: int, rounds: int,
                                     opt_cfg, xent_chunk: int = 256,
                                     kv_chunk: int = 1024,
                                     ring_grad_dtype=jnp.float32,
                                     prefetch_program=None, lora=None,
                                     pool_dtype: str = "none",
                                     grad_compress: str = "none",
                                     tick_program=None, g0: int = 0,
                                     injections: dict | None = None):
    """Cross-step chained body (paper §4.3, DESIGN.md §6): ``steps``
    optimizer iterations executed back-to-back in ONE ring program of
    ``I*R*S + N - 1`` ticks — step ``T+1``'s round injection begins while
    step ``T``'s gradient waves are still draining to their pool owners,
    so the ``N-1``-tick fill/drain is paid once per CALL, not once per
    step.

    What makes the overlap sound is staleness-1 parameter versioning:
    step ``T`` reads version ``v_{T-1}`` (grads ``0..T-2`` applied) while
    the in-program optimizer (``repro.optim.adam.apply_updates`` on this
    worker's pool shard — the "host-resident" copy) consumes step
    ``T-1``'s freshly-drained gradients.  The five §4.3 ordering
    constraints are realized by data dependence and certified at build
    time by ``repro.core.consistency.verify_async_ticks``:

      * injections of step ``T`` read the version list entry staged at
        step ``T-2``'s deposit-complete tick ``D_{T-2}`` (constraint 2);
      * the gradient accumulators are snapshotted + reset at ``D_T``
        before step ``T+1``'s first wave exits the ring (constraints 3/4);
      * the update runs once per step at ``D_T``, sequentially
        (constraint 5), writing the double-buffered read slot whose last
        reader — step ``T``'s deepest backward — retired at that same
        tick (constraint 1, double-buffered form);

    Replicated parameters (embed / LM head / final norm) read by *traced*
    work need per-worker version selection (a worker may still run step
    ``T``'s deep slots while the injection front is in step ``T+1``), so
    they live in a 2-deep parity buffer indexed by the traced work-step.

    ``batch`` leaves arrive ``(steps, rounds, B_w, ...)``.  Returns
    ``(new_params, new_opt_state, metrics)`` with per-step ``loss`` /
    ``tokens`` / ``grad_norm`` arrays of shape ``(steps,)``; the final
    update (step ``I-1``'s) is applied before returning — the flush —
    so the result matches ``reference_staleness1`` over ``steps``
    iterations exactly.

    ``lora`` selects the frozen-base mode: the DENSE pool is read-only
    (every step injects the same rows, so there is no cross-step dense-
    weight staleness at all) and only the adapter ring versions — step
    ``T`` assembles its adapter blocks from ``v_{T-1}``'s adapter pool and
    the in-program optimizer updates the adapter leaves alone.  Because
    embed / LM head / final norm are frozen too, they need no parity
    buffers; per-step embeddings are exact (they vary only with the step's
    batch).  ``opt_state`` must cover the adapter leaves only (same shape
    as the synchronous LoRA step's).

    ``pool_dtype`` streams the resident pool QUANTIZED under chaining
    (DESIGN.md §7/§8): the codes+scales image versions exactly like the
    dense pool — step ``T`` injects the quantization of ``v_{T-1}`` — and
    each ``D_k`` update tick folds a re-quantization of the fresh
    ``v_{k+1}`` pool into the same tick that publishes it, so the program
    still runs ONE quantization pass per step.  ``grad_compress="int8"``
    runs every deposit through the error-feedback codec with the residual
    carried in ``opt_state["grad_residual"]`` ACROSS the chained steps
    (the residual telescopes from step to step exactly as it does across
    synchronous calls).

    ``tick_program`` optionally supplies the generated schedule IR
    (validated against the plan); ``None`` generates
    ``plan.tick_program(rounds, steps)``.

    ``g0`` rotates the ring's physical endpoints exactly as in the
    synchronous driver (a supplied ``tick_program``'s stamp governs); the
    staleness-1 protocol is rotation-invariant — versions, parity buffers
    and D_k ticks are all logical-coordinate.  Local slots and
    ``injections`` are as in the synchronous driver: a local block reads
    the version its injection step reads.
    """
    n = n_workers
    l_total = cfg.n_layers
    program = (_check_program(tick_program, plan, rounds, steps)
               if tick_program is not None
               else plan.tick_program(rounds, steps, g0=g0))
    g0 = program.g0                        # the IR's rotation stamp governs
    # logical ring position of this physical worker (see sync driver)
    w = worker_id[0] if g0 == 0 else (worker_id[0] - g0) % n

    slots = plan.stages
    sf = plan.n_fwd
    s_total = plan.n_slots
    kmax = plan.max_block
    fused_spec = plan.fused
    rs = rounds * s_total                  # live ticks per step
    live = steps * rs
    tied = "lm_head" not in params
    frozen = lora is not None

    starts_arr = jnp.array([s.start for s in slots] + [0], jnp.int32)
    sizes_arr = jnp.array([s.size for s in slots] + [0], jnp.int32)

    def sel2(leaf, i, j):
        """leaf[(traced i, traced j)] along the two leading axes."""
        leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
        return jax.lax.dynamic_index_in_dim(leaf, j, 0, keepdims=False)

    def batch_step(i):                     # static leading-index slice
        return jax.tree.map(lambda x: x[i], batch)

    tokens = batch.get("tokens")           # (I, R, B_w, S) or None
    labels = batch["labels"]

    # ---- staleness-1 version bookkeeping ------------------------------------
    # versions[k] = params with grads 0..k-1 applied (v_0 = the input);
    # step T's injections read versions[max(0, T-1)] — STATIC selection,
    # since injection ticks are static.  Appended at each deposit-complete
    # tick D_k below, in step order (constraint 5).
    versions = [params]
    quant = pool_dtype != "none"
    compress = grad_compress != "none"
    if compress and grad_compress != "int8":
        raise ValueError(f"unknown grad_compress {grad_compress!r}; "
                         f"expected none|int8")
    if compress:
        # the error-feedback residual rides beside the Adam state; pop it so
        # the in-program apply_updates sees a clean optimizer dict, thread
        # it through every deposit, and re-attach it before returning —
        # the residual telescopes across the chained steps.
        opt = dict(opt_state)
        grad_residual = opt.pop("grad_residual")
    else:
        opt = opt_state

    def emb_for(p, i):                     # (R, B_w, S, D) for step i
        return T.embed_inputs(p, batch_step(i), cfg)

    head0 = T.lm_head_weights(params, cfg)
    if frozen:
        # frozen base: embed / head / final norm never version, so traced
        # reads need no parity selection — per-step embeddings are exact
        # functions of the step's batch under the one frozen embed table
        x_emb_all = jnp.stack([emb_for(params, i) for i in range(steps)])
        bshape = x_emb_all.shape[2:]       # (B_w, S, D)
        emb_dtype = x_emb_all.dtype
    else:
        # parity buffers for TRACED reads: slot T % 2 holds what step T's
        # work consumes (replicated params of v_{max(0,T-1)} and its
        # embeddings of step T's batch).  Steps 0 and 1 both read v_0.
        x_emb_pair = jnp.stack([emb_for(params, 0),
                                emb_for(params, min(1, steps - 1))])
        fnorm_pair = jax.tree.map(lambda a: jnp.stack([a, a]),
                                  params["final_norm"])
        head_pair = jnp.stack([head0, head0])
        bshape = x_emb_pair.shape[2:]      # (B_w, S, D)
        emb_dtype = x_emb_pair.dtype

    # ---- tick-state ---------------------------------------------------------
    pool = params["layers"]
    rm = RingMachine(cfg=cfg, plan=plan, n_workers=n, l_pad=l_pad,
                     worker_id=worker_id, pool_template=pool,
                     xent_chunk=xent_chunk, kv_chunk=kv_chunk,
                     prefetch_program=prefetch_program, pool_dtype=pool_dtype,
                     g0=g0, injections=injections)
    # per-step accumulators are parity-PAIRED (leading dim 2, indexed by the
    # traced work-step, see ring.ParityAccum): on shallow plans (sf < N-1 or
    # S < N) a worker starts step k+1's fused/backward work before step k's
    # deposit-complete tick D_k, so a single accumulator would leak early
    # step-k+1 contributions into step k's snapshot.  Pool deposits need no
    # pairing — waves exit the ring strictly in step order (step k's last
    # deposit is tick D_k, step k+1's first is D_k + 1).
    A = ParityAccum
    ring = zeros_block(pool, kmax)
    # frozen-base: the traveling gradient buffer / pool accumulator shrink
    # to ADAPTER shape and a second ring carries each slot's versioned
    # adapter block (the sync runtime's layout, plus staleness-1)
    grad_pool = params["lora"] if frozen else pool
    if frozen:
        a_ring = zeros_block(grad_pool, kmax)
    gbuf = jax.tree.map(lambda a: a.astype(ring_grad_dtype),
                        zeros_block(grad_pool, kmax))
    pool_grads = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              grad_pool)
    stash = jnp.zeros((l_total + 1,) + bshape, emb_dtype)
    act = jnp.zeros(bshape, emb_dtype)
    grad_carry = jnp.zeros(bshape, jnp.float32)
    loss_sum = A.zeros((), jnp.float32)
    tok_count = A.zeros((), jnp.int32)
    if not frozen:
        embed_grad = A.zeros(params["embed"].shape, jnp.float32)
        head_grad = A.zeros(head0.shape, jnp.float32)
        fnorm_grad = A.tree_zeros(params["final_norm"], jnp.float32)
    losses, toks, gnorms = [], [], []

    def inj_pool(t_step):                  # version step t_step injects
        return versions[max(0, t_step - 1)]["layers"]

    def inj_apool(t_step):                 # adapter version step t_step reads
        return versions[max(0, t_step - 1)]["lora"]

    # quantized chaining: the codes+scales image versions like the dense
    # pool — q_versions[k] is the quantization of versions[k]'s pool, with
    # each re-quantization folded into the D_k tick that publishes v_{k+1}
    # (one quantization pass per step, DESIGN.md §8).  Frozen-base mode:
    # the dense pool is read-only, so one image serves every step.
    if quant:
        q_versions = [rm.quantize_pool(pool)]

        def inj_qpool(t_step):
            if frozen:
                return q_versions[0]
            return q_versions[max(0, t_step - 1)]

    def _upload_for(t_step, slot_idx):
        """Fill a fresh standby for ``slot_idx`` from the pool version step
        ``t_step`` injects, through the selected codec."""
        if quant:
            qp = inj_qpool(t_step)
            return rm.upload_slot_q(rm.zeros_standby_q(qp), slot_idx, qp)
        return rm.upload_slot(
            rm.zeros_standby(), slot_idx,
            jax.tree_util.tree_flatten(inj_pool(t_step))[0])

    if rm.uses_standby(0):
        standby = _upload_for(0, 0)

    for rec in program.records:
        t, entry = rec.t, rec.entry
        # ---- ring plumbing (static per tick) --------------------------------
        shifted = rm.shift(ring)
        gbuf = rm.shift(gbuf)
        if frozen:
            a_shifted = rm.shift(a_ring)
        if entry is not None:
            t_inj = rec.inject_step        # static injection step
            spec = slots[entry[1]]
            if rm.uses_standby(entry[1]):
                if spec.size:
                    promoted = (rm.dequant_block(standby[0], standby[1], spec)
                                if quant
                                else rm.promote_standby(standby, spec))
                    ring = ring_add(shifted, promoted)
                else:
                    ring = shifted
            else:
                inj = (rm.assemble_block_q(spec, inj_qpool(t_inj)) if quant
                       else rm.assemble_block(spec, inj_pool(t_inj)))
                ring = ring_add(shifted, inj) if inj is not None else shifted
            if frozen:
                # adapters skip the standby machinery (sync-runtime
                # rationale: far smaller than one chunk) but version like
                # the dense async pool: step T reads v_{T-1}'s adapters
                inj_a = rm.assemble_block(spec, inj_apool(t_inj))
                a_ring = ring_add(a_shifted, inj_a) \
                    if inj_a is not None else a_shifted
        else:
            ring = shifted
            if frozen:
                a_ring = a_shifted

        # ---- compute: worker w holds stitched global tick (t - w) -----------
        fb = t - w                                          # traced
        on_ring = jnp.logical_and(fb >= 0, fb < live)
        slot_i = jnp.where(on_ring, jnp.mod(fb, s_total), s_total)
        g_round = jnp.clip(jnp.floor_divide(fb, s_total), 0,
                           steps * rounds - 1)
        ri = jnp.mod(g_round, rounds)                       # round in step
        parity = jnp.mod(jnp.floor_divide(g_round, rounds), 2)
        round_start = slot_i == 0
        plain_on = jnp.logical_and(on_ring, slot_i < sf)
        fused_on = jnp.logical_and(on_ring, slot_i == sf)
        bwd_on = jnp.logical_and(on_ring, slot_i > sf)
        start = starts_arr[slot_i]
        n_act = sizes_arr[slot_i]

        step_tr = jnp.floor_divide(g_round, rounds)

        def x_emb_cur():
            if frozen:      # exact: embed frozen, only the batch varies
                return sel2(x_emb_all, step_tr, ri)
            return sel2(x_emb_pair, parity, ri)

        @spans.scope(spans.SLOT_F)
        def do_plain(op):
            act_, stash_ = op
            eff_ring = lora_mod.merge_layers(ring, a_ring, lora) \
                if frozen else ring
            x_in = jnp.where(round_start, x_emb_cur(), act_)

            def step_one(xc, st_, k, lw):
                active = k < n_act
                lid = jnp.where(active, jnp.minimum(start + k, l_total),
                                l_total)
                with jax.named_scope(spans.STASH):
                    st_ = jax.lax.dynamic_update_slice(
                        st_, xc[None].astype(st_.dtype),
                        (lid,) + (jnp.int32(0),) * len(bshape))
                y = T.layer_forward(xc, lw, cfg, kv_chunk=kv_chunk)
                return jnp.where(active, y, xc), st_

            if kmax == 1:
                return step_one(x_in, stash_, 0, block_row(eff_ring, 0))

            def body(carry, inp):
                xc, st_ = carry
                k, lw = inp
                return step_one(xc, st_, k, lw), None

            (y, stash_), _ = jax.lax.scan(body, (x_in, stash_),
                                          (jnp.arange(kmax), eff_ring))
            return y, stash_

        act, stash = jax.lax.cond(plain_on, do_plain,
                                  lambda op: op, (act, stash))

        if frozen:
            # frozen base: differentiate through the adapter ring only —
            # replicated params are constants, no parity selection needed
            @spans.scope(spans.SLOT_FB)
            def do_fused(op):
                act_, ls, tc, gcarry, gb_ = op
                x_in = jnp.where(round_start, x_emb_cur(), act_)

                def floss(ablk, xx):
                    return rm.fused_loss(
                        lora_mod.merge_layers(ring, ablk, lora),
                        params["final_norm"], head0, xx,
                        sel2(labels, step_tr, ri))

                tot, vjp, cnt = jax.vjp(floss, a_ring, x_in, has_aux=True)
                ga, gx = vjp(jnp.float32(1.0))
                gb_ = gbuf_add(gb_, ga)
                return (act_, A.add(ls, tot, parity),
                        A.add(tc, cnt, parity), gx.astype(jnp.float32), gb_)

            act, loss_sum, tok_count, grad_carry, gbuf = jax.lax.cond(
                fused_on, do_fused, lambda op: op,
                (act, loss_sum, tok_count, grad_carry, gbuf))

            @spans.scope(spans.SLOT_B)
            def do_bwd(op):
                gcarry, gb_ = op
                with jax.named_scope(spans.STASH):
                    x_in = jax.lax.dynamic_index_in_dim(
                        stash, jnp.minimum(start, l_total), 0,
                        keepdims=False)
                y, vjp = jax.vjp(
                    lambda ablk, xx: rm.stage_fwd(
                        lora_mod.merge_layers(ring, ablk, lora), n_act, xx),
                    a_ring, x_in)
                ga, gx = vjp(gcarry.astype(y.dtype))
                gb_ = gbuf_add(gb_, ga)
                return gx.astype(jnp.float32), gb_

            grad_carry, gbuf = jax.lax.cond(
                bwd_on, do_bwd, lambda op: op, (grad_carry, gbuf))
        else:
            @spans.scope(spans.SLOT_FB)
            def do_fused(op):
                act_, ls, tc, gcarry, hg, fg, gb_, eg = op
                x_in = jnp.where(round_start, x_emb_cur(), act_)  # Sf==0 edge
                labels_cur = sel2(labels, step_tr, ri)
                fnorm_cur = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, parity, 0,
                                                           keepdims=False),
                    fnorm_pair)
                head_cur = jax.lax.dynamic_index_in_dim(head_pair, parity, 0,
                                                        keepdims=False)
                tot, vjp, cnt = jax.vjp(
                    lambda blk, fn, hw_, xx: rm.fused_loss(blk, fn, hw_, xx,
                                                           labels_cur),
                    ring, fnorm_cur, head_cur, x_in, has_aux=True)
                gb, gf, gh, gx = vjp(jnp.float32(1.0))
                gb_ = gbuf_add(gb_, gb)
                if sf == 0 and fused_spec.layers and tokens is not None:
                    eg = A.token_add(eg, sel2(tokens, step_tr, ri),
                                     gx.astype(jnp.float32), parity)
                return (act_, A.add(ls, tot, parity),
                        A.add(tc, cnt, parity), gx.astype(jnp.float32),
                        A.add_f32(hg, gh, parity),
                        A.tree_add_f32(fg, gf, parity),
                        gb_, eg)

            (act, loss_sum, tok_count, grad_carry, head_grad, fnorm_grad,
             gbuf, embed_grad) = jax.lax.cond(
                fused_on, do_fused, lambda op: op,
                (act, loss_sum, tok_count, grad_carry, head_grad, fnorm_grad,
                 gbuf, embed_grad))

            @spans.scope(spans.SLOT_B)
            def do_bwd(op):
                gcarry, gb_, eg = op
                with jax.named_scope(spans.STASH):
                    x_in = jax.lax.dynamic_index_in_dim(
                        stash, jnp.minimum(start, l_total), 0,
                        keepdims=False)
                y, vjp = jax.vjp(lambda blk, xx: rm.stage_fwd(blk, n_act, xx),
                                 ring, x_in)
                gb, gx = vjp(gcarry.astype(y.dtype))
                gb_ = gbuf_add(gb_, gb)

                def embed_bwd(e):
                    if tokens is None:
                        return e
                    return A.token_add(e, sel2(tokens, step_tr, ri),
                                       gx.astype(jnp.float32), parity)

                eg = jax.lax.cond(jnp.logical_and(start == 0, n_act > 0),
                                  embed_bwd, lambda e: e, eg)
                return gx.astype(jnp.float32), gb_, eg

            grad_carry, gbuf, embed_grad = jax.lax.cond(
                bwd_on, do_bwd, lambda op: op, (grad_carry, gbuf, embed_grad))

        # ---- gradient deposit -----------------------------------------------
        if rec.deposit is not None:
            for k, lid in enumerate(slots[rec.deposit].layers):
                owner, idx = divmod(lid, rm.per)
                row = block_row(gbuf, k)
                if compress:
                    pool_grads, grad_residual = rm.deposit_ef(
                        pool_grads, grad_residual, row, owner, idx)
                else:
                    pool_grads = rm.deposit_plain(pool_grads, row, owner, idx)

        # ---- D_k: step k's grads fully drained -> host optimizer update -----
        if rec.update_step is not None:
            k = rec.update_step            # static step index, in order
            p_k = k % 2                    # step k's accumulator parity slot
            loss_k = jax.lax.psum(A.read(loss_sum, p_k), AXIS)
            tok_k = jax.lax.psum(A.read(tok_count, p_k), AXIS)
            scale = 1.0 / jnp.maximum(tok_k.astype(jnp.float32), 1.0)
            if frozen:
                # adapter-only update: the deposited pytree holds exactly
                # the adapter leaves (already ring-reduced, rows disjoint
                # across shards -> psum for the global clip norm)
                grads = {"lora": jax.tree.map(lambda x: x * scale,
                                              pool_grads)}
                gnorm = jnp.sqrt(jax.lax.psum(
                    sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(grads["lora"])), AXIS))
                mask = lora_mod.param_mask(params)
                new_tr, opt, _ = apply_updates(
                    opt, grads, opt_cfg,
                    param_like=trainable_leaves(params, mask),
                    grad_norm=gnorm)
                # frozen leaves are identical across versions, so merging
                # into v_0 reconstructs v_{k+1} exactly
                new_params = merge_trainable(params, new_tr, mask)
            else:
                eg = jax.lax.psum(A.read(embed_grad, p_k), AXIS)
                hg = jax.lax.psum(A.read(head_grad, p_k), AXIS)
                fg = jax.tree.map(lambda x: jax.lax.psum(x, AXIS),
                                  A.tree_read(fnorm_grad, p_k))
                grads = {"embed": eg, "layers": pool_grads, "final_norm": fg}
                if not tied:
                    grads["lm_head"] = hg
                else:
                    grads["embed"] = grads["embed"] + hg.T
                grads = jax.tree.map(lambda x: x * scale, grads)
                # global clip norm: pool rows are disjoint across shards
                # (psum); replicated grads are identical everywhere (once)
                pool_sq = jax.lax.psum(
                    sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(grads["layers"])), AXIS)
                rep_sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                             for key, v in grads.items() if key != "layers"
                             for x in jax.tree.leaves(v))
                gnorm = jnp.sqrt(pool_sq + rep_sq)
                new_params, opt, _ = apply_updates(opt, grads, opt_cfg,
                                                   param_like=params,
                                                   grad_norm=gnorm)
            versions.append(new_params)
            if quant and not frozen:
                # requantization folded into D_k: v_{k+1}'s codes+scales are
                # produced here, so staleness-1 injection reads quantized
                # versions exactly like the dense version list
                q_versions.append(rm.quantize_pool(new_params["layers"]))
            losses.append(loss_k * scale)
            toks.append(tok_k)
            gnorms.append(gnorm)
            # the G-copy/reset: pool deposits clear fully (step k+1's first
            # wave exits at tick g+N, strictly later); the paired
            # accumulators clear ONLY step k's parity slot — the other slot
            # may already hold step k+1's early fused/backward contributions,
            # and step k+2 (which reuses slot p_k) starts no earlier than
            # tick (k+2)*R*S > D_k
            pool_grads = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), grad_pool)
            loss_sum = A.reset(loss_sum, p_k)
            tok_count = A.reset(tok_count, p_k)
            if not frozen:
                embed_grad = A.reset(embed_grad, p_k)
                head_grad = A.reset(head_grad, p_k)
                fnorm_grad = A.tree_reset(fnorm_grad, p_k)
                # publish v_{k+1} into the parity slot step k+2 will read;
                # its previous occupant (v_{k-1}) had its last reader retire
                # at this very tick — constraint (1), double-buffered form.
                # (Frozen mode: replicated params never version, nothing to
                # publish — the adapter versions ride the list above.)
                nxt = k + 2
                if nxt < steps:
                    x_emb_pair = x_emb_pair.at[nxt % 2].set(
                        emb_for(new_params, nxt))
                    fnorm_pair = jax.tree.map(
                        lambda pair, v: pair.at[nxt % 2].set(v),
                        fnorm_pair, new_params["final_norm"])
                    head_pair = head_pair.at[nxt % 2].set(
                        T.lm_head_weights(new_params, cfg))

        # ---- standby upload for tick t+1 (after any version publish) --------
        if rec.upload is not None and rm.uses_standby(rec.upload[0]):
            standby = _upload_for(rec.upload[1], rec.upload[0])

    if compress:
        opt = dict(opt, grad_residual=grad_residual)
    metrics = {"loss": jnp.stack(losses), "tokens": jnp.stack(toks),
               "grad_norm": jnp.stack(gnorms), "step": opt["step"]}
    return versions[-1], opt, metrics


# ---------------------------------------------------------------------------
# jit-level builders (strategy="roundpipe")
# ---------------------------------------------------------------------------

def roundpipe_param_specs(cfg: ModelConfig, abstract) -> dict:
    """Pool layout: layer dim sharded over `model`; the rest replicated on the
    manual axis (auto axes may still shard them).  The adapter pool
    (``"lora"``) shards over its leading layer dim exactly like the dense
    pool it decorates."""
    def rule(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        if names and names[0] in ("layers", "lora"):
            return P(AXIS, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(rule, abstract)


def resolve_plan(cfg: ModelConfig, step_cfg, n_workers: int):
    """The plan a roundpipe step executes: ``step_cfg.partition`` if set
    (entry points hand in an auto- or hand-partitioned :class:`Partition`),
    else auto-derived from the architecture's cost model (paper §4.4)."""
    from repro.core.plan import ExecutionPlan, plan_from_config

    partition = getattr(step_cfg, "partition", None)
    if isinstance(partition, ExecutionPlan):
        return partition
    return plan_from_config(cfg, n_workers, partition=partition,
                            lora=getattr(step_cfg, "lora", None),
                            pool_dtype=getattr(step_cfg, "pool_dtype",
                                               "none"))


def pool_rows(cfg: ModelConfig, n_workers: int) -> int:
    """Pool depth after padding the stacked layer dim to a multiple of N
    (`n_layers % N != 0` support — the ring staggers by stage, not layer).
    Shares ``plan.pool_layout`` with ``prefetch_program`` so the chunk
    tables' owner/pool_row always match the runtime shard layout."""
    from repro.core.plan import pool_layout
    return pool_layout(cfg.n_layers, n_workers)[0]


def pad_pool(params, cfg: ModelConfig, n_workers: int):
    """Zero-pad ``params['layers']`` (and the adapter pool ``params['lora']``
    when present) to ``pool_rows`` rows.  Padding rows are never referenced
    by any plan slot, receive exactly-zero gradients, and therefore stay
    zero under the optimizer — they exist only so the pools shard evenly
    over the `model` axis."""
    l_pad = pool_rows(cfg, n_workers)
    if l_pad == cfg.n_layers:
        return params
    out = dict(params)

    def pad(a):
        return jnp.pad(
            a, [(0, l_pad - cfg.n_layers)] + [(0, 0)] * (a.ndim - 1))

    out["layers"] = jax.tree.map(pad, params["layers"])
    if "lora" in params:
        out["lora"] = jax.tree.map(pad, params["lora"])
    return out


def _build_mapped(cfg: ModelConfig, mesh, plan, *, xent_chunk: int,
                  kv_chunk: int, ring_grad_dtype, prefetch_program=None,
                  lora=None, rounds=None, pool_dtype: str = "none",
                  grad_compress: str = "none", tick_program=None,
                  g0: int = 0):
    """The shard_map'ed plan executor over PADDED params.

    Returns ``(mapped, l_pad, pspecs, grads_specs)`` where
    ``mapped(padded_params, batch) -> (padded_grads, loss, tokens)``;
    ``mapped.injections`` holds the last trace's ``local`` and
    ``streamed`` injection counts (``RingMachine``).
    With ``lora`` the params carry a ``"lora"`` adapter pool and the grads
    pytree holds exactly ``{"lora": ...}`` (frozen-base mode).
    With ``rounds`` the batch leaves must carry a leading round axis
    ``(rounds, B, ...)``; dim 0 stays replicated (each worker sees every
    round of its resident group) while dim 1 shards over `model`.
    With ``grad_compress`` the call becomes
    ``mapped(padded_params, batch, grad_residual) ->
    (padded_grads, loss, tokens, new_residual)`` — the error-feedback
    residual (a fp32 tree shaped like the deposited pool) threads through.
    """
    n = axis_size(mesh, AXIS)
    if plan.n_workers != n:
        raise ValueError(
            f"plan compiled for {plan.n_workers} workers, mesh has {n}")
    if plan.n_layers != cfg.n_layers:
        raise ValueError(
            f"plan covers {plan.n_layers} layers, model has {cfg.n_layers}")
    plan.validate()
    if prefetch_program is not None:
        if prefetch_program.n_workers != n:
            raise ValueError(
                f"prefetch program compiled for {prefetch_program.n_workers} "
                f"workers, mesh has {n}")
        prefetch_program.validate(plan)
    l_pad = pool_rows(cfg, n)

    abstract = T.abstract_params(cfg)
    if lora is not None:
        abstract = dict(abstract, lora=lora_mod.adapter_abstract(cfg, lora))
    pspecs = roundpipe_param_specs(cfg, abstract)
    injections = {}
    body = functools.partial(
        roundpipe_forward_backward, cfg=cfg, plan=plan, n_workers=n,
        l_pad=l_pad, xent_chunk=xent_chunk, kv_chunk=kv_chunk,
        ring_grad_dtype=ring_grad_dtype, prefetch_program=prefetch_program,
        lora=lora, rounds=rounds, pool_dtype=pool_dtype,
        grad_compress=grad_compress, tick_program=tick_program, g0=g0,
        injections=injections)
    if lora is not None:
        grads_specs = {"lora": pspecs["lora"]}
    elif "lm_head" in abstract:
        grads_specs = dict(pspecs)
    else:
        grads_specs = {k: pspecs[k] for k in ("embed", "layers", "final_norm")}
    # the error-feedback residual shards like the pool it shadows
    res_specs = pspecs["lora"] if lora is not None else pspecs["layers"]

    def mapped(padded_params, batch, grad_residual=None):
        if rounds is None:
            bspecs = jax.tree.map(
                lambda leaf: P(AXIS, *([None] * (leaf.ndim - 1))), batch)
        else:    # leading round axis replicated, per-round batch dim sharded
            bspecs = jax.tree.map(
                lambda leaf: P(None, AXIS, *([None] * (leaf.ndim - 2))),
                batch)
        if grad_compress != "none":
            f = jax.shard_map(
                body, mesh=mesh, axis_names={AXIS},
                in_specs=(pspecs, bspecs, P(AXIS), res_specs),
                out_specs=(grads_specs, P(), P(), res_specs),
                check_vma=False)
            return f(padded_params, batch, jnp.arange(n, dtype=jnp.int32),
                     grad_residual)
        f = jax.shard_map(
            body, mesh=mesh, axis_names={AXIS},
            in_specs=(pspecs, bspecs, P(AXIS)),
            out_specs=(grads_specs, P(), P()),
            check_vma=False)
        return f(padded_params, batch, jnp.arange(n, dtype=jnp.int32))

    mapped.injections = injections
    return mapped, l_pad, pspecs, grads_specs


def build_roundpipe_grads_fn(cfg: ModelConfig, mesh, plan, *,
                             xent_chunk: int = 256, kv_chunk: int = 1024,
                             ring_grad_dtype=jnp.float32,
                             prefetch_program=None, lora=None,
                             n_microbatches=None, pool_dtype: str = "none",
                             grad_compress: str = "none", tick_program=None,
                             g0: int = 0):
    """shard_map'ed ``f(params, batch) -> (grads, loss, tokens)`` executing
    ``plan`` on UNPADDED params (reference-comparison API): pads the pool on
    the way in and slices the gradient rows back out.  ``prefetch_program``
    selects the chunked double-buffered injection path (None = whole-block);
    ``lora`` selects the frozen-base mode (params must carry ``"lora"``,
    grads come back as ``{"lora": ...}``); ``n_microbatches`` (a multiple
    ``M = R*N`` of the worker count) selects the multi-round steady-state
    path — the flat batch splits into ``R`` leading round groups and the
    returned grads are accumulated over all ``M`` micro-batches (the
    full-batch token-mean, same normalization as the single-round path).
    ``pool_dtype`` streams the resident pool quantized (int8/int4 codes +
    scales, fused dequant at promote time); ``grad_compress="int8"``
    switches the call to ``f(params, batch, residual) -> (grads, loss,
    tokens, new_residual)`` with an UNPADDED pool-shaped fp32 residual."""
    rounds = None if n_microbatches is None else plan.rounds_for(n_microbatches)
    mapped, l_pad, _, _ = _build_mapped(
        cfg, mesh, plan, xent_chunk=xent_chunk, kv_chunk=kv_chunk,
        ring_grad_dtype=ring_grad_dtype, prefetch_program=prefetch_program,
        lora=lora, rounds=rounds, pool_dtype=pool_dtype,
        grad_compress=grad_compress, tick_program=tick_program, g0=g0)
    n = axis_size(mesh, AXIS)

    def pad_rows(tree):
        if l_pad == cfg.n_layers:
            return tree
        return jax.tree.map(
            lambda a: jnp.pad(a, [(0, l_pad - cfg.n_layers)]
                              + [(0, 0)] * (a.ndim - 1)), tree)

    def grads_fn(params, batch, grad_residual=None):
        if rounds is not None:
            def split(x):
                if x.shape[0] % n_microbatches:
                    raise ValueError(
                        f"global batch {x.shape[0]} not divisible by "
                        f"n_microbatches {n_microbatches}")
                return x.reshape(rounds, x.shape[0] // rounds, *x.shape[1:])
            batch = jax.tree.map(split, batch)
        padded = pad_pool(params, cfg, n)
        if grad_compress != "none":
            grads, loss, tokens, res = mapped(padded, batch,
                                              pad_rows(grad_residual))
            if l_pad != cfg.n_layers:
                res = jax.tree.map(lambda a: a[:cfg.n_layers], res)
        else:
            grads, loss, tokens = mapped(padded, batch)
        if l_pad != cfg.n_layers:
            grads = {k: jax.tree.map(lambda a: a[:cfg.n_layers], v)
                     if k in ("layers", "lora") else v
                     for k, v in grads.items()}
        if grad_compress != "none":
            return grads, loss, tokens, res
        return grads, loss, tokens

    return grads_fn


def _select_schedule(step_cfg, plan, rounds: int, iterations: int,
                     device_scale=None):
    """Resolve ``step_cfg.schedule`` into the tick program the driver runs.

    ``"hand"`` (default) returns None — the driver generates the canonical
    ``plan.tick_program`` internally, exactly the pre-IR behavior.
    ``"searched"`` runs :func:`repro.core.simulator.search_schedule` over
    the schedule family and hands the certified winner's
    :class:`~repro.core.schedule.TickProgram` to the driver explicitly
    (``_check_program`` re-validates it at trace time); the search keeps
    the hand config as candidate 0 with strict-< replacement, so the
    executed schedule's simulated bubble never exceeds the hand-written
    table's.  The winner's ``g0`` stamp rides the program — a winning
    rotation is executed, not just logged (the ring rotates its
    permutation endpoints at trace time).

    ``device_scale`` (per-device compute multipliers) re-scores the family
    under an observed straggler — the goodput supervisor's mitigation path.
    """
    sel = getattr(step_cfg, "schedule", "hand")
    if sel == "hand":
        return None
    if sel == "searched":
        from repro.core.simulator import search_schedule
        result = search_schedule(
            plan, rounds * plan.n_workers, iterations=iterations,
            device_scale=device_scale)
        return result.program
    raise ValueError(f"unknown schedule selector {sel!r}: "
                     "expected 'hand' or 'searched'")


def build_roundpipe_train_step(cfg: ModelConfig, mesh, step_cfg,
                               global_batch: int, seq_len: int, *,
                               plan=None, round_major: bool = False):
    """Compile the full roundpipe train step for ``plan`` (auto-derived from
    ``step_cfg.partition`` / the cost model when None).

    The train state keeps the layer pool PADDED at rest (``pool_rows`` rows,
    see ``pad_pool``) so it shards evenly over the `model` axis even when
    ``n_layers % N != 0`` — use ``init_roundpipe_state(..., n_workers=N)``.

    ``step_cfg.prefetch`` selects the chunked double-buffered weight
    uploader (the plan's compiled PrefetchProgram, paper §4.2); False falls
    back to the whole-block per-tick gather.

    ``step_cfg.n_microbatches`` (``M = R*N``) selects the multi-round
    steady-state regime: the global batch splits into ``M`` micro-batches
    executed as ``R`` stitched rounds per step (``plan.tick_table``),
    gradients accumulated across rounds before the single optimizer
    update.  ``None`` keeps the legacy one-round-per-step path.

    ``round_major=True`` (multi-round only) changes the compiled batch
    contract to the data pipeline's round-major layout ``(R, G/R, ...)``
    (``DataConfig.rounds``): the step consumes the batch as-is — no
    in-step reshape — and ``batch_shardings`` reflect the leading round
    axis.  The default keeps the flat ``(G, ...)`` contract with the
    legacy reshape.

    ``step_cfg.pool_dtype`` ("int8"/"int4") streams the resident pool
    quantized with fused dequant-on-upload; ``step_cfg.grad_compress``
    ("int8") runs deposits through the error-feedback codec, with the
    residual carried in ``state["opt"]["grad_residual"]``.

    Returns ``(step, state_shardings, batch_shardings, plan)`` — the returned
    plan is the exact object the step executes, so callers can simulate it
    (``simulate_plan(plan, M, round_size=N)``) and compare against the
    real run.  Once traced, ``step.injections`` holds the program's
    ``local`` and ``streamed`` ring injections.
    """
    n = axis_size(mesh, AXIS)
    if global_batch % n:
        raise ValueError("global batch must divide the model axis")
    if plan is None:
        plan = resolve_plan(cfg, step_cfg, n)
    m_micro = getattr(step_cfg, "n_microbatches", None)
    rounds = None
    if m_micro is not None:
        rounds = plan.rounds_for(m_micro)
        if global_batch % m_micro:
            raise ValueError(
                f"global batch {global_batch} must be divisible by "
                f"n_microbatches {m_micro} (micro-batch size = "
                f"global_batch / M)")
    program = None
    if getattr(step_cfg, "prefetch", True):
        program = plan.prefetch_program(
            chunk_limit=getattr(step_cfg, "prefetch_chunk_limit", None))
    lora = getattr(step_cfg, "lora", None)
    pool_dtype = getattr(step_cfg, "pool_dtype", "none")
    grad_compress = getattr(step_cfg, "grad_compress", "none")
    if round_major and rounds is None:
        raise ValueError("round_major=True requires the multi-round path "
                         "(set step_cfg.n_microbatches)")
    tick_program = _select_schedule(
        step_cfg, plan, rounds or 1, 1,
        device_scale=getattr(step_cfg, "device_scale", None))
    # rotation: the searched program's stamp governs; under "hand" the
    # StepConfig.g0 knob (the supervisor's straggler mitigation) applies
    g0 = tick_program.g0 if tick_program is not None \
        else getattr(step_cfg, "g0", 0)

    mapped, l_pad, pspecs, _ = _build_mapped(
        cfg, mesh, plan, xent_chunk=step_cfg.xent_chunk,
        kv_chunk=step_cfg.kv_chunk, ring_grad_dtype=step_cfg.accum_dtype,
        prefetch_program=program, lora=lora, rounds=rounds,
        pool_dtype=pool_dtype, grad_compress=grad_compress,
        tick_program=tick_program, g0=g0)
    if lora is None:
        ospecs = opt_state_specs(pspecs, step_cfg.opt)
    else:
        # frozen base: optimizer state (fp32 master + moments — the §4.3
        # host-resident copies) exists for the adapter leaves ONLY
        ospecs = opt_state_specs(
            trainable_leaves(pspecs, lora_mod.param_mask(pspecs)),
            step_cfg.opt)
    if grad_compress != "none":
        # the error-feedback residual lives beside the Adam state, sharded
        # like the pool it shadows (adapter pool under LoRA)
        ospecs = dict(ospecs, grad_residual=(
            pspecs["lora"] if lora is not None else pspecs["layers"]))
    state_specs = {"params": pspecs, "opt": ospecs}

    batch_abs = {}
    if cfg.frontend:
        batch_abs["embeds"] = jax.ShapeDtypeStruct(
            (global_batch, seq_len, cfg.d_model), jnp.bfloat16)
    else:
        batch_abs["tokens"] = jax.ShapeDtypeStruct((global_batch, seq_len),
                                                   jnp.int32)
    batch_abs["labels"] = jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
    if round_major:
        # pipeline-native layout: the round split happened at emission time
        batch_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (rounds, s.shape[0] // rounds) + s.shape[1:], s.dtype),
            batch_abs)
        bspecs = jax.tree.map(
            lambda leaf: P(None, AXIS, *([None] * (leaf.ndim - 2))),
            batch_abs)
    else:
        bspecs = jax.tree.map(
            lambda leaf: P(AXIS, *([None] * (leaf.ndim - 1))), batch_abs)

    def train_step(state, batch):
        if rounds is not None and not round_major:
            # flat (G, ...) -> (R, G/R, ...): round r owns micro-batch
            # groups r*N..(r+1)*N-1 of the step (leading round axis).
            # round_major batches arrive pre-shaped — no reshape at all.
            batch = jax.tree.map(
                lambda x: x.reshape(rounds, x.shape[0] // rounds,
                                    *x.shape[1:]), batch)
        if grad_compress != "none":
            opt_in = dict(state["opt"])
            residual = opt_in.pop("grad_residual")
            grads, loss, tokens, new_residual = mapped(
                state["params"], batch, residual)
        else:
            opt_in = state["opt"]
            grads, loss, tokens = mapped(state["params"], batch)
        if lora is None:
            new_params, new_opt, metrics = apply_updates(
                opt_in, grads, step_cfg.opt, param_like=state["params"])
        else:
            # update the adapter leaves only; the frozen base passes through
            # bit-identical (no master copy, no moments, no decay)
            mask = lora_mod.param_mask(state["params"])
            trainable = trainable_leaves(state["params"], mask)
            new_tr, new_opt, metrics = apply_updates(
                opt_in, grads, step_cfg.opt, param_like=trainable)
            new_params = merge_trainable(state["params"], new_tr, mask)
        if grad_compress != "none":
            new_opt = dict(new_opt, grad_residual=new_residual)
        metrics = dict(metrics, loss=loss, tokens=tokens)
        return {"params": new_params, "opt": new_opt}, metrics

    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    batch_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                                   is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(train_step,
                   in_shardings=(state_shardings, batch_shardings),
                   out_shardings=(state_shardings, None),
                   donate_argnums=(0,))
    step.injections = mapped.injections
    return step, state_shardings, batch_shardings, plan


def build_roundpipe_async_train_step(cfg: ModelConfig, mesh, step_cfg,
                                     global_batch: int, seq_len: int, *,
                                     steps_per_call: int, plan=None,
                                     overlap: bool = True):
    """Compile the cross-step staleness-1 async train program (paper §4.3,
    DESIGN.md §6): ``multi_step(state, batches) -> (state, metrics)`` runs
    ``steps_per_call`` optimizer steps back-to-back in ONE chained ring
    program — step ``T+1``'s injection streams while step ``T``'s
    gradients drain and the in-program optimizer applies them, so the
    fill/drain bubble amortizes to ``(N-1)/(I*R*S + N-1)`` (the
    ``iterations=I`` mode of ``simulate_plan``).

    ``batches`` leaves carry a leading ``(steps_per_call,)`` axis (one
    global batch per step); ``metrics['loss'/'tokens'/'grad_norm']`` come
    back per-step with shape ``(steps_per_call,)``.  The state is the same
    ``{"params", "opt"}`` pytree as the synchronous step (padded pool,
    ``init_roundpipe_state``) — checkpoints interchange freely.  The final
    step's update is applied before returning (flush), so the result
    matches ``repro.core.consistency.reference_staleness1`` over
    ``steps_per_call`` iterations.

    ``overlap=False`` degenerates to the PR-4 synchronous runtime: the
    same multi-batch calling convention driven by the unmodified one-step
    program per sub-step (staleness-0) — bit-identical to calling
    ``build_roundpipe_train_step``'s step ``steps_per_call`` times.

    ``step_cfg.lora`` selects the frozen-base variant: the in-program
    optimizer updates the ADAPTER pool only, versioned staleness-1, while
    the dense pool is read-only for the whole program (no cross-step dense
    staleness at all — injections of any step may stream it freely).  The
    result matches ``reference_staleness1`` restricted to the trainable
    adapter leaves; the base passes through bit-identical.

    ``step_cfg.pool_dtype`` streams every staleness-1 version of the pool
    quantized (the D_k update tick requantizes ``v_{k+1}`` into the
    version list); ``step_cfg.grad_compress`` runs deposits through the
    error-feedback codec with the residual threading through
    ``state["opt"]["grad_residual"]`` across the whole chained program —
    the same knobs as the synchronous step.

    Returns ``(multi_step, state_shardings, batch_shardings, plan)``;
    ``multi_step.injections`` as in the synchronous builder.
    """
    from repro.core.consistency import verify_async_ticks

    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
    pool_dtype = getattr(step_cfg, "pool_dtype", "none")
    grad_compress = getattr(step_cfg, "grad_compress", "none")
    lora = getattr(step_cfg, "lora", None)
    n = axis_size(mesh, AXIS)
    if global_batch % n:
        raise ValueError("global batch must divide the model axis")
    if plan is None:
        plan = resolve_plan(cfg, step_cfg, n)
    m_micro = getattr(step_cfg, "n_microbatches", None) or n
    rounds = plan.rounds_for(m_micro)
    if global_batch % m_micro:
        raise ValueError(
            f"global batch {global_batch} must be divisible by "
            f"n_microbatches {m_micro}")

    if not overlap:
        sync_step, state_sh, batch_sh, plan = build_roundpipe_train_step(
            cfg, mesh, step_cfg, global_batch, seq_len, plan=plan)

        def multi_step(state, batches):
            per_step = []
            for i in range(steps_per_call):
                sub = jax.tree.map(lambda x: x[i], batches)
                state, m = sync_step(state, sub)
                per_step.append(m)
            metrics = {k: jnp.stack([m[k] for m in per_step])
                       for k in ("loss", "tokens", "grad_norm")}
            metrics["step"] = per_step[-1]["step"]
            return state, metrics

        multi_step.injections = sync_step.injections
        stacked_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, P(None, *s.spec)), batch_sh)
        return multi_step, state_sh, stacked_sh, plan

    plan.validate()
    plan.validate_async(rounds)
    # the tick program the chained driver runs: hand-generated or searched
    # (either way stamped with the rotation the ring realizes)
    ticks = _select_schedule(
        step_cfg, plan, rounds, steps_per_call,
        device_scale=getattr(step_cfg, "device_scale", None))
    if ticks is None:
        ticks = plan.tick_program(rounds, steps_per_call,
                                  g0=getattr(step_cfg, "g0", 0))
    # certify the chained tick order satisfies the five §4.3 constraints
    # AND that the generated IR's annotations match the protocol replay
    verify_async_ticks(plan, rounds, steps_per_call, program=ticks)
    program = None
    if getattr(step_cfg, "prefetch", True):
        program = plan.prefetch_program(
            chunk_limit=getattr(step_cfg, "prefetch_chunk_limit", None))
        program.validate(plan)
    l_pad = pool_rows(cfg, n)

    abstract = T.abstract_params(cfg)
    if lora is not None:
        abstract = dict(abstract, lora=lora_mod.adapter_abstract(cfg, lora))
    pspecs = roundpipe_param_specs(cfg, abstract)
    if lora is None:
        ospecs = opt_state_specs(pspecs, step_cfg.opt)
    else:
        # frozen base: the in-program optimizer state covers the adapter
        # leaves only (the dense pool never updates inside the program)
        ospecs = opt_state_specs(
            trainable_leaves(pspecs, lora_mod.param_mask(pspecs)),
            step_cfg.opt)
    if grad_compress != "none":
        # the error-feedback residual rides the opt pytree through the whole
        # chained program, sharded like the pool it shadows
        ospecs = dict(ospecs, grad_residual=(
            pspecs["lora"] if lora is not None else pspecs["layers"]))
    state_specs = {"params": pspecs, "opt": ospecs}
    injections = {}
    body = functools.partial(
        roundpipe_async_forward_backward, cfg=cfg, plan=plan, n_workers=n,
        l_pad=l_pad, steps=steps_per_call, rounds=rounds, opt_cfg=step_cfg.opt,
        xent_chunk=step_cfg.xent_chunk, kv_chunk=step_cfg.kv_chunk,
        ring_grad_dtype=step_cfg.accum_dtype, prefetch_program=program,
        lora=lora, pool_dtype=pool_dtype, grad_compress=grad_compress,
        tick_program=ticks, injections=injections)

    batch_abs = {}
    if cfg.frontend:
        batch_abs["embeds"] = jax.ShapeDtypeStruct(
            (steps_per_call, global_batch, seq_len, cfg.d_model), jnp.bfloat16)
    else:
        batch_abs["tokens"] = jax.ShapeDtypeStruct(
            (steps_per_call, global_batch, seq_len), jnp.int32)
    batch_abs["labels"] = jax.ShapeDtypeStruct(
        (steps_per_call, global_batch, seq_len), jnp.int32)
    bspecs = jax.tree.map(
        lambda leaf: P(None, AXIS, *([None] * (leaf.ndim - 2))), batch_abs)
    # inside the manual region: (I, R, B_w, ...) — step and round axes
    # replicated, per-round batch dim sharded over `model`
    inner_bspecs = jax.tree.map(
        lambda leaf: P(None, None, AXIS, *([None] * (leaf.ndim - 2))),
        batch_abs)

    def multi_step(state, batches):
        # (I, G, ...) -> (I, R, G/R, ...): step i round r owns micro-batch
        # groups r*N..(r+1)*N-1 of that step's global batch
        batches = jax.tree.map(
            lambda x: x.reshape(x.shape[0], rounds, x.shape[1] // rounds,
                                *x.shape[2:]), batches)
        f = jax.shard_map(
            body, mesh=mesh, axis_names={AXIS},
            in_specs=(pspecs, ospecs, inner_bspecs, P(AXIS)),
            out_specs=(pspecs, ospecs, P()),
            check_vma=False)
        new_params, new_opt, metrics = f(
            state["params"], state["opt"], batches,
            jnp.arange(n, dtype=jnp.int32))
        return {"params": new_params, "opt": new_opt}, metrics

    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    batch_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                                   is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(multi_step,
                   in_shardings=(state_shardings, batch_shardings),
                   out_shardings=(state_shardings, None),
                   donate_argnums=(0,))
    step.injections = injections
    return step, state_shardings, batch_shardings, plan


def init_roundpipe_state(key, cfg: ModelConfig, step_cfg,
                         n_workers: int | None = None):
    """Fresh roundpipe train state; pass ``n_workers`` (the `model` axis
    size) so the layer pool is padded to shard evenly (``pad_pool``).

    With ``step_cfg.lora`` the params gain a fresh adapter pool (zero-``B``,
    so step 0 computes exactly the base model) and the optimizer state
    covers the adapter leaves only.

    With ``step_cfg.grad_compress`` the optimizer state carries the
    error-feedback residual ``opt["grad_residual"]`` — fp32 zeros shaped
    like the (padded) deposited pool."""
    params = T.init_params(key, cfg)
    lora = getattr(step_cfg, "lora", None)
    if lora is not None:
        params["lora"] = lora_mod.init_adapters(
            jax.random.fold_in(key, 0x10A), params["layers"], lora)
    if n_workers is not None:
        params = pad_pool(params, cfg, n_workers)
    if lora is None:
        opt = init_opt_state(params, step_cfg.opt)
    else:
        opt = init_opt_state(
            trainable_leaves(params, lora_mod.param_mask(params)),
            step_cfg.opt)
    if getattr(step_cfg, "grad_compress", "none") != "none":
        pool = params["lora"] if lora is not None else params["layers"]
        opt = dict(opt, grad_residual=jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), pool))
    return {"params": params, "opt": opt}


def reshape_pooled_state(state, cfg: ModelConfig, n_new: int):
    """Elastic-restore transform: re-pad every pooled leaf of ``state``
    (a checkpoint written under SOME previous worker count) to the
    ``pool_rows(cfg, n_new)`` layout.

    Only the PADDING row count depends on the worker count — the first
    ``cfg.n_layers`` rows are the model and the padding rows are exactly
    zero (never referenced by any slot, zero gradients, zero moments), so
    slice-then-repad is lossless.  The writer's pool depth is inferred
    from the tree itself (every stacked ``params['layers']`` leaf carries
    it as its leading dim), so restoring a N=4 checkpoint onto N=3 needs
    no out-of-band record of the old topology.  Applies to
    ``params['layers']`` / ``params['lora']`` and every optimizer mirror
    of them (fp32 masters, Adam moments, the error-feedback
    ``grad_residual``), identified by tree path + a leading dim equal to
    the old pool depth (Adafactor's factored stats that drop the pool dim
    pass through untouched).

    Operates on host or device arrays; callers re-place the result under
    the new mesh's shardings (``jax.device_put``) afterwards.
    """
    pooled = {"layers", "lora", "grad_residual"}
    rows_old = None
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        names = {getattr(k, "key", None) for k in path}
        if names & pooled and getattr(leaf, "ndim", 0) >= 1:
            rows_old = leaf.shape[0]
            break
    rows_new = pool_rows(cfg, n_new)
    if rows_old is None or rows_old == rows_new:
        return state
    if rows_old < cfg.n_layers:
        raise ValueError(
            f"pool depth {rows_old} in the restored state is smaller than "
            f"n_layers={cfg.n_layers}: not a padded pool for this model")

    def fix(path, leaf):
        names = {getattr(k, "key", None) for k in path}
        if not (names & pooled):
            return leaf
        if getattr(leaf, "ndim", 0) < 1 or leaf.shape[0] != rows_old:
            return leaf
        real = leaf[:cfg.n_layers]
        return jnp.pad(real, [(0, rows_new - cfg.n_layers)]
                       + [(0, 0)] * (real.ndim - 1))

    return jax.tree_util.tree_map_with_path(fix, state)
