"""The parameterized ring machine: ONE implementation of every slot-ring
primitive both dispatch drivers execute (DESIGN.md §8).

Before this module existed, ``roundpipe_forward_backward`` and
``roundpipe_async_forward_backward`` each carried private copies of the
upload / promote / stage-forward / deposit helpers, so every new
capability (quantized pool, compressed deposits, standby caching) had to
be ported twice or stayed sync-only.  The refactor inverts that: the
helpers live HERE exactly once — a CI gate (``scripts/check_ring_dedup.py``)
asserts no second definition ever reappears in ``src/repro/core`` — and the
two drivers in ``core/dispatch.py`` reduce to thin loops over a generated
:class:`~repro.core.schedule.TickProgram`, differing only in the three
parameterization axes:

* **source pool** — every gather/upload takes the pool (or its flattened
  leaves) per call: the sync driver passes the live pool, the async driver
  passes the staleness-1 version list entry the tick's injection step reads.
* **payload codec** — dense leaves (``assemble_block`` / ``upload_slot`` /
  ``promote_standby``) or blockwise-absmax codes+scales
  (``quantize_pool`` / ``upload_slot_q`` / ``dequant_block`` /
  ``assemble_block_q``) with the fused dequant-on-upload kernel at promote
  time; deposits are exact fp32 (``deposit_plain``) or error-feedback int8
  (``deposit_ef``).  On a one-worker ring every slot is a ``local_slot``:
  ``local_block`` reads it in place from the pool, with no standby.
* **accumulator family** — :class:`StepAccum` (one buffer per quantity,
  read once at program end — the synchronous shape) or :class:`ParityAccum`
  (2-deep buffers indexed by the traced work-step's parity — the async
  shape, where a worker may run step ``k+1``'s slots before step ``k``'s
  deposit-complete tick ``D_k``).

Everything in a :class:`RingMachine` is static per trace (plan structure,
chunk tables, leaf shapes); traced operands flow through method arguments,
so constructing one inside a ``shard_map`` body is free and the emitted ops
are identical to the pre-refactor closures — the subprocess equivalence
matrix asserts the sync path bit-exactly.

Each primitive traces under its ``rp.*`` scope (``runtime/spans.py``:
``rp.shift``, ``rp.upload``, ``rp.promote``, ``rp.quantize``,
``rp.deposit``), so both drivers name their ops from this one place.
"""
from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp

from repro.core.partition import POOL_DTYPE_BITS
from repro.kernels import ops as kops
from repro.kernels.dequant import quantize_rows
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm
from repro.optim.compress import compress_int8, decompress_int8
from repro.runtime import spans

AXIS = "model"


def shift_perm(n, g0=0):
    """Open-ring permutation: LOGICAL position i -> i+1, logical N-1 drops
    off.  ``g0`` rotates logical onto physical workers (paper slot->worker
    map ``(g0 + i) mod N``): physical ``(g0+i)%n -> (g0+i+1)%n``, so the
    open edge sits between logical N-1 and logical 0 wherever they
    physically live.  ``g0=0`` emits exactly the legacy perm list."""
    return [((g0 + i) % n, (g0 + i + 1) % n) for i in range(n - 1)]


def ring_add(tree_a, tree_b):
    return jax.tree.map(jnp.add, tree_a, tree_b)


def zeros_block(layers_local, depth):
    """A zero ring buffer shaped like ``depth`` stacked pool rows."""
    return jax.tree.map(
        lambda a: jnp.zeros((depth,) + a.shape[1:], a.dtype), layers_local)


def block_row(block, k):
    return jax.tree.map(lambda a: a[k], block)


def gbuf_add(gbuf, delta):
    """Accumulate a vjp's block gradients into the traveling buffer (in the
    buffer's own dtype — fp32 for exactness, bf16 under §Perf C1b)."""
    return jax.tree.map(lambda a, d: a + d.astype(a.dtype), gbuf, delta)


# ---------------------------------------------------------------------------
# Accumulator families (replicated-param grads, loss, token counts)
# ---------------------------------------------------------------------------

class StepAccum:
    """Per-step accumulators: one buffer per quantity, accumulated across
    every tick and read once at the end of the program — the synchronous
    driver's shape (``slot`` is ignored everywhere)."""
    depth = 0                      # no leading parity axis

    @staticmethod
    def zeros(shape, dtype):
        return jnp.zeros(shape, dtype)

    @staticmethod
    def tree_zeros(tree, dtype):
        return jax.tree.map(lambda a: jnp.zeros(a.shape, dtype), tree)

    @staticmethod
    def add(acc, val, slot):
        return acc + val

    @staticmethod
    def add_f32(acc, val, slot):
        return acc + val.astype(jnp.float32)

    @staticmethod
    def tree_add_f32(acc, val, slot):
        return jax.tree.map(lambda a, d: a + d.astype(jnp.float32), acc, val)

    @staticmethod
    def token_add(acc, tok, val, slot):
        return acc.at[tok].add(val)

    @staticmethod
    def read(acc, slot):
        return acc

    @staticmethod
    def tree_read(acc, slot):
        return acc


class ParityAccum:
    """2-deep parity accumulators for the async driver: slot ``k % 2`` holds
    what step ``k``'s work writes.  On shallow plans (``Sf < N-1`` or
    ``S < N``) a worker starts step ``k+1``'s fused/backward slots before
    step ``k``'s deposit-complete tick ``D_k``, so a single buffer would
    leak early step-``k+1`` contributions into step ``k``'s snapshot; step
    ``k+2`` (the slot's next tenant) starts no earlier than tick
    ``(k+2)·R·S > D_k``, so two buffers always suffice."""
    depth = 2

    @staticmethod
    def zeros(shape, dtype):
        return jnp.zeros((2,) + shape, dtype)

    @staticmethod
    def tree_zeros(tree, dtype):
        return jax.tree.map(
            lambda a: jnp.zeros((2,) + a.shape, dtype), tree)

    @staticmethod
    def add(acc, val, slot):
        return acc.at[slot].add(val)

    @staticmethod
    def add_f32(acc, val, slot):
        return acc.at[slot].add(val.astype(jnp.float32))

    @staticmethod
    def tree_add_f32(acc, val, slot):
        return jax.tree.map(
            lambda a, d: a.at[slot].add(d.astype(jnp.float32)), acc, val)

    @staticmethod
    def token_add(acc, tok, val, slot):
        return acc.at[slot, tok].add(val)

    @staticmethod
    def read(acc, slot):
        return acc[slot]

    @staticmethod
    def tree_read(acc, slot):
        return jax.tree.map(lambda a: a[slot], acc)

    @staticmethod
    def reset(acc, slot):
        return acc.at[slot].set(0)

    @staticmethod
    def tree_reset(acc, slot):
        return jax.tree.map(lambda a: a.at[slot].set(0.0), acc)


# ---------------------------------------------------------------------------
# The machine
# ---------------------------------------------------------------------------

class RingMachine:
    """Static ring plumbing for one compiled plan inside a shard_map body.

    Construction captures only trace-static structure (slot specs, chunk
    tables, pool leaf shapes) plus the worker-id iota used for owner gating;
    every traced pool / standby / gradient operand is a method argument, so
    the sync and async drivers share these methods verbatim while feeding
    them different pools (live vs per-version), payloads (dense vs
    codes+scales) and accumulator families.
    """

    def __init__(self, *, cfg: ModelConfig, plan, n_workers: int, l_pad: int,
                 worker_id, pool_template, xent_chunk: int = 256,
                 kv_chunk: int = 1024, prefetch_program=None,
                 pool_dtype: str = "none", g0: int = 0,
                 injections: dict | None = None):
        self.cfg = cfg
        self.plan = plan
        self.n = n_workers
        self.per = l_pad // n_workers
        self.worker_id = worker_id
        # g0 rotates LOGICAL ring positions onto physical workers (paper
        # slot->worker map (g0 + i) mod N): injection enters at physical
        # ``inj`` (logical 0), the reduced wave exits at physical ``tail``
        # (logical N-1).  Pool ownership stays physical — the pool shards
        # never move, only the ring's entry/exit endpoints rotate.  g0=0
        # emits exactly the legacy perms (bit-identical programs).
        if not 0 <= g0 < n_workers:
            raise ValueError(f"g0 must be in [0, {n_workers}), got {g0}")
        self.g0 = g0
        self.inj = g0
        self.tail = (g0 + n_workers - 1) % n_workers
        self.xent_chunk = xent_chunk
        self.kv_chunk = kv_chunk
        self.prefetch_program = prefetch_program
        self.kmax = plan.max_block
        self.fused_spec = plan.fused
        self.pool_dtype = pool_dtype
        if pool_dtype != "none" and pool_dtype not in POOL_DTYPE_BITS:
            raise ValueError(f"unknown pool_dtype {pool_dtype!r}; expected "
                             f"none|{'|'.join(POOL_DTYPE_BITS)}")

        leaves, self.pool_def = jax.tree_util.tree_flatten(pool_template)
        self.leaf_shapes = [l.shape[1:] for l in leaves]
        self.leaf_dtypes = [l.dtype for l in leaves]
        self.leaf_elems = [int(math.prod(s)) for s in self.leaf_shapes]
        self.leaf_offs = list(
            itertools.accumulate([0] + self.leaf_elems[:-1]))
        self.row_elems = sum(self.leaf_elems)
        # trace-time tally of the blocks injected into the rings (the weight
        # ring and, frozen-base, the adapter ring): ``local`` read in place
        # from the injection worker's own pool, ``streamed`` through a
        # standby fill or a whole-block gather.  The step builders pass one
        # dict and expose it on the built step; each trace restarts it.
        self.injections = {} if injections is None else injections
        self.injections.update(local=0, streamed=0)

    # ---- ring hop ----------------------------------------------------------
    @spans.scope(spans.SHIFT)
    def shift(self, tree):
        """One open-ring hop: every row moves one logical position up the
        ring (logical N-1 exits); ``g0`` decides where that lives
        physically."""
        return jax.tree.map(
            lambda a: jax.lax.ppermute(a, AXIS, shift_perm(self.n, self.g0)),
            tree)

    # ---- stage compute -----------------------------------------------------
    def stage_fwd(self, block, n_active, x):
        """Fold a padded block over x; inactive rows are identity.  The
        single-layer fast path skips the scan wrapper — the seed runtime's
        exact per-tick compute shape (MoE archs compile slowly under an
        extra scan level around each vjp)."""
        if self.kmax == 1:
            y = T.layer_forward(x, block_row(block, 0), self.cfg,
                                kv_chunk=self.kv_chunk)
            return jnp.where(n_active > 0, y, x)

        def body(xc, inp):
            k, lw = inp
            y = T.layer_forward(xc, lw, self.cfg, kv_chunk=self.kv_chunk)
            return jnp.where(k < n_active, y, xc), None

        out, _ = jax.lax.scan(body, x, (jnp.arange(self.kmax), block))
        return out

    def fused_loss(self, block, fnorm, hw, x, labels_cur):
        """The FB slot's forward: (optional) deepest body block + final norm
        + chunked LM-head softmax-xent; the token count rides as vjp aux."""
        if self.fused_spec.size:               # static: fused body block
            x = self.stage_fwd(block, self.fused_spec.size, x)
        h = apply_norm(x, fnorm, self.cfg.norm_kind, self.cfg.norm_eps)
        tot, cnt = T.chunked_softmax_xent(h, hw, labels_cur,
                                          chunk=self.xent_chunk)
        return tot, cnt

    # ---- local-row injection -----------------------------------------------
    def local_slot(self, spec):
        """Whether slot ``spec``'s injection block is read in place from the
        injection worker's own pool shard, with no standby and no gather.

        True on a one-worker ring, where the ownership table puts every row
        on the injecting worker.  An N>1 slot whose rows all live on the
        injector is not taken: in the SPMD body every other worker would
        then need a gated select to add zeros to the ring.

        Assumes the pool lives in device memory.  A pool in host memory
        (``pinned_host``) makes the upload a real host-to-device transfer,
        which must go through the standby path (``upload_slot``)."""
        return self.n == 1 and all(
            lid // self.per == self.inj for lid in spec.layers)

    def uses_standby(self, slot_idx):
        """Whether slot ``slot_idx`` is injected through the double-buffered
        standby (``upload_slot`` then ``promote_standby``/``dequant_block``):
        under a prefetch program, unless it is a ``local_slot``."""
        return (self.prefetch_program is not None
                and not self.local_slot(self.plan.stages[slot_idx]))

    @spans.scope(spans.PROMOTE)
    def local_block(self, spec, src, quantized: bool = False):
        """Slot ``spec``'s injection block straight from the local pool rows
        (``local_slot``): a static slice, with padding rows repeating the
        slot's first layer exactly as ``promote_standby`` does — on a
        one-slot ring the slice is the whole pool.  ``src`` is a dense pool
        pytree, or with ``quantized`` a ``(codes, scales)`` pair, whose rows
        go through ``dequant_block`` in place."""
        if not spec.layers:
            return None
        self.injections["local"] += 1
        lo = spec.start % self.per

        def rows(a):
            blk = a[lo:lo + spec.size]
            if spec.size < self.kmax:
                pad = jnp.broadcast_to(
                    blk[0], (self.kmax - spec.size,) + blk.shape[1:])
                blk = jnp.concatenate([blk, pad])
            return blk

        if quantized:
            codes, scales = src
            return self.dequant_block(rows(codes), rows(scales), spec)
        return jax.tree.map(rows, src)

    # ---- dense payload codec -----------------------------------------------
    @spans.scope(spans.PROMOTE)
    def assemble_block(self, spec, src_pool):
        """Gather slot ``spec``'s layers from their pool owners to the
        injection worker (physical ``self.inj``, logical 0 — static
        plumbing).  Padding rows repeat the first layer so every
        ring row holds real weights (finite jacobians for the masked
        lanes).  ``src_pool`` is the parameterization point: the live pool
        (sync), a staleness-1 version entry (async), or the adapter pool
        (frozen-base LoRA).  A ``local_slot`` reads its rows in place."""
        if self.local_slot(spec):
            return self.local_block(spec, src_pool)
        rows = []
        for lid in spec.layers:
            owner, idx = divmod(lid, self.per)
            inj = jax.tree.map(lambda a: a[idx], src_pool)
            rows.append(jax.lax.ppermute(inj, AXIS, [(owner, self.inj)]))
        if not rows:
            return None
        self.injections["streamed"] += 1
        rows += [rows[0]] * (self.kmax - len(rows))
        return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)

    def chunk_elem_range(self, cu):
        """Map the chunk's plan-byte range to an element range of the actual
        row (the cost-model byte total need not match the array dtype)."""
        if cu.parent_bytes <= 0:
            return 0, self.row_elems
        return (cu.lo * self.row_elems // cu.parent_bytes,
                cu.hi * self.row_elems // cu.parent_bytes)

    @spans.scope(spans.UPLOAD)
    def upload_slot(self, stand, slot_idx, pool_leaves):
        """Stream slot ``slot_idx``'s chunks into the standby leaves, one
        ppermute per (chunk x overlapped leaf), in LPT window order.  The
        chunk byte-ranges partition each row, so the union of writes equals
        the whole-block gather exactly.  ``pool_leaves`` is the flattened
        source pool (live or versioned)."""
        if self.plan.stages[slot_idx].layers:
            self.injections["streamed"] += 1
        stand = list(stand)
        for cu in self.prefetch_program.uploads[slot_idx]:
            if cu.row < 0:          # replicated LM head: never ring-resident
                continue
            a, b = self.chunk_elem_range(cu)
            for i, (off, ne) in enumerate(zip(self.leaf_offs,
                                              self.leaf_elems)):
                la, lb = max(a - off, 0), min(b - off, ne)
                if la >= lb:
                    continue
                src = jax.lax.slice(
                    pool_leaves[i][cu.pool_row].reshape(-1), (la,), (lb,))
                src = jax.lax.ppermute(src, AXIS, [(cu.owner, self.inj)])
                flat = stand[i].reshape(self.kmax, -1)
                stand[i] = flat.at[cu.row, la:lb].set(src).reshape(
                    stand[i].shape)
        return stand

    @spans.scope(spans.PROMOTE)
    def promote_standby(self, stand, spec):
        """Standby -> injection block: replicate row 0 into padding rows
        (same real-weight padding as ``assemble_block``)."""
        leaves = []
        for l in stand:
            if spec.size < self.kmax:
                pad = jnp.broadcast_to(
                    l[0], (self.kmax - spec.size,) + l.shape[1:])
                l = l.at[spec.size:].set(pad)
            leaves.append(l)
        return jax.tree_util.tree_unflatten(self.pool_def, leaves)

    @spans.scope(spans.UPLOAD)
    def zeros_standby(self):
        return [jnp.zeros((self.kmax,) + s, d)
                for s, d in zip(self.leaf_shapes, self.leaf_dtypes)]

    # ---- quantized payload codec -------------------------------------------
    @spans.scope(spans.QUANTIZE)
    def quantize_pool(self, pool):
        """One quantization pass over a LOCAL pool shard: the "host-side"
        codes+scales image whose bytes the up lane ships
        (``plan.stage_bytes`` counts exactly this payload).  The sync
        driver runs it once per step over the live pool; the async driver
        folds a re-quantization of each fresh version into its ``D_T``
        update tick."""
        leaves = jax.tree_util.tree_flatten(pool)[0]
        # concatenate in the pool's own dtype: quantize_rows widens per
        # block, so no fp32 copy of the whole pool is materialized
        pool_cat = jnp.concatenate(
            [l.reshape(self.per, -1) for l in leaves], axis=1)
        return quantize_rows(pool_cat, bits=POOL_DTYPE_BITS[self.pool_dtype])

    @spans.scope(spans.UPLOAD)
    def zeros_standby_q(self, qpair):
        q_codes, q_scales = qpair
        return (jnp.zeros((self.kmax, q_codes.shape[1]), q_codes.dtype),
                jnp.zeros((self.kmax, q_scales.shape[1]), jnp.float32))

    @spans.scope(spans.UPLOAD)
    def upload_slot_q(self, stand, slot_idx, qpair):
        """Quantized standby fill: each ChunkUpload's plan-byte range maps
        proportionally onto the CODE columns (endpoints are exact, so chunk
        boundaries still partition every row); the fp32 scale row rides the
        slot's first chunk (its 4B/block are part of the plan's quantized
        byte total)."""
        if self.plan.stages[slot_idx].layers:
            self.injections["streamed"] += 1
        q_codes, q_scales = qpair
        code_len = q_codes.shape[1]
        codes, scales = stand
        for cu in self.prefetch_program.uploads[slot_idx]:
            if cu.row < 0:          # replicated LM head: never streamed
                continue
            if cu.parent_bytes <= 0:
                la, lb = 0, code_len
            else:
                la = cu.lo * code_len // cu.parent_bytes
                lb = cu.hi * code_len // cu.parent_bytes
            if la < lb:
                src = jax.lax.slice(q_codes[cu.pool_row], (la,), (lb,))
                src = jax.lax.ppermute(src, AXIS, [(cu.owner, self.inj)])
                codes = codes.at[cu.row, la:lb].set(src)
            if cu.lo == 0:
                srow = jax.lax.ppermute(q_scales[cu.pool_row], AXIS,
                                        [(cu.owner, self.inj)])
                scales = scales.at[cu.row].set(srow)
        return codes, scales

    @spans.scope(spans.PROMOTE)
    def dequant_block(self, codes, scales, spec):
        """Fused dequant-on-upload: codes+scales -> injection block in the
        pool's compute dtype (``kernels.ops.dequant_rows``), split back into
        the pool's leaf structure with the same real-weight padding rows as
        ``assemble_block``."""
        flat = kops.dequant_rows(                   # (kmax, nb*QB)
            codes, scales, out_dtype=jnp.result_type(*self.leaf_dtypes))
        flat = flat[:, :self.row_elems]
        if spec.size < self.kmax:
            pad = jnp.broadcast_to(
                flat[0], (self.kmax - spec.size,) + flat.shape[1:])
            flat = flat.at[spec.size:].set(pad)
        leaves = [
            jax.lax.slice(flat, (0, off), (self.kmax, off + ne)).reshape(
                (self.kmax,) + s).astype(d)
            for s, d, off, ne in zip(self.leaf_shapes, self.leaf_dtypes,
                                     self.leaf_offs, self.leaf_elems)]
        return jax.tree_util.tree_unflatten(self.pool_def, leaves)

    @spans.scope(spans.PROMOTE)
    def assemble_block_q(self, spec, qpair):
        """Whole-block fallback, quantized: gather full code+scale rows from
        their owners, then one fused dequant.  A ``local_slot`` reads its
        rows in place."""
        if not spec.layers:
            return None
        if self.local_slot(spec):
            return self.local_block(spec, qpair, quantized=True)
        self.injections["streamed"] += 1
        q_codes, q_scales = qpair
        crows, srows = [], []
        for lid in spec.layers:
            owner, idx = divmod(lid, self.per)
            crows.append(
                jax.lax.ppermute(q_codes[idx], AXIS, [(owner, self.inj)]))
            srows.append(
                jax.lax.ppermute(q_scales[idx], AXIS, [(owner, self.inj)]))
        crows += [crows[0]] * (self.kmax - len(crows))
        srows += [srows[0]] * (self.kmax - len(srows))
        return self.dequant_block(jnp.stack(crows), jnp.stack(srows), spec)

    # ---- gradient deposits (slot exits the ring at logical worker N-1) -----
    @spans.scope(spans.DEPOSIT)
    def deposit_plain(self, pool_grads, row, owner, idx):
        """Exact fp32 deposit: the fully ring-reduced row crosses the down
        lane tail -> owner and sums into the owner's accumulator row
        (successive rounds'/steps' waves ``.at[].add`` into the same row)."""
        arriving = jax.tree.map(
            lambda a: jax.lax.ppermute(a, AXIS, [(self.tail, owner)]), row)
        return jax.tree.map(
            lambda pg, ar: pg.at[idx].add(ar.astype(jnp.float32)),
            pool_grads, arriving)

    @spans.scope(spans.DEPOSIT)
    def deposit_ef(self, pg_tree, res_tree, row, owner, idx):
        """Error-feedback int8 deposit (DESIGN.md §7).  The tail worker
        compresses the fully ring-reduced row PLUS the row's carried
        residual; the code+scale payload is what crosses the down lane to
        the pool owner, which dequantizes into its accumulator and stores
        the fresh residual for the next deposit into this row.  (In this
        SPMD harness the residual round-trips owner->tail->owner; the real
        system keeps it host-side at the tail — see DESIGN.md §7.)"""
        tail = self.tail
        pg_leaves, pg_def = jax.tree_util.tree_flatten(pg_tree)
        res_leaves = jax.tree_util.tree_flatten(res_tree)[0]
        row_leaves = jax.tree_util.tree_flatten(row)[0]
        new_pg, new_res = [], []
        for pg, res, rw in zip(pg_leaves, res_leaves, row_leaves):
            res_row = jax.lax.ppermute(res[idx], AXIS, [(owner, tail)])
            codes, cscale, fresh = compress_int8(
                rw.astype(jnp.float32), res_row)
            codes = jax.lax.ppermute(codes, AXIS, [(tail, owner)])
            cscale = jax.lax.ppermute(cscale, AXIS, [(tail, owner)])
            fresh = jax.lax.ppermute(fresh, AXIS, [(tail, owner)])
            deq = decompress_int8(codes, cscale, rw.shape)
            new_pg.append(pg.at[idx].add(deq))
            # every worker runs this SPMD block, but the ppermute delivers
            # ``fresh`` only to the owner — everyone else receives zeros.
            # The grad add is naturally a no-op there (deq == 0), but a
            # bare .set would CLOBBER the non-owner's own residual row at
            # this local index (it shadows a different layer), so gate it.
            keep = jnp.where(self.worker_id == owner, fresh, res[idx])
            new_res.append(res.at[idx].set(keep))
        return (jax.tree_util.tree_unflatten(pg_def, new_pg),
                jax.tree_util.tree_unflatten(pg_def, new_res))
