"""ExecutionPlan compilation tests: partition -> plan -> schedule -> simulate.

Property-style over random uneven LayerCost vectors (plain `random`, seeded —
no hypothesis dependency): every auto-partitioned plan must validate, its
per-stage layer sets must exactly cover range(L), and its schedule must
simulate deadlock-free with the expected amount of total work.
"""
import random

import pytest

from repro.core.partition import LayerCost, Partition, auto_partition
from repro.core.plan import compile_plan, uniform_partition
from repro.core.schedule import validate
from repro.core.simulator import simulate, simulate_plan
from repro.core.transfer import WindowPlan

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_auto_partition_matches_reference():
    """Auto-partitioned uneven stages (incl. head-only fused slot)."""
    run_mode("qwen3-1.7b", "auto")


@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_auto_partition_nondivisible_layers():
    """n_layers % n_workers != 0: the ring staggers by stage, not layer."""
    run_mode("qwen3-1.7b", "auto", n_layers=7)


@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_handmade_uneven_partition():
    """Hand-built Partition with blocks of size 2/2/2+head/1/3 on L=6, N=4."""
    run_mode("qwen3-1.7b", "uneven")


@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_prefetch_matches_whole_block():
    """Chunked double-buffered PrefetchProgram injection vs the monolithic
    whole-block gather on an uneven plan (n_layers % N != 0): gradients and
    loss must agree (and both must match the single-program reference)."""
    run_mode("qwen3-1.7b", "prefetch", n_layers=7)


def random_layers(rng, n):
    return [LayerCost(rng.uniform(0.5, 3.0), rng.uniform(0.5, 5.0),
                      weight_bytes=rng.randrange(1, 1 << 20))
            for _ in range(n)]


class TestCompileRandomUneven:
    def test_auto_partition_plans_cover_and_simulate(self):
        rng = random.Random(0)
        for trial in range(25):
            n_layers = rng.randrange(3, 15)
            n_workers = rng.randrange(2, 6)
            m = n_workers * rng.randrange(1, 4)
            layers = random_layers(rng, n_layers)
            part = auto_partition(layers, n_devices=n_workers,
                                  n_microbatches=m)
            plan = compile_plan(part, layers, n_workers=n_workers)
            plan.validate()
            # backward slots exactly cover range(L); forward is a prefix
            bwd_ids = sorted(l for s in plan.stages[plan.n_fwd:]
                             for l in s.layers)
            assert bwd_ids == list(range(n_layers)), trial
            fwd_ids = [l for s in plan.stages[:plan.n_fwd] for l in s.layers]
            assert fwd_ids == list(range(len(fwd_ids))), trial
            # the compiled schedule is well-formed and deadlock-free
            sched = plan.schedule(m, round_size=n_workers)
            validate(sched)
            res = simulate(sched)
            assert res.makespan > 0
            total = sum(t.duration for t in sched.tasks)
            assert res.makespan >= total / n_workers - 1e-9, trial

    def test_simulate_plan_entrypoint(self):
        rng = random.Random(1)
        layers = random_layers(rng, 9)
        part = auto_partition(layers, n_devices=4, n_microbatches=4)
        plan = compile_plan(part, layers, n_workers=4)
        res = simulate_plan(plan)
        assert 0.0 <= res.bubble_ratio < 1.0


class TestHeadPseudoLayer:
    def test_head_lands_in_fused_stage(self):
        layers = [LayerCost(1.0, 2.0) for _ in range(7)] + [LayerCost(3.0, 6.0)]
        part = auto_partition(layers, n_devices=4, n_microbatches=8)
        plan = compile_plan(part, layers, n_workers=4, n_body_layers=7)
        plan.validate()
        assert plan.has_head_stage
        assert plan.fused.includes_head
        assert all(not s.includes_head for s in plan.stages if s.kind != "FB")
        # body layers still exactly covered despite the pseudo-layer
        bwd_ids = sorted(l for s in plan.stages[plan.n_fwd:] for l in s.layers)
        assert bwd_ids == list(range(7))

    def test_bad_body_count_rejected(self):
        layers = [LayerCost(1.0, 2.0) for _ in range(6)]
        part = auto_partition(layers, n_devices=2, n_microbatches=2)
        with pytest.raises(ValueError):
            compile_plan(part, layers, n_workers=2, n_body_layers=4)


class TestUniformPartition:
    def test_matches_seed_runtime_shape(self):
        plan = compile_plan(uniform_partition(8),
                            [LayerCost(1.0, 2.0)] * 8, n_workers=4)
        assert plan.n_fwd == 7
        assert plan.n_slots == 15               # (L-1) F + FB + (L-1) B
        assert plan.max_block == 1
        assert plan.fused.layers == (7,)

    def test_single_layer_model(self):
        plan = compile_plan(uniform_partition(1),
                            [LayerCost(1.0, 2.0)], n_workers=2)
        plan.validate()
        assert plan.n_fwd == 0 and plan.n_slots == 1
        simulate_plan(plan)


class TestPrefetchOrder:
    def test_window_plans_cover_all_stage_bytes(self):
        rng = random.Random(2)
        layers = random_layers(rng, 10)
        part = auto_partition(layers, n_devices=4, n_microbatches=4)
        plan = compile_plan(part, layers, n_workers=4)
        window_plans = plan.prefetch()
        assert len(window_plans) == plan.n_slots
        for stage, wp in zip(plan.stages, window_plans):
            assert isinstance(wp, WindowPlan)
            want = sum(layers[l].weight_bytes for l in stage.layers)
            assert wp.total == want

    def test_head_bytes_in_fused_window(self):
        layers = [LayerCost(1.0, 2.0, weight_bytes=100) for _ in range(5)]
        layers += [LayerCost(4.0, 8.0, weight_bytes=1000)]       # head
        part = auto_partition(layers, n_devices=2, n_microbatches=2)
        plan = compile_plan(part, layers, n_workers=2, n_body_layers=5)
        wp = plan.prefetch()[plan.n_fwd]
        assert wp.total == 100 * plan.fused.size + 1000


class TestPrefetchProgram:
    def _plan(self, n_layers=10, n_workers=4, seed=3):
        rng = random.Random(seed)
        layers = random_layers(rng, n_layers)
        part = auto_partition(layers, n_devices=n_workers,
                              n_microbatches=n_workers)
        return compile_plan(part, layers, n_workers=n_workers), layers

    def test_upload_tables_cover_every_row(self):
        plan, layers = self._plan()
        prog = plan.prefetch_program()
        prog.validate(plan)          # byte coverage per (slot, layer)
        assert prog.n_slots == plan.n_slots
        # a chunked program still covers exactly
        big = max(int(c.weight_bytes) for c in plan.layer_costs)
        chunked = plan.prefetch_program(chunk_limit=max(1, big // 4))
        chunked.validate(plan)
        assert sum(len(t) for t in chunked.uploads) > \
            sum(len(t) for t in prog.uploads)

    def test_owner_and_pool_row_match_padded_pool(self):
        plan, _ = self._plan(n_layers=7, n_workers=4)   # 7 % 4 != 0
        per = -(-plan.n_layers // plan.n_workers)
        for table in plan.prefetch_program().uploads:
            for cu in table:
                if cu.layer < 0:
                    continue
                assert cu.owner == cu.layer // per
                assert cu.pool_row == cu.layer % per
                assert 0 <= cu.owner < plan.n_workers

    def test_window_major_order_and_row_bounds(self):
        plan, _ = self._plan()
        prog = plan.prefetch_program()
        for spec, table in zip(plan.stages, prog.uploads):
            windows = [cu.window for cu in table]
            assert windows == sorted(windows)            # window-major
            for cu in table:
                if cu.row >= 0:
                    assert 0 <= cu.row < max(spec.size, 1)

    def test_head_chunks_are_budget_only(self):
        layers = [LayerCost(1.0, 2.0, weight_bytes=64) for _ in range(5)]
        layers += [LayerCost(4.0, 8.0, weight_bytes=4096)]
        part = auto_partition(layers, n_devices=2, n_microbatches=2)
        plan = compile_plan(part, layers, n_workers=2, n_body_layers=5)
        prog = plan.prefetch_program()
        fused_table = prog.uploads[plan.n_fwd]
        head = [cu for cu in fused_table if cu.layer < 0]
        assert head and all(cu.row == -1 and cu.owner == -1 for cu in head)
        assert sum(cu.bytes for cu in head) == 4096

    def test_capacity_threads_through_to_halving(self):
        """A slot of two 1.5x-capacity layers in 3 windows needs the §4.2.2
        chunk-limit halving (capacity-sized chunks LPT-pack to 1.5x the
        cap); the program must compile, fit, and still cover every row."""
        layers = [LayerCost(1.0, 2.0, weight_bytes=150) for _ in range(4)]
        part = Partition(fwd_stages=((0, 1),), bwd_stages=((2, 3), (0, 1)),
                         t_max=6.0, objective=0.0, n_stages=3)
        plan = compile_plan(part, layers, n_workers=2)
        prog = plan.prefetch_program(n_windows=3, window_capacity_bytes=100)
        prog.validate(plan)
        assert prog.max_window_load <= 100
        assert prog.window_capacity_bytes == 100
        assert all(wp.chunk_limit == 50 for wp in prog.window_plans)

    def test_stage_bytes_matches_prefetch_totals(self):
        plan, _ = self._plan()
        prog = plan.prefetch_program()
        assert tuple(wp.total for wp in prog.window_plans) == plan.stage_bytes

    def test_mismatched_plan_rejected(self):
        plan_a, _ = self._plan(n_layers=10, seed=3)
        plan_b, _ = self._plan(n_layers=9, seed=4)
        prog = plan_a.prefetch_program()
        with pytest.raises(ValueError):
            prog.validate(plan_b)


class TestPlanFromConfig:
    """Architecture-derived default plans (the StepConfig partition=None path)."""

    def _cfg(self):
        from repro.configs import smoke_config
        from repro.models.config import get_config
        return smoke_config(get_config("qwen3-1.7b"))

    def test_auto_plan_has_head_stage(self):
        from repro.core.plan import plan_from_config
        cfg = self._cfg()
        plan = plan_from_config(cfg, 4)
        plan.validate()
        assert plan.has_head_stage and plan.fused.includes_head
        assert plan.n_layers == cfg.n_layers
        simulate_plan(plan)

    def test_explicit_headless_partition_inferred(self):
        from repro.core.plan import plan_from_config
        cfg = self._cfg()
        plan = plan_from_config(cfg, 4,
                                partition=uniform_partition(cfg.n_layers))
        plan.validate()
        assert not plan.has_head_stage
        assert plan.max_block == 1


class TestValidationRejects:
    def test_noncontiguous_slot(self):
        layers = [LayerCost(1.0, 2.0)] * 4
        bad = Partition(fwd_stages=((0, 2),), bwd_stages=((3,), (1,), (0, 2)),
                        t_max=3.0, objective=0.0, n_stages=4)
        with pytest.raises(ValueError):
            compile_plan(bad, layers, n_workers=2)

    def test_forward_gap(self):
        layers = [LayerCost(1.0, 2.0)] * 4
        bad = Partition(fwd_stages=((1, 2),), bwd_stages=((3,), (1, 2), (0,)),
                        t_max=3.0, objective=0.0, n_stages=4)
        with pytest.raises(ValueError):
            compile_plan(bad, layers, n_workers=2)

    def test_empty_backward_stage(self):
        """An empty B slot would double-deposit the embedding gradient at
        runtime (StageSpec.start == 0 for empty tuples) — must not validate."""
        layers = [LayerCost(1.0, 2.0)] * 4
        bad = Partition(fwd_stages=((0, 1),), bwd_stages=((2, 3), (), (0, 1)),
                        t_max=3.0, objective=0.0, n_stages=4)
        with pytest.raises(ValueError, match="empty"):
            compile_plan(bad, layers, n_workers=2)
