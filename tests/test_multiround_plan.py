"""Multi-round steady-state plan layer (ISSUE 4): the round-stitched tick
table both consumers follow, its agreement with the schedule generator's
dispatch order, and the paper's bubble -> 0 claim as rounds grow.

These are the fast-tier complements of the slow subprocess equivalence
proofs in this file and ``test_models_lora.py`` (modes ``rounds`` /
``rounds-lora``), which prove the dispatch runtime executes this exact
order numerically.
"""
import random

import pytest

from repro.configs import smoke_config
from repro.core.partition import LayerCost, auto_partition
from repro.core.plan import compile_plan, plan_from_config
from repro.core.schedule import dispatch_slot_order, validate
from repro.core.simulator import simulate_plan
from repro.models.config import get_config

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_multiround_accumulation_matches_full_batch():
    """Multi-round steady state: for R in {1, 2, 3} an
    R-round gradient-accumulated step (M = R*N micro-batches stitched
    back-to-back in R*S + N - 1 ticks) on the uneven 7-layer/4-worker auto
    plan must per-leaf allclose a single-program full-batch reference over
    the same M micro-batches, R = 1 must be BIT-identical to the legacy
    single-round path, and the schedule generator must dispatch the exact
    round-stitched tick order the runtime executes."""
    run_mode("qwen3-1.7b", "rounds", n_layers=7)


def random_plan(rng, n_layers=None, n_workers=None):
    n_layers = n_layers or rng.randrange(3, 12)
    n_workers = n_workers or rng.randrange(2, 6)
    layers = [LayerCost(rng.uniform(0.5, 3.0), rng.uniform(0.5, 5.0),
                        weight_bytes=rng.randrange(1, 1 << 20))
              for _ in range(n_layers)]
    part = auto_partition(layers, n_devices=n_workers,
                          n_microbatches=n_workers)
    return compile_plan(part, layers, n_workers=n_workers)


class TestTickTable:
    def test_stitching_and_drain(self):
        rng = random.Random(7)
        for _ in range(10):
            plan = random_plan(rng)
            s, n = plan.n_slots, plan.n_workers
            for rounds in (1, 2, 3, 5):
                table = plan.tick_table(rounds)
                assert len(table) == rounds * s + n - 1
                live, drain = table[:rounds * s], table[rounds * s:]
                # one (round, slot) per live tick, slots modulo S in order
                assert list(live) == [divmod(t, s) for t in range(rounds * s)]
                # the N-1 drain ticks are paid ONCE per step, at the end
                assert list(drain) == [None] * (n - 1)

    def test_single_round_is_plain_slot_order(self):
        plan = random_plan(random.Random(1))
        table = plan.tick_table(1)
        live = [e for e in table if e is not None]
        assert live == [(0, j) for j in range(plan.n_slots)]

    def test_rejects_nonpositive_rounds(self):
        plan = random_plan(random.Random(2))
        for bad in (0, -1):
            with pytest.raises(ValueError, match="rounds"):
                plan.tick_table(bad)

    def test_rounds_for_validates_multiples(self):
        plan = random_plan(random.Random(3), n_workers=4)
        assert plan.rounds_for(4) == 1
        assert plan.rounds_for(12) == 3
        with pytest.raises(ValueError, match="multiple"):
            plan.rounds_for(6)
        with pytest.raises(ValueError, match="micro-batch group per worker"):
            plan.rounds_for(2)


class TestScheduleConsumesTickTable:
    """`plan.schedule` (what `simulate_plan` times) and the dispatch runtime
    (which iterates `plan.tick_table`) must follow the SAME round-stitched
    order: the schedule's per-slot dispatch sequence, deduped, is exactly
    the tick table's live entries."""

    def test_dispatch_order_matches_tick_table(self):
        rng = random.Random(11)
        for _ in range(8):
            plan = random_plan(rng)
            n = plan.n_workers
            for rounds in (1, 2, 4):
                sched = plan.schedule(rounds * n, round_size=n)
                validate(sched)
                table = plan.tick_table(rounds)
                assert dispatch_slot_order(sched, n) == \
                    [e for e in table if e is not None]

    def test_simulate_plan_accepts_stitched_microbatches(self):
        plan = random_plan(random.Random(13), n_workers=4)
        res = simulate_plan(plan, 12, round_size=4)
        assert 0.0 <= res.bubble_ratio < 1.0


class TestCrossStepTickTable:
    """ISSUE 5: optimizer steps chain like rounds — ``tick_table(R, I)``
    stitches I*R*S live ticks with ONE trailing drain, the schedule
    generator (``iterations > 1``, g0 advancing) dispatches the identical
    order, and the simulated cross-step bubble undercuts the per-step
    synchronous bubble on real workload cost models."""

    def test_stitching_across_steps(self):
        rng = random.Random(23)
        for _ in range(6):
            plan = random_plan(rng)
            s, n = plan.n_slots, plan.n_workers
            for rounds, iters in ((1, 3), (2, 2), (3, 4)):
                table = plan.tick_table(rounds, iters)
                live = iters * rounds * s
                assert len(table) == live + n - 1
                assert list(table[:live]) == [divmod(t, s)
                                              for t in range(live)]
                assert list(table[live:]) == [None] * (n - 1)
                # iterations=1 is exactly the table
                assert plan.tick_table(rounds, 1) == plan.tick_table(rounds)

    def test_rejects_nonpositive_iterations(self):
        plan = random_plan(random.Random(29))
        with pytest.raises(ValueError, match="iterations"):
            plan.tick_table(1, 0)

    def test_schedule_dispatches_crossstep_order(self):
        rng = random.Random(31)
        for _ in range(5):
            plan = random_plan(rng)
            n = plan.n_workers
            for rounds, iters in ((1, 3), (2, 2)):
                sched = plan.schedule(rounds * n, round_size=n,
                                      iterations=iters)
                validate(sched)
                table = plan.tick_table(rounds, iters)
                assert dispatch_slot_order(sched, n,
                                           rounds_per_iteration=rounds) == \
                    [e for e in table if e is not None]

    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "llama-3.1-8b"])
    def test_crossstep_bubble_below_per_step_sync(self, arch):
        cfg = smoke_config(get_config(arch))
        n = 4
        plan = plan_from_config(cfg, n)
        sync = simulate_plan(plan, 2 * n, round_size=n).bubble_ratio
        chained = [simulate_plan(plan, 2 * n, round_size=n,
                                 iterations=i).bubble_ratio
                   for i in (2, 3, 4)]
        assert all(c < sync for c in chained), (sync, chained)
        assert all(b < a for a, b in zip(chained, chained[1:])), chained

    def test_uniform_crossstep_matches_formula(self):
        """Uniform slot costs: the chained bubble is exactly
        (N-1)/(I*R*S + N-1) — the fill/drain amortized over every step
        (DESIGN.md §6)."""
        from repro.core.plan import uniform_partition
        from repro.core.schedule import theoretical_bubble_crossstep

        n, n_layers = 4, 9
        layers = [LayerCost(1.0, 0.0) for _ in range(n_layers)]
        plan = compile_plan(uniform_partition(n_layers, grad_ratio=0.0),
                            layers, n_workers=n)
        s = plan.n_slots
        for rounds, iters in ((1, 1), (1, 4), (2, 3), (4, 8)):
            got = simulate_plan(plan, rounds * n, round_size=n,
                                iterations=iters).bubble_ratio
            want = theoretical_bubble_crossstep(n, rounds, s, iters)
            assert got == pytest.approx(want, rel=1e-9), \
                (rounds, iters, got, want)


class TestSteadyStateBubble:
    """Paper §3.2/§3.3: with rounds chained back-to-back the fill/drain is
    paid once per iteration, so the simulated bubble falls strictly and
    monotonically with R — on real workload cost models, not just uniform
    costs."""

    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "llama-3.1-8b"])
    def test_bubble_strictly_decreases_with_rounds(self, arch):
        cfg = smoke_config(get_config(arch))
        n = 4
        plan = plan_from_config(cfg, n)
        bubbles = [simulate_plan(plan, r * n, round_size=n).bubble_ratio
                   for r in (1, 2, 3, 4)]
        assert all(b2 < b1 for b1, b2 in zip(bubbles, bubbles[1:])), bubbles

    def test_uniform_plan_matches_paper_formula_and_vanishes(self):
        """Under uniform slot costs the stitched bubble is EXACTLY
        (N-1)/(R*S + N-1) (paper §3.3 with the fill/drain amortized over R
        rounds) and hence -> 0; uneven plans floor at their residual
        per-round imbalance instead (see the monotonic test above)."""
        from repro.core.plan import uniform_partition

        n, n_layers = 4, 9
        # zero grad cost: every slot (F and B alike) costs exactly 1.0
        layers = [LayerCost(1.0, 0.0) for _ in range(n_layers)]
        plan = compile_plan(uniform_partition(n_layers, grad_ratio=0.0),
                            layers, n_workers=n)
        s = plan.n_slots
        for r in (1, 2, 8, 32):
            got = simulate_plan(plan, r * n, round_size=n).bubble_ratio
            want = (n - 1) / (r * s + n - 1)
            assert got == pytest.approx(want, rel=1e-9), (r, got, want)
        assert (n - 1) / (32 * s + n - 1) < 0.01
