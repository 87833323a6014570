"""Event-based staleness-1 consistency protocol tests (paper §4.3)."""
import random
import threading
import time

import pytest

from repro.core.consistency import (
    AsyncTrainer,
    ConsistencyProtocol,
    EventBook,
    reference_staleness1,
)

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_async_crossstep_matches_staleness1():
    """Cross-step staleness-1 async optimizer: the
    chained ring program — I optimizer steps in I*R*S + N - 1 ticks, step
    T+1 injecting while step T's gradients drain into the in-program host
    optimizer — on the uneven 7-layer/4-worker auto plan must per-leaf
    allclose reference_staleness1 (and be distinguishable from the
    staleness-0 trajectory), degenerate BIT-identically to the
    synchronous loop with overlap disabled, and agree with the threaded
    HostAsyncRoundPipe worker that drives the five per-layer §4.3
    constraints around the real dispatch grads_fn."""
    run_mode("qwen3-1.7b", "async", n_layers=7)


@pytest.mark.slow     # a multi-minute XLA compile
def test_dispatch_async_shallow_plan_parity():
    """Shallow plan (3 layers on 4 workers: sf=1 < N-1): step k+1's fused
    work starts BEFORE step k's deposit-complete tick, so the per-step
    loss/replicated-grad accumulators must separate by work-step parity —
    the chained program must still match reference_staleness1."""
    run_mode("qwen3-1.7b", "async", n_layers=3)


def make_workload(n_layers, jitter=0.0, seed=0):
    """Deterministic math, optional random sleeps to shake out races."""
    rng = random.Random(seed)

    def device_fn(weights, t):
        if jitter:
            time.sleep(rng.random() * jitter)
        return [w * 0.1 + (t + 1) * (l + 1) for l, w in enumerate(weights)]

    def optimizer_fn(opt, grads, t):
        if jitter:
            time.sleep(rng.random() * jitter)
        return [w - 0.01 * g for w, g in zip(opt, grads)]

    return device_fn, optimizer_fn


class TestEventBook:
    def test_negative_iteration_vacuous(self):
        book = EventBook()
        book.wait("pcp", 0, -1)  # must not block

    def test_set_then_wait(self):
        book = EventBook()
        book.set("up", 3, 7)
        book.wait("up", 3, 7, timeout=0.1)

    def test_timeout(self):
        book = EventBook()
        with pytest.raises(TimeoutError):
            book.wait("up", 0, 0, timeout=0.05)

    def test_cross_thread(self):
        book = EventBook()
        def setter():
            time.sleep(0.02)
            book.set("down", 1, 2)
        th = threading.Thread(target=setter)
        th.start()
        book.wait("down", 1, 2, timeout=1.0)
        th.join()


class TestStalenessSemantics:
    @pytest.mark.parametrize("n_layers,n_iters", [(1, 3), (4, 6), (8, 10)])
    def test_async_matches_reference(self, n_layers, n_iters):
        dev, opt = make_workload(n_layers)
        init = [float(i + 1) for i in range(n_layers)]
        trainer = AsyncTrainer(n_layers, dev, opt, init)
        got = trainer.train(n_iters)
        want = reference_staleness1(n_layers, *make_workload(n_layers)[0:2], init, n_iters)
        assert got == pytest.approx(want)

    def test_async_matches_reference_with_jitter(self):
        """Random sleeps on both workers must not change the result."""
        n_layers, n_iters = 5, 8
        init = [1.0] * n_layers
        for seed in range(3):
            dev, opt = make_workload(n_layers, jitter=0.003, seed=seed)
            got = AsyncTrainer(n_layers, dev, opt, init).train(n_iters)
            ref_dev, ref_opt = make_workload(n_layers)  # no jitter in oracle
            want = reference_staleness1(n_layers, ref_dev, ref_opt, init, n_iters)
            assert got == pytest.approx(want), f"seed {seed}"

    def test_iteration_reads_stale_weights(self):
        """Iteration T must read weights produced after iteration T-2."""
        seen = []

        def device_fn(weights, t):
            seen.append((t, list(weights)))
            return [1.0 for _ in weights]

        def optimizer_fn(opt, grads, t):
            return [w - 1.0 for w in opt]  # each step subtracts exactly 1

        trainer = AsyncTrainer(2, device_fn, optimizer_fn, [10.0, 10.0])
        trainer.train(5)
        seen.sort()
        for t, w in seen:
            # weights read at iteration t reflect max(0, t-1) optimizer steps
            assert w[0] == pytest.approx(10.0 - max(0, t - 1))

    def test_worker_exception_propagates(self):
        def device_fn(weights, t):
            raise RuntimeError("device failure")

        def optimizer_fn(opt, grads, t):
            return opt

        trainer = AsyncTrainer(2, device_fn, optimizer_fn, [1.0, 1.0])
        with pytest.raises((RuntimeError, TimeoutError)):
            trainer.train(2, timeout=2.0)


class TestProtocolOrdering:
    def test_pcopy_blocks_until_upload(self):
        """Constraint (1): P-copy of iter T waits for upload of iter T+1."""
        p = ConsistencyProtocol(1)
        done = []

        def pcopy():
            p.before_p_copy(0, 0)
            done.append("pcp")

        th = threading.Thread(target=pcopy)
        th.start()
        time.sleep(0.05)
        assert done == []          # blocked
        p.after_param_upload(0, 1)  # upload for iteration 1
        th.join(1.0)
        assert done == ["pcp"]

    def test_grad_write_blocks_until_gcopy(self):
        """Constraint (4): grad download of iter T waits G-copy of T-1."""
        p = ConsistencyProtocol(1)
        done = []

        def writer():
            p.before_grad_download(0, 1)
            done.append("down")

        th = threading.Thread(target=writer)
        th.start()
        time.sleep(0.05)
        assert done == []
        p.after_g_copy(0, 0)
        th.join(1.0)
        assert done == ["down"]

    def test_first_iteration_unblocked(self):
        p = ConsistencyProtocol(3)
        for l in range(3):
            p.before_param_upload(l, 0)   # no P-copy history: must not block
            p.before_param_upload(l, 1)
            p.before_grad_download(l, 0)  # no G-copy history: must not block

    def test_timeout_message_names_event(self):
        book = EventBook()
        with pytest.raises(TimeoutError, match=r"\(gcp, layer=2, it=5\)"):
            book.wait("gcp", 2, 5, timeout=0.01)

    def test_is_set_vacuous_for_prehistory(self):
        book = EventBook()
        assert book.is_set("up", 0, -1)        # constraints into pre-history
        assert book.is_set("pcp", 9, -3)       # are vacuously satisfied
        assert not book.is_set("up", 0, 0)

    def test_nonblocking_predicates_mirror_waits(self):
        p = ConsistencyProtocol(1)
        assert p.may_param_upload(0, 0) and p.may_param_upload(0, 1)
        assert not p.may_param_upload(0, 2)    # needs pcp(0, 0)
        p.after_p_copy(0, 0)
        assert p.may_param_upload(0, 2)
        assert not p.may_g_copy(0, 0)
        p.after_grad_download(0, 0)
        assert p.may_g_copy(0, 0)
        assert not p.may_grad_download(0, 1)   # needs gcp(0, 0)
        p.after_g_copy(0, 0)
        assert p.may_grad_download(0, 1)
        # (1): single-buffer waits T+1's upload, double-buffered only T's
        p.after_param_upload(0, 3)
        assert p.may_p_copy(0, 3, double_buffered=True)
        assert not p.may_p_copy(0, 3)
        p.after_param_upload(0, 4)
        assert p.may_p_copy(0, 3)


class TestVerifyAsyncTicks:
    """Static certification of the cross-step chained tick order (the
    dispatch async runtime calls this at build time)."""

    def plan(self, n_layers=7, n_workers=4):
        from repro.core.partition import LayerCost, auto_partition
        from repro.core.plan import compile_plan

        layers = [LayerCost(1.0, 2.0) for _ in range(n_layers)]
        part = auto_partition(layers, n_devices=n_workers,
                              n_microbatches=n_workers)
        return compile_plan(part, layers, n_workers=n_workers)

    def test_certifies_feasible_chains(self):
        from repro.core.consistency import verify_async_ticks

        plan = self.plan()
        for rounds, iterations in ((1, 1), (1, 4), (2, 3), (3, 2)):
            verify_async_ticks(plan, rounds, iterations)  # must not raise

    def test_rejects_injection_overtaking_drain(self):
        """R*S < N-1: step T's first injection lands before step T-2's
        gradients finished draining — constraint (2) must fire."""
        from repro.core.consistency import verify_async_ticks
        from repro.core.partition import LayerCost
        from repro.core.plan import compile_plan, uniform_partition

        # 1 layer -> a single fused slot (S = 1) on 4 workers: rs = 1 < 3
        plan = compile_plan(uniform_partition(1), [LayerCost(1.0, 2.0)],
                            n_workers=4, n_body_layers=1)
        with pytest.raises(ValueError, match=r"constraint \(2\)"):
            verify_async_ticks(plan, 1, 4)
        # the plan-level feasibility guard names the same condition
        with pytest.raises(ValueError, match="infeasible"):
            plan.validate_async(1)

    def test_matches_plan_feasibility_guard(self):
        plan = self.plan()
        plan.validate_async(1)                 # S = 11 >= N-1 = 3: fine
