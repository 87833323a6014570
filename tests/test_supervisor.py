"""Goodput supervisor unit suite (fast tier): the state machine against a
mock step — detect→mitigate transitions for each fault class, the async
re-plan refusal, the goodput ledger, the raising watchdog, and the async
checkpoint writer's crash race — no XLA compiles, milliseconds per case.
The real compiled step goes through the same paths in the slow-tier
``chaos`` subprocess proof at the top of this file."""
import dataclasses
import itertools
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.checkpoint.store import (AsyncCheckpointWriter, latest_step,
                                    load_checkpoint, save_checkpoint)
from repro.runtime.fault_tolerance import (FaultTolerantLoop,
                                           HeartbeatMonitor, StepHungError,
                                           StragglerPolicy)
from repro.runtime.supervisor import (GoodputMeter, Supervisor, WorkerFault,
                                      analytic_goodput,
                                      checkpoint_cost_model)

from roundpipe_proof import run_mode


# End-to-end subprocess proof(s) of this feature through the ring.
# Kept first in the file: see tests/roundpipe_proof.py.
@pytest.mark.slow     # a multi-minute XLA compile
def test_supervisor_chaos_harness():
    """Goodput supervisor chaos harness: the REAL
    compiled step driven through the full detect→mitigate state machine on
    the uneven 7-layer/4-worker auto plan.  A 5x-slowed worker must
    trigger the straggler streak → device_scale re-score → g0=3 rotation
    rebuild; a killed worker must trigger the elastic re-plan to N-1=3
    (fresh auto partition, M' floored to 3) + restore of the newest
    async-written checkpoint onto the (2,3) mesh.  Final params must match
    the uninterrupted N=4 reference trajectory, the replayed step's loss
    must reproduce its pre-fault value (deterministic replay), and the
    goodput ledger must charge nonzero replay + replan overhead."""
    run_mode("qwen3-1.7b", "chaos", n_layers=7)


def fake_clock():
    """Deterministic clock: +1.0 s per call — every (t0, dt) pair in the
    supervisor brackets exactly one tick, so ledger entries are integers."""
    c = itertools.count()
    return lambda: float(next(c))


def make_factory(record, step_impl=None, worker_times=None, rescore=None):
    """Mock runtime factory: integer-counter 'training' (state x counts
    committed steps) with deterministic replay (batch_for(step) = step)."""

    def factory(*, n_workers, g0, use_async, replan=None):
        record.append(dict(n_workers=n_workers, g0=g0, use_async=use_async,
                           replan=replan))
        rt = SimpleNamespace()
        rt.init_state = lambda: {"x": np.zeros(())}
        rt.like = {"x": np.zeros(())}
        rt.shardings = None
        rt.batch_for = lambda step: step

        def default_step(state, batch):
            return {"x": np.asarray(state["x"]) + 1}, {"step": batch}

        rt.step_fn = step_impl or default_step
        if worker_times is not None:
            rt.worker_times = worker_times
        if rescore is not None:
            rt.rescore = rescore
        return rt

    return factory


class TestGoodputArithmetic:
    def test_meter_categories_and_ratio(self):
        m = GoodputMeter()
        m.add("productive", 6.0)
        m.add("ckpt", 1.0)
        m.add("replan", 2.0)
        m.add("replay", 3.0)
        assert m.total == 12.0
        assert m.goodput == pytest.approx(0.5)
        rep = m.report()
        assert rep["goodput"] == pytest.approx(0.5)
        assert rep["replay_s"] == 3.0 and rep["wall_s"] == 12.0

    def test_empty_meter_is_perfect(self):
        assert GoodputMeter().goodput == 1.0

    def test_analytic_matches_hand_ledger(self):
        # M=100 steps of 2s, ckpt every 10 at 4s, one failure: replan 8s
        # + K/2 = 5 steps replayed -> 200 / (200 + 40 + 8 + 10)
        g = analytic_goodput(2.0, mtbf_steps=100, ckpt_every=10,
                             ckpt_cost_s=4.0, replan_s=8.0)
        assert g == pytest.approx(200.0 / 258.0)

    def test_async_cost_strictly_below_sync(self):
        c_sync, c_async = checkpoint_cost_model(1e9, host_bw=25e9,
                                                disk_bw=2e9)
        assert 0 < c_async < c_sync
        ga = analytic_goodput(1.0, mtbf_steps=1000, ckpt_every=50,
                              ckpt_cost_s=c_async)
        gs = analytic_goodput(1.0, mtbf_steps=1000, ckpt_every=50,
                              ckpt_cost_s=c_sync)
        assert ga > gs

    def test_analytic_rejects_degenerate_inputs(self):
        with pytest.raises(ValueError):
            analytic_goodput(0.0, mtbf_steps=10, ckpt_every=5,
                             ckpt_cost_s=1.0)
        with pytest.raises(ValueError):
            analytic_goodput(1.0, mtbf_steps=10, ckpt_every=0,
                             ckpt_cost_s=1.0)


class TestSupervisorLedger:
    def test_clean_run_ledger(self, tmp_path):
        record = []
        sup = Supervisor(make_factory(record), tmp_path / "ck", n_workers=4,
                         save_every=2, async_ckpt=False, clock=fake_clock())
        state, step = sup.run(4)
        assert step == 4 and float(np.asarray(state["x"])) == 4.0
        # 4 productive ticks, checkpoints after steps 1 and 3 (one tick each)
        assert sup.meter.seconds["productive"] == 4.0
        assert sup.meter.seconds["ckpt"] == 2.0
        assert sup.meter.goodput == pytest.approx(4.0 / 6.0)
        assert latest_step(tmp_path / "ck") == 3
        assert [r["n_workers"] for r in record] == [4]


class TestStragglerMitigation:
    def test_detect_then_rotate(self, tmp_path):
        record = []

        def worker_times(metrics):
            # worker 2 runs 5x slow from step 3 onward
            t = [1.0, 1.0, 1.0, 1.0]
            if metrics["step"] >= 3:
                t[2] = 5.0
            return t

        sup = Supervisor(
            make_factory(record, worker_times=worker_times),
            tmp_path / "ck", n_workers=4, save_every=100, async_ckpt=False,
            straggler=StragglerPolicy(factor=2.0, min_samples=2))
        state, step = sup.run(8)
        assert step == 8 and float(np.asarray(state["x"])) == 8.0
        # detected at steps 3 and 4, rotated once the streak persisted
        stragglers = sup.events_of("straggler")
        assert stragglers and stragglers[0].detail["worker"] == 2
        rotations = sup.events_of("rotate")
        assert len(rotations) == 1
        assert rotations[0].detail == {"g0": 3, "worker": 2, "ratio": 5.0}
        assert sup.g0 == 3
        # the factory rebuilt the step with the rotation, same N
        assert [r["g0"] for r in record] == [0, 3]
        assert all(r["n_workers"] == 4 for r in record)

    def test_rescore_hook_chooses_rotation(self, tmp_path):
        record = []
        seen_scales = []

        def rescore(scales):
            seen_scales.append(list(scales))
            return 1           # schedule search says: inject at worker 1

        sup = Supervisor(
            make_factory(record, worker_times=lambda m: [1, 1, 1, 4.0],
                         rescore=rescore),
            tmp_path / "ck", n_workers=4, save_every=100, async_ckpt=False,
            straggler=StragglerPolicy(factor=2.0, min_samples=1))
        sup.run(4)
        assert sup.g0 == 1 and [r["g0"] for r in record] == [0, 1]
        # the measured slowdown reached the re-scorer as device_scale
        assert seen_scales[0] == [1.0, 1.0, 1.0, 4.0]

    def test_healthy_run_never_rotates(self, tmp_path):
        record = []
        sup = Supervisor(
            make_factory(record, worker_times=lambda m: [1.0, 1.1, 0.9, 1.0]),
            tmp_path / "ck", n_workers=4, save_every=100, async_ckpt=False,
            straggler=StragglerPolicy(factor=2.0, min_samples=1))
        sup.run(6)
        assert not sup.events and sup.g0 == 0 and len(record) == 1


class TestDeadWorkerReplan:
    def _killing_factory(self, record, kill_at, killed):
        def step_impl(state, batch):
            if batch == kill_at and not killed:
                killed.append(batch)
                raise WorkerFault(1, "simulated device loss")
            return {"x": np.asarray(state["x"]) + 1}, {"step": batch}

        return make_factory(record, step_impl=step_impl)

    def test_replan_to_survivors_and_replay(self, tmp_path):
        from repro.core.plan import ReplanResult

        record, killed = [], []
        replans = []

        def replan_fn(n):
            replans.append(n)
            return ReplanResult(plan=None, n_microbatches=n, rounds=1,
                                async_ok=True)

        sup = Supervisor(self._killing_factory(record, 5, killed),
                         tmp_path / "ck", n_workers=4, replan_fn=replan_fn,
                         save_every=2, async_ckpt=False, clock=fake_clock())
        state, step = sup.run(8)
        # trajectory is exact despite the mid-run death: deterministic
        # replay of steps 4..5 from the step-3 checkpoint on N=3
        assert step == 8 and float(np.asarray(state["x"])) == 8.0
        assert replans == [3] and sup.n_workers == 3
        assert [e.kind for e in sup.events] == \
            ["worker_dead", "replan", "restore"]
        assert sup.events_of("replan")[0].detail["n_workers"] == 3
        assert sup.events_of("restore")[0].detail["resumed_at"] == 4
        # ledger: step 4 re-runs as replay (step 5 never committed, so its
        # re-run counts as the first productive pass), the rest productive
        assert sup.meter.seconds["replay"] == 1.0
        assert sup.meter.seconds["replan"] == 1.0
        assert sup.meter.seconds["productive"] == 8.0
        assert sup.meter.goodput < 1.0
        # the factory was re-invoked for the survivors with the replan result
        assert [(r["n_workers"], r["g0"]) for r in record] == [(4, 0), (3, 0)]
        assert record[1]["replan"].n_microbatches == 3

    def test_async_infeasible_falls_back_to_sync(self, tmp_path):
        from repro.core.plan import ReplanResult

        record, killed = [], []
        sup = Supervisor(
            self._killing_factory(record, 3, killed), tmp_path / "ck",
            n_workers=4, save_every=2, async_ckpt=False, use_async=True,
            replan_fn=lambda n: ReplanResult(
                plan=None, n_microbatches=n, rounds=1, async_ok=False,
                async_refusal="R*S = 1 < N-1 = 2"))
        with pytest.warns(RuntimeWarning, match="async infeasible"):
            state, step = sup.run(6)
        assert step == 6 and float(np.asarray(state["x"])) == 6.0
        assert not sup.use_async
        fallback = sup.events_of("sync_fallback")
        assert fallback and "R*S" in fallback[0].detail["reason"]
        # first build async, post-replan build sync
        assert [r["use_async"] for r in record] == [True, False]

    def test_restart_budget_is_enforced(self, tmp_path):
        record = []

        def always_dies(state, batch):
            raise WorkerFault(0)

        sup = Supervisor(make_factory(record, step_impl=always_dies),
                         tmp_path / "ck", n_workers=8, max_restarts=2,
                         async_ckpt=False)
        with pytest.raises(RuntimeError, match="max_restarts"):
            sup.run(4)


class TestReplanForSurvivors:
    def test_refuses_async_when_protocol_infeasible(self):
        # 1-layer model: S*R = rounds_for(M) * n_slots can never reach
        # N-1 = 3, so the staleness-1 chain must be refused at N=4
        from repro.configs import smoke_config
        from repro.core.plan import replan_for_survivors
        from repro.models.config import get_config

        cfg = dataclasses.replace(smoke_config(get_config("qwen3-1.7b")),
                                  n_layers=1, name="one-layer")
        rr = replan_for_survivors(cfg, 4, async_steps=4)
        assert not rr.async_ok
        assert rr.async_refusal
        # sync (async_steps=1) never refuses: no chain, no constraint
        assert replan_for_survivors(cfg, 4, async_steps=1).async_ok

    def test_microbatches_round_down_to_survivors(self):
        from repro.configs import smoke_config
        from repro.core.plan import replan_for_survivors
        from repro.models.config import get_config

        cfg = dataclasses.replace(smoke_config(get_config("qwen3-1.7b")),
                                  n_layers=7, name="seven-layer")
        rr = replan_for_survivors(cfg, 3, n_microbatches=4, async_steps=4)
        assert rr.n_microbatches == 3          # 4 rounded down to N' = 3
        assert rr.rounds == rr.plan.rounds_for(3) == 1
        assert rr.plan.n_workers == 3
        assert rr.async_ok                     # 7 layers: S >= N-1 holds


class TestHangDetection:
    def test_exit_raises_when_step_hung(self):
        # regression: the watchdog used to only append to events, so an
        # in-step hang was indistinguishable from a slow step
        with pytest.raises(StepHungError):
            with HeartbeatMonitor(0.05):
                time.sleep(0.2)

    def test_beat_raises_into_the_loop(self):
        with pytest.raises(StepHungError, match="heartbeat"):
            with HeartbeatMonitor(0.05) as hb:
                time.sleep(0.2)
                hb.beat()

    def test_exit_does_not_mask_step_exceptions(self):
        with pytest.raises(KeyError):
            with HeartbeatMonitor(0.05):
                time.sleep(0.2)
                raise KeyError("real failure wins")

    def test_fast_step_never_trips(self):
        with HeartbeatMonitor(0.5) as hb:
            time.sleep(0.01)
            hb.beat()
        assert not hb.events and not hb.hung

    def test_fault_tolerant_loop_restarts_hung_step(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        hung = []

        def step_fn(state, batch):
            if batch == 2 and not hung:
                hung.append(batch)
                time.sleep(0.5)        # deliberately hung step
            return {"x": np.asarray(state["x"]) + 1}, {"step": batch}

        loop = FaultTolerantLoop(
            step_fn, CheckpointManager(tmp_path / "ck", save_every=1),
            SimpleNamespace(batch=lambda s: s), step_timeout_s=0.1)
        state, step = loop.run(lambda: {"x": np.zeros(())},
                               {"x": np.zeros(())}, 4)
        assert step == 4 and float(np.asarray(state["x"])) == 4.0
        assert loop.restarts == 1      # the hang raised and restored

    def test_supervisor_restores_after_hang(self, tmp_path):
        record, hung = [], []

        def step_impl(state, batch):
            if batch == 3 and not hung:
                hung.append(batch)
                time.sleep(0.5)
            return {"x": np.asarray(state["x"]) + 1}, {"step": batch}

        sup = Supervisor(make_factory(record, step_impl=step_impl),
                         tmp_path / "ck", n_workers=4, save_every=2,
                         async_ckpt=False, step_timeout_s=0.1)
        state, step = sup.run(4)
        assert step == 4 and float(np.asarray(state["x"])) == 4.0
        assert [e.kind for e in sup.events] == ["hang", "restore"]
        assert sup.n_workers == 4      # same topology: restart, not replan
        assert sup.meter.seconds["replay"] > 0


class TestAsyncCheckpointWriter:
    def test_crash_race_mid_write_keeps_old_checkpoint(self, tmp_path):
        d = tmp_path / "ck"
        save_checkpoint(d, 0, {"x": np.ones(3)})
        gate, started = threading.Event(), threading.Event()

        def slow_save(directory, step, state, keep=3):
            # simulate a crash window: a half-written checkpoint dir with
            # no manifest is on disk while the writer is mid-flight
            junk = d / f"step_{step:010d}"
            junk.mkdir()
            (junk / "leaf00000.npy").write_bytes(b"garbage")
            started.set()
            gate.wait(10)
            return save_checkpoint(directory, step, state, keep=keep)

        with AsyncCheckpointWriter(d, save_fn=slow_save) as w:
            blocked = w.submit(1, {"x": np.full(3, 2.0)})
            assert blocked >= 0.0      # caller paid only the snapshot
            assert started.wait(10)
            # mid-write: manifest-last atomicity keeps step 0 the newest
            # restorable checkpoint despite the manifest-less step_1 dir
            assert latest_step(d) == 0
            st, step = load_checkpoint(d, 0, {"x": np.zeros(3)})
            assert step == 0
            np.testing.assert_array_equal(np.asarray(st["x"]), np.ones(3))
            gate.set()
            w.wait()
            assert latest_step(d) == 1

    def test_snapshot_is_immune_to_later_mutation(self, tmp_path):
        # the device→host snapshot happens IN submit: mutating (or
        # donating) the live buffers afterwards must not corrupt the write
        gate = threading.Event()

        def gated_save(directory, step, state, keep=3):
            gate.wait(10)
            return save_checkpoint(directory, step, state, keep=keep)

        live = {"x": np.ones(4)}
        with AsyncCheckpointWriter(tmp_path / "ck", save_fn=gated_save) as w:
            w.submit(0, live)
            live["x"][:] = -1.0        # next step clobbers the buffer
            gate.set()
            w.wait()
        st, _ = load_checkpoint(tmp_path / "ck", 0, {"x": np.zeros(4)})
        np.testing.assert_array_equal(np.asarray(st["x"]), np.ones(4))

    def test_writer_errors_surface_on_wait(self, tmp_path):
        def bad_save(directory, step, state, keep=3):
            raise OSError("disk full")

        w = AsyncCheckpointWriter(tmp_path / "ck", save_fn=bad_save)
        w.submit(0, {"x": np.zeros(1)})
        with pytest.raises(RuntimeError, match="async checkpoint"):
            w.wait()
        w.close()                      # error already consumed: clean close

    def test_supervisor_async_ckpt_path(self, tmp_path):
        record = []
        sup = Supervisor(make_factory(record), tmp_path / "ck", n_workers=4,
                         save_every=2, async_ckpt=True)
        state, step = sup.run(6)
        assert step == 6
        # run() closed the writer, so every submitted write has landed
        assert latest_step(tmp_path / "ck") == 5
        st, saved = load_checkpoint(tmp_path / "ck", 5, {"x": np.zeros(())})
        assert saved == 5 and float(np.asarray(st["x"])) == 6.0
