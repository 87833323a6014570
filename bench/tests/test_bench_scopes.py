"""The program's ``rp.*`` scopes reach the compiled step, and
``bench/scopes.py`` files each op of a trace under its innermost scope."""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, scopes
from bench.tests import tiny
from repro.runtime import spans

RING_PRIMS = {spans.SHIFT, spans.UPLOAD, spans.PROMOTE, spans.DEPOSIT}


def _uniform_plan(monkeypatch):
    """Every slot kind on one worker: the tiny model's auto plan is a
    single fused slot, so the step is built over the 1-layer-per-slot
    partition F[0] FB[1] B[0] instead."""
    import repro.core.plan as plan
    auto = plan.plan_from_config
    monkeypatch.setattr(plan, "plan_from_config", lambda cfg, n, **kw: auto(
        cfg, n, partition=plan.uniform_partition(cfg.n_layers), **kw))


def _write_cell(root: Path, **mix) -> dict:
    tiny.write_cell(root)
    cell = harness.load_cell("tiny", root)
    cell["mix"] = dict(cell["mix"], **mix)
    (root / "traffic" / "tiny-mix.json").write_text(json.dumps(cell["mix"]))
    return cell


def _lowered_scopes(cell: dict) -> dict:
    """The innermost scopes of the cell's step, lowered (metadata kept, no
    instruction of XLA's own yet), and those of its collective-permutes."""
    import jax

    from bench import traffic
    prog = harness.Program(cell)
    state = jax.eval_shape(prog.init, harness.seed_key(SEED))
    batch = traffic.Batches(cell["mix"], prog.cfg.vocab_size, SEED).batch(0)
    with prog.mesh:
        text = prog.step.lower(state, batch).as_text(dialect="hlo",
                                                     debug_info=True)
    permutes = re.findall(r" collective-permute\(.*" + scopes.OP_NAME.pattern,
                          text)
    return {"scopes": sorted({scopes.innermost(n)
                              for n in scopes.OP_NAME.findall(text)} - {""}),
            "permutes": sorted({scopes.innermost(n) for n in permutes})}


def _four_devices(root: str):
    """In a process with four CPU devices: the tiny cell's N=4 ring (F, FB
    and B slots from its auto plan), lowered; prints the scopes."""
    cell = harness.load_cell("tiny", Path(root))
    cell.update(chips=4, mesh="1x4")
    print(json.dumps(_lowered_scopes(cell)))


SEED = 2 ** 33 + 1


@pytest.fixture(scope="module")
def ring4(tmp_path_factory):
    """The N=4 ring's scopes, from a process with four CPU devices that the
    first case to ask starts, so that it runs beside the one-device cases;
    ``ring4()`` waits for them."""
    root = tmp_path_factory.mktemp("ring4")
    _write_cell(root, batch=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(harness.ROOT), str(harness.ROOT / "src")]))
    code = ("from bench.tests.test_bench_scopes import _four_devices; "
            f"_four_devices({str(root)!r})")
    p = subprocess.Popen([sys.executable, "-c", code], cwd=harness.ROOT,
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)

    def wait() -> dict:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-3000:]
        return json.loads(out.strip().splitlines()[-1])
    yield wait
    if p.poll() is None:
        p.kill()
        p.wait()


@pytest.mark.parametrize("pool,devices", [("none", 1), ("int8", 1),
                                          ("none", 4)])
def test_every_scope_reaches_the_step(tmp_path, monkeypatch, ring4, pool,
                                      devices):
    """The tiny cell's step on one device: compiled with an int8 pool over a
    plan with every slot kind, where every scope reaches the compiled HLO;
    lowered with a bf16 pool over its auto plan (one fused slot, as on the
    chip; the lowered driver still holds every slot kind's branch).  A
    one-worker ring reads each block in place from its pool, so no
    standby is uploaded there and ``rp.upload`` is not among its scopes;
    a bf16 pool's block in place is the pool itself, an identity slice
    with no op, so ``rp.promote`` is not among the bf16 step's either (the
    int8 pool's dequant in place carries it).  And its N=4 ring on four
    virtual devices, lowered, where the standby upload runs, every
    collective-permute carries a ring primitive's scope and the ring hop's
    carry ``rp.shift``."""
    want = set(spans.DEVICE_SCOPES)
    if devices == 1:
        want.discard(spans.UPLOAD)
    if devices == 4:
        got = ring4()
        assert spans.SHIFT in got["permutes"]
        assert set(got["permutes"]) <= RING_PRIMS
        want.discard(spans.QUANTIZE)
    elif pool == "int8":
        _uniform_plan(monkeypatch)
        found, _, _ = scopes.scope_map(scopes.compile_step(
            _write_cell(tmp_path, pool_dtype=pool), SEED))
        got = {"scopes": set(found.values()) - {""}}
    else:
        got = _lowered_scopes(_write_cell(tmp_path, pool_dtype=pool))
        want -= {spans.QUANTIZE, spans.PROMOTE}
    assert set(got["scopes"]) == want


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/rp.ring/cond/branch_1_fun/rp.slot.FB/"
     "transpose(jvp(rp.merge))/dot_general", "rp.merge"),
    ("jit(train_step)/rp.ring/cond/branch_1_fun/rp.slot.B/jvp(rp.merge)/"
     "convert_element_type", "rp.merge"),
    ("jit(train_step)/rp.ring/cond/branch_1_fun/rp.slot.FB/transpose(jvp())/"
     "while/body/closed_call/checkpoint/rematted_computation/ne",
     "rp.slot.FB"),
    ("jit(train_step)/rp.ring/cond/branch_1_fun/rp.slot.F/rp.stash/"
     "dynamic_update_slice", "rp.stash"),
    ("jit(train_step)/rp.ring/rp.promote/jit(dequant_rows)/dequant_rows/"
     "pallas_call", "rp.promote"),
    ("jit(train_step)/rp.ring/rp.shift/ppermute", "rp.shift"),
    ("jit(train_step)/rp.optimizer/sqrt", "rp.optimizer"),
    ("jit(train_step)/add", ""),
    ("psum", ""),
])
def test_innermost_scope_sees_through_wrappers(op_name, scope):
    assert scopes.innermost(op_name) == scope


MODULE = """\
%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(train_step)/rp.ring/rp.slot.F/rp.merge/neg"}
}

%body (p.2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.2 = (s32[], f32[4]{0}) parameter(0)
  %gte.3 = f32[4]{0:T(128)} get-tuple-element(%p.2), index=1
  %copy.5 = f32[4]{0:T(128)S(1)} copy(%gte.3)
  %i.4 = s32[] get-tuple-element(%p.2), index=0
  ROOT %tuple.6 = (s32[], f32[4]{0}) tuple(%i.4, %copy.5)
}

%cond (p.7: (s32[], f32[4])) -> pred[] {
  %p.7 = (s32[], f32[4]{0}) parameter(0)
  ROOT %c.8 = pred[] constant(false)
}

ENTRY %main.9 (p: f32[4]) -> (f32[4], f32[4], f32[4]) {
  %p = f32[4]{0} parameter(0), metadata={op_name="state"}
  %zero = f32[] constant(0)
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/rp.ring/rp.slot.B/transpose(jvp(rp.merge))/neg"}
  %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %wrapped_convert = f32[4]{0} convert(%p)
  %broadcast.7 = f32[4]{0} broadcast(%zero), dimensions={}
  %collective-permute.2 = f32[4]{0} collective-permute(%fusion.3, %broadcast.7), channel_id=1, source_target_pairs={{0,1}}, metadata={op_name="jit(train_step)/rp.ring/rp.shift/ppermute" stack_frame_id=3}
  %i.0 = s32[] constant(0)
  %tuple.0 = (s32[], f32[4]{0}) tuple(%i.0, %fusion.4)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body
  %gte.9 = f32[4]{0} get-tuple-element(%while.1), index=1
  %add.10 = f32[4]{0} add(%gte.9, %broadcast.7), metadata={op_name="jit(train_step)/rp.ring/rp.upload/scatter"}
  ROOT %tuple.4 = (f32[4]{0}, f32[4]{0}, f32[4]{0}) tuple(%collective-permute.2, %wrapped_convert, %add.10), metadata={op_name="jit(train_step)/rp.ring/tuple"}
}
"""


def test_scope_map_follows_metadata_then_neighbours():
    """An instruction's own op_name decides; a fusion without one takes its
    fused computation's; other ops without one take their consumers'
    scope where the consumers agree (a loop's body reaches its loop's
    consumers), else none."""
    found, kinds, inferred = scopes.scope_map(MODULE)
    assert {n: found[n] for n in ("neg.1", "fusion.3", "collective-permute.2",
                                  "add.10", "tuple.4")} == {
        "neg.1": "rp.merge", "fusion.3": "rp.merge",
        "collective-permute.2": "rp.shift", "add.10": "rp.upload",
        "tuple.4": "rp.ring"}
    assert found["fusion.4"] == "rp.merge"          # its fused computation's
    assert found["wrapped_convert"] == "rp.ring"    # its one consumer's
    for n in ("while.1", "gte.9", "tuple.0", "copy.5", "gte.3", "tuple.6",
              "c.8"):              # the loop and its body: the scatter's
        assert found[n] == "rp.upload", n
    assert found["broadcast.7"] == ""     # shared by rp.shift and rp.upload
    assert found["p"] == ""               # rp.merge's and rp.ring's operand
    assert inferred == {"fusion.4", "param_0.1", "wrapped_convert", "while.1",
                        "gte.9", "tuple.0", "i.0", "p.2", "gte.3", "copy.5",
                        "i.4", "tuple.6", "p.7", "c.8"}
    assert kinds["fusion.3"] == "fusion"
    assert kinds["collective-permute.2"] == "collective-permute"
    assert kinds["tuple.0"] == "tuple"


def test_buckets_sum_to_the_op_time():
    ops_s = {"fusion.3": 0.25, "collective-permute.2": 0.125,
             "broadcast.7": 0.0625, "not.in.module": 0.03125}
    found, _, _ = scopes.scope_map(MODULE)
    by_scope, unscoped = scopes.split(ops_s, found)
    assert by_scope == {"rp.merge": 0.25, "rp.shift": 0.125}
    assert unscoped == 0.0625 + 0.03125
    assert math.fsum(by_scope.values()) + unscoped == sum(ops_s.values())


def test_log_splits_each_scope_into_own_and_inferred():
    """A scope's line gives its inferred part; the inferred and the
    unscoped ops are listed by time, each with its kind."""
    found, kinds, inferred = scopes.scope_map(MODULE)
    trace = {"ops_s": {"fusion.3": 0.25, "fusion.4": 0.125,
                       "broadcast.7": 0.0625, "wrapped_convert": 0.03125},
             "steps": 2, "busy_s": 0.5}
    scopes.add(trace, found, inferred)
    assert trace["inferred_s"] == {"rp.merge": 0.125, "rp.ring": 0.03125}
    lines = scopes.describe(trace, kinds)
    assert "scope rp.merge 187.500 ms/step 80.00% (inferred 62.500)" in lines
    assert [l for l in lines if l.startswith("inferred ")] == [
        "inferred fusion.4 fusion rp.merge 62.500 ms/step",
        "inferred wrapped_convert convert rp.ring 15.625 ms/step"]
    assert [l for l in lines if l.startswith("unscoped ")] == [
        "unscoped broadcast.7 broadcast 31.250 ms/step"]
    assert lines[-1].startswith("ops 234.375 ms/step, busy 250.000 ms/step")


def test_scope_build_has_a_cache_entry_of_its_own(tmp_path):
    """JAX's persistent-cache key leaves op_name out, so an entry written
    by a build without a scope answers one with it; under the metadata
    key the scoped build gets its own entry, and the key is restored."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def lowered(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2
        return jax.jit(f).lower(jnp.ones(8))

    config = {"jax_compilation_cache_dir": str(tmp_path),
              "jax_enable_compilation_cache": True,
              "jax_persistent_cache_min_compile_time_secs": 0,
              "jax_persistent_cache_min_entry_size_bytes": 0}
    saved = {k: getattr(jax.config, k) for k in config}
    try:
        for k, v in config.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        lowered("rp.before").compile()
        assert "rp.before" in lowered("rp.after").compile().as_text()
        with scopes._metadata_keyed():
            assert "rp.after" in lowered("rp.after").compile().as_text()
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


SCOPE_METRICS = ("slot_compute_ms", "lora_merge_ms", "ring_driver_ms",
                 "ring_prims_ms", "requantize_ms", "optimizer_ms")


def test_metrics_read_nothing_without_the_programs_scopes():
    """A program without rp.* scopes (the parent of this change, or a
    rename) reads nothing, and no trace reads nothing."""
    trace = {"ops_s": {"fusion.3": 0.25}, "steps": 2, "busy_s": 0.25}
    scopes.add(trace, {"fusion.3": ""})
    for name in SCOPE_METRICS:
        read = harness.load_reader(name)
        assert read({}, trace) is None
        assert read({}, None) is None


# ---- a trace recorded on the chip, with the compiled step's op_names -------

DATA = Path(__file__).resolve().parents[1] / "testdata"
RECORDED = DATA / "qwen3-32b-2l.lora-int8.scopes.events.json"
METRIC_SCOPES = {"slot_compute_ms": ("rp.slot.F", "rp.slot.FB", "rp.slot.B"),
                 "lora_merge_ms": ("rp.merge",),
                 "ring_driver_ms": ("rp.ring", "rp.stash"),
                 "ring_prims_ms": ("rp.shift", "rp.upload", "rp.promote",
                                   "rp.deposit"),
                 "requantize_ms": ("rp.quantize",),
                 "optimizer_ms": ("rp.optimizer",)}


def _by_hand(op_name: str) -> str:
    """The innermost scope the plain way: walk the path's parts from the
    end, peel each part's ``f(...)`` wrappers, take the first rp.* name."""
    for part in reversed(op_name.split("/")):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part.startswith("rp."):
            return part
    return ""


@pytest.fixture(scope="module")
def recorded():
    """The fixture's two steps, reduced as a traced run reduces them, with
    its ops filed under the scopes recorded beside them (``scope_map`` of
    the compiled step)."""
    from bench import trace as trace_mod
    rec = json.loads(RECORDED.read_text())
    trace = trace_mod.reduce({"devices": rec["devices"],
                              "spans": rec["spans"]}, n_devices=1)
    trace["steps"] = rec["steps"]
    inferred = {op for op, sc in rec["scopes"].items()
                if sc and not _by_hand(rec["op_names"].get(op, ""))}
    scopes.add(trace, rec["scopes"], inferred)
    return rec, trace


def _direct_ms(rec: dict, names) -> float:
    """Clipped event time of the ops recorded under one of ``names``, per
    step, in ms."""
    w0 = min(s for s, _, _ in rec["spans"])
    w1 = max(s + d for s, d, _ in rec["spans"])
    ns = 0.0
    for events in rec["devices"].values():
        for s, d, op, _ in events:
            if rec["scopes"].get(op, "") in names:
                ns += max(0.0, min(s + d, w1) - max(s, w0))
    return ns / 1e9 / rec["steps"] * 1e3


def test_recorded_op_names_follow_the_innermost_rule(recorded):
    """Every op whose own op_name holds an rp.* scope is filed under the
    innermost one, wrappers seen through; the others took a neighbour's."""
    rec, _ = recorded
    wrapped = [n for n in rec["op_names"].values()
               if "(jvp(rp." in n or "/jvp(rp." in n]
    assert wrapped, "the fixture should hold ops of a vjp of a scope"
    for op, name in rec["op_names"].items():
        assert scopes.innermost(name) == _by_hand(name), name
        if _by_hand(name):
            assert rec["scopes"][op] == _by_hand(name), (op, name)


def test_recorded_buckets_sum_to_the_op_time(recorded):
    rec, trace = recorded
    total = math.fsum(trace["ops_s"].values())
    assert math.fsum(trace["scopes_s"].values()) + trace["unscoped_s"] == \
        pytest.approx(total, rel=1e-12)
    assert trace["scopes_s"] == pytest.approx(rec["known"]["scopes_s"],
                                              rel=1e-12)
    assert trace["unscoped_s"] == pytest.approx(rec["known"]["unscoped_s"],
                                                rel=1e-9, abs=1e-15)


def test_recorded_inferred_part_is_what_no_own_op_name_gives(recorded):
    """Each scope's inferred seconds are its ops' whose own op_name names
    no rp.* scope; the rest are the ops that name it themselves."""
    rec, trace = recorded
    own = {op: _by_hand(name) for op, name in rec["op_names"].items()}
    assert 0 < math.fsum(trace["inferred_s"].values()) < \
        math.fsum(trace["scopes_s"].values())
    for sc, seconds in trace["scopes_s"].items():
        named = math.fsum(s for op, s in trace["ops_s"].items()
                          if own.get(op) == sc)
        assert seconds - trace["inferred_s"].get(sc, 0.0) == \
            pytest.approx(named, rel=1e-9, abs=1e-15), sc


@pytest.mark.parametrize("name", sorted(METRIC_SCOPES))
def test_recorded_metric_reads_the_direct_count(recorded, name):
    rec, trace = recorded
    got = harness.load_reader(name)({}, trace)
    assert got == pytest.approx(_direct_ms(rec, METRIC_SCOPES[name]),
                                rel=1e-9, abs=1e-12)
    # per step: the same ops over one step read twice as much
    assert harness.load_reader(name)({}, dict(trace, steps=1)) == \
        pytest.approx(2 * got, rel=1e-12, abs=1e-12)


def test_recorded_scopes_are_logged_one_line_each(recorded):
    rec, trace = recorded
    lines = scopes.describe(trace, rec["kinds"])
    assert sum(l.startswith("scope ") for l in lines) == \
        len(trace["scopes_s"]) + 1
    assert lines[-1].startswith("ops ")
