"""The benchmark harness: one run of one cell.

Everything that belongs to a configuration, a traffic mix, a cell or a metric
is a file found by its name:

  bench/configs/<config>.json    the model as run (published keys, the
                                 registry arch and its fields as run, and
                                 ``"reference"``: the name of its module)
  bench/references/<name>.py     the architecture's float32 reference and
                                 its operations and bytes from shapes (the
                                 interface: ``references/qwen3_dense.py``)
  bench/traffic/<mix>.json       batch, sequence, token statistics, LoRA and
                                 optimizer settings of the training job
  bench/workloads/<cell>.json    config + mix + chips + mesh, the control
                                 and the limits of the comparison
  bench/metrics/<metric>.py      ``read(record, trace) -> float | None``

A reader's ``record`` (:func:`run_cell`) holds ``cell``, ``chips``, ``seed``,
``setup_s``, ``tokens_per_step``, ``window`` (:class:`Window`),
``peak_bytes``, ``peaks`` (the chip's row of ``peaks.json``),
``flops_per_token`` and ``dequant_bytes_per_call`` (None without a quantized
pool), and what a reader needs to count a kernel of its own: ``reference``
(the configuration's reference module), ``shape`` (its ``Shape`` of the
configuration), ``job`` (``reference.Job``), ``plan`` (the program's
``ExecutionPlan``) and ``cfg`` (the program's model config).

``BENCHMARK.json`` at the checkout's root says which metrics a cell
reports.  The harness drives the program's synchronous RoundPipe LoRA step
(the one ``launch/train.run_training --strategy roundpipe`` builds) through
the same calls: ``plan_from_config``, ``build_train_step`` and
``init_roundpipe_state`` jitted with the state's shardings.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import re
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import compare, flops, reference, traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
TRACE_SECONDS = 3.0          # longest traced window: traces are large
AHEAD_SECONDS = 5.0          # device work kept dispatched ahead in a window
MAX_AHEAD = 32


class SpecError(ValueError):
    """An unknown or malformed name or spec file."""


def load_spec(kind: str, name: str, root: Path = BENCH) -> dict:
    """``<root>/<kind>/<name>.json``; an unknown name is an error."""
    if not NAME_RE.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"unknown {kind} entry {name!r}: no file "
                        f"bench/{kind}/{name}.json")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = BENCH) -> dict:
    """A cell's spec, with its configuration's spec, its mix and its
    configuration's reference module attached."""
    cell = load_spec("workloads", name, root)
    cell["name"] = name
    cell["config_spec"] = load_spec("configs", cell["config"], root)
    cell["mix"] = load_spec("traffic", cell["traffic"], root)
    if "reference" not in cell["config_spec"]:
        raise SpecError(f"bench/configs/{cell['config']}.json names no "
                        f"reference: give it \"reference\": \"<name>\" "
                        f"of a module bench/references/<name>.py")
    cell["reference"] = load_reference(cell["config_spec"]["reference"], root)
    return cell


def declared_metrics(cell_name: str, traced: bool,
                     benchmark: Path = ROOT / "BENCHMARK.json") -> dict:
    """``{name: unit}`` of the metrics ``BENCHMARK.json`` asks of this
    cell: its end-to-end metrics untraced, its per-layer metrics traced."""
    spec = json.loads(benchmark.read_text())
    if cell_name not in {w["name"] for w in spec["workloads"]}:
        raise SpecError(f"cell {cell_name!r} is not in {benchmark.name}")
    entries = spec["per_layer" if traced else "end_to_end"]
    return {m["name"]: m["unit"] for m in entries
            if "workloads" not in m or cell_name in m["workloads"]}


def _load_module(kind: str, what: str, name: str, root: Path):
    """``<root>/<kind>/<name>.py`` as a module; an unknown name is an
    error, never a default."""
    if not NAME_RE.match(name):
        raise SpecError(f"bad {what} name {name!r}: no file "
                        f"bench/{kind}/{name}.py")
    path = root / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"unknown {what} {name!r}: no file "
                        f"bench/{kind}/{name}.py")
    mod_name = f"bench_{what}_{name}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs: a dataclass looks its module up there
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = BENCH):
    """``read`` of ``<root>/metrics/<name>.py``."""
    return _load_module("metrics", "metric", name, root).read


def load_reference(name: str, root: Path = BENCH):
    """The reference module ``<root>/references/<name>.py`` (the interface:
    ``references/qwen3_dense.py``)."""
    return _load_module("references", "reference", name, root)


def peak_entry(device_kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = json.loads(path.read_text())["chips"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def seed_key(seed: int):
    """A PRNG key from a seed of any size: the low 32 bits seed it, the
    rest are folded in."""
    import jax
    if seed < 0:
        raise SpecError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def model_config(config_spec: dict):
    from repro.models.config import get_config
    return dataclasses.replace(get_config(config_spec["arch"]),
                               **config_spec["model"])


class Program:
    """The compiled RoundPipe LoRA step of one cell and its initializer.

    ``pool_dtype`` overrides the mix's pool (the control runs the program's
    own lower-precision pool)."""

    def __init__(self, cell: dict, pool_dtype: str | None = None):
        import jax

        from repro.core.dispatch import init_roundpipe_state
        from repro.core.plan import plan_from_config
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import StepConfig, build_train_step
        from repro.models.lora import LoraConfig
        from repro.optim import OptConfig

        mix = cell["mix"]
        self.cfg = cfg = model_config(cell["config_spec"])
        n_data, n_model = (int(x) for x in cell["mesh"].split("x"))
        if n_data * n_model != cell["chips"]:
            raise SpecError(f"mesh {cell['mesh']} is not {cell['chips']} "
                            f"chips")
        self.pool_dtype = pool_dtype or mix["pool_dtype"]
        lora = LoraConfig(rank=mix["lora"]["rank"],
                          alpha=float(mix["lora"]["alpha"]),
                          target_modules=tuple(mix["lora"]["targets"]))
        o = mix["optimizer"]
        self.opt = OptConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                             grad_clip=o["grad_clip"],
                             weight_decay=o["weight_decay"])
        self.plan = plan_from_config(cfg, n_model, lora=lora,
                                     pool_dtype=self.pool_dtype)
        seq = mix["seq"]
        step_cfg = StepConfig(strategy="roundpipe", grad_accum=1,
                              async_optimizer=False,
                              sequence_parallel=n_model > 1,
                              kv_chunk=min(1024, seq),
                              xent_chunk=min(256, seq), partition=self.plan,
                              lora=lora, pool_dtype=self.pool_dtype,
                              opt=self.opt)
        self.mesh = make_mesh((n_data, n_model), ("data", "model"))
        with self.mesh:
            self.step, self.state_sh, _ = build_train_step(
                cfg, self.mesh, step_cfg, mix["batch"], seq)
        self.init = jax.jit(lambda key: init_roundpipe_state(
            key, cfg, step_cfg, n_workers=n_model),
            out_shardings=self.state_sh)

    def call(self, state, batch):
        """The call the window times (inside ``with self.mesh``)."""
        return self.step(state, batch)


def _host(tree, n_layers):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float64)[:n_layers],
                        jax.device_get(tree))


def check_steps(prog: Program, call, state, batches, n: int):
    """Drive the program's first ``n`` steps through the window's own
    ``call`` and feed, and keep what the comparison reads: each step's loss,
    the first gradient as the optimizer took it (from Adam's first moment
    after step 1) and the change of the float32 master adapters over the
    ``n`` steps; and each step's time, which sizes the window's look-ahead."""
    import jax
    n_layers = prog.cfg.n_layers
    master0 = _host(state["opt"]["master"]["lora"], n_layers)
    losses, m1, step_s = [], None, []
    for i in range(n):
        t0 = time.perf_counter()
        state, metrics = call(state, batches.batch(i))
        jax.block_until_ready(state)
        losses.append(float(metrics["loss"]))
        step_s.append(time.perf_counter() - t0)
        if i == 0:
            m1 = _host(state["opt"]["m"]["lora"], n_layers)
    master = _host(state["opt"]["master"]["lora"], n_layers)
    grad1 = jax.tree.map(lambda m: m / (1.0 - prog.opt.b1), m1)
    change = jax.tree.map(lambda a, b: a - b, master, master0)
    return state, {"losses": losses, "step_s": step_s,
                   "grad1": reference.layer_rows(grad1, n_layers),
                   "change": reference.layer_rows(change, n_layers)}


def reference_run(cell: dict, seed: int, batches, n: int) -> dict:
    """The cell's reference module over its first ``n`` batches."""
    module = cell["reference"]
    s = module.Shape.from_config(cell["config_spec"])
    job = reference.Job.from_mix(cell["mix"])
    return reference.run(seed_key(seed),
                         [batches.batch(i) for i in range(n)], module, s, job)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    step_s: list
    dispatch_s: list
    seconds: float
    failed: int
    ahead: int
    sent: int

    @property
    def steps(self) -> int:
        return len(self.step_s)


def steps_ahead(step_s: float, seconds: float) -> int:
    """How many steps the window keeps dispatched ahead of the one it waits
    on: ``AHEAD_SECONDS`` of device work, or the window's length where that
    is shorter, at the given step time; at least 1, at most ``MAX_AHEAD``."""
    want = min(AHEAD_SECONDS, seconds) / max(step_s, 1e-6)
    return max(1, min(MAX_AHEAD, math.ceil(want)))


def timed_window(call, state, batches, first: int, seconds: float,
                 ahead: int, annotate: bool = False):
    """Steps from batch ``first``, with ``ahead`` steps dispatched ahead of
    the one waited on, so that the chip stays fed while the host stands
    still.  When ``seconds`` have passed nothing more is sent, every step
    sent is waited for, and the clock is read after that wait: all the work
    sent counts, over all that time.  Each step's loss is read when the
    window waits on it, ``ahead - 1`` steps after the step was sent.  A
    step's time runs from the previous step's completion as the host sees it
    (the window's start for the first) to its own: the wait on its loss,
    and on the whole state after the last step.  ``dispatch_s`` is the time
    until the step call returned.  A step that raises, and every step sent
    before it and not yet waited on, count as failed.  Returns (state,
    Window)."""
    import jax
    span = jax.profiler.TraceAnnotation if annotate else \
        (lambda name: contextlib.nullcontext())
    step_s, dispatch_s, failed = [], [], 0
    pending = collections.deque()
    t_w0 = t_prev = time.perf_counter()
    i, closed, sent = first, False, 0
    try:
        while True:
            while not closed and len(pending) < ahead:
                with span("bench.batch"):
                    batch = batches.batch(i)
                t0 = time.perf_counter()
                sent += 1
                with span("bench.dispatch"):
                    state, metrics = call(state, batch)
                dispatch_s.append(time.perf_counter() - t0)
                pending.append(metrics["loss"])
                i += 1
            with span("bench.block"):
                loss = float(pending.popleft())
                if not pending:
                    jax.block_until_ready(state)
            t_end = time.perf_counter()
            if not math.isfinite(loss):
                failed += 1
            step_s.append(t_end - t_prev)
            t_prev = t_end
            closed = closed or t_end - t_w0 >= seconds
            if closed and not pending:
                break
    except Exception:                          # noqa: BLE001 — counted
        import traceback
        traceback.print_exc()
        failed += 1 + len(pending)
        t_prev = time.perf_counter()
    return state, Window(step_s, dispatch_s, t_prev - t_w0, failed, ahead,
                         sent)


class CompileCounter:
    """Backend compiles seen by JAX's monitoring while it is open, with
    their seconds."""

    def __enter__(self):
        import jax
        self.seconds: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds.append(secs)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    """The most HBM any of the cell's chips held: the peak of the buffers
    in use plus the peak the runtime reserved for programs' scratch (XLA's
    temporaries, which ``peak_bytes_in_use`` leaves out on a TPU)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def stalls(window: Window, top: int = 5) -> str:
    """The window's slowest steps against its median, each with the step
    after it, for standard error: a rate that moves while the 90th
    percentile holds comes from a few long steps, and these name them.  A
    long step followed by short ones is the host standing still while the
    chip worked through the steps dispatched ahead; one followed by steps
    of the median's length held the chip up."""
    if not window.step_s:
        return "no steps"
    t = window.step_s
    med = sorted(t)[len(t) // 2]
    slow = sorted(range(window.steps), key=lambda i: -t[i])[:top]
    return (f"median step {med * 1e3:.2f} ms; slowest (then the next) "
            + ", ".join(f"#{i} {t[i] * 1e3:.1f} ms ("
                        + (f"{t[i + 1] * 1e3:.1f}" if i + 1 < len(t) else "end")
                        + ")" for i in slow))


def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             t_start: float, metric_units: dict, peaks: dict, log=print,
             prog=None, fault=None) -> dict:
    """One run of ``cell``; returns the result object (without printing).
    ``prog`` reuses a built program; ``fault`` (tests only) wraps the
    step's call, breaking the timed path underneath."""
    from repro.launch.train import use_compile_cache
    use_compile_cache()
    chips = cell["chips"]
    mix = cell["mix"]
    n_check = mix["check_steps"]
    with CompileCounter() as compiles:
        prog = prog or Program(cell)
        call = prog.call if fault is None else fault(prog.call)
        batches = traffic.Batches(mix, prog.cfg.vocab_size, seed)
        with prog.mesh:
            state = prog.init(seed_key(seed))
            state, got = check_steps(prog, call, state, batches, n_check)
            n_compiles = len(compiles.seconds)
            t_setup = time.perf_counter()
            if traced:
                seconds = min(seconds, TRACE_SECONDS)
            ahead = steps_ahead(min(got["step_s"][1:] or got["step_s"]),
                                seconds)
            if traced:
                state, window, trace = traced_window(
                    call, chips, state, batches, n_check, seconds, ahead)
            else:
                state, window = timed_window(call, state, batches, n_check,
                                             seconds, ahead)
                trace = None
            in_window = len(compiles.seconds) - n_compiles
            peak = memory_peak(chips)
            del state
            gc.collect()
    if in_window:
        raise RuntimeError(f"{in_window} compile(s) inside the measured "
                           f"window")
    log(f"setup {t_setup - t_start:.3f} s (backend compiles "
        f"{sum(compiles.seconds[:n_compiles]):.1f} s in {n_compiles}); "
        f"window {window.steps} steps in {window.seconds:.3f} s, "
        f"{window.ahead} dispatched ahead; peak HBM "
        f"{peak} bytes")
    log(stalls(window))
    want = reference_run(cell, seed, batches, n_check)
    readings = compare.readings(got, want)
    correct, checks = compare.judge(readings, cell["limits"])
    for line in compare.describe(readings, got, want):
        log(line)

    module = cell["reference"]
    s = module.Shape.from_config(cell["config_spec"])
    job = reference.Job.from_mix(mix)
    dev = device_info(chips)
    record = {
        "cell": cell, "chips": chips, "seed": seed,
        "setup_s": t_setup - t_start,
        "tokens_per_step": batches.tokens_per_batch,
        "window": window, "peak_bytes": peak,
        "peaks": peaks,
        "flops_per_token": module.step_flops_per_token(s, job, mix["seq"]),
        "dequant_bytes_per_call": (
            flops.dequant_bytes(prog.plan.max_block, module.layer_elems(s),
                                prog.pool_dtype)
            if prog.pool_dtype != "none" else None),
        "reference": module, "shape": s, "job": job, "plan": prog.plan,
        "cfg": prog.cfg,
    }
    metrics = {}
    for name, unit in metric_units.items():
        value = load_reader(name)(record, trace)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    dev["memory_peak_bytes"] = peak
    if trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
    result = {"correct": correct, "attempted": window.sent,
              "failed": window.failed, "metrics": metrics, "device": dev}
    if trace is not None:
        result["breakdown"] = trace["breakdown"]
    result["checks"] = checks
    return result


def traced_window(call, chips, state, batches, first, seconds, ahead):
    """The timed window under the profiler, reduced to busy time, op time
    and idle gaps labelled by the harness's own spans."""
    import jax

    from bench import trace as trace_mod
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            state, window = timed_window(call, state, batches, first,
                                         seconds, ahead, annotate=True)
        finally:
            jax.profiler.stop_trace()
        events = trace_mod.load(trace_mod.find_xplane(trace_dir))
    reduced = trace_mod.reduce(events, n_devices=chips)
    reduced["steps"] = window.steps
    return state, window, reduced


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = load_cell(args.workload)
        units = declared_metrics(args.workload, bool(args.trace))
    except SpecError as e:
        log(f"bench: {e}")
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU; JAX found {devices[0].platform} devices")
        return 1
    if len(devices) < cell["chips"]:
        log(f"bench: the cell needs {cell['chips']} chips, JAX found "
            f"{len(devices)}")
        return 1
    peaks = peak_entry(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, units, peaks, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
