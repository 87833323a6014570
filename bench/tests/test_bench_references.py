"""Each configuration brings its architecture's reference module, found by
the name in its file: the harness reaches the reference, the operations per
token and the pool row's elements only through that module, so a new
architecture is new files alone."""
from __future__ import annotations

import ast
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, reference, traffic
from bench.tests import tiny

CONFIGS = sorted(p.stem for p in (harness.BENCH / "configs").glob("*.json"))
SEED = 2 ** 32 + 977            # more than 32 bits
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

STUB = '''"""A stand-in architecture: one adapter pair per layer, a loss that is
its ``A`` factors' mean square, counts that are easy to tell apart."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    width: int

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(n_layers=c["num_hidden_layers"], width=c["hidden_size"])


def make_weights(key, s, job):
    a = jax.random.normal(key, (s.n_layers, job.rank, s.width), jnp.bfloat16)
    b = jnp.zeros((s.n_layers, s.width, job.rank), jnp.bfloat16)
    return {"scale": jnp.full((), 3.0)}, {"w": {"A": a, "B": b}}


def loss_fn(adapters, base, batch, s, job):
    a = adapters["w"]["A"]
    return base["scale"] * jnp.mean(a * a) + jnp.sum(adapters["w"]["B"])


def step_flops_per_token(s, job, seq):
    return 1000.0 * seq + s.width


def layer_elems(s):
    return 7 * s.width
'''


def _stub_cell(root, like="qwen3-1.7b.lora-bf16"):
    tiny.write_cell(root, like=like, reference="stub")
    (root / "references" / "stub.py").write_text(STUB)
    return harness.load_cell("tiny", root)


@pytest.mark.parametrize("name", ["no_such_arch", "a/b", None])
def test_unknown_or_missing_reference_is_refused(tmp_path, name):
    tiny.write_cell(tmp_path, reference=name or "qwen3_dense")
    if name is None:
        path = tmp_path / "configs" / "tiny.json"
        spec = json.loads(path.read_text())
        del spec["reference"]
        path.write_text(json.dumps(spec))
    with pytest.raises(harness.SpecError) as e:
        harness.load_cell("tiny", tmp_path)
    assert f"bench/references/{name or '<name>'}.py" in str(e.value)
    if name is None:
        assert "bench/configs/tiny.json" in str(e.value)
    else:
        with pytest.raises(harness.SpecError):
            harness.load_reference(name)


@pytest.mark.parametrize("config", CONFIGS)
def test_every_config_names_a_reference_that_reads_it(config):
    spec = harness.load_spec("configs", config)
    module = harness.load_reference(spec["reference"])
    for fn in ("make_weights", "loss_fn", "step_flops_per_token",
               "layer_elems"):
        assert callable(getattr(module, fn)), fn
    n = spec["num_hidden_layers"]
    assert module.Shape.from_config(spec).n_layers == n
    assert module.Shape.from_config(
        dict(spec, num_hidden_layers=n + 1)).n_layers == n + 1


def test_harness_imports_no_architecture():
    """``harness.py`` takes from ``bench/reference.py`` and
    ``bench/flops.py`` only what no architecture changes, and they hold
    nothing of one."""
    tree = ast.parse((harness.BENCH / "harness.py").read_text())
    shared = {"reference": {"Job", "run", "layer_rows"},
              "flops": {"dequant_bytes"}}
    used = {(n.value.id, n.attr) for n in ast.walk(tree)
            if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name)
            and n.value.id in shared}
    assert used and all(attr in shared[mod] for mod, attr in used), used
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            assert node.module == "bench" or not node.module.startswith(
                "bench"), node.module
            assert "references" not in node.module
    for name in ("Shape", "make_weights", "loss_fn", "target_paths",
                 "_layer", "_init_layer"):
        assert not hasattr(reference, name), name
    for name in ("layer_matrices", "layer_elems", "step_flops_per_token"):
        assert not hasattr(flops, name), name


def test_reference_run_is_the_configs_module(tmp_path):
    """A configuration that names a stub module: ``reference_run`` runs the
    stub's weights and loss."""
    cell = _stub_cell(tmp_path)
    stub = cell["reference"]
    assert stub.__file__ == str(tmp_path / "references" / "stub.py")
    batches = traffic.Batches(cell["mix"], 512, SEED)
    got = harness.reference_run(cell, SEED, batches, 2)
    s = stub.Shape.from_config(cell["config_spec"])
    job = reference.Job.from_mix(cell["mix"])
    base, adapters = stub.make_weights(harness.seed_key(SEED), s, job)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), adapters)
    want = float(stub.loss_fn(f32, base, None, s, job))
    assert len(got["losses"]) == 2
    assert got["losses"][0] == pytest.approx(want, rel=1e-6)
    assert sorted(got["grad1"]) == [f"w.{f}[{i}]" for f in "AB"
                                    for i in range(s.n_layers)]


def test_record_counts_are_the_configs_module(tmp_path, monkeypatch):
    """A run of a cell whose configuration names a stub module: the
    record's operations per token and dequant bytes are the stub's counts,
    and the record carries the module, its shape, the job and the
    program's plan and model for a reader of a kernel of its own."""
    cell = _stub_cell(tmp_path, like="qwen3-32b-2l.lora-int8")
    seen = {}

    def reader(name):
        def read(record, trace):
            seen.update(record)
            return 1.0
        return read

    monkeypatch.setattr(harness, "load_reader", reader)
    prog = harness.Program(cell)
    res = harness.run_cell(cell, SEED, 0.05, False, time.perf_counter(),
                           {"probe": "1"}, PEAKS, log=lambda m: None,
                           prog=prog)
    assert res["metrics"] == {"probe": {"value": 1.0, "unit": "1"}}
    stub = cell["reference"]
    s = stub.Shape.from_config(cell["config_spec"])
    assert seen["reference"] is stub and seen["shape"] == s
    assert seen["job"] == reference.Job.from_mix(cell["mix"])
    assert seen["plan"] is prog.plan and seen["cfg"] is prog.cfg
    seq = cell["mix"]["seq"]
    assert seen["flops_per_token"] == 1000.0 * seq + s.width
    assert seen["dequant_bytes_per_call"] == flops.dequant_bytes(
        prog.plan.max_block, 7 * s.width, "int8")
