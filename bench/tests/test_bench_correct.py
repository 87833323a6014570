"""The comparison that decides ``correct``, driven through the rest of a
run on the CPU (the harness's look for a chip skipped) at a small size:
a sound run comes out correct, each fault a cell can have and the cell's
control come out not correct."""
from __future__ import annotations

import time

import pytest

from bench import faults, harness
from bench.tests import tiny

UNITS = {"tokens_per_s": "tokens/s", "setup_s": "s"}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 31 + 4049          # more than 32 signed bits hold


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A small cell with the bf16 cell's job and limits, and its program
    and control program, compiled once."""
    root = tiny.write_cell(tmp_path_factory.mktemp("cell"), small=True)
    cell = harness.load_cell("tiny", root)
    return (cell, harness.Program(cell),
            harness.Program(cell, pool_dtype=cell["control"]["pool_dtype"]))


def _run(cell, prog, fault=None, limits=None):
    if limits:
        cell = dict(cell, limits=limits)
    return harness.run_cell(cell, SEED, 0.05, False, time.perf_counter(),
                            UNITS, PEAKS, log=lambda m: None, prog=prog,
                            fault=fault)


def test_sound_run_is_correct(small):
    cell, prog, _ = small
    res = _run(cell, prog)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == set(UNITS)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "token_altered"])
def test_fault_is_not_correct(small, fault):
    cell, prog, _ = small
    assert fault in cell["faults"]
    res = _run(cell, prog, fault=faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(small):
    """The program's own lower-precision pool (int8 under the bf16 cell),
    compared with the same reference."""
    cell, _, control = small
    res = _run(cell, control)
    assert not res["correct"], res["checks"]


def test_readings_tool_prints_one_line_per_variant_and_seed(tmp_path,
                                                             capsys):
    import json

    from bench import readings
    tiny.write_cell(tmp_path)
    out = tmp_path / "r.jsonl"
    assert readings.main(["--workload", "tiny", "--seeds", "3,4",
                          "--variants", "sound,half_batch",
                          "--specs", str(tmp_path), "--out", str(out)]) == 0
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [(x["seed"], x["variant"]) for x in lines] == [
        (3, "sound"), (3, "half_batch"), (4, "sound"), (4, "half_batch")]
    for x in lines:
        assert {"loss_gap", "grad_gap", "grad_diff", "change_gap"} <= set(x)
    assert lines[1]["grad_gap"] > 10 * lines[0]["grad_gap"]
    assert capsys.readouterr().out.count("\n") == 4
