"""The harness finds configurations, cells, mixes and metrics by name,
refuses unknown ones, and takes a new cell from files alone."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import compare, harness
from bench.tests import tiny

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_with_its_config_and_mix(cell):
    c = harness.load_cell(cell)
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert (c["config"], c["traffic"], c["chips"]) == (
        w["config"], w["traffic"], w["chips"])
    assert c["why"] == w["why"]
    assert set(c["limits"]) == set(compare.NAMES)


@pytest.mark.parametrize("kind,name", [
    ("workloads", "no-such-cell"), ("configs", "no-such-config"),
    ("traffic", "no-such-mix"), ("workloads", "../BENCHMARK"),
    ("workloads", "a/b")])
def test_unknown_or_bad_names_are_refused(kind, name):
    with pytest.raises(harness.SpecError):
        harness.load_spec(kind, name)


def test_unknown_metric_is_refused():
    with pytest.raises(harness.SpecError):
        harness.load_reader("no_such_metric")


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_every_declared_metric_has_a_reader(name):
    assert callable(harness.load_reader(name))


@pytest.mark.parametrize("cell", CELLS)
def test_declared_metrics_follow_their_workloads_keys(cell):
    e2e = harness.declared_metrics(cell, traced=False)
    per_layer = harness.declared_metrics(cell, traced=True)
    assert "setup_s" in e2e and len(e2e) >= 2 and per_layer
    for m in METRICS:
        listed = m["name"] in (per_layer if m in SPEC["per_layer"] else e2e)
        assert listed == ("workloads" not in m or cell in m["workloads"])


def test_unknown_device_kind_has_no_peaks():
    assert harness.peak_entry("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.SpecError):
        harness.peak_entry("TPU v9 imaginary")


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_file_runs_its_published_keys(config):
    """The registry fields the harness runs agree with the published keys
    the reference reads, and ``reduced`` names what differs."""
    c = harness.load_spec("configs", config)
    entry = next(e for e in SPEC["configs"] if e["name"] == config)
    assert entry["file"] == f"bench/configs/{config}.json"
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    m = c["model"]
    pairs = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "tie_word_embeddings": "tie_embeddings",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}
    for pub, field in pairs.items():
        assert c[pub] == m[field], pub
    for key in c["reduced"]:
        assert c[key] != c["published"][key]
    cfg = harness.model_config(c)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) == (
        m["n_layers"], m["d_model"], m["d_ff"])


def test_benchmark_json_keeps_the_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith("bench/") and SPEC["paths"] == [
        "bench"]
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"] + METRICS]
    assert len(names) == len(set(names))
    pattern = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    assert all(pattern.match(n) for n in names)
    for w in SPEC["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_a_new_cell_is_files_alone(tmp_path):
    """A throwaway configuration, mix, cell and metric, added as files in
    a directory of their own, load with no edit to the harness."""
    tiny.write_cell(tmp_path, name="throwaway")
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "steps_seen.py").write_text(
        "def read(record, trace):\n    return float(record['steps'])\n")
    spec = {"workloads": [{"name": "throwaway"}],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "steps_seen", "unit": "1",
                           "workloads": ["throwaway"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("throwaway", tmp_path)
    assert cell["config_spec"]["model"]["d_model"] == 64
    assert cell["mix"]["seq"] == 64
    assert harness.declared_metrics("throwaway", True,
                                    tmp_path / "BENCHMARK.json") == {
        "steps_seen": "1"}
    read = harness.load_reader("steps_seen", tmp_path)
    assert read({"steps": 3}, None) == 3.0
    shutil.rmtree(tmp_path / "metrics")
    with pytest.raises(harness.SpecError):
        harness.load_reader("steps_seen", tmp_path)
