"""``bench/trace.py`` reduces a trace to busy, idle, collective and
breakdown numbers that a direct count over the events confirms."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"


def _mask_count(events, dev, w0, w1, pick=lambda n: True):
    """Busy nanoseconds by painting a 1 ns grid (the slow, plain way)."""
    grid = np.zeros(int(w1 - w0), bool)
    for s, d, _, k in events["devices"][dev]:
        if pick(k):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                grid[int(a - w0):int(b - w0)] = True
    return grid


SYNTHETIC = {
    "devices": {"/device:TPU:0": [
        [100, 50, "fusion.1", "fusion"],
        [140, 30, "collective-permute-start.2", "collective-permute-start"],
        [300, 100, "dequant_rows.1", "custom-call"],
        [350, 10, "fusion.1", "fusion"],
        [480, 40, "fusion.3", "fusion"]]},
    "spans": [[90, 200, "bench.dispatch"], [290, 200, "bench.block"]]}


def test_synthetic_trace_reduces_to_hand_numbers():
    r = trace.reduce(SYNTHETIC, n_devices=1)
    assert r["window_s"] == pytest.approx(400e-9)
    # [100, 170) and [300, 400) and [480, 490) inside [90, 490)
    assert r["busy_s"] == pytest.approx(180e-9)
    dev = r["per_device"]["/device:TPU:0"]
    assert dev["collective_s"] == pytest.approx(30e-9)
    assert dev["collective_exposed_s"] == pytest.approx(20e-9)
    assert r["ops_n"] == {"fusion.1": 2, "collective-permute-start.2": 1,
                          "dequant_rows.1": 1, "fusion.3": 1}
    assert r["breakdown"]["device_ops"][0] == ["dequant_rows.1",
                                               pytest.approx(100e-9)]
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.dispatch", "bench.block",
                                    "bench.dispatch"]
    assert [g[1] for g in gaps] == pytest.approx([130e-9, 80e-9, 10e-9])


def test_fewer_planes_than_chips_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(SYNTHETIC, n_devices=4)
    with pytest.raises(ValueError):
        trace.reduce({"devices": SYNTHETIC["devices"], "spans": []})


@pytest.mark.parametrize("path", sorted(DATA.glob("*.events.json")),
                         ids=lambda p: p.name)
def test_recorded_trace_matches_a_direct_count(path):
    """A trace recorded on the chip, trimmed: busy time and exposed
    collective time agree with painting the events on a 1 ns grid, and
    with the numbers recorded beside it."""
    events = json.loads(path.read_text())
    known = events.pop("known")
    events.pop("source")
    r = trace.reduce(events, n_devices=len(events["devices"]))
    w0 = min(s for s, _, _ in events["spans"])
    w1 = max(s + d for s, d, _ in events["spans"])
    for dev, numbers in r["per_device"].items():
        busy = _mask_count(events, dev, w0, w1)
        coll = _mask_count(events, dev, w0, w1,
                           lambda k: bool(trace.COLLECTIVE.match(k)))
        comp = _mask_count(events, dev, w0, w1,
                           lambda k: not trace.COLLECTIVE.match(k))
        assert numbers["busy_s"] == pytest.approx(busy.sum() / 1e9, abs=2e-9)
        assert numbers["collective_exposed_s"] == pytest.approx(
            (coll & ~comp).sum() / 1e9, abs=2e-9)
    assert r["busy_s"] == pytest.approx(known["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(known["window_s"], rel=1e-9)
    assert r["breakdown"]["idle_gaps"][0][0] == known["longest_gap_span"]


@pytest.mark.parametrize("text,name,kind", [
    ("%dequant_rows.1 = bf16[2,487598080]{1,0:T(2,128)(2,1)} custom-call("
     "s8[2,487598080]{1,0:T(4,128)(4,1)} %dynamic-update-slice.33), "
     'custom_call_target="tpu_custom_call"', "dequant_rows.1", "custom-call"),
    ("%collective-permute-start.2 = (s8[487598080]{0:T(1024)(128)(4,1)}, "
     "u32[]{:S(2)}) collective-permute-start(s8[487598080]{0} %x), "
     "channel_id=1", "collective-permute-start.2",
     "collective-permute-start"),
    ("%while.400 = (u32[]{:T(128)}, s8[4]{0}) while((u32[]{:T(128)}) "
     "%tuple.581), condition=%cond.1", "while.400", "while"),
    ("fusion.7", "fusion.7", ""),
])
def test_hlo_op_names_and_opcodes(text, name, kind):
    assert trace.parse_op(text) == (name, kind)
