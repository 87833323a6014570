"""Profiler trace -> device busy time, op time, collectives and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a small
dict of events; ``reduce`` turns that dict into numbers.  The dict is what
``bench/testdata`` keeps of a trace recorded on the chip:

  {"devices": {"/device:TPU:0": [[start_ns, dur_ns, op, kind], ...], ...},
   "spans":   [[start_ns, dur_ns, span_name], ...]}

Device events are the "XLA Ops" line of each TPU plane, whose event names
are HLO instructions (``%dequant_rows.1 = bf16[...] custom-call(...)``):
``op`` is the instruction's name (``dequant_rows.1``) and ``kind`` its
opcode (``custom-call``).  Control flow (``while``, ``conditional``,
``call``) holds other ops and is left out.  Spans are the harness's own host
annotations (``bench.batch``, ``bench.dispatch``, ``bench.block``), on the
same clock; the traced window runs from the first span's start to the last
span's end.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
HLO = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][a-z0-9-]*)\(")
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-broadcast|send|recv)")
TOP = 10


def parse_op(text: str):
    """(name, opcode) of an HLO instruction's text; the text itself and
    "" where it is not one."""
    m = HLO.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = []
                    for e in line.events:
                        name, kind = parse_op(e.name)
                        if kind not in CONTAINERS:
                            ops.append([float(e.start_ns),
                                        float(e.duration_ns), name, kind])
                    devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[float(e.start_ns), float(e.duration_ns), e.name]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans)}


def _union(intervals):
    """Sorted, merged [a, b) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _subtract(merged, cover):
    """Length of ``merged`` not covered by ``cover`` (both merged)."""
    left, j = 0.0, 0
    for a, b in merged:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            ca, cb = cover[k]
            if ca > cur:
                left += ca - cur
            cur = max(cur, cb)
            k += 1
        if cur < b:
            left += b - cur
    return left


def _label(a, b, spans) -> str:
    """The host span that overlaps [a, b) the most."""
    best, name = 0.0, "no span"
    for s, d, n in spans:
        ov = min(b, s + d) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def reduce(events: dict, n_devices: int | None = None) -> dict:
    """Numbers of the traced window, times in seconds.

    ``busy_s`` is the union of op intervals, averaged over the devices;
    ``per_device`` holds each device's busy, collective and exposed
    collective seconds (collective time during which no other op runs);
    ``ops_s`` / ``ops_n`` are each op's device seconds (averaged over
    devices) and event count, by HLO name; ``breakdown`` lists the ops that took most
    time and the longest idle gaps, each gap named by the host span open
    during most of it."""
    spans = events["spans"]
    if not spans:
        raise ValueError("the trace holds no bench.* host spans")
    w0 = min(s for s, _, _ in spans)
    w1 = max(s + d for s, d, _ in spans)
    names = sorted(events["devices"])
    if n_devices is not None:
        if len(names) < n_devices:
            raise ValueError(f"the trace holds {len(names)} TPU planes, the "
                             f"run used {n_devices}")
        names = names[:n_devices]
    per_device, ops_s, ops_n, gaps = {}, {}, {}, []
    for dev in names:
        clipped = [(max(s, w0), min(s + d, w1), n, k)
                   for s, d, n, k in events["devices"][dev]
                   if s + d > w0 and s < w1]
        busy = _union([a, b] for a, b, _, _ in clipped)
        coll = _union([a, b] for a, b, _, k in clipped
                      if COLLECTIVE.match(k))
        compute = _union([a, b] for a, b, _, k in clipped
                         if not COLLECTIVE.match(k))
        per_device[dev] = {"busy_s": _length(busy) / 1e9,
                           "collective_s": _length(coll) / 1e9,
                           "collective_exposed_s":
                               _subtract(coll, compute) / 1e9}
        for a, b, n, _ in clipped:
            ops_s[n] = ops_s.get(n, 0.0) + (b - a) / 1e9 / len(names)
            ops_n[n] = ops_n.get(n, 0) + 1
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    window_s = (w1 - w0) / 1e9
    busy_s = sum(d["busy_s"] for d in per_device.values()) / len(names)
    top_ops = sorted(ops_s.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, reverse=True)[:TOP]
    return {
        "window_s": window_s, "busy_s": busy_s, "per_device": per_device,
        "ops_s": ops_s, "ops_n": ops_n,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[_label(a, b, spans), d / 1e9]
                          for d, a, b in top_gaps]},
    }
