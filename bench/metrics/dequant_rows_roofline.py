"""Roofline share of the pool's dequant kernel (``kernels/dequant.py``,
called by ``ring.dequant_block``), in %: the bytes its calls must move
(``bench/flops.py``, from the rows of the plan's largest block and the
reference module's ``layer_elems``), at the chip's HBM bandwidth, over the
device time of its events.  The kernel is bound by bytes: it does one
multiply per element written.

The kernel has no ``name=`` of its own; on a TPU v5e its events are the
HLO instructions ``%dequant_rows.N = bf16[rows, elems] custom-call(...)``,
named after the Pallas kernel's Python function (seen in a trace of
``qwen3-32b-2l.lora-int8``).  The match below is by that name and so is
fragile: a rename in the program silences this metric rather than
misreading it."""
import re

KERNEL = re.compile(r"^dequant_rows(\.\d+)?$")


def read(record, trace):
    if trace is None or record["dequant_bytes_per_call"] is None:
        return None
    names = [n for n in trace["ops_s"] if KERNEL.match(n)]
    seconds = sum(trace["ops_s"][n] for n in names)
    calls = sum(trace["ops_n"][n] for n in names) / record["chips"]
    if not names or seconds <= 0:
        return None
    bound = calls * record["dequant_bytes_per_call"] / \
        record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * bound / seconds
