"""Model FLOP utilization of the whole step, in %: the step's required
operations per token (the configuration's reference module,
``step_flops_per_token``: no recomputation, no merge) times the traced
window's tokens per second, over the chips' bf16 peak."""


def read(record, trace):
    if trace is None:
        return None
    w = record["window"]
    tokens_per_s = w.steps * record["tokens_per_step"] / w.seconds
    peak = record["chips"] * record["peaks"]["bf16_flops"]
    return 100.0 * record["flops_per_token"] * tokens_per_s / peak
