"""The whole compiled step's share of the chip's peak: the model's operations
per step (forward + backward, causal attention as half, recomputation not
counted; ``benchmarks/flops.py``) over the device's busy time per step in the
trace, against the peak bf16 rate."""

from benchmarks import flops


def read(trace, cell, window, peaks):
    if trace is None or not trace.busy_s or not window["steps"]:
        return None
    per_step = flops.train_flops_per_token(
        cell["config"], window["seq_len"]) * window["batch"] * window["seq_len"]
    busy_per_step = trace.busy_s / window["steps"]
    return {"value": 100.0 * per_step / (busy_per_step * peaks["bf16_flops_per_s"])}
