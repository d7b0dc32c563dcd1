"""The whole compiled step's share of the chip's peak for a model of the
decoder-hybrid-decoder family: the model's operations per step (forward +
backward; ``benchmarks/flops_sambay.py``: every block's matrices by
``layer_types``, the scans, differential attention on the keys its masks keep,
the head once, recomputation not counted) over the device's busy time per
step in the trace, against the peak bf16 rate."""

from benchmarks import flops_sambay


def read(trace, cell, window, peaks):
    if trace is None or not trace.busy_s or not window["steps"]:
        return None
    if "sliding_window" not in cell["config"]:
        return None  # not this family's configuration: nothing to read
    per_step = flops_sambay.train_flops_per_token(
        cell["config"], window["seq_len"]) * window["batch"] * window["seq_len"]
    busy_per_step = trace.busy_s / window["steps"]
    return {"value": 100.0 * per_step / (busy_per_step * peaks["bf16_flops_per_s"])}
