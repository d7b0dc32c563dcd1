"""The whole compiled step's share of the chip's peak for a model of the LFM2
mixture-of-experts family: the model's operations per step (forward +
backward; ``benchmarks/flops_lfm2.py``: every layer's matrices by
``layer_types``, the held experts at ``k * held / E`` a token, the causal half
of the scores, the head once; recomputation and the expert bias count
nothing) over the device's busy time per step in the trace, against the peak
bf16 rate. Its notes carry what the step holds of the experts: the program's
``dl4j_moe_experts_held`` and ``dl4j_moe_layers`` (set when the step is
built) and ``dl4j_moe_held_share{stat="min"|"max"}`` (set by the driver from
the expert bias's calibration), where the program has them."""

from benchmarks import flops_lfm2, program_registry

GAUGES = (("experts_held", "dl4j_moe_experts_held", {}),
          ("moe_layers", "dl4j_moe_layers", {}),
          ("held_share_min", "dl4j_moe_held_share", {"stat": "min"}),
          ("held_share_max", "dl4j_moe_held_share", {"stat": "max"}))


def read(trace, cell, window, peaks):
    if trace is None or not trace.busy_s or not window["steps"]:
        return None
    if "num_routed_experts" not in cell["config"]:
        return None  # not this family's configuration: nothing to read
    per_step = flops_lfm2.train_flops_per_token(
        cell["config"], window["seq_len"]) * window["batch"] * window["seq_len"]
    busy_per_step = trace.busy_s / window["steps"]
    return {"value": 100.0 * per_step / (busy_per_step * peaks["bf16_flops_per_s"]),
            **program_registry.notes(**{
                note: program_registry.gauge(name, **labels)
                for note, name, labels in GAUGES})}
