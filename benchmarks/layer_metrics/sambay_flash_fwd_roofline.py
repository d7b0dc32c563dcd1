"""The flash forward kernel's (``flash_fwd``) share of its roofline in a model
of the decoder-hybrid-decoder family: the least time the chip could take for
the differential attention that the window's steps need, a layer of
``layer_types`` at a time (the band of a windowed layer, the causal half of a
full or cross one; ``benchmarks/flops_sambay.py``), over the kernel's summed
device time in the trace, however many calls the program makes of a layer."""

from benchmarks import flops, flops_sambay

KERNELS = ("flash_fwd",)
COST = flops_sambay.attention_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    cfg = cell["config"]
    if trace is None or "sliding_window" not in cfg:
        return None  # no capture, or a configuration of another family
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    layers = [flops.roofline_seconds(
        cost(kind, cfg, window["batch"], window["seq_len"]), peaks)
        for kind in cfg["layer_types"]
        if kind in flops_sambay.ATTENTION_KINDS]
    least = sum(seconds_ for seconds_, _ in layers)
    return {"value": 100.0 * window["steps"] * least / seconds,
            "bound": sorted({bound for _, bound in layers}),
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
