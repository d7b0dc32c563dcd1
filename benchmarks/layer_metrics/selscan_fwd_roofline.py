"""The selective scan's forward kernel's (``selscan_fwd``) share of its
roofline: the least time the chip could take for the scans that the window's
steps need, one a Mamba-1 layer a step (``benchmarks/flops_sambay.py``), over
the kernel's summed device time in the trace. Memory-bound by the count; the
kernel's work is on the VPU, for which the chip publishes no peak. A step that
recomputes its block bodies without keeping the scan's output runs the kernel
twice a layer: that is kernel time and no more work, so the share falls."""

from benchmarks import flops, flops_sambay

KERNELS = ("selscan_fwd",)
COST = flops_sambay.selscan_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    cfg = cell["config"]
    if trace is None or "sliding_window" not in cfg:
        return None  # no capture, or a configuration of another family
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    least, bound = flops.roofline_seconds(
        cost(cfg, window["batch"], window["seq_len"]), peaks)
    calls = cfg["layer_types"].count("mamba") * window["steps"]
    return {"value": 100.0 * calls * least / seconds, "bound": bound,
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
