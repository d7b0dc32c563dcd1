"""The chunked scan's forward kernel's (``ssd_fwd``) share of its roofline:
the least time the chip could take for the scans that the window's steps
need, one a Mamba-2 layer a step (``benchmarks/flops_hybrid.py``), over the
kernel's summed device time in the trace. A step that recomputes its block
bodies runs the kernel twice a layer: that is kernel time and no more work, so
the share falls, as it should."""

from benchmarks import flops, flops_hybrid

KERNELS = ("ssd_fwd",)
COST = flops_hybrid.ssd_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    if trace is None:
        return None
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    cfg = cell["config"]
    least, bound = flops.roofline_seconds(
        cost(cfg, window["batch"], window["seq_len"]), peaks)
    calls = cfg["layer_types"].count("mamba") * window["steps"]
    return {"value": 100.0 * calls * least / seconds, "bound": bound,
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
