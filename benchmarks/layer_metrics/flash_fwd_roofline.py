"""The flash forward kernel's share of its roofline: the least time the chip
could take for the calls the window made (``benchmarks/flops.py``) over the
kernel's summed device time in the trace."""

from benchmarks import flops

KERNELS = ("flash_fwd",)
COST = flops.flash_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    if trace is None:
        return None
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    cfg = cell["config"]
    one = cost(window["batch"], cfg["n_head"], window["seq_len"],
               cfg["n_embd"] // cfg["n_head"])
    least, bound = flops.roofline_seconds(one, peaks)
    calls = cfg["n_layer"] * window["steps"]
    return {"value": 100.0 * calls * least / seconds, "bound": bound,
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
