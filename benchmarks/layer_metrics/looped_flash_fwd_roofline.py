"""The flash forward kernel's (``flash_fwd``) share of its roofline in a
looped language model: the least time the chip could take for the attention
calls that the window's steps need (``benchmarks/flops.py::flash_fwd_cost``
at the configuration's heads and ``head_dim``), one a block application,
``total_ut_steps * n_layer`` a step, over the kernel's summed device time in
the trace. ``flash_fwd_roofline`` counts ``n_layer`` calls a step and would
read ``total_ut_steps`` times too low here. A step that runs the kernel again
in a recomputed block body spends kernel time and does no more work, so the
share falls, as it should."""

from benchmarks import flops, flops_looped

KERNELS = ("flash_fwd",)
COST = flops.flash_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    cfg = cell["config"]
    if trace is None or "total_ut_steps" not in cfg:
        return None  # no capture, or a configuration of another family
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    least, bound = flops.roofline_seconds(
        cost(window["batch"], cfg["num_attention_heads"], window["seq_len"],
             cfg["head_dim"]), peaks)
    calls = flops_looped.block_applications(cfg) * window["steps"]
    return {"value": 100.0 * calls * least / seconds, "bound": bound,
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
