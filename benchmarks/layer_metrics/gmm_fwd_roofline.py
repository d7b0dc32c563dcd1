"""The grouped-matmul forward kernel's (``gmm_fwd``) share of its roofline in
a model of the LFM2 mixture-of-experts family: the least time the chip could
take for the held experts' two products that the window's steps need
(``benchmarks/flops_lfm2.py::gmm_fwd_cost``: the rows the router sends to
held experts, not the padded tiles), over the kernel's summed device time in
the trace. A step that recomputes its block bodies without keeping the
products runs the kernel again: that is kernel time and no more work."""

from benchmarks import flops, flops_lfm2

KERNELS = ("gmm_fwd",)
COST = flops_lfm2.gmm_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    cfg = cell["config"]
    if trace is None or "num_routed_experts" not in cfg:
        return None  # no capture, or a configuration of another family
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    least, bound = flops.roofline_seconds(
        cost(cfg, window["batch"] * window["seq_len"]), peaks)
    return {"value": 100.0 * window["steps"] * least / seconds, "bound": bound,
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
