"""The flash forward kernel's (``flash_fwd``) share of its roofline in a model
of the hybrid state-space family: the least time the chip could take for the
attention calls that the window's steps need, one an attention layer of
``layer_types`` a step at ``num_attention_heads`` query heads
(``benchmarks/flops_hybrid.py``), over the kernel's summed device time in the
trace. ``flash_fwd_roofline`` counts a GPT-2 block from ``n_layer`` and
``n_head`` and would count every layer of this family an attention layer. A
step that recomputes its block bodies runs the kernel twice a layer: that is
kernel time and no more work, so the share falls, as it should."""

from benchmarks import flops, flops_hybrid

KERNELS = ("flash_fwd",)
COST = flops_hybrid.attention_fwd_cost


def read(trace, cell, window, peaks, kernels=KERNELS, cost=COST):
    cfg = cell["config"]
    if trace is None or "layer_types" not in cfg:
        return None  # no capture, or a configuration of another family
    seconds = trace.kernel_seconds(*kernels)
    if not seconds:
        return None  # the kernel did not run: nothing to read, never 0
    least, bound = flops.roofline_seconds(
        cost(cfg, window["batch"], window["seq_len"]), peaks)
    calls = cfg["layer_types"].count("attention") * window["steps"]
    return {"value": 100.0 * calls * least / seconds, "bound": bound,
            "kernel_s": seconds, "kernel_events": trace.kernel_count(*kernels)}
