"""The flash backward kernels' (``flash_dq`` + ``flash_dkv`` together, or the
one ``flash_dq_dkv``) share of their roofline in a looped language model: the
algorithm's four backward products and its reads and writes
(``benchmarks/flops.py::flash_bwd_cost``), ``total_ut_steps * n_layer`` calls
a step, not the scores that the kernels make again."""

from benchmarks import flops
from benchmarks.layer_metrics import looped_flash_fwd_roofline


def read(trace, cell, window, peaks):
    return looped_flash_fwd_roofline.read(
        trace, cell, window, peaks, kernels=("flash_dq", "flash_dkv"),
        cost=flops.flash_bwd_cost)
