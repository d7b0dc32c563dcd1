"""The flash backward kernels' (``flash_dq`` + ``flash_dkv`` together, or the
one ``flash_dq_dkv``) share of their roofline in a model of the
decoder-hybrid-decoder family: the algorithm's backward products and its reads
and writes, not the scores that the kernels make again."""

from benchmarks import flops_sambay
from benchmarks.layer_metrics import sambay_flash_fwd_roofline


def read(trace, cell, window, peaks):
    return sambay_flash_fwd_roofline.read(
        trace, cell, window, peaks, kernels=("flash_dq", "flash_dkv"),
        cost=flops_sambay.attention_bwd_cost)
