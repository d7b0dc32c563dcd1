"""The flash backward kernels' (``flash_dq`` + ``flash_dkv`` together, or the
one ``flash_dq_dkv``) share of their roofline in a model of the hybrid
state-space family: the algorithm's four backward products and its reads and
writes, not the scores that the kernels make again."""

from benchmarks import flops_hybrid
from benchmarks.layer_metrics import hybrid_flash_fwd_roofline


def read(trace, cell, window, peaks):
    return hybrid_flash_fwd_roofline.read(
        trace, cell, window, peaks, kernels=("flash_dq", "flash_dkv"),
        cost=flops_hybrid.attention_bwd_cost)
