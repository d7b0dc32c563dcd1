"""The flash backward kernels' (``flash_dq`` + ``flash_dkv`` together) share
of their roofline: the algorithm's four backward products and its reads and
writes, not the scores that the two kernels compute again."""

from benchmarks import flops
from benchmarks.layer_metrics import flash_fwd_roofline


def read(trace, cell, window, peaks):
    return flash_fwd_roofline.read(trace, cell, window, peaks,
                                   kernels=("flash_dq", "flash_dkv"),
                                   cost=flops.flash_bwd_cost)
