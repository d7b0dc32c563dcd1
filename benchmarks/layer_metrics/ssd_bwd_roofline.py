"""The chunked scan's backward kernel's (``ssd_bwd``) share of its roofline:
the algorithm's backward products and its reads and writes, not the scores
that the kernel makes again."""

from benchmarks import flops_hybrid
from benchmarks.layer_metrics import ssd_fwd_roofline


def read(trace, cell, window, peaks):
    return ssd_fwd_roofline.read(trace, cell, window, peaks,
                                 kernels=("ssd_bwd",),
                                 cost=flops_hybrid.ssd_bwd_cost)
