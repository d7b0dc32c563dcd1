"""The selective scan's backward kernel's (``selscan_bwd``) share of its
roofline: the adjoint recurrence's multiply-adds and its reads and writes, not
the chunk's states that the kernel makes again."""

from benchmarks import flops_sambay
from benchmarks.layer_metrics import selscan_fwd_roofline


def read(trace, cell, window, peaks):
    return selscan_fwd_roofline.read(trace, cell, window, peaks,
                                     kernels=("selscan_bwd",),
                                     cost=flops_sambay.selscan_bwd_cost)
