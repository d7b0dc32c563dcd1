"""The grouped-matmul backward kernels' (``gmm_dx`` + ``gmm_dw`` together)
share of their roofline in a model of the LFM2 mixture-of-experts family:
both products' gradients of their rows and of their matrices
(``benchmarks/flops_lfm2.py::gmm_bwd_cost``) over the kernels' summed device
time."""

from benchmarks import flops_lfm2
from benchmarks.layer_metrics import gmm_fwd_roofline


def read(trace, cell, window, peaks):
    return gmm_fwd_roofline.read(trace, cell, window, peaks,
                                 kernels=("gmm_dx", "gmm_dw"),
                                 cost=flops_lfm2.gmm_bwd_cost)
