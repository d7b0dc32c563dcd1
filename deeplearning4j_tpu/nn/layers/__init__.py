"""Layer implementations: pure ``init_params`` / ``forward`` pairs.

Rebuild of ``nn/layers/`` (SURVEY.md §2.1). Design difference from the
reference: DL4J layers are stateful objects holding activations for
backprop; here each impl is a pair of pure functions and the container
differentiates the whole composed forward with ``jax.grad`` — there is no
hand-written ``backpropGradient`` (XLA derives and fuses it), and the
cuDNN helper seam (``ConvolutionHelper.java:30``) has no analog because
XLA emits TPU kernels for conv/pool/norm directly.
"""

from deeplearning4j_tpu.nn.layers.base import LayerImpl, build_layer  # noqa: F401
from deeplearning4j_tpu.nn.layers import (  # noqa: F401  (registers impls)
    attention,
    convolution,
    feedforward,
    hybrid,
    moe,
    normalization,
    recurrent,
    transformer,
)
