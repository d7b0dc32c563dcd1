"""ResNet-50 as a ComputationGraph (BASELINE.json config #3).

The reference's ResNet-50 story is "ComputationGraph + cuDNN conv
helpers" (``nn/graph/ComputationGraph.java:677``,
``deeplearning4j-cuda/.../CudnnConvolutionHelper.java:51``); here the
whole bottleneck DAG — convs, batch norms, residual adds — is traced
into one XLA program per train step, NHWC, with bf16 compute feeding
the MXU (128x128 systolic tiles like the conv channel widths here) and
f32 parameters/statistics.

Architecture: ResNet-v1.5 (stride-2 on the 3x3 of downsampling
bottlenecks — the variant every modern benchmark uses), stages
[3, 4, 6, 3], widths 64/128/256/512, expansion 4.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.layers import (
    ActivationLayer,
    BatchNormalization,
    ConvolutionLayer,
    GlobalPoolingLayer,
    OutputLayer,
    PoolingType,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph,
    ComputationGraphConfiguration,
)

STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)
EXPANSION = 4


def _conv(n_in, n_out, k, s):
    # has_bias=False: every conv here feeds a BN whose beta absorbs it,
    # and the bias gradient would cost a full HBM reduce per conv output.
    return ConvolutionLayer(n_in=n_in, n_out=n_out, kernel_size=(k, k),
                            stride=(s, s), convolution_mode="same",
                            activation="identity", weight_init="relu",
                            has_bias=False)


def _bn(n, gamma: float = 1.0):
    # gamma=0 on the last BN of each block makes residual branches start
    # as identity: bounded activations at init (even in inference mode,
    # where moving stats haven't converged) and better early training.
    return BatchNormalization(n_in=n, n_out=n, gamma=gamma)


def resnet(stages=STAGES, widths=WIDTHS, num_classes: int = 1000,
           compute_dtype: str = "bfloat16", learning_rate: float = 0.1,
           seed: int = 12345) -> ComputationGraph:
    """Build a bottleneck ResNet for [b, H, W, 3] NHWC inputs."""
    base = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(learning_rate).updater("nesterovs")
            .momentum(0.9).weight_init("relu").activation("identity")
            .compute_dtype(compute_dtype)
            .build())
    g = (ComputationGraphConfiguration.builder(base)
         .add_inputs("in")
         .add_layer("stem_conv", _conv(3, 64, 7, 2), "in")
         .add_layer("stem_bn", _bn(64), "stem_conv")
         .add_layer("stem_relu", ActivationLayer(activation="relu"), "stem_bn")
         .add_layer("stem_pool",
                    SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2),
                                     padding=(1, 1),
                                     pooling_type=PoolingType.MAX),
                    "stem_relu"))

    prev, prev_c = "stem_pool", 64
    for si, (blocks, width) in enumerate(zip(stages, widths)):
        out_c = width * EXPANSION
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            p = f"s{si}b{bi}"
            g = (g
                 .add_layer(f"{p}_c1", _conv(prev_c, width, 1, 1), prev)
                 .add_layer(f"{p}_bn1", _bn(width), f"{p}_c1")
                 .add_layer(f"{p}_r1", ActivationLayer(activation="relu"), f"{p}_bn1")
                 .add_layer(f"{p}_c2", _conv(width, width, 3, stride), f"{p}_r1")
                 .add_layer(f"{p}_bn2", _bn(width), f"{p}_c2")
                 .add_layer(f"{p}_r2", ActivationLayer(activation="relu"), f"{p}_bn2")
                 .add_layer(f"{p}_c3", _conv(width, out_c, 1, 1), f"{p}_r2")
                 .add_layer(f"{p}_bn3", _bn(out_c, gamma=0.0), f"{p}_c3"))
            if bi == 0:
                # projection shortcut when shape changes
                g = (g.add_layer(f"{p}_sc", _conv(prev_c, out_c, 1, stride), prev)
                      .add_layer(f"{p}_scbn", _bn(out_c), f"{p}_sc"))
                shortcut = f"{p}_scbn"
            else:
                shortcut = prev
            g = (g.add_vertex(f"{p}_add", ElementWiseVertex(op="add"),
                              f"{p}_bn3", shortcut)
                  .add_layer(f"{p}_out", ActivationLayer(activation="relu"),
                             f"{p}_add"))
            prev, prev_c = f"{p}_out", out_c

    g = (g.add_layer("pool", GlobalPoolingLayer(pooling_type=PoolingType.AVG), prev)
          .add_layer("fc", OutputLayer(n_in=prev_c, n_out=num_classes,
                                       activation="softmax",
                                       loss_function="mcxent",
                                       weight_init="xavier"), "pool")
          .set_outputs("fc"))
    return ComputationGraph(g.build())


def resnet50(num_classes: int = 1000, compute_dtype: str = "bfloat16",
             learning_rate: float = 0.1, seed: int = 12345) -> ComputationGraph:
    """ResNet-50 (stages 3/4/6/3) for [b, 224, 224, 3] NHWC inputs."""
    return resnet(STAGES, WIDTHS, num_classes, compute_dtype, learning_rate, seed)


def resnet50_train_flops_per_example(image_size: int = 224) -> float:
    """Analytic conv/fc MACs summed over the v1.5 graph; train ≈ 3x fwd,
    fwd = 2*MACs."""
    macs = 0
    hw = image_size // 2  # stem conv output 112
    macs += hw * hw * 64 * 3 * 49
    hw //= 2  # 56 after maxpool
    prev_c = 64
    for si, (blocks, width) in enumerate(zip(STAGES, WIDTHS)):
        out_c = width * EXPANSION
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            out_hw = hw // stride
            macs += hw * hw * width * prev_c          # 1x1 (input res)
            macs += out_hw * out_hw * width * width * 9   # 3x3 (strided)
            macs += out_hw * out_hw * out_c * width   # 1x1 expand
            if bi == 0:
                macs += out_hw * out_hw * out_c * prev_c  # projection
            hw = out_hw
            prev_c = out_c
    macs += prev_c * 1000
    return 3.0 * 2.0 * macs
