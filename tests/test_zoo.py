"""Model-zoo tests: ResNet bottleneck graphs.

Parity: the reference's ResNet-50-class capability is "ComputationGraph
+ conv helpers" (``ComputationGraph.java:677``,
``CudnnConvolutionHelper.java:51``). No benchmark cell runs the full
50-layer graph (PERF.md §3); here a 1/1/1/1-stage bottleneck
variant proves the block wiring (projection shortcuts, zero-init last
BN, strided 3x3) on the CPU mesh cheaply.
"""

import numpy as np

from deeplearning4j_tpu.datasets.dataset import MultiDataSet
from deeplearning4j_tpu.models.zoo.resnet import (
    resnet, resnet50, resnet50_train_flops_per_example)


def test_tiny_resnet_trains(rng):
    net = resnet(stages=(1, 1), widths=(8, 16), num_classes=4,
                 compute_dtype="float32", learning_rate=0.01).init()
    x = rng.standard_normal((4, 32, 32, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 4)]
    mds = MultiDataSet([x], [y])
    net.fit(mds)
    s0 = net._score
    for _ in range(6):
        net.fit(mds)
    assert np.isfinite(net._score)
    assert net._score < s0
    out = net.output(x)
    assert out.shape == (4, 4)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)


def test_resnet50_graph_shape():
    net = resnet50(num_classes=1000)
    # 50 conv/fc layers: 1 stem + 3*16 bottleneck convs + fc
    convs = [v for v in net.conf.vertices
             if v.layer is not None and type(v.layer).__name__ == "ConvolutionLayer"]
    assert len(convs) == 1 + 3 * 16 + 4  # stem + block convs + 4 projections
    assert len(net.order) == len(net.conf.vertices)  # acyclic, fully ordered


def test_resnet50_flops_model():
    # torchvision-reported ~4.09 GMACs fwd => ~24.5 GFLOP per training example
    f = resnet50_train_flops_per_example()
    assert 22e9 < f < 27e9
