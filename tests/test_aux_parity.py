"""Small parity items: profiler hooks, ParamAndGradient listener,
TrainingHook seam, Curves fetcher.

Parity: SURVEY §5 tracing ("XLA/TPU profiler traces"),
``ParamAndGradientIterationListener.java``, ``spark/api/TrainingHook``,
``CurvesDataFetcher.java``.
"""

import numpy as np

from deeplearning4j_tpu.datasets.curves import load_curves
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import ParamAndGradientIterationListener
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingHook
from deeplearning4j_tpu.util import profiler


def _net_and_data(rng):
    conf = (NeuralNetConfiguration.builder().seed(3).learning_rate(0.1)
            .updater("sgd").activation("tanh")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=2, activation="softmax",
                               loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = rng.standard_normal((16, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
    return net, DataSet(x, y)


def test_param_and_gradient_listener_writes_tsv(rng, tmp_path):
    net, ds = _net_and_data(rng)
    path = str(tmp_path / "pg.tsv")
    net.set_listeners(ParamAndGradientIterationListener(path=path))
    for _ in range(3):
        net.fit(ds)
    lines = open(path).read().strip().split("\n")
    assert len(lines) == 4  # header + 3 iterations
    header = lines[0].split("\t")
    assert header[:2] == ["iteration", "score"]
    assert "layer0/W:norm" in header and "layer0/W:upd" in header
    row = lines[2].split("\t")
    assert len(row) == len(header)
    assert float(row[header.index("layer0/W:norm")]) > 0
    assert np.isfinite(float(row[header.index("layer0/W:upd")]))


def test_training_hooks_called(rng):
    net, ds = _net_and_data(rng)
    calls = []

    class Recorder(TrainingHook):
        def pre_update(self, model, iteration):
            calls.append(("pre", iteration))

        def post_update(self, model, iteration):
            calls.append(("post", iteration))

    pw = ParallelWrapper(net, hooks=[Recorder()])
    pw.fit(ds)
    assert calls[0][0] == "pre" and calls[1][0] == "post"
    assert calls[1][1] > calls[0][1]


def test_training_hooks_see_fresh_params_in_averaging_mode(rng):
    """post_update must observe updated params in BOTH modes
    (regression: averaging mode handed hooks the stale pre-fit copy)."""
    import jax

    net, ds = _net_and_data(rng)
    before = np.asarray(jax.device_get(net.params["layer0"]["W"])).copy()
    seen = []

    class Snap(TrainingHook):
        def post_update(self, model, iteration):
            seen.append(np.asarray(jax.device_get(model.params["layer0"]["W"])))

    pw = ParallelWrapper(net, mode="averaging", hooks=[Snap()])
    pw.fit(ds)
    assert seen and np.abs(seen[-1] - before).max() > 1e-7


def test_listeners_see_fresh_params_in_averaging_mode(rng, tmp_path):
    """Listeners too — without any hook registered (regression: the
    refresh was gated on hooks)."""
    net, ds = _net_and_data(rng)
    path = str(tmp_path / "avg_pg.tsv")
    net.set_listeners(ParamAndGradientIterationListener(path=path))
    pw = ParallelWrapper(net, mode="averaging")
    for _ in range(3):
        pw.fit(ds)
    lines = open(path).read().strip().split("\n")
    header = lines[0].split("\t")
    col = header.index("layer0/W:upd")
    upds = [float(line.split("\t")[col]) for line in lines[2:]]
    assert any(u > 1e-9 for u in upds), f"stale params: updates {upds}"


def test_profiler_trace_captures_annotated_regions(tmp_path, rng):
    """trace() runs the body once and leaves a capture that holds the
    annotated host region."""
    ran = []
    with profiler.trace(str(tmp_path / "trace")):
        with profiler.annotate("custom-phase"):
            ran.append(1)
    assert ran == [1]
    capture = profiler.load_trace(str(tmp_path / "trace"))
    names = {e.name for p in capture.planes for line in p.lines
             for e in line.events}
    assert "custom-phase" in names
    assert profiler.device_planes(capture) == []   # CPU: host plane only


def test_curves_fetcher(rng):
    ds = load_curves(num_examples=32, seed=9)
    assert ds.features.shape == (32, 784)
    assert ds.labels.shape == (32, 6)
    assert 0.0 <= ds.features.min() and ds.features.max() <= 1.0
    # every image has an actual stroke, none is saturated
    on = (ds.features > 0.5).sum(axis=1)
    assert (on > 10).all() and (on < 400).all()
    # deterministic by seed
    ds2 = load_curves(num_examples=32, seed=9)
    np.testing.assert_array_equal(ds.features, ds2.features)
    nhwc = load_curves(num_examples=4, flat=False)
    assert nhwc.features.shape == (4, 28, 28, 1)
