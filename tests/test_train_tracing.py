"""The training step named from inside: scopes on the compiled program,
``fit_scan``'s dispatch as a span tree, and the readers of a device capture
(``util/profiler.scope_seconds`` / ``host_spans`` / ``gaps_by_host_span``).

CPU, tiny net. The persistent compile cache is off around the tests that
compare a compiling dispatch with a warm one."""

import contextlib
import re
import threading
import types

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.multilayer import STEP_SCOPES as SCOPES
from deeplearning4j_tpu.util import profiler


@pytest.fixture
def registry():
    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        yield reg
    finally:
        monitor.set_registry(old)
        monitor.disable_tracing()


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def _net_and_set(seed=3):
    net = gpt(vocab_size=64, d_model=32, n_layers=2, num_heads=2,
              max_len=32, compute_dtype="bfloat16", seed=seed).init()
    ids = np.random.default_rng(0).integers(0, 64, (8, 17))
    data = DataSet(ids[:, :-1].astype(np.float32),
                   ids[:, 1:].astype(np.float32))
    return net, net.stage_scan(data, 2)


def _lowered(net, staged, **kw):
    fit = net._make_scan_fit(1)
    return fit.lower(net.params, net.opt_state, net.states, *staged,
                     net._train_rng()).as_text(**kw)


# ------------------------------------------------------------------ scopes

def test_scopes_name_the_scan_program_and_change_nothing(monkeypatch):
    net, staged = _net_and_set()
    text = _lowered(net, staged, debug_info=True)
    assert len(SCOPES) == 14  # the list the readers take names them all
    for scope in SCOPES:
        if scope == "grad_norm":
            continue  # no gradient normalization configured: traces nothing
        assert re.search(r'[/("]%s[)/]' % scope, text), scope
    # forward and backward read apart, by JAX's own wrappers
    assert '"jvp(mlp_fc)/' in text and '"transpose(jvp(mlp_fc))/' in text
    assert '"optimizer_update/' in text
    assert 'jvp(attention)/fold_heads/' in text
    scoped_program = _lowered(net, staged)
    scoped = net.fit_scan(None, 2, staged=staged)

    # the same net with every scope taken out: the same program, the
    # same losses to the bit
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_net, bare_staged = _net_and_set()
    assert "optimizer_update" not in _lowered(bare_net, bare_staged,
                                              debug_info=True)
    assert _lowered(bare_net, bare_staged) == scoped_program
    bare = bare_net.fit_scan(None, 2, staged=bare_staged)
    assert scoped.tobytes() == bare.tobytes()
    assert np.isfinite(scoped).all() and len(scoped) == 4


def test_grad_norm_scope_appears_when_normalization_is_on():
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.1)
            .gradient_normalization("clip_l2_per_layer")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=2, activation="softmax",
                               loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = np.zeros((4, 4), np.float32)
    y = np.eye(2, dtype=np.float32)[[0, 1, 0, 1]]
    staged = net.stage_scan(DataSet(x, y), 2)
    text = _lowered(net, staged, debug_info=True)
    assert '"grad_norm/' in text and '"optimizer_update/' in text
    assert '"jvp(lm_head)/' in text and '"jvp(loss)/' in text


def test_the_step_picks_the_target_logit_without_a_gather(gathers_under):
    """A gather fixes the logits' layout (a 1.65 GB relayout a step of the
    GPT cells until PR 38): the loss reads its target with a masked sum."""
    import jax.numpy as jnp
    net, staged = _net_and_set()
    text = _lowered(net, staged, debug_info=True)
    assert gathers_under(text, "loss") == []
    # the reader sees the step's other gather, and one where it is looked for
    assert gathers_under(text, "embed")

    def gathered(z, ids):
        with jax.named_scope("loss"):
            return jnp.take_along_axis(z, ids, axis=1).sum()

    planted = jax.jit(gathered).lower(
        jnp.zeros((8, 64)), jnp.zeros((8, 1), jnp.int32))
    assert gathers_under(planted.as_text(debug_info=True), "loss")


# --------------------------------------------------------------- span tree

def _graph_and_set():
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.graph import (
        ComputationGraph, ComputationGraphConfiguration)
    base = NeuralNetConfiguration.builder().seed(1).learning_rate(0.1).build()
    conf = (ComputationGraphConfiguration.builder(base).add_inputs("in")
            .add_layer("d1", DenseLayer(n_in=4, n_out=8), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=2,
                                          activation="softmax",
                                          loss_function="mcxent"), "d1")
            .set_outputs("out").build())
    graph = ComputationGraph(conf).init()
    data = MultiDataSet([np.zeros((8, 4), np.float32)],
                        [np.eye(2, dtype=np.float32)[[0, 1] * 4]])
    return graph, graph.stage_scan(data, 2)


STAGES = ("trace_step", "lower_step", "load_step", "first_launch")
TREE = ("compile", "device_step", "compile_launch", "launch", "fetch") + STAGES
#: the two containers share one dispatch (nn/scan_dispatch.py)
CONTAINERS = pytest.mark.parametrize("make, path", [
    (_net_and_set, "fit_scan"), (_graph_and_set, "graph_fit_scan")],
    ids=["network", "graph"])


def _tree_of(tracer):
    return [e for e in tracer.events()
            if e["type"] == "span" and e["name"] in TREE]


@CONTAINERS
def test_fit_scan_dispatch_is_a_span_tree(registry, no_compile_cache, make,
                                          path):
    net, staged = make()
    tracer = monitor.enable_tracing()
    net.fit_scan(None, 2, staged=staged)
    net.fit_scan(None, 2, staged=staged)
    monitor.disable_tracing()
    spans = _tree_of(tracer)
    # the first dispatch makes the program in stages, each under a name of
    # its own; the second calls what the first made
    assert [s["name"] for s in spans] == [
        *STAGES, "compile_launch", "fetch", "compile",
        "launch", "fetch", "device_step"]
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == 10
    end = lambda s: s["ts_us"] + s["dur_us"]
    for launch, fetch, parent in (spans[4:7], spans[7:10]):
        assert parent["parent"] is None and parent["dispatch"] == parent["id"]
        for child in (launch, fetch):
            assert by_id[child["parent"]] is parent  # the edge resolves
            assert child["dispatch"] == parent["id"]  # one id a dispatch
            assert parent["ts_us"] <= child["ts_us"]
            assert end(child) <= end(parent) + 1e-3
        assert end(launch) <= fetch["ts_us"] + 1e-3
    assert spans[6]["dispatch"] != spans[9]["dispatch"]

    # the four stages are compile_launch's children, in order, and tile it
    # to within a millisecond
    made = spans[4]
    for before, stage in zip((None,) + tuple(spans[:3]), spans[:4]):
        assert by_id[stage["parent"]] is made
        assert stage["dispatch"] == spans[6]["id"]
        if before is not None:
            assert end(before) <= stage["ts_us"] + 1e-3
    assert made["ts_us"] <= spans[0]["ts_us"] and end(spans[3]) <= end(made) + 1e-3
    assert abs(made["dur_us"] - sum(s["dur_us"] for s in spans[:4])) < 1e3

    # the stages held the trace, the lowering and the compile: the call of
    # what they made is a call, as the second dispatch's is
    assert spans[3]["dur_us"] < made["dur_us"] / 10
    assert spans[7]["dur_us"] < made["dur_us"] / 10
    assert spans[6]["attrs"] == spans[9]["attrs"] == {"path": path,
                                                      "epochs": 1}
    # so the launch histogram holds steady-state calls only, and the second
    # dispatch added nothing to the stages' histograms
    count = lambda phase: registry.get(monitor.PHASE_HISTOGRAM,
                                       phase=phase).count
    assert count("launch") == 1 and count("fetch") == 2
    assert [count(p) for p in ("compile_launch",) + STAGES] == [1] * 5


@CONTAINERS
def test_another_staged_shape_is_a_compile_and_says_so(registry, make, path):
    net, (xb, yb) = make()
    miss = lambda: registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
    count = lambda phase: getattr(registry.get(
        monitor.PHASE_HISTOGRAM, phase=phase), "count", 0)
    net.fit_scan(None, 2, staged=(xb, yb))
    net.fit_scan(None, 2, staged=(xb, yb))
    assert (miss(), count("launch"), count("compile")) == (1, 1, 1)
    # half as many minibatches: the same epochs, another program
    half = jax.tree.map(lambda a: a[:2], (xb, yb))
    tracer = monitor.enable_tracing()
    net.fit_scan(None, 2, staged=half)
    monitor.disable_tracing()
    assert [s["name"] for s in _tree_of(tracer)] == [
        *STAGES, "compile_launch", "fetch", "compile"]
    assert miss() == 2 and count("launch") == 1  # it did not hide in launch
    assert [count(p) for p in ("compile", "compile_launch") + STAGES] == [2] * 6
    # both programs stay: either set's next dispatch is a launch
    net.fit_scan(None, 2, staged=half)
    net.fit_scan(None, 2, staged=(xb, yb))
    assert (miss(), count("launch"), count("compile")) == (2, 3, 2)
    # another dtype is another program too
    net.fit_scan(None, 2, staged=jax.tree.map(
        lambda a: a.astype(np.float16), half))
    assert (miss(), count("launch"), count("compile")) == (3, 3, 3)


def test_the_staged_first_call_is_the_jit_calls_program(registry):
    # the program that the stages make is the one a plain first call of the
    # jit function made before: the same text, the same losses to the bit,
    # the same state after it
    net, staged = _net_and_set()
    args = (net.params, net.opt_state, net.states, *staged, net._train_rng())
    fit = net._make_scan_fit(1)
    assert fit.trace(*args).lower().as_text() == _lowered(net, staged)
    staged_losses = net.fit_scan(None, 2, staged=staged)

    plain, plain_staged = _net_and_set()
    p, o, s, losses = plain._make_scan_fit(1)(
        plain.params, plain.opt_state, plain.states, *plain_staged,
        plain._train_rng())
    assert np.asarray(losses).tobytes() == staged_losses.tobytes()
    same = jax.tree.map(lambda a, b: np.asarray(a).tobytes()
                        == np.asarray(b).tobytes(),
                        (net.params, net.opt_state), (p, o))
    assert all(jax.tree.leaves(same))
    # and the second dispatch goes on from there as the jit function does
    again = net.fit_scan(None, 2, staged=staged)
    *_, plain_again = plain._make_scan_fit(1)(p, o, s, *plain_staged,
                                              plain._train_rng())
    assert np.asarray(plain_again).tobytes() == again.tobytes()


def test_what_was_made_is_set_once_as_gauges(registry):
    from deeplearning4j_tpu.nn import scan_dispatch
    assert scan_dispatch.step_program_report() is None  # nothing made yet
    net, staged = _net_and_set()
    fit = net._make_scan_fit(1)
    mem = fit.trace(net.params, net.opt_state, net.states, *staged,
                    net._train_rng()).lower().compile().memory_analysis()
    net.fit_scan(None, 2, staged=staged)
    part = lambda p: registry.get(monitor.STEP_PROGRAM_BYTES_GAUGE,
                                  part=p).value
    assert set(dict(k)["part"] for k in registry.family(
        monitor.STEP_PROGRAM_BYTES_GAUGE)) == {
            "code", "arguments", "temporaries", "outputs", "aliased"}
    assert part("arguments") == mem.argument_size_in_bytes > 0
    assert part("temporaries") == mem.temp_size_in_bytes
    assert part("outputs") == mem.output_size_in_bytes > 0
    assert part("aliased") == mem.alias_size_in_bytes
    assert part("code") == mem.generated_code_size_in_bytes
    flops = registry.get(monitor.STEP_PROGRAM_FLOPS_GAUGE).value
    assert flops > 0
    report = scan_dispatch.step_program_report()
    assert report["count_bytes"] == (
        part("arguments") + part("temporaries") + part("outputs")
        - part("aliased"))
    assert report["flops"] == flops and report["code_bytes"] == part("code")
    # a steady-state dispatch sets nothing again
    registry.gauge(monitor.STEP_PROGRAM_FLOPS_GAUGE).set(-1.0)
    net.fit_scan(None, 2, staged=staged)
    assert registry.get(monitor.STEP_PROGRAM_FLOPS_GAUGE).value == -1.0


def test_a_runtime_without_analysis_leaves_the_gauges_unset(registry):
    from deeplearning4j_tpu.nn import scan_dispatch

    class Silent:
        def memory_analysis(self):
            return None  # as a runtime that has none answers

        def cost_analysis(self):
            raise NotImplementedError("no cost analysis on this platform")

    scan_dispatch.record_step_program(Silent())
    assert registry.get(monitor.STEP_PROGRAM_FLOPS_GAUGE) is None
    assert registry.family(monitor.STEP_PROGRAM_BYTES_GAUGE) == {}  # never 0
    assert scan_dispatch.step_program_report() is None


def test_monitor_spans_need_no_jax():
    # the registry's other users (router, broker, UI) import no jax: a span
    # there opens no TraceAnnotation and still nests and feeds its histogram
    import subprocess
    import sys
    code = (
        "import sys\n"
        "from deeplearning4j_tpu import monitor\n"
        "with monitor.span('inference') as a:\n"
        "    with monitor.span('eval') as b:\n"
        "        assert b.parent == a.id and b._annotation is None\n"
        "assert 'jax' not in sys.modules\n"
        "h = monitor.get_registry().get(monitor.PHASE_HISTOGRAM, phase='eval')\n"
        "assert h.count == 1\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_only_a_programs_first_dispatch_ticks_the_miss_counter(registry):
    net, staged = _net_and_set()
    miss = lambda: registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
    net.fit_scan(None, 2, staged=staged)
    assert miss() == 1
    net.fit_scan(None, 2, staged=staged)
    assert miss() == 1
    net.fit_scan(None, 2, epochs=2, staged=staged)  # another program
    assert miss() == 2


def test_no_tracer_no_event(registry):
    net, staged = _net_and_set()
    tracer = monitor.enable_tracing()
    net.fit_scan(None, 2, staged=staged)
    monitor.disable_tracing()
    n = len(tracer.events())
    assert n >= 3
    net.fit_scan(None, 2, staged=staged)
    assert len(tracer.events()) == n and monitor.active_tracer() is None
    # the histogram is fed all the same
    assert registry.get(monitor.PHASE_HISTOGRAM, phase="launch").count == 1


def test_spans_nest_per_thread_and_survive_an_exception(registry):
    tracer = monitor.enable_tracing()
    with monitor.span("device_step") as outer:
        seen = []
        t = threading.Thread(target=lambda: seen.append(
            monitor.span("data_load").__enter__()))
        t.start()
        t.join(timeout=10)
        assert seen[0].parent is None  # another thread: another tree
        with pytest.raises(RuntimeError):
            with monitor.span("launch"):
                raise RuntimeError("boom")
        with monitor.span("fetch") as after:
            assert after.parent == outer.id  # the failed child was popped
        # closed out of order: the one that stays open is not orphaned,
        # and the one that went leaves no stale parent behind
        first, second = monitor.span("stage"), monitor.span("data_load")
        first.__enter__(), second.__enter__()
        first.__exit__(None, None, None)
        with monitor.span("fetch") as inner:
            assert inner.parent == second.id
        second.__exit__(None, None, None)
        with monitor.span("fetch") as last:
            assert last.parent == outer.id
    monitor.disable_tracing()
    failed = [e for e in tracer.events() if e["name"] == "launch"][0]
    assert failed["attrs"]["error"] == "RuntimeError"


# ---------------------------------------------------- readers of a capture

def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _profile(device_events, host_events):
    line = lambda name, ev: types.SimpleNamespace(name=name, events=ev)
    plane = lambda name, lines: types.SimpleNamespace(name=name, lines=lines)
    return types.SimpleNamespace(planes=[
        plane("/device:TPU:0", [line("XLA Ops", device_events),
                                line("Steps", [_event("0", 0, 10 ** 9)])]),
        plane("/device:CUSTOM:Megascale Trace", []),
        plane("/host:CPU", [line("python3", host_events)])])


def test_scope_seconds_on_a_written_out_profile():
    ops = {"%fusion.2 = f32[4] fusion(...)":
           "jit(run)/while/body/transpose(jvp(mlp_fc))/dot_general:"[:-1]}
    profile = _profile([
        _event("%while.1 = (...) while(...)", 0, 1000),
        _event("%fusion.1 = bf16[8] fusion(...)", 100, 300,
               tf_op="jit(run)/while/body/jvp(mlp_fc)/dot_general"),
        _event("%fusion.2 = f32[4] fusion(...)", 400, 200),
        _event("%copy.7 = bf16[8] copy(...)", 600, 100,
               tf_op="jit(run)/while/body/jvp(attention)/fold_heads/transpose"),
        _event("%divide_subtract_fusion.3 = f32[4] fusion(...)", 700, 250,
               tf_op="jit(run)/while/body/optimizer_update/sub"),
        _event("%fusion.9 = f32[] fusion(...)", 950, 50,
               tf_op="jit(run)/while/body/jvp()/mul"),
    ], [])
    got = profiler.scope_seconds(
        profile, ["mlp_fc", "attention", "fold_heads", "optimizer_update"],
        ops)
    assert got == pytest.approx({
        "mlp_fc/fwd": 300e-9, "mlp_fc/bwd": 200e-9,
        "fold_heads/fwd": 100e-9,  # the innermost scope wins
        "optimizer_update/fwd": 250e-9,
        "": 150e-9})  # the while's own 100 and the unscoped fusion's 50
    rows = profiler.scoped_self_times(profile, ["optimizer_update"], ops)
    assert ("divide_subtract_fusion", "optimizer_update", "fwd", 250) in rows
    # an executable compiled without scopes: nothing is claimed, nothing fails
    assert profiler.scope_seconds(profile, [], {}) == {"": pytest.approx(1e-6)}


def test_host_spans_and_gaps_on_a_written_out_profile():
    ms = 10 ** 6
    device = [_event("%while.1", 10 * ms, 100 * ms),
              _event("%fusion.1", 20 * ms, 30 * ms),
              _event("%while.1", 120 * ms, 100 * ms)]
    host = [
        _event("dl4j/device_step", 5 * ms, 109 * ms, id=1, dispatch=1),
        _event("dl4j/launch", 6 * ms, 3 * ms, id=2, dispatch=1),
        _event("dl4j/fetch", 9 * ms, 104 * ms, id=3, dispatch=1),
        _event("PjitFunction(run)", 6 * ms, 2 * ms),
        _event("dl4j/device_step", 115 * ms, 108 * ms, id=4, dispatch=4),
        _event("dl4j/launch", 116 * ms, 2 * ms, id=5, dispatch=4),
        _event("dl4j/fetch", 118 * ms, 104 * ms, id=6, dispatch=4),
    ]
    profile = _profile(device, host)
    spans = profiler.host_spans(profile)
    assert [s["name"] for s in spans] == ["device_step", "launch", "fetch",
                                          "device_step", "launch", "fetch"]
    assert spans[1]["stats"] == {"id": 2, "dispatch": 1}
    assert spans[0]["line"] == "python3"
    got = profiler.gaps_by_host_span(profile)
    # window 5..223 ms, busy 10..110 and 120..220: idle 5 + 10 + 3
    assert got["dispatches"] == 2
    assert got["window_s"] == pytest.approx(0.218)
    assert got["idle_s"] == pytest.approx(0.018)
    # 5..6 python, 6..9 launch, 9..10 device start | 110..113 fetch tail,
    # 113..116 python, 116..118 launch, 118..120 device start | 220..222
    # fetch tail, 222..223 python
    assert got["launch_s"] == pytest.approx(0.005)
    assert got["fetch_s"] == pytest.approx(0.005)
    assert got["python_s"] == pytest.approx(0.005)
    assert got["unattributed_s"] == pytest.approx(0.003)
    # a capture of a program that opens no spans, or of the CPU alone
    assert profiler.gaps_by_host_span(_profile(device, [])) == {}
    assert profiler.host_spans(_profile(device, [])) == []


def test_gaps_put_the_four_stages_down_to_launch():
    ms = 10 ** 6
    device = [_event("%while.1", 60 * ms, 40 * ms)]
    host = [
        _event("dl4j/compile", 0, 105 * ms, id=1, dispatch=1),
        _event("dl4j/compile_launch", 1 * ms, 54 * ms, id=2, dispatch=1),
        _event("dl4j/trace_step", 1 * ms, 20 * ms, id=3, dispatch=1),
        _event("dl4j/lower_step", 21 * ms, 10 * ms, id=4, dispatch=1),
        _event("dl4j/load_step", 31 * ms, 20 * ms, id=5, dispatch=1),
        _event("dl4j/first_launch", 51 * ms, 4 * ms, id=6, dispatch=1),
        _event("dl4j/fetch", 56 * ms, 48 * ms, id=7, dispatch=1),
    ]
    got = profiler.gaps_by_host_span(_profile(device, host))
    # idle 0..60 and 100..105: 1 python, 54 launch (the stages are inside
    # compile_launch: counted once), 1 python, 4 device start | 4 fetch, 1
    assert got["launch_s"] == pytest.approx(0.054)
    assert got["python_s"] == pytest.approx(0.003)
    assert got["unattributed_s"] == pytest.approx(0.004)
    assert got["fetch_s"] == pytest.approx(0.004)
    assert got["idle_s"] == pytest.approx(0.065)
    # a capture that began inside compile_launch still sums
    late = [e for e in host if e.name != "dl4j/compile_launch"]
    got = profiler.gaps_by_host_span(_profile(device, late))
    assert got["launch_s"] == pytest.approx(0.054)
    assert got["idle_s"] == pytest.approx(0.065)


def test_op_names_reads_the_metadata_stat_from_the_file(tmp_path):
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    field = lambda num, body: varint(num << 3 | 2) + varint(len(body)) + body
    num = lambda n, v: varint(n << 3) + varint(v)
    stat_meta = lambda i, name: field(5, num(1, i) + field(
        2, num(1, i) + field(2, name)))
    event_meta = lambda i, name, stats: field(4, num(1, i) + field(
        2, num(1, i) + field(2, name) + stats))
    plane = lambda name, body: field(1, field(2, name) + body)
    space = (
        plane(b"/host:CPU", event_meta(1, b"%fusion.1 = host", field(
            5, num(1, 26) + field(5, b"not/a/device:")))) +
        plane(b"/device:TPU:0",
              field(3, b"\x12\x07XLA Ops") +  # a line: skipped
              stat_meta(26, b"tf_op") + stat_meta(27, b"jit(run)/interned") +
              event_meta(1, b"%fusion.1 = f32[4] fusion()", field(
                  5, num(1, 26) + field(5, b"jit(run)/jvp(ln1)/add:"))) +
              event_meta(2, b"%copy.2 = f32[4] copy()", field(
                  5, num(1, 26) + num(7, 27))) +
              event_meta(3, b"%while.3", field(5, num(1, 9) + num(3, 5)))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert profiler.op_names(str(path)) == {
        "%fusion.1 = f32[4] fusion()": "jit(run)/jvp(ln1)/add",
        "%copy.2 = f32[4] copy()": "jit(run)/interned"}


def test_self_times_is_the_walk_the_script_uses():
    events = [_event("%while.1", 0, 100), _event("%fusion.1", 10, 30),
              _event("%copy.1", 50, 20), _event("%fusion.2", 120, 5)]
    got = {e.name: ns for e, ns in profiler.self_times(events)}
    assert got == {"%while.1": 50, "%fusion.1": 30, "%copy.1": 20,
                   "%fusion.2": 5}
    assert profiler.op_group("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion"
    assert profiler.op_group("%jvp_flash_fwd_.288 = (...)") == "jvp_flash_fwd_"


def test_compile_cell_lists_the_relayouts_of_a_compiled_text():
    """``scripts/compile_cell.py`` on the three lines PR 38 found in the GPT
    cells' step, beside what it must not list."""
    from scripts.compile_cell import large_copies
    text = """
  %get-tuple-element.29904 = f32[8,1024,50257]{1,2,0:T(8,128)} get-tuple-element(%fusion.5134), index=1
  %copy.299 = f32[8,1024,50257]{2,1,0:T(8,128)} copy(%get-tuple-element.29904), metadata={op_name="jit(run)/jvp(lm_head)/add" source_file="f.py"}
  %bitcast.2927 = f32[8192,50257]{1,0:T(8,128)} bitcast(%copy.299), metadata={op_name="jit(run)/jvp(loss)/reshape"}
  %param.3 = f32[2048,8512]{0,1:T(8,128)} parameter(3)
  %copy.7 = f32[2048,8512]{0,1:T(8,128)S(1)} copy(%param.3), metadata={op_name="jit(run)/remat2"}
  %copy.8 = f32[2048,8512]{0,1:T(8,128)S(1)} copy(%param.3), metadata={op_name="jit(run)/remat2"}
  %copy.9 = f32[1024,1024]{0,1:T(8,128)} copy(%get-tuple-element.29904)
  %copy-done.4 = f32[8,1024,50257]{1,2,0:T(8,128)S(1)} copy-done(%copy-start.4)
"""
    assert large_copies(text) == [
        {"shape": "f32[8,1024,50257]", "MB": 1646.8,
         "from": "{1,2,0:T(8,128)}", "to": "{2,1,0:T(8,128)}",
         "relayout": True, "op_name": "jit(run)/jvp(lm_head)/add",
         "copies": 1},
        {"shape": "f32[2048,8512]", "MB": 69.7, "from": "{0,1:T(8,128)}",
         "to": "{0,1:T(8,128)S(1)}", "relayout": False,
         "op_name": "jit(run)/remat2", "copies": 2}]


# ------------------------------------------------------------------ schema

def _schema():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                        "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("check_telemetry_schema",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_schema_knows_the_span_tree_and_the_new_families(
        registry, no_compile_cache, tmp_path, capsys):
    schema = _schema()
    jsonl = str(tmp_path / "events.jsonl")
    tracer = monitor.enable_tracing(jsonl)
    net, staged = _net_and_set()
    net.fit_scan(None, 2, staged=staged)
    monitor.disable_tracing()
    assert schema.validate_events_file(jsonl) == []
    trace_path = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(trace_path)
    assert schema.validate_chrome_trace_file(trace_path) == []
    text = registry.prometheus_text()
    assert "# TYPE dl4j_jit_cache_miss_total" in text
    assert 'dl4j_step_program_bytes{part="temporaries"}' in text
    assert "# TYPE dl4j_step_program_flops gauge" in text
    assert 'dl4j_phase_duration_ms_count{phase="load_step"} 1' in text
    assert schema.validate_prometheus_text(text) == []
    assert schema.validate_known_metrics(text) == []

    span = {"type": "span", "name": "launch", "ts_us": 1.0, "dur_us": 2.0,
            "pid": 1, "tid": 1, "id": 2, "parent": 1, "dispatch": 1}
    root = {**span, "name": "device_step", "id": 1, "parent": None}
    lines = lambda *objs: [__import__("json").dumps(o) for o in objs]
    assert schema.validate_events_lines(lines(span, root)) == []
    # a stream cut before the tree's root closed warns, and validates
    assert schema.validate_events_lines(lines(span)) == []
    assert "never closed" in capsys.readouterr().err
    # an edge that does not resolve inside a tree that did close, a
    # dispatch id that differs, half a tree
    leaf = {**span, "name": "inner", "id": 3, "parent": 2}
    assert schema.validate_events_lines(lines(leaf, root)) != []
    assert schema.validate_events_lines(
        lines({**span, "dispatch": 7}, root)) != []
    assert schema.validate_events_lines(lines({**root, "dispatch": 9})) != []
    half = {k: v for k, v in span.items() if k != "dispatch"}
    assert schema.validate_events_lines(lines(half, root)) != []
    assert schema.validate_events_lines(lines({**span, "id": "2"}, root)) != []
    # spans from before the tree (no ids at all) still validate
    old = {k: v for k, v in span.items()
           if k not in ("id", "parent", "dispatch")}
    assert schema.validate_events_lines(lines(old)) == []
