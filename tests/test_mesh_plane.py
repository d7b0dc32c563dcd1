"""Mesh-plane parity + robustness suite (ISSUE 9 tentpole).

Three contracts on the rebuilt NamedSharding plane:

1. **Layout parity** — one fit step under each layout (dp / fsdp / tp /
   pipeline) on the forced-8-device CPU mesh matches the plain
   single-device run: allclose where GSPMD inserts collectives, BITWISE
   where the program is identical (same mesh, same placement).
2. **Checkpoint mesh portability** — a unit written on 8 devices
   restores on 4 and on 1 (``restore_checkpoint(mesh=...)`` re-lowers
   the recorded SpecLayout), forward outputs allclose across shapes and
   bitwise on the shape-identical round trip; training resumes.
3. **Mesh-shrink drill** — the ``faultinject.MeshShrink`` scenario
   (kill mid-epoch → checkpoint fallback → MeshPlane rebuild from the
   survivors → resume) is deterministic: reruns produce bitwise-equal
   restored forwards.

Plus the satellite guards: the check_mesh_api lint keeps the repo clean
(and catches crafted violations), the dl4j_mesh_* metric family is
schema-pinned, and /healthz reports the active topology.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import (MeshPlane, SpecLayout,
                                              active_plane, make_mesh)
from deeplearning4j_tpu.parallel.tensor_parallel import (apply_shardings,
                                                         dense_tp_specs)
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
from deeplearning4j_tpu.parallel.zero import apply_fsdp, apply_zero1
from deeplearning4j_tpu.util.sharded_checkpoint import (restore_checkpoint,
                                                        save_checkpoint)
from jax.sharding import NamedSharding, PartitionSpec as P

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _need8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")


def _net(seed=21):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").activation("tanh")
            .list()
            .layer(DenseLayer(n_in=8, n_out=16))
            .layer(DenseLayer(n_in=16, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _batch(rng, n=32):
    x = rng.standard_normal((n, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
    return DataSet(x, y)


# ------------------------------------------------------------- SpecLayout

def test_speclayout_roundtrip_and_restriction():
    layout = SpecLayout({"layer0": {"W": P(None, "data"), "b": P("data")},
                         "layer1": {"W": P(("fsdp", "tp"), None)}})
    back = SpecLayout.from_payload(layout.to_payload())
    assert back == layout
    # restriction: a mesh without 'fsdp'/'tp' drops those axes; a dim
    # that stops dividing falls back to replication
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    assert back.restricted_spec("layer0", "W", (8, 16), mesh) == \
        P(None, "data")
    assert back.restricted_spec("layer1", "W", (16, 16), mesh) == P()
    # indivisible: 6 % 4 != 0 → replicated
    assert back.restricted_spec("layer0", "b", (6,), mesh) == P()
    # unknown param → replicated
    assert back.restricted_spec("layerX", "W", (4, 4), mesh) == P()


def test_speclayout_from_live_params():
    _need8()
    net = _net()
    mesh = make_mesh({"data": 8})
    apply_fsdp(net, mesh)
    layout = SpecLayout.from_params(net.params)
    assert layout  # something was sharded
    assert layout.get("layer0", "W") == P(None, "data")
    assert net.mesh_plane is not None
    assert net.mesh_plane.topology()["axes"] == {"data": 8}


# ---------------------------------------------------- layout parity suite

def _one_step_ref(rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    ds = _batch(rng)
    ref = _net()
    ref.fit(ds)
    return ds, np.asarray(ref.params_flat())


def test_parity_dp_one_step():
    """One allreduce fit step over data=8 vs the single-device step."""
    _need8()
    ds, ref_flat = _one_step_ref()
    net = _net()
    pw = ParallelWrapper(net, mesh=MeshPlane.build({"data": 8}))
    pw.fit(ds)
    np.testing.assert_allclose(np.asarray(net.params_flat()), ref_flat,
                               rtol=2e-5, atol=1e-6)


def test_parity_fsdp_one_step():
    _need8()
    ds, ref_flat = _one_step_ref()
    net = _net()
    apply_fsdp(net, make_mesh({"data": 8}))
    net.fit(ds)
    np.testing.assert_allclose(np.asarray(net.params_flat()), ref_flat,
                               rtol=2e-5, atol=1e-6)


def test_parity_tp_one_step():
    _need8()
    ds, ref_flat = _one_step_ref()
    net = _net()
    mesh = make_mesh({"model": 8})
    apply_shardings(net, mesh, dense_tp_specs(["layer0", "layer1"]))
    assert net.mesh_plane is not None  # applier pinned the plane
    net.fit(ds)
    np.testing.assert_allclose(np.asarray(net.params_flat()), ref_flat,
                               rtol=2e-5, atol=1e-6)


def test_parity_pipeline_one_step():
    """One SGD step through the stage pipeline == the sequential stack:
    same loss gradient, same updated stage params (allclose — the
    pipelined program psums over the pp axis)."""
    _need8()
    from deeplearning4j_tpu.parallel.pipeline import pipeline_apply

    p_stages, width, b = 8, 8, 16
    rng = np.random.default_rng(3)
    W = jnp.asarray(rng.standard_normal((p_stages, width, width)) * 0.2,
                    jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, width)), jnp.float32)
    mesh = make_mesh({"pp": p_stages})
    fn = lambda w, h: jnp.tanh(h @ w)

    def loss_pp(W):
        return jnp.sum(pipeline_apply(W, fn, x, mesh, "pp") ** 2)

    def loss_seq(W):
        h = x
        for s in range(p_stages):
            h = fn(W[s], h)
        return jnp.sum(h ** 2)

    lr = 0.01
    g_pp = jax.grad(loss_pp)(W)
    g_seq = jax.grad(loss_seq)(W)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(W - lr * g_pp),
                               np.asarray(W - lr * g_seq),
                               rtol=2e-4, atol=2e-5)


def test_parity_same_mesh_is_bitwise():
    """Where the program IS identical (same mesh, same placement, same
    batch), two runs are bitwise equal — the deterministic half of the
    parity contract."""
    _need8()
    rng = np.random.default_rng(7)
    ds = _batch(rng)
    outs = []
    for _ in range(2):
        net = _net()
        apply_fsdp(net, make_mesh({"data": 8}))
        net.fit(ds)
        outs.append(np.asarray(net.params_flat()))
    np.testing.assert_array_equal(outs[0], outs[1])


# ------------------------------------------- checkpoint mesh portability

def test_checkpoint_mesh_reshape_8_4_1_8(rng, tmp_path):
    """Save FSDP-sharded on 8 devices; restore on 4, on 1, and back on
    8. Forward outputs allclose across mesh shapes, BITWISE on the
    shape-identical round trip; the relayout counter ticks only for the
    actual reshapes; training resumes on the shrunken mesh."""
    _need8()
    from deeplearning4j_tpu.monitor import (MESH_RESTORE_RELAYOUT_COUNTER,
                                            get_registry)

    ds = _batch(rng)
    net = _net()
    net.fit(ds)
    mesh8 = make_mesh({"data": 8})
    apply_fsdp(net, mesh8)
    net.fit(ds)
    ref = np.asarray(net.output(ds.features))
    path = save_checkpoint(net, str(tmp_path / "ckpt"))
    with open(os.path.join(path, "layout.json")) as f:
        layout = json.load(f)
    assert layout["mesh"]["axes"] == {"data": 8}
    assert layout["params"]["layer0"]["W"] == [None, "data"]

    before = get_registry().counter(
        MESH_RESTORE_RELAYOUT_COUNTER, "").value

    mesh4 = make_mesh({"data": 4}, devices=jax.devices()[:4])
    r4 = restore_checkpoint(str(tmp_path / "ckpt"), mesh=mesh4)
    assert r4.params["layer0"]["W"].sharding.spec == P(None, "data")
    assert r4.params["layer0"]["W"].sharding.mesh.shape["data"] == 4
    np.testing.assert_allclose(np.asarray(r4.output(ds.features)), ref,
                               rtol=1e-5, atol=1e-6)

    mesh1 = make_mesh({"data": 1}, devices=jax.devices()[:1])
    r1 = restore_checkpoint(str(tmp_path / "ckpt"), mesh=mesh1)
    np.testing.assert_allclose(np.asarray(r1.output(ds.features)), ref,
                               rtol=1e-5, atol=1e-6)

    r8 = restore_checkpoint(str(tmp_path / "ckpt"), mesh=mesh8)
    np.testing.assert_array_equal(np.asarray(r8.output(ds.features)), ref)

    after = get_registry().counter(MESH_RESTORE_RELAYOUT_COUNTER, "").value
    assert after - before == 2  # 8→4 and 8→1 relayouts; 8→8 is not one

    # the restored-on-4 model trains on and its plane is pinned
    assert r4.mesh_plane is not None
    assert r4.mesh_plane.topology()["axes"] == {"data": 4}
    r4.fit(ds)
    assert np.isfinite(float(r4.score()))


def test_checkpoint_zero1_asymmetric_roundtrip(rng, tmp_path):
    """ZeRO-1 (params replicated, updater sharded) round-trips: the
    updater layout is recorded separately and re-lowered; params stay
    replicated on restore."""
    _need8()
    ds = _batch(rng)
    net = _net()
    net.fit(ds)
    mesh8 = make_mesh({"data": 8})
    apply_zero1(net, mesh8)
    # NOTE: saved BEFORE any further step — a fit would let GSPMD's
    # output-sharding propagation move the updated params to a sharded
    # placement (updater is sharded), which the layout would then
    # truthfully record; the asymmetric ZeRO-1 placement under test is
    # the post-apply state
    ref = np.asarray(net.output(ds.features))
    save_checkpoint(net, str(tmp_path / "z1"))
    with open(str(tmp_path / "z1" / "layout.json")) as f:
        layout = json.load(f)
    assert layout["params"] == {}          # replicated params → empty
    assert layout["updater"]["layer0"]["W"] == [None, "data"]

    mesh4 = make_mesh({"data": 4}, devices=jax.devices()[:4])
    r4 = restore_checkpoint(str(tmp_path / "z1"), mesh=mesh4)
    w = r4.params["layer0"]["W"]
    assert w.sharding.is_fully_replicated
    m = jax.tree.leaves(r4.opt_state["updater"]["layer0"]["W"])[0]
    assert not m.sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(r4.output(ds.features)), ref,
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- supervisor on shards

def test_supervisor_rollback_on_sharded_pytree(rng):
    """NaN batch under an FSDP-sharded model: the supervisor rolls back
    to the pre-batch snapshot BITWISE and the restored params keep
    their shardings (per-shard capture, no relayout)."""
    _need8()
    from deeplearning4j_tpu.faultinject import FailingDataSetIterator
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optimize.supervisor import TrainingSupervisor

    ds = _batch(rng, n=64)
    net = _net()
    apply_fsdp(net, make_mesh({"data": 8}))
    net.fit(ds)
    snap_flat = np.asarray(net.params_flat())
    snap_sharding = net.params["layer0"]["W"].sharding

    sup = TrainingSupervisor(net, max_rollbacks=2, enabled=True)
    it = FailingDataSetIterator(ListDataSetIterator(ds, 64), nan_at=(0,))
    it.reset()
    took = sup.step(it.next())
    assert not took and sup.rollbacks == 1
    # bitwise rollback, placement preserved
    np.testing.assert_array_equal(np.asarray(net.params_flat()), snap_flat)
    assert net.params["layer0"]["W"].sharding.spec == snap_sharding.spec
    assert net.params["layer0"]["W"].sharding.mesh.shape == \
        snap_sharding.mesh.shape
    # and the next healthy batch takes
    assert sup.step(ds)


# ---------------------------------------------------- mesh-shrink drill

def _run_shrink_drill(tmp_path, tag, seed=5):
    """One full MeshShrink drill: train FSDP on 8 devices checkpointing
    every step, die mid-epoch, rebuild a plane from the survivors,
    restore the newest unit onto it, return (survivors, restored step,
    post-restore forward bits, resumed forward bits)."""
    from deeplearning4j_tpu.faultinject import ChipFailure, MeshShrink
    from deeplearning4j_tpu.util.sharded_checkpoint import checkpoint_steps

    rng = np.random.default_rng(seed)
    batches = [_batch(rng) for _ in range(6)]
    eval_x = batches[0].features
    ckdir = str(tmp_path / f"drill_{tag}")

    net = _net()
    apply_fsdp(net, make_mesh({"data": 8}))
    ms = MeshShrink(fail_at_step=3, survivors=4, total=8, seed=seed)
    try:
        for i, b in enumerate(batches):
            ms.step()
            net.fit(b)
            save_checkpoint(net, ckdir, keep=3, step=i)
        pytest.fail("drill never fired")
    except ChipFailure as e:
        survivors = [d for d in jax.devices() if d.id in e.survivor_ids]
        small = make_mesh({"data": len(survivors)}, devices=survivors)
        restored = restore_checkpoint(ckdir, mesh=small)
        step = checkpoint_steps(ckdir)[-1]
        fwd = np.asarray(restored.output(eval_x))
        restored.fit(batches[3])  # resume where the dead run stopped
        resumed = np.asarray(restored.output(eval_x))
        return e.survivor_ids, step, fwd, resumed


@pytest.mark.faultinject
def test_mesh_shrink_drill_deterministic(tmp_path):
    """kill → checkpoint fallback → resume on the smaller mesh, twice:
    the survivor set, restored step, restored forward AND the resumed
    forward are bitwise identical across reruns."""
    _need8()
    s1, step1, fwd1, res1 = _run_shrink_drill(tmp_path, "a")
    s2, step2, fwd2, res2 = _run_shrink_drill(tmp_path, "b")
    assert s1 == s2 and len(s1) == 4
    assert step1 == step2 == 2  # failed entering step 3 → newest unit is 2
    np.testing.assert_array_equal(fwd1, fwd2)
    np.testing.assert_array_equal(res1, res2)
    assert np.all(np.isfinite(res1))


# --------------------------------------------------- satellite guards

def test_mesh_api_lint_repo_clean_and_catches_violations(tmp_path):
    lint = _load_script("check_mesh_api")
    root = os.path.dirname(_SCRIPTS)
    assert lint.check_repo(root) == []
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "f = jax.shard_map(lambda x: x, mesh=None, in_specs=None,"
        " out_specs=None)\n"
        "m = Mesh([], ('data',))\n"
        "from jax.experimental.shard_map import shard_map\n")
    problems = lint.check_file(str(bad))
    assert len(problems) == 3
    assert any("shard_map reference outside" in p for p in problems)
    assert any("raw Mesh(...)" in p for p in problems)
    assert any("jax.experimental.shard_map is the deprecated" in p
               for p in problems)
    good = tmp_path / "good.py"
    good.write_text(
        "from deeplearning4j_tpu.parallel.mesh import make_mesh,"
        " device_collective\n"
        "m = make_mesh({'data': 8})\n")
    assert lint.check_file(str(good)) == []


def test_mesh_metrics_pinned_and_exposed():
    _need8()
    from deeplearning4j_tpu.monitor import get_registry

    schema = _load_script("check_telemetry_schema")
    for name in ("dl4j_mesh_devices", "dl4j_mesh_axis_size",
                 "dl4j_mesh_restore_relayouts_total"):
        assert name in schema.KNOWN_DL4J_METRICS
    MeshPlane.build({"data": 4, "tp": 2})
    text = get_registry().prometheus_text()
    assert 'dl4j_mesh_devices 8' in text
    assert 'dl4j_mesh_axis_size{axis="data"} 4' in text
    assert 'dl4j_mesh_axis_size{axis="tp"} 2' in text
    assert schema.validate_prometheus_text(text) == []


def test_healthz_reports_mesh_topology():
    _need8()
    import urllib.request

    from deeplearning4j_tpu.ui import InMemoryStatsStorage, UiServer

    plane = MeshPlane.build({"data": 8})
    assert active_plane() is plane
    srv = UiServer(InMemoryStatsStorage()).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz") as r:
            body = json.loads(r.read())
        assert body["mesh"]["devices"] == 8
        assert body["mesh"]["axes"] == {"data": 8}
    finally:
        srv.stop()
