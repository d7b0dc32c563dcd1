"""Worker script for the multi-host equivalence tests (the cluster
analog of the reference's ``TestCompareParameterAveragingSparkVsSingleMachine``):
run as N processes × M CPU devices, train over the global mesh, have
process 0 dump the final params.

Usage: python multihost_worker.py <pid> <nproc> <port> <out.npz> [mode]
(single-process reference mode: nproc=1, no distributed init)

Modes (VERDICT r4 #6 — the sharded axes must CROSS the process
boundary, not just DP):
  dp    params replicated, batch sharded over data (original test)
  fsdp  ZeRO-3: params+opt state sharded over the data axis, which
        spans both processes — every forward all-gathers shards over
        DCN (gloo here), every backward reduce-scatters across it
  tp    tensor parallelism with the MODEL axis as the OUTER (cross-
        process) mesh axis — per-layer psum/all-gather collectives
        cross the process boundary every step

Env (set by the spawner, BEFORE interpreter start): JAX_PLATFORMS=cpu,
GRAFT_LOCAL_DEVICES=<M> mirrored into
XLA_FLAGS=--xla_force_host_platform_device_count=<M> (the worker
asserts the resulting device count — the count must never silently
degrade to 1 again).
"""

import os
import sys

pid, nproc, port, out = (int(sys.argv[1]), int(sys.argv[2]), sys.argv[3],
                         sys.argv[4])
mode = sys.argv[5] if len(sys.argv) > 5 else "dp"
assert mode in ("dp", "fsdp", "tp"), f"unknown mode {mode!r}"

import jax  # noqa: E402

import numpy as np  # noqa: E402

from deeplearning4j_tpu.parallel import multihost  # noqa: E402

if nproc > 1:
    multihost.initialize(coordinator_address=f"127.0.0.1:{port}",
                         num_processes=nproc, process_id=pid)

# re-assert the device count EXPLICITLY: the spawner sets XLA_FLAGS to
# --xla_force_host_platform_device_count=<GRAFT_LOCAL_DEVICES> before
# interpreter start. A mismatch here means the env plumbing regressed
# and every "multi-host" assertion below would be vacuous.
_want_local = int(os.environ.get("GRAFT_LOCAL_DEVICES", "4"))
assert len(jax.local_devices()) == _want_local, (
    f"worker {pid}: expected {_want_local} local devices from XLA_FLAGS, "
    f"got {len(jax.local_devices())} "
    f"(XLA_FLAGS={os.environ.get('XLA_FLAGS')!r})")
assert len(jax.devices()) == _want_local * nproc, (
    f"worker {pid}: global mesh has {len(jax.devices())} devices, "
    f"expected {_want_local * nproc}")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402

GLOBAL_BATCH = 32
STEPS = 5

conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.1)
        .updater("sgd").activation("tanh")
        .list()
        .layer(DenseLayer(n_in=6, n_out=16))
        .layer(DenseLayer(n_in=16, n_out=16))
        .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                           loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()

rng = np.random.default_rng(0)  # same data in every process
X = rng.standard_normal((GLOBAL_BATCH, 6)).astype(np.float32)
Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, GLOBAL_BATCH)]

if mode == "tp":
    # MODEL axis OUTER = across processes; per-layer collectives ride
    # the process boundary. data axis is the local devices.
    n_dev = len(jax.devices())
    mesh = multihost.make_multihost_mesh(
        dcn_axes={"model": 2}, ici_axes={"data": n_dev // 2})
else:
    mesh = multihost.make_multihost_mesh()  # pure DP over all devices
    assert dict(mesh.shape)["data"] == len(jax.devices()), dict(mesh.shape)

# batch sharded over data. In tp mode the data axis lives inside each
# process (the model axis spans them), so every process contributes the
# FULL batch; in dp/fsdp each contributes its slice.
if mode == "tp":
    x_local, y_local = X, Y
else:
    per = GLOBAL_BATCH // max(nproc, 1)
    lo = pid * per
    x_local, y_local = X[lo:lo + per], Y[lo:lo + per]
xg, yg = multihost.global_batch(mesh, [x_local, y_local])

# broadcast (replicate) params + optimizer state over the global mesh
net.params = multihost.replicate(mesh, jax.device_get(net.params))
net.opt_state = multihost.replicate(mesh, jax.device_get(net.opt_state))
net.states = multihost.replicate(mesh, jax.device_get(net.states))

if mode == "fsdp":
    from deeplearning4j_tpu.parallel.zero import apply_fsdp
    specs = apply_fsdp(net, mesh, axis="data")
    assert specs, "no parameter was FSDP-sharded"
    # placement proof: at least one param's shards live on devices of
    # BOTH processes (the data axis spans them)
    if nproc > 1:
        spanned = False
        for layer, ps in specs.items():
            for pname in ps:
                shards = net.params[layer][pname].sharding \
                    .device_set
                if len({d.process_index for d in shards}) > 1:
                    spanned = True
        assert spanned, "FSDP shards never crossed the process boundary"
elif mode == "tp":
    from deeplearning4j_tpu.parallel.tensor_parallel import (
        apply_shardings, dense_tp_specs)
    specs = dense_tp_specs(["layer0", "layer1", "layer2"])
    apply_shardings(net, mesh, specs)
    if nproc > 1:
        w0 = net.params["layer0"]["W"]
        assert len({d.process_index
                    for d in w0.sharding.device_set}) > 1, \
            "TP model axis did not cross the process boundary"

step = net._get_jit("train", fm=False, lm=False)

zero = jnp.zeros(())
key = jax.random.PRNGKey(1)
for _ in range(STEPS):
    net.params, net.opt_state, net.states, score = step(
        net.params, net.opt_state, net.states, xg, yg, zero, zero, key)

# gather sharded params back to replicated THROUGH the mesh (an
# all-gather program over DCN in fsdp/tp mode — itself part of the
# cross-process proof), then dump on rank 0
gather = jax.jit(lambda t: t, out_shardings=NamedSharding(mesh, P()))
params_full = jax.device_get(gather(net.params))

if pid == 0:
    flat = {}
    for ln, ps in params_full.items():
        for pn, v in ps.items():
            flat[f"{ln}/{pn}"] = np.asarray(v)
    np.savez(out, score=float(score), **flat)
    print("saved", out, "score", float(score), flush=True)
if nproc > 1:
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("done")
