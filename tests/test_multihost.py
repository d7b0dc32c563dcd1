"""Multi-host distributed equivalence test.

The cluster analog of round-1's single-process mesh equivalence tests
and the reference's Spark-vs-local doctrine
(``TestCompareParameterAveragingSparkVsSingleMachine.java:41``,
``BaseSparkTest.java:90`` local[N]): 2 REAL processes × 2 CPU devices
each, connected by ``jax.distributed`` + gloo, train data-parallel over
the 4-device global mesh; final params must match a single-process run
on the same global batch.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(pid, nproc, port, out, local_devices=4, mode="dp"):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the worker needs ITS OWN device count, not whatever the parent's
    # XLA_FLAGS carries (conftest forces 8); set the flag explicitly and
    # the worker re-asserts the resulting count after backend init
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}")
    env["GRAFT_LOCAL_DEVICES"] = str(local_devices)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, WORKER, str(pid), str(nproc), str(port), out, mode],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def _run_equivalence(tmp_path, mode):
    """2 processes × 4 devices vs 1 process × 8 devices — a REAL
    8-device global mesh (the same width conftest forces in-process),
    same global mesh semantics; final params must match."""
    port = _free_port()
    out_multi = str(tmp_path / f"multi_{mode}.npz")
    out_single = str(tmp_path / f"single_{mode}.npz")

    procs = [_spawn(i, 2, port, out_multi, mode=mode) for i in range(2)]
    for p in procs:
        stdout, stderr = p.communicate(timeout=540)
        assert p.returncode == 0, f"worker failed:\n{stdout}\n{stderr[-3000:]}"

    single = _spawn(0, 1, port, out_single, local_devices=8, mode=mode)
    stdout, stderr = single.communicate(timeout=540)
    assert single.returncode == 0, f"single failed:\n{stdout}\n{stderr[-3000:]}"

    a = np.load(out_multi)
    b = np.load(out_single)
    assert set(a.files) == set(b.files)
    for k in a.files:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6,
                                   err_msg=f"{mode}:{k}")


def test_two_process_dp_matches_single_process(tmp_path):
    _run_equivalence(tmp_path, "dp")


def test_two_process_fsdp_matches_single_process(tmp_path):
    """VERDICT r4 #6: ZeRO-3 param/opt shards span the process boundary
    (asserted inside the worker) and the trajectory matches the
    single-process run."""
    _run_equivalence(tmp_path, "fsdp")


def test_two_process_tp_matches_single_process(tmp_path):
    """VERDICT r4 #6: tensor-parallel with the model axis ACROSS
    processes — per-layer collectives ride the process boundary."""
    _run_equivalence(tmp_path, "tp")


def test_make_multihost_mesh_single_process_shapes():
    """In-process sanity: data absorbs free devices; explicit ICI axes
    stay inner (rightmost = fastest-varying = on-host)."""
    import jax
    from deeplearning4j_tpu.parallel.multihost import make_multihost_mesh
    n = len(jax.devices())
    m = make_multihost_mesh()
    assert dict(m.shape) == {"data": n}
    if n % 2 == 0:
        m2 = make_multihost_mesh(ici_axes={"model": 2})
        assert dict(m2.shape) == {"data": n // 2, "model": 2}
        assert tuple(m2.axis_names) == ("data", "model")
