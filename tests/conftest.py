"""Test harness: force an 8-device CPU-backed virtual mesh.

This is the TPU analog of the reference's ``local[N]`` fake Spark cluster
(``BaseSparkTest.java:90``, SURVEY.md §4): multi-device semantics are
exercised without real chips by splitting the host CPU into 8 XLA
devices. Must run before the first ``import jax``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from deeplearning4j_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

# XLA compile time dominates the suite; cache executables across runs
# (JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache).
enable_compile_cache()

# Gradient checks are finite-difference vs analytic (the reference runs
# them in double precision, GradientCheckUtil.java); enable x64 so the
# same tolerances hold.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
# numpy.testing's import-time SVE probe spawns a subprocess; forking from
# this process becomes unreliable (C-level segfault in the parent) once
# enough XLA state has accumulated, so force the probe NOW while fork is
# still safe — later lazy `np.testing` imports then hit the module cache.
import numpy.testing  # noqa: E402,F401
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture
def assert_bucket_exact():
    """``check(got, net, x, buckets)``: ``got`` equals, bit for bit, the
    rows ``net.output`` yields for ``x`` through ONE of the bucket
    programs that can carry it (``x`` zero-padded to that bucket size).

    This is the like-with-like form of "routing and padding change
    nothing": XLA:CPU gives a row the same bits at a given batch size
    wherever it sits and whoever shares the batch, but not the same
    last bit at two batch sizes — and which bucket a request rides is
    the coalescer's timing-dependent choice."""
    from deeplearning4j_tpu.datasets.iterators import pad_rows

    def check(got, net, x, buckets):
        x = np.asarray(x)
        n = len(x)
        refs = [np.asarray(net.output(pad_rows(x, b - n)))[:n]
                for b in buckets if b >= n]
        assert any(np.array_equal(got, r) for r in refs), (
            f"rows match no bucket program's output: max abs diff "
            f"{min(float(np.abs(got - r).max()) for r in refs):.3g}")

    return check
