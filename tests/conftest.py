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


@pytest.fixture
def gathers_under():
    """``find(text, scope)``: the names of the gathers that stand under the
    named scope ``scope`` in a lowered text (``as_text(debug_info=True)``).
    A gather's name is its own location's, or, where it sits in a private
    function (``jit(take_along_axis)``, ``jit(_take)``), the name of each call
    that reaches that function: the scope is on the call."""
    import re

    def find(text, scope):
        named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
        sites, holders, inside = [], set(), None  # (function, callee, name)
        for line in text.splitlines():
            func = re.match(r"\s*func\.func \w+ @(\w+)", line)
            if func:
                inside = func.group(1)
            at = re.search(r"loc\((#loc\d+)\)\s*$", line)
            name = named.get(at.group(1), "") if at else ""
            call = re.search(r"\bcall @(\w+)\(", line)
            if "stablehlo.gather" in line:
                holders.add(inside)
                sites.append((inside, None, name))
            elif call:
                sites.append((inside, call.group(1), name))
        grew = True
        while grew:  # a function that calls a holder holds a gather too
            callers = {f for f, callee, _ in sites if callee in holders}
            grew = not callers <= holders
            holders |= callers
        part = re.compile(rf"(^|[/(]){re.escape(scope)}[)/]")
        return sorted({name for _, callee, name in sites
                       if (callee is None or callee in holders)
                       and part.search(name + "/")})

    return find
