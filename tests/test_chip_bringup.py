"""Bring-up contracts (ISSUE 21): nothing stands in for the chip, and
what cannot run says so.

``chip_smoke.py``'s CPU rehearsal runs green in-process (the suite must
not fork after JAX) and its default invocation refuses a machine
without a TPU; the compile-cache helper leaves the directory alone when
the environment names one; interpret mode, hardware peaks, device
tracing and the multi-chip dry run raise instead of falling back.
"""

import hashlib
import os

import jax
import pytest

import chip_smoke
from deeplearning4j_tpu.util import compile_cache, device, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ chip_smoke

def test_chip_smoke_default_invocation_needs_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""                     # no result line, nothing run
    assert "needs a TPU" in out.err


def test_chip_smoke_rehearsal_runs_green(capsys):
    """Every phase at tiny widths on the CPU mesh — the four-device
    phases included (conftest forces 8 devices) — labelled on every
    line, and WITHOUT the result line a chip run ends with."""
    assert chip_smoke.main(["--rehearsal"]) == 0
    lines = capsys.readouterr().out.strip().split("\n")
    assert all(l.startswith("rehearsal, not a chip result | ") or
               l.startswith("dryrun_multichip(") for l in lines), lines
    for phase in ("train", "serve", "kernels", "trace", "clock", "four"):
        assert any(f"[{phase}] passed" in l for l in lines), phase
    assert not any(l.startswith("{") for l in lines)


# ---------------------------------------------------------- compile cache

@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cache_helper_leaves_an_environment_directory_alone(
        monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert compile_cache.enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == "/sentinel"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_helper_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


# ------------------------------------------------------ interpret / peaks

@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_interpret_mode_is_decided_by_the_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert device.pallas_interpret() is want


def test_interpret_mode_rejects_an_unknown_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        device.pallas_interpret()


def test_peak_table_rejects_an_unknown_device_kind():
    assert jax.devices()[0].device_kind == "cpu"
    with pytest.raises(KeyError, match="no published peaks"):
        device.device_peaks()
    v5e = device.DEVICE_PEAKS["TPU v5 lite"]
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s, v5e.hbm_bytes) == (
        197e12, 819e9, 16e9)
    assert v5e.source


# ------------------------------------------------------- trace / dry run

def test_profiler_trace_raises_when_it_cannot_trace(tmp_path):
    with profiler.trace(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError):    # one capture at a time
            with profiler.trace(str(tmp_path / "inner")):
                pass
    assert profiler.load_trace(str(tmp_path / "outer")).planes
    with pytest.raises(FileNotFoundError):
        profiler.load_trace(str(tmp_path / "inner"))


def test_dryrun_multichip_raises_when_short_of_devices():
    import __graft_entry__
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"needs {n} devices"):
        __graft_entry__.dryrun_multichip(n)


# ------------------------------------------------------------- native lib

def test_native_library_is_keyed_on_its_source():
    from deeplearning4j_tpu import native
    with open(native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(native._lib_path()) == (
        f"libdl4jtpu_io.{digest}.so")
    assert native.data_plane() in ("native", "numpy")
