"""XLA/TPU profiler hooks.

Parity: SURVEY §5 tracing — the reference has PerformanceListener +
Spark phase timers + StatsListener telemetry (all rebuilt:
``optimize/listeners.py``, ``optimize/training_stats.py``,
``ui/stats.py``); the named TPU equivalent "XLA/TPU profiler traces"
is this module: thin wrappers over ``jax.profiler`` producing traces of
the real device timeline (compilation, fusion, HBM traffic — the layers
Python timers can't see), and the reader that finds them again.

A trace that was asked for and cannot be taken is an error: a silent
no-op here once hid that device tracing had been off for a whole round.
"""

from __future__ import annotations

import contextlib
import glob
import os
from typing import List


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace for the enclosed block::

        with profiler.trace("/tmp/jax-trace"):
            net.fit_scan(ds, 512, epochs=1)
        profile = profiler.load_trace("/tmp/jax-trace")

    Writes ``<log_dir>/plugins/profile/<time>/<host>.xplane.pb``
    (TensorBoard's profile plugin loads the directory). The Python
    tracer is off: the host-side story lives in ``monitor/`` spans and
    :func:`annotate` regions, which the trace still records. Raises if
    the profiler cannot start or cannot write."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.raise_error_on_start_failure = True
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_trace(log_dir: str):
    """The newest capture under ``log_dir`` as a
    ``jax.profiler.ProfileData`` (planes → lines → events with
    ``start_ns``/``duration_ns``); raises when there is none."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return jax.profiler.ProfileData.from_file(paths[-1])


def device_planes(profile) -> List:
    """The accelerator planes of a capture (``/device:TPU:0`` …) — a
    CPU-only capture has none, only ``/host:CPU``. The runtime's own
    ``/device:CUSTOM:…`` planes (e.g. the Megascale trace, empty on one
    host) are not devices."""
    return [p for p in profile.planes
            if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM:")]


def start_server(port: int = 9999):
    """Start the on-demand profiling server (connect with TensorBoard's
    capture-profile button)."""
    import jax

    return jax.profiler.start_server(port)


def annotate(name: str):
    """TraceAnnotation context manager: names a host-side region in the
    captured timeline (StepTraceAnnotation role for custom phases)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
