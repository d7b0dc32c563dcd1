"""What the call ``fit(...)`` costs the host in each of the window's
dispatches: argument handling and the enqueue, the ``launch`` span of
``fit_scan``'s span tree, as the mean of the program's histogram
``dl4j_phase_duration_ms{phase="launch"}`` over the window. Read from the
process-wide registry: the net is gone by the time a reader runs.

The program gives the call that compiles a name of its own
(``compile_launch``), so set-up's warm dispatch is not in this histogram.
Nothing is read unless the histogram holds just the window's dispatches:
a program without the span, or one that launched outside the window, gives
``None``."""

HISTOGRAM = "dl4j_phase_duration_ms"


def read(trace, cell, window, peaks):
    from deeplearning4j_tpu.monitor import get_registry

    hist = get_registry().get(HISTOGRAM, phase="launch")
    n = window["dispatches"]
    if hist is None or not n or hist.count != n:
        return None  # nothing to read, as from a program without the span
    s = hist.summary()
    return {"value": s["total"] / n, "min_ms": s["min"], "max_ms": s["max"],
            "launches": n}
