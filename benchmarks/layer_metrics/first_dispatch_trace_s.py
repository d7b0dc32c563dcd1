"""Seconds the first dispatch of the cell's step program spent tracing it to a
jaxpr: the ``trace_step`` span under ``compile`` > ``compile_launch``
(``nn/scan_dispatch.py``), from the program's histogram
``dl4j_phase_duration_ms{phase="trace_step"}``. Part of ``setup_s``, warm or
cold: Python's work, which no cache shortens."""

from benchmarks import program_registry


def read(trace, cell, window, peaks):
    value = program_registry.stage_seconds("trace_step")
    return None if value is None else {"value": value}
