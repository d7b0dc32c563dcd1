"""Seconds the first dispatch of the cell's step program spent lowering the
traced step to StableHLO: the ``lower_step`` span under ``compile`` >
``compile_launch`` (``nn/scan_dispatch.py``), from the program's histogram
``dl4j_phase_duration_ms{phase="lower_step"}``. Part of ``setup_s``, warm or
cold."""

from benchmarks import program_registry


def read(trace, cell, window, peaks):
    value = program_registry.stage_seconds("lower_step")
    return None if value is None else {"value": value}
