"""Seconds the first dispatch of the cell's step program spent getting its
executable: the ``load_step`` span under ``compile`` > ``compile_launch``
(``nn/scan_dispatch.py``), which holds the persistent cache's retrieval and
load in a warm run and the backend's compile in a cold one, from the
program's histogram ``dl4j_phase_duration_ms{phase="load_step"}``.

The notes hold the rest of the first dispatch, so that a line shows all of
it: ``first_launch_s`` (the call of what was made), ``compile_launch_s`` (the
span the four stages tile), ``compile_s`` (the whole dispatch) and
``first_fetch_s`` (the wait for the device and the copy: what ``compile``
holds beside ``compile_launch``)."""

from benchmarks import program_registry


def read(trace, cell, window, peaks):
    value = program_registry.stage_seconds("load_step")
    if value is None:
        return None
    made = program_registry.stage_seconds("compile_launch")
    whole = program_registry.stage_seconds("compile")
    return {"value": value, **program_registry.notes(
        first_launch_s=program_registry.stage_seconds("first_launch"),
        compile_launch_s=made, compile_s=whole,
        first_fetch_s=None if made is None or whole is None else whole - made)}
