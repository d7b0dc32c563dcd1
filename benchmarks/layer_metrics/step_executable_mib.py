"""The size of the cell's compiled step program, in MiB: the executable's
generated code by the compiler's own count, the gauge
``dl4j_step_program_bytes{part="code"}`` that the program sets once, when its
first dispatch has the executable (``nn/scan_dispatch.py``). It is what a warm
start loads and what has to fit the chip machines' 192 MiB compile cache
beside the other trees' programs. Read only from a process that got one step
program's executable (one ``load_step`` span): a gauge holds what was written
last."""

from benchmarks import program_registry


def read(trace, cell, window, peaks):
    code = program_registry.program_gauge(program_registry.PROGRAM_BYTES,
                                          part="code")
    return None if code is None else {"value": code / 2 ** 20}
