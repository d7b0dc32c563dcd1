"""What the cell's compiled step needs of the chip's memory by the compiler's
own count, in GB: arguments + temporaries + outputs less the outputs that
alias donated arguments (``scripts/compile_cell.py``'s ``count_GB``), from the
gauges ``dl4j_step_program_bytes{part=...}`` that the program sets once, when
its first dispatch has the executable (``nn/scan_dispatch.py``). The only
number that sees the step's temporaries: ``memory_peak_bytes`` does not. Room
under the chip's 16 GB is what every keep-set of a recomputed block is bought
with, so a line that shows tokens/s up shows what it cost beside it. Read only
from a process that got one step program's executable (one ``load_step``
span): a gauge holds what was written last.

The notes hold the four parts, XLA's operation count of a step
(``dl4j_step_program_flops``) and the gauges of what the step keeps and
repeats (``dl4j_recomputed_blocks``, ``dl4j_recompute_kept_values``,
``dl4j_span_passes``, ``dl4j_block_applications``,
``dl4j_forwarded_values``)."""

from benchmarks import program_registry

PARTS = ("arguments", "temporaries", "outputs", "aliased")
KEPT = ("dl4j_recomputed_blocks", "dl4j_recompute_kept_values",
        "dl4j_span_passes", "dl4j_block_applications",
        "dl4j_forwarded_values")


def read(trace, cell, window, peaks):
    size = {p: program_registry.program_gauge(program_registry.PROGRAM_BYTES,
                                              part=p) for p in PARTS}
    if None in size.values():
        return None
    flops = program_registry.program_gauge(program_registry.PROGRAM_FLOPS)
    return {"value": (size["arguments"] + size["temporaries"]
                      + size["outputs"] - size["aliased"]) / 1e9,
            **{p + "_gb": v / 1e9 for p, v in size.items()},
            **program_registry.notes(
                xla_tflops_a_step=None if flops is None else flops / 1e12,
                **{name[len("dl4j_"):]: program_registry.gauge(name)
                   for name in KEPT})}
