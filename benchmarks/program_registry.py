"""What the readers of set-up share: a first-dispatch stage's seconds and a
gauge's value from the program's process-wide registry (the net is gone by
the time a reader runs). A program that has no such span or gauge, as every
program before PR 37, gives ``None``: nothing is read, nothing raises."""

import math

HISTOGRAM = "dl4j_phase_duration_ms"
PROGRAM_BYTES = "dl4j_step_program_bytes"
PROGRAM_FLOPS = "dl4j_step_program_flops"


def stage_seconds(phase):
    """Seconds of the one span named ``phase`` that set-up's first dispatch
    opened. Vouched for as ``dispatch_launch_ms`` vouches: the histogram
    holds exactly one observation (the process made one step program), or
    nothing is read."""
    from deeplearning4j_tpu.monitor import get_registry

    hist = get_registry().get(HISTOGRAM, phase=phase)
    if hist is None or hist.count != 1:
        return None
    return hist.summary()["total"] / 1e3


def gauge(name, **labels):
    """A gauge's value; ``None`` where it does not exist or was never set
    (an unset gauge reads NaN), never 0."""
    from deeplearning4j_tpu.monitor import get_registry

    g = get_registry().get(name, **labels)
    return None if g is None or math.isnan(g.value) else g.value


def program_gauge(name, **labels):
    """A gauge of the step program that set-up's first dispatch made. A gauge
    holds what was written last, so it is vouched for as the stages are: the
    process got one step program's executable (one ``load_step``), or
    nothing is read."""
    if stage_seconds("load_step") is None:
        return None
    return gauge(name, **labels)


def notes(**values):
    """The notes of a line: what was read, without what was not."""
    return {k: v for k, v in values.items() if v is not None}
