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

import collections
import contextlib
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.monitor.tracing import TRACE_PREFIX as SPAN_PREFIX


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


def newest_xplane(log_dir: str) -> str:
    """Path of the newest ``.xplane.pb`` under ``log_dir``; raises when
    there is none."""
    paths = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_trace(log_dir: str):
    """The newest capture under ``log_dir`` as a
    ``jax.profiler.ProfileData`` (planes → lines → events with
    ``start_ns``/``duration_ns``); raises when there is none."""
    import jax

    return jax.profiler.ProfileData.from_file(newest_xplane(log_dir))


def device_planes(profile) -> List:
    """The accelerator planes of a capture (``/device:TPU:0`` …) — a
    CPU-only capture has none, only ``/host:CPU``. The runtime's own
    ``/device:CUSTOM:…`` planes (e.g. the Megascale trace, empty on one
    host) are not devices."""
    return [p for p in profile.planes if _is_device(p.name)]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") \
        and not plane_name.startswith("/device:CUSTOM:")


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


# ------------------------------------------------- reading a capture
#
# What a v5e capture holds (PERF.md section 5, "step 0"): the device
# plane's ``XLA Ops`` line has one event per executed HLO instruction,
# named by the instruction's HLO text (``%fusion.12 = bf16[...] fusion(
# ...), kind=kOutput, calls=...``) with three timing stats and nothing
# else. The HLO ``op_name`` — ``jit(run)/while/body/.../jvp(mlp_fc)/
# dot_general``, where the program's ``jax.named_scope`` names live — is
# the ``tf_op`` stat of the event's *metadata*, which
# ``jax.profiler.ProfileData`` does not hand out; ``op_names`` reads it
# from the file. The program's host spans (``monitor.span``) lie on the
# ``/host:CPU`` plane as ``dl4j/<name>`` events on the same clock.

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SCOPE_STAT = "tf_op"
#: the spans that ``fit_scan`` opens around one dispatch, and their
#: children by the row of ``gaps_by_host_span`` that each is put down to
DISPATCH_SPANS = ("compile", "device_step")
_CHILD_KEYS = {"launch": "launch", "compile_launch": "launch",
               "trace_step": "launch", "lower_step": "launch",
               "load_step": "launch", "first_launch": "launch",
               "fetch": "fetch"}

_WRAPPED = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


def op_group(name: str) -> str:
    """An op group from an event name: the HLO instruction's name with
    its ``.N`` instance suffixes dropped (``%fusion.12 = bf16[...]``
    gives ``fusion``)."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ")[0].lstrip("%"))


def self_times(events: Iterable) -> List[Tuple[object, int]]:
    """Self time of each event of one line: its duration less what its
    children cover (events nest: while > fusion). Events are objects with
    ``start_ns`` and ``duration_ns``; returns ``[(event, self_ns)]``. The
    one walk ``scripts/profile_gpt.py`` and the readers below share."""
    out = []
    stack: List[list] = []  # [end_ns, event, child_ns]

    def pop_one():
        _, e, child = stack.pop()
        out.append((e, max(e.duration_ns - child, 0)))
        if stack:
            stack[-1][2] += e.duration_ns

    for e in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        while stack and e.start_ns >= stack[-1][0]:
            pop_one()
        stack.append([e.start_ns + e.duration_ns, e, 0])
    while stack:
        pop_one()
    return out


def _fields(buf: bytes, start: int, end: int):
    """``(field number, value)`` of the protobuf message ``buf[start:end]``:
    a varint as an int, a length-delimited field as its ``(start, end)``,
    a fixed-width field as ``None``."""
    def varint(i):
        shift = out = 0
        while True:
            out |= (buf[i] & 0x7F) << shift
            i += 1
            if buf[i - 1] < 0x80:
                return out, i
            shift += 7

    i = start
    while i < end:
        key, i = varint(i)
        wire, v = key & 7, None
        if wire == 0:
            v, i = varint(i)
        elif wire == 2:
            n, i = varint(i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def op_names(path: str) -> Dict[str, str]:
    """``{event name: HLO op_name}`` for the device planes of the
    ``.xplane.pb`` at ``path`` (or the newest one under a log
    directory). The op_name is the ``tf_op`` stat of the event's metadata,
    which ``ProfileData`` does not expose, so this reads the file's
    ``XPlane.event_metadata`` tables itself (field numbers of tsl's
    ``xplane.proto``) and skips the lines. A capture whose metadata
    carries no such stat gives ``{}``."""
    if os.path.isdir(path):
        path = newest_xplane(path)
    with open(path, "rb") as f:
        buf = f.read()
    sub = lambda span, num: [v for f, v in _fields(buf, *span) if f == num]
    text = lambda spans: "".join(
        buf[a:b].decode("utf-8", "replace") for a, b in spans)
    out: Dict[str, str] = {}
    for plane in sub((0, len(buf)), 1):  # XSpace.planes
        if not _is_device(text(sub(plane, 2))):  # XPlane.name
            continue
        # XPlane.stat_metadata: map entries of XStatMetadata{id=1, name=2}
        stat_names = {}
        for entry in sub(plane, 5):
            for meta in sub(entry, 2):
                stat_names[(sub(meta, 1) or [0])[0]] = text(sub(meta, 2))
        # XPlane.event_metadata: entries of XEventMetadata{name=2, stats=5}
        for entry in sub(plane, 4):
            for meta in sub(entry, 2):
                for stat in sub(meta, 5):  # XStat{metadata_id=1, str=5, ref=7}
                    got = dict(_fields(buf, *stat))
                    if stat_names.get(got.get(1)) != SCOPE_STAT:
                        continue
                    op = text([got[5]]) if 5 in got \
                        else stat_names.get(got.get(7), "")
                    if op:
                        out[text(sub(meta, 2))] = op.rstrip(":")
    return out


def scope_of(op_name: str, names: Sequence[str]) -> Tuple[str, str]:
    """``(scope, pass)`` of an HLO op_name: the innermost path component
    that is one of ``names`` (bare, or wrapped as JAX wraps it:
    ``jvp(name)``, ``transpose(jvp(name))``), and ``"bwd"`` where a
    ``transpose(`` wraps the path, else ``"fwd"``. ``("", pass)`` where
    no component matches."""
    found = ""
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in names:
            found = m.group(1)
    return found, "bwd" if "transpose(" in op_name else "fwd"


def scoped_self_times(profile, names: Sequence[str],
                      ops: Optional[Dict[str, str]] = None):
    """``[(op group, scope, pass, self_ns)]`` for every ``XLA Ops`` event
    of the capture's device planes. An event's op_name is its ``tf_op``
    stat where the event carries one, else ``ops[event.name]`` (from
    :func:`op_names`), else unknown: scope ``""``. XLA gives a fusion the
    op_name of ONE of its instructions, so attribution is by fusion."""
    ops = ops or {}
    rows = []
    for plane in device_planes(profile):
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e, ns in self_times(line.events):
                op = dict(e.stats).get(SCOPE_STAT) or ops.get(e.name, "")
                rows.append((op_group(e.name), *scope_of(op, names), ns))
    return rows


def scope_seconds(profile, names: Sequence[str],
                  ops: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """Self seconds of the device's ops by named scope:
    ``{"mlp_fc/fwd": s, "mlp_fc/bwd": s, ..., "": unclaimed}`` summed
    over the device planes. ``""`` holds what no scope of ``names``
    claimed (the ``while`` shells, and every op when the executable was
    compiled without the scopes)."""
    out: Dict[str, float] = collections.Counter()
    for _, scope, pass_, ns in scoped_self_times(profile, names, ops):
        out[f"{scope}/{pass_}" if scope else ""] += ns / 1e9
    return dict(out)


def host_spans(profile) -> List[Dict[str, object]]:
    """The program's spans on the host plane, on the profiler's clock —
    the same clock as the device's ops: ``[{name, start_ns, duration_ns,
    line, stats}]`` for every ``dl4j/`` event, sorted by start, the
    prefix dropped from ``name``."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append({"name": e.name[len(SPAN_PREFIX):],
                                "start_ns": int(e.start_ns),
                                "duration_ns": int(e.duration_ns),
                                "line": line.name, "stats": dict(e.stats)})
    return sorted(out, key=lambda s: (s["start_ns"], -s["duration_ns"]))


def _busy(events) -> List[Tuple[int, int]]:
    """The union of the events' intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def gaps_by_host_span(profile) -> Dict[str, object]:
    """Where the device's idle time went, by what the host was doing.

    The window runs from the start of the first dispatch span (a
    ``dl4j/`` span named in ``DISPATCH_SPANS``) to the end of the last; its
    idle time is the window less the union of the first device plane's
    ``XLA Ops`` intervals. Each idle nanosecond is put down to the
    innermost ``dl4j/`` span covering it:

    - ``fetch``: inside a ``fetch`` span that was open before the gap
      began — the device is done, the host still waits and copies;
    - ``python``: inside no child span — the caller's loop and the
      span's own bookkeeping between ``fetch`` and ``launch``;
    - ``launch``: inside a ``launch`` span (or ``compile_launch`` and its
      four stages) — argument handling, enqueue;
    - ``unattributed``: after ``launch`` returned and before the first
      op ran — the device's own start, which no host span explains.

    Returns seconds for each, ``idle_s`` (their sum), ``window_s`` and
    ``dispatches``; ``{}`` when the capture has no dispatch span or no
    device plane."""
    spans = host_spans(profile)
    roots = [s for s in spans if s["name"] in DISPATCH_SPANS]
    planes = device_planes(profile)
    if not roots or not planes:
        return {}
    w0 = roots[0]["start_ns"]
    w1 = max(s["start_ns"] + s["duration_ns"] for s in roots)
    busy = _busy(e for line in planes[0].lines if line.name == OPS_LINE
                 for e in line.events)
    gaps, at = [], w0
    for a, b in busy:
        if a > at:
            gaps.append((at, min(a, w1)))
        at = max(at, b)
        if at >= w1:
            break
    if at < w1:
        gaps.append((at, w1))
    children = [(s["start_ns"], s["start_ns"] + s["duration_ns"],
                 _CHILD_KEYS[s["name"]])
                for s in spans if s["name"] in _CHILD_KEYS]
    out = {"fetch": 0, "python": 0, "launch": 0, "unattributed": 0}
    for g0, g1 in gaps:
        at = g0
        for c0, c1, name in children:
            if c1 <= at or c0 >= g1:
                continue
            out["python"] += max(c0 - at, 0)
            lo, hi = max(c0, at), min(c1, g1)
            # a fetch that opened inside the gap waits for a device that
            # has not started yet
            key = "unattributed" if name == "fetch" and c0 > g0 else name
            out[key] += hi - lo
            at = hi
        out["python"] += max(g1 - at, 0)
    res: Dict[str, object] = {k + "_s": v / 1e9 for k, v in out.items()}
    res["idle_s"] = sum(out.values()) / 1e9
    res["window_s"] = (w1 - w0) / 1e9
    res["dispatches"] = len(roots)
    return res
