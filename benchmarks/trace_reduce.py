"""From a device trace to numbers: busy time (the union of the intervals in
which an operation ran), self time by op group, kernel time by name, and the
longest idle gaps. Works on plain ``(name, start_ns, duration_ns)`` tuples so
that a test can hand it a small written-out trace; ``read_xplane`` turns the
profiler's ``.xplane.pb`` into those.

The self-time walk is copied from ``scripts/profile_gpt.py::aggregate_trace``
(sound on the chip since PR 21); the busy union and the gaps are added here.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns


def read_xplane(log_dir: str, line_name: str = "XLA Ops") -> List[List[Event]]:
    """One list of events for each device plane of the newest capture under
    ``log_dir``. A capture with no device plane gives ``[]``."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    profile = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in profile.planes:
        # the runtime's own /device:CUSTOM:... planes are not devices
        if not plane.name.startswith("/device:") \
                or plane.name.startswith("/device:CUSTOM:"):
            continue
        events = [(e.name, int(e.start_ns), int(e.duration_ns))
                  for line in plane.lines if line.name == line_name
                  for e in line.events]
        planes.append(events)
    return planes


def group_of(name: str) -> str:
    """An op group from an event name: the HLO instruction's name with its
    ``.N`` instance suffixes dropped (``%fusion.12 = bf16[...]`` gives
    ``fusion``)."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ")[0].lstrip("%"))


def busy_intervals(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """The union of the events' intervals, as sorted disjoint (start, end)."""
    out: List[List[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def self_times(events: Sequence[Event]):
    """Self time of each event: its duration less what its children cover
    (events nest: while > fusion). Returns ``[(name, self_ns)]``."""
    out = []
    stack: List[list] = []  # [end_ns, name, dur_ns, child_ns]

    def pop_one():
        _, name, dur, child = stack.pop()
        out.append((name, max(dur - child, 0)))
        if stack:
            stack[-1][3] += dur

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            pop_one()
        stack.append([start + dur, name, dur, 0])
    while stack:
        pop_one()
    return out


class TraceReduction:
    """What the per-layer readers read. Times are in seconds, and those of
    several device planes are averaged over the planes."""

    def __init__(self, planes: Sequence[Sequence[Event]]):
        n = max(len(planes), 1)
        self.busy_s = sum(b - a for p in planes
                          for a, b in busy_intervals(p)) / n / 1e9
        by_group: Dict[str, float] = collections.Counter()
        count: Dict[str, int] = collections.Counter()
        for p in planes:
            for name, ns in self_times(p):
                g = group_of(name)
                by_group[g] += ns / n / 1e9
                count[g] += 1
        self.self_s_by_group = dict(by_group)
        self.count_by_group = dict(count)
        self._planes = planes

    def kernel_seconds(self, *substrings: str) -> float:
        """Summed self time of the groups whose name holds one of
        ``substrings``; 0.0 where none ran."""
        return sum(s for g, s in self.self_s_by_group.items()
                   if any(sub in g for sub in substrings))

    def kernel_count(self, *substrings: str) -> int:
        return sum(c for g, c in self.count_by_group.items()
                   if any(sub in g for sub in substrings))

    def top_groups(self, n: int = 10):
        rows = sorted(self.self_s_by_group.items(), key=lambda kv: -kv[1])
        return [[g, s] for g, s in rows[:n]]

    def idle_gaps(self, n: int = 10):
        """The longest gaps between busy intervals on the first plane, named
        by the top-level ops on either side."""
        if not self._planes:
            return []
        events = sorted(self._planes[0], key=lambda e: (e[1], -e[2]))
        busy = busy_intervals(events)
        # the top-level event that opens / closes each busy interval
        opens = {}
        closes = {}
        for name, start, dur in events:
            opens.setdefault(start, group_of(name))
            end = start + dur
            closes[end] = group_of(name)
        merged: Dict[str, float] = collections.Counter()
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            label = f"{closes.get(e0, '?')}>{opens.get(s1, '?')}"
            merged[label[:64]] += (s1 - e0) / 1e9
        return [[k, v] for k, v in
                sorted(merged.items(), key=lambda kv: -kv[1])[:n]]
