"""Profiler trace -> device busy time, idle share, launches and breakdown.

``reduce_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote under a
directory and reduces it with :func:`reduce_events`, which works on plain
event tuples so that tests can feed it a small synthetic trace.

* The **window** is the span of the benchmark's own host annotations in the
  trace (``TraceAnnotation`` around every submit, enqueue and drain of the
  measured window), on the profiler's clock.
* **Busy** is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), clipped
  to the window and averaged over the devices.
* **Launches** are program executions on a device (events of its
  ``XLA Modules`` line that start inside the window), summed over devices.
* ``device_ops``: the 10 programs that took most device time, by name
  (shapes of one jitted function together: the trace's program id after
  the name is dropped).
* ``idle_gaps``: device idle time, by the innermost benchmark annotation
  open at the time ("outside" where none was; a gap that spans several is
  split at their edges); the 10 largest totals.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)

BENCH_ANNOTATIONS = ("submit ", "enqueue ", "drain")
TOP = 10


@dataclasses.dataclass
class Reduced:
    busy_s: float  # per device, averaged over devices
    window_s: float
    launches: int
    device_ops: List[list]  # [[name, seconds], ...]
    idle_gaps: List[list]  # [[host activity, seconds], ...]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted union of half-open intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) between the disjoint ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def innermost(annotations: Sequence[Tuple[str, int, int]], t: int) -> str:
    """Name of the shortest annotation open at time ``t``."""
    best, width = "outside", None
    for name, s, e in annotations:
        if s <= t < e and (width is None or e - s < width):
            best, width = name, e - s
    return best


class Activity:
    """What the host was doing, by time: the annotations' edges cut time into
    segments, each labelled once with its innermost annotation."""

    def __init__(self, annotations: Sequence[Tuple[str, int, int]]):
        self.edges = sorted({t for _, s, e in annotations for t in (s, e)})
        self.labels = [innermost(annotations, (a + b) // 2)
                       for a, b in zip(self.edges, self.edges[1:])]

    def split(self, s: int, e: int):
        """(label, ns) pieces of the interval [s, e)."""
        edges, i = self.edges, max(bisect.bisect_right(self.edges, s) - 1, 0)
        if not edges or e <= edges[0] or s >= edges[-1]:
            yield "outside", e - s
            return
        if s < edges[0]:
            yield "outside", edges[0] - s
            s = edges[0]
        while i < len(self.labels) and edges[i] < e:
            a, b = max(s, edges[i]), min(e, edges[i + 1])
            if b > a:
                yield self.labels[i], b - a
            i += 1
        if e > edges[-1]:
            yield "outside", e - edges[-1]


def reduce_events(
    ops: Dict[str, List[Interval]],
    modules: Dict[str, List[Tuple[str, int, int]]],
    annotations: List[Tuple[str, int, int]],
) -> Reduced:
    """``ops``: device -> op intervals; ``modules``: device -> (program name,
    start, end) executions; ``annotations``: the benchmark's host spans
    (name, start, end). Times in ns on one clock."""
    if not annotations:
        raise ValueError("trace holds no benchmark annotation: no window")
    lo = min(s for _, s, _ in annotations)
    hi = max(e for _, _, e in annotations)
    devices = sorted(set(ops) | set(modules))
    if not devices:
        raise ValueError("trace holds no device plane")
    busy_ns = 0
    idle: Dict[str, int] = defaultdict(int)
    per_op: Dict[str, int] = defaultdict(int)
    launches = 0
    activity = Activity(annotations)
    for dev in devices:
        intervals = ops.get(dev) or [(s, e) for _, s, e in modules.get(dev, [])]
        busy = union(clip(intervals, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for s, e in gaps(busy, lo, hi):
            for label, ns in activity.split(s, e):
                idle[label] += ns
        for name, s, e in modules.get(dev, []):
            if lo <= s < hi:
                launches += 1
                per_op[name.split("(", 1)[0]] += min(e, hi) - s
    ranked = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]  # noqa: E731
    return Reduced(
        busy_s=busy_ns / len(devices) / 1e9,
        window_s=(hi - lo) / 1e9,
        launches=launches,
        device_ops=ranked(per_op),
        idle_gaps=ranked({k: v / len(devices) for k, v in idle.items()}),
    )


def read_xplane(path: Path):
    """(ops, modules, annotations) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Tuple[str, int, int]]] = defaultdict(list)
    annotations: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] += [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(BENCH_ANNOTATIONS):
                        annotations.append(
                            (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        )
    return ops, modules, annotations


def reduce_dir(directory: Path) -> Reduced:
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_events(*read_xplane(files[-1]))
