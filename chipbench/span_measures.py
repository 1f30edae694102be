"""Reductions over the program's own spans (``run.spans``, traced runs) that
several metric readers share. Each takes a run.Run and returns None where
the run holds nothing to read: no answered query, or a program whose engine
opens no ``device.wait`` (or ``sort``) span."""
from __future__ import annotations

from collections import defaultdict
from typing import Optional

WAIT = "device.wait"
COMPILE = "xla.compile"
SORT = "sort"


def _named(run, name: str) -> list:
    return [s for s in run.spans if s.name == name]


def _per_query(run, spans: list, value) -> Optional[float]:
    if not spans or not run.answered:
        return None
    return sum(value(s) for s in spans) / len(run.answered)


def device_wait_s(run) -> Optional[float]:
    """Seconds per answered query in which the host waited on the device."""
    return _per_query(run, _named(run, WAIT), lambda s: s.seconds)


def host_syncs(run) -> Optional[float]:
    """Host waits on the device per answered query."""
    return _per_query(run, _named(run, WAIT), lambda s: 1)


def sort_s(run) -> Optional[float]:
    """Seconds per answered query inside bitonic sorts, all they hold
    included."""
    return _per_query(run, _named(run, SORT), lambda s: s.seconds)


def dispatch_s(run) -> Optional[float]:
    """Seconds per answered query that plan nodes spent on the host issuing
    work: each ``node[...]`` span less the outermost ``xla.compile`` and
    ``device.wait`` spans below it (a compile phase that JAX runs inside
    another is its child, and is not taken off twice)."""
    if not _named(run, WAIT) or not run.answered:
        return None
    kids = defaultdict(list)
    for s in run.spans:
        kids[s.parent_id].append(s)
    total = 0.0
    for node in run.spans:
        if not node.name.startswith("node["):
            continue
        blocked, stack = 0.0, list(kids[node.span_id])
        while stack:
            s = stack.pop()
            if s.name in (WAIT, COMPILE):
                blocked += s.seconds
            else:
                stack.extend(kids[s.span_id])
        total += node.seconds - blocked
    return total / len(run.answered)
