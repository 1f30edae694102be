"""Reductions that several metric readers share. Each takes a run.Run."""
from __future__ import annotations

from typing import Optional


def per_query_node_seconds(run, prefix: str) -> Optional[float]:
    """Mean seconds per answered query spent in plan nodes whose name
    starts with ``prefix`` (the engine's blocked per-node timer), or None
    where no query has such a node."""
    answered = run.answered
    total, seen = 0.0, False
    for d in answered:
        for s in d.result.report.nodes:
            if s.node.startswith(prefix):
                total += s.seconds
                seen = True
    return total / len(answered) if seen else None


def rounds_per_query(run) -> Optional[float]:
    answered = run.answered
    if not answered:
        return None
    return sum(d.result.report.total_rounds for d in answered) / len(answered)


def service_ms(run) -> Optional[float]:
    """Milliseconds per answered query that the service spent outside the
    engine: root spans of the program's tracer (``query``, ``batch.flush``)
    less their ``execute`` spans."""
    if not run.spans or not run.answered:
        return None
    roots = sum(s.seconds for s in run.spans if s.parent_id is None)
    execute = sum(s.seconds for s in run.spans if s.name == "execute")
    return 1000.0 * (roots - execute) / len(run.answered)


def idle_pct(run) -> Optional[float]:
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def launches_per_query(run) -> Optional[float]:
    t = run.trace
    if t is None or not run.answered:
        return None
    return t.launches / len(run.answered)
