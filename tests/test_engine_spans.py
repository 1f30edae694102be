"""The engine's spans on the profiler's clock (DESIGN.md §14.1): node, sort,
device.wait and xla.compile spans of one traced ``dosage_study`` through
``ReflexClient.in_process``, their intervals, their agreement with the
per-node report and JAX's compile events, their host events in a profiler
trace, and that tracing off builds none of them."""
from __future__ import annotations

import collections

import jax
import pytest

from repro.core.noise import TruncatedLaplace
from repro.data import generate_healthlnk
from repro.data.queries import QUERY_SQL
from repro.obs import Tracer
from repro.obs import trace as obs_trace
from repro.runtime import ReflexClient

SQL = QUERY_SQL["dosage_study"]
# waits and compiles are where a node's host time is not dispatch
BLOCKING = ("device.wait", "xla.compile")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One 64-row dosage_study under a Tracer and the JAX profiler, with a
    compile-event listener of the test's own over the same interval."""
    tables, _ = generate_healthlnk(n=64, seed=3)
    client = ReflexClient.in_process(
        tables, key=jax.random.PRNGKey(1),
        noise=TruncatedLaplace(eps=0.5, sensitivity=1.0),
    )
    compile_s = [0.0]

    def listen(event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            compile_s[0] += duration

    trace_dir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(trace_dir))
    try:
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            with Tracer() as tr:
                result = client.submit("alice", SQL)
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
    finally:
        jax.profiler.stop_trace()
    yield {"client": client, "tracer": tr, "result": result,
           "compile_s": compile_s[0], "trace_dir": trace_dir}
    client.close()


def _by_id(tr):
    return {s.span_id: s for s in tr.spans}


def _blocking_under(tr, root):
    """The outermost device.wait and xla.compile spans below ``root``."""
    kids = collections.defaultdict(list)
    for s in tr.spans:
        kids[s.parent_id].append(s)
    out, stack = [], list(kids[root.span_id])
    while stack:
        s = stack.pop()
        if s.name in BLOCKING:
            out.append(s)
        else:
            stack.extend(kids[s.span_id])
    return out


def test_engine_spans_lie_inside_their_parents(traced):
    tr = traced["tracer"]
    by_id = _by_id(tr)
    names = collections.Counter(
        "node" if s.name.startswith("node[") else s.name for s in tr.spans
    )
    for want in ("node", "sort", "device.wait", "xla.compile"):
        assert names[want] > 0, want
    for s in tr.spans:
        if s.parent_id is None:
            continue
        p = by_id[s.parent_id]
        if s.name.startswith("node[") or s.name in ("sort",) + BLOCKING:
            # an xla.compile span's seconds is JAX's own duration, read on
            # time.time(); the tracer's clock is perf_counter: 1 us of room
            assert p.ts <= s.ts, (s.name, p.name)
            assert s.ts + s.seconds <= p.ts + p.seconds + 1e-6, (s.name, p.name)


def test_node_stats_seconds_are_the_node_spans(traced):
    tr, report = traced["tracer"], traced["result"].report
    nodes = [s for s in tr.spans if s.name.startswith("node[")]
    assert [s.attrs["op"] for s in nodes] == [n.node for n in report.nodes]
    assert [s.seconds for s in nodes] == [n.seconds for n in report.nodes]


def test_compile_spans_sum_to_jax_compile_events(traced):
    spans = traced["tracer"].find("xla.compile")
    assert {s.attrs["phase"] for s in spans} == {"trace", "lower", "backend"}
    assert sum(s.seconds for s in spans) == pytest.approx(
        traced["compile_s"], abs=1e-6
    )


def test_a_nodes_waits_and_compiles_do_not_overlap(traced):
    tr = traced["tracer"]
    for node in (s for s in tr.spans if s.name.startswith("node[")):
        under = sorted(_blocking_under(tr, node), key=lambda s: s.ts)
        assert any(s.name == "device.wait" for s in under), node.name
        for a, b in zip(under, under[1:]):
            assert a.ts + a.seconds <= b.ts, (node.name, a.name, b.name)
        assert node.seconds - sum(s.seconds for s in under) >= 0.0


def test_waits_name_their_site(traced):
    whats = collections.Counter(
        s.attrs["what"] for s in traced["tracer"].find("device.wait")
    )
    assert set(whats) == {"node", "resize.count", "resize.open", "reveal"}
    report = traced["result"].report
    assert whats["node"] == len(report.nodes)
    resizes = sum(n.node.startswith("Resize") for n in report.nodes)
    assert whats["resize.count"] == whats["resize.open"] == resizes
    assert whats["reveal"] == 1


def test_spans_are_host_events_of_the_profiler_trace(traced):
    from jax.profiler import ProfileData

    (path,) = traced["trace_dir"].rglob("*.xplane.pb")
    host = collections.Counter()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host.update(e.name for e in line.events)
    mine = collections.Counter(s.name for s in traced["tracer"].spans)
    for name in ("query", "execute", "sort", "device.wait", "xla.compile",
                 "node[Distinct]", "compile", "admit", "reveal"):
        assert host[name] == mine[name] > 0, name


def test_tracing_off_builds_no_span_and_no_annotation(traced, monkeypatch):
    built = collections.Counter()

    class CountedAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            built["annotation"] += 1
            super().__init__(*a, **kw)

    class CountedSpan(obs_trace.Span):
        def __init__(self, *a, **kw):
            built["span"] += 1
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountedAnnotation)
    monkeypatch.setattr(obs_trace, "Span", CountedSpan)
    assert obs_trace.active_tracer() is None
    res = traced["client"].submit("alice", SQL)
    assert res.report.nodes and built == {}
    with Tracer() as tr:
        with obs_trace.span("probe"):
            pass
    assert built == {"annotation": 1, "span": 1} and len(tr.spans) == 1
