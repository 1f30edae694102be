"""The readers of the program's engine spans (``dispatch_s``,
``device_wait_s``, ``sort_s``, ``host_syncs``) on synthetic runs, and on the
spans of one traced study round at 64 rows a table, whose names must leave
the benchmark's own annotations to the benchmark."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HOME = Path(__file__).resolve().parents[1]
ROOT = HOME.parent
sys.path.insert(0, str(HOME))

import bench  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from loadgen import Mix  # noqa: E402

from repro.obs.trace import Span  # noqa: E402

READERS = ("dispatch_s.study", "device_wait_s.study", "sort_s.study", "host_syncs.study")
BENCH = bench.Benchmark(ROOT)


def read(metric: str, r) -> float:
    return BENCH.reader(metric).read(r)


def make_run(spans, answered=2) -> run.Run:
    done = [run.Done(request=None, latency_s=1.0, result=object(), unit=0)
            for _ in range(answered)]
    return run.Run(cell=None, setup_s=0.0, window_s=1.0, done=done,
                   compile_s=0.0, compiles=0, spans=spans)


def span(name, span_id, parent_id, seconds, ts=0.0):
    return Span(name=name, span_id=span_id, parent_id=parent_id, ts=ts, seconds=seconds)


# Two queries: a Join node that compiled (a trace phase holding a nested
# one), sorted and waited; a Resize node that waited twice; a reveal's wait
# outside any node.
SYNTHETIC = [
    span("query", 1, None, 10.0),
    span("execute", 2, 1, 9.0),
    span("node[JoinSortMerge]", 3, 2, 6.0),
    span("xla.compile", 4, 3, 1.5),
    span("xla.compile", 5, 4, 0.5),  # nested in 4: not taken off again
    span("sort", 6, 3, 3.0),
    span("xla.compile", 7, 6, 0.25),
    span("device.wait", 8, 3, 0.75),
    span("node[Resize]", 9, 2, 2.0),
    span("device.wait", 10, 9, 0.5),
    span("device.wait", 11, 9, 0.25),
    span("reveal", 12, 1, 0.5),
    span("device.wait", 13, 12, 0.125),
]


@pytest.mark.parametrize("metric,want", [
    # (6 - 1.5 - 0.25 - 0.75) + (2 - 0.5 - 0.25), over 2 queries
    ("dispatch_s.study", (3.5 + 1.25) / 2),
    ("device_wait_s.study", (0.75 + 0.5 + 0.25 + 0.125) / 2),
    ("sort_s.study", 3.0 / 2),
    ("host_syncs.study", 4 / 2),
])
def test_reader_on_a_synthetic_run(metric, want):
    assert read(metric, make_run(SYNTHETIC)) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_where_the_program_has_no_such_span(metric):
    # the program before these spans: node spans alone, no wait or sort
    before = [span("query", 1, None, 2.0), span("execute", 2, 1, 1.0),
              span("node[Join]", 3, 2, 1.0)]
    assert read(metric, make_run(before)) is None
    assert read(metric, make_run([])) is None
    assert read(metric, make_run(SYNTHETIC, answered=0)) is None


def test_every_reader_is_a_per_layer_metric_of_both_study_cells():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    for metric in READERS:
        assert entries[metric]["workloads"] == ["reflex-study", "oblivious-study"]
        assert entries[metric]["moves"] == "query_s"


@pytest.fixture(scope="module")
def traced_round():
    """One study round at 64 rows a table through the harness's own pieces,
    under the program's tracer."""
    import jax

    from repro.obs.trace import Tracer
    from repro.runtime import ReflexClient

    cell = BENCH.cell("reflex-study")
    config = json.loads(json.dumps(cell.config))
    config.update(rows=64, demographics_rows=16)
    config["resizer"]["sensitivity"] = 1.0  # room below N - T at 64 rows
    cell.config = config
    tables, _, catalog = run.make_tables(cell, run.derive_seeds(2**31 + 7))
    client = ReflexClient.in_process(
        tables, catalog=catalog, key=jax.random.PRNGKey(3), **run.service_kwargs(config)
    )
    with Tracer() as tr:
        done = run.run_unit(client, Mix.from_file(cell.traffic_file).unit(0), 0)
    client.close()
    return run.Run(cell=cell, setup_s=0.0, window_s=1.0, done=done,
                   compile_s=0.0, compiles=0, spans=tr.spans)


def test_no_program_span_takes_a_benchmark_annotations_name(traced_round):
    names = {s.name for s in traced_round.spans}
    assert {"query", "execute", "sort", "device.wait", "xla.compile"} <= names
    assert not [n for n in names if n.startswith(trace_reduce.BENCH_ANNOTATIONS)]


def test_readers_read_a_traced_round(traced_round):
    assert len(traced_round.answered) == 2
    values = {m: read(m, traced_round) for m in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
