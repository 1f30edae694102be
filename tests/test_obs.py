"""Observability primitives: redaction boundary, tracer, metrics registry —
plus the ledger's coalesced ``count`` semantics and the report round-trip
satellites (ISSUE 7)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ledger import CommLedger, batched_tally, log_comm
from repro.engine.executor import ExecutionReport, NodeStats
from repro.obs import (
    Tracer,
    MetricsRegistry,
    active_tracer,
    redact,
    record,
    span,
)


# -----------------------------------------------------------------------------
# redact: the disclosure audit boundary
# -----------------------------------------------------------------------------

RESIZER_INFO = {"n": 144, "t": 9, "s": 23, "s_padded": 32, "eta": 14}


def test_public_view_drops_secret_keys():
    pub = redact.public_view(RESIZER_INFO)
    assert pub == {"n": 144, "s": 23, "s_padded": 32}
    assert "t" not in pub and "eta" not in pub


def test_public_view_default_denies_unknown_keys():
    dropped = []
    pub = redact.public_view({"n": 4, "mystery_field": 7}, dropped)
    assert pub == {"n": 4}
    assert "mystery_field" in dropped


def test_public_view_recurses_into_nested_dicts():
    pub = redact.public_view({"node": "Resize", "count": {"t": 3, "s": 5}})
    assert pub == {"node": "Resize", "count": {"s": 5}}


def test_assert_emittable_raises_on_secret():
    with pytest.raises(redact.RedactionError):
        redact.assert_emittable(RESIZER_INFO)
    redact.assert_emittable({"n": 144, "s": 23})  # public-only: fine


def test_audit_labels_rejects_secret_dimension():
    with pytest.raises(redact.RedactionError):
        redact.audit_labels("m", ("tenant", "t"))
    redact.audit_labels("m", ("tenant", "sig"))


def test_metric_with_secret_labelname_cannot_be_declared():
    m = MetricsRegistry()
    with pytest.raises(redact.RedactionError):
        m.counter("bad_total", "", ("eta",))


def test_fingerprint_hash_is_stable_and_short():
    fp = "Join(pid==pid)\n  Scan(a)\n  Scan(b)"
    h = redact.fingerprint_hash(fp)
    assert h == redact.fingerprint_hash(fp) and len(h) == 12
    assert "\n" not in h


# -----------------------------------------------------------------------------
# Tracer
# -----------------------------------------------------------------------------

def test_tracer_nests_spans_and_redacts_attrs():
    with Tracer() as tr:
        with span("query", tenant="alice"):
            with span("execute"):
                record("node[Resize]", seconds=0.5, **RESIZER_INFO)
    q, ex, nd = tr.spans
    assert q.parent_id is None
    assert ex.parent_id == q.span_id
    assert nd.parent_id == ex.span_id
    assert nd.seconds == 0.5
    assert nd.attrs == {"n": 144, "s": 23, "s_padded": 32}
    assert sorted(set(tr.redactions)) == ["eta", "t"]


def test_module_helpers_are_noops_without_tracer():
    assert active_tracer() is None
    with span("query"):  # nullcontext
        record("node[x]", n_out=1)
    annotated = Tracer()
    assert annotated.spans == []


def test_tracer_jsonl_round_trip(tmp_path):
    with Tracer() as tr:
        with span("query", tenant="a", sql="SELECT 1"):
            record("compile", seconds=0.1, cache_hit=True)
    path = tmp_path / "trace.jsonl"
    tr.write(str(path))
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2
    objs = [json.loads(ln) for ln in lines]
    assert {o["name"] for o in objs} == {"query", "compile"}
    by_name = {o["name"]: o for o in objs}
    assert by_name["compile"]["parent_id"] == by_name["query"]["span_id"]
    assert by_name["compile"]["attrs"]["cache_hit"] is True


def test_record_starts_its_span_seconds_before_now():
    with Tracer() as tr:
        with span("before"):
            pass
        record("wait", seconds=0.25)
        with span("after"):
            pass
    before, wait, after = tr.spans
    end = wait.ts + wait.seconds
    assert before.ts + before.seconds <= end <= after.ts
    assert wait.ts == pytest.approx(end - 0.25)


def test_set_attrs_merges_redacted_attrs_into_a_closed_span():
    with Tracer() as tr:
        with span("node[Resize]", op="Resize") as sp:
            pass
        from repro.obs import set_attrs

        set_attrs(sp, s=23, t=9, eta=14)
        set_attrs(None, s=1)  # what span() yields with tracing off
    assert sp.attrs == {"op": "Resize", "s": 23}
    assert sorted(tr.redactions) == ["eta", "t"]


def test_compile_phases_are_spans_and_nest_as_jax_runs_them():
    inner = jax.jit(lambda x: x * 3 + 1)
    outer = jax.jit(lambda x: inner(x) - 2)
    x = jnp.arange(5, dtype=jnp.int32).block_until_ready()
    with Tracer() as tr:
        with span("node[Probe]"):
            outer(x).block_until_ready()
    (node,) = tr.find("node[Probe]")
    compiles = {s.span_id: s for s in tr.find("xla.compile")}
    top = [s for s in compiles.values() if s.parent_id == node.span_id]
    assert [s.attrs["phase"] for s in top] == ["trace", "lower", "backend"]
    # the inner jit is traced while the outer one is: a child of that phase
    nested = [s for s in compiles.values() if s.parent_id in compiles]
    assert nested and all(
        compiles[s.parent_id].attrs["phase"] == "trace" for s in nested
    )
    for s in top:
        assert node.ts <= s.ts and s.ts + s.seconds <= node.ts + node.seconds + 1e-6


# -----------------------------------------------------------------------------
# MetricsRegistry
# -----------------------------------------------------------------------------

def test_counter_labels_total_and_touch():
    m = MetricsRegistry()
    c = m.counter("q_total", "queries", ("tenant",))
    c.touch(tenant="bob")
    c.inc(tenant="alice")
    c.inc(2, tenant="alice")
    assert c.value(tenant="alice") == 3
    assert c.value(tenant="bob") == 0
    assert c.total() == 3
    assert dict((k[0], v) for k, v in c.samples()) == {"alice": 3, "bob": 0}
    with pytest.raises(ValueError):
        c.inc(-1, tenant="alice")


def test_counter_rejects_undeclared_labels():
    m = MetricsRegistry()
    c = m.counter("q_total", "", ("tenant",))
    with pytest.raises(ValueError):
        c.inc(reason="full")


def test_registry_dedupes_and_rejects_shape_conflicts():
    m = MetricsRegistry()
    a = m.counter("x_total", "", ("tenant",))
    assert m.counter("x_total", "", ("tenant",)) is a
    with pytest.raises(ValueError):
        m.counter("x_total", "", ("reason",))
    with pytest.raises(ValueError):
        m.gauge("x_total", "")


def test_histogram_buckets_sum_count():
    m = MetricsRegistry()
    h = m.histogram("lat_seconds", "", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 4 and h.sum() == pytest.approx(5.555)
    text = m.render_prometheus()
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="0.1"} 2' in text
    assert 'lat_seconds_bucket{le="1.0"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text


def test_prometheus_exposition_format():
    m = MetricsRegistry()
    c = m.counter("reflex_queries_total", "Completed queries", ("tenant",))
    c.inc(tenant='we"ird\nname')
    g = m.gauge("reflex_queue_depth", "Pending")
    g.set(3)
    text = m.render_prometheus()
    assert "# HELP reflex_queries_total Completed queries" in text
    assert "# TYPE reflex_queries_total counter" in text
    assert "# TYPE reflex_queue_depth gauge" in text
    assert 'reflex_queries_total{tenant="we\\"ird\\nname"} 1.0' in text
    assert "reflex_queue_depth 3.0" in text


def test_snapshot_is_json_safe():
    m = MetricsRegistry()
    m.counter("a_total", "", ("tenant",)).inc(tenant="x")
    m.histogram("b_seconds", "").observe(0.2)
    blob = json.loads(json.dumps(m.snapshot()))
    assert blob["a_total"]["samples"] == [
        {"labels": {"tenant": "x"}, "value": 1.0}
    ]
    assert blob["b_seconds"]["samples"][0]["count"] == 1


# -----------------------------------------------------------------------------
# Ledger satellite: coalesced count semantics
# -----------------------------------------------------------------------------

def test_ledger_coalesces_identical_runs():
    """Regression (ISSUE 7): ``count`` was hardwired to 1 — a loop logging
    the same op N times produced N entries and ``by_op()['calls']`` counted
    log entries, not calls. Identical consecutive logs now coalesce into one
    entry with the true repetition count, and every aggregate scales by it."""
    led = CommLedger()
    with led:
        for _ in range(5):
            log_comm("mul", 1, 64)
        log_comm("eq", 5, 20)
        log_comm("mul", 1, 64)  # new run: eq broke the streak
    assert [(e.op, e.count) for e in led.entries] == [
        ("mul", 5), ("eq", 1), ("mul", 1),
    ]
    assert led.tally() == {"bytes_per_party": 6 * 64 + 20, "rounds": 6 + 5}
    by = led.by_op()
    assert by["mul"] == {"rounds": 6, "bytes_per_party": 384, "calls": 6}
    assert by["eq"] == {"rounds": 5, "bytes_per_party": 20, "calls": 1}


def test_fused_scales_coalesced_bytes():
    led = CommLedger()
    with led:
        with led.fused("eqtree", 5):
            for _ in range(4):
                log_comm("and", 1, 8)
    (e,) = led.entries
    assert (e.op, e.rounds, e.bytes_per_party, e.count) == ("eqtree", 5, 32, 1)
    assert led.tally() == {"bytes_per_party": 32, "rounds": 5}


def test_by_op_matches_tally_under_vmapped_pass():
    """batched_tally composes with by_op(): the one traced profile of a
    vmapped protocol is the per-slot cost, so physical bytes scale by K while
    by_op() keeps reporting per-slot calls and rounds."""
    def proto(x):
        for _ in range(3):
            log_comm("mul", 1, int(x.shape[-1]) * 4)
        return x * 2

    xs = jnp.ones((4, 8), jnp.uint32)  # K=4 slots of 8 lanes
    with CommLedger() as led:
        jax.vmap(proto)(xs)  # traces once with per-slot shapes
    per_slot = led.tally()
    assert per_slot == {"bytes_per_party": 3 * 32, "rounds": 3}
    assert led.by_op()["mul"]["calls"] == 3  # coalesced run of 3
    phys = batched_tally(per_slot, slots=4)
    assert phys["bytes_per_party"] == 4 * per_slot["bytes_per_party"]
    assert phys["rounds"] == per_slot["rounds"]  # rounds shared by the batch
    # tally and by_op agree on totals whatever the coalescing did
    by = led.by_op()
    assert sum(v["bytes_per_party"] for v in by.values()) == per_slot["bytes_per_party"]
    assert sum(v["rounds"] for v in by.values()) == per_slot["rounds"]


# -----------------------------------------------------------------------------
# Report satellites: to_dict/to_json round-trip, summary rendering
# -----------------------------------------------------------------------------

def _scalar_report():
    """NodeStats carrying numpy/jax scalars and nested extra — exactly what
    the engine produces when resize info flows through jit boundaries."""
    return ExecutionReport(nodes=[
        NodeStats(
            node="Scan(t)", n_in=0, n_ins=[], n_out=8,
            seconds=np.float64(0.25), bytes_per_party=0, rounds=0,
        ),
        NodeStats(
            node="Resize[rho]", n_in=8, n_ins=[8],
            n_out=int(jnp.asarray(5)),
            seconds=0.5, bytes_per_party=1024, rounds=7,
            extra={
                "n": np.int64(8), "t": jnp.asarray(3, jnp.uint32),
                "s": np.uint32(5), "s_padded": 8,
                "nested": {"p": np.float32(0.4), "list": [np.int32(1), 2]},
            },
        ),
    ])


def test_to_dict_to_json_round_trip_with_foreign_scalars():
    rep = _scalar_report()
    blob = json.loads(rep.to_json())  # would raise if any scalar leaked
    rz = blob["nodes"][1]
    assert rz["extra"]["n"] == 8 and rz["extra"]["s"] == 5
    assert rz["extra"]["nested"]["list"] == [1, 2]
    assert isinstance(rz["extra"]["nested"]["p"], float)
    assert blob["total_bytes"] == 1024 and blob["total_rounds"] == 7
    assert blob["total_seconds"] == pytest.approx(0.75)
    # a second encode of the decoded blob is the identity (fully JSON-native)
    assert json.loads(json.dumps(blob)) == blob


def test_summary_renders_all_inputs_and_extra():
    rep = ExecutionReport(nodes=[
        NodeStats(
            node="Join(pid==pid)", n_in=12, n_ins=[12, 16], n_out=192,
            seconds=0.1, bytes_per_party=2048, rounds=7,
        ),
        NodeStats(
            node="Resize[rho]", n_in=192, n_ins=[192], n_out=32,
            seconds=0.2, bytes_per_party=4096, rounds=9,
            extra={"n": 192, "t": 11, "s": 25, "s_padded": 32, "eta": 14},
        ),
        NodeStats(
            node="Resize[skip]", n_in=32, n_ins=[32], n_out=32,
            seconds=0.0, bytes_per_party=0, rounds=0,
            extra={"n": 32, "t": 11, "s": 32, "skipped": True},
        ),
    ])
    text = rep.summary()
    join_line, rz_line, skip_line = text.splitlines()[1:4]
    assert "12x16" in join_line  # every input size, not just the first
    assert "S=25" in rz_line and "pad->32" in rz_line
    assert "trim skipped" in skip_line
    # the secret resizer fields never reach the rendered summary
    assert "t=11" not in text and "eta" not in text
