"""Offline randomness subsystem (DESIGN.md §15): manifest exactness,
pool hit/miss fallback, counter-range ownership, provisioner refills, and
bit-exact hot/cold/mixed parity through the engine and the service."""
import threading

import jax
import numpy as np
import pytest

from repro.core import material
from repro.core.noise import BetaNoise
from repro.core.resizer import ResizerConfig
from repro.data import generate_healthlnk, plaintext_oracle
from repro.data.queries import QUERY_SQL
from repro.engine import Engine
from repro.obs.explain import explain_text
from repro.offline import Provisioner, RandomnessPlanner, RandomnessPool
from repro.ops.filter import Predicate
from repro.plan.nodes import Filter, Resize, Scan, Sum
from repro.service import AnalyticsService, PrivacyAccountant
from repro.sql.catalog import HEALTHLNK_CATALOG


@pytest.fixture(scope="module")
def data():
    return generate_healthlnk(n=16, seed=3, aspirin_frac=0.5, icd_heart_frac=0.4)


def _engine(tables, seed=0):
    return Engine(tables, key=jax.random.PRNGKey(seed))


def _recorded(tables, plan, seed=0):
    """Run ``plan`` cold on a fresh engine under a recording PoolSource."""
    eng = _engine(tables, seed)
    pool = RandomnessPool()
    src = pool.source(("bundle",), eng.prf.pair_keys)
    with material.material_scope(src):
        out, rep = eng.execute(plan)
    src.finish()
    return eng, pool, src, out, rep


# -----------------------------------------------------------------------------
# Manifest exactness: planned counts == recorded derivation events
# -----------------------------------------------------------------------------

EXACT_PLANS = {
    "filter": lambda: Filter(Scan("diagnoses"), [Predicate("icd9", "eq", 414)]),
    "sum": lambda: Sum(
        Filter(Scan("medications"), [Predicate("med", "eq", 1)]), "dosage"
    ),
    "resize_parallel": lambda: Resize(
        Filter(Scan("diagnoses"), [Predicate("icd9", "eq", 414)]),
        ResizerConfig(noise=BetaNoise(2, 6), addition="parallel"),
    ),
    "resize_sequential": lambda: Resize(
        Filter(Scan("diagnoses"), [Predicate("icd9", "eq", 414)]),
        ResizerConfig(noise=BetaNoise(2, 6), addition="sequential"),
    ),
}


@pytest.mark.parametrize("name", list(EXACT_PLANS))
def test_manifest_exact_counts_match_recorded_events(data, name):
    """For the statically-enumerable operators the manifest is EXACT: the
    planner's per-template counts equal the unique derivation events a cold
    recording run actually intercepted, op for op."""
    tables, _ = data
    plan = EXACT_PLANS[name]()
    manifest = RandomnessPlanner(catalog=HEALTHLNK_CATALOG).manifest(plan)
    assert manifest.exact, [ (nm.op, nm.exact) for nm in manifest.nodes ]
    _, _, src, _, _ = _recorded(tables, plan)
    got = src.event_counts()
    totals = manifest.totals()
    assert got.get("fold", 0) == totals["folds"]
    assert got.get("draw", 0) + got.get("uniform", 0) == totals["draws"]
    assert got.get("zero_add", 0) + got.get("zero_xor", 0) == totals["zero_shares"]
    assert got.get("perm", 0) == totals["perms"]


def test_manifest_flags_sort_based_operators_inexact(data):
    from repro.sql import compile_logical

    plan = compile_logical(QUERY_SQL["dosage_study"])
    manifest = RandomnessPlanner(catalog=HEALTHLNK_CATALOG).manifest(plan)
    assert not manifest.exact  # Join + Distinct are sizing estimates
    assert manifest.totals()["events"] > 0


# -----------------------------------------------------------------------------
# Engine-level parity: hot == cold == no-pool, bit for bit
# -----------------------------------------------------------------------------

def test_hot_run_bit_identical_to_cold_and_unpooled(data):
    tables, _ = data
    plan = EXACT_PLANS["resize_parallel"]()

    # reference: no material source at all
    out_ref, rep_ref = _engine(tables).execute(plan)

    # cold recording run fills the pool (static backfill + recipe)
    eng1, pool, src1, out_cold, rep_cold = _recorded(tables, plan)
    assert src1.misses > 0 and pool.has_recipe(("bundle",))

    # provision counter material for a second engine's upcoming counters
    eng2 = _engine(tables)
    prov = Provisioner(
        pool, eng2.prf, ctr_fn=lambda: eng2._resize_ctr, window=4
    )
    summary = prov.refill(trigger="test")
    assert summary["counter_entries"] > 0
    lo, hi, count = pool.owned_counters(("bundle",))
    assert (lo, count) == (1, 4)  # counters 1..4 owned, engine allocates them

    src2 = pool.source(("bundle",), eng2.prf.pair_keys)
    with material.material_scope(src2):
        out_hot, rep_hot = eng2.execute(plan)
    assert src2.hits > 0

    for o in (out_cold, out_hot):
        ref, got = out_ref.reveal(), o.reveal()
        assert ref.keys() == got.keys()
        for k in ref:
            np.testing.assert_array_equal(ref[k], got[k])
    # ledger parity: same bytes/rounds per node, same revealed trim sizes
    tally = lambda rep: [
        (s.node, s.bytes_per_party, s.rounds) for s in rep.nodes
    ]
    assert tally(rep_ref) == tally(rep_cold) == tally(rep_hot)
    s_of = lambda rep: [
        s.extra.get("s") for s in rep.nodes if s.node.startswith("Resize")
    ]
    assert s_of(rep_ref) == s_of(rep_cold) == s_of(rep_hot)


def test_mixed_run_partial_pool_still_bit_identical(data):
    """GC away the counter material (simulating a pool that fell behind):
    the hot pass degrades to static-only hits + on-demand counter material,
    from the SAME engine counter — results stay bit-identical."""
    tables, _ = data
    plan = EXACT_PLANS["resize_sequential"]()
    out_ref, _ = _engine(tables).execute(plan)
    eng1, pool, _, _, _ = _recorded(tables, plan)

    eng2 = _engine(tables)
    Provisioner(pool, eng2.prf, ctr_fn=lambda: eng2._resize_ctr).refill()
    pool.gc(10**6)  # drop ALL provisioned counter entries
    assert pool.stats()["counter_entries"] == 0

    src = pool.source(("bundle",), eng2.prf.pair_keys)
    with material.material_scope(src):
        out_mixed, _ = eng2.execute(plan)
    assert src.hits > 0 and src.misses > 0  # static hot, counters cold
    ref, got = out_ref.reveal(), out_mixed.reveal()
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


def test_pool_budget_evicts_static_bundles_not_correctness(data):
    """A budget that holds either bundle but not both evicts the LRU *other*
    bundle (never the in-flight one) to make room — and eviction only costs
    future hits, never correctness."""
    tables, _ = data
    plan = EXACT_PLANS["filter"]()
    plan2 = Filter(Scan("medications"), [Predicate("med", "eq", 1)])
    out_ref, _ = _engine(tables).execute(plan)
    b1 = _recorded(tables, plan)[1].total_bytes()
    b2 = _recorded(tables, plan2)[1].total_bytes()
    pool = RandomnessPool(max_bytes=max(b1, b2))
    eng = _engine(tables)
    src = pool.source(("b1",), eng.prf.pair_keys)
    with material.material_scope(src):
        out, _ = eng.execute(plan)
    assert pool.total_bytes() == b1 > 0  # b1 held whole
    src2 = pool.source(("b2",), eng.prf.pair_keys)
    with material.material_scope(src2):
        eng.execute(plan2)
    stats = pool.stats()
    assert stats["evictions"] == 1 and stats["bundles"] == 1  # b1 evicted
    assert pool.total_bytes() == b2 > 0  # to make room for b2, whole
    for k, v in out_ref.reveal().items():
        np.testing.assert_array_equal(v, out.reveal()[k])


def test_pool_value_past_the_budget_evicts_nothing():
    """A value that could never fit is dropped without emptying the pool."""
    pool = RandomnessPool(max_bytes=64)
    pool.put(("b1",), ("k",), jax.numpy.zeros(8, jax.numpy.uint32))
    pool.put(("b2",), ("k",), jax.numpy.zeros(32, jax.numpy.uint32))
    stats = pool.stats()
    assert stats["evictions"] == 0 and stats["static_entries"] == 1
    assert pool.total_bytes() == 32


def test_pool_budget_bounds_the_in_flight_bundle(data):
    """One execution's static material never holds more than the budget:
    what does not fit is not kept (device memory stays bounded however large
    the query), and the next run derives it on demand, bit-identically."""
    tables, _ = data
    plan = EXACT_PLANS["filter"]()
    out_ref, _ = _engine(tables).execute(plan)
    _, full, _, _, _ = _recorded(tables, plan)
    budget = full.total_bytes() // 2
    pool = RandomnessPool(max_bytes=budget)
    for _ in range(2):  # cold fill, then a partly hot run
        eng = _engine(tables)
        src = pool.source(("b",), eng.prf.pair_keys)
        with material.material_scope(src):
            out, _ = eng.execute(plan)
        src.finish()
        assert 0 < pool.total_bytes() <= budget
        for k, v in out_ref.reveal().items():
            np.testing.assert_array_equal(v, out.reveal()[k])
    assert src.hits > 0 and src.misses > 0


# -----------------------------------------------------------------------------
# Counter-range ownership under exhaustion
# -----------------------------------------------------------------------------

def test_exhaustion_mid_stream_never_splits_counter_stream(data):
    """Provision only counters 1..2, then run three resize executions: the
    third is a pool miss that derives on demand from the engine's OWN next
    counter (3) — the counter stream stays contiguous and results match a
    never-pooled engine exactly."""
    tables, _ = data
    plan = EXACT_PLANS["resize_parallel"]()

    eng_ref = _engine(tables)
    refs = [eng_ref.execute(plan) for _ in range(3)]

    eng1, pool, _, _, _ = _recorded(tables, plan)
    eng = _engine(tables)
    Provisioner(pool, eng.prf, ctr_fn=lambda: eng._resize_ctr, window=2).refill()
    assert pool.owned_counters(("bundle",))[2] == 2

    outs = []
    for _ in range(3):
        src = pool.source(("bundle",), eng.prf.pair_keys)
        with material.material_scope(src):
            outs.append(eng.execute(plan))
    assert eng._resize_ctr == eng_ref._resize_ctr == 3  # contiguous allocation
    for (out_r, rep_r), (out_p, rep_p) in zip(refs, outs):
        for k, v in out_r.reveal().items():
            np.testing.assert_array_equal(v, out_p.reveal()[k])
        assert [s.extra.get("s") for s in rep_r.nodes if s.node.startswith("Resize")] \
            == [s.extra.get("s") for s in rep_p.nodes if s.node.startswith("Resize")]


def test_gc_drops_consumed_counters(data):
    tables, _ = data
    plan = EXACT_PLANS["resize_parallel"]()
    eng1, pool, _, _, _ = _recorded(tables, plan)
    eng = _engine(tables)
    Provisioner(pool, eng.prf, ctr_fn=lambda: eng._resize_ctr, window=4).refill()
    before = pool.stats()["counter_entries"]
    assert before > 0
    src = pool.source(("bundle",), eng.prf.pair_keys)
    with material.material_scope(src):
        eng.execute(plan)  # consumes counter 1
    dropped = pool.gc(eng._resize_ctr)
    assert dropped > 0
    lo, _, count = pool.owned_counters(("bundle",))
    assert lo > eng._resize_ctr and count == 3  # only future counters remain


# -----------------------------------------------------------------------------
# Concurrency: provisioner refills racing the consuming engine
# -----------------------------------------------------------------------------

def test_concurrent_refill_and_drain_race(data):
    tables, _ = data
    plan = EXACT_PLANS["resize_parallel"]()
    eng_ref = _engine(tables)  # advances its counter in lockstep below
    eng1, pool, _, _, _ = _recorded(tables, plan)

    eng = _engine(tables)
    prov = Provisioner(pool, eng.prf, ctr_fn=lambda: eng._resize_ctr, window=4)
    stop = threading.Event()
    errors = []

    def hammer():
        while not stop.is_set():
            try:
                prov.refill(trigger="race")
                pool.gc(eng._resize_ctr)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(4):
            out_ref, _ = eng_ref.execute(plan)
            src = pool.source(("bundle",), eng.prf.pair_keys)
            with material.material_scope(src):
                out, _ = eng.execute(plan)
            for k, v in out_ref.reveal().items():
                np.testing.assert_array_equal(v, out.reveal()[k])
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors


# -----------------------------------------------------------------------------
# Service integration: scopes, attribution, metrics, status
# -----------------------------------------------------------------------------

def _service(tables, offline="on", **kw):
    return AnalyticsService(
        tables,
        noise=BetaNoise(2, 6),
        addition="sequential",
        placement="after_joins",
        accountant=PrivacyAccountant(policy="escalate"),
        key=jax.random.PRNGKey(9),
        offline=offline,
        **kw,
    )


def test_service_hot_cold_parity_and_attribution(data):
    tables, plain = data
    sql = QUERY_SQL["dosage_study"]

    off = _service(tables, offline="off")
    ref = [off.submit("t", sql) for _ in range(3)]

    svc = _service(tables, offline="on")
    cold = svc.submit("t", sql)
    svc.provisioner.refill(trigger="test")
    hot = [svc.submit("t", sql) for _ in range(2)]

    oracle = plaintext_oracle("dosage_study", plain)
    for res in ref + [cold] + hot:
        assert sorted(set(res.rows["pid"].tolist())) == oracle
    # ledger parity per submission ordinal (noise counters advance per query)
    for r, p in zip(ref, [cold] + hot):
        assert [(s.node, s.bytes_per_party, s.rounds) for s in r.report.nodes] \
            == [(s.node, s.bytes_per_party, s.rounds) for s in p.report.nodes]

    # hot passes actually hit the pool, and the engine attributes per node
    ps = svc.pool.stats()
    assert ps["hits"] > 0 and ps["recipes"] >= 1
    extras = [s.extra.get("offline") for s in hot[-1].report.nodes]
    assert any(e and e.get("hits", 0) > 0 for e in extras if e)

    # EXPLAIN ANALYZE renders the hot/cold column
    txt = explain_text(hot[-1].plan, report=hot[-1].report)
    assert "offline" in txt.splitlines()[0]
    assert any(("hot" in ln or "h/" in ln) for ln in txt.splitlines()[1:])

    st = svc.status()["offline"]
    assert st["mode"] == "on" and st["recipes"] >= 1
    assert svc.status()["offline"]["provisioner"]["refills"] >= 1


def test_service_offline_metrics_export_and_redaction(data):
    tables, _ = data
    svc = _service(tables, offline="on")
    svc.submit("t", QUERY_SQL["dosage_study"])
    svc.provisioner.refill(trigger="test")
    svc.submit("t", QUERY_SQL["dosage_study"])
    text = svc.metrics.render_prometheus()
    for name in (
        "reflex_offline_hits_total",
        "reflex_offline_misses_total",
        "reflex_offline_demand_total",
        "reflex_offline_pool_depth_bytes",
        "reflex_offline_pool_entries",
        "reflex_offline_refills_total",
        "reflex_offline_refill_seconds",
    ):
        assert name in text, name
    # labels passed the registration-time disclosure audit; the rendered
    # text must never carry a secret label (true size / noise draw) —
    # match label positions ({eta=... or ,eta=...), not value substrings
    import re

    assert "true_rows" not in text
    assert not re.search(r'[{,](?:eta|t|p)="', text)


def test_service_offline_modes_validate():
    with pytest.raises(ValueError, match="offline"):
        AnalyticsService({}, offline="sometimes")


def test_scheduler_batches_share_one_offline_scope(data):
    """A batched flush consumes pool material through the same scope a
    serial submit would — results match the offline-off scheduler exactly
    and the demand counter reflects every admission."""
    from repro.service.scheduler import QueryScheduler

    tables, plain = data
    sql = QUERY_SQL["dosage_study"]

    off = _service(tables, offline="off")
    sched_off = QueryScheduler(off, max_batch=4)
    for _ in range(3):
        sched_off.submit("t", sql)
    ref = sched_off.drain()

    svc = _service(tables, offline="on")
    sched = QueryScheduler(svc, max_batch=4)
    svc.submit("t", sql)  # cold pass records the recipe
    svc.provisioner.refill(trigger="test")
    for _ in range(3):
        sched.submit("t", sql)
    got = sched.drain()  # drain also hints the provisioner (idle refill)

    oracle = plaintext_oracle("dosage_study", plain)
    for res in ref + got:
        assert sorted(set(res.rows["pid"].tolist())) == oracle
    assert svc.pool.stats()["hits"] > 0
    assert svc.provisioner.stats()["refills"] >= 2  # explicit + idle hint
