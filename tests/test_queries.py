"""End-to-end: the four HealthLnK queries under all execution modes."""
import jax
import numpy as np
import pytest

from repro.core.noise import BetaNoise, RevealNoise, shrinkwrap_default
from repro.core.resizer import ResizerConfig
from repro.data import all_query_plans, generate_healthlnk, plaintext_oracle
from repro.engine import Engine
from repro.plan import insert_resizers
from repro.plan.cost import CostModel


@pytest.fixture(scope="module")
def data():
    return generate_healthlnk(n=24, seed=3, aspirin_frac=0.4, icd_heart_frac=0.3)


def _run(tables, plan, placement, noise=None):
    eng = Engine(tables, key=jax.random.PRNGKey(5))
    noise = noise or BetaNoise(2, 6)
    p = insert_resizers(plan, lambda n: ResizerConfig(noise=noise), placement=placement)
    return eng.execute(p)


def test_comorbidity(data):
    tables, plain = data
    out, rep = _run(tables, all_query_plans()["comorbidity"], "none")
    d = out.reveal()
    mask = d["_valid"].astype(bool)
    got = dict(zip(d["major_icd9"][mask].tolist(), d["cnt"][mask].tolist()))
    vals, counts = np.unique(plain["diagnoses"]["major_icd9"], return_counts=True)
    full = dict(zip(vals.tolist(), counts.tolist()))
    assert all(full[k] == v for k, v in got.items())
    assert sorted(got.values(), reverse=True) == sorted(full.values(), reverse=True)[: len(got)]


@pytest.mark.parametrize("placement", ["none", "all_internal", "after_joins"])
def test_dosage_study_all_modes(data, placement):
    tables, plain = data
    out, rep = _run(tables, all_query_plans()["dosage_study"], placement)
    got = sorted(set(out.reveal_true_rows()["pid"].tolist()))
    assert got == plaintext_oracle("dosage_study", plain)


@pytest.mark.parametrize("placement", ["none", "all_internal"])
def test_aspirin_count(data, placement):
    tables, plain = data
    out, rep = _run(tables, all_query_plans()["aspirin_count"], placement)
    got = int(out.reveal_true_rows()["cnt"][0])
    assert got == plaintext_oracle("aspirin_count", plain)


def test_three_join_with_resizers(data):
    tables, plain = data
    out, rep = _run(tables, all_query_plans()["three_join"], "after_joins")
    got = int(out.reveal_true_rows()["cnt"][0])
    assert got == plaintext_oracle("three_join", plain)


def test_revealed_mode_matches_secretflow_semantics(data):
    tables, plain = data
    out, rep = _run(
        tables, all_query_plans()["dosage_study"], "all_internal", noise=RevealNoise()
    )
    got = sorted(set(out.reveal_true_rows()["pid"].tolist()))
    assert got == plaintext_oracle("dosage_study", plain)
    # resize nodes disclosed the exact true size
    for s in rep.nodes:
        if s.node.startswith("Resize"):
            assert s.extra["s"] == s.extra["t"]


def test_resizers_shrink_intermediates(data):
    tables, plain = data
    _, rep_fo = _run(tables, all_query_plans()["aspirin_count"], "none")
    _, rep_rx = _run(tables, all_query_plans()["aspirin_count"], "all_internal")
    fo_bytes = rep_fo.total_bytes
    rx_bytes = rep_rx.total_bytes
    assert rx_bytes < fo_bytes  # trimming reduces total communication


def test_cost_model_estimates_and_placement():
    plans = all_query_plans()
    cm = CostModel(
        table_sizes={"diagnoses": 1000, "medications": 1000, "demographics": 250},
        table_cols={"diagnoses": 5, "medications": 4, "demographics": 2},
        noise=shrinkwrap_default(),
    )
    fo = cm.plan_bytes(plans["aspirin_count"])
    rx = cm.plan_bytes(
        insert_resizers(
            plans["aspirin_count"],
            lambda n: ResizerConfig(noise=shrinkwrap_default()),
            placement="all_internal",
        )
    )
    assert rx < fo  # the model agrees trimming helps on join-heavy queries

    # cost-based placement inserts at least one resizer on a join query
    p = insert_resizers(
        plans["aspirin_count"],
        lambda n: ResizerConfig(noise=shrinkwrap_default()),
        placement="cost_based",
        cost_model=cm,
    )
    assert "Resize" in p.pretty()


# -----------------------------------------------------------------------------
# The vectorized join oracles against the nested-loop definitions they replace
# -----------------------------------------------------------------------------

def _loop_oracle(query, plain):
    from repro.data.healthlnk import (
        DIAG_HEART_DISEASE,
        DOSAGE_325MG,
        ICD9_CIRCULATORY,
        ICD9_HEART_414,
        MED_ASPIRIN,
    )

    d, m = plain["diagnoses"], plain["medications"]
    demo_pids = set(plain["demographics"]["pid"].tolist())
    pids, pairs = set(), set()
    for i in range(len(d["pid"])):
        for j in range(len(m["pid"])):
            if m["pid"][j] != d["pid"][i]:
                continue
            pid = int(d["pid"][i])
            aspirin = m["med"][j] == MED_ASPIRIN
            before = d["time"][i] <= m["time"][j]
            if query == "dosage_study" and d["icd9"][i] == ICD9_CIRCULATORY \
                    and aspirin and m["dosage"][j] == DOSAGE_325MG:
                pids.add(pid)
            if query == "aspirin_count" and d["icd9"][i] == ICD9_HEART_414 \
                    and aspirin and before:
                pids.add(pid)
            if query == "three_join" and d["diag"][i] == DIAG_HEART_DISEASE \
                    and aspirin and before and pid in demo_pids:
                pids.add(pid)
            if query == "projection_join" and aspirin:
                pairs.add((pid, int(m["dosage"][j])))
    if query == "dosage_study":
        return sorted(pids)
    if query == "projection_join":
        return sorted(pairs)
    return len(pids)


@pytest.mark.parametrize(
    "query", ["dosage_study", "aspirin_count", "three_join", "projection_join"]
)
def test_join_oracle_matches_nested_loop(query):
    for seed in range(4):
        _, plain = generate_healthlnk(
            n=96, seed=seed, aspirin_frac=0.4, icd_heart_frac=0.3
        )
        assert plaintext_oracle(query, plain) == _loop_oracle(query, plain)
