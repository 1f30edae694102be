"""Multi-party runtime end-to-end over the loopback mesh: networked
execution must be bit-exact with the single-process oracle, wire bytes must
equal ledger bytes per party, and failures (party crash, lockstep desync)
must surface as typed TransportErrors that ride the service's
failed-execution budget path."""
import jax
import numpy as np
import pytest

from repro.config import RuntimeConfig
from repro.data import generate_healthlnk
from repro.data.queries import QUERY_SQL
from repro.errors import TransportError
from repro.plan.nodes import JoinSortMerge
from repro.runtime import (
    ReflexClient,
    RemoteEngine,
    decode_table,
    encode_table,
    launch_loopback_mesh,
)
from repro.sql.catalog import Catalog

JOIN_GOLDEN = QUERY_SQL["dosage_study"]      # join + resize + reveal_k
GROUPBY_GOLDEN = QUERY_SQL["med_dosage_sum"]  # shuffle/sort groupby


@pytest.fixture(scope="module")
def data():
    return generate_healthlnk(n=16, seed=3, aspirin_frac=0.5,
                              icd_heart_frac=0.4)


@pytest.fixture(scope="module")
def clients(data):
    tables, _ = data
    oracle = ReflexClient.in_process(
        tables, key=jax.random.PRNGKey(0), offline="off"
    )
    networked = ReflexClient.networked(tables, key_seed=0)
    yield oracle, networked
    networked.close()
    oracle.close()


def assert_same_result(a, b):
    assert set(a.rows) == set(b.rows)
    for k in a.rows:
        np.testing.assert_array_equal(a.rows[k], b.rows[k])


# -----------------------------------------------------------------------------
# Bit-exactness vs the single-process oracle
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("sql", [JOIN_GOLDEN, GROUPBY_GOLDEN],
                         ids=["join_resize", "groupby"])
def test_networked_matches_oracle(clients, sql):
    oracle, networked = clients
    want = oracle.submit("tenant", sql)
    got = networked.submit("tenant", sql)
    assert_same_result(want, got)
    # the ledger (bytes, rounds, node sizes) is topology-invariant
    wd, gd = want.report.to_dict(), got.report.to_dict()
    for w, g in zip(wd["nodes"], gd["nodes"]):
        assert (w["node"], w["n_ins"], w["n_out"], w["bytes_per_party"],
                w["rounds"]) == (g["node"], g["n_ins"], g["n_out"],
                                 g["bytes_per_party"], g["rounds"])


def test_wire_bytes_equal_ledger_bytes_per_party(clients):
    _oracle, networked = clients
    res = networked.submit("tenant", JOIN_GOLDEN)
    audit = networked.service.engine.last_wire_audit
    assert [a["party"] for a in audit] == [0, 1, 2]
    total = res.report.to_dict()["total_bytes"]
    for a in audit:
        assert a["wire_bytes"] == a["exchange_bytes"] == a["ledger_bytes"]
        assert a["ledger_bytes"] == total
        assert a["exchanges"] > 0


def test_networked_batched_drain_matches_oracle(clients):
    oracle, networked = clients
    for c in (oracle, networked):
        c.enqueue("t1", GROUPBY_GOLDEN)
        c.enqueue("t2", GROUPBY_GOLDEN)
    want = oracle.drain()
    got = networked.drain()
    assert len(want) == len(got) == 2
    for w, g in zip(want, got):
        assert_same_result(w, g)


def test_networked_explain_analyze_and_status(clients):
    _oracle, networked = clients
    text, res = networked.explain_analyze("tenant", GROUPBY_GOLDEN)
    assert "act.rows" in text and res.rows
    st = networked.status()
    assert st["runtime"]["mode"] == "networked"
    assert st["runtime"]["wire_audit"]  # audit of the last engine pass


def test_networked_config_is_shipped_to_parties(data):
    tables, plain = data
    cfg = RuntimeConfig(join_algo="sortmerge")
    # sort-merge is applicable only under a declared per-key fanout bound
    mult = {
        t: {"pid": int(np.bincount(cols["pid"]).max())}
        for t, cols in plain.items()
    }
    catalog = Catalog.from_tables(tables, multiplicity=mult)
    oracle = ReflexClient.in_process(
        tables, key=jax.random.PRNGKey(0), offline="off", config=cfg,
        catalog=catalog,
    )
    networked = ReflexClient.networked(
        tables, key_seed=0, config=cfg, catalog=catalog
    )
    try:
        want = oracle.submit("tenant", JOIN_GOLDEN)
        got = networked.submit("tenant", JOIN_GOLDEN)

        def walk(n):
            yield n
            for c in n.children():
                yield from walk(c)

        # the mesh-wide config made every party pick the sort-merge join —
        # divergence from the oracle (or between parties) would have failed
        assert any(isinstance(n, JoinSortMerge) for n in walk(got.plan))
        assert_same_result(want, got)
    finally:
        networked.close()
        oracle.close()


def test_networked_sort_under_pallas_matches_oracle(clients, data, monkeypatch):
    """A sort-based GROUP BY with every kernel on (the sort's stages run as
    bitonic_stage launches) on all three parties: rows and per-node ledger
    equal the gate-path oracle's, and every party's wire bytes equal its
    ledger bytes."""
    from collections import Counter

    from repro import kernels

    launches = Counter()  # one count for the party threads together
    monkeypatch.setattr(kernels, "_counter", lambda: launches)
    oracle, _ = clients
    tables, _ = data
    networked = ReflexClient.networked(
        tables, key_seed=0, config=RuntimeConfig(use_pallas=True)
    )
    try:
        want = oracle.submit("tenant", GROUPBY_GOLDEN)
        got = networked.submit("tenant", GROUPBY_GOLDEN)
        assert launches["bitonic_stage"] > 0
        assert_same_result(want, got)
        tally = lambda r: [
            (s.node, s.bytes_per_party, s.rounds) for s in r.report.nodes
        ]
        assert tally(want) == tally(got)
        audit = networked.service.engine.last_wire_audit
        assert [a["party"] for a in audit] == [0, 1, 2]
        for a in audit:
            assert a["wire_bytes"] == a["exchange_bytes"] == a["ledger_bytes"]
    finally:
        networked.close()


# -----------------------------------------------------------------------------
# Failure taxonomy
# -----------------------------------------------------------------------------


def test_party_crash_mid_query_raises_and_charges_budget(data):
    tables, _ = data
    coord, _servers, _threads = launch_loopback_mesh(
        fault_after={1: 5}, exchange_timeout=5.0
    )
    client = ReflexClient.networked(tables, coordinator=coord, key_seed=0)
    acct = client.service.accountant
    assert acct.status() == []  # nothing observed yet
    with pytest.raises(TransportError):
        client.submit("tenant", JOIN_GOLDEN)
    # the failed run may have disclosed its noisy sizes: charge_failed must
    # have conservatively charged one observation per resize
    st = acct.status()
    assert st and all(s["observed"] >= 1 for s in st)
    client.service.close()
    coord.close()


def test_lockstep_desync_is_rejected(data):
    tables, _ = data
    networked = ReflexClient.networked(tables, key_seed=0)
    try:
        networked.submit("tenant", JOIN_GOLDEN)  # parties advance their ctr
        eng = networked.service.engine
        eng._resize_ctr = 999  # coordinator now disagrees with the mesh
        with pytest.raises(TransportError) as ei:
            networked.submit("tenant", JOIN_GOLDEN)
        assert ei.value.reason == "divergence"
        assert "desync" in str(ei.value)
    finally:
        networked.close()


def test_remote_engine_rejects_jit_ops(data):
    tables, _ = data
    with pytest.raises(ValueError, match="jit_ops"):
        RemoteEngine(tables, coordinator=None, jit_ops=True)


@pytest.mark.parametrize("kwarg", [
    {"jit_ops": True}, {"offline": "on"}, {"engine_factory": object},
])
def test_networked_client_pins_constructor_args(data, kwarg):
    tables, _ = data
    with pytest.raises(ValueError, match="pinned"):
        ReflexClient.networked(tables, **kwarg)


# -----------------------------------------------------------------------------
# Table shipping
# -----------------------------------------------------------------------------


def test_encode_decode_table_round_trip(data):
    tables, _ = data
    for name, t in tables.items():
        back = decode_table(encode_table(t))
        assert back.column_names() == t.column_names()
        np.testing.assert_array_equal(
            np.asarray(back.valid.shares), np.asarray(t.valid.shares)
        )
        for col in t.column_names():
            a, b = t.col(col), back.col(col)
            assert type(a) is type(b)
            np.testing.assert_array_equal(
                np.asarray(a.shares), np.asarray(b.shares)
            )
