"""jit_ops=True ledger-replay path (ISSUE 3 satellite): the trace-time tally
captured on first execution must replay identically on cache hits, so eager
and jitted runs of the same plan report the same per-node (bytes, rounds)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import generate_healthlnk
from repro.engine import Engine
from repro.ops.filter import Or, Predicate
from repro.plan.nodes import CountValid, Filter, Join, Scan


@pytest.fixture(scope="module")
def tables():
    return generate_healthlnk(n=8, seed=2, aspirin_frac=0.5)[0]


def _plan():
    d = Filter(
        Scan("diagnoses"),
        [Or((Predicate("icd9", "eq", 414), Predicate("icd9", "eq", 390)))],
    )
    return CountValid(Join(d, Scan("medications"), ("pid", "pid")))


def _profile(report):
    return [(s.node, s.bytes_per_party, s.rounds) for s in report.nodes]


def test_jit_ledger_parity_with_eager(tables):
    _, rep_eager = Engine(tables, key=jax.random.PRNGKey(3)).execute(_plan())

    Engine._JIT_CACHE.clear()
    eng = Engine(tables, key=jax.random.PRNGKey(3), jit_ops=True)
    _, rep_trace = eng.execute(_plan())  # first run: traces + captures tally
    assert _profile(rep_trace) == _profile(rep_eager)

    # protocol ops were cached (Scan bypasses the jit path)
    assert len(Engine._JIT_CACHE) == 3  # Filter, Join, CountValid


def test_jit_cache_hit_replays_recorded_tally(tables):
    Engine._JIT_CACHE.clear()
    eng = Engine(tables, key=jax.random.PRNGKey(3), jit_ops=True)
    _, rep_first = eng.execute(_plan())
    n_cached = len(Engine._JIT_CACHE)
    _, rep_hit = eng.execute(_plan())  # second run: pure replay, no trace
    assert len(Engine._JIT_CACHE) == n_cached  # no new entries -> cache hits
    assert _profile(rep_hit) == _profile(rep_first)

    # a second engine instance shares the process-wide cache: still parity
    eng2 = Engine(tables, key=jax.random.PRNGKey(9), jit_ops=True)
    _, rep_other = eng2.execute(_plan())
    assert _profile(rep_other) == _profile(rep_first)


def test_jit_results_match_eager_results(tables):
    out_e, _ = Engine(tables, key=jax.random.PRNGKey(3)).execute(_plan())
    Engine._JIT_CACHE.clear()
    eng = Engine(tables, key=jax.random.PRNGKey(3), jit_ops=True)
    out_1, _ = eng.execute(_plan())
    out_2, _ = eng.execute(_plan())
    e = int(out_e.reveal_true_rows()["cnt"][0])
    assert int(out_1.reveal_true_rows()["cnt"][0]) == e
    assert int(out_2.reveal_true_rows()["cnt"][0]) == e


# -----------------------------------------------------------------------------
# The bitonic network's ledger entry: one per sort, the per-stage path's cost
# -----------------------------------------------------------------------------

def _sort_cols(n, n_cols, seed=0):
    from repro.core.sharing import share_b

    rng = np.random.default_rng(seed)
    return {
        f"c{i}": share_b(rng.integers(0, 4, n).astype(np.uint32), jax.random.PRNGKey(i))
        for i in range(n_cols)
    }


def _one_sort_tally(n, n_keys, n_cols):
    """What the per-stage path logged for one sort: each stage runs the lt
    of every key (11 AND-words a lane), the ties' eq (5) and the combine
    (2 a key past the first, 1 more a key past the second), and one select
    word a column; 7 rounds a stage, 2 more a key past the first."""
    m = int(np.log2(n))
    stages = m * (m + 1) // 2
    words = 11 * n_keys + 7 * (n_keys - 1) + max(n_keys - 2, 0) + n_cols
    return {
        "bytes_per_party": stages * words * n * 4,
        "rounds": stages * (7 + 2 * (n_keys - 1)),
    }


# recorded from the per-stage path before the network became one program
PER_STAGE_TALLIES = {
    (32, 1, 2): {"bytes_per_party": 24960, "rounds": 105},
    (32, 2, 3): {"bytes_per_party": 61440, "rounds": 135},
    (1024, 1, 2): {"bytes_per_party": 2928640, "rounds": 385},
    (1024, 2, 3): {"bytes_per_party": 7208960, "rounds": 495},
}


@pytest.mark.parametrize("n", (2, 32, 1024))
@pytest.mark.parametrize("n_keys", (1, 2))
@pytest.mark.parametrize("descending", (False, True), ids=("asc", "desc"))
@pytest.mark.parametrize("mode", ("eager", "jit", "vmap"))
def test_sort_ledger_is_stage_count_times_one_stage(n, n_keys, descending, mode):
    """One ``bitonic_sort`` entry per sort, with the rounds and bytes the
    per-stage path logged, whether the sort runs eagerly, inside a jit trace
    (what the engine's ``jit_ops`` profile captures) or under vmap (per
    slot, as the stacked batch path records it): the network's body is
    traced once, and its cost counted once per stage."""
    from repro.core.ledger import CommLedger
    from repro.core.prf import setup_prf
    from repro.core.sort import bitonic_sort

    prf = setup_prf(jax.random.PRNGKey(1))
    cols = _sort_cols(n, n_keys + 1)
    keys = [f"c{i}" for i in range(n_keys)]

    def sort(shares):
        from repro.core.sharing import BShare

        out = bitonic_sort({nm: BShare(s) for nm, s in shares.items()}, keys, prf,
                           descending)
        return {nm: c.shares for nm, c in out.items()}

    shares = {nm: c.shares for nm, c in cols.items()}
    with CommLedger() as led:
        if mode == "eager":
            sort(shares)
        elif mode == "jit":
            jax.jit(sort)(shares)
        else:
            jax.vmap(sort, in_axes=1, out_axes=1)(
                {nm: jnp.stack([s, s], axis=1) for nm, s in shares.items()}
            )
    want = _one_sort_tally(n, n_keys, n_keys + 1)
    assert led.tally() == want
    assert [(e.op, e.count) for e in led.entries] == [("bitonic_sort", 1)]
    assert PER_STAGE_TALLIES.get((n, n_keys, n_keys + 1), want) == want


DISTINCT_NODE = ("Distinct(pid)", 3424, 56)  # recorded from the per-stage path


def test_distinct_sort_tally_eager_jit_and_batched(tables):
    """A Distinct plan's node tally is the per-stage path's, served eagerly,
    replayed from the ``jit_ops`` profile, and from a stacked (vmapped)
    batch of two slots."""
    from repro.plan.nodes import Distinct

    plan = lambda: Distinct(Scan("diagnoses"), "pid")
    _, rep_eager = Engine(tables, key=jax.random.PRNGKey(3)).execute(plan())
    Engine._JIT_CACHE.clear()
    jit_eng = Engine(tables, key=jax.random.PRNGKey(3), jit_ops=True)
    _, rep_trace = jit_eng.execute(plan())
    _, rep_hit = jit_eng.execute(plan())
    batch_eng = Engine(tables, key=jax.random.PRNGKey(3))
    batched = batch_eng.execute_batch([plan(), plan()])
    assert batch_eng.last_batch_stats["stacked_nodes"] >= 1
    for rep in [rep_eager, rep_trace, rep_hit] + [r for _, r in batched]:
        assert _profile(rep)[-1] == DISTINCT_NODE
