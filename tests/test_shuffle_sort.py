"""Tests: secure shuffle (linkage, multiset, comm) and bitonic sort."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ledger import measure_comm
from repro.core.prf import setup_prf
from repro.core.sharing import reveal_b, share_b
from repro.core.shuffle import composed_permutation, secure_shuffle
from repro.core.sort import (
    bitonic_network,
    bitonic_sort,
    bitonic_sort_narrow,
    sort_valid_first,
)

PRF = setup_prf(jax.random.PRNGKey(2))
rng = np.random.default_rng(2)


def _cols(n, seed=0):
    k = rng.integers(0, 1000, n).astype(np.uint32)
    p = rng.integers(0, 2**32, n, dtype=np.uint32)
    return k, p, {
        "k": share_b(k, jax.random.PRNGKey(seed)),
        "p": share_b(p, jax.random.PRNGKey(seed + 1)),
    }


def test_shuffle_applies_hidden_common_permutation():
    n = 64
    k, p, cols = _cols(n)
    out = secure_shuffle(cols, PRF)
    ko, po = np.asarray(reveal_b(out["k"])), np.asarray(reveal_b(out["p"]))
    pi = np.asarray(composed_permutation(PRF, n))
    assert (ko == k[pi]).all() and (po == p[pi]).all()


def test_shuffle_rerandomizes_shares():
    n = 32
    k, p, cols = _cols(n)
    out = secure_shuffle(cols, PRF)
    pi = np.asarray(composed_permutation(PRF, n))
    # values moved, but every share leg must be freshly masked (not a pure
    # permutation of the old legs — otherwise parties could link rows)
    old = np.asarray(cols["k"].shares[0])
    new = np.asarray(out["k"].shares[0])
    assert not np.array_equal(np.sort(old), np.sort(new))


def test_shuffle_comm_is_constant_rounds_linear_bytes():
    for n in (64, 128):
        _, _, cols = _cols(n)
        c = measure_comm(lambda cc: secure_shuffle(cc, PRF), cols)
        assert c["rounds"] == 3
        assert c["bytes_per_party"] == 3 * n * 8  # 2 cols x 4B x 3 hops


def test_bitonic_sort_matches_numpy():
    n = 256
    k, p, cols = _cols(n)
    out = bitonic_sort(cols, "k", PRF)
    ks = np.asarray(reveal_b(out["k"]))
    ps = np.asarray(reveal_b(out["p"]))
    assert (ks == np.sort(k)).all()
    assert sorted(zip(ks.tolist(), ps.tolist())) == sorted(zip(k.tolist(), p.tolist()))


def _opened_rows(out, names):
    return list(zip(*(np.asarray(reveal_b(out[nm])).tolist() for nm in names)))


@pytest.mark.parametrize("n", (2, 4, 32, 1024))
@pytest.mark.parametrize("n_keys", (1, 2))
@pytest.mark.parametrize("descending", (False, True), ids=("asc", "desc"))
def test_bitonic_network_sorts_numpy_order(n, n_keys, descending):
    """Revealed keys come out in numpy's (lexicographic) order, and the
    revealed (key, payload) rows are the input's rows as a multiset: the
    network is not stable, so rows with equal keys may come in any order."""
    keys = [f"k{i}" for i in range(n_keys)]
    plain = {nm: rng.integers(0, 4, n).astype(np.uint32) for nm in keys}
    plain["p"] = rng.integers(0, 2**32, n, dtype=np.uint32)
    cols = {nm: share_b(v, jax.random.PRNGKey(i)) for i, (nm, v) in enumerate(plain.items())}
    out = bitonic_sort(cols, keys if n_keys > 1 else keys[0], PRF, descending)
    order = np.lexsort([plain[nm] for nm in reversed(keys)])
    if descending:
        order = order[::-1]
    want_keys = list(zip(*(plain[nm][order].tolist() for nm in keys)))
    assert _opened_rows(out, keys) == want_keys
    names = list(plain)
    rows_in = list(zip(*(plain[nm].tolist() for nm in names)))
    assert sorted(_opened_rows(out, names)) == sorted(rows_in)
    assert list(out) == names


@pytest.mark.parametrize("n", (2, 32, 1024))
@pytest.mark.parametrize("n_payload", (0, 1, 3))
def test_bitonic_sort_narrow_keeps_rows(n, n_payload):
    """The narrowed sort (key and row index in the network, payload moved
    by the sorted index) and its full-network fallback both give numpy's
    key order and the input's rows."""
    plain = {"k": rng.integers(0, 8, n).astype(np.uint32)}
    for i in range(n_payload):
        plain[f"p{i}"] = rng.integers(0, 2**32, n, dtype=np.uint32)
    cols = {
        nm: share_b(v, jax.random.PRNGKey(20 + i)) for i, (nm, v) in enumerate(plain.items())
    }
    out = bitonic_sort_narrow(cols, "k", PRF)
    assert [r[0] for r in _opened_rows(out, ["k"])] == np.sort(plain["k"]).tolist()
    names = list(plain)
    rows_in = list(zip(*(plain[nm].tolist() for nm in names)))
    assert sorted(_opened_rows(out, names)) == sorted(rows_in)


def test_bitonic_network_program_holds_one_stage_body():
    """The network's program is one stage body in a loop, whatever the
    stage count: 21 stages (2^6 rows) and 78 stages (2^12 rows) lower to
    texts within 1.25x of each other, and neither holds a gather (partner
    lanes come by roll)."""
    keys = jax.ShapeDtypeStruct((3, 2), jnp.uint32)
    texts = [
        bitonic_network.lower(
            jax.ShapeDtypeStruct((3, 2, 1 << m), jnp.uint32), keys,
            n_keys=1, descending=False, kernels=False, fusion=False,
        ).as_text()
        for m in (6, 12)
    ]
    small, large = sorted(len(t) for t in texts)
    assert large <= 1.25 * small
    assert not any("gather" in t for t in texts)


def test_bitonic_sort_descending():
    n = 64
    k, _, cols = _cols(n)
    out = bitonic_sort(cols, "k", PRF, descending=True)
    ks = np.asarray(reveal_b(out["k"]))
    assert (ks == np.sort(k)[::-1]).all()


def test_sort_valid_first():
    n = 128
    v = (rng.random(n) < 0.4).astype(np.uint32)
    cols = {"v": share_b(v, jax.random.PRNGKey(5))}
    out = sort_valid_first(cols, "v", PRF)
    vo = np.asarray(reveal_b(out["v"]))
    t = int(v.sum())
    assert (vo[:t] == 1).all() and (vo[t:] == 0).all()
