"""Parity tests for the single-launch fused circuit kernels (DESIGN.md §7.1).

Three layers of guarantees:

* kernel vs jnp oracle (`ref.py`) — raw array semantics;
* fused vs gate-by-gate circuit path — *bit-identical* shares (same PRF
  folds) and *bit-identical* ledger tallies (comm is protocol-determined,
  not launch-determined), across widths and both rings;
* launch accounting — the fused paths must cut kernel dispatches >= 3x for
  ``lt_public`` and ``a2b`` (the ISSUE's acceptance bar; actual: 5x / 12x).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.circuits import (
    a2b,
    b2a,
    bit2a,
    eq,
    eq_public,
    ks_add,
    lt,
    lt_public,
)
from repro.core.ledger import measure_comm
from repro.core.prf import setup_prf, zero_share_xor
from repro.core.ring import RING32
from repro.core.sharing import reveal_a, reveal_b, share_a, share_b
from repro.kernels import (
    launch_counts,
    override_fusion,
    override_kernels,
    reset_launch_counts,
    total_launches,
)

PRF = setup_prf(jax.random.PRNGKey(5))
rng = np.random.default_rng(5)

WIDTHS = [8, 16, 32]


def _vals(width, n=96):
    x = rng.integers(0, 1 << width, n).astype(np.uint32)
    y = rng.integers(0, 1 << width, n).astype(np.uint32)
    y[: n // 3] = x[: n // 3]
    return x, y


def _run(fn, fused: bool):
    if fused:
        with override_kernels(True), override_fusion(True):
            return fn()
    with override_kernels(False):
        return fn()


def _assert_bit_identical(fn):
    f, u = _run(fn, True), _run(fn, False)
    np.testing.assert_array_equal(np.asarray(f.shares), np.asarray(u.shares))
    return f


@pytest.mark.parametrize("width", WIDTHS)
def test_comparisons_fused_parity(width):
    x, y = _vals(width)
    xb = share_b(x, jax.random.PRNGKey(1))
    yb = share_b(y, jax.random.PRNGKey(2))
    c = int(rng.integers(0, 1 << width))

    got = _assert_bit_identical(lambda: lt_public(xb, c, PRF, width=width))
    assert (np.asarray(reveal_b(got)) == (x < c)).all()

    got = _assert_bit_identical(lambda: eq(xb, yb, PRF, width=width))
    assert (np.asarray(reveal_b(got)) == (x == y)).all()

    got = _assert_bit_identical(lambda: eq_public(xb, c, PRF, width=width))
    assert (np.asarray(reveal_b(got)) == (x == c)).all()

    got = _assert_bit_identical(lambda: lt(xb, yb, PRF, width=width))
    # borrow-out of width-bit x - y == unsigned x < y on width-bit values
    assert (np.asarray(reveal_b(got)) == (x < y)).all()


@pytest.mark.parametrize("width", WIDTHS)
def test_conversions_fused_parity(width):
    x, y = _vals(width)
    xb = share_b(x, jax.random.PRNGKey(3))
    yb = share_b(y, jax.random.PRNGKey(4))
    xa = share_a(x, jax.random.PRNGKey(5))
    mask = (1 << width) - 1

    got = _assert_bit_identical(lambda: ks_add(xb, yb, PRF, width=width))
    assert (np.asarray(reveal_b(got)) & mask == ((x + y) & mask)).all()

    got = _assert_bit_identical(lambda: a2b(xa, PRF, width=width))
    if width == 32:
        assert (np.asarray(reveal_b(got)) == x).all()

    got = _assert_bit_identical(lambda: b2a(xb, PRF, width=width))
    if width == 32:
        assert (np.asarray(reveal_a(got)) == x).all()

    bits = (x & 1).astype(np.uint32)
    bb = share_b(bits, jax.random.PRNGKey(6))
    got = _assert_bit_identical(lambda: bit2a(bb, PRF))
    assert (np.asarray(reveal_a(got)) == bits).all()


def test_fused_parity_nonpow2_width_and_multidim():
    """The Resizer's a2b runs at width 18; b2a stacks (n, k) planes."""
    x = rng.integers(0, 1 << 18, 64).astype(np.uint32)
    xa = share_a(x, jax.random.PRNGKey(7))
    _assert_bit_identical(lambda: a2b(xa, PRF, width=18))

    xm = rng.integers(0, 2**32, (4, 33), dtype=np.uint32)
    xmb = share_b(xm, jax.random.PRNGKey(8))
    got = _assert_bit_identical(lambda: eq(xmb, xmb, PRF))
    assert (np.asarray(reveal_b(got)) == 1).all()


@pytest.mark.parametrize("width", WIDTHS)
def test_ledger_tallies_identical(width):
    """(rounds, bytes/party) must not depend on the execution path."""
    x, y = _vals(width, 32)
    xb = share_b(x, jax.random.PRNGKey(1))
    yb = share_b(y, jax.random.PRNGKey(2))
    xa = share_a(x, jax.random.PRNGKey(3))
    cases = [
        lambda: lt_public(xb, 5, PRF, width=width),
        lambda: lt(xb, yb, PRF, width=width),
        lambda: eq(xb, yb, PRF, width=width),
        lambda: ks_add(xb, yb, PRF, width=width),
        lambda: a2b(xa, PRF, width=width),
        lambda: b2a(xb, PRF, width=width),
        lambda: bit2a(xb, PRF),
    ]
    for fn in cases:
        tf = _run(lambda: measure_comm(lambda: fn()), True)
        tu = _run(lambda: measure_comm(lambda: fn()), False)
        assert tf == tu


def test_launch_reduction():
    """Acceptance bar: >= 3x fewer kernel launches for lt_public and a2b."""
    x, _ = _vals(32, 256)
    xb = share_b(x, jax.random.PRNGKey(1))
    xa = share_a(x, jax.random.PRNGKey(2))
    for fn, fused_kind in [
        (lambda: lt_public(xb, 7, PRF), "ks_prefix"),
        (lambda: a2b(xa, PRF), "a2b_fused"),
    ]:
        with override_kernels(True), override_fusion(True):
            reset_launch_counts()
            fn()
            fused_n = total_launches()
            assert launch_counts() == {fused_kind: 1}
        with override_kernels(True), override_fusion(False):
            reset_launch_counts()
            fn()
            unfused_n = total_launches()
        assert fused_n == 1
        assert unfused_n >= 3 * fused_n


def test_b2a_halves_launches():
    x, _ = _vals(32, 64)
    xb = share_b(x, jax.random.PRNGKey(1))
    with override_kernels(True), override_fusion(True):
        reset_launch_counts()
        b2a(xb, PRF)
        assert launch_counts() == {"bit2a_fused": 1}
    with override_kernels(True), override_fusion(False):
        reset_launch_counts()
        b2a(xb, PRF)
        assert launch_counts() == {"rss_gate": 2}


# -- kernel vs jnp oracle -----------------------------------------------------

@pytest.mark.parametrize("n", [128, 333, 2048, 4097])
def test_ks_prefix_kernel_vs_ref(n):
    from repro.kernels.ks_prefix.ks_prefix import ks_prefix
    from repro.kernels.ks_prefix.ref import ks_prefix_ref, ks_shifts

    shifts = ks_shifts(32)
    g = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    p = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, 2 * len(shifts), n), dtype=np.uint32)
    pad = (-n) % 128
    pd = lambda a: np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    got = np.asarray(
        ks_prefix(pd(g), pd(p), pd(al), shifts, block=128)
    )[:, :n]
    np.testing.assert_array_equal(got, np.asarray(ks_prefix_ref(g, p, al, shifts)))


@pytest.mark.parametrize("width", WIDTHS)
def test_and_fold_kernel_vs_ref(width):
    from repro.kernels.ks_prefix.ks_prefix import and_fold
    from repro.kernels.ks_prefix.ref import and_fold_ref, fold_shifts

    n = 256
    shifts = fold_shifts(width)
    v = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, len(shifts), n), dtype=np.uint32)
    got = np.asarray(and_fold(v, al, shifts, block=256))
    np.testing.assert_array_equal(got, np.asarray(and_fold_ref(v, al, shifts)))


@pytest.mark.parametrize("width", WIDTHS)
def test_a2b_kernel_vs_ref(width):
    from repro.kernels.a2b_fused.a2b_fused import a2b_kernel
    from repro.kernels.a2b_fused.ref import a2b_ref
    from repro.kernels.ks_prefix.ref import ks_shifts

    n = 256
    shifts = ks_shifts(width)
    xs = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, 2 * (1 + 2 * len(shifts)), n), dtype=np.uint32)
    got = np.asarray(a2b_kernel(xs, al, shifts, block=256))
    np.testing.assert_array_equal(got, np.asarray(a2b_ref(xs, al, shifts)))


def test_bit2a_kernel_vs_ref():
    from repro.kernels.a2b_fused.a2b_fused import bit2a_kernel
    from repro.kernels.a2b_fused.ref import bit2a_ref

    n = 512
    bs = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, 2, n), dtype=np.uint32)
    got = np.asarray(bit2a_kernel(bs, al, block=512))
    np.testing.assert_array_equal(got, np.asarray(bit2a_ref(bs, al)))


def test_fused_output_is_valid_sharing():
    """Protocol invariant: the fused a2b output XORs to the plaintext and is
    re-randomized by the same zero-sharings as the unfused path."""
    x = rng.integers(0, 2**32, 200, dtype=np.uint32)
    xa = share_a(x, jax.random.PRNGKey(9))
    with override_kernels(True), override_fusion(True):
        out = a2b(xa, PRF)
    v = np.asarray(out.shares)
    np.testing.assert_array_equal(v[0] ^ v[1] ^ v[2], x)


RING64_SCRIPT = textwrap.dedent(
    """
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.core.circuits import a2b, ks_add, lt_public
    from repro.core.prf import setup_prf
    from repro.core.ring import RING64
    from repro.core.sharing import reveal_b, share_a, share_b
    from repro.kernels import override_fusion, override_kernels

    prf = setup_prf(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    x = rng.integers(0, 1 << 63, 64, dtype=np.uint64)
    y = rng.integers(0, 1 << 63, 64, dtype=np.uint64)
    xb = share_b(x, jax.random.PRNGKey(2), ring=RING64)
    yb = share_b(y, jax.random.PRNGKey(3), ring=RING64)
    xa = share_a(x, jax.random.PRNGKey(4), ring=RING64)
    c = int(rng.integers(0, 1 << 63))

    def run(fn, fused):
        if fused:
            with override_kernels(True), override_fusion(True):
                return fn()
        with override_kernels(False):
            return fn()

    for fn, want in [
        (lambda: lt_public(xb, c, prf), x < c),
        (lambda: ks_add(xb, yb, prf), x + y),
        (lambda: a2b(xa, prf), x),
    ]:
        f, u = run(fn, True), run(fn, False)
        assert np.array_equal(np.asarray(f.shares), np.asarray(u.shares))
        assert np.array_equal(np.asarray(reveal_b(f)), want)
    print("ring64 parity OK")
    """
)


def test_fused_parity_ring64_subprocess():
    """64-bit ring needs jax_enable_x64, which must be set before any array
    is created — run in a clean interpreter."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", RING64_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ring64 parity OK" in proc.stdout


def test_share_parity_uses_matching_randomness():
    """Sanity: the bit-identity above is meaningful — the fused path's alphas
    really are the unfused folds (a different fold must change the shares)."""
    shape = (16,)
    a1 = np.asarray(zero_share_xor(PRF.fold(101), shape, RING32))
    a2 = np.asarray(zero_share_xor(PRF.fold(102), shape, RING32))
    assert not np.array_equal(a1, a2)


# -----------------------------------------------------------------------------
# Sort and shuffle through their kernels: bit-identical shares, ledger and
# exchanged payloads
# -----------------------------------------------------------------------------


def _payload_ledger():
    """A CommLedger that also keeps every sync point's payload bytes (what a
    networked party ships and checks where the point is not fused)."""
    from repro.core.ledger import CommLedger

    class PayloadLedger(CommLedger):
        def __init__(self):
            super().__init__()
            self.payloads = []

        def log(self, op, rounds, bytes_per_party, payload=None):
            body = None if payload is None else np.asarray(payload).tobytes()
            self.payloads.append((op, int(bytes_per_party), body))
            super().log(op, rounds, bytes_per_party, payload)

    return PayloadLedger()


@pytest.mark.parametrize("key_cols", [("k",), ("k", "j")], ids=["1key", "2key"])
def test_bitonic_sort_stage_kernel_parity(key_cols):
    from repro.core.sort import bitonic_sort

    n = 64
    cols = {
        name: share_b(rng.integers(0, 8, n).astype(np.uint32), jax.random.PRNGKey(i))
        for i, name in enumerate(("k", "j", "v"))
    }

    def sort():
        with _payload_ledger() as led:
            out = bitonic_sort(cols, list(key_cols), PRF)
        return out, led.by_op(), led.payloads

    reset_launch_counts()
    (f, f_led, f_pay), (u, u_led, u_pay) = _run(sort, True), _run(sort, False)
    assert launch_counts()["bitonic_stage"] == 6 * 7 // 2  # one per stage
    # a cached network program counts its stages the same as a fresh trace
    _run(sort, True)
    assert launch_counts()["bitonic_stage"] == 2 * 6 * 7 // 2
    for name in cols:
        np.testing.assert_array_equal(np.asarray(f[name].shares), np.asarray(u[name].shares))
    assert f_led == u_led
    # the network is one program: its stages' messages ride one payload-less
    # sort entry, 21 stages of the lt and the 3-column select words
    k = len(key_cols)
    words = 11 * k + 7 * (k - 1) + 3
    assert f_pay == u_pay == [("bitonic_sort", 21 * words * n * 4, None)]


def test_secure_shuffle_gather_kernel_parity():
    from repro.core.shuffle import HOPS, inverse_shuffle, secure_shuffle

    n = 100
    cols = {
        "a": share_a(rng.integers(0, 1 << 20, n).astype(np.uint32), jax.random.PRNGKey(7)),
        "b": share_b(rng.integers(0, 1 << 20, (n, 2)).astype(np.uint32), jax.random.PRNGKey(8)),
    }

    def round_trip():
        fwd = secure_shuffle(cols, PRF)
        return fwd, inverse_shuffle(fwd, PRF)

    reset_launch_counts()
    (f_fwd, f_back), (u_fwd, u_back) = _run(round_trip, True), _run(round_trip, False)
    # one launch per column per hop, all three shares at once
    assert launch_counts() == {"shuffle_gather": 2 * HOPS * len(cols)}
    for name in cols:
        np.testing.assert_array_equal(np.asarray(f_fwd[name].shares), np.asarray(u_fwd[name].shares))
        np.testing.assert_array_equal(
            np.asarray(f_back[name].shares), np.asarray(u_back[name].shares)
        )
