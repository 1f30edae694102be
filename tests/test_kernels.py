"""Pallas kernel validation: shape/dtype sweeps against pure-jnp oracles
(interpret mode on CPU; BlockSpecs target TPU v5e VMEM)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bitonic_stage.ops import stage_swap
from repro.kernels.bitonic_stage.ref import bitonic_swap_ref
from repro.kernels.rss_gate.ops import gate
from repro.kernels.rss_gate.ref import rss_gate_ref
from repro.kernels.shuffle_gather.ops import gather_rows

rng = np.random.default_rng(7)


@pytest.mark.parametrize("n", [64, 100, 256, 2048, 4097])
@pytest.mark.parametrize("boolean", [True, False])
def test_rss_gate_sweep(n, boolean):
    xs = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    ys = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    got = np.asarray(gate(xs, ys, al, boolean=boolean))
    want = np.asarray(rss_gate_ref(xs, ys, al, boolean=boolean))
    np.testing.assert_array_equal(got, want)


def test_rss_gate_multidim():
    xs = rng.integers(0, 2**32, (3, 4, 33), dtype=np.uint32)
    ys = rng.integers(0, 2**32, (3, 4, 33), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, 4, 33), dtype=np.uint32)
    got = np.asarray(gate(xs, ys, al, boolean=True))
    np.testing.assert_array_equal(got, np.asarray(rss_gate_ref(xs, ys, al, True)))


def test_rss_gate_broadcast_operands():
    """Broadcast-compatible operands ((3,n,2) x against a (3,n,1) y, the
    shape the segmented (sum,count) scan feeds mul) must align per-lane —
    the flattener used to misalign them silently."""
    xs = rng.integers(0, 2**32, (3, 200, 2), dtype=np.uint32)
    ys = rng.integers(0, 2**32, (3, 200, 1), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, 200, 2), dtype=np.uint32)
    for boolean in (True, False):
        got = np.asarray(gate(xs, ys, al, boolean=boolean))
        want = np.asarray(
            rss_gate_ref(xs, np.broadcast_to(ys, xs.shape), al, boolean)
        )
        np.testing.assert_array_equal(got, want)


def test_rss_gate_preserves_protocol_semantics(prf):
    """Kernel output must be a valid sharing of x*y (sums to the product)."""
    from repro.core.prf import zero_share_add
    from repro.core.ring import RING32

    n = 512
    x = rng.integers(0, 2**16, n, dtype=np.uint32)
    y = rng.integers(0, 2**16, n, dtype=np.uint32)
    from repro.core.sharing import share_a

    xs = share_a(x, jax.random.PRNGKey(0)).shares
    ys = share_a(y, jax.random.PRNGKey(1)).shares
    alpha = zero_share_add(prf, (n,), RING32)
    z = np.asarray(gate(xs, ys, alpha, boolean=False))
    np.testing.assert_array_equal(z[0] + z[1] + z[2], x * y)


@pytest.mark.parametrize("n,c", [(64, 1), (128, 3), (333, 5), (1024, 2)])
def test_shuffle_gather_sweep(n, c):
    t = rng.integers(0, 2**32, (n, c), dtype=np.uint32)
    p = rng.permutation(n).astype(np.int32)
    got = np.asarray(gather_rows(jnp.asarray(t), jnp.asarray(p)))
    np.testing.assert_array_equal(got, t[p])


def test_shuffle_gather_large_falls_back():
    from repro.kernels import launch_counts, reset_launch_counts
    from repro.kernels.shuffle_gather.ops import VMEM_TABLE_BYTES, vmem_table_bytes

    n, c = 70000, 1  # VMEM image (rows x 128 lanes) > cap -> XLA path
    assert vmem_table_bytes(n, c, 4) > VMEM_TABLE_BYTES
    t = rng.integers(0, 2**32, (n, c), dtype=np.uint32)
    p = rng.permutation(n).astype(np.int32)
    reset_launch_counts()
    got = np.asarray(gather_rows(jnp.asarray(t), jnp.asarray(p)))
    np.testing.assert_array_equal(got, t[p])
    assert launch_counts() == {"shuffle_gather_xla": 1}


@pytest.mark.parametrize("n,c", [(128, 1), (512, 4), (100, 3)])
def test_bitonic_stage_sweep(n, c):
    mask = rng.integers(0, 2**32, (3, n), dtype=np.uint32)
    own = rng.integers(0, 2**32, (3, c, n), dtype=np.uint32)
    other = rng.integers(0, 2**32, (3, c, n), dtype=np.uint32)
    al = rng.integers(0, 2**32, (3, c, n), dtype=np.uint32)
    got = np.asarray(stage_swap(mask, own, other, al))
    want = np.asarray(bitonic_swap_ref(mask, own, other, al))
    np.testing.assert_array_equal(got, want)


def test_bitonic_stage_swap_semantics():
    """all-ones mask swaps, all-zero mask keeps (on zero alpha)."""
    n, c = 128, 2
    own = rng.integers(0, 2**32, (3, c, n), dtype=np.uint32)
    other = rng.integers(0, 2**32, (3, c, n), dtype=np.uint32)
    zeros = np.zeros((3, c, n), dtype=np.uint32)
    ones = np.zeros((3, n), dtype=np.uint32)
    ones[0] = 0xFFFFFFFF
    got_swap = np.asarray(stage_swap(ones, own, other, zeros))
    # value(out) = value(own) ^ value(own^other) = value(other)
    v = lambda a: a[0] ^ a[1] ^ a[2]
    np.testing.assert_array_equal(v(got_swap), v(other))
    got_keep = np.asarray(stage_swap(np.zeros((3, n), np.uint32), own, other, zeros))
    np.testing.assert_array_equal(v(got_keep), v(own))
