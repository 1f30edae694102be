"""Every Pallas kernel of the main path, compiled by the TPU compiler for a
described TPU v5e (``v5e:2x2``) at real widths — no chip needed, nothing
runs. This is what interpret mode cannot show: Mosaic refusing a load, a
layout, or more VMEM than a kernel may use."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.a2b_fused.a2b_fused import a2b_kernel, bit2a_kernel
from repro.kernels.bitonic_stage.bitonic_stage import bitonic_swap
from repro.kernels.ks_prefix.ks_prefix import and_fold, ks_prefix
from repro.kernels.ks_prefix.ref import fold_shifts, ks_shifts
from repro.kernels.rss_gate.rss_gate import rss_gate
from repro.kernels.shuffle_gather.ops import VMEM_TABLE_BYTES, vmem_table_bytes
from repro.kernels.shuffle_gather.shuffle_gather import shuffle_gather

LANES = 1 << 20  # a 2^20-row table's share lanes
WIDTH = 32  # the 32-bit ring
SHIFTS = ks_shifts(WIDTH)
FOLDS = fold_shifts(WIDTH)
# the shuffle's largest whole-table stage: one column's three shares, as many
# rows as the VMEM cap admits
GATHER_ROWS = 8 * VMEM_TABLE_BYTES // vmem_table_bytes(8, 3, 4)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("boolean", [True, False], ids=["bool", "arith"])
def test_rss_gate_compiles(one_chip, boolean):
    s = _spec(one_chip, (3, LANES))
    _compile(lambda x, y, a: rss_gate(x, y, a, boolean=boolean, interpret=False), s, s, s)


def test_ks_prefix_compiles(one_chip):
    s = _spec(one_chip, (3, LANES))
    al = _spec(one_chip, (3, 2 * len(SHIFTS), LANES))
    _compile(lambda g, p, a: ks_prefix(g, p, a, SHIFTS, interpret=False), s, s, al)


def test_and_fold_compiles(one_chip):
    s = _spec(one_chip, (3, LANES))
    al = _spec(one_chip, (3, len(FOLDS), LANES))
    _compile(lambda v, a: and_fold(v, a, FOLDS, interpret=False), s, al)


def test_a2b_compiles(one_chip):
    s = _spec(one_chip, (3, LANES))
    al = _spec(one_chip, (3, 2 * (1 + 2 * len(SHIFTS)), LANES))
    _compile(lambda x, a: a2b_kernel(x, a, SHIFTS, interpret=False), s, al)


def test_bit2a_compiles(one_chip):
    s = _spec(one_chip, (3, LANES))
    al = _spec(one_chip, (3, 2, LANES))
    _compile(lambda b, a: bit2a_kernel(b, a, interpret=False), s, al)


@pytest.mark.parametrize("cols", range(1, 9))
def test_bitonic_swap_compiles(one_chip, cols):
    mask = _spec(one_chip, (3, LANES))
    c = _spec(one_chip, (3, cols, LANES))
    _compile(
        lambda m, o, t, a: bitonic_swap(m, o, t, a, interpret=False), mask, c, c, c
    )


def test_shuffle_gather_compiles_at_vmem_cap(one_chip):
    assert vmem_table_bytes(GATHER_ROWS, 3, 4) == VMEM_TABLE_BYTES
    table = _spec(one_chip, (GATHER_ROWS, 3))
    perm = _spec(one_chip, (GATHER_ROWS,), jnp.int32)
    _compile(lambda t, p: shuffle_gather(t, p, interpret=False), table, perm)


@pytest.mark.parametrize(
    "rows,cols,n_keys",
    [(1 << 17, 3, 2), (1 << 21, 2, 1)],
    ids=["join-2^17", "distinct-2^21"],
)
def test_bitonic_network_compiles_looped(one_chip, rows, cols, n_keys):
    """The study's largest sort networks (a two-key join union, a Distinct
    over the padded join output) compile for the chip as one program on the
    default gate path, with the stage body in one loop and no gather."""
    from repro.core.sort import bitonic_network

    compiled = bitonic_network.lower(
        _spec(one_chip, (3, cols, rows)),
        _spec(one_chip, (3, 2)),
        n_keys=n_keys,
        descending=False,
        kernels=False,
        fusion=False,
    ).compile()
    text = compiled.as_text()
    assert "while" in text and "gather" not in text
