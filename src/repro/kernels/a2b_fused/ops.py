"""Protocol-level wrappers for the fused share-conversion kernels.

Entry points for ``core/circuits.py`` when ``fusion_enabled()``. Randomness
and ledger parity with the gate-by-gate path are exact (same PRF folds, same
per-gate log entries); see ``ks_prefix/ops.py`` for the rationale.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from .. import interpret_mode, record_launch
from ...core.ledger import fused_scope, log_comm
from ...core.prf import PRFSetup, zero_share_add, zero_share_xor
from ...core.sharing import AShare, BShare
from ..ks_prefix.ops import _flat_pad, _pick_block
from ..ks_prefix.ref import ks_shifts
from .a2b_fused import BLOCK, a2b_kernel, bit2a_kernel


def _ks_add_alphas(prf: PRFSetup, shape, ring, shifts: Tuple[int, ...]):
    """Alpha words of one fused Kogge-Stone adder, in kernel packing order
    [init, lvl0_pg, lvl0_pp, lvl1_pg, ...] — same PRF folds as the unfused
    ``ks_add`` (init gate: fold(11); level d: fold(200 + d))."""
    words = [zero_share_xor(prf.fold(11), shape, ring).reshape(3, 1, -1)]
    for d in shifts:
        a = zero_share_xor(prf.fold(200 + d), (2,) + shape, ring)
        words.append(a.reshape(3, 2, -1))
    return jnp.concatenate(words, axis=1)


def a2b_fused(x: AShare, prf: PRFSetup, width: int) -> BShare:
    """Full arithmetic -> boolean conversion in ONE kernel launch (vs
    2 * (1 + log2 k) gate launches): trivial leg sharing + two chained
    Kogge-Stone adders, all VMEM-resident."""
    ring = x.ring
    shape = x.shape
    shifts = ks_shifts(width)
    levels = width.bit_length() - 1  # ledger round count (matches ks_add)
    lanes = x.size

    al = jnp.concatenate(
        [
            _ks_add_alphas(prf.fold(31), shape, ring, shifts),
            _ks_add_alphas(prf.fold(32), shape, ring, shifts),
        ],
        axis=1,
    )

    xs = x.shares.reshape(3, -1)
    n = xs.shape[1]
    if n == 0:  # pallas_call cannot slice 0-lane operands
        from .ref import a2b_ref

        out = a2b_ref(xs, al, shifts)
    else:
        block = _pick_block(n, BLOCK)
        xs, al = _flat_pad([xs, al], n, block)
        interpret = interpret_mode(xs.dtype)
        record_launch("a2b_fused")
        out = a2b_kernel(xs, al, shifts, interpret=interpret, block=block)
    # Ledger: identical to the two unfused ks_add invocations.
    for _ in range(2):
        with fused_scope("ks_add", rounds=1 + levels):
            log_comm("and", 1, lanes * ring.bytes)
            for _d in shifts:
                log_comm("and", 1, 2 * lanes * ring.bytes)
    return BShare(out[:, :n].reshape((3,) + shape))


def bit2a_fused(b: BShare, prf: PRFSetup) -> AShare:
    """Both dependent ring multiplications of the bit injection in ONE
    launch (vs 2 ``rss_gate`` dispatches)."""
    ring = b.ring
    shape = b.shape
    lanes = b.size

    al = jnp.stack(
        [
            zero_share_add(prf.fold(21), shape, ring).reshape(3, -1),
            zero_share_add(prf.fold(22), shape, ring).reshape(3, -1),
        ],
        axis=1,
    )

    bs = b.shares.reshape(3, -1)
    n = bs.shape[1]
    if n == 0:
        from .ref import bit2a_ref

        out = bit2a_ref(bs, al)
    else:
        block = _pick_block(n, BLOCK)
        bs, al = _flat_pad([bs, al], n, block)
        interpret = interpret_mode(bs.dtype)
        record_launch("bit2a_fused")
        out = bit2a_kernel(bs, al, interpret=interpret, block=block)
    for _ in range(2):
        log_comm("mul", 1, lanes * ring.bytes)
    return AShare(out[:, :n].reshape((3,) + shape))
