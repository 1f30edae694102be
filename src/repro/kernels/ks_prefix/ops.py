"""Protocol-level wrappers for the fused Kogge-Stone / AND-fold kernels.

These are the entry points ``core/circuits.py`` routes through when
``fusion_enabled()``. They own three responsibilities the raw kernels do not:

* **randomness parity** — the per-level zero-sharings are derived with the
  *same* PRF folds as the gate-by-gate path (``prf.fold(base + d)`` per level,
  ``(2,) + lane_shape`` draws for the batched AND pairs), so fused and unfused
  outputs are bit-identical, not merely semantically equal;
* **ledger parity** — each level logs the same ``("and", 1 round, bytes)``
  entry the unfused ``and_`` calls would have logged: communication cost is
  protocol-determined, not launch-determined;
* **shape plumbing** — arbitrary lane shapes are flattened and padded to the
  block size, mirroring ``rss_gate.ops.gate``.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from .. import interpret_mode, record_launch
from ...core.ledger import log_comm
from ...core.prf import PRFSetup, zero_share_xor
from ...core.sharing import BShare
from .ks_prefix import BLOCK, and_fold, ks_prefix
from .ref import fold_shifts, ks_shifts


def _pick_block(n: int, block: int) -> int:
    return min(block, max(128, 1 << (n - 1).bit_length()))


def _flat_pad(arrs, n: int, block: int):
    pad = (-n) % block
    if not pad:
        return arrs
    return [jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),)) for a in arrs]


def ks_levels_fused(
    g: BShare, p: BShare, prf: PRFSetup, width: int, fold_base: int
) -> BShare:
    """All Kogge-Stone levels of ``circuits._ks_levels`` in one kernel launch."""
    ring = g.ring
    shape = g.shape
    shifts: Tuple[int, ...] = ks_shifts(width)
    lanes = g.size

    # Same draws as the unfused _and_pair path: one (2, *lane_shape) XOR
    # zero-sharing per level, alpha[:, 0] for the pg gate, alpha[:, 1] for pp.
    alphas = [
        zero_share_xor(prf.fold(fold_base + d), (2,) + shape, ring) for d in shifts
    ]
    al = jnp.concatenate([a.reshape(3, 2, -1) for a in alphas], axis=1)

    gs = g.shares.reshape(3, -1)
    ps = p.shares.reshape(3, -1)
    n = gs.shape[1]
    if n == 0:  # pallas_call cannot slice 0-lane operands
        from .ref import ks_prefix_ref

        out = ks_prefix_ref(gs, ps, al, shifts)
    else:
        block = _pick_block(n, BLOCK)
        gs, ps, al = _flat_pad([gs, ps, al], n, block)
        interpret = interpret_mode(gs.dtype)
        record_launch("ks_prefix")
        out = ks_prefix(gs, ps, al, shifts, interpret=interpret, block=block)
    for _ in shifts:
        log_comm("and", 1, 2 * lanes * ring.bytes)
    return BShare(out[:, :n].reshape((3,) + shape))


def and_fold_fused(v: BShare, prf: PRFSetup, width: int) -> BShare:
    """The equality AND-reduce tree of ``circuits._and_reduce_bits`` in one
    kernel launch (caller still masks the LSB)."""
    ring = v.ring
    shape = v.shape
    shifts: Tuple[int, ...] = fold_shifts(width)
    lanes = v.size

    alphas = [zero_share_xor(prf.fold(d), shape, ring) for d in shifts]
    al = jnp.stack([a.reshape(3, -1) for a in alphas], axis=1)

    vs = v.shares.reshape(3, -1)
    n = vs.shape[1]
    if n == 0:
        from .ref import and_fold_ref

        out = and_fold_ref(vs, al, shifts)
    else:
        block = _pick_block(n, BLOCK)
        vs, al = _flat_pad([vs, al], n, block)
        interpret = interpret_mode(vs.dtype)
        record_launch("and_fold")
        out = and_fold(vs, al, shifts, interpret=interpret, block=block)
    for _ in shifts:
        log_comm("and", 1, lanes * ring.bytes)
    return BShare(out[:, :n].reshape((3,) + shape))
