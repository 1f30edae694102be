"""Secure multi-party shuffle (MPS) — permutation-composition protocol.

Reflex shuffles the Resizer's output (after noise addition, before
reveal-and-trim) to break linkage between input and output positions (§4.4).

We implement the honest-majority 3-party shuffle in the style of Araki et al. /
Asharov et al. [CCS'22] (the protocol family MP-SPDZ's shuffle also belongs
to): the global permutation is the composition ``pi = pi_2 ∘ pi_1 ∘ pi_0``
where ``pi_j`` is derived from pair key ``j`` and hence known to exactly two
parties; the third party receives freshly re-randomized shares after each hop
and cannot link positions. Since every party is ignorant of at least one
``pi_j``, nobody knows the composed permutation.

Costs (Table 1 of the paper): 3 rounds (constant), each hop moves the whole
table once => ``3 * N * M`` bytes per party for N rows of M bytes. The
computational cost of *applying* a permutation is a row gather — the hot loop
that ``repro.kernels.shuffle_gather`` implements as a Pallas kernel (the
table staged whole in VMEM, one row copy per output row; tables too large for
that stage take the XLA gather); with kernels off it is ``jnp.take``.
"""
from __future__ import annotations

from typing import Dict, Union

import jax
import jax.numpy as jnp

from . import material
from .ledger import fused_scope, log_comm
from .prf import PRFSetup, zero_share_add, zero_share_xor
from .sharing import AShare, BShare

__all__ = [
    "secure_shuffle",
    "inverse_shuffle",
    "apply_secret_perm",
    "composed_permutation",
    "HOPS",
]

HOPS = 3

Share = Union[AShare, BShare]


def _hop_perm(prf: PRFSetup, hop: int, n: int) -> jnp.ndarray:
    """Permutation for hop ``hop`` — derived from pair key ``hop``, i.e. known
    to parties hop and hop+1 only."""
    sub = prf.fold(1000 + hop)

    def compute():
        key = jax.random.wrap_key_data(sub.pair_keys[hop])
        return jax.random.permutation(key, n)

    src = material.active_if_concrete(sub.pair_keys)
    if src is None:
        return compute()
    return src.fetch("perm", sub.pair_keys, (int(hop), int(n)), compute)


def composed_permutation(prf: PRFSetup, n: int) -> jnp.ndarray:
    """The (secret) composed permutation — exposed for tests/oracles only."""
    pi = jnp.arange(n)
    for hop in range(HOPS):
        pi = jnp.take(pi, _hop_perm(prf, hop, n), axis=0)
    return pi


def _rerandomize(col: Share, prf: PRFSetup, tag: int) -> Share:
    p = prf.fold(tag)
    if isinstance(col, AShare):
        return AShare(col.shares + zero_share_add(p, col.shape, col.ring))
    return BShare(col.shares ^ zero_share_xor(p, col.shape, col.ring))


def _row_take():
    """Row gather of a (3, N, ...) share array by a hop permutation: the
    ``shuffle_gather`` kernel when kernels are on (all three shares of a
    column as one (N, 3 * ...) table, one launch), else ``jnp.take``."""
    from ..kernels import kernels_enabled

    if not kernels_enabled():
        return lambda shares, perm: jnp.take(shares, perm, axis=1)
    from ..kernels.shuffle_gather.ops import gather_rows

    def take(shares, perm):
        n = shares.shape[1]
        rows = jnp.moveaxis(shares, 1, 0).reshape(n, -1)
        out = gather_rows(rows, perm).reshape((n, 3) + shares.shape[2:])
        return jnp.moveaxis(out, 0, 1)

    return take


def secure_shuffle(cols: Dict[str, Share], prf: PRFSetup) -> Dict[str, Share]:
    """Shuffle all columns of a table with one hidden common permutation."""
    if not cols:
        return cols
    first = next(iter(cols.values()))
    n = first.shape[0]
    row_bytes = sum(
        c.ring.bytes * (c.size // max(c.shape[0], 1)) for c in cols.values()
    )
    take = _row_take()

    with fused_scope("shuffle", rounds=HOPS):
        out = dict(cols)
        for hop in range(HOPS):
            perm = _hop_perm(prf, hop, n)
            new = {}
            for idx, (name, col) in enumerate(out.items()):
                moved = col.map_shares(lambda s, p=perm: take(s, p))
                new[name] = _rerandomize(moved, prf, 5000 + 17 * hop + idx)
            out = new
            # one resharing hop: the pi_j-ignorant party receives fresh shares
            log_comm("shuffle_hop", 1, n * row_bytes)
    return out


def inverse_shuffle(cols: Dict[str, Share], prf: PRFSetup) -> Dict[str, Share]:
    """Undo ``secure_shuffle(cols, prf)``: apply the hop permutations inverted
    and in reverse order. Same round/byte pattern as the forward shuffle (each
    hop is one table move + resharing); the re-randomization tags differ so
    forward and inverse hops never reuse a zero-sharing.
    """
    if not cols:
        return cols
    first = next(iter(cols.values()))
    n = first.shape[0]
    row_bytes = sum(
        c.ring.bytes * (c.size // max(c.shape[0], 1)) for c in cols.values()
    )
    take = _row_take()

    with fused_scope("shuffle", rounds=HOPS):
        out = dict(cols)
        for hop in reversed(range(HOPS)):
            perm = jnp.argsort(_hop_perm(prf, hop, n))
            new = {}
            for idx, (name, col) in enumerate(out.items()):
                moved = col.map_shares(lambda s, p=perm: take(s, p))
                new[name] = _rerandomize(moved, prf, 5500 + 17 * hop + idx)
            out = new
            log_comm("shuffle_hop", 1, n * row_bytes)
    return out


def apply_secret_perm(
    cols: Dict[str, Share], pi: "BShare", prf: PRFSetup
) -> Dict[str, Share]:
    """Gather rows of ``cols`` by a secret-shared permutation: out_i = cols_{pi(i)}.

    Shuffle-and-reveal (Asharov et al. style): shuffle the shared index vector
    ``pi`` by a hidden permutation sigma, open ``r = pi ∘ sigma`` — a uniformly
    random permutation, so the opening leaks nothing about ``pi`` — gather the
    payload by the public ``r`` (free), then inverse-shuffle the result to peel
    sigma back off. Only sound when ``pi`` is a true permutation of 0..n-1
    (e.g. a sorted row-index column); arbitrary index vectors would leak their
    multiplicity pattern through ``r``.

    Cost: one 1-column shuffle + one n-word reveal + one W-column inverse
    shuffle — O(n) bytes per payload column, vs. O(n log^2 n) for carrying the
    payload through a sorting network.
    """
    from .sharing import reveal_b

    shuffled = secure_shuffle({"__pi": pi}, prf)
    r = reveal_b(shuffled["__pi"])
    moved = {name: col.take(r, axis=0) for name, col in cols.items()}
    return inverse_shuffle(moved, prf)
