"""Oblivious bitonic sorting network over secret-shared tables.

Used by: OrderBy, GroupBy (sort as pre-pass), Distinct, and the Shrinkwrap
"sort&cut" baseline that Reflex compares against (sort valid tuples to the
front, then cut at the DP size).

A bitonic network on N = 2^m rows has m(m+1)/2 compare-exchange stages; each
stage costs one oblivious ``lt`` over N lanes (6 rounds, 11 AND-words) plus one
oblivious select per payload column (1 AND-word). Total rounds
O(log^2 N) — vs. the shuffle's O(1), which is exactly the paper's argument for
replacing Shrinkwrap's sort with a shuffle (Fig. 5a / Fig. 8).

The per-stage compare-exchange is the compute hot spot; with fused kernels on,
its conditional swap runs as one ``repro.kernels.bitonic_stage`` launch, with
this module's gate-by-gate path as the oracle.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Union

import jax
import jax.numpy as jnp

from ..kernels import fusion_enabled
from .circuits import and_bit, eq, lt, or_bit
from .ledger import active_ledger, log_comm
from .prf import PRFSetup, zero_share_xor
from .sharing import AShare, BShare, and_, const_b

__all__ = [
    "bitonic_sort",
    "bitonic_sort_narrow",
    "bitonic_stages",
    "sort_valid_first",
]

Share = Union[AShare, BShare]


def bitonic_stages(n: int):
    """Yield (k, j) for the standard iterative bitonic network on n = 2^m."""
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            yield k, j
            j //= 2
        k *= 2


def _lex_lt(
    his: List[BShare], los: List[BShare], prf: PRFSetup
) -> BShare:
    """Lexicographic ``his < los`` over parallel key columns: column 0
    decides unless it ties, in which case column 1 decides, and so on —
    lt_0 OR (eq_0 AND lt_1) OR (eq_0 AND eq_1 AND lt_2) ...

    All columns' lt circuits (and all tie eq circuits) are independent, so
    they run as one batched call each — the rounds the ledger already models;
    only the shallow combine chain stays sequential."""
    if len(his) == 1:
        return lt(his[0], los[0], prf.fold(0))
    h = BShare(jnp.stack([c.shares for c in his], axis=1))  # (3, K, n)
    lo = BShare(jnp.stack([c.shares for c in los], axis=1))
    lts = lt(h, lo, prf.fold(0))
    eqs = eq(BShare(h.shares[:, :-1]), BShare(lo.shares[:, :-1]), prf.fold(6))
    res = BShare(lts.shares[:, 0])
    ties = None
    for i in range(1, len(his)):
        p = prf.fold(i)
        e = BShare(eqs.shares[:, i - 1])
        ties = e if ties is None else and_bit(ties, e, p.fold(2))
        lt_i = BShare(lts.shares[:, i])
        res = or_bit(res, and_bit(ties, lt_i, p.fold(4)), p.fold(5))
    return res


def _stage(
    cols: Dict[str, BShare],
    key_cols: Sequence[str],
    k: int,
    j: int,
    prf: PRFSetup,
    descending: bool,
) -> Dict[str, BShare]:
    keyb = cols[key_cols[0]]
    n = keyb.shape[0]
    idx = jnp.arange(n)
    partner = idx ^ j
    is_lo = idx < partner  # public lane predicate
    asc = (idx & k) == 0  # public direction per pair (bit k equal for both)
    if descending:
        asc = ~asc

    # lo/hi views on public masks (local): lo = value at the lower lane index
    def lo_hi(col: BShare):
        a = col  # own value
        b = col.take(partner, axis=0)  # partner value
        return (
            BShare(jnp.where(is_lo, a.shares, b.shares)),
            BShare(jnp.where(is_lo, b.shares, a.shares)),
        )

    los, his = zip(*(lo_hi(cols[kc]) for kc in key_cols))
    # swap decision, identical at both lanes of the pair (ties don't swap)
    p = prf.fold(7 * k + j)
    if len(key_cols) == 1:
        s = lt(his[0], los[0], p)  # hi < lo -> out of order (asc)
    else:
        s = _lex_lt(list(his), list(los), p)
    # descending pairs invert the decision (local XOR with a public bit)
    s = s.xor_public(jnp.where(asc, 0, 1).astype(s.ring.dtype))
    mask = s.lsb_mask()

    # conditional swap of every column in one batched AND (per-column selects
    # are independent; same words, one dispatch)
    names = list(cols)
    own = BShare(jnp.stack([cols[nm].shares for nm in names], axis=1))  # (3,C,n)
    other = own.take(partner, axis=1)
    p_swap = prf.fold(9000 + 31 * k + 7 * j)
    if fusion_enabled():
        # the same AND (same alpha, same ledger entry) with the select's
        # XORs fused around it: one bitonic_stage launch
        from ..kernels.bitonic_stage.ops import stage_swap

        alpha = zero_share_xor(p_swap, own.shape, own.ring)
        new = BShare(stage_swap(mask.shares, own.shares, other.shares, alpha))
        # the AND's output is what the parties exchange
        log_comm(
            "and", 1, own.size * own.ring.bytes, payload=new.shares ^ own.shares
        )
    else:
        m3 = BShare(jnp.broadcast_to(mask.shares[:, None, :], own.shares.shape))
        new = own ^ and_(m3, own ^ other, p_swap)
    return {nm: BShare(new.shares[:, i]) for i, nm in enumerate(names)}


def bitonic_sort(
    cols: Dict[str, BShare],
    key_col: Union[str, Sequence[str]],
    prf: PRFSetup,
    descending: bool = False,
) -> Dict[str, BShare]:
    """Sort all columns by ``key_col`` (32-bit unsigned order) — a single
    column name or a sequence of names compared lexicographically (composite
    GROUP BY keys). N must be a power of two (the engine's bucketing
    guarantees this)."""
    key_cols = [key_col] if isinstance(key_col, str) else list(key_col)
    n = next(iter(cols.values())).shape[0]
    if n & (n - 1):
        raise ValueError(f"bitonic_sort requires power-of-two rows, got {n}")
    m = int(math.log2(n))
    led = active_ledger()
    import contextlib

    from ..obs import trace as obs_trace

    n_stages = m * (m + 1) // 2
    # per-stage rounds: 6 (lt, all key columns in parallel) + 2 combining
    # levels per extra key (tie-AND + OR) + 1 select
    rounds_per_stage = 7 + 2 * (len(key_cols) - 1)
    scope = (
        led.fused("bitonic_sort", rounds=rounds_per_stage * n_stages)
        if led is not None
        else contextlib.nullcontext()
    )
    # Inside a jit or vmap trace the span would time the tracing, not the
    # sort: open it only on concrete arrays.
    traced = obs_trace.active_tracer() is not None and not any(
        isinstance(c.shares, jax.core.Tracer) for c in cols.values()
    )
    timed = (
        obs_trace.span("sort", n=n, stages=n_stages, key_cols=len(key_cols))
        if traced
        else contextlib.nullcontext()
    )
    with scope, timed:
        for k, j in bitonic_stages(n):
            cols = _stage(cols, key_cols, k, j, prf, descending)
    return cols


def bitonic_sort_narrow(
    cols: Dict[str, Share],
    key_col: Union[str, Sequence[str]],
    prf: PRFSetup,
    descending: bool = False,
) -> Dict[str, Share]:
    """``bitonic_sort`` with payload narrowing: only the key columns plus a
    shared row-index column ride the compare-exchange network; the remaining
    (payload) columns are gathered once post-sort by the sorted index — a
    secret permutation — via shuffle-and-reveal (``apply_secret_perm``).

    Network traffic per payload column drops from O(n log^2 n) select words to
    O(n) shuffle words. The index column itself costs one network column, so
    narrowing only pays for >= 2 payload columns; below that we fall back to
    the classic full-payload network (identical output either way).
    """
    key_cols = [key_col] if isinstance(key_col, str) else list(key_col)
    payload = {n_: c for n_, c in cols.items() if n_ not in key_cols}
    if len(payload) < 2:
        return bitonic_sort(cols, key_col, prf, descending)
    from .shuffle import apply_secret_perm

    n = next(iter(cols.values())).shape[0]
    net = {kc: cols[kc] for kc in key_cols}
    assert "__idx" not in cols, "__idx is reserved by bitonic_sort_narrow"
    net["__idx"] = const_b(jnp.arange(n, dtype=jnp.uint32), (n,))
    net = bitonic_sort(net, key_cols, prf, descending)
    idx = net.pop("__idx")
    moved = apply_secret_perm(payload, idx, prf.fold(686))
    # reassemble in the caller's original column order
    return {n_: (net[n_] if n_ in net else moved[n_]) for n_ in cols}


def sort_valid_first(
    cols: Dict[str, BShare], valid_col: str, prf: PRFSetup
) -> Dict[str, BShare]:
    """Shrinkwrap's pre-cut sort: true tuples (valid=1) to the front.

    Sorting descending on the single-bit valid column suffices; equal keys
    keep arbitrary relative order (the network is not stable, which is fine —
    and is why Shrinkwrap needs no tie-breaking either).
    """
    return bitonic_sort_narrow(cols, valid_col, prf, descending=True)
