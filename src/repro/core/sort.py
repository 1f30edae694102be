"""Oblivious bitonic sorting network over secret-shared tables.

Used by: OrderBy, GroupBy (sort as pre-pass), Distinct, and the Shrinkwrap
"sort&cut" baseline that Reflex compares against (sort valid tuples to the
front, then cut at the DP size).

A bitonic network on N = 2^m rows has m(m+1)/2 compare-exchange stages; each
stage costs one oblivious ``lt`` over N lanes (6 rounds, 11 AND-words) plus one
oblivious select per payload column (1 AND-word). Total rounds
O(log^2 N) — vs. the shuffle's O(1), which is exactly the paper's argument for
replacing Shrinkwrap's sort with a shuffle (Fig. 5a / Fig. 8).

One network is one device program (``bitonic_network``): the columns ride
it stacked as one (3, C, N) array, and the compare-exchange stage is written
once, in a ``lax.scan`` over the network's (k, j) table, so a network
compiles to the same program body whatever its stage count. The partner
lane ``i ^ j`` is fetched by two lane rolls (``i + j`` where bit j of i is
clear, ``i - j`` where it is set), so the program holds no gather. With
fused kernels on, the stage's conditional swap is the
``repro.kernels.bitonic_stage`` kernel, with the gate path as its oracle.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Dict, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import (
    capture_launches,
    fusion_enabled,
    kernels_enabled,
    override_fusion,
    override_kernels,
    record_launch,
)
from .circuits import and_bit, eq, lt, or_bit
from .ledger import CommLedger, exchange_scope, log_comm
from .prf import PRFSetup, zero_share_xor
from .sharing import AShare, BShare, and_, const_b

__all__ = [
    "bitonic_network",
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


def _lex_lt(his: BShare, los: BShare, prf: PRFSetup) -> BShare:
    """Lexicographic ``his < los`` over K parallel key columns, given as
    (3, K, n) sharings: column 0 decides unless it ties, in which case
    column 1 decides, and so on —
    lt_0 OR (eq_0 AND lt_1) OR (eq_0 AND eq_1 AND lt_2) ...

    All columns' lt circuits (and all tie eq circuits) are independent, so
    they run as one batched call each — the rounds the ledger already models;
    only the shallow combine chain stays sequential."""
    lts = lt(his, los, prf.fold(0))
    eqs = eq(BShare(his.shares[:, :-1]), BShare(los.shares[:, :-1]), prf.fold(6))
    res = BShare(lts.shares[:, 0])
    ties = None
    for i in range(1, his.shape[0]):
        p = prf.fold(i)
        e = BShare(eqs.shares[:, i - 1])
        ties = e if ties is None else and_bit(ties, e, p.fold(2))
        lt_i = BShare(lts.shares[:, i])
        res = or_bit(res, and_bit(ties, lt_i, p.fold(4)), p.fold(5))
    return res


def _stage(
    own: jnp.ndarray,
    n_keys: int,
    k: jnp.ndarray,
    j: jnp.ndarray,
    prf: PRFSetup,
    descending: bool,
) -> jnp.ndarray:
    """One compare-exchange stage over the stacked (3, C, n) shares, the
    first ``n_keys`` columns the sort key; ``k`` and ``j`` are traced."""
    n = own.shape[-1]
    idx = lax.iota(jnp.int32, n)
    is_lo = (idx & j) == 0  # public lane predicate: partner i ^ j = i + j
    asc = (idx & k) == 0  # public direction per pair (bit k equal for both)
    if descending:
        asc = ~asc
    # partner lanes by roll: roll(-j) at the lower lane, roll(+j) at the upper
    both = jnp.concatenate([own, own], axis=-1)
    other = jnp.where(
        is_lo,
        lax.dynamic_slice_in_dim(both, j, n, axis=-1),
        lax.dynamic_slice_in_dim(both, n - j, n, axis=-1),
    )

    # lo/hi views on public masks (local): lo = value at the lower lane index
    los = BShare(jnp.where(is_lo, own[:, :n_keys], other[:, :n_keys]))
    his = BShare(jnp.where(is_lo, other[:, :n_keys], own[:, :n_keys]))
    # swap decision, identical at both lanes of the pair (ties don't swap)
    p = prf.fold(7 * k + j)
    if n_keys == 1:
        s = lt(BShare(his.shares[:, 0]), BShare(los.shares[:, 0]), p)
    else:
        s = _lex_lt(his, los, p)
    # descending pairs invert the decision (local XOR with a public bit)
    s = s.xor_public(jnp.where(asc, 0, 1).astype(s.ring.dtype))
    mask = s.lsb_mask()

    # conditional swap of every column in one batched AND (per-column selects
    # are independent; same words, one dispatch)
    own_b = BShare(own)
    p_swap = prf.fold(9000 + 31 * k + 7 * j)
    if fusion_enabled():
        # the same AND (same alpha, same ledger entry) with the select's
        # XORs fused around it: the bitonic_stage kernel
        from ..kernels.bitonic_stage.ops import stage_swap

        alpha = zero_share_xor(p_swap, own_b.shape, own_b.ring)
        log_comm("and", 1, own_b.size * own_b.ring.bytes)
        return stage_swap(mask.shares, own, other, alpha)
    m3 = BShare(jnp.broadcast_to(mask.shares[:, None, :], own.shape))
    return (own_b ^ and_(m3, own_b ^ BShare(other), p_swap)).shares


@contextlib.contextmanager
def _stage_trace(kernels: bool, fusion: bool):
    """Trace stage bodies on the given gate path, off the caller's ledger,
    exchange boundary and launch counts; yields the private ledger and
    launch counter that take what the body logs and launches."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(exchange_scope(None))
        led = stack.enter_context(CommLedger())
        launches = stack.enter_context(capture_launches())
        stack.enter_context(override_kernels(kernels))
        stack.enter_context(override_fusion(fusion))
        yield led, launches


@functools.partial(
    jax.jit, static_argnames=("n_keys", "descending", "kernels", "fusion")
)
def bitonic_network(
    own: jnp.ndarray,
    pair_keys: jnp.ndarray,
    n_keys: int,
    descending: bool,
    kernels: bool,
    fusion: bool,
) -> jnp.ndarray:
    """The whole bitonic network over the stacked (3, C, n) shares, sorted by
    their first ``n_keys`` columns, as one program: ``lax.scan`` over the
    network's (k, j) table runs the one traced stage body. ``kernels`` and
    ``fusion`` are the gate path the body is traced with (they change the
    program, so they are part of its cache key). The body's single trace
    pass logs and launches nothing: ``bitonic_sort`` counts every stage."""
    n = own.shape[-1]
    table = np.array(list(bitonic_stages(n)), dtype=np.int32).reshape(-1, 2)
    prf = PRFSetup(pair_keys)

    def body(carry, kj):
        return _stage(carry, n_keys, kj[0], kj[1], prf, descending), None

    with _stage_trace(kernels, fusion):
        out, _ = lax.scan(body, own, jnp.asarray(table))
    return out


@functools.lru_cache(maxsize=None)
def _stage_cost(
    shape: tuple,
    dtype: str,
    keys_shape: tuple,
    keys_dtype: str,
    n_keys: int,
    descending: bool,
    kernels: bool,
    fusion: bool,
) -> Tuple[int, Tuple[Tuple[str, int], ...]]:
    """Bytes per party that one stage of the network sends, and the kernel
    launches it makes, from one abstract trace of the stage body."""
    scalar = jax.ShapeDtypeStruct((), jnp.int32)

    def stage(own, pair_keys, k, j):
        return _stage(own, n_keys, k, j, PRFSetup(pair_keys), descending)

    with _stage_trace(kernels, fusion) as (led, launches):
        jax.eval_shape(
            stage,
            jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(keys_shape, keys_dtype),
            scalar,
            scalar,
        )
    return int(led.tally()["bytes_per_party"]), tuple(sorted(launches.items()))


def bitonic_sort(
    cols: Dict[str, BShare],
    key_col: Union[str, Sequence[str]],
    prf: PRFSetup,
    descending: bool = False,
) -> Dict[str, BShare]:
    """Sort all columns by ``key_col`` (32-bit unsigned order) — a single
    column name or a sequence of names compared lexicographically (composite
    GROUP BY keys). N must be a power of two (the engine's bucketing
    guarantees this).

    The ledger gets one ``bitonic_sort`` entry per sort, as the network's
    stages would have logged it: ``rounds_per_stage * stages`` rounds and
    the stage's bytes times the stage count."""
    key_cols = [key_col] if isinstance(key_col, str) else list(key_col)
    n = next(iter(cols.values())).shape[0]
    if n & (n - 1):
        raise ValueError(f"bitonic_sort requires power-of-two rows, got {n}")
    m = int(math.log2(n))
    from ..obs import trace as obs_trace

    n_stages = m * (m + 1) // 2
    # per-stage rounds: 6 (lt, all key columns in parallel) + 2 combining
    # levels per extra key (tie-AND + OR) + 1 select
    rounds_per_stage = 7 + 2 * (len(key_cols) - 1)
    if n_stages == 0:
        log_comm("bitonic_sort", 0, 0)
        return cols
    names = key_cols + [nm for nm in cols if nm not in key_cols]
    own = jnp.stack([cols[nm].shares for nm in names], axis=1)  # (3, C, n)
    static = dict(
        n_keys=len(key_cols),
        descending=descending,
        kernels=kernels_enabled(),
        fusion=fusion_enabled(),
    )
    # Inside a jit or vmap trace the span would time the tracing, not the
    # sort: open it only on concrete arrays.
    traced = obs_trace.active_tracer() is not None and not isinstance(
        own, jax.core.Tracer
    )
    timed = (
        obs_trace.span("sort", n=n, stages=n_stages, key_cols=len(key_cols))
        if traced
        else contextlib.nullcontext()
    )
    with timed:
        out = bitonic_network(own, prf.pair_keys, **static)
    stage_bytes, stage_launches = _stage_cost(
        own.shape,
        jnp.dtype(own.dtype).name,
        prf.pair_keys.shape,
        jnp.dtype(prf.pair_keys.dtype).name,
        **static,
    )
    log_comm("bitonic_sort", rounds_per_stage * n_stages, stage_bytes * n_stages)
    # the network program runs the stage body's kernels once per stage
    for kind, count in stage_launches:
        record_launch(kind, count * n_stages)
    sorted_cols = {nm: BShare(out[:, i]) for i, nm in enumerate(names)}
    return {nm: sorted_cols[nm] for nm in cols}


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
