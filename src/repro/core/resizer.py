"""The Resizer operator (rho) — the paper's core contribution (§4).

Pipeline (Fig. 3): noise generation -> noise addition (mark eta filler tuples
in a secret column k alongside the true-tuple column c) -> secure shuffle
(break linkage) -> reveal-and-trim (open k, keep rows with k=1; the only
disclosure is the noisy size S = T + eta).

Two noise-addition designs (§4.2):

* ``sequential`` (Alg. 1): exactly eta fillers, deterministic. We implement it
  as an *arithmetic prefix-sum + one vectorized secure comparison* — additions
  are free under additive sharing, so the secure counter parallelizes; this is
  a beyond-paper optimization over MP-SPDZ's unbatchable per-tuple loop. The
  ledger can optionally model the paper's N-round sequential cost
  (``paper_round_model=True``) for like-for-like comparison (Fig. 5a).
* ``parallel`` (Alg. 2): a coin toss per tuple. Parties contribute private
  fixed-point uniforms; the per-tuple sum is compared to a threshold over
  secret shares (one a2b + comparison), then OR-ed with c — matching the
  "online comparison and a logical OR gate" cost the paper reports (§5.2).

Coin-toss fidelity (documented in DESIGN.md): Algorithm 2 as written compares
the *sum* of m uniforms to m*p, i.e. P(IrwinHall_m < m*p) != p in general —
a bias we reproduce under ``coin_mode="paper"``. The default
``coin_mode="corrected"`` compares the *fractional part* of the sum (uniform
on [0,1), still maskingly secure) to p, giving an exact Bernoulli(p).

Reveal-and-trim opens k (public), so the trimmed size S becomes public — the
controlled disclosure. Optional bucketing rounds S up to a bucket boundary:
coarser disclosure, fewer downstream compilation shapes (beyond-paper).

Lazy payload (DESIGN.md §7.2): when the input table carries
:class:`~repro.ops.table.LazyGather` columns (the lazy join's un-expanded
payload views), only the physical columns (k, valid, and any already-material
columns) flow through the secure shuffle; the deferred payload is gathered
directly from its base tables for the S surviving rows only — O(S * cols)
instead of O(N * cols) host memory — then freshly re-randomized. The ledger
still logs the full shuffle traffic for the deferred columns
(``shuffle_deferred_payload``): in a real deployment the payload must ride
the same 3-hop resharing, so the communication profile is unchanged; only the
simulation's materialization is deferred. The trim-side linkage uses the
simulation-side ``composed_permutation`` oracle, which a real deployment
realizes by running the recorded hops on the payload columns.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.table import SecretTable
from .circuits import a2b, bit2a, lt_public, or_bit
from .ledger import log_comm
from .noise import NoiseStrategy, NoTrim
from .prf import PRFSetup
from .sharing import AShare, BShare
from .shuffle import secure_shuffle

__all__ = ["ResizerConfig", "Resizer", "oracle_true_count"]

FP_BITS = 16  # fixed-point fraction bits for the coin toss
FP_ONE = 1 << FP_BITS


def oracle_true_count(table: SecretTable) -> int:
    """Plaintext T — simulation oracle only (used for the paper's runtime clip
    eta <- min(eta, N - T) and for tests; never enters the protocol view)."""
    from ..obs import trace as obs_trace

    with obs_trace.span("device.wait", what="resize.count"):
        v = np.asarray(table.valid.shares)
    return int(((v[0] ^ v[1] ^ v[2]) & 1).sum())


@dataclasses.dataclass
class ResizerConfig:
    noise: NoiseStrategy
    addition: str = "parallel"  # "parallel" | "sequential"
    coin_mode: str = "corrected"  # "corrected" | "paper"
    bucket: int = 1  # round the trimmed size up to a multiple of this
    paper_round_model: bool = False  # ledger sequential Alg.1 as N rounds
    use_sort: bool = False  # Shrinkwrap "sort&cut" baseline: bitonic sort on
    # the keep-bit instead of the secure shuffle (O(log^2 N) rounds vs O(1))

    def describe(self) -> str:
        tag = "sortcut" if self.use_sort else self.addition
        return f"rho({self.noise.name},{tag})"


class Resizer:
    """Stateless executor for one Resizer instance; see module docstring."""

    def __init__(self, cfg: ResizerConfig):
        self.cfg = cfg

    # -- noise addition: mark k ------------------------------------------------

    def _coins_parallel(
        self, n: int, p: float, prf: PRFSetup, key: jax.Array
    ) -> BShare:
        """Secret-shared Bernoulli coins via m private fixed-point uniforms.

        Each party's draw is a trivial arithmetic sharing; the sum is local.
        One a2b + one comparison per tuple, fully vectorized (1 round-trip
        pattern), matching Table 1's O(N) communication.
        """
        draws = jax.random.bits(key, shape=(3, n), dtype=jnp.uint32) & jnp.uint32(
            FP_ONE - 1
        )
        legs = jnp.zeros((3, 3, n), dtype=jnp.uint32)
        for i in range(3):
            legs = legs.at[i, i].set(draws[i])
        total = AShare(legs[0]) + AShare(legs[1]) + AShare(legs[2])

        if self.cfg.coin_mode == "corrected":
            # frac(sum) uniform on [0,1): exact Bernoulli(p)
            sum_b = a2b(total, prf.fold(801), width=FP_BITS + 2)
            frac = sum_b.and_public(FP_ONE - 1)
            thresh = int(round(p * FP_ONE))
            return lt_public(frac, thresh, prf.fold(802), width=FP_BITS)
        elif self.cfg.coin_mode == "paper":
            # Algorithm 2 verbatim: sum of m uniforms vs m*p (Irwin-Hall bias)
            sum_b = a2b(total, prf.fold(801), width=FP_BITS + 2)
            thresh = int(round(3 * p * FP_ONE))
            return lt_public(sum_b, thresh, prf.fold(802), width=FP_BITS + 2)
        raise ValueError(self.cfg.coin_mode)

    def _mark_parallel(
        self, table: SecretTable, p: float, prf: PRFSetup, key: jax.Array
    ) -> BShare:
        coin = self._coins_parallel(table.n, p, prf, key)
        return or_bit(table.valid, coin, prf.fold(803))

    def _mark_sequential(
        self, table: SecretTable, eta: int, prf: PRFSetup
    ) -> BShare:
        """Alg. 1 semantics: keep the first eta fillers (by position).

        filler prefix-count via bit2a + local cumsum; one vectorized secure
        comparison against the budget. (Beyond-paper parallelization; the
        original's N sequential rounds can be modeled in the ledger.)
        """
        c = table.valid
        not_c = c.xor_public(c.ring.const(1))
        fa = bit2a(not_c, prf.fold(811))
        cum = fa.cumsum(axis=0)
        cum_b = a2b(cum, prf.fold(812))
        within = lt_public(cum_b, eta + 1, prf.fold(813))  # cum <= eta
        k = or_bit(c, within, prf.fold(814))
        if self.cfg.paper_round_model:
            # MP-SPDZ's unbatchable secure counter: N dependent rounds
            log_comm("seq_round_model_extra", table.n, 0)
        return k

    # -- full resize -----------------------------------------------------------

    def __call__(
        self,
        table: SecretTable,
        prf: PRFSetup,
        key: jax.Array,
        bucket_fn: Optional[Callable[[int], int]] = None,
    ) -> Tuple[SecretTable, Dict]:
        cfg = self.cfg
        n = table.n
        t = oracle_true_count(table)

        if isinstance(cfg.noise, NoTrim):
            return table, {"n": n, "t": t, "s": n, "skipped": True}

        k_noise, k_shuf = jax.random.split(key)

        # 1-2. noise generation + addition
        if cfg.addition == "parallel":
            p = cfg.noise.sample_p(k_noise, n, t)
            k_col = self._mark_parallel(table, p, prf, k_noise)
            info_noise = {"p": p}
        elif cfg.addition == "sequential":
            eta = int(np.clip(cfg.noise.sample_eta(k_noise, n, t), 0, max(n - t, 0)))
            k_col = self._mark_sequential(table, eta, prf)
            info_noise = {"eta": eta}
        else:
            raise ValueError(cfg.addition)

        # 3. break linkage: secure shuffle (Reflex) or Shrinkwrap's bitonic
        #    sort on the keep-bit (sort&cut baseline; keeps true+filler rows
        #    at the front so revealing the sorted k discloses only S).
        #    Lazy (join-view) columns skip the physical shuffle: their shares
        #    are gathered from the base tables only for the S kept rows below;
        #    their shuffle traffic is still ledgered (comm is protocol-
        #    determined — see module docstring). AShare-backed views are
        #    excluded: the eager path a2b-converts them at full size before
        #    shuffling, and deferring that conversion would change the ledger.
        from ..ops.table import LazyGather

        lazy_cols = {
            name: c
            for name, c in table.cols.items()
            if isinstance(c, LazyGather)
            and isinstance(c.base, BShare)
            and not cfg.use_sort
        }
        cols = {"__k": k_col, "__valid": table.valid}
        cols.update(
            {
                name: table.bshare_col(name, prf)
                for name in table.cols
                if name not in lazy_cols
            }
        )
        if cfg.use_sort:
            from .sort import bitonic_sort_narrow
            from ..ops.groupby import pad_pow2

            padded = pad_pow2(SecretTable({k: v for k, v in cols.items() if k not in ("__k", "__valid")}, table.valid))
            # re-assemble with the padded keep column (pad rows keep=0);
            # only the keep bit + a row index ride the sorting network — the
            # payload is gathered once post-sort (bitonic_sort_narrow)
            k_pad = k_col.pad_rows(padded.n)
            cols = {"__k": k_pad, "__valid": padded.valid}
            cols.update(padded.cols)
            shuffled = bitonic_sort_narrow(cols, "__k", prf.fold(821), descending=True)
            n = padded.n
        else:
            shuffled = secure_shuffle(cols, prf.fold(821))
            if lazy_cols:
                from .shuffle import HOPS

                lazy_row_bytes = sum(
                    c.ring.bytes * (c.size // max(c.shape[0], 1))
                    for c in lazy_cols.values()
                )
                log_comm("shuffle_deferred_payload", 0, HOPS * n * lazy_row_bytes)
        k_col = shuffled.pop("__k")
        valid = shuffled.pop("__valid")

        # 4. reveal-and-trim: open k (the only disclosure), drop k=0 rows
        from ..obs import trace as obs_trace

        k_bits = (k_col.shares[0] ^ k_col.shares[1] ^ k_col.shares[2]) & 1
        with obs_trace.span("device.wait", what="resize.open"):
            k_open = np.asarray(k_bits)
        log_comm("reveal_k", 1, n * k_col.ring.bytes, payload=k_col.shares)
        s = int(k_open.sum())
        keep = np.nonzero(k_open)[0]

        s_padded = s
        if bucket_fn is not None:
            s_padded = max(bucket_fn(s), s)
        elif cfg.bucket > 1:
            s_padded = ((s + cfg.bucket - 1) // cfg.bucket) * cfg.bucket
        s_padded = min(max(s_padded, 1), n)

        keep = jnp.asarray(keep)
        out = SecretTable(dict(shuffled), valid).gather_rows(keep)
        if lazy_cols:
            # Deferred payload: map the kept (shuffled) positions back through
            # the composed permutation to product rows, gather exactly S rows
            # from each base table, and re-randomize (the resharing the
            # payload would have received in the shuffle hops).
            from .shuffle import _rerandomize, composed_permutation

            orig_rows = jnp.take(composed_permutation(prf.fold(821), n), keep)
            for i, (name, lc) in enumerate(lazy_cols.items()):
                out.cols[name] = _rerandomize(
                    lc.gather(orig_rows), prf.fold(823), 860 + i
                )
        if s_padded > s:
            out = out.pad_rows(s_padded)

        info = {"n": n, "t": t, "s": s, "s_padded": s_padded, **info_noise}
        return out, info
