"""Query execution engine.

Executes a plan tree bottom-up. Every operator protocol runs on static shapes;
the *only* place a public size changes is a ``Resize`` node's reveal-and-trim
(and a public LIMIT) — so dynamic re-dispatch on the revealed size is both
legitimate (it is the disclosed value) and bounded by bucketing.

The engine records a per-node execution report: wall seconds, the ledger's
(rounds, bytes/party), and input/output oblivious sizes — this is what the
benchmarks print and what reproduces the paper's Figures 6-9.

Batched execution (DESIGN.md §11): :meth:`Engine.execute_batch` runs K
structurally identical plans as ONE engine pass. Each operator's protocol is
``jax.vmap``-ed over the K input tables stacked along a new leading batch
axis, so every kernel launch — Kogge-Stone comparison levels, a2b
conversions, bitonic compare-exchange stages — and its PRF folds are shared
across the batch instead of repeated per query. Because the engine's PRF is
fixed per instance and a vmapped body traces with per-slot shapes, every
slot's shares are bit-identical to what a serial :meth:`execute` of that
query would have produced, and the one traced ledger profile IS each slot's
per-query tally (demuxed into per-slot :class:`ExecutionReport`s). Resize
nodes run per slot — each query folds its own noise counter, so noise stays
fresh and i.i.d. per query and CRT observations are never merged — and if
the revealed trim sizes diverge, the batch splits into per-slot execution
for the remainder of the plan.
"""
from __future__ import annotations

import dataclasses
import json
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..config import RuntimeConfig, use_config
from ..core.ledger import CommLedger, active_exchange, batched_tally, log_comm
from ..core import material
from ..core.prf import PRFSetup, setup_prf
from ..obs import redact
from ..obs import trace as obs_trace
from ..ops import SecretTable
from ..plan.nodes import PlanNode
from ..plan.registry import infer_schema, lookup, plan_batchable

__all__ = ["Engine", "ExecutionReport", "NodeStats"]


@dataclasses.dataclass
class NodeStats:
    node: str
    n_in: int  # first input's oblivious size (legacy field; see n_ins)
    n_out: int
    seconds: float
    bytes_per_party: int
    rounds: int
    n_ins: List[int] = dataclasses.field(default_factory=list)  # all inputs
    extra: Dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ExecutionReport:
    nodes: List[NodeStats] = dataclasses.field(default_factory=list)

    @property
    def total_seconds(self) -> float:
        return sum(s.seconds for s in self.nodes)

    @property
    def total_bytes(self) -> int:
        return sum(s.bytes_per_party for s in self.nodes)

    @property
    def total_rounds(self) -> int:
        return sum(s.rounds for s in self.nodes)

    def to_dict(self) -> Dict:
        """JSON-safe per-node report (machine-readable twin of summary())."""

        def safe(v):
            if isinstance(v, dict):
                return {k: safe(x) for k, x in v.items()}
            if isinstance(v, (list, tuple)):
                return [safe(x) for x in v]
            if hasattr(v, "item"):  # numpy / jax scalars
                return v.item()
            return v

        return {
            "nodes": [
                {
                    "node": s.node,
                    "n_in": int(s.n_in),
                    "n_ins": [int(n) for n in s.n_ins],
                    "n_out": int(s.n_out),
                    "seconds": float(s.seconds),
                    "bytes_per_party": int(s.bytes_per_party),
                    "rounds": int(s.rounds),
                    "extra": safe(s.extra),
                }
                for s in self.nodes
            ],
            "total_seconds": float(self.total_seconds),
            "total_bytes": int(self.total_bytes),
            "total_rounds": int(self.total_rounds),
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Dict) -> "ExecutionReport":
        """Rebuild a report from :meth:`to_dict` output — the wire form the
        networked runtime's party servers return to the coordinator."""
        return cls(
            nodes=[
                NodeStats(
                    node=n["node"],
                    n_in=int(n["n_in"]),
                    n_ins=[int(x) for x in n.get("n_ins", [])],
                    n_out=int(n["n_out"]),
                    seconds=float(n["seconds"]),
                    bytes_per_party=int(n["bytes_per_party"]),
                    rounds=int(n["rounds"]),
                    extra=dict(n.get("extra", {})),
                )
                for n in d.get("nodes", [])
            ]
        )

    def summary(self) -> str:
        def ins(s: NodeStats) -> str:
            # all inputs, not just the first: a join reads "512x128"
            return "x".join(str(n) for n in s.n_ins) if s.n_ins else "-"

        def note(s: NodeStats) -> str:
            if not s.extra:
                return ""
            pub = redact.public_view(s.extra)
            if pub.get("skipped"):
                return "trim skipped"
            parts = []
            if pub.get("s") is not None:
                parts.append(f"S={pub['s']}")
            sp = pub.get("s_padded")
            if sp is not None and sp != pub.get("s"):
                parts.append(f"pad->{sp}")
            return " ".join(parts)

        lines = [
            f"{'node':<42}{'n_ins':>11}{'n_out':>9}{'sec':>9}"
            f"{'MiB/party':>11}{'rounds':>8}  extra"
        ]
        for s in self.nodes:
            lines.append(
                (
                    f"{s.node:<42}{ins(s):>11}{s.n_out:>9}{s.seconds:>9.3f}"
                    f"{s.bytes_per_party / 2**20:>11.3f}{s.rounds:>8}  {note(s)}"
                ).rstrip()
            )
        lines.append(
            f"{'TOTAL':<42}{'':>11}{'':>9}{self.total_seconds:>9.3f}"
            f"{self.total_bytes / 2**20:>11.3f}{self.total_rounds:>8}"
        )
        return "\n".join(lines)


def _block(table: SecretTable) -> None:
    jax.block_until_ready(table.valid.shares)


# -----------------------------------------------------------------------------
# Batched-execution plumbing
# -----------------------------------------------------------------------------

def _stack_tables(tables: Sequence[SecretTable]) -> SecretTable:
    """K structurally identical tables -> one table whose leaves carry a new
    leading batch axis (shares become ``(K, 3, n)``)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tables)


def _broadcast_table(table: SecretTable, k: int) -> SecretTable:
    """One shared table viewed as a K-slot batch (zero-copy broadcast)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), table
    )


def _unstack_table(stacked: SecretTable, i: int) -> SecretTable:
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


@dataclasses.dataclass
class _BatchVal:
    """A plan node's output across the batch: either one stacked table (the
    vmapped fast path) or a per-slot list (after the batch split on divergent
    Resize trim sizes, or through a stateful per-slot hook)."""

    k: int
    stacked: Optional[SecretTable] = None
    slots: Optional[List[SecretTable]] = None

    def to_slots(self) -> List[SecretTable]:
        if self.slots is None:
            self.slots = [_unstack_table(self.stacked, i) for i in range(self.k)]
        return self.slots

    def slot_n(self, i: int) -> int:
        if self.slots is not None:
            return self.slots[i].n
        return int(self.stacked.valid.shares.shape[-1])


def _physical_sig(plan: PlanNode) -> tuple:
    """Preorder tuple of operator class names — the *physical* plan shape
    (logical fingerprints collapse physical variants by design)."""
    return (plan.label,) + tuple(
        s for c in plan.children() for s in _physical_sig(c)
    )


def _count_resizes(plan: PlanNode) -> int:
    """Noise-counter consumers per plan (post-order Resize count)."""
    n = sum(_count_resizes(c) for c in plan.children())
    return n + (1 if lookup(type(plan)).provides_resize_info else 0)


@dataclasses.dataclass
class _BatchCtx:
    """Per-``execute_batch`` state threaded through the plan walk."""

    k: int
    reports: List[ExecutionReport]
    ctr_base: int  # engine._resize_ctr before the batch started
    resizes_per_slot: int  # Resize nodes per plan (post-order count)
    resize_idx: int = 0  # next Resize node's post-order index

    def next_resize_index(self) -> int:
        j = self.resize_idx
        self.resize_idx += 1
        return j

    def slot_ctr_before(self, slot: int, resize_index: int) -> int:
        """The counter value engine._resize_ctr must hold *before* this
        slot executes its ``resize_index``-th Resize, so the fold matches a
        serial run of the K queries in submission order exactly: slot i's
        j-th resize consumes ``base + i * R + j + 1``."""
        return self.ctr_base + slot * self.resizes_per_slot + resize_index


class Engine:
    """Executes plans over a set of secret-shared base tables."""

    # process-wide jit cache: operator protocols are pure functions of
    # (static node spec, table shapes) — reusing compiled executables across
    # Engine instances removes both eager-dispatch overhead and recompiles
    # (a beyond-paper optimization; see EXPERIMENTS.md §Perf). LRU-bounded:
    # a long-running serving session sees an unbounded stream of (query,
    # revealed-size) shapes, so the cache would otherwise grow without limit;
    # eviction only costs a recompile on a shape not seen recently.
    _JIT_CACHE: "OrderedDict" = OrderedDict()
    _JIT_CACHE_MAX = 128
    # Logical hit/miss counters. "Logical" because a batched pass that reuses
    # one compiled program for K slots served K queries from the cache: a
    # lookup counts `count` hits on presence, and a batched compile counts one
    # miss plus K-1 hits (the other slots ride the same executable).
    _JIT_STATS: Dict[str, int] = {"hits": 0, "misses": 0}

    @classmethod
    def _jit_cache_get(cls, key, count: int = 1):
        hit = cls._JIT_CACHE.get(key)
        if hit is not None:
            cls._JIT_CACHE.move_to_end(key)
            cls._JIT_STATS["hits"] += count
        else:
            cls._JIT_STATS["misses"] += 1
            if count > 1:
                cls._JIT_STATS["hits"] += count - 1
        return hit

    @classmethod
    def _jit_cache_put(cls, key, value) -> None:
        cls._JIT_CACHE[key] = value
        cls._JIT_CACHE.move_to_end(key)
        while len(cls._JIT_CACHE) > cls._JIT_CACHE_MAX:
            cls._JIT_CACHE.popitem(last=False)

    @classmethod
    def jit_cache_stats(cls) -> Dict[str, float]:
        h, m = cls._JIT_STATS["hits"], cls._JIT_STATS["misses"]
        return {
            "hits": h,
            "misses": m,
            "hit_rate": h / max(h + m, 1),
            "size": len(cls._JIT_CACHE),
        }

    @classmethod
    def reset_jit_stats(cls) -> None:
        cls._JIT_STATS["hits"] = cls._JIT_STATS["misses"] = 0

    def __init__(
        self,
        tables: Dict[str, SecretTable],
        key: jax.Array | None = None,
        prf: PRFSetup | None = None,
        bucket_fn: Optional[Callable[[int], int]] = None,
        jit_ops: bool = False,  # per-op jit pays off for REPEATED same-shape
        # queries (serving); one-shot plans are faster eager (XLA-CPU compile
        # of a 4k-row sort network costs minutes) — see §Perf
        validate: bool = True,  # schema-check plans before any MPC work
        config: Optional[RuntimeConfig] = None,  # execution-strategy knobs;
        # None = the env fallback (repro.config.current_config)
    ):
        self.tables = tables
        key = key if key is not None else jax.random.PRNGKey(0)
        self.key = key
        self.prf = prf if prf is not None else setup_prf(jax.random.fold_in(key, 7))
        self.bucket_fn = bucket_fn
        self.jit_ops = jit_ops
        self.validate = validate
        self.config = config
        self._resize_ctr = 0
        self._last_resize_info: Optional[Dict] = None
        self.last_batch_stats: Dict = {}
        # revealed-size feedback: called as hook(node, info) after every
        # non-skipped Resize reveal-and-trim (serial and per-batch-slot alike).
        # The service wires this to the CalibrationStore so sizes that are
        # ALREADY public refine future planning — zero extra disclosure.
        self.reveal_hook: Optional[Callable[[PlanNode, Dict], None]] = None

    def execute(self, plan: PlanNode) -> tuple[SecretTable, ExecutionReport]:
        if self.validate:
            # registry schema propagation: unknown columns raise SchemaError
            # here, before a single share moves
            from ..sql.catalog import Catalog

            infer_schema(plan, Catalog.from_tables(self.tables))
        report = ExecutionReport()
        self._last_resize_info = None  # never carry info across runs
        with use_config(self.config), obs_trace.span("execute"):
            out = self._run(plan, report)
        return out, report

    # ------------------------------------------------------------------
    def _run_node_slot(
        self, node: PlanNode, children: List[SecretTable]
    ) -> Tuple[SecretTable, NodeStats]:
        """Execute one node for one slot under its own ledger and return the
        output with its filled report entry. The single accounting path for
        serial `_run`, the batch's split tail, and per-slot Resize — so
        batched and serial reports can never desynchronize field by field.

        Consumes the resize info `_apply` may have produced; clearing it
        keeps a later Resize (or a later run) from reporting stale info."""
        led = CommLedger()
        src = material.active_source()
        h0, m0 = (src.hits, src.misses) if src is not None else (0, 0)
        drv = active_exchange()
        if drv is not None:
            x0 = (drv.count, drv.stall_seconds, drv.wire_bytes)
        n_ins = [t.n for t in children]
        t0 = time.perf_counter()
        with obs_trace.span(
            f"node[{node.label}]", op=node.describe(), n_ins=n_ins
        ) as sp:
            with led:
                out = self._apply(node, children)
            with obs_trace.span("device.wait", what="node"):
                _block(out)
        dt = sp.seconds if sp is not None else time.perf_counter() - t0
        tally = led.tally()
        extra = {}
        if src is not None and (src.hits - h0 or src.misses - m0):
            # hot/cold attribution for EXPLAIN ANALYZE: how much of this
            # node's correlated randomness came from the offline pool
            extra["offline"] = {"hits": src.hits - h0, "misses": src.misses - m0}
        if drv is not None and drv.count > x0[0]:
            # network attribution (networked mode only): this node's share
            # of the ring exchanges, with the time spent blocked on the
            # inbound frame — "net stall" in EXPLAIN ANALYZE. Stall is this
            # party's own clock; wire bytes equal the ledger's by audit.
            extra["wire"] = {
                "exchanges": drv.count - x0[0],
                "stall_seconds": round(drv.stall_seconds - x0[1], 6),
                "wire_bytes": drv.wire_bytes - x0[2],
            }
        if lookup(type(node)).provides_resize_info:
            info = self._last_resize_info or {}
            self._last_resize_info = None
            if self.reveal_hook is not None and info and not info.get("skipped"):
                self.reveal_hook(node, info)
            extra = {**info, **extra}
        stats = NodeStats(
            node=node.describe(),
            n_in=n_ins[0] if n_ins else 0,
            n_ins=n_ins,
            n_out=out.n,
            seconds=dt,
            bytes_per_party=int(tally["bytes_per_party"]),
            rounds=int(tally["rounds"]),
            extra=extra,
        )
        # `extra` passes the redaction boundary inside set_attrs(): the
        # resizer's t/p/eta never reach the span, S and padding do.
        obs_trace.set_attrs(
            sp,
            n_out=stats.n_out,
            bytes_per_party=stats.bytes_per_party,
            rounds=stats.rounds,
            **extra,
        )
        return out, stats

    def _run(self, node: PlanNode, report: ExecutionReport) -> SecretTable:
        children = [self._run(c, report) for c in node.children()]
        out, stats = self._run_node_slot(node, children)
        report.nodes.append(stats)
        return out

    @staticmethod
    def _cache_key(node: PlanNode, children: List[SecretTable]):
        child_sig = tuple(
            (t.n, tuple(sorted((k, type(v).__name__) for k, v in t.cols.items())))
            for t in children
        )
        # node.label disambiguates physical variants that share a describe()
        # string by design (JoinSortMerge inherits Join's — fingerprints must
        # not move when the planner flips algorithms, but compiled programs do)
        return (node.label, node.describe(), child_sig)

    def _apply(self, node: PlanNode, children: List[SecretTable]) -> SecretTable:
        prf = self.prf
        d = lookup(type(node))
        if d.engine_apply is not None:
            # stateful operators (Scan reads the table dict; Resize folds the
            # per-execution noise counter) bypass the jit path
            return d.engine_apply(self, node, children)
        fn = d.protocol(node)
        if not self.jit_ops:
            return fn(prf, *children)
        key = self._cache_key(node, children)
        jitted = Engine._jit_cache_get(key)
        if jitted is None:
            # Capture the ledger profile once at trace time: jit re-executions
            # skip the Python body, so replay the recorded cost on cache hits.
            profile: Dict = {}

            def traced(prf_arg, *tables, _fn=fn, _profile=profile):
                with CommLedger() as led:
                    out = _fn(prf_arg, *tables)
                _profile.setdefault("tally", led.tally())
                return out

            jitted = (jax.jit(traced), profile)
            Engine._jit_cache_put(key, jitted)
        jfn, profile = jitted
        out = jfn(prf, *children)
        if profile.get("tally"):
            t = profile["tally"]
            log_comm(node.label.lower(), int(t["rounds"]), int(t["bytes_per_party"]))
        return out

    # ------------------------------------------------------------------
    # Batched execution: K same-shape queries, one engine pass
    # ------------------------------------------------------------------

    def execute_batch(
        self, plans: Sequence[PlanNode]
    ) -> List[Tuple[SecretTable, ExecutionReport]]:
        """Execute K structurally identical plans as one stacked engine pass.

        Every plan must have the same fingerprint (``plan.pretty()``) — the
        admission scheduler's bucketing guarantees this. Slot i's result and
        per-node ledger tallies are bit-identical to what ``execute(plans[i])``
        would have produced had the K queries run serially in order (the
        noise-counter allocation in :class:`_BatchCtx` preserves per-slot
        Resize freshness exactly). Plans containing non-batchable operators,
        and batches of one, fall back to serial execution.

        ``last_batch_stats`` afterwards holds the physical cost of the pass:
        per-slot bytes all really move (bytes scale with K) but vmapped nodes
        share their synchronous rounds across the batch.
        """
        plans = list(plans)
        if not plans:
            return []
        if len(plans) == 1 or not plan_batchable(plans[0]):
            results = [self.execute(p) for p in plans]
            # same shape as the batched stats: serial execution shares nothing,
            # so the physical pass is just the sum of the per-query tallies
            self.last_batch_stats = {
                "slots": len(plans),
                "stacked_nodes": 0,
                "split_nodes": 0,
                "physical_bytes_per_party": sum(
                    r.total_bytes for _, r in results
                ),
                "physical_rounds": sum(r.total_rounds for _, r in results),
            }
            return results
        fp = plans[0].pretty()
        # pretty() is the *logical* fingerprint and is deliberately identical
        # across physical join variants; the preorder label tuple is the
        # physical signature — stacking a Join slot with a JoinSortMerge slot
        # would vmap one algorithm over the other's inputs
        psig = _physical_sig(plans[0])
        for p in plans[1:]:
            if p.pretty() != fp or _physical_sig(p) != psig:
                raise ValueError(
                    "execute_batch requires structurally identical plans; "
                    "bucket by full plan fingerprint (and physical operator "
                    "signature) before batching"
                )
        if self.validate:
            from ..sql.catalog import Catalog

            infer_schema(plans[0], Catalog.from_tables(self.tables))

        k = len(plans)
        resizes = _count_resizes(plans[0])
        ctx = _BatchCtx(
            k=k,
            reports=[ExecutionReport() for _ in range(k)],
            ctr_base=self._resize_ctr,
            resizes_per_slot=resizes,
        )
        self._last_resize_info = None
        self.last_batch_stats = {
            "slots": k,
            "stacked_nodes": 0,
            "split_nodes": 0,
            "physical_bytes_per_party": 0,
            "physical_rounds": 0,
        }
        try:
            with use_config(self.config), obs_trace.span(
                "execute", slots=k, batched=True
            ):
                out = self._run_batch(plans[0], ctx)
        finally:
            # The batch owns the counter range [base+1, base+k*R]; per-slot
            # execution rewinds within it non-monotonically. Skip past the
            # WHOLE range even on failure — some slots may already have
            # revealed sizes for counters in it, and a later query refolding
            # one would reuse noise the attacker has seen (unused counters
            # are merely skipped, which is safe).
            self._resize_ctr = ctx.ctr_base + k * resizes
        return list(zip(out.to_slots(), ctx.reports))

    def _run_batch(self, node: PlanNode, ctx: _BatchCtx) -> _BatchVal:
        children = [self._run_batch(c, ctx) for c in node.children()]
        d = lookup(type(node))
        if d.batch_apply is not None:
            return d.batch_apply(self, node, children, ctx)
        if all(c.stacked is not None for c in children):
            return self._run_batch_stacked(node, children, ctx)
        return self._run_batch_split(node, children, ctx)

    def _run_batch_stacked(
        self, node: PlanNode, children: List[_BatchVal], ctx: _BatchCtx
    ) -> _BatchVal:
        """One vmapped launch for all K slots. The traced ledger profile is
        the per-slot cost (the body traces with per-slot shapes), so it is
        replayed verbatim into every slot's report — exact parity with a
        serial run — while the physical tally charges bytes K times and the
        shared rounds once."""
        led = CommLedger()
        src = material.active_source()
        h0, m0 = (src.hits, src.misses) if src is not None else (0, 0)
        n_ins = [c.slot_n(0) for c in children]
        t0 = time.perf_counter()
        with obs_trace.span(
            f"node[{node.label}]", op=node.describe(), n_ins=list(n_ins),
            slots=ctx.k, stacked=True,
        ) as sp:
            with led:
                out = self._apply_batched(
                    node, [c.stacked for c in children], ctx.k
                )
            with obs_trace.span("device.wait", what="node"):
                _block(out)
        dt = sp.seconds if sp is not None else time.perf_counter() - t0
        tally = led.tally()
        val = _BatchVal(k=ctx.k, stacked=out)
        extra = {}
        if src is not None and (src.hits - h0 or src.misses - m0):
            # one vmapped launch serves all K slots: pool traffic is shared,
            # so the whole-pass delta is reported identically into each slot
            extra["offline"] = {"hits": src.hits - h0, "misses": src.misses - m0}
        for report in ctx.reports:
            report.nodes.append(
                NodeStats(
                    node=node.describe(),
                    n_in=n_ins[0] if n_ins else 0,
                    n_ins=list(n_ins),
                    n_out=val.slot_n(0),
                    seconds=dt / ctx.k,  # amortized wall share
                    bytes_per_party=int(tally["bytes_per_party"]),
                    rounds=int(tally["rounds"]),
                    extra=dict(extra),
                )
            )
        obs_trace.set_attrs(
            sp,
            n_out=val.slot_n(0),
            bytes_per_party=int(tally["bytes_per_party"]),
            rounds=int(tally["rounds"]),
            **extra,
        )
        # physical cost of the pass: bytes x K, synchronous rounds shared
        phys = batched_tally(tally, ctx.k)
        bs = self.last_batch_stats
        bs["stacked_nodes"] += 1
        bs["physical_bytes_per_party"] += int(phys["bytes_per_party"])
        bs["physical_rounds"] += int(phys["rounds"])
        return val

    def _run_batch_split(
        self, node: PlanNode, children: List[_BatchVal], ctx: _BatchCtx
    ) -> _BatchVal:
        """Per-slot execution through the normal `_apply` path — used after a
        Resize split (divergent trim sizes make the slots un-stackable)."""
        slot_children = [c.to_slots() for c in children]
        outs: List[SecretTable] = []
        bs = self.last_batch_stats
        bs["split_nodes"] += 1
        for i in range(ctx.k):
            out, stats = self._run_node_slot(
                node, [sc[i] for sc in slot_children]
            )
            ctx.reports[i].nodes.append(stats)
            bs["physical_bytes_per_party"] += stats.bytes_per_party
            bs["physical_rounds"] += stats.rounds
            outs.append(out)
        return _BatchVal(k=ctx.k, slots=outs)

    def _apply_batched(
        self, node: PlanNode, stacked: List[SecretTable], k: int
    ) -> SecretTable:
        """vmap the node's protocol over the batch axis; under ``jit_ops`` the
        vmapped program is cached like the serial one, and a cache entry that
        serves K slots counts K logical hits (one compile covers them all)."""
        d = lookup(type(node))
        fn = d.protocol(node)

        def batched(prf_arg, *tables, _fn=fn):
            return jax.vmap(lambda *ts: _fn(prf_arg, *ts))(*tables)

        if not self.jit_ops:
            return batched(self.prf, *stacked)
        key = (node.label, node.describe(), self._batch_sig(stacked), ("batch", k))
        jitted = Engine._jit_cache_get(key, count=k)
        if jitted is None:
            profile: Dict = {}

            def traced(prf_arg, *tables, _profile=profile):
                with CommLedger() as led:
                    out = batched(prf_arg, *tables)
                _profile.setdefault("tally", led.tally())
                return out

            jitted = (jax.jit(traced), profile)
            Engine._jit_cache_put(key, jitted)
        jfn, profile = jitted
        out = jfn(self.prf, *stacked)
        if profile.get("tally"):
            t = profile["tally"]
            log_comm(node.label.lower(), int(t["rounds"]), int(t["bytes_per_party"]))
        return out

    @staticmethod
    def _batch_sig(stacked: List[SecretTable]):
        return tuple(
            (
                int(t.valid.shares.shape[-1]),
                tuple(sorted((c, type(v).__name__) for c, v in t.cols.items())),
            )
            for t in stacked
        )

    # -- stateful batch hooks (dispatched via OperatorDef.batch_apply) -------

    def _batch_scan(self, node: PlanNode, ctx: _BatchCtx) -> _BatchVal:
        """All slots read the same secret-shared base table; a zero-copy
        broadcast along the batch axis stands in for K stacked uploads."""
        table = self.tables[node.table]
        for report in ctx.reports:
            report.nodes.append(
                NodeStats(
                    node=node.describe(), n_in=0, n_ins=[], n_out=table.n,
                    seconds=0.0, bytes_per_party=0, rounds=0,
                )
            )
        with obs_trace.span(
            f"node[{node.label}]", op=node.describe(), n_ins=[],
            n_out=table.n, bytes_per_party=0, rounds=0,
            slots=ctx.k, stacked=True,
        ):
            return _BatchVal(k=ctx.k, stacked=_broadcast_table(table, ctx.k))

    def _batch_resize(
        self, node: PlanNode, children: List[_BatchVal], ctx: _BatchCtx
    ) -> _BatchVal:
        """Per-slot reveal-and-trim: slot i's j-th Resize folds exactly the
        noise counter a serial run would have (fresh i.i.d. noise per query —
        one CRT observation each, never merged across tenants). Slots whose
        revealed sizes agree are re-stacked so the rest of the plan stays
        vmapped; divergent sizes split the batch."""
        j = ctx.next_resize_index()
        slots_in = children[0].to_slots()
        outs: List[SecretTable] = []
        bs = self.last_batch_stats
        for i, tbl in enumerate(slots_in):
            self._resize_ctr = ctx.slot_ctr_before(i, j)
            out, stats = self._run_node_slot(node, [tbl])
            ctx.reports[i].nodes.append(stats)
            bs["physical_bytes_per_party"] += stats.bytes_per_party
            bs["physical_rounds"] += stats.rounds
            outs.append(out)
        if all(o.n == outs[0].n for o in outs):
            return _BatchVal(k=ctx.k, stacked=_stack_tables(outs))
        return _BatchVal(k=ctx.k, slots=outs)
