"""SecretTable: a relation under 3-party replicated secret sharing.

Columns are XOR-shared 32-bit words (:class:`BShare`) — the comparison-friendly
representation (Secrecy-style). Aggregate columns produced by GroupBy live as
arithmetic shares (:class:`AShare`) and are converted lazily (``a2b``) when a
downstream operator needs to compare or sort on them.

``valid`` is the secret single-bit column marking true output tuples (§2.2 of
the paper). The *public* row count ``n`` is the oblivious size N.

Lazy columns
------------
A column may also be a :class:`LazyGather` — a deferred row-gather view
``value = base[index]`` of a physical base column, with a *public* index map.
The oblivious join produces these instead of materializing every payload
column at the |R1| x |R2| Cartesian size: the N1*N2-row table then costs
O(N1*N2) (the valid column + index maps) instead of O(N1*N2 * cols), and the
next Resizer gathers only the S surviving rows from the base tables
(DESIGN.md §7.2). Gathers with public indices compose lazily
(``gather_rows``); the first operator that needs the physical shares
(``col`` / ``bshare_col``) materializes in place.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.circuits import a2b
from ..core.prf import PRFSetup
from ..core.sharing import AShare, BShare, share_b, reveal_a, reveal_b

Share = Union[AShare, BShare]

__all__ = ["SecretTable", "LazyGather", "gather_log", "reset_gather_log", "table_nbytes"]


# Instrumentation: every physical gather realized from a LazyGather records
# its output row count here (tests assert payload is never expanded to the
# product-grid size before trim; the benchmarks report peak realized rows).
# Thread-local (concurrent engines must not interleave) and bounded (a
# serving session materializes lazy columns on every query, forever).
_GATHER_LOG_MAX = 4096
_GATHER_STATE = threading.local()


def _gather_log() -> "deque":
    if not hasattr(_GATHER_STATE, "log"):
        _GATHER_STATE.log = deque(maxlen=_GATHER_LOG_MAX)
    return _GATHER_STATE.log


def gather_log() -> List[int]:
    return list(_gather_log())


def reset_gather_log() -> None:
    _gather_log().clear()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LazyGather:
    """Deferred row-gather view of a base column: ``value = base[index]``.

    ``index`` is public (it encodes only *structure* — e.g. the Cartesian
    product layout row -> (i, j) — never data). Composing a further public
    gather stays lazy; padding or any share-level access materializes.
    """

    base: Share
    index: jnp.ndarray  # (n,) public int32 row map into base

    # -- pytree ---------------------------------------------------------------
    def tree_flatten(self):
        return (self.base, self.index), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- structure ------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self.index.shape) + self.base.shape[1:]

    @property
    def size(self) -> int:
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ring(self):
        return self.base.ring

    # -- lazy ops -------------------------------------------------------------
    def take(self, indices, axis: int = 0) -> "LazyGather":
        if axis != 0:
            raise ValueError("LazyGather only supports row (axis 0) gathers")
        return LazyGather(self.base, jnp.take(self.index, indices, axis=0))

    def gather(self, rows) -> Share:
        """Materialize only the given output rows: ``base[index[rows]]`` —
        the Resizer's trim-time path (O(S) rows, never the full view)."""
        idx = jnp.take(self.index, jnp.asarray(rows), axis=0)
        _gather_log().append(int(idx.shape[0]))
        return self.base.take(idx, axis=0)

    def materialize(self) -> Share:
        _gather_log().append(int(self.index.shape[0]))
        return self.base.take(self.index, axis=0)

    def pad_rows(self, n_rows: int) -> Share:
        return self.materialize().pad_rows(n_rows)

    def nbytes(self) -> int:
        """Actual backing-store footprint: base shares + public index map."""
        return int(self.base.shares.nbytes) + int(self.index.nbytes)


Column = Union[AShare, BShare, LazyGather]


def table_nbytes(table: "SecretTable") -> int:
    """Physical bytes held by a table (share arrays + lazy index maps) —
    the benchmarks' intermediate-size metric. Aliased buffers (e.g. the one
    product-layout index map shared by every LazyGather of the same side)
    are counted once."""
    seen = set()
    total = 0

    def add(arr) -> None:
        nonlocal total
        if id(arr) not in seen:
            seen.add(id(arr))
            total += int(arr.nbytes)

    add(table.valid.shares)
    for c in table.cols.values():
        if isinstance(c, LazyGather):
            add(c.base.shares)
            add(c.index)
        else:
            add(c.shares)
    return total


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SecretTable:
    cols: Dict[str, Column]
    valid: BShare  # (n,) single-bit

    # -- pytree ---------------------------------------------------------------
    def tree_flatten(self):
        # Preserve insertion order: protocols derive per-column PRF folds from
        # dict position (e.g. bitonic_sort's select gates), so a table that
        # round-trips through a jax transform (vmap in the batched engine
        # pass, jit) must reconstruct with the same column order it was
        # built with — sorting here would silently re-key that randomness.
        names = tuple(self.cols)
        return tuple(self.cols[k] for k in names) + (self.valid,), names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(dict(zip(names, children[:-1])), children[-1])

    # -- structure ------------------------------------------------------------
    @property
    def n(self) -> int:
        return self.valid.shape[0]

    @property
    def width_bytes(self) -> int:
        """Plaintext row width in bytes (columns + valid bit word)."""
        return 4 * (len(self.cols) + 1)

    def column_names(self):
        return list(self.cols)

    def lazy_names(self):
        return [k for k, v in self.cols.items() if isinstance(v, LazyGather)]

    def select_columns(self, names) -> "SecretTable":
        return SecretTable({k: self.cols[k] for k in names}, self.valid)

    def rename(self, mapping: Dict[str, str]) -> "SecretTable":
        return SecretTable(
            {mapping.get(k, k): v for k, v in self.cols.items()}, self.valid
        )

    def with_prefix(self, prefix: str) -> "SecretTable":
        return SecretTable(
            {f"{prefix}.{k}" if "." not in k else k: v for k, v in self.cols.items()},
            self.valid,
        )

    def gather_rows(self, idx) -> "SecretTable":
        """Public row gather; lazy columns compose (stay lazy)."""
        return SecretTable(
            {k: v.take(idx, axis=0) for k, v in self.cols.items()},
            self.valid.take(idx, axis=0),
        )

    def pad_rows(self, n_rows: int) -> "SecretTable":
        """Pad with rows whose shares are all-zero: value 0, valid 0 — a valid
        sharing of an invalid filler tuple. (Materializes lazy columns: filler
        shares cannot be represented as a base-row view.)"""
        return SecretTable(
            {k: v.pad_rows(n_rows) for k, v in self.cols.items()},
            self.valid.pad_rows(n_rows),
        )

    def col(self, name: str) -> Share:
        """Column as physical shares — first direct access materializes a
        lazy column in place (cached for later operators)."""
        c = self.cols[name]
        if isinstance(c, LazyGather):
            c = c.materialize()
            self.cols[name] = c
        return c

    def bshare_col(self, name: str, prf: PRFSetup) -> BShare:
        """Column as BShare, converting from AShare if necessary."""
        col = self.col(name)
        if isinstance(col, AShare):
            return a2b(col, prf)
        return col

    # -- I/O (data-owner side / test oracle) ----------------------------------
    @classmethod
    def from_plaintext(
        cls,
        data: Dict[str, np.ndarray],
        key: jax.Array,
        valid: Optional[np.ndarray] = None,
    ) -> "SecretTable":
        n = len(next(iter(data.values())))
        keys = jax.random.split(key, len(data) + 1)
        cols = {
            name: share_b(np.asarray(vals, dtype=np.uint32), k)
            for (name, vals), k in zip(data.items(), keys[:-1])
        }
        v = np.ones(n, dtype=np.uint32) if valid is None else np.asarray(valid, np.uint32)
        return cls(cols, share_b(v, keys[-1]))

    def reveal(self) -> Dict[str, np.ndarray]:
        """Open everything (tests / final results only)."""
        from ..obs import trace as obs_trace

        opened = {}
        for k in self.cols:
            v = self.col(k)
            opened[k] = reveal_a(v) if isinstance(v, AShare) else reveal_b(v)
        opened["_valid"] = reveal_b(self.valid)
        with obs_trace.span("device.wait", what="reveal"):
            out = {k: np.asarray(v) for k, v in opened.items()}
        out["_valid"] = out["_valid"] & 1
        return out

    def reveal_true_rows(self) -> Dict[str, np.ndarray]:
        d = self.reveal()
        mask = d.pop("_valid").astype(bool)
        return {k: v[mask] for k, v in d.items()}
