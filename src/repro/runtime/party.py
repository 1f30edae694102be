"""PartyServer: one RSS party's execution loop.

A party server owns two transports:

* a **control link** to the coordinator (CTRL frames carrying pickled
  messages: hello / load_tables / execute / stats / shutdown), and
* a **data mesh** to the other two parties (DATA frames: one per ledger
  sync point, driven by :class:`~repro.runtime.exchange.RingExchange`).

On ``execute`` it runs its local :class:`~repro.engine.Engine` over the
shipped plan — eager (``jit_ops=False``: jit re-executions skip the Python
protocol bodies, and with them the exchange boundaries), under the
mesh-wide :class:`~repro.config.RuntimeConfig` the coordinator shipped —
with the ring exchange installed, so every ledger entry is a real framed
wire exchange verified against the peer. It replies with its *own share
slice* of the output columns (party ``p`` contributes canonical share
``s_p``; the coordinator reassembles the triple from three distinct
slices, which is bit-exact only if all three processes computed identical
triples), the execution report, the per-op exchange log (or its capped
deterministic summary) for the wire-vs-ledger audit, the per-query network
stall total, and — when the coordinator shipped a trace context — this
party's redacted spans plus the control-frame clock stamps the coordinator
uses for clock-offset normalization (DESIGN.md §17).

The same class serves both process topologies: ``scripts/run_parties.py``
runs it standalone over :class:`TcpTransport`; the in-process tests run it
on a thread over :class:`LoopbackTransport`. Thread-local engine/ledger/
tracer state means three party threads in one process stay fully isolated.
"""
from __future__ import annotations

import contextlib
import os
import pickle
import time
import traceback
from typing import Dict, Mapping, Optional

import jax
import numpy as np

from ..config import RuntimeConfig
from ..core.ledger import exchange_scope
from ..core.sharing import AShare, BShare
from ..engine.executor import Engine
from ..errors import TransportError
from ..obs import trace as obs_trace
from ..ops.table import SecretTable
from .exchange import RingExchange
from .transport import COORD, CTRL, Transport

__all__ = [
    "PartyServer",
    "encode_table",
    "decode_table",
    "device_info",
    "party_env",
    "tpu_chip_env",
    "PARTY_PLATFORMS",
]

PARTY_PLATFORMS = ("cpu", "tpu")
TPU_PROCESS_PORT_BASE = 8476  # libtpu's default port, one per chip process


def party_env(
    party: int, platform: str, base: Optional[Mapping[str, str]] = None
) -> Dict[str, str]:
    """Environment for one party process, explicit about its device.

    ``cpu`` pins JAX to the CPU (CI's three-process runs). ``tpu`` gives the
    process chip ``party`` of the host and nothing else, so three parties
    hold three chips side by side (a process that sees the whole host would
    take every chip and lock the others out). All three parties must get
    the same platform: their replicated computations are audited for
    equality byte by byte."""
    if platform not in PARTY_PLATFORMS:
        raise ValueError(f"party platform {platform!r} (expected cpu|tpu)")
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = platform
    if platform == "tpu":
        env.update(tpu_chip_env(party))
    return env


def tpu_chip_env(chip: int) -> Dict[str, str]:
    """libtpu variables that make a process see exactly one chip of a
    multi-chip host."""
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_PROCESS_PORT": str(TPU_PROCESS_PORT_BASE + chip),
    }


def device_info() -> Dict:
    """The devices this process computes on, as JAX reports them."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def encode_table(table: SecretTable) -> Dict:
    """SecretTable -> picklable dict of full canonical share triples (the
    replicated-simulation contract: every party holds the whole triple;
    see DESIGN.md §16.3)."""
    cols = {}
    for name in table.column_names():
        c = table.col(name)  # materializes lazy views
        cols[name] = (
            "a" if isinstance(c, AShare) else "b",
            np.asarray(c.shares),
        )
    return {"cols": cols, "valid": np.asarray(table.valid.shares)}


def decode_table(d: Dict) -> SecretTable:
    import jax.numpy as jnp

    cols = {}
    for name, (kind, arr) in d["cols"].items():
        sh = jnp.asarray(arr)
        cols[name] = AShare(sh) if kind == "a" else BShare(sh)
    return SecretTable(cols, BShare(jnp.asarray(d["valid"])))


class PartyServer:
    def __init__(
        self,
        party: int,
        ctrl: Transport,
        data: Transport,
        *,
        fault_after: Optional[int] = None,
        exchange_timeout: float = 60.0,
    ):
        self.party = party
        self.ctrl = ctrl
        self.data = data
        self.fault_after = fault_after
        self.exchange_timeout = exchange_timeout
        self.engine: Optional[Engine] = None
        self.queries = 0

    # -- control-message helpers ---------------------------------------------
    def _reply(self, msg: Dict) -> None:
        self.ctrl.send(COORD, msg["type"], pickle.dumps(msg), kind=CTRL)

    def _handle_load_tables(self, msg: Dict) -> Dict:
        tables = {name: decode_table(d) for name, d in msg["tables"].items()}
        cfg = (
            RuntimeConfig.from_dict(msg["config"])
            if msg.get("config") is not None
            else None
        )
        self.engine = Engine(
            tables,
            key=jax.random.PRNGKey(int(msg["key_seed"])),
            jit_ops=False,  # exchange boundaries require eager protocol bodies
            config=cfg,
        )
        return {
            "type": "load_ack",
            "party": self.party,
            "tables": sorted(tables),
        }

    def _handle_execute(self, msg: Dict) -> Dict:
        t_recv = time.time()  # control-frame receipt on THIS party's clock
        if self.engine is None:
            return {
                "type": "error",
                "party": self.party,
                "error": "execute before load_tables",
                "reason": "protocol",
            }
        plan = pickle.loads(msg["plan"])
        base = msg.get("resize_ctr_base")
        if base is not None and self.engine._resize_ctr != base:
            # lockstep invariant: every party must fold the same noise
            # counters, or Resize draws diverge silently
            return {
                "type": "error",
                "party": self.party,
                "error": (
                    f"resize counter desync: party at "
                    f"{self.engine._resize_ctr}, coordinator at {base}"
                ),
                "reason": "divergence",
            }
        drv = RingExchange(
            self.data,
            self.party,
            timeout=self.exchange_timeout,
            fault_after=self.fault_after,
        )
        # trace-context propagation (DESIGN.md §17): a traced coordinator
        # ships (trace_id, parent_span_id); this query runs under a fresh
        # per-query tracer carrying that id, and the reply ships the
        # party's redacted spans back for the coordinator-side merge. An
        # untraced execute runs with no tracer at all — zero overhead.
        tctx = msg.get("trace")
        tracer = (
            obs_trace.Tracer(party=self.party, trace_id=tctx["trace_id"])
            if tctx is not None
            else None
        )
        cm = tracer if tracer is not None else contextlib.nullcontext()
        wire_before = self.data.sent_bytes  # counters span queries; audit per
        with cm, exchange_scope(drv):
            out, report = self.engine.execute(plan)
        self.queries += 1
        slices = {}
        for name in out.column_names():
            c = out.col(name)
            slices[name] = (
                "a" if isinstance(c, AShare) else "b",
                np.asarray(c.shares[self.party]),
            )
        # cap the shipped exchange log: large plans produce thousands of
        # per-op entries; past the cap the reply carries the deterministic
        # summary (exact byte/round totals) instead of the full list
        cap = int(msg.get("exchange_log_cap") or 0)
        log = drv.log if not (cap and len(drv.log) > cap) else drv.log_summary()
        reply = {
            "type": "result",
            "party": self.party,
            "cols": slices,
            "valid": np.asarray(out.valid.shares[self.party]),
            "report": report.to_dict(),
            "exchange_log": log,
            "wire_bytes": self.data.sent_bytes - wire_before,
            "stall_seconds": drv.stall_seconds,
            "resize_ctr": self.engine._resize_ctr,
            "clock": {"t_recv": t_recv, "t_reply": time.time()},
        }
        if tracer is not None:
            reply["trace_id"] = tracer.trace_id
            reply["spans"] = [s.to_dict() for s in tracer.spans]
            reply["redactions"] = len(tracer.redactions)
        return reply

    def _handle_stats(self) -> Dict:
        """Mesh-health snapshot for the ``stats`` control verb: this party's
        cumulative wire counters (data mesh + control link) and query count.
        Read-only — never touches engine state."""
        wire = self.data.wire_snapshot()
        if self.ctrl is not self.data:
            extra = self.ctrl.wire_snapshot()
            for k in ("sent", "recv", "rejects", "connects", "links"):
                wire[k] = wire[k] + extra[k]
        return {
            "type": "stats",
            "party": self.party,
            "queries": self.queries,
            "wire": wire,
            "clock": {"t_recv": time.time(), "t_reply": time.time()},
        }

    # -- main loop ------------------------------------------------------------
    def serve(self) -> None:
        """Process control messages until shutdown (or a fatal transport
        failure). Execution errors are reported to the coordinator and the
        loop continues; an injected crash (``fault_after``) tears the whole
        server down the way a dead process would."""
        while True:
            try:
                frame = self.ctrl.recv(COORD, timeout=None)
            except TransportError:
                return  # coordinator is gone; nothing to serve
            msg = pickle.loads(frame.body)
            mtype = msg.get("type")
            try:
                if mtype == "hello":
                    self._reply({
                        "type": "hello_ack",
                        "party": self.party,
                        "device": device_info(),
                    })
                elif mtype == "load_tables":
                    self._reply(self._handle_load_tables(msg))
                elif mtype == "execute":
                    self._reply(self._handle_execute(msg))
                elif mtype == "stats":
                    self._reply(self._handle_stats())
                elif mtype == "shutdown":
                    self._reply({"type": "bye", "party": self.party})
                    return
                else:
                    self._reply({
                        "type": "error",
                        "party": self.party,
                        "error": f"unknown message type {mtype!r}",
                        "reason": "protocol",
                    })
            except TransportError as e:
                if e.reason == "crashed" and self.fault_after is not None:
                    return  # injected crash: die silently, like a real one
                try:
                    self._reply({
                        "type": "error",
                        "party": self.party,
                        "error": str(e),
                        "reason": e.reason,
                    })
                except TransportError:
                    return
            except Exception as e:  # report, keep serving
                self._reply({
                    "type": "error",
                    "party": self.party,
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc(),
                    "reason": "execution",
                })

    def close(self) -> None:
        self.ctrl.close()
        self.data.close()
