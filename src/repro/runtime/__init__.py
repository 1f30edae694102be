"""Multi-party runtime: real processes, real sockets, one client API.

Layers (DESIGN.md §16):

* :mod:`~repro.runtime.transport` — length-prefixed CRC-checked framing
  over loopback queues or TCP, with per-link sequence numbers.
* :mod:`~repro.runtime.exchange` — the ring-exchange driver that turns
  every :class:`~repro.core.ledger.CommLedger` sync point into a verified
  wire exchange.
* :mod:`~repro.runtime.party` — one RSS party's server loop.
* :mod:`~repro.runtime.coordinator` — drives three parties, audits
  wire-vs-ledger bytes, reassembles results (:class:`RemoteEngine`).
* :mod:`~repro.runtime.client` — :class:`ReflexClient`, the unified facade
  over in-process and networked execution.
"""
from .client import ReflexClient
from .coordinator import (
    Coordinator,
    RemoteEngine,
    connect_tcp,
    launch_loopback_mesh,
)
from .exchange import RingExchange
from .party import (
    PartyServer,
    decode_table,
    device_info,
    encode_table,
    party_env,
    tpu_chip_env,
)
from .transport import (
    COORD,
    CTRL,
    DATA,
    Frame,
    LoopbackMesh,
    LoopbackTransport,
    TcpTransport,
    Transport,
    decode_frame,
    encode_frame,
)

__all__ = [
    "ReflexClient",
    "Coordinator",
    "RemoteEngine",
    "connect_tcp",
    "launch_loopback_mesh",
    "RingExchange",
    "PartyServer",
    "party_env",
    "tpu_chip_env",
    "device_info",
    "encode_table",
    "decode_table",
    "Transport",
    "LoopbackMesh",
    "LoopbackTransport",
    "TcpTransport",
    "Frame",
    "encode_frame",
    "decode_frame",
    "DATA",
    "CTRL",
    "COORD",
]
