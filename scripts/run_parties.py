#!/usr/bin/env python
"""Launch RSS party server(s) over TCP.

One process per party (the production topology)::

    PYTHONPATH=src python scripts/run_parties.py --party 0 &
    PYTHONPATH=src python scripts/run_parties.py --party 1 &
    PYTHONPATH=src python scripts/run_parties.py --party 2 &

or a compose-style launcher that forks all three and waits::

    PYTHONPATH=src python scripts/run_parties.py --party all

The forked parties are pinned to the CPU (``repro.runtime.party_env``), CI's
topology; the launcher itself starts no JAX backend. A single ``--party p``
runs on whatever its environment selects (``chip_smoke.py --parties`` gives
each its own TPU chip).

Parties listen on ``base_port + party`` and build the pair mesh among
themselves (party p dials every lower-numbered party; higher-numbered
parties dial in). The coordinator (see ``repro.runtime.connect_tcp`` /
``scripts/runtime_smoke.py``) dials all three and ships tables, the engine
key seed, and the mesh-wide RuntimeConfig — party processes hold no data
until then.

Each server runs until the coordinator sends ``shutdown`` (or its stdin
pipeline is torn down). See scripts/compose.yaml for the service layout.
"""
import argparse
import os
import signal
import subprocess
import sys


def serve_one(party: int, host: str, base_port: int) -> None:
    from repro.runtime import PartyServer, TcpTransport

    endpoints = {p: (host, base_port + p) for p in range(3)}
    tr = TcpTransport(party, endpoints)
    bound = tr.listen()
    print(f"[party {party}] listening on {bound[0]}:{bound[1]}", flush=True)
    for q in range(3):
        if q < party:
            tr.dial(q)
    for q in range(3):
        if q > party:
            tr.wait_for(q, timeout=60.0)
    import jax

    dev = jax.devices()[0]
    print(
        f"[party {party}] mesh up on {dev.platform} ({dev.device_kind}); "
        "serving",
        flush=True,
    )
    server = PartyServer(party, tr, tr)
    try:
        server.serve()
    finally:
        server.close()
    print(f"[party {party}] shut down", flush=True)


def launch_all(host: str, base_port: int) -> int:
    """Compose-style launcher: three party processes pinned to the CPU,
    torn down together."""
    from repro.runtime.party import party_env

    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--party", str(p), "--host", host,
                "--base-port", str(base_port),
            ],
            env=party_env(p, "cpu"),
        )
        for p in range(3)
    ]

    def tear_down(*_sig):
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()

    signal.signal(signal.SIGINT, tear_down)
    signal.signal(signal.SIGTERM, tear_down)
    rc = 0
    for pr in procs:
        rc = max(rc, pr.wait())
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--party", required=True,
                    help="party id 0..2, or 'all' to fork the full mesh")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--base-port", type=int, default=9600,
                    help="party p listens on base-port + p (default 9600)")
    args = ap.parse_args()
    if args.party == "all":
        return launch_all(args.host, args.base_port)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    serve_one(int(args.party), args.host, args.base_port)
    return 0


if __name__ == "__main__":
    sys.exit(main())
