#!/usr/bin/env python
"""3-process runtime smoke test (the CI `runtime-smoke` job).

Launches three real party processes on localhost TCP, drives two golden
queries through :class:`~repro.runtime.ReflexClient` in networked mode —
one resized join (``dosage_study``) and one sort-merge join
(``projection_join`` under ``join_algo="sortmerge"``) — and fails on any
divergence from the single-process oracle:

* result rows must match bit-for-bit,
* per-node ledger tallies must match,
* each party's wire bytes must equal its exchange-log bytes and the
  report's ledger bytes (audited inside RemoteEngine; re-printed here).

Every process runs on the CPU, set explicitly: the parties through
``repro.runtime.party_env(p, "cpu")``, the coordinator and its oracle by
``JAX_PLATFORMS=cpu`` before JAX starts. The TPU form of this check, one
chip per party, is ``python chip_smoke.py --parties``.

Exit code 0 = all checks passed.

Usage::

    PYTHONPATH=src python scripts/runtime_smoke.py [--base-port 9700] [--n 64]
"""
import argparse
import os
import subprocess
import sys

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base-port", type=int, default=9700)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args()

    # parties and the in-process oracle all on the CPU, said explicitly: a
    # parent that took an accelerator would lock its children out of it,
    # and the audit compares the oracle and the parties byte by byte
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.config import RuntimeConfig
    from repro.data.healthlnk import generate_healthlnk
    from repro.data.queries import QUERY_SQL
    from repro.runtime import ReflexClient, connect_tcp, party_env

    cfg = RuntimeConfig(join_algo="sortmerge")
    goldens = ["dosage_study", "projection_join"]

    here = os.path.dirname(os.path.abspath(__file__))
    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.join(here, "run_parties.py"),
                "--party", str(p), "--base-port", str(args.base_port),
            ],
            env=party_env(p, "cpu"),
        )
        for p in range(3)
    ]
    try:
        coord = connect_tcp(
            {p: ("127.0.0.1", args.base_port + p) for p in range(3)}
        )
        print("[smoke] coordinator connected to 3 party processes")

        tables, _ = generate_healthlnk(n=args.n, seed=args.seed)
        oracle_tables, _ = generate_healthlnk(n=args.n, seed=args.seed)
        client = ReflexClient.networked(
            tables, coordinator=coord, key_seed=0, config=cfg
        )
        oracle = ReflexClient.in_process(
            oracle_tables, offline="off", config=cfg
        )

        failures = 0
        for name in goldens:
            sql = QUERY_SQL[name]
            want = oracle.submit("smoke", sql)
            got = client.submit("smoke", sql)
            ok = set(want.rows) == set(got.rows) and all(
                np.array_equal(want.rows[k], got.rows[k]) for k in want.rows
            )
            wd, gd = want.report.to_dict(), got.report.to_dict()
            ok = ok and wd["total_bytes"] == gd["total_bytes"] \
                and wd["total_rounds"] == gd["total_rounds"]
            audit = client.service.engine.last_wire_audit
            for a in audit:
                ok = ok and (
                    a["ledger_bytes"] == a["exchange_bytes"] == a["wire_bytes"]
                )
            status = "OK" if ok else "DIVERGED"
            failures += 0 if ok else 1
            print(
                f"[smoke] {name}: {status} "
                f"rows={len(next(iter(got.rows.values()), []))} "
                f"ledger_bytes={gd['total_bytes']} "
                f"wire={[a['wire_bytes'] for a in audit]}"
            )
        client.close()
        oracle.close()
        if failures:
            print(f"[smoke] FAILED: {failures} golden(s) diverged")
            return 1
        print("[smoke] all goldens bit-exact; wire bytes == ledger bytes")
        return 0
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            pr.wait(timeout=30)


if __name__ == "__main__":
    sys.exit(main())
