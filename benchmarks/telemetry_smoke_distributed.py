"""CI distributed-telemetry smoke: a traced query over a real 3-process mesh.

Launches three party processes on localhost TCP (``scripts/run_parties.py``),
drives a traced workload through :class:`~repro.runtime.ReflexClient` in
networked mode, and writes the distributed-observability artifacts under
``benchmarks/out/`` (gitignored):

* ``TELEMETRY_distributed_spans.jsonl``  — the MERGED distributed trace:
  coordinator spans plus every party's redacted spans, one trace_id,
  clock-offset-normalized, party-attributed (DESIGN.md §17)
* ``TELEMETRY_distributed_trace.chrome.json`` — the same trace as Chrome
  trace-event JSON (load in chrome://tracing or Perfetto; one row per party)
* ``TELEMETRY_distributed_metrics.json`` — the service registry snapshot
  after a ``status()`` pull, so the ``reflex_wire_*`` mesh series are live

``benchmarks/validate_telemetry.py`` checks the span artifact against
``telemetry_distributed_span_schema.json`` — which additionally requires a
single trace_id spanning >= 3 attributed parties and re-runs the secret-key
deny-list audit over the party-shipped spans — and the metrics artifact
against ``telemetry_distributed_metrics_schema.json`` (wire metric kinds +
the party/link label vocabulary).

Usage::

    PYTHONPATH=src python benchmarks/telemetry_smoke_distributed.py \
        [--base-port 9800] [--n 32]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
SPANS_PATH = os.path.join(OUT_DIR, "TELEMETRY_distributed_spans.jsonl")
CHROME_PATH = os.path.join(OUT_DIR, "TELEMETRY_distributed_trace.chrome.json")
METRICS_PATH = os.path.join(OUT_DIR, "TELEMETRY_distributed_metrics.json")

JOIN_SQL = (
    "SELECT DISTINCT d.pid FROM diagnoses d, medications m "
    "WHERE d.pid = m.pid AND m.med = 1"
)
COUNT_SQL = "SELECT COUNT(*) FROM diagnoses WHERE diag = 414"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base-port", type=int, default=9800)
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args()

    # coordinator and parties on the CPU, said explicitly: a parent that
    # took an accelerator would lock its children out of it
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.data.healthlnk import generate_healthlnk
    from repro.obs import Tracer
    from repro.obs.distributed import write_chrome_trace
    from repro.runtime import ReflexClient, connect_tcp, party_env

    os.makedirs(OUT_DIR, exist_ok=True)
    here = os.path.dirname(os.path.abspath(__file__))
    run_parties = os.path.join(here, "..", "scripts", "run_parties.py")
    procs = [
        subprocess.Popen(
            [
                sys.executable, run_parties,
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
        print("[dist-smoke] coordinator connected to 3 party processes")

        tables, _ = generate_healthlnk(n=args.n, seed=args.seed)
        client = ReflexClient.networked(tables, coordinator=coord, key_seed=0)
        with Tracer() as tr:
            client.submit("alice", JOIN_SQL)
            client.submit("alice", COUNT_SQL)
        parties = sorted(
            {s.attrs["party"] for s in tr.spans if "party" in s.attrs}
        )
        trace_ids = {tr.trace_id}
        print(
            f"[dist-smoke] merged trace: {len(tr.spans)} spans, "
            f"trace_id={tr.trace_id}, parties={parties}, "
            f"{len(tr.redactions)} secret attrs redacted"
        )
        tr.write(SPANS_PATH)
        write_chrome_trace(CHROME_PATH, tr.spans, trace_id=tr.trace_id)

        # networked EXPLAIN ANALYZE: the net-stall column plus the per-party
        # wire trailer must render over a real TCP mesh
        text, _res = client.explain_analyze("alice", COUNT_SQL)
        print(text)
        if "net stall" not in text or "wire:" not in text:
            print("[dist-smoke] FAILED: explain lacks network attribution")
            return 1

        # status() pulls the `stats` verb and publishes reflex_wire_* series
        st = client.status()
        mesh = st["runtime"]["mesh"]
        if not mesh["ok"] or len(mesh["parties"]) != 3:
            print(f"[dist-smoke] FAILED: mesh health {mesh}")
            return 1
        print(
            "[dist-smoke] mesh health: "
            + "  ".join(
                f"p{p['party']}: up={p['up']} sent={p['bytes']['sent']}B "
                f"rejects={p['rejects']}"
                for p in mesh["parties"]
            )
        )
        with open(METRICS_PATH, "w") as f:
            json.dump(
                client.service.metrics_snapshot(), f, indent=2, sort_keys=True
            )
        client.close()
        if len(parties) < 3 or len(trace_ids) != 1:
            print("[dist-smoke] FAILED: trace does not span all 3 parties")
            return 1
        print(
            f"wrote {os.path.normpath(SPANS_PATH)}, "
            f"{os.path.normpath(CHROME_PATH)}, "
            f"{os.path.normpath(METRICS_PATH)}"
        )
        return 0
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            pr.wait(timeout=30)


if __name__ == "__main__":
    sys.exit(main())
