#!/usr/bin/env python
"""Chip smoke: the Reflex query engine's main path, end to end, on a TPU.

Default phase (one process, one chip)::

    python chip_smoke.py

1. **XLA pass.** Synthetic HealthLnK at full schema width (every column of
   ``diagnoses``, ``medications`` and ``demographics``), secret-shared and
   queried through ``ReflexClient.in_process`` with the default
   ``join_algo="auto"`` and the per-key ``pid`` fanout bound declared as
   schema metadata, so the cost model may pick the sort-merge join.
   ``dosage_study`` (join + Resizer) runs at 2^18 rows per table;
   the goldens ``aspirin_count`` (theta join + Resizers), ``diag_breakdown``
   (GROUP BY), ``comorbidity`` (ORDER BY ... LIMIT) and ``med_dosage_avg``
   (a post-reveal aggregate) at 2^16 rows. Each result is checked
   against ``plaintext_oracle``.
2. **Pallas pass.** The same client under ``RuntimeConfig(use_pallas=True)``
   over the 2^16 goldens, which between them reach every kernel: each of
   the seven launch kinds must fire, and the rows must equal the XLA
   pass's.
3. **Cross-device check.** ``dosage_study`` at n=64 on the host CPU and on
   the TPU: rows, revealed trim sizes and per-node ledger tallies must be
   bit-identical (integer ring, so any difference is a bug).

Per query it prints N, wall seconds (the rows are revealed to host memory,
so the device work is done), XLA compile seconds inside that wall time,
the process's peak device memory so far, rows, revealed trim sizes and
kernel launch counts. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Off a TPU (e.g. ``JAX_PLATFORMS=cpu``), or on any failed check, it exits
non-zero and prints no such line.

Four-chip phase (one TPU host with four chips)::

    python chip_smoke.py --parties

Three ``PartyServer`` processes over ``TcpTransport``, party p alone on
chip p; this coordinator holds chip 3. ``dosage_study`` and
``projection_join`` (sort-merge) run through ``ReflexClient.networked`` and
are checked against ``plaintext_oracle``, and every party's wire bytes must
equal its exchange-log bytes and the ledger's. No other phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# dosage_study rows per table. The paper goes to 2^20, but there the
# sort-merge join's scan state (fanout copies x union rows x build columns)
# is estimated at ~20 GB, more than the 16 GB of one v5e: the smoke stops
# at 2^18.
N_JOIN = 1 << 18
N_QUERY = 1 << 16  # rows per table for the other goldens and the Pallas pass
N_PARTIES = 1 << 12  # rows per table on the three-process mesh
N_CROSS = 64  # cross-device check
SEED = 3
GOLDENS = (  # at N_QUERY, in both passes
    "aspirin_count",
    "diag_breakdown",
    "comorbidity",
    "med_dosage_avg",
)
KERNEL_KINDS = (
    "rss_gate",
    "ks_prefix",
    "and_fold",
    "a2b_fused",
    "bit2a_fused",
    "shuffle_gather",
    "bitonic_stage",
)
PARTY_BASE_PORT = 9650
COORDINATOR_CHIP = 3


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number of
    backend compiles, from its own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")


def healthlnk(n: int):
    """Tables, plaintext and a catalog that declares the observed per-pid
    fanout (the schema metadata that makes sort-merge applicable)."""
    import numpy as np

    from repro.data import generate_healthlnk
    from repro.sql.catalog import Catalog

    tables, plain = generate_healthlnk(n=n, seed=SEED)
    mult = {t: {"pid": int(np.bincount(c["pid"]).max())} for t, c in plain.items()}
    return tables, plain, Catalog.from_tables(tables, multiplicity=mult)


def join_algos(plan) -> list:
    from repro.plan.nodes import Join, JoinSortMerge

    out, stack = [], [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, Join):
            out.append("sortmerge" if isinstance(node, JoinSortMerge) else "product")
        stack.extend(node.children())
    return out


def trims(report) -> list:
    return [s.n_out for s in report.nodes if s.node.startswith("Resize")]


def tallies(report) -> list:
    return [(s.node, s.n_in, s.n_out, s.bytes_per_party, s.rounds) for s in report.nodes]


def peak_device_gib():
    """The default device's peak bytes in use since the process started, in
    GiB, or None where the backend keeps no such count (the host CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else round(peak / 2**30, 3)


def run_query(client, name, plain, n, clock, tag):
    """Submit one golden, check it, print its line; returns (ok, rows)."""
    from repro import kernels
    from repro.data import check_rows, plaintext_oracle
    from repro.data.queries import QUERY_SQL

    kernels.reset_launch_counts()
    c0, k0 = clock.seconds, clock.compiles
    t0 = time.perf_counter()
    res = client.submit("smoke", QUERY_SQL[name])
    wall = time.perf_counter() - t0  # rows are host numpy: device work done
    shown, ok = check_rows(name, res.rows, plaintext_oracle(name, plain))
    n_rows = len(next(iter(res.rows.values()), []))
    log(
        f"[{tag}] {name}: n={n} wall_s={wall:.3f} "
        f"xla_compile_s={clock.seconds - c0:.3f} compiles={clock.compiles - k0} "
        f"peak_gib={peak_device_gib()} rows={n_rows} "
        f"joins={join_algos(res.plan)} trims={trims(res.report)} "
        f"launches={kernels.launch_counts()} "
        f"{'OK' if ok else 'MISMATCH ' + repr(shown)[:200]}"
    )
    return ok, res.rows


def same_rows(a, b) -> bool:
    import numpy as np

    return set(a) == set(b) and all(np.array_equal(a[k], b[k]) for k in a)


def cross_device_check() -> bool:
    """dosage_study at N_CROSS on the host CPU and on the default (TPU)
    device: rows, trims and ledger tallies must be bit-identical."""
    import jax

    from repro.data import generate_healthlnk
    from repro.data.queries import QUERY_SQL
    from repro.runtime import ReflexClient

    results = {}
    for dev in (jax.devices("cpu")[0], jax.devices()[0]):
        with jax.default_device(dev):
            tables, _ = generate_healthlnk(n=N_CROSS, seed=SEED)
            client = ReflexClient.in_process(tables, key=jax.random.PRNGKey(0))
            res = client.submit("smoke", QUERY_SQL["dosage_study"])
            client.close()
        placed = {d.platform for d in res.table.valid.shares.devices()}
        results[dev.platform] = (res.rows, trims(res.report), tallies(res.report), placed)
    (cr, ct, cl, cp), (tr, tt, tl, tp) = results["cpu"], results["tpu"]
    ok = cp == {"cpu"} and tp == {"tpu"} and same_rows(cr, tr) and ct == tt and cl == tl
    log(
        f"[cross-device] dosage_study n={N_CROSS}: cpu trims={ct} tpu trims={tt} "
        f"ledger_identical={cl == tl} rows_identical={same_rows(cr, tr)} "
        f"{'OK' if ok else 'MISMATCH'}"
    )
    return ok


def single_chip() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    failures = default_phases(N_JOIN, N_QUERY, cross_device=True)
    if failures:
        log(f"chip_smoke: {failures} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


def default_phases(n_join: int, n: int, cross_device: bool) -> int:
    """The one-chip phases on the default device; returns the number of
    failed checks."""
    import jax

    from repro import kernels
    from repro.config import RuntimeConfig
    from repro.runtime import ReflexClient

    clock = CompileClock()
    failures = 0

    # 1. XLA pass
    tables, plain, catalog = healthlnk(n_join)
    client = ReflexClient.in_process(tables, catalog=catalog, key=jax.random.PRNGKey(0))
    ok, _ = run_query(client, "dosage_study", plain, n_join, clock, "xla")
    failures += not ok
    client.close()
    del tables, client

    tables, plain, catalog = healthlnk(n)
    xla_rows = {}
    client = ReflexClient.in_process(tables, catalog=catalog, key=jax.random.PRNGKey(0))
    for name in GOLDENS:
        ok, xla_rows[name] = run_query(client, name, plain, n, clock, "xla")
        failures += not ok
    client.close()

    # 2. Pallas pass: same tables, key and queries, kernels on
    totals = {}
    client = ReflexClient.in_process(
        tables, catalog=catalog, key=jax.random.PRNGKey(0),
        config=RuntimeConfig(use_pallas=True),
    )
    for name in GOLDENS:
        ok, rows = run_query(client, name, plain, n, clock, "pallas")
        for kind, count in kernels.launch_counts().items():
            totals[kind] = totals.get(kind, 0) + count
        if not same_rows(rows, xla_rows[name]):
            log(f"[pallas] {name}: rows differ from the XLA pass")
            ok = False
        failures += not ok
    client.close()
    missing = [k for k in KERNEL_KINDS if not totals.get(k)]
    log(f"[pallas] launch totals={totals} missing={missing}")
    failures += bool(missing)

    # 3. cross-device check
    if cross_device:
        failures += not cross_device_check()

    cache = Path(jax.config.jax_compilation_cache_dir)
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    log(
        f"compile: {clock.seconds:.3f} s in all, {clock.compiles} backend compiles; "
        f"cache {cache} holds {entries} entries"
    )
    return failures


def parties() -> int:
    """Three party processes, one chip each, behind ReflexClient.networked."""
    from repro.runtime.party import party_env, tpu_chip_env

    # this process takes the fourth chip before its JAX backend starts
    os.environ.update(tpu_chip_env(COORDINATOR_CHIP))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run", file=sys.stderr)
        return 2
    from repro.config import RuntimeConfig
    from repro.data import check_rows, plaintext_oracle
    from repro.data.queries import QUERY_SQL
    from repro.runtime import ReflexClient, connect_tcp

    procs = []
    for p in range(3):
        env = party_env(p, "tpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [str(ROOT / "src")] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        procs.append(subprocess.Popen(
            [sys.executable, str(ROOT / "scripts" / "run_parties.py"),
             "--party", str(p), "--base-port", str(PARTY_BASE_PORT)],
            env=env, stdout=sys.stderr,
        ))
    failures = 0
    try:
        coord = connect_tcp({p: ("127.0.0.1", PARTY_BASE_PORT + p) for p in range(3)})
        devices = [r["device"] for r in sorted(coord.hello(), key=lambda r: r["party"])]
        log(f"[parties] coordinator on {dev.platform} {dev.device_kind}; parties on {devices}")
        if any(d["platform"] != "tpu" or d["count"] != 1 for d in devices):
            log("[parties] every party must hold exactly one TPU chip")
            return 1
        tables, plain, catalog = healthlnk(N_PARTIES)
        client = ReflexClient.networked(
            tables, coordinator=coord, key_seed=0,
            config=RuntimeConfig(join_algo="sortmerge"), catalog=catalog,
        )
        for name in ("dosage_study", "projection_join"):
            t0 = time.perf_counter()
            res = client.submit("smoke", QUERY_SQL[name])
            wall = time.perf_counter() - t0
            shown, ok = check_rows(name, res.rows, plaintext_oracle(name, plain))
            audit = client.service.engine.last_wire_audit
            wire_ok = bool(audit) and all(
                a["ledger_bytes"] == a["exchange_bytes"] == a["wire_bytes"] for a in audit
            )
            algos = join_algos(res.plan)
            ok = ok and wire_ok and algos == ["sortmerge"]
            failures += not ok
            log(
                f"[parties] {name}: n={N_PARTIES} wall_s={wall:.3f} "
                f"rows={len(next(iter(res.rows.values()), []))} joins={algos} "
                f"trims={trims(res.report)} wire={[a['wire_bytes'] for a in audit]} "
                f"wire_equals_ledger={wire_ok} {'OK' if ok else 'MISMATCH'}"
            )
        client.close()  # shuts the parties down
        for pr in procs:
            pr.wait(timeout=120)
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.kill()
                pr.wait()
    if failures:
        log(f"chip_smoke --parties: {failures} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()) + sum(d["count"] for d in devices),
    }}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--parties", action="store_true",
                    help="three party processes on chips 0-2 (needs four chips)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    return parties() if args.parties else single_chip()


if __name__ == "__main__":
    sys.exit(main())
