"""HealthLnK workloads end-to-end, SQL edition: the paper's four queries
(Table 2) submitted as SQL strings through the unified
:class:`~repro.runtime.ReflexClient` facade (over the multi-tenant
AnalyticsService) — parse -> optimize -> Resizer placement -> execute,
with plan-cache and CRT-budget telemetry, result validation against the
plaintext oracle, and a runtime + communication comparison across
fully-oblivious / Reflex / revealed placements (the Fig. 8 experiment,
interactive edition). Ends with the batched-admission demo: many tenants'
identical queries enqueued and drained as ONE stacked engine pass
(DESIGN.md §11), with bit-identical results and amortized rounds.

Run:  PYTHONPATH=src python examples/healthlnk_queries.py [n_rows]
"""
import sys
import time

import jax

from repro.core.noise import NoTrim, RevealNoise, TruncatedLaplace
from repro.data import check_rows, generate_healthlnk, plaintext_oracle
from repro.data.queries import QUERY_SQL
from repro.runtime import ReflexClient
from repro.service import PrivacyAccountant


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    tables, plain = generate_healthlnk(
        n=n, seed=3, aspirin_frac=0.35, icd_heart_frac=0.3
    )
    tlap = TruncatedLaplace(eps=0.5, delta=5e-5, sensitivity=max(n // 8, 1))
    modes = {
        "fully_oblivious": dict(noise=NoTrim(), placement="none"),
        "reflex": dict(noise=tlap, placement="all_internal"),
        "revealed": dict(noise=RevealNoise(), placement="all_internal"),
    }
    print(
        f"{'query':<16}{'mode':<18}{'sec':>8}{'MiB/party':>12}{'rounds':>9}"
        f"{'cache':>7}  result"
    )
    for mode, cfg in modes.items():
        svc = ReflexClient.in_process(
            tables,
            accountant=PrivacyAccountant(policy="escalate"),
            key=jax.random.PRNGKey(5),
            **cfg,
        )
        session = svc.session("example")
        for qname, sql in QUERY_SQL.items():
            res = session.submit(sql)
            shown, ok = check_rows(qname, res.rows, plaintext_oracle(qname, plain))
            print(
                f"{qname:<16}{mode:<18}{res.report.total_seconds:>8.2f}"
                f"{res.report.total_bytes / 2**20:>12.3f}"
                f"{res.report.total_rounds:>9}"
                f"{'hit' if res.cache_hit else 'miss':>7}"
                f"  {'OK' if ok else 'MISMATCH'} {shown}"
            )
        # resubmit the first query: the plan cache serves it, and the
        # accountant keeps charging the CRT budget per disclosure
        res = session.submit(QUERY_SQL["comorbidity"])
        stats = svc.cache_stats()
        print(
            f"  [{mode}] plan-cache hit rate {stats['hit_rate']:.0%} "
            f"({stats['hits']}/{stats['hits'] + stats['misses']}), "
            f"escalations {svc.service.accountant.escalation_count}"
        )
    # a fresh service under a tight budget: watch the escalation ladder fire
    print("\nescalation-ladder demo (fresh tight-budget service):")
    svc = ReflexClient.in_process(
        tables,
        noise=TruncatedLaplace(eps=2.0, sensitivity=1),
        addition="sequential",
        placement="after_joins",
        accountant=PrivacyAccountant(policy="escalate"),
        key=jax.random.PRNGKey(7),
    )
    session = svc.session("attacker")
    for i in range(6):
        res = session.submit(QUERY_SQL["dosage_study"])
        note = (
            "escalated -> " + res.escalations[-1]["to"].split("|")[0]
            if res.escalations
            else "ok"
        )
        print(f"  submit {i + 1}: {note}")
    for st in svc.service.accountant.status():
        print(
            f"  {st['strategy'].split('|')[0]:<60} observed {st['observed']}"
            f"/{st['budget']}"
        )

    # batched admission: 8 tenants ask the same GROUP BY — the scheduler
    # buckets them and the engine answers all of them with one stacked pass
    print("\nbatched-admission demo (8 tenants, one engine pass):")
    sql = "SELECT major_icd9, COUNT(*) AS c FROM diagnoses GROUP BY major_icd9"
    tenants = [f"clinic_{i}" for i in range(8)]
    mk = lambda seed: ReflexClient.in_process(
        tables, noise=NoTrim(), placement="none", jit_ops=True,
        key=jax.random.PRNGKey(seed), batch_wait_s=60.0,
    )
    svc_serial = mk(5)
    svc_serial.submit("warm", sql)
    t0 = time.perf_counter()
    serial = [svc_serial.submit(t, sql) for t in tenants]
    t_serial = time.perf_counter() - t0

    svc_batch = mk(5)
    for t in tenants:  # warm drain: compiles the 8-slot batched programs
        svc_batch.session(t).enqueue(sql)
    svc_batch.drain()
    t0 = time.perf_counter()  # include enqueue: same work the serial timer sees
    for t in tenants:
        svc_batch.session(t).enqueue(sql)
    results = svc_batch.drain()
    t_batch = time.perf_counter() - t0
    same = all(
        all((rs.rows[c] == rb.rows[c]).all() for c in rs.rows)
        for rs, rb in zip(serial, results)
    )
    bs = svc_batch.service.engine.last_batch_stats
    print(
        f"  serial {len(tenants)/t_serial:7.1f} q/s   "
        f"batched {len(results)/t_batch:7.1f} q/s   "
        f"({t_serial/t_batch:.2f}x, results identical: {same})"
    )
    print(
        f"  physical pass: {bs['slots']} slots, {bs['stacked_nodes']} stacked "
        f"ops, {bs['physical_rounds']} rounds total vs "
        f"{sum(r.report.total_rounds for r in results)} if run serially"
    )
    print(f"  scheduler: {svc_batch.service.scheduler.stats}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
