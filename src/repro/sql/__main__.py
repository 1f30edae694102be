"""CLI: parse/compile SQL against the HealthLnK catalog.

    python -m repro.sql --check            # goldens + dialect execution smoke
    python -m repro.sql "SELECT ..."       # pretty-print the compiled plan
    python -m repro.sql --explain ["SQL"]  # plan tree + cost estimates
    python -m repro.sql --explain-analyze ["SQL"]
                                           # execute on synthetic HealthLnK
                                           # data: estimates vs actuals per
                                           # node (+ resizer trim outcomes)
    python -m repro.sql --explain-analyze --networked ["SQL"]
                                           # same, but executed on a 3-party
                                           # loopback mesh via ReflexClient
    python -m repro.sql --explain-analyze --networked --trace-out PATH ["SQL"]
                                           # also write the merged distributed
                                           # trace (JSONL + Chrome trace JSON)

``--explain`` / ``--explain-analyze`` with no SQL run every golden query in
``data/queries.py`` (DESIGN.md §14.4 documents the output format; every
printed value passes the repro.obs.redact disclosure audit).

``--check`` is the CI smoke step, in two phases:

1. every golden SQL string (the four HealthLnK queries *and* the dialect-
   growth goldens) must compile to a plan structurally equal to its
   hand-compiled twin in data/queries.py;
2. one query per new dialect feature (PROJECT-narrowed join, SUM, AVG,
   MIN/MAX sort-head, OR-predicate, 2-column GROUP BY) is compiled AND
   executed on a tiny synthetic dataset and checked against the plaintext
   oracle. Under
   ``REPRO_USE_PALLAS=1`` (the CI kernel-parity job) this drives the Pallas
   kernels in interpret mode.

Exits non-zero on any mismatch.
"""
from __future__ import annotations

import sys


def check() -> int:
    from ..data.queries import all_query_plans, all_query_sql
    from .compile import compile_logical, plan_fingerprint

    plans = all_query_plans()
    failures = 0
    for name, sql_text in all_query_sql().items():
        try:
            compiled = compile_logical(sql_text)
        except Exception as e:  # noqa: BLE001 — report and keep checking
            print(f"FAIL {name}: {type(e).__name__}: {e}")
            failures += 1
            continue
        if compiled != plans[name]:
            print(f"FAIL {name}: compiled plan differs from hand-compiled plan")
            print("  compiled:\n" + plan_fingerprint(compiled))
            print("  expected:\n" + plan_fingerprint(plans[name]))
            failures += 1
        else:
            print(f"OK   {name}")
    failures += _check_dialect_execution()
    failures += _check_sortmerge_execution()
    return 1 if failures else 0


def _check_dialect_execution() -> int:
    """Compile + execute one query per new dialect operator on a tiny
    dataset and compare against the plaintext oracle."""
    import jax

    from ..data.healthlnk import generate_healthlnk, plaintext_oracle
    from ..data.queries import DIALECT_QUERIES, QUERY_SQL
    from ..engine.executor import Engine
    from .compile import compile_logical

    tables, plain = generate_healthlnk(n=8, seed=3, aspirin_frac=0.5)
    eng = Engine(tables, key=jax.random.PRNGKey(2))
    failures = 0
    for name in DIALECT_QUERIES:
        try:
            out, report = eng.execute(compile_logical(QUERY_SQL[name]))
            rows = out.reveal_true_rows()
            oracle = plaintext_oracle(name, plain)
            if name == "projection_join":
                got = sorted(zip(rows["pid"].tolist(), rows["dosage"].tolist()))
                ok = sorted(set(got)) == oracle and set(rows) == {"pid", "dosage"}
            elif name == "dosage_sum":
                ok = int(rows["total"][0]) == oracle
            elif name == "dosage_avg":
                got_avg = int(rows["avg_dosage_sum"][0]) // max(
                    int(rows["avg_dosage_cnt"][0]), 1
                )
                ok = got_avg == oracle["avg"]
            elif name == "dosage_min":
                ok = int(rows["lo"][0]) == oracle
            elif name == "dosage_max":
                ok = int(rows["hi"][0]) == oracle
            elif name == "heart_or_circulatory":
                ok = int(rows["cnt"][0]) == oracle
            elif name == "med_dosage_sum":
                got = {
                    int(k): int(v)
                    for k, v in zip(rows["med"], rows["total"])
                }
                ok = got == oracle
            elif name == "med_dosage_avg":
                got = {
                    int(k): {"sum": int(s), "cnt": int(c), "avg": int(s) // max(int(c), 1)}
                    for k, s, c in zip(
                        rows["med"], rows["mean_sum"], rows["mean_cnt"]
                    )
                }
                ok = got == oracle
            elif name == "repeat_diagnoses":
                got = {
                    int(k): int(v)
                    for k, v in zip(rows["major_icd9"], rows["cnt"])
                }
                ok = got == oracle
            else:  # diag_breakdown
                got = {
                    (int(a), int(b)): int(c)
                    for a, b, c in zip(
                        rows["major_icd9"], rows["diag"], rows["cnt"]
                    )
                }
                ok = got == oracle
            # every plan node must have produced a ledger entry
            ok = ok and len(report.nodes) >= 2
            if ok:
                print(f"OK   exec {name}")
            else:
                print(f"FAIL exec {name}: result mismatch vs plaintext oracle")
                failures += 1
        except Exception as e:  # noqa: BLE001
            print(f"FAIL exec {name}: {type(e).__name__}: {e}")
            failures += 1
    return failures


def _check_sortmerge_execution() -> int:
    """Force the sort-merge physical join on one golden join query and check
    its revealed rows match the product join and the plaintext oracle."""
    import jax
    import numpy as np

    from ..data.healthlnk import generate_healthlnk, plaintext_oracle
    from ..data.queries import QUERY_SQL
    from ..engine.executor import Engine
    from ..plan.nodes import JoinSortMerge
    from .catalog import Catalog
    from .compile import compile_query

    name = "dosage_study"
    try:
        tables, plain = generate_healthlnk(n=8, seed=3, aspirin_frac=0.5)
        # declare the observed per-key duplicate bound so the planner may
        # pick the sort-merge algorithm (a real deployment declares this as
        # schema metadata)
        mult = {
            t: {"pid": int(np.bincount(cols["pid"]).max())}
            for t, cols in plain.items()
        }
        catalog = Catalog.from_tables(tables, multiplicity=mult)
        eng = Engine(tables, key=jax.random.PRNGKey(2))
        results = {}
        for mode in ("product", "sortmerge"):
            plan = compile_query(QUERY_SQL[name], catalog, join_algo=mode)
            has_sm = any(
                isinstance(n, JoinSortMerge) for n in _walk_nodes(plan)
            )
            if (mode == "sortmerge") != has_sm:
                print(f"FAIL exec {name} [{mode}]: algorithm selection "
                      f"did not produce the expected physical join")
                return 1
            out, _ = eng.execute(plan)
            results[mode] = sorted(out.reveal_true_rows()["pid"].tolist())
        oracle = sorted(set(plaintext_oracle(name, plain)))
        if results["product"] == results["sortmerge"] == oracle:
            print(f"OK   exec {name} [sortmerge == product == oracle]")
            return 0
        print(f"FAIL exec {name} [sortmerge]: {results} vs oracle {oracle}")
        return 1
    except Exception as e:  # noqa: BLE001
        print(f"FAIL exec {name} [sortmerge]: {type(e).__name__}: {e}")
        return 1


def _walk_nodes(plan):
    yield plan
    for c in plan.children():
        yield from _walk_nodes(c)


def explain(argv, analyze: bool) -> int:
    """EXPLAIN [ANALYZE] the given SQL — or every golden query when no SQL is
    given — against a small synthetic HealthLnK dataset (the same generator
    the CI smoke uses, so the CLI needs no external state). With
    ``--networked``, EXPLAIN ANALYZE executes on a 3-party loopback mesh
    through the same client facade (actuals come from real wire exchanges).
    ``--trace-out PATH`` (ANALYZE only) runs the queries under a tracer and
    writes the trace — in networked mode the merged distributed trace with
    all three parties' spans — as JSONL to PATH, plus a Chrome trace-event
    file at PATH + ".chrome.json" for chrome://tracing / Perfetto."""
    from ..data.healthlnk import generate_healthlnk
    from ..data.queries import all_query_sql
    from ..obs import trace as obs_trace
    from ..obs.distributed import write_chrome_trace
    from ..runtime import ReflexClient

    networked = "--networked" in argv
    argv = [a for a in argv if a != "--networked"]
    trace_out = None
    if "--trace-out" in argv:
        i = argv.index("--trace-out")
        if i + 1 >= len(argv):
            print("--trace-out requires a PATH argument")
            return 1
        trace_out = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    tables, _ = generate_healthlnk(n=16, seed=3, aspirin_frac=0.5)
    if networked:
        client = ReflexClient.networked(tables, key_seed=2)
    else:
        import jax

        client = ReflexClient.in_process(tables, key=jax.random.PRNGKey(2))
    queries = (
        {"query": " ".join(argv)} if argv else all_query_sql()
    )
    tracer = obs_trace.Tracer() if (trace_out and analyze) else None
    import contextlib

    failures = 0
    with tracer if tracer is not None else contextlib.nullcontext():
        for name, sql_text in queries.items():
            try:
                if analyze:
                    text, _res = client.explain_analyze("explain-cli", sql_text)
                else:
                    text = client.explain(sql_text)
            except Exception as e:  # noqa: BLE001 — report and keep going
                print(f"FAIL {name}: {type(e).__name__}: {e}")
                failures += 1
                continue
            print(text)
            print()
    if tracer is not None:
        with open(trace_out, "w") as f:
            f.write(tracer.to_jsonl())
        write_chrome_trace(
            trace_out + ".chrome.json", tracer.spans, trace_id=tracer.trace_id
        )
        print(f"trace: {len(tracer.spans)} spans -> {trace_out} "
              f"(+ {trace_out}.chrome.json)")
    client.close()
    return 1 if failures else 0


def main(argv) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    if argv[0] == "--check":
        return check()
    if argv[0] in ("--explain", "--explain-analyze"):
        return explain(argv[1:], analyze=argv[0] == "--explain-analyze")
    from .compile import compile_query

    plan = compile_query(" ".join(argv))
    print(plan.pretty())
    return 0


if __name__ == "__main__":
    from ..compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main(sys.argv[1:]))
