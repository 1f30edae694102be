"""Quickstart: secure collaborative analytics with Reflex in ~40 lines.

Three data owners upload secret-shared rows; the engine runs an oblivious
Filter -> Join, inserts a Resizer after the join (Beta(2,6) noise, parallel
addition), and reveals only the final result + the noisy intermediate size.
The finale re-asks the same question through :class:`repro.runtime.
ReflexClient` — first in-process, then against a real 3-party mesh — and
shows both answers (and their communication ledgers) are identical.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.core.crt import crt_rounds
from repro.core.noise import BetaNoise
from repro.core.resizer import ResizerConfig
from repro.engine import Engine
from repro.ops import Predicate, SecretTable
from repro.plan import insert_resizers
from repro.plan.nodes import Distinct, Filter, Join, Scan
from repro.runtime import ReflexClient


def main():
    rng = np.random.default_rng(7)
    n = 48
    # --- data owners share their private tables (dictionary-encoded) -------
    patients = {
        "pid": rng.integers(0, 12, n).astype(np.uint32),
        "icd9": rng.choice([390, 401, 414], n).astype(np.uint32),
    }
    meds = {
        "pid2": rng.integers(0, 12, n).astype(np.uint32),
        "med": rng.choice([1, 2, 3], n).astype(np.uint32),
    }
    tables = {
        "diagnoses": SecretTable.from_plaintext(patients, jax.random.PRNGKey(0)),
        "medications": SecretTable.from_plaintext(meds, jax.random.PRNGKey(1)),
    }

    # --- a hand-compiled plan, then Resizers inserted by policy ------------
    plan = Distinct(
        Join(
            Filter(Scan("diagnoses"), [Predicate("icd9", "eq", 414)]),
            Filter(Scan("medications"), [Predicate("med", "eq", 1)]),
            ("pid", "pid2"),
        ),
        "pid",
    )
    noise = BetaNoise(2, 6)
    plan = insert_resizers(
        plan, lambda node: ResizerConfig(noise=noise, addition="parallel"),
        placement="all_internal",
    )
    print(plan.pretty(), "\n")

    # --- execute -------------------------------------------------------------
    eng = Engine(tables, key=jax.random.PRNGKey(42))
    out, report = eng.execute(plan)
    print(report.summary())

    pids = sorted(set(out.reveal_true_rows()["pid"].tolist()))
    print("\npatients on aspirin with icd9=414:", pids)

    # --- what did we disclose? ----------------------------------------------
    for s in report.nodes:
        if s.node.startswith("Resize"):
            e = s.extra
            print(
                f"\ndisclosure at {s.node}: S={e['s']} (true T={e['t']}, hidden) — "
                f"CRT: attacker needs ~{crt_rounds(noise, 'parallel', e['n'], e['t']):.0f} "
                "equivalent repetitions to pin T within +-1"
            )

    # --- the same study through the unified client, both topologies ---------
    # ReflexClient speaks SQL and hides the execution topology: in_process
    # runs the single-process oracle; networked ships shares to three party
    # processes (here: an in-process loopback mesh) and every comm-ledger
    # sync point becomes a real, verified wire exchange.
    sql = (
        "SELECT DISTINCT d.pid FROM diagnoses d, medications m "
        "WHERE d.pid = m.pid2 AND d.icd9 = 414 AND m.med = 1"
    )
    local = ReflexClient.in_process(tables)
    res_local = local.submit("quickstart", sql)
    with ReflexClient.networked(tables, key_seed=0) as networked:
        res_net = networked.submit("quickstart", sql)
        audit = networked.service.engine.last_wire_audit
    same = all(
        np.array_equal(res_local.rows[c], res_net.rows[c])
        for c in res_local.rows
    )
    print(
        f"\nReflexClient: in-process and 3-party answers identical: {same}"
    )
    for a in audit:
        print(
            f"  party {a['party']}: {a['exchanges']} exchanges, "
            f"{a['wire_bytes']} wire bytes == {a['ledger_bytes']} ledger bytes"
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
