"""Synthetic HealthLnK-like clinical data (the paper's §5.3 workload tables).

The real HealthLnK extract is not public; we generate schema-compatible
synthetic relations with dictionary-encoded categorical columns (which is how
strings enter MPC engines anyway) and tunable selectivities so the paper's
queries produce non-trivial intermediate sizes.

Tables (column -> meaning):
  diagnoses     pid, icd9, major_icd9, diag, time
  medications   pid, med, dosage, time
  demographics  pid, zip

Encodings used by the queries:
  ICD9_CIRCULATORY (icd9 == 'circulatory disorder'), ICD9_HEART_414
  MED_ASPIRIN, DOSAGE_325MG, DIAG_HEART_DISEASE
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np

from ..ops.table import SecretTable

__all__ = ["generate_healthlnk", "plaintext_oracle", "check_rows"]

ICD9_CIRCULATORY = 390
ICD9_HEART_414 = 414
MED_ASPIRIN = 1
DOSAGE_325MG = 325
DIAG_HEART_DISEASE = 7


def generate_healthlnk(
    n: int = 128,
    key: jax.Array | None = None,
    seed: int = 0,
    n_patients: int | None = None,
    aspirin_frac: float = 0.2,
    icd_heart_frac: float = 0.15,
) -> Tuple[Dict[str, SecretTable], Dict[str, Dict[str, np.ndarray]]]:
    """Returns ({table -> SecretTable}, {table -> plaintext columns})."""
    key = key if key is not None else jax.random.PRNGKey(11)
    rng = np.random.default_rng(seed)
    n_patients = n_patients or max(n // 4, 4)

    diag = {
        "pid": rng.integers(0, n_patients, n).astype(np.uint32),
        "icd9": np.where(
            rng.random(n) < icd_heart_frac,
            ICD9_HEART_414,
            rng.choice([ICD9_CIRCULATORY, 401, 250, 486], n),
        ).astype(np.uint32),
        "diag": np.where(
            rng.random(n) < icd_heart_frac, DIAG_HEART_DISEASE, rng.integers(0, 6, n)
        ).astype(np.uint32),
        "time": rng.integers(0, 1000, n).astype(np.uint32),
    }
    diag["major_icd9"] = (diag["icd9"] // 100).astype(np.uint32)

    meds = {
        "pid": rng.integers(0, n_patients, n).astype(np.uint32),
        "med": np.where(
            rng.random(n) < aspirin_frac, MED_ASPIRIN, rng.integers(2, 12, n)
        ).astype(np.uint32),
        "dosage": rng.choice([81, 100, DOSAGE_325MG, 500], n).astype(np.uint32),
        "time": rng.integers(0, 1000, n).astype(np.uint32),
    }

    demo = {
        "pid": np.arange(n_patients, dtype=np.uint32),
        "zip": rng.integers(10000, 99999, n_patients).astype(np.uint32),
    }

    plain = {"diagnoses": diag, "medications": meds, "demographics": demo}
    keys = jax.random.split(key, 3)
    shared = {
        name: SecretTable.from_plaintext(cols, k)
        for (name, cols), k in zip(plain.items(), keys)
    }
    return shared, plain


def _count_diag_then_aspirin(d, m, diag_sel, demo_pids=None) -> int:
    """COUNT(DISTINCT pid) over diagnoses ``diag_sel`` joined with aspirin
    medications on pid where d.time <= m.time (optionally also joined with
    ``demo_pids``): a pid qualifies iff its earliest selected diagnosis is
    no later than its latest aspirin prescription."""
    msel = m["med"] == MED_ASPIRIN
    size = int(max(d["pid"].max(initial=0), m["pid"].max(initial=0))) + 1
    first_diag = np.full(size, np.iinfo(np.int64).max)
    np.minimum.at(first_diag, d["pid"][diag_sel], d["time"][diag_sel].astype(np.int64))
    last_med = np.full(size, -1, dtype=np.int64)
    np.maximum.at(last_med, m["pid"][msel], m["time"][msel].astype(np.int64))
    hit = first_diag <= last_med
    if demo_pids is not None:
        hit &= np.isin(np.arange(size), demo_pids)
    return int(hit.sum())


# -----------------------------------------------------------------------------
# Plaintext oracles for the four paper queries (Table 2)
# -----------------------------------------------------------------------------

def plaintext_oracle(query: str, plain: Dict[str, Dict[str, np.ndarray]]):
    d, m, demo = plain["diagnoses"], plain["medications"], plain["demographics"]
    if query == "comorbidity":
        vals, counts = np.unique(d["major_icd9"], return_counts=True)
        order = np.argsort(-counts, kind="stable")
        top = sorted(
            zip(counts.tolist(), vals.tolist()), key=lambda t: (-t[0], t[1])
        )[:10]
        return {int(v): int(c) for c, v in top}
    if query == "dosage_study":
        dp = d["pid"][d["icd9"] == ICD9_CIRCULATORY]
        mp = m["pid"][(m["med"] == MED_ASPIRIN) & (m["dosage"] == DOSAGE_325MG)]
        return [int(p) for p in np.intersect1d(dp, mp)]
    if query == "aspirin_count":
        return _count_diag_then_aspirin(d, m, d["icd9"] == ICD9_HEART_414)
    if query == "three_join":
        return _count_diag_then_aspirin(
            d, m, d["diag"] == DIAG_HEART_DISEASE, demo_pids=demo["pid"]
        )
    # -- dialect-growth goldens (projection / SUM / AVG / OR / 2-col GROUP BY)
    if query == "projection_join":
        sel = (m["med"] == MED_ASPIRIN) & np.isin(m["pid"], d["pid"])
        return sorted(set(zip(m["pid"][sel].tolist(), m["dosage"][sel].tolist())))
    if query == "dosage_sum":
        mask = m["med"] == MED_ASPIRIN
        return int(m["dosage"][mask].sum())
    if query == "dosage_avg":
        mask = m["med"] == MED_ASPIRIN
        total, cnt = int(m["dosage"][mask].sum()), int(mask.sum())
        return {"sum": total, "cnt": cnt, "avg": total // max(cnt, 1)}
    if query in ("dosage_min", "dosage_max"):
        vals = m["dosage"][m["med"] == MED_ASPIRIN]
        if len(vals) == 0:
            return None  # empty selection: the engine reveals zero rows
        return int(vals.min() if query == "dosage_min" else vals.max())
    if query == "heart_or_circulatory":
        return int(
            ((d["icd9"] == ICD9_HEART_414) | (d["icd9"] == ICD9_CIRCULATORY)).sum()
        )
    if query == "diag_breakdown":
        counts: Dict[Tuple[int, int], int] = {}
        for mi, di in zip(d["major_icd9"].tolist(), d["diag"].tolist()):
            counts[(int(mi), int(di))] = counts.get((int(mi), int(di)), 0) + 1
        return counts
    if query in ("med_dosage_sum", "med_dosage_avg"):
        sums: Dict[int, int] = {}
        cnts: Dict[int, int] = {}
        for mv, dv in zip(m["med"].tolist(), m["dosage"].tolist()):
            sums[int(mv)] = sums.get(int(mv), 0) + int(dv)
            cnts[int(mv)] = cnts.get(int(mv), 0) + 1
        if query == "med_dosage_sum":
            return sums
        return {k: {"sum": sums[k], "cnt": cnts[k], "avg": sums[k] // cnts[k]}
                for k in sums}
    if query == "repeat_diagnoses":
        vals, counts = np.unique(d["major_icd9"], return_counts=True)
        return {int(v): int(c) for v, c in zip(vals, counts) if c >= 2}
    raise ValueError(query)


def check_rows(qname: str, rows, oracle):
    """Check one query's revealed ``rows`` against its plaintext oracle;
    returns ``(shown, ok)``, the rows in the oracle's form and whether they
    match. Exact for every golden except ``comorbidity``'s LIMIT boundary,
    where count ties may break either way."""
    if qname == "comorbidity":
        shown = {int(v): int(c) for v, c in zip(rows["major_icd9"], rows["cnt"])}
        # the sort is on COUNT(*) alone, so the LIMIT boundary may break
        # count-ties differently than the oracle's (count, value) order.
        # Require: count multiset matches; every value strictly above the
        # boundary count appears with its exact count (only boundary TIES
        # may substitute); and any overlap agrees exactly
        boundary = min(oracle.values(), default=0)
        ok = (
            sorted(shown.values()) == sorted(oracle.values())
            and all(shown.get(v) == c
                    for v, c in oracle.items() if c > boundary)
            and all(shown[v] == c for v, c in oracle.items() if v in shown)
        )
        return shown, ok
    if qname == "diag_breakdown":
        shown = {
            (int(a), int(b)): int(c)
            for a, b, c in zip(rows["major_icd9"], rows["diag"], rows["cnt"])
        }
        return shown, shown == oracle
    if qname == "dosage_sum":
        shown = int(rows["total"][0])
        return shown, shown == oracle
    if qname == "dosage_avg":
        shown = {k: int(rows[k][0]) for k in ("avg_dosage_sum",
                                              "avg_dosage_cnt", "avg_dosage")}
        ok = (shown["avg_dosage_sum"] == oracle["sum"]
              and shown["avg_dosage_cnt"] == oracle["cnt"]
              and shown["avg_dosage"] == oracle["avg"])
        return shown["avg_dosage"], ok
    if qname == "med_dosage_sum":
        shown = {int(k): int(v) for k, v in zip(rows["med"], rows["total"])}
        return shown, shown == oracle
    if qname == "repeat_diagnoses":
        shown = {int(k): int(v)
                 for k, v in zip(rows["major_icd9"], rows["cnt"])}
        return shown, shown == oracle
    if qname == "med_dosage_avg":
        # the service's post_reveal already folded (sum, cnt) -> mean
        shown = {int(k): int(v) for k, v in zip(rows["med"], rows["mean"])}
        return shown, shown == {k: v["avg"] for k, v in oracle.items()}
    if qname == "projection_join":
        # the oracle is the sorted (pid, dosage) pair set
        shown = sorted({(int(p), int(v))
                        for p, v in zip(rows["pid"], rows["dosage"])})
        return shown, shown == oracle
    if qname in ("dosage_min", "dosage_max"):
        col = "lo" if qname == "dosage_min" else "hi"
        if oracle is None:  # empty selection: nothing may be revealed
            return None, len(rows[col]) == 0
        shown = int(rows[col][0])
        return shown, shown == oracle
    if "cnt" in rows and len(rows["cnt"]) == 1:
        shown = int(rows["cnt"][0])
        return shown, shown == oracle
    shown = sorted(set(rows["pid"].tolist()))
    return shown, shown == oracle
