"""HealthLnK clinical federation: data, answers and row checks in numpy.

A copy of the system's synthetic HealthLnK generator (Reflex §5.3 schema:
``diagnoses``, ``medications``, ``demographics``, dictionary-encoded
categorical columns), its plaintext answers for the paper's join queries,
the true sizes of their intermediates, and its row comparison, kept here so
that a change to the program cannot move the yardstick. ``generate`` draws exactly the values the
program's generator draws for the same ``n`` and seed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

ICD9_CIRCULATORY = 390
ICD9_HEART_414 = 414
MED_ASPIRIN = 1
DOSAGE_325MG = 325
DIAG_HEART_DISEASE = 7

Tables = Dict[str, Dict[str, np.ndarray]]


def generate(
    n: int,
    seed: int,
    n_patients: int | None = None,
    aspirin_frac: float = 0.2,
    icd_heart_frac: float = 0.15,
) -> Tables:
    """Plaintext tables: ``n`` rows of diagnoses and of medications, pid
    uniform over ``n_patients`` (default n/4), one demographics row each."""
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
    return {"diagnoses": diag, "medications": meds, "demographics": demo}


def max_fanout(tables: Tables, key: str = "pid") -> Dict[str, Dict[str, int]]:
    """The largest number of rows that share one ``key`` value, per table:
    the per-key multiplicity bound a deployment declares as schema metadata."""
    return {t: {key: int(np.bincount(c[key]).max())} for t, c in tables.items()}


def _first_diag_before_last_aspirin(d, m, diag_sel) -> int:
    """COUNT(DISTINCT pid) of pids whose earliest selected diagnosis is no
    later than their latest aspirin prescription."""
    msel = m["med"] == MED_ASPIRIN
    size = int(max(d["pid"].max(initial=0), m["pid"].max(initial=0))) + 1
    first_diag = np.full(size, np.iinfo(np.int64).max)
    np.minimum.at(first_diag, d["pid"][diag_sel], d["time"][diag_sel].astype(np.int64))
    last_med = np.full(size, -1, dtype=np.int64)
    np.maximum.at(last_med, m["pid"][msel], m["time"][msel].astype(np.int64))
    return int((first_diag <= last_med).sum())


def answer(template: str, t: Tables):
    """The plaintext answer of one query template, in the form
    :func:`check_rows` compares against."""
    d, m = t["diagnoses"], t["medications"]
    if template == "dosage_study":
        dp = d["pid"][d["icd9"] == ICD9_CIRCULATORY]
        mp = m["pid"][(m["med"] == MED_ASPIRIN) & (m["dosage"] == DOSAGE_325MG)]
        return [int(p) for p in np.intersect1d(dp, mp)]
    if template == "aspirin_count":
        return _first_diag_before_last_aspirin(d, m, d["icd9"] == ICD9_HEART_414)
    raise KeyError(f"no reference answer for template {template!r}")


def true_sizes(template: str, t: Tables) -> Dict[str, int]:
    """True row counts of the intermediates a Resizer may trim in this
    template, by what the intermediate is: each filtered base table, by its
    name, and the join output, ``"join"``."""
    d, m = t["diagnoses"], t["medications"]
    aspirin = m["med"] == MED_ASPIRIN

    def join_pairs(dsel, msel, theta=False):
        dp, mp = d["pid"][dsel], m["pid"][msel]
        if not theta:
            return int(np.sum(np.bincount(dp, minlength=1 << 20)[mp]))
        dt, mt = d["time"][dsel], m["time"][msel]
        order = np.lexsort((dt, dp))
        dp, dt = dp[order], dt[order]
        total = 0
        for p, tm in zip(mp.tolist(), mt.tolist()):
            lo, hi = np.searchsorted(dp, p, "left"), np.searchsorted(dp, p, "right")
            total += int(np.searchsorted(dt[lo:hi], tm, "right"))
        return total

    if template == "dosage_study":
        ds = d["icd9"] == ICD9_CIRCULATORY
        ms = aspirin & (m["dosage"] == DOSAGE_325MG)
        return {"diagnoses": int(ds.sum()), "medications": int(ms.sum()),
                "join": join_pairs(ds, ms)}
    if template == "aspirin_count":
        ds = d["icd9"] == ICD9_HEART_414
        return {"diagnoses": int(ds.sum()), "medications": int(aspirin.sum()),
                "join": join_pairs(ds, aspirin, theta=True)}
    raise KeyError(f"no true sizes for template {template!r}")


def check_rows(template: str, rows, expected) -> bool:
    """Whether revealed ``rows`` (column -> array) give the ``expected``
    answer, exactly."""
    if rows is None:
        return False
    try:
        if template == "aspirin_count":
            return len(rows["cnt"]) == 1 and int(rows["cnt"][0]) == expected
        if template == "dosage_study":
            pids = [int(p) for p in rows["pid"]]
            return len(pids) == len(set(pids)) and sorted(pids) == expected
    except (KeyError, TypeError, ValueError):
        return False
    raise KeyError(f"no row check for template {template!r}")
