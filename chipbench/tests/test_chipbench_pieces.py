"""The benchmark's pieces on the CPU: reference, traffic, the noise check's
arithmetic, trace reduction, discovery by name, and the refusal to run
without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HOME = Path(__file__).resolve().parents[1]
ROOT = HOME.parent
sys.path.insert(0, str(HOME))

import bench  # noqa: E402
import loadgen  # noqa: E402
import peaks  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

ref = bench.load_module(HOME / "reference" / "healthlnk.py")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TEMPLATES = sorted({
    t["name"] for f in (HOME / "traffic").glob("*.json")
    for t in json.loads(f.read_text())["templates"]
})


# -- the copied reference ------------------------------------------------------

@pytest.mark.parametrize("n", [64, 256])
def test_reference_generates_the_programs_tables(n):
    from repro.data import generate_healthlnk

    _, plain = generate_healthlnk(n=n, seed=5)
    mine = ref.generate(n=n, seed=5)
    assert plain.keys() == mine.keys()
    for t in plain:
        assert plain[t].keys() == mine[t].keys()
        for c in plain[t]:
            np.testing.assert_array_equal(plain[t][c], mine[t][c])


def _program_form(template, oracle):
    """The program oracle's answer in the reference's form."""
    return oracle


@pytest.mark.parametrize("seed", range(1, 9))
@pytest.mark.parametrize("template", TEMPLATES)
def test_reference_answers_agree_with_the_program_oracle(template, seed):
    from repro.data import plaintext_oracle

    plain = ref.generate(n=512, seed=seed)
    assert ref.answer(template, plain) == _program_form(template, plaintext_oracle(template, plain))


def test_reference_true_sizes_count_the_intermediates():
    plain = ref.generate(n=96, seed=9)
    d, m = plain["diagnoses"], plain["medications"]
    pairs = lambda dsel, msel, theta: sum(  # noqa: E731
        1 for i in np.flatnonzero(dsel) for j in np.flatnonzero(msel)
        if d["pid"][i] == m["pid"][j] and (not theta or d["time"][i] <= m["time"][j])
    )
    ds, ms = d["icd9"] == 390, (m["med"] == 1) & (m["dosage"] == 325)
    assert ref.true_sizes("dosage_study", plain) == {
        "diagnoses": ds.sum(), "medications": ms.sum(), "join": pairs(ds, ms, False)}
    dh, ma = d["icd9"] == 414, m["med"] == 1
    assert ref.true_sizes("aspirin_count", plain) == {
        "diagnoses": dh.sum(), "medications": ma.sum(), "join": pairs(dh, ma, True)}
    with pytest.raises(KeyError):
        ref.true_sizes("comorbidity", plain)


def test_reference_row_check_rejects_an_altered_answer():
    plain = ref.generate(n=256, seed=4)
    good = {"pid": np.array(ref.answer("dosage_study", plain), dtype=np.uint32)}
    assert ref.check_rows("dosage_study", good, ref.answer("dosage_study", plain))
    bad = {"pid": good["pid"].copy()}
    bad["pid"][0] += 1
    assert not ref.check_rows("dosage_study", bad, ref.answer("dosage_study", plain))
    assert not ref.check_rows("aspirin_count", None, 5)


# -- traffic -------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["study"])
def test_traffic_is_the_same_for_the_same_seed(mix):
    """A seed of more than 32 bits fixes the data; the mix's requests are
    the same in every run."""
    m = loadgen.Mix.from_file(HOME / "traffic" / f"{mix}.json")
    big = 2**31 + 12345
    assert [m.unit(i) for i in range(8)] == [m.unit(i) for i in range(8)]
    assert run.derive_seeds(big) == run.derive_seeds(big) != run.derive_seeds(big + 1)
    a = ref.generate(n=64, seed=run.derive_seeds(big)["data"])
    b = ref.generate(n=64, seed=run.derive_seeds(big)["data"])
    assert all(np.array_equal(a[t][c], b[t][c]) for t in a for c in a[t])


def test_study_units_are_whole_rounds_whatever_the_seed():
    m = loadgen.Mix.from_file(HOME / "traffic" / "study.json")
    assert m.unit(0) == m.unit(5)
    assert [r.template for r in m.unit(0)] == ["dosage_study", "aspirin_count"]


# -- the noise check's arithmetic ------------------------------------------------

@pytest.mark.parametrize("cap", [3.0, 18.0, 30.0, 1e9])
def test_tlap_mean_eta_agrees_with_sampling(cap):
    """E[min(X, cap)], X Laplace(mu, b) cut to [0, inf), against a million
    draws (eps 0.5, delta 5e-5, sensitivity 1: mu 18.4, b 2)."""
    eps, delta, sens = 0.5, 5e-5, 1.0
    b = sens / eps
    mu = -b * np.log(2 * delta)
    x = np.random.default_rng(3).laplace(mu, b, 1_000_000)
    x = x[x >= 0]
    want = np.minimum(x, cap).mean()
    assert run.tlap_mean_eta(eps, delta, sens, cap) == pytest.approx(want, rel=2e-3, abs=1e-3)


def test_tlap_mean_eta_is_the_papers_figure():
    """The paper's example (eps 0.5, delta 5e-5, sensitivity 1000) keeps
    ~18336 fillers on average, uncapped."""
    assert run.tlap_mean_eta(0.5, 5e-5, 1000.0, 1e12) == pytest.approx(18336, rel=0.01)


# -- trace reduction -----------------------------------------------------------

def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    ops = {"/device:TPU:0": [(10 * ms, 20 * ms), (15 * ms, 30 * ms), (50 * ms, 60 * ms),
                             (95 * ms, 130 * ms)]}
    modules = {"/device:TPU:0": [("jit_a(11)", 10 * ms, 30 * ms), ("jit_b(12)", 50 * ms, 60 * ms),
                                 ("jit_a(13)", 95 * ms, 130 * ms), ("jit_c(14)", 200 * ms, 210 * ms)]}
    annotations = [("submit q", 0, 70 * ms), ("drain", 70 * ms, 100 * ms)]
    r = tr.reduce_events(ops, modules, annotations)
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.035)  # 10-30, 50-60, 95-100
    assert r.launches == 3  # jit_c starts after the window
    assert r.device_ops[0] == ["jit_a", pytest.approx(0.025)]
    gaps = dict(r.idle_gaps)
    assert gaps["submit q"] == pytest.approx(0.040)  # 0-10, 30-50, 60-70
    assert gaps["drain"] == pytest.approx(0.025)  # 70-95
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_trace_reduction_needs_a_window_and_a_device():
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": [(0, 1)]}, {}, [])
    with pytest.raises(ValueError):
        tr.reduce_events({}, {}, [("drain", 0, 5)])


def test_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# -- discovery by name ---------------------------------------------------------

def test_every_named_piece_has_its_file():
    b = bench.Benchmark(ROOT)
    for w in SPEC["workloads"]:
        cell = b.cell(w["name"])
        assert cell.traffic_file.is_file() and cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert loadgen.Mix.from_file(cell.traffic_file).templates
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(b.reader(m["name"]).read)


def _copy_checkout(tmp_path: Path) -> Path:
    shutil.copytree(HOME, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    return tmp_path


def test_a_cell_config_mix_and_metric_added_as_files_are_found(tmp_path):
    root = _copy_checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*") if p.is_file()}
    cfg = json.loads((root / "chipbench/configs/healthlnk-reflex.json").read_text())
    cfg["rows"] = 1 << 17
    (root / "chipbench/configs/healthlnk-big.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "chipbench/traffic/study.json").read_text())
    mix["name"] = "study3"
    mix["templates"] = mix["templates"][:1]
    (root / "chipbench/traffic/study3.json").write_text(json.dumps(mix))
    (root / "chipbench/metrics/rounds_total.study.py").write_text(
        "def read(run):\n    return 7.0\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({**spec["configs"][0], "name": "healthlnk-big",
                            "file": "chipbench/configs/healthlnk-big.json"})
    spec["workloads"].append({"name": "big-study3", "config": "healthlnk-big",
                              "traffic": "study3", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "rounds_total.study", "unit": "rounds",
                              "better": "lower", "source": "program_counter",
                              "layer": "protocol", "moves": "query_s",
                              "workloads": ["big-study3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    b = bench.Benchmark(root)
    cell = b.cell("big-study3")
    assert cell.config["rows"] == 1 << 17
    assert list(loadgen.Mix.from_file(cell.traffic_file).templates) == ["dosage_study"]
    assert [m["name"] for m in cell.per_layer] == ["rounds_total.study"]
    assert b.reader("rounds_total.study").read(None) == 7.0
    for p, data in before.items():
        assert p.read_bytes() == data


# -- refusing to run -----------------------------------------------------------

def _run_py(cwd: Path, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "reflex-study",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    root = _copy_checkout(tmp_path)
    p = _run_py(root, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
