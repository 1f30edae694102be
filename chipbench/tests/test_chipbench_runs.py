"""Whole runs of the benchmark's cells on the CPU at 64 rows a table, past
the harness's look for a chip: sound runs come out correct, and every
control and each fault a cell can have come out not correct."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HOME = Path(__file__).resolve().parents[1]
ROOT = HOME.parent
sys.path.insert(0, str(HOME))

import bench  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 99
NOISE_KEY = 12345
# At 64 rows the paper's sensitivity (1000) draws more fillers than a table
# has, and every Resizer keeps all of them; sensitivity 1 leaves room below
# N - T for the stated noise and for a tenth of it.
TINY_SENSITIVITY = 1.0


@pytest.fixture(scope="module")
def tiny(tmp_path_factory) -> bench.Benchmark:
    """The benchmark at 64 rows a table."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(HOME, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for f in (root / "chipbench/configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg.update(rows=64, demographics_rows=16)
        for r in [cfg["resizer"]] + [c["override"]["resizer"] for c in cfg["controls"]]:
            if "sensitivity" in r:
                r["sensitivity"] = TINY_SENSITIVITY
        f.write_text(json.dumps(cfg))
    return bench.Benchmark(root)


def one_run(b: bench.Benchmark, cell: str, override=None) -> dict:
    return run.run_cell(b, cell, SEED, 0.0, False, noise_key=NOISE_KEY,
                        service_override=override)


@pytest.mark.parametrize("cell", ["reflex-study", "oblivious-study"])
def test_sound_run_is_correct(tiny, cell):
    line = one_run(tiny, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in tiny.cell(cell).end_to_end}


CONTROLS = [("reflex-study", "reveal"), ("reflex-study", "eps5"),
            ("oblivious-study", "cost_based")]


@pytest.mark.parametrize("cell,control", CONTROLS)
def test_control_is_not_correct(tiny, cell, control):
    controls = {c["name"]: c for c in tiny.cell(cell).config["controls"]}
    assert set(controls) == {c for w, c in CONTROLS if w == cell}
    line = one_run(tiny, cell, controls[control]["override"])
    assert not line["correct"], line["checks"]
    assert line["checks"]["wrong"]["value"] == 0  # answers stay right: a guarantee broke


def test_an_answer_altered_where_it_is_produced_is_caught(tiny, monkeypatch):
    from repro.ops.table import SecretTable

    reveal = SecretTable.reveal_true_rows

    def altered(self):
        rows = reveal(self)
        col = next(iter(rows))
        rows[col] = rows[col].copy()
        if len(rows[col]):
            rows[col][0] += 1
        return rows

    monkeypatch.setattr(SecretTable, "reveal_true_rows", altered)
    line = one_run(tiny, "reflex-study")
    assert not line["correct"] and line["checks"]["wrong"]["value"] > 0


@pytest.mark.parametrize("template", ["dosage_study", "aspirin_count"])
def test_each_trim_is_matched_to_its_intermediate(tiny, template):
    """The check pairs every Resize with the reference's true size of the
    intermediate below it: the plan's three Resizers, in execution order,
    each keep between 0 and N - T fillers."""
    import jax

    from repro.runtime import ReflexClient

    cell = tiny.cell("reflex-study")
    tables, plain, catalog = run.make_tables(cell, run.derive_seeds(SEED))
    sql = run.Mix.from_file(cell.traffic_file).templates[template]
    with ReflexClient.in_process(tables, catalog=catalog, key=jax.random.PRNGKey(NOISE_KEY),
                                 **run.service_kwargs(cell.config)) as client:
        res = client.submit("t", sql)
    got = run.trims(res)
    truth = cell.reference.true_sizes(template, plain)
    assert [i for i, _, _ in got] == ["diagnoses", "medications", "join"]
    for inter, n, s in got:
        assert truth[inter] <= s <= n
