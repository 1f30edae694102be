"""Finds a benchmark's pieces by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration, a traffic mix or a metric:

* cell ``c``: the ``workloads`` entry named ``c``;
* its configuration: the ``configs`` entry it names, whose ``file`` holds
  the deployment, and whose ``reference`` key names ``reference/<r>.py``;
* its traffic: ``traffic/<traffic>.json``, read by :mod:`loadgen`;
* metric ``m``: the ``end_to_end`` or ``per_layer`` entry named ``m``, read
  by ``metrics/<m>.py``, whose ``read(run)`` returns a number or None.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HOME = "chipbench"  # the benchmark's directory under a checkout's root


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location("chipbench_" + path.stem.replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict  # the configuration file's contents
    traffic_file: Path
    reference: ModuleType
    end_to_end: List[Dict]  # metric entries this cell reports with tracing off
    per_layer: List[Dict]  # ... and with tracing on


class Benchmark:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.home = self.root / HOME
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _applies(self, metric: Dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell(self, name: str) -> Cell:
        entries = {w["name"]: w for w in self.spec["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        entry = entries[name]
        cfg_entry = {c["name"]: c for c in self.spec["configs"]}[entry["config"]]
        config = json.loads((self.root / cfg_entry["file"]).read_text())
        return Cell(
            name=name,
            chips=int(entry["chips"]),
            config=config,
            traffic_file=self.home / "traffic" / f"{entry['traffic']}.json",
            reference=load_module(self.home / "reference" / f"{config['reference']}.py"),
            end_to_end=[m for m in self.spec["end_to_end"] if self._applies(m, name)],
            per_layer=[m for m in self.spec["per_layer"] if self._applies(m, name)],
        )

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.home / "metrics" / f"{metric}.py")
