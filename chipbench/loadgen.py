"""The one load generator: turns a traffic file into the requests of a run.

A traffic mix is a JSON file under ``traffic/`` (see ``README.md``). Its
requests come in *units*: one round in which every client sends every
template, in the file's order, each waiting for its answer before the next
(a closed loop with no think time). Every unit is the same, so a run's work
is fixed by the mix, and ``--seed`` changes only the data.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class Request:
    tenant: str
    template: str
    sql: str


class Mix:
    """A parsed traffic file."""

    def __init__(self, spec: Dict):
        self.name = spec["name"]
        self.clients = int(spec["clients"])
        self.templates: Dict[str, str] = {t["name"]: t["sql"] for t in spec["templates"]}
        if self.clients < 1 or not self.templates:
            raise ValueError(f"traffic {self.name}: needs clients and templates")

    @classmethod
    def from_file(cls, path: Path) -> "Mix":
        return cls(json.loads(Path(path).read_text()))

    def tenants(self) -> List[str]:
        return [f"tenant{i:02d}" for i in range(self.clients)]

    def unit(self, index: int) -> List[Request]:
        """The requests of unit ``index``, in the order they are sent."""
        return [Request(t, n, sql) for t in self.tenants() for n, sql in self.templates.items()]
