"""What a cell is: its entry in ``BENCHMARK.json``, its configuration
file and its traffic file, found by name; and the one way the harness
finds the code a name stands for (:func:`plugin`)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def weighted(self) -> bool:
        return bool(self.config["weighted"])


def plugin(folder: str, name: str) -> ModuleType:
    """The module ``bench/<folder>/<name>.py``.  Graph families, input
    formats, window drivers and metric readers are each a file of their
    own, found so by the name a configuration, a traffic file or
    ``BENCHMARK.json`` gives; a new one is a new file, never an edit."""
    path = BENCH / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {path} for {folder} {name!r}")
    key = "bench_" + f"{folder}_{name}".replace("/", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> Dict[str, Any]:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, overrides: Dict[str, Any] | None = None) -> Cell:
    """The cell ``name``; ``overrides`` replaces configuration keys (the
    tests' tiny rehearsals, never a benchmark run)."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(ROOT / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    config.update(overrides or {})
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)
