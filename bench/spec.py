"""What a run is made of, found by name: the cell's entry in BENCHMARK.json,
its configuration file, its traffic mix, its limits, and one reader per
metric.  Adding a cell or a metric adds files and entries; nothing here
changes."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable            # read(record) -> float | None


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                 # bench/configs/<config>.json
    mix: dict                 # bench/traffic/<traffic>.json
    limits: dict              # bench/limits/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def reader(name: str) -> Callable:
    """The `read` function of bench/metrics/<name>.py."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(entries: List[dict], cell: str) -> List[Metric]:
    return [Metric(m["name"], m["unit"], reader(m["name"]))
            for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT / configs[w["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")
    return Cell(name, int(w["chips"]), cfg, mix, limits,
                _metrics(bench["end_to_end"], name),
                _metrics(bench["per_layer"], name))


def reference_module(cfg: dict):
    """bench/references/<cfg['reference']>.py: the plain forward, the
    weight maker and the sizes of the configuration's family."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")
