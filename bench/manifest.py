"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a metric names a reader.
Each lives in a file of its own under ``bench/``, found by name, so a
later change adds a configuration, a mix, a generator kind or a metric
as new files plus new manifest entries, and edits no file already here:

  configs/<config>.json        sizes, source, deployment, check limit
  traffic/<mix>.json           a generator kind and its parameters
  traffic/kinds/<kind>.py      one generator per kind
  metrics/<metric>.py          one reader per metric
  reference/<name>.py          the plain reference a config names
  arch/<name>.py               the architecture of the same name: its
                               weights, the program config it implies,
                               its operation counts

A configuration names its architecture once, under ``reference``: the
plain reference and the architecture's module share that name.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict            # the config file's contents
    traffic: Dict           # the traffic file's contents, plus "name"
    chips: int
    end_to_end: List[Dict]  # manifest entries reported with --trace 0
    per_layer: List[Dict]   # manifest entries reported with --trace 1
    bench_dir: Path


def load_module(path: Path) -> ModuleType:
    """Import a file by path: metric files carry dots in their names."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reported_in(metric: Dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def load_cell(name: str, manifest_path: Optional[Path] = None) -> Cell:
    manifest_path = Path(manifest_path or ROOT / "BENCHMARK.json")
    root = manifest_path.parent
    man = json.loads(manifest_path.read_text())
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r} in {manifest_path}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    bench_dir = (root / cfg_entry["file"]).parent.parent
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    traffic["name"] = w["traffic"]
    return Cell(
        name=name, config=config, traffic=traffic, chips=int(w["chips"]),
        end_to_end=[m for m in man["end_to_end"] if _reported_in(m, name)],
        per_layer=[m for m in man["per_layer"] if _reported_in(m, name)],
        bench_dir=bench_dir)


def metric_reader(cell: Cell, metric: str) -> ModuleType:
    return load_module(cell.bench_dir / "metrics" / f"{metric}.py")


def traffic_kind(cell: Cell) -> ModuleType:
    kind = cell.traffic["kind"]
    return load_module(cell.bench_dir / "traffic" / "kinds" / f"{kind}.py")


def reference_module(cell: Cell) -> ModuleType:
    return load_module(
        cell.bench_dir / "reference" / f"{cell.config['reference']}.py")


def arch_by_name(bench_dir, name: str) -> ModuleType:
    """``arch/<name>.py`` of the benchmark at ``bench_dir``: ``make`` and
    ``shapes`` (the program's parameter tree drawn from a seed),
    ``program_fields`` (the program config's attributes the published
    keys fix), ``step_flops`` and ``kernel_counts``."""
    return load_module(Path(bench_dir) / "arch" / f"{name}.py")


def arch_module(cell: Cell) -> ModuleType:
    return arch_by_name(cell.bench_dir, cell.config["reference"])
