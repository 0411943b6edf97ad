"""Finds a cell's configuration, traffic mix and metric readers by the names
`BENCHMARK.json` gives them.

    configs/<config>.json      sizes as run, source, cuts, serving deployment
    configs/<reference>.py     the plain float32 reference the file names
    traffic/<mix>.json         parameters of the closed-loop traffic mix
    metrics/<metric>.py        ``read(run) -> float | None`` for one metric

A later cell adds files of its own and an entry in `BENCHMARK.json`; nothing
here changes.  A name that finds no file is an error.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout
BENCHMARK_JSON = ROOT / "BENCHMARK.json"


class SpecError(ValueError):
    """A cell names a configuration, traffic mix or metric with no file."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                       # the configuration file, parsed
    traffic: dict                      # the traffic file, parsed
    end_to_end: List[str]              # metric names this cell reports
    per_layer: List[str]
    readers: Dict[str, Callable] = field(default_factory=dict)
    units: Dict[str, str] = field(default_factory=dict)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"no file {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"no {what} file {path}")
    return json.loads(path.read_text())


def reader(metric: str) -> Callable:
    mod = _load_module(HERE / "metrics" / f"{metric}.py", metric)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metrics/{metric}.py has no read(run)")
    return mod.read


def reference(config: dict):
    """The plain reference module a configuration file names."""
    name = config.get("reference")
    if not name:
        raise SpecError(f"configuration {config.get('name')!r} names no "
                        f"reference")
    return _load_module(HERE / "configs" / f"{name}.py", name)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json(BENCHMARK_JSON,
                                                      "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown configuration "
                        f"{w['config']!r}")
    config = load_json(ROOT / configs[w["config"]]["file"], "configuration")
    config.setdefault("name", w["config"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json", "traffic")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=[m["name"] for m in e2e],
                per_layer=[m["name"] for m in layer])
    for m in e2e + layer:
        cell.readers[m["name"]] = reader(m["name"])
        cell.units[m["name"]] = m["unit"]
    reference(config)                  # a missing reference is an error now
    return cell
