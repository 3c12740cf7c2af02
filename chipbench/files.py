"""Finding a cell's files by the names ``BENCHMARK.json`` gives them.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a cell's serving settings ``cells/<name>.json``
and a per-layer metric ``metrics/<name>.py``.  Adding a cell or a metric
adds files; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with everything its names point to."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    serve: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = HERE,
              benchmark: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``benchmark`` (the checkout's ``BENCHMARK.json``
    by default), its files looked up under ``root``."""
    bench = read_json(benchmark or CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=read_json(root / "configs" / f"{w['config']}.json"),
        traffic=read_json(root / "traffic" / f"{w['traffic']}.json"),
        serve=read_json(root / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_metric(name: str, root: Path = HERE) -> ModuleType:
    """The reader ``metrics/<name>.py``; it defines ``read(run)``."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path
    )
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
