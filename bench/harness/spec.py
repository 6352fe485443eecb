"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a data file of its own:

- ``bench/configs/<config>.json``: the model's sizes as run;
- ``bench/traffic/<traffic>.json``: the mix, whose ``loop`` names
  ``bench/loops/<loop>.py``, the module that draws and drives its requests;
- ``bench/cells/<cell>.json``: the serving knobs and the check's limits;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding any of them means adding files and entries, never editing code.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic mix, knobs and the
    metrics it reports. Raises ``KeyError`` for an unknown cell."""
    bm = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    e2e = [Metric(m["name"], m["unit"])
           for m in bm["end_to_end"] if _applies(m, name)]
    moved = {m.name for m in e2e}
    per_layer = [Metric(m["name"], m["unit"]) for m in bm["per_layer"]
                 if _applies(m, name) and m["moves"] in moved]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_load_json(ROOT / conf["file"]),
        traffic=_load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        params=_load_json(BENCH_DIR / "cells" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(name: str) -> Callable:
    """``read(record) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[Metric], record) -> Dict[str, dict]:
    """Run each metric's reader; a reader that finds nothing to read
    returns ``None`` and its metric is left out of the line."""
    out = {}
    for m in metrics:
        value = metric_reader(m.name)(record)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out
