"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own, found by name:

* ``BENCHMARK.json``'s ``configs[].file`` -> the configuration's sizes;
* ``bench/traffic/<traffic>.json`` -> the traffic mix's parameters;
* ``bench/metrics/<metric>.py`` -> the reader of one per-layer metric;
* ``bench/roofline/<kernel>.py`` -> a kernel's operation and byte counts.

Adding a cell therefore means adding files and entries, never editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the metrics it reports."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / cfgs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def load_module(path: Path):
    """Import one file of the benchmark by path (metric readers, roofline
    counts), without putting its directory on ``sys.path``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return load_module(BENCH / "metrics" / f"{name}.py").read


def roofline(kernel: str):
    """The module ``bench/roofline/<kernel>.py``."""
    return load_module(BENCH / "roofline" / f"{kernel}.py")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``; an unknown
    kind is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
