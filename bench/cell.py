"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

A cell ``<config>.<traffic>`` joins ``configs/<config>.json`` (the model
configuration as it is run) and ``traffic/<traffic>.json`` (the traffic mix
and the limits of the check).  The traffic's ``kind`` names the driver,
``drivers/<kind>.py``.  A per-layer metric ``<name>`` is read by
``metrics/<name>.py``.  New cells, mixes and metrics are new files.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]   # the cell's end-to-end metric entries
    per_layer: List[dict]    # the cell's per-layer metric entries


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT, bench: Optional[Path] = None
              ) -> Cell:
    bench = bench or BENCH
    spec = load_json(root / "BENCHMARK.json")
    ws = [w for w in spec["workloads"] if w["name"] == name]
    if not ws:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = ws[0]
    cfg = [c for c in spec["configs"] if c["name"] == w["config"]][0]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / cfg["file"]),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[e for e in spec["end_to_end"] if _applies(e, name)],
        per_layer=[e for e in spec["per_layer"] if _applies(e, name)],
    )


def model_sizes(config: dict) -> dict:
    """The sizes the harness and the references use, read from the
    configuration file through its ``harness_keys`` map."""
    keys = config["harness_keys"]
    return {k: config[v] for k, v in keys.items()}


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_for(cell: Cell, bench: Optional[Path] = None):
    return load_module((bench or BENCH) / "drivers"
                       / f"{cell.traffic['kind']}.py")


def metric_reader(name: str, bench: Optional[Path] = None):
    return load_module((bench or BENCH) / "metrics" / f"{name}.py")
