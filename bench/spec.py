"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

* ``BENCHMARK.json`` (checkout root): the cells, the configurations
  (each names its file), the end-to-end and per-layer metrics;
* ``bench/workloads/<cell>.json``: the cell's own settings (its rate);
* ``bench/traffic/<traffic>.json``: the parameters of a traffic mix;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

A new cell, configuration, traffic mix or metric is a new file and a new
entry in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file's contents
    traffic: dict             # the traffic mix file's contents
    settings: dict            # the cell's own file
    end_to_end: List[dict]    # end-to-end metric entries this cell reports
    per_layer: List[dict]     # per-layer metric entries this cell reports


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with everything it needs, read from files."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    bench_dir = root / BENCH_DIR.name
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    settings = json.loads(
        (bench_dir / "workloads" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, settings=settings, end_to_end=e2e,
                per_layer=layer)


def metric_reader(name: str,
                  root: pathlib.Path = ROOT) -> Callable[[object],
                                                         Optional[float]]:
    """``read(ctx) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = root / BENCH_DIR.name / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def files_named(root: pathlib.Path = ROOT) -> Dict[str, pathlib.Path]:
    """Every file that ``BENCHMARK.json`` names, directly or by name."""
    bench = load_benchmark(root)
    bench_dir = root / BENCH_DIR.name
    out: Dict[str, pathlib.Path] = {}
    for c in bench["configs"]:
        out[f"config:{c['name']}"] = root / c["file"]
    for w in bench["workloads"]:
        out[f"workload:{w['name']}"] = bench_dir / "workloads" / \
            f"{w['name']}.json"
        out[f"traffic:{w['traffic']}"] = bench_dir / "traffic" / \
            f"{w['traffic']}.json"
    for m in bench["per_layer"]:
        out[f"metric:{m['name']}"] = bench_dir / "metrics" / f"{m['name']}.py"
    return out
