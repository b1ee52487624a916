"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration is the ``file`` its entry names; a traffic mix is
``bench/traffic/<mix>.json``; a metric (end-to-end or per-layer) is read by
``bench/metrics/<metric>.py``, a module with ``read(run) -> float | None``;
a cell's output limits are ``bench/checks/<cell>.json``.  Adding a cell,
mix, configuration or metric is adding files and entries: nothing here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list            # BENCHMARK.json metric entries of this cell
    per_layer: list
    root: pathlib.Path


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    root = pathlib.Path(root)
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    mix = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(root / "bench" / "checks" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                limits=limits,
                end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                root=root)


def reader(root: pathlib.Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
