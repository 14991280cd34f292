"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, named after it:

    BENCHMARK.json                 cells, metrics, configuration files
    bench/configs/<config>.json    sizes; ``system`` names the code that runs it
    bench/traffic/<traffic>.json   parameters that the system's generator reads
    bench/metrics/<metric>.py      ``read(ctx)``: the number, or None
    bench/systems/<system>.py      ``run(cell, seed, window, rec, dev)``
    bench/models/<model_type>.py   a trained model's ``model_config(cfg)``
                                   and ``train_flops_per_token(cfg)``
    bench/reference/<model_type>.py  its plain reference's ``train(...)``

A trainer configuration names its ``model_type`` (HF's own key).

So a new cell, mix or metric is new files plus new entries in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``; KeyError names what is missing."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {workload!r} names config {w['config']!r}, "
                       f"which BENCHMARK.json does not list")
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    traffic_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_file.is_file():
        raise KeyError(f"no traffic file {traffic_file.relative_to(root)}")
    with open(traffic_file) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_in(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if _reported_in(m, workload)])


def _load_file(path: Path, module_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def system(name: str, root: Path = ROOT) -> ModuleType:
    """``bench/systems/<name>.py``: the code that runs one kind of system."""
    return _load_file(root / "bench" / "systems" / f"{name}.py",
                      f"bench_system_{name}")


def _named_file(kind: str, name: str, what: str, root: Path) -> ModuleType:
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"{what} {name!r} has no file at "
                       f"{path.relative_to(root)}")
    return _load_file(path, f"bench_{kind}_" + name.replace(".", "_")
                      .replace("-", "_"))


def metric_reader(name: str, root: Path = ROOT) -> ModuleType:
    """``bench/metrics/<name>.py``, whose ``read(ctx)`` gives the metric."""
    return _named_file("metrics", name, "per-layer metric", root)


def model(model_type: str, root: Path = ROOT) -> ModuleType:
    """``bench/models/<model_type>.py``: ``model_config(cfg)``, the
    program's ModelConfig, and ``train_flops_per_token(cfg)``."""
    return _named_file("models", model_type, "model_type", root)


def reference(model_type: str, root: Path = ROOT) -> ModuleType:
    """``bench/reference/<model_type>.py``: the plain reference, whose
    ``train(cfg, data, seed, n_steps, precision=, rows=)`` gives its
    readings."""
    return _named_file("reference", model_type, "model_type", root)
