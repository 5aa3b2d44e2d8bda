"""Resolve a cell of BENCHMARK.json to the files that define it.

A cell names a configuration and a traffic mix; each is a JSON file of
its own (``configs/<config>.json``, ``traffic/<traffic>.json``).  Its
correctness limits are ``limits/<cell>.json``; the configuration's
``reference`` key names its architecture, whose plain reference is
``references/<reference>.py`` and whose program is built by
``programs/<reference>.py``; each per-layer metric that lists the cell
(or lists no cells) is read by ``metrics/<metric>.py``.  Nothing here
names a cell or an architecture: a new cell, or a new architecture, is
new files and new entries.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file, as run
    traffic: dict          # the traffic mix file
    limits: dict           # {number: limit} for the correctness check
    end_to_end: list       # BENCHMARK.json entries that this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(kind: str, name: str, base: Path = HERE) -> dict:
    """``<base>/<kind>/<name>.json``."""
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def resolve(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    base = root / "chipbench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json("configs", w["config"], base),
        traffic=load_json("traffic", w["traffic"], base),
        limits=load_json("limits", workload, base),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reference(config: dict):
    """The plain reference module of a configuration's architecture."""
    return importlib.import_module(f"chipbench.references.{config['reference']}")


def program(config: dict):
    """The module that builds the program for a configuration's
    architecture (``arch_config(config)``)."""
    return importlib.import_module(f"chipbench.programs.{config['reference']}")


def metric_reader(name: str, base: Path = HERE):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
