"""A cell, found by its name: ``BENCHMARK.json`` names the configuration and
the traffic, and each lives in a file of its own under ``bench/``.

A traffic file holds ``TrainerConfig`` fields under their own names, plus
``steps`` (the module under ``bench/steps/`` that restates what those steps
do, for the check), ``adamw`` (the optimizer the check holds the program to)
and ``rows`` (the generator's parameters). A configuration file names its
``reference`` (``bench/references/``: the model's plain forward, its weights'
layout and its work) and its ``adapter`` (``bench/adapters/``: the program's
model config made from the file).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRAFFIC_KEYS = ("steps", "adamw", "rows")   # the benchmark's, not the trainer's


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]      # entries of BENCHMARK.json
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, limits=limits["limits"],
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


@functools.lru_cache(maxsize=None)
def module(kind: str, name: str):
    """``bench/<kind>/<name>.py``: a metric's reader (``read(run)``), a
    reference, the steps a traffic file names, an adapter."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    return module("metrics", name).read
