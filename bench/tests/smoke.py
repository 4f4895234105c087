"""Test-size cells: a real cell's traffic and metrics with a small model
(``data/smoke_*.json``), short rows and the limits set for that size
(``data/limits_smoke.json``, from CPU readings at this size)."""
import dataclasses
import json

from conftest import BENCH
from lib.spec import load_cell

DATA = BENCH / "tests" / "data"
CONFIG = {"qwen0.5b_es_k1": "smoke_qwen", "qwen0.5b_baseline_b4": "smoke_qwen",
          "olmo1b_es_k1": "smoke_olmo", "olmo1b_es_k4": "smoke_olmo"}


def smoke_cell(name: str):
    cell = load_cell(name)
    t = dict(cell.traffic)
    scale = 2 if t["method"] != "baseline" else 1
    t.update(meta_batch=t["meta_batch"] // scale,
             minibatch=t["minibatch"] // scale, seq_len=32, n_samples=4096)
    limits = json.loads((DATA / "limits_smoke.json").read_text())
    keep = {k: v for k, v in limits.items() if k in cell.limits}
    return dataclasses.replace(
        cell, config=json.loads((DATA / f"{CONFIG[name]}.json").read_text()),
        traffic=t, limits=keep)
