"""The harness's plumbing at test size on the CPU.

The command itself refuses to run without a TPU; here ``run_cell`` is driven
directly on a test-size cell, past the look for a chip.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH
from lib import harness
from lib.spec import load_cell, metric_reader
from smoke import smoke_cell

ROOT = BENCH.parent
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=300)
    assert p.returncode == 2
    assert "correct" not in p.stdout
    assert "TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = load_cell(name)
    assert cell.config["vocab_size"] > 0 and cell.traffic["seq_len"] > 0
    for m in cell.end_to_end + cell.per_layer:
        assert callable(metric_reader(m["name"]))
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert {"seen_miss", "score_gap", "grad_gap", "update_gap"} <= set(cell.limits)


@pytest.mark.parametrize("key,value,match", [
    ("pipelined", True, "do not restate pipelined"),       # a trainer field
    ("method", "eswp", "do not restate method"),            # another value
    ("n_train", 4096, "not TrainerConfig fields"),          # no field
    ("seed", 3, "not TrainerConfig fields"),                # the harness's
])
def test_traffic_key_refused(key, value, match):
    cell = load_cell(CELLS[0])
    cell.traffic = dict(cell.traffic, **{key: value})
    with pytest.raises(ValueError, match=match):
        harness.trainer_config(cell, 1)


@pytest.mark.parametrize("name", CELLS)
def test_traffic_maps_onto_trainer_config(name):
    cell = load_cell(name)
    tc = harness.trainer_config(cell, 7)
    for k, v in cell.traffic.items():
        if k not in ("steps", "adamw", "rows"):
            assert getattr(tc, k) == v, k
    assert (tc.arch, tc.seed, tc.smoke) == (cell.config["arch"], 7, False)


@pytest.mark.parametrize("name,traced", [("qwen0.5b_es_k1", False),
                                         ("olmo1b_es_k4", True),
                                         ("qwen0.5b_baseline_b4", False)])
def test_smoke_run_result_line(name, traced):
    cell = smoke_cell(name)
    out = harness.run_cell(cell, 2**31 + 5, 0.5, traced, time.perf_counter())
    keys = KEYS + (["breakdown"] if traced else []) + ["checks"]
    assert list(out) == keys
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    want = {m["name"] for m in cell.end_to_end}
    if traced:
        # no device plane on the CPU: the trace readers find nothing
        assert out["metrics"] == {}
        assert out["device"]["window_s"] > 0
    else:
        assert set(out["metrics"]) == want
        assert out["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert list(out["checks"]) == list(cell.limits)
