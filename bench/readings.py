"""Read the numbers the check compares, for setting a cell's limits.

    python3 bench/readings.py --workload <cell> --mode <mode>[,<mode>...] \
        --seeds 1,2,3

``program``: the cell's timed path, run as ``run.py`` runs it, with a window
of one step, on each seed. ``control``: the reference put in the program's
place, its matrix products in fp8 (the precision below the configuration's
bf16). ``half_batch``, ``half_train``, ``token`` and ``answer``: the reference
in the program's place with a fault planted (``lib/check.py`` and the
traffic's ``bench/steps/`` module): half of the rows left out of the scoring
forward and of the training mean, or of the training mean alone; one input
token of the first row altered; one row's store update not written. Each mode
and seed prints one JSON line of numbers. Needs the chip the cell names;
``run.py`` never runs this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
MODES = ("program", "control", "half_batch", "half_train", "token",
         "answer")


def readings(cell, seed: int, mode: str, t0: float) -> dict:
    import numpy as np
    from lib import check
    from lib.harness import run_cell
    if mode == "program":
        nums: dict = {}
        out = run_cell(cell, seed, 0.0, False, t0, numbers=nums)
        return dict(nums, correct=out["correct"])
    t = cell.traffic
    rng = np.random.default_rng([seed, 1])
    ids = rng.choice(t["n_samples"], check.CHECKED * t["meta_batch"],
                     replace=False).reshape(check.CHECKED, -1).tolist()
    prog = check.reference_steps(cell.config, t, seed, ids,
                                 fp8=mode == "control",
                                 fault=None if mode == "control" else mode)
    ref = check.reference_steps(cell.config, t, seed, ids, prog.sel)
    print(f"worst leaves {check.worst_leaves(prog, ref)}", file=sys.stderr)
    return check.numbers(prog, ref)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    help=f"comma-separated, of {', '.join(MODES)}")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH.parent / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from lib.spec import load_cell
    cell = load_cell(args.workload)
    modes = args.mode.split(",")
    if set(modes) - set(MODES):
        ap.error(f"unknown modes {sorted(set(modes) - set(MODES))}")
    for mode in modes:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            nums = readings(cell, seed, mode, t)
            print(json.dumps({"workload": args.workload, "mode": mode,
                              "seed": seed, "numbers": nums,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
