"""The check fails what it must fail, at test size on the CPU.

The control (the reference in the program's place, in fp8) and each fault a
training cell can have, planted in the program underneath a run, must make
``correct`` false: the state returned unchanged, half of the trained rows left
out (the mean taken over the rest, the scoring forward untouched), and an
answer altered where it is produced (one row's store update not written). The cells run on one chip, so there is
no exchange between chips to leave out. One input token altered moves a
row's loss by less than the program's own bf16 rounding (PERF.md), so no
comparison with the reference can see it.
"""
import time

import jax
import numpy as np
import pytest

from lib import check, harness
from smoke import smoke_cell

SEED = 2**31 + 11


def test_control_is_not_correct():
    cell = smoke_cell("qwen0.5b_es_k1")
    t = cell.traffic
    rng = np.random.default_rng([SEED, 1])
    ids = rng.choice(t["n_samples"], check.CHECKED * t["meta_batch"],
                     replace=False).reshape(check.CHECKED, -1).tolist()
    prog = check.reference_steps(cell.config, t, SEED, ids, fp8=True)
    ref = check.reference_steps(cell.config, t, SEED, ids, prog.sel)
    nums = check.numbers(prog, ref)
    assert not check.judge(nums, cell.limits), nums


def _unchanged(trainer):
    eng = trainer.engine

    def jitted(kind):
        step = eng.build_step(kind)
        return jax.jit(lambda state, batch: (state, step(state, batch)[1]))
    eng.jitted = jitted


def _half_batch(trainer):
    eng = trainer.engine
    grad, loss = eng._grad_fn, eng._loss_fn

    def half(params, batch):
        # the mean and its gradient over the first half of the rows; every
        # row's own loss as before, for a step that scores from them
        n = batch["tokens"].shape[0] // 2
        (mean, _), g = grad(params, {k: v[:n] for k, v in batch.items()})
        return (mean, loss(params, batch)[1]), g
    eng._grad_fn = half


def _answer(trainer):
    eng = trainer.engine
    update = eng._update_scores

    def dropped(scores, ids, losses):
        return update(scores, ids.at[-1].set(-1), losses)
    eng._update_scores = dropped


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _answer],
                         ids=["state_unchanged", "half_batch", "answer"])
def test_fault_is_not_correct(fault):
    cell = smoke_cell("qwen0.5b_es_k1")
    out = harness.run_cell(cell, SEED, 0.2, False, time.perf_counter(),
                           on_trainer=fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("name", ["qwen0.5b_es_k1", "olmo1b_es_k4",
                                  "qwen0.5b_baseline_b4"])
def test_half_of_the_trained_rows_fails_the_sketch(name):
    """The norms of the gradient hardly see half of the rows left out; its
    sketch does, in every kind of cell."""
    cell = smoke_cell(name)
    out = harness.run_cell(cell, SEED + 1, 0.2, False, time.perf_counter(),
                           on_trainer=_half_batch)
    sketch = {k: v for k, v in out["checks"].items()
              if k.startswith("grad_sketch")}
    assert sketch and any(v["value"] > v["limit"] for v in sketch.values()), \
        out["checks"]
