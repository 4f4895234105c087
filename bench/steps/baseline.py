"""What the program's plain training step does, restated for the check.

The step trains on its whole batch: the mean loss, its gradient, AdamW. The
store still takes each row's loss from that forward, by Eq. (3.1):
s <- b2 * s + (1 - b2) * loss, w <- b1 * s + (1 - b1) * loss, seen + 1. The
loss a step reports is the batch's mean.

Faults: ``half_batch`` leaves half of the batch out of the mean and of the
store; ``half_train`` out of the mean alone; ``answer`` leaves the first
step's last store row unwritten.
"""
from __future__ import annotations

FOLLOWS = {"method": {"baseline"}, "meta_batch": None, "minibatch": None,
           "score_every": None, "freq_schedule": None, "seq_len": None,
           "n_samples": None, "beta1": None, "beta2": None, "lr": None,
           "schedule": {"constant"}, "anneal_ratio": {0.0}, "epochs": {1},
           "optimizer": {"adamw"}}


def scores(traffic: dict, step: int) -> bool:
    return False


def work_rows(traffic: dict, step: int):
    return traffic["meta_batch"], traffic["meta_batch"]


def step(ctx, t: int, ids, toks, labs, pick, fault):
    n = len(ids)
    keep = n // 2 if fault == "half_batch" else n
    k = n // 2 if fault in ("half_batch", "half_train") else n
    loss, grads = ctx.grads(toks[:k], labs[:k])
    losses = ctx.losses(toks[:keep], labs[:keep])
    for j, i in enumerate(ids[:keep]):
        ctx.store.update(i, losses[j], write=not (
            fault == "answer" and t == 0 and j == n - 1))
    return float(loss), grads, None, None
