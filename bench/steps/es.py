"""What the program's ES step does, restated for the check (paper Sec. 3).

Every ``score_every``-th step (0-based step % k == 0) scores: a forward over
the B meta-batch rows gives each row's loss, Eq. (3.1) gives its weight
w = b1 * s + (1 - b1) * loss from its stored score s, and the store takes
s <- b2 * s + (1 - b2) * loss, w, seen + 1. A step that does not score takes
each row's stored w. Every step then splits the selection key off the
state's, adds Gumbel noise to log w and trains on the top b rows: the mean
loss over them, its gradient, AdamW. The loss a step reports is the scoring
mean where it scored and the trained rows' mean where it did not.

Faults, for reading what a broken program gives: ``half_batch`` leaves out
half of the meta-batch from the scoring forward and half of the selected
rows from the training mean; ``half_train`` the latter alone; ``answer``
leaves the first step's last store row unwritten.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the TrainerConfig fields this restates, and the values it restates them at
FOLLOWS = {"method": {"es"}, "meta_batch": None, "minibatch": None,
           "score_every": None, "freq_schedule": {"fixed"}, "seq_len": None,
           "n_samples": None, "beta1": None, "beta2": None, "lr": None,
           "schedule": {"constant"}, "anneal_ratio": {0.0}, "epochs": {1},
           "optimizer": {"adamw"}}


def scores(traffic: dict, step: int) -> bool:
    """Does 0-based step ``step`` run the scoring forward?"""
    return step % traffic["score_every"] == 0


def work_rows(traffic: dict, step: int):
    """(rows through the forward, rows through the backward) that the step
    requires: the selected rows' training forward is not counted again."""
    b = traffic["minibatch"]
    return (traffic["meta_batch"] if scores(traffic, step) else b), b


def step(ctx, t: int, ids, toks, labs, pick, fault):
    """Step ``t``; ``pick`` is the program's selection or ``None``.
    -> (reported loss, gradient, own selection, selection keys)."""
    sel_key = ctx.next_key()
    keep = len(ids) // 2 if fault == "half_batch" else len(ids)
    scored = scores(ctx.traffic, t)
    if scored:
        losses = ctx.losses(toks[:keep], labs[:keep])
        w = np.empty(len(ids), np.float32)
        for j, i in enumerate(ids):
            if j < keep:
                w[j] = ctx.store.update(i, losses[j], write=not (
                    fault == "answer" and t == 0 and j == len(ids) - 1))
            else:
                w[j] = ctx.store.get(i)[1]
    else:
        w = np.array([ctx.store.get(i)[1] for i in ids], np.float32)
    g = jax.random.gumbel(sel_key, (len(ids),), jnp.float32)
    keys = np.asarray(jnp.log(jnp.maximum(jnp.asarray(w), 1e-20)) + g)
    own = np.asarray(jax.lax.top_k(jnp.asarray(keys),
                                   ctx.traffic["minibatch"])[1])
    pick = own if pick is None else np.asarray(pick)
    k = len(pick) // 2 if fault in ("half_batch", "half_train") else len(pick)
    loss, grads = ctx.grads(toks[pick[:k]], labs[pick[:k]])
    reported = float(np.mean(losses)) if scored else float(loss)
    return reported, grads, own.tolist(), keys.tolist()
