"""What decides ``correct``: the first steps of the timed path against the
plain reference.

The program's record of its first ``CHECKED`` steps (a ``Record``) is compared
with the same steps taken by the reference (``reference_steps``): each step's
loss; the Eq. (3.1) store at every row scored, and how often each was scored;
the Gumbel top-k selection; the first gradient as the optimizer got it, by
its norm and by its sketch (``model.sketch``); and the parameters' change
after the checked steps. The reference knows the configuration, the traffic
and the seed, the rows the program was fed and the rows it selected. It takes
no weight, score or table that the program made. What a step does is the
module the traffic names (``bench/steps/<steps>.py``); the model's forward is
the module the configuration names (``bench/references/<reference>.py``).

``reference_steps`` also stands in the program's place: as the control
(``fp8=True``), and with a fault planted (``fault``), to read the numbers a
broken program gives: the steps module's faults (``half_batch``,
``half_train``, ``answer``) and ``token`` (one input token of the first row
altered). A state returned unchanged needs no run: its change reads 1.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import model
from .spec import module
from .traffic import Rows

CHECKED = 3


@dataclasses.dataclass
class Record:
    """What one side produced in the checked steps."""
    ids: List[List[int]]                 # meta-batch row ids per step
    losses: List[float]                  # the step's reported loss
    sel: List[Optional[List[int]]]       # selected meta-batch positions
    store: Dict[str, List[float]]        # s, w, seen at every id of `ids`
    grad_norms: Dict[str, float]         # first gradient, per leaf
    grad_sketch: Dict[str, List[float]]  # first gradient's sketch, per leaf
    change_norms: Dict[str, float]       # params after CHECKED - initial
    keys: List[Optional[List[float]]] = dataclasses.field(
        default_factory=list)            # reference only: selection keys


class Store:
    """The Eq. (3.1) store, row by row, from the 1/n prior."""

    def __init__(self, n: int, beta1: float, beta2: float):
        self.prior, self.b1, self.b2 = 1.0 / n, beta1, beta2
        self.rows: Dict[int, tuple] = {}

    def get(self, i: int) -> tuple:
        return self.rows.get(i, (self.prior, self.prior, 0))

    def update(self, i: int, loss: float, write: bool = True) -> float:
        """Row ``i`` takes ``loss``; returns its new weight w."""
        s, _, seen = self.get(i)
        w = self.b1 * s + (1 - self.b1) * loss
        if write:
            self.rows[i] = (self.b2 * s + (1 - self.b2) * loss, w, seen + 1)
        return w


class _Context:
    """What a steps module is given: the traffic, the store, the selection
    key, and the reference's losses and gradient at the current weights."""

    def __init__(self, cfg, traffic, seed, fp8):
        ref = module("references", cfg["reference"])
        self.traffic = traffic
        self.store = Store(traffic["n_samples"], traffic["beta1"],
                           traffic["beta2"])
        self.rng = model.selection_key(seed)
        self.params = None
        self._losses = jax.jit(
            lambda p, t, l: ref.row_losses(cfg, p, t, l, fp8))
        self._grads = jax.jit(
            lambda p, t, l: ref.mean_loss_and_grads(cfg, p, t, l, fp8))

    def next_key(self) -> jax.Array:
        self.rng, key = jax.random.split(self.rng)
        return key

    def losses(self, toks, labs) -> np.ndarray:
        return np.asarray(self._losses(self.params, toks, labs))

    def grads(self, toks, labs):
        return self._grads(self.params, toks, labs)


def _adamw(opt: dict):
    b1, b2, eps, wd = (opt["beta1"], opt["beta2"], opt["eps"],
                       opt["weight_decay"])

    def step(params, grads, m, v, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree.leaves(grads)))
        clip = opt["grad_clip_norm"]
        if clip > 0:
            scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m, v):
            return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)
        return (jax.tree.map(upd, params, m, v), m, v,
                model.leaf_norms(grads), model.sketch(grads))
    return jax.jit(step, donate_argnums=(0, 2, 3))


def reference_steps(cfg: dict, traffic: dict, seed: int, ids: List[List[int]],
                    sel: Optional[List[Optional[List[int]]]] = None, *,
                    fp8: bool = False, fault: Optional[str] = None) -> Record:
    """The reference's first ``len(ids)`` steps on the rows ``ids``.

    ``sel`` gives the program's selection (the reference then trains on the
    rows the program chose); ``None`` makes the reference select by its own
    keys, as it does in the program's place.
    """
    steps = module("steps", traffic["steps"])
    rows = Rows.for_cell(cfg, traffic, seed)
    ctx = _Context(cfg, traffic, seed, fp8)
    key = model.weights_key(seed)
    ctx.params = model.weights_fn(cfg)(key)
    m = jax.tree.map(jnp.zeros_like, ctx.params)
    v = jax.tree.map(jnp.zeros_like, ctx.params)
    adamw = _adamw(traffic["adamw"])
    out = Record(ids=[list(map(int, x)) for x in ids], losses=[], sel=[],
                 store={}, grad_norms={}, grad_sketch={}, change_norms={},
                 keys=[])
    for t, step_ids in enumerate(out.ids):
        win = rows.windows(step_ids)
        toks, labs = win[:, :-1].astype(np.int32), win[:, 1:].astype(np.int32)
        if fault == "token":
            toks = toks.copy()
            toks[0, toks.shape[1] // 2] = (toks[0, toks.shape[1] // 2] + 1) \
                % cfg["vocab_size"]
        loss, grads, own, keys = steps.step(
            ctx, t, step_ids, toks, labs, None if sel is None else sel[t],
            fault)
        out.losses.append(loss)
        out.sel.append(own)
        out.keys.append(keys)
        ctx.params, m, v, gnorms, gsketch = adamw(
            ctx.params, grads, m, v, jnp.float32(t + 1),
            jnp.float32(traffic["lr"]))
        if t == 0:
            out.grad_norms = model.flat_paths(jax.device_get(gnorms))
            out.grad_sketch = model.flat_paths(jax.device_get(gsketch))
        del grads
    out.change_norms = model.flat_paths(jax.device_get(
        model.change_norms_fn(cfg)(ctx.params, key)))
    vals = [ctx.store.get(i) for s in out.ids for i in s]
    out.store = {"s": [float(x[0]) for x in vals],
                 "w": [float(x[1]) for x in vals],
                 "seen": [int(x[2]) for x in vals]}
    return out


def numbers(prog: Record, ref: Record) -> Dict[str, float]:
    """The numbers compared, each a gap between program and reference."""
    out = {}
    lp, lr = np.asarray(prog.losses), np.asarray(ref.losses)
    out["loss_gap"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    # mean over the rows scored: a steady number, where the widest row
    # swings with the rounding of one row (an unwritten row reads 1 on it
    # and is caught exactly by seen_miss)
    rows = np.asarray(ref.store["seen"]) > 0
    gaps = [np.abs(np.asarray(prog.store[k]) - np.asarray(ref.store[k]))[rows]
            / np.abs(np.asarray(ref.store[k]))[rows] for k in ("s", "w")]
    out["score_gap"] = float(np.mean(gaps))
    out["seen_miss"] = float(np.sum(np.asarray(prog.store["seen"])
                                    != np.asarray(ref.store["seen"])))
    if any(k is not None for k in ref.keys):
        worst = 0.0
        for keys, pick in zip(ref.keys, prog.sel):
            keys = np.asarray(keys)
            kth = np.sort(keys)[::-1][len(pick) - 1]
            worst = max(worst, float(np.max(kth - keys[np.asarray(pick)])))
        out["select_gap"] = worst
    out["grad_gap"] = _leaf_gap(prog.grad_norms, ref.grad_norms)
    # the median leaf: the worst is always the tied embedding, whose bf16
    # gradient reads up to 0.28 off on sound runs, as far as the control
    sketch = list(_sketch_gaps(prog.grad_sketch, ref.grad_sketch).values())
    out["grad_sketch_gap_med"] = float(np.median(sketch)) if sketch \
        else float("inf")
    moved = _moved(ref.grad_norms)
    out["update_gap"] = _leaf_gap(
        {k: prog.change_norms[k] for k in moved},
        {k: ref.change_norms[k] for k in moved},
        floor=float(np.median(list(ref.change_norms.values()))))
    return out


def worst_leaves(prog: Record, ref: Record) -> Dict[str, str]:
    """The leaf that sets ``grad_gap`` and the one that sets ``update_gap``."""
    def worst(p, r, med):
        return max(r, key=lambda k: abs(p[k] - r[k]) / max(r[k], med, 1e-30))
    moved = _moved(ref.grad_norms)
    return {"grad_gap": worst(prog.grad_norms, ref.grad_norms,
                              float(np.median(list(ref.grad_norms.values())))),
            "update_gap": worst(
                {k: prog.change_norms[k] for k in moved},
                {k: ref.change_norms[k] for k in moved},
                float(np.median(list(ref.change_norms.values()))))}


def _sketch_gaps(prog: Dict[str, List[float]],
                 ref: Dict[str, List[float]]) -> Dict[str, float]:
    """Per leaf, |sketch_prog - sketch_ref| / max(|sketch_ref|, median
    leaf's |sketch_ref|); empty where the two sides' leaves differ."""
    if set(prog) != set(ref):
        return {}
    norm = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norm.values())))
    return {k: float(np.linalg.norm(np.subtract(prog[k], ref[k])))
            / max(norm[k], med, 1e-30) for k in ref}


def _moved(grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's: the others (a key bias under softmax) move by round-off alone."""
    med = float(np.median(list(grad_norms.values())))
    return sorted(k for k, g in grad_norms.items() if g >= 1e-3 * med)


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              floor: Optional[float] = None) -> float:
    """Worst leaf of |norm_prog - norm_ref| / max(norm_ref, median leaf)."""
    if set(prog) != set(ref):
        return float("inf")
    med = float(np.median(list(ref.values()))) if floor is None else floor
    return float(max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
                     for k in ref))


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct iff every limit holds; a number that is not finite fails."""
    if set(limits) - set(nums):
        return False
    return all(np.isfinite(nums[k]) and nums[k] <= lim
               for k, lim in limits.items())
