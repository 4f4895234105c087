"""One run of one cell: set-up, the measured window, the check, the result.

The window drives ``repro.launch.train.Trainer.train()``, the path behind
``python -m repro.launch.train``, on the benchmark's own rows and weights:

  set-up   build the Trainer, hand it the benchmark's weights, then let
           ``train()`` run WARMUP steps. The first compiles; the first
           CHECKED are recorded for the check. The window opens at the
           host-clock stamp of step WARMUP.
  window   every later step is stamped by the step hook. The loop already
           waits for each step's loss, so a stamp marks a finished step. The
           hook closes the window at the first step boundary after
           ``seconds`` (``TRACE_STEPS`` steps in a traced run) by lowering
           ``max_steps``, so ``train()`` stops after one more step, which is
           not counted.
  check    the train state is blocked on and freed; then the reference takes
           the checked steps again and the numbers are compared.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, model
from . import trace as trace_lib
from .spec import TRAFFIC_KEYS, Cell, metric_reader, module
from .traffic import Rows, batch_ms

WARMUP = 4          # steps before the window opens (a whole cadence period)
TRACE_STEPS = 8     # steps in a traced window (two cadence periods)
OWNED = {"arch", "smoke", "seed", "max_steps"}   # TrainerConfig fields set here


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    cell: Cell
    stamps: List[float]          # host clock after step 1, 2, ...
    open_step: int               # the window holds steps open_step+1..close_step
    close_step: int
    setup_s: float
    rows_per_step: int
    trace: Optional[trace_lib.Trace]
    device_kind: str

    def stamp(self, step: int) -> float:
        return self.stamps[step - 1]

    @property
    def window_s(self) -> float:
        return self.stamp(self.close_step) - self.stamp(self.open_step)

    def step_at(self, t: float) -> int:
        """The step whose stamp is the first at or after host time ``t``."""
        return next((k + 1 for k, s in enumerate(self.stamps) if s >= t),
                    len(self.stamps) + 1)

    @property
    def window_steps(self) -> List[int]:
        """0-based optimizer steps that ran in the window."""
        return list(range(self.open_step, self.close_step))


def trainer_config(cell: Cell, seed: int):
    """The ``TrainerConfig`` the traffic file states, field by field.

    A key that is no field, or that the harness sets itself, is refused; so
    is a field, or a value, that the traffic's steps module does not
    restate, since the check would then hold the program to other steps.
    """
    from repro.launch.train import TrainerConfig
    fields = {f.name for f in dataclasses.fields(TrainerConfig)}
    stated = {k: v for k, v in cell.traffic.items() if k not in TRAFFIC_KEYS}
    bad = sorted(set(stated) - (fields - OWNED))
    if bad:
        raise ValueError(f"traffic keys {bad} are not TrainerConfig fields "
                         f"that a traffic file may set")
    follows = module("steps", cell.traffic["steps"]).FOLLOWS
    for k, v in stated.items():
        if k not in follows or (follows[k] is not None
                                and v not in follows[k]):
            raise ValueError(
                f"steps {cell.traffic['steps']!r} do not restate {k}={v!r}; "
                f"a traffic file that sets it names steps that do")
    return TrainerConfig(arch=cell.config["arch"], smoke=False, seed=seed,
                         max_steps=1 << 40, **stated)


def _check_program(trainer, cell: Cell) -> None:
    """Refuse to run a program that departs from what the cell states."""
    import jax
    want = {"/".join(p): s for p, s, _ in model.leaf_specs(cell.config)}
    got = {"/".join(getattr(k, "key", str(k)) for k in path): tuple(x.shape)
           for path, x in jax.tree_util.tree_flatten_with_path(
               trainer.state.params)[0]}
    if want != got:
        raise RuntimeError(f"the program's weights are laid out as {got}, "
                           f"the configuration states {want}")
    oc, adamw = trainer.opt_cfg, cell.traffic["adamw"]
    stated = (oc.kind, oc.beta1, oc.beta2, oc.eps, oc.weight_decay,
              oc.grad_clip_norm)
    if stated != ("adamw", adamw["beta1"], adamw["beta2"], adamw["eps"],
                  adamw["weight_decay"], adamw["grad_clip_norm"]):
        raise RuntimeError(f"the program's optimizer {oc} is not the AdamW "
                           f"the traffic states: {adamw}")


_HOST_EVENTS: List[tuple] = []   # (host clock at the end, what, seconds)
_LISTENING: List[bool] = []


def _on_compile(event: str, seconds: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _HOST_EVENTS.append((time.perf_counter(),
                             f"compile {kw.get('fun_name')}", seconds))


def _on_gc(phase: str, info: dict, _start: List[float] = [0.0]) -> None:
    if info.get("generation") != 2:
        return
    if phase == "start":
        _start[0] = time.perf_counter()
    else:
        now = time.perf_counter()
        _HOST_EVENTS.append((now, "gc gen 2", now - _start[0]))


def _listen() -> None:
    """Record compiles (and reads of the compile cache) and full garbage
    collections on the host clock, to name what a stalled step waited on."""
    import jax
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        gc.callbacks.append(_on_gc)
        _LISTENING.append(True)


class _Capture:
    """Records the program's checked steps from inside the step hook."""

    def __init__(self, cell: Cell, seed: int, rows: Rows):
        import jax
        self.cell, self.rows = cell, rows
        self.key = model.weights_key(seed)
        self.losses: List[float] = []
        self.sel: List[Optional[List[int]]] = []
        self.grad_norms: Dict[str, float] = {}
        self.grad_sketch: Dict[str, list] = {}
        self.change_norms: Dict[str, float] = {}
        self.store: Dict[str, list] = {}
        self._norms = jax.jit(model.leaf_norms)
        self._sketch = jax.jit(model.sketch)
        self._change = model.change_norms_fn(cell.config)
        self._gather = jax.jit(lambda sc, ids: (sc.s[ids], sc.w[ids],
                                                sc.seen[ids]))

    def step(self, trainer, n: int) -> None:
        import jax
        rec = trainer.metrics_log[-1]
        self.losses.append(float(rec["loss"]))
        self.sel.append(rec.get("sel_ids"))
        st = trainer.state
        if n == 1:
            b1 = self.cell.traffic["adamw"]["beta1"]
            m = model.flat_paths(jax.device_get(self._norms(st.opt.m)))
            self.grad_norms = {k: x / (1.0 - b1) for k, x in m.items()}
            m = model.flat_paths(jax.device_get(self._sketch(st.opt.m)))
            self.grad_sketch = {k: [x / (1.0 - b1) for x in v]
                                for k, v in m.items()}
        if n == check.CHECKED:
            self.change_norms = model.flat_paths(jax.device_get(
                self._change(st.params, self.key)))
            ids = np.concatenate(self.rows.requested[:check.CHECKED])
            s, w, seen = jax.device_get(self._gather(st.scores, ids))
            self.store = {"s": s.astype(float).tolist(),
                          "w": w.astype(float).tolist(),
                          "seen": seen.astype(int).tolist()}

    def record(self) -> check.Record:
        ids = [r.tolist() for r in self.rows.requested[:check.CHECKED]]
        return check.Record(ids=ids, losses=self.losses, sel=self.sel,
                            store=self.store, grad_norms=self.grad_norms,
                            grad_sketch=self.grad_sketch,
                            change_norms=self.change_norms)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, t0: float,
             *, on_trainer: Optional[Callable] = None,
             trace_dir: Optional[str] = None,
             numbers: Optional[dict] = None) -> dict:
    """One run; returns the result line. ``on_trainer(trainer)`` is for tests
    that break the timed path underneath; a ``trace_dir`` given is kept; a
    ``numbers`` dict given takes every number the check read, compared or
    not (for ``readings.py``)."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.configs.registry import get_config
    from repro.launch.train import Trainer

    cfg, traffic = cell.config, cell.traffic
    dev = jax.devices()[0]
    rows = Rows.for_cell(cfg, traffic, seed)
    print(json.dumps({"traffic_batch_ms": batch_ms(rows, traffic["meta_batch"])}),
          flush=True)
    adapter = module("adapters", cfg["adapter"])
    trainer = Trainer(trainer_config(cell, seed), source=rows,
                      model_cfg=adapter.program_config(
                          cfg, get_config(cfg["arch"])))
    _check_program(trainer, cell)
    trainer.state = dataclasses.replace(
        trainer.state, params=model.weights_fn(cfg)(model.weights_key(seed)),
        rng=model.selection_key(seed))
    if on_trainer is not None:
        on_trainer(trainer)
    cap = _Capture(cell, seed, rows)
    stamps: List[float] = []
    win = {"open": None, "close": None, "ann": None}
    tdir = trace_dir or (tempfile.mkdtemp(prefix="bench_trace_")
                         if traced else None)

    def hook(tr, _epoch):
        now = time.perf_counter()
        if win["close"] is not None:
            return
        with TraceAnnotation("bench.step_hook"):
            stamps.append(now)
            n = tr.global_step
            if n <= check.CHECKED:
                cap.step(tr, n)
            if n == WARMUP:
                win["open"] = n
                if traced:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(tdir, profiler_options=opts)
                    win["ann"] = TraceAnnotation(trace_lib.WINDOW)
                    win["ann"].__enter__()
                return
            if win["open"] is None:
                return
            done = (n - WARMUP >= TRACE_STEPS if traced
                    else now - stamps[WARMUP - 1] >= seconds)
            if done:
                if traced:
                    win["ann"].__exit__(None, None, None)
                win["close"] = n
                tr.tc.max_steps = n

    trainer.step_hooks.append(hook)
    _listen()
    _HOST_EVENTS.clear()
    with TraceAnnotation("bench.train"):
        trainer.train()
    jax.block_until_ready(trainer.state)
    if win["close"] is None:
        raise RuntimeError(f"the window never closed: {len(stamps)} steps")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    losses = [r["loss"] for r in trainer.metrics_log[win["open"]:win["close"]]]
    prog = cap.record()
    del trainer, hook
    gc.collect()
    tr_obj = None
    if traced:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        t_stop = time.perf_counter() - t
        tr_obj = trace_lib.load(tdir)
        print(f"trace: stop {t_stop:.1f} s, read "
              f"{time.perf_counter() - t - t_stop:.1f} s", file=sys.stderr)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    run = Run(cell=cell, stamps=stamps, open_step=win["open"],
              close_step=win["close"], setup_s=stamps[WARMUP - 1] - t0,
              rows_per_step=traffic["meta_batch"], trace=tr_obj,
              device_kind=dev.device_kind)
    steps = np.diff(stamps[win["open"] - 1:win["close"]]) * 1e3
    slow = np.argsort(steps)[::-1][:5]
    print(f"window: {len(steps)} steps in {run.window_s:.3f} s, median "
          f"{np.median(steps):.1f} ms, longest (step, ms) "
          f"{[(int(win['open'] + 1 + i), round(float(steps[i]), 1)) for i in slow]}",
          file=sys.stderr)
    lo, hi = run.stamp(run.open_step), run.stamp(run.close_step)
    print("in the window (step, what, ms): "
          f"{[(run.step_at(t), what, round(sec * 1e3, 1)) for t, what, sec in _HOST_EVENTS if lo < t <= hi]}",
          file=sys.stderr)

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": False, "attempted": len(losses),
           "failed": int(sum(not np.isfinite(x) for x in losses)),
           "metrics": metrics, "device": device}
    if tr_obj is not None:
        device["busy_s"] = trace_lib.busy_s(tr_obj)
        device["window_s"] = trace_lib.window_s(tr_obj)
        out["breakdown"] = {"device_ops": trace_lib.top_ops(tr_obj),
                            "idle_gaps": trace_lib.idle_gaps(tr_obj)}

    ref = check.reference_steps(cfg, traffic, seed, prog.ids, prog.sel)
    nums = check.numbers(prog, ref)
    if numbers is not None:
        numbers.update(nums)
    print(f"worst leaves {check.worst_leaves(prog, ref)}", file=sys.stderr)
    out["correct"] = check.judge(nums, cell.limits) and out["failed"] == 0
    free = {k: v for k, v in nums.items() if k not in cell.limits}
    if free:
        print(f"not compared in this cell: {free}", file=sys.stderr)
    out["checks"] = {k: {"value": nums.get(k), "limit": lim}
                     for k, lim in cell.limits.items()}
    for k, lim in cell.limits.items():
        print(f"check {k} {nums.get(k)!r} limit {lim!r}", file=sys.stderr,
              flush=True)
    return out
