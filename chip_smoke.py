"""Run the ES trainer's main path on a TPU and check what comes out.

    python chip_smoke.py                 # one chip: phases a, b, c + kernels
    python chip_smoke.py --four-chips    # four chips: phase a, sharded store
                                         # against the replicated one

Every phase builds ``repro.launch.train.Trainer`` from a ``TrainerConfig``,
as ``python -m repro.launch.train`` does, for qwen1.5-0.5b at its published
widths (``--full``, random weights from ``--seed``) on the synthetic source
with n_samples=1024 and seq_len=1024, and trains 8 steps:

  a  method=es, meta-batch 16, minibatch 4, f32 score store
  b  the same with the int8 score store (``quant_scores``)
  c  method=baseline at meta-batch 4 (the backprop per step of a and b)

Each phase checks a finite loss, that the score store counted every scored
sample (``sum(seen)``), and, for a and b, that the compiled step holds the
score_update kernel (``tpu_custom_call``).  Then one compiled call of each
score_update kernel is compared with ``kernels/score_update/ref.py``.

Anything that fails exits non-zero.  With no TPU it exits at once and prints
no result.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The wall time of the 8 steps is printed for information only.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.score_update.ref import (  # noqa: E402
    quant_score_update_ref, score_update_ref)
from repro.kernels.score_update.score_update import (  # noqa: E402
    fused_quant_score_update, fused_score_update)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.inputs import host_batch_placer  # noqa: E402
from repro.launch.train import Trainer, TrainerConfig  # noqa: E402

ARCH = "qwen1.5-0.5b"
STEPS = 8


def log(**kv) -> None:
    print(json.dumps(kv), flush=True)


def phase_config(method: str, meta_batch: int, minibatch: int, *,
                 seed: int, quant: bool = False, shard: bool = False
                 ) -> TrainerConfig:
    return TrainerConfig(arch=ARCH, smoke=False, method=method, epochs=1,
                         meta_batch=meta_batch, minibatch=minibatch,
                         n_samples=1024, seq_len=1024, seed=seed,
                         quant_scores=quant, shard_scores=shard,
                         max_steps=STEPS)


def seen_total(scores) -> int:
    seen = scores.seen_q if hasattr(scores, "seen_q") else scores.seen
    return int(np.asarray(seen, np.int64).sum())


def run_phase(name: str, tc: TrainerConfig, *, need_kernel: bool):
    """Train one phase; returns the trainer and its per-step ``seen``."""
    tr = Trainer(tc)
    cfg = tr.model_cfg
    kind = "baseline" if tc.method == "baseline" else "scheduled"
    batch = host_batch_placer(tr.ctx)(tr.pipeline.batch_at(0, 0))
    t0 = time.perf_counter()
    compiled = tr.engine.jitted(kind).lower(tr.state, batch).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    del compiled, batch
    seen_steps = []
    tr.step_hooks.append(lambda t, _epoch: seen_steps.append(
        np.asarray(getattr(t.state.scores, "seen", 0))))
    out = tr.train()
    scored = out["steps"] * tc.meta_batch
    seen = seen_total(tr.state.scores)
    stats = jax.devices()[0].memory_stats() or {}
    summary = {
        "phase": name, "arch": cfg.name, "method": tc.method,
        "widths": {"layers": cfg.num_layers, "d_model": cfg.d_model,
                   "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
                   "d_ff": cfg.d_ff, "vocab": cfg.vocab_size},
        "B": tc.meta_batch,
        "b": tc.minibatch if tc.method != "baseline" else tc.meta_batch,
        "S": tc.seq_len, "store": "int8" if tc.quant_scores else "f32",
        "store_sharded": out["score_store_sharded"],
        "steps": out["steps"], "compile_s": compile_s,
        "final_loss": out["final_loss"], "sum_seen": seen,
        "samples_scored": scored,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "tpu_custom_call": has_kernel,
        # host clock over the 8 steps, ended by the last loss read
        "train_wall_s_informational": out["wall_time"],
    }
    log(**summary)
    failed = []
    if out["steps"] != STEPS:
        failed.append(f"ran {out['steps']} steps, not {STEPS}")
    if not math.isfinite(out["final_loss"]):
        failed.append(f"final loss {out['final_loss']}")
    if seen != scored:
        failed.append(f"sum(seen)={seen} but {scored} samples were scored")
    if need_kernel and not has_kernel:
        failed.append("the compiled step holds no tpu_custom_call")
    if failed:
        raise AssertionError(f"phase {name}: " + "; ".join(failed))
    return tr, seen_steps


def check_f32_kernel(seed: int) -> None:
    """One compiled masked f32 call at 2^20 rows against ref.py."""
    n, B = 1 << 20, 64
    rng = np.random.default_rng(seed)
    s = jnp.asarray(rng.uniform(0.1, 2.0, n), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 2.0, n), jnp.float32)
    seen = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    ids = jnp.asarray(rng.choice(n, B, replace=False), jnp.int32)
    losses = jnp.asarray(rng.uniform(0.1, 4.0, B), jnp.float32)
    want = score_update_ref(s, w, seen, ids, losses, beta1=0.2, beta2=0.9)
    got = fused_score_update(s, w, seen, ids, losses, beta1=0.2, beta2=0.9,
                             masked=True)
    diff = {k: float(jnp.max(jnp.abs(g - x))) for k, g, x in
            zip(("s", "w"), got[:2], want[:2])}
    seen_equal = bool(jnp.array_equal(got[2], want[2]))
    log(kernel="fused_score_update", n=n, B=B, max_abs_diff=diff,
        seen_equal=seen_equal)
    for k, g, x in zip(("s", "w"), got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-6,
                                   err_msg=k)
    assert seen_equal, "fused_score_update: seen differs from ref.py"


def check_quant_kernel(seed: int) -> None:
    """One compiled int8 call (2^20 rows, block 1024, ring 1024) against
    ref.py on the contract it pins: unique ids, a few masked, fresh ring
    slots, half the batch holding live residuals."""
    n, block, R, B = 1 << 20, 1024, 1024, 64
    rng = np.random.default_rng(seed + 1)
    nb = n // block
    rows = rng.choice(n, B, replace=False).astype(np.int32)
    ids = rows.copy()
    ids[-8:] = -1                                    # other shards' rows
    live = R // 2
    er = np.full(R, -1, np.int32)
    er[:live] = rng.choice(n, live, replace=False)
    er[:B // 2] = rows[:B // 2]                      # ring hits
    et = np.zeros(R, np.int32)
    et[:live] = rng.permutation(live) + 1            # unique live stamps
    es = np.zeros(R, np.float32)
    ew = np.zeros(R, np.float32)
    es[:live] = rng.uniform(-4e-3, 4e-3, live)
    ew[:live] = rng.uniform(-4e-3, 4e-3, live)
    args = [
        jnp.asarray(rng.integers(0, 128, n), jnp.int8),
        jnp.asarray(rng.integers(0, 128, n), jnp.int8),
        jnp.asarray(rng.integers(0, 100, n), jnp.int8),
        jnp.asarray(rng.uniform(5e-3, 2e-2, nb), jnp.float32),
        jnp.asarray(rng.uniform(5e-3, 2e-2, nb), jnp.float32),
        jnp.asarray(er), jnp.asarray(et), jnp.asarray(es), jnp.asarray(ew),
        jnp.asarray(ids), jnp.asarray(ids),
        jnp.asarray(rng.uniform(0.1, 2.0, B), jnp.float32),
        jnp.asarray(live + np.arange(B), jnp.int32),
        jnp.asarray(live + 1 + np.arange(B), jnp.int32)]
    want = quant_score_update_ref(*args, beta1=0.2, beta2=0.9, block=block)
    got = fused_quant_score_update(*args, beta1=0.2, beta2=0.9, block=block)
    names = ("s_q", "w_q", "seen_q", "err_rows", "err_seq", "err_s", "err_w")
    exact = {k: bool(jnp.array_equal(g, x))
             for k, g, x in zip(names[:5], got, want)}
    diff = {k: float(jnp.max(jnp.abs(g - x)))
            for k, g, x in zip(names[5:], got[5:], want[5:])}
    log(kernel="fused_quant_score_update", n=n, block=block, ring=R, B=B,
        bitwise_equal=exact, max_abs_diff=diff)
    assert all(exact.values()), f"int8 kernel: integer leaves differ {exact}"
    for k, g, x in zip(names[5:], got[5:], want[5:]):
        # residuals: FMA slack of a few ulp of |s_new| <= 4 (see ref.py)
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), atol=2e-6,
                                   err_msg=k)


def one_chip(seed: int) -> None:
    for name, tc, need_kernel in (
            ("a", phase_config("es", 16, 4, seed=seed), True),
            ("b", phase_config("es", 16, 4, seed=seed, quant=True), True),
            ("c", phase_config("baseline", 4, 4, seed=seed), False)):
        run_phase(name, tc, need_kernel=need_kernel)
        gc.collect()                  # free this phase's train state
    check_f32_kernel(seed)
    check_quant_kernel(seed)


def four_chips(seed: int) -> None:
    """Phase a with the store row-sharded over every device, then with it
    replicated: the same selections and counts, the same scores."""
    runs = {}
    for shard in (True, False):
        name = "a-sharded" if shard else "a-replicated"
        tr, seen_steps = run_phase(
            name, phase_config("es", 16, 4, seed=seed, shard=shard),
            need_kernel=True)
        leaf = jax.tree.leaves(tr.state.params)[0]
        shards = [sh.data.shape[0]
                  for sh in tr.state.scores.s.addressable_shards]
        log(phase=name, store_shard_rows=shards,
            params_devices=sorted(d.id for d in leaf.devices()),
            params_sharding=str(leaf.sharding))
        if shard and len(shards) != len(jax.devices()):
            raise AssertionError(f"store not sharded over every device: "
                                 f"{shards}")
        runs[shard] = (
            [m["sel_ids"] for m in tr.metrics_log], seen_steps,
            np.asarray(tr.state.scores.s), np.asarray(tr.state.scores.w))
        del tr
        gc.collect()
    (sel_a, seen_a, s_a, w_a), (sel_b, seen_b, s_b, w_b) = runs[True], \
        runs[False]
    same_sel = sel_a == sel_b
    same_seen = all(np.array_equal(x, y) for x, y in zip(seen_a, seen_b))
    log(compare="sharded vs replicated", selected_ids_equal=same_sel,
        seen_equal_every_step=same_seen,
        s_max_rel_diff=float(np.max(np.abs(s_a - s_b) / np.abs(s_b))),
        w_max_rel_diff=float(np.max(np.abs(w_a - w_b) / np.abs(w_b))))
    assert same_sel, f"selected ids differ: {sel_a} vs {sel_b}"
    assert same_seen and len(seen_a) == len(seen_b) == STEPS
    np.testing.assert_allclose(s_a, s_b, rtol=1e-6, err_msg="s")
    np.testing.assert_allclose(w_a, w_b, rtol=1e-6, err_msg="w")


def main() -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only phase a, score store sharded over 4 chips "
                         "against replicated")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
