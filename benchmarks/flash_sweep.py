"""Block-size sweep of the flash attention kernel on one TPU chip.

    PYTHONPATH=src python benchmarks/flash_sweep.py --out <file.jsonl>

Times each leg of ``kernels/flash_attn`` (forward without and with the
log-sum-exp, dQ, dK/dV) for each (block, block_h) at shapes of the
benchmark's cells (S = 1024, 16 heads: B = 16 at head 64 as in
qwen1.5-0.5b's scoring forward, B = 4 at head 128 as in olmo-1b's
training), and beside them the kernel at its own blocks and
``models/attention.py``'s q-chunked XLA path (chunk 512), forward and
forward+backward.  One JSON line per measurement: ms per
call (host clock around ``block_until_ready`` of REPS calls, the median of
3), and the share of the chip's bf16 peak that the leg's own matmuls reach
on the causal half of the scores.  Needs a TPU; exits 2 without one.
"""
from __future__ import annotations

import argparse
import functools
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn import flash_attn as fa
from repro.kernels.flash_attn.ops import gqa_flash_attention
from repro.models.attention import _chunked_attn

PEAK = 197e12                 # TPU v5e bf16 FLOP/s (Google Cloud, "TPU v5e")
REPS = 10
SHAPES = [(16, 1024, 16, 64), (4, 1024, 16, 128)]      # (B, S, H, hd)
# matmuls of size S*S*hd each leg runs (causal half): fwd QK, PV; dkv
# K Q^T, P^T dO, V dO^T, dS^T Q; dq Q K^T, dO V^T, dS K
MATMULS = {"fwd": 2, "fwd_lse": 2, "dkv": 4, "dq": 3}


def _time(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t) / REPS * 1e3)
    return sorted(runs)[1]


def _legs(q, k, v, do, block, block_h):
    plan = fa._Plan(q, True, block, block_h)
    o, lse = fa._fwd(q, k, v, plan, True, False)
    _, di = fa._dq(q, k, v, do, o, lse, plan, False)
    return {
        "fwd": (jax.jit(lambda q, k, v: fa._fwd(q, k, v, plan, False, False)),
                (q, k, v)),
        "fwd_lse": (jax.jit(lambda q, k, v: fa._fwd(q, k, v, plan, True,
                                                    False)), (q, k, v)),
        "dkv": (jax.jit(lambda *a: fa._dkv(*a, plan, False)),
                (q, k, v, do, lse, di)),
        "dq": (jax.jit(lambda *a: fa._dq(*a, plan, False)),
               (q, k, v, do, o, lse)),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    out = open(args.out, "w")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    for B, S, H, hd in SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (jax.random.normal(kk, (B, S, H, hd), jnp.bfloat16)
                       for kk in ks)
        flop = B * H * S * S * hd      # one S*S*hd matmul on the causal half
        shape = [B, S, H, hd]
        xla = functools.partial(_chunked_attn, positions=jnp.arange(S),
                                segment_ids=None, chunk_q=512, causal=True)
        for path, fn in (("xla_chunk512", xla),
                         ("flash_default", gqa_flash_attention)):
            grad = jax.jit(lambda q, k, v, do, fn=fn: jax.vjp(
                fn, q, k, v)[1](do))
            emit({"shape": shape, "path": path, "leg": "fwd",
                  "ms": _time(jax.jit(fn), q, k, v)})
            emit({"shape": shape, "path": path, "leg": "fwd+bwd",
                  "ms": _time(grad, q, k, v, do)})
        per_slab = fa.LANES // hd               # heads in one 128-lane slab
        for b, bh in itertools.product((256, 512, 1024),
                                       (per_slab, 2 * per_slab)):
            rec = {"shape": shape, "path": "flash", "blocks": [b, bh]}
            try:
                legs = _legs(q, k, v, do, b, bh)
                for name, (fn, a) in legs.items():
                    ms = _time(fn, *a)
                    rec[name] = ms
                    rec[name + "_peak_pct"] = (100 * MATMULS[name] * flop
                                               / (ms * 1e-3) / PEAK)
            except Exception as e:          # a block the compiler refuses
                rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            emit(rec)
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
