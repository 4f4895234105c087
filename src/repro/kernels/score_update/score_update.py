"""Fused ES score/weight scatter-update Pallas kernel (paper Eq. 3.1).

One kernel applies, in place (input/output aliased):

    w[ids] = beta1 * s[ids] + (1-beta1) * losses
    s[ids] = beta2 * s[ids] + (1-beta2) * losses
    seen[ids] += 1

The (n,) store stays in HBM.  The batch's ids and losses are scalar-
prefetched into SMEM, and a ``fori_loop`` walks them in order: for each id
the kernel DMAs the ``TILE``-row tile that holds it into VMEM, rewrites
that one lane under a lane mask, and DMAs the tile back before the next id
is read.  So the work and the VMEM footprint are O(B) tiles, whatever n is,
and a duplicate id sees the earlier occurrence's write.  ``TILE`` is the
HBM tiling of a 1-D array on TPU (1024 rows for f32, i32 and int8), the
smallest slice a DMA of such an array may take; stores whose size is not a
multiple of it are padded around the call.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 1024


def _pad_rows(x: jax.Array, n_pad: int) -> jax.Array:
    return x if x.shape[0] == n_pad else jnp.pad(x, (0, n_pad - x.shape[0]))


def _tile_of(idx):
    return pl.multiple_of((idx // TILE) * TILE, TILE)


def _tile_copies(hbm_refs, bufs, sems, base, *, to_hbm: bool):
    """Start one DMA per leaf between its HBM tile at ``base`` and its VMEM
    buffer, then wait for all of them."""
    copies = []
    for k, (ref, buf) in enumerate(zip(hbm_refs, bufs)):
        tile = ref.at[pl.ds(base, TILE)]
        src, dst = (buf, tile) if to_hbm else (tile, buf)
        copies.append(pltpu.make_async_copy(src, dst, sems.at[k]))
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _score_kernel(ids_ref, losses_ref, lanes_ref, s_in, w_in, seen_in,
                  s_hbm, w_hbm, seen_hbm, s_buf, w_buf, seen_buf, sems, *,
                  beta1: float, beta2: float, n_updates: int, masked: bool):
    del s_in, w_in, seen_in            # aliased with the *_hbm outputs
    hbm = (s_hbm, w_hbm, seen_hbm)
    bufs = (s_buf, w_buf, seen_buf)

    def body(i, carry):
        idx = ids_ref[i]
        loss = losses_ref[i]

        def apply():
            base = _tile_of(idx)
            _tile_copies(hbm, bufs, sems, base, to_hbm=False)
            hit = lanes_ref[...] == idx - base
            s_prev = s_buf[...]
            w_new = beta1 * s_prev + (1.0 - beta1) * loss
            s_new = beta2 * s_prev + (1.0 - beta2) * loss
            w_buf[...] = jnp.where(hit, w_new, w_buf[...])
            s_buf[...] = jnp.where(hit, s_new, s_prev)
            seen_buf[...] = jnp.where(hit, seen_buf[...] + 1, seen_buf[...])
            _tile_copies(hbm, bufs, sems, base, to_hbm=True)

        if masked:
            # per-shard dispatch: ids the shard does not own arrive as -1
            pl.when(idx >= 0)(apply)
        else:
            apply()
        return carry

    jax.lax.fori_loop(0, n_updates, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("beta1", "beta2", "interpret", "masked"))
def fused_score_update(s: jax.Array, w: jax.Array, seen: jax.Array,
                       ids: jax.Array, losses: jax.Array, *,
                       beta1: float, beta2: float,
                       interpret: bool = False, masked: bool = False
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """s/w: (n,) f32; seen: (n,) i32; ids: (B,) i32; losses: (B,) f32.

    ``masked=True`` skips entries whose id is negative — the per-shard
    dispatch (``ops.update_scores_fused`` with a ``ScoreSharding``) marks
    ids owned by other shards that way.
    """
    n = s.shape[0]
    n_pad = pl.cdiv(n, TILE) * TILE
    kernel = functools.partial(_score_kernel, beta1=beta1, beta2=beta2,
                               n_updates=ids.shape[0], masked=masked)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), hbm, hbm, hbm],
        out_specs=[hbm, hbm, hbm],
        scratch_shapes=[pltpu.VMEM((TILE,), jnp.float32),
                        pltpu.VMEM((TILE,), jnp.float32),
                        pltpu.VMEM((TILE,), jnp.int32),
                        pltpu.SemaphoreType.DMA((3,))])
    s_o, w_o, seen_o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.float32),
                   jax.ShapeDtypeStruct((n_pad,), jnp.float32),
                   jax.ShapeDtypeStruct((n_pad,), jnp.int32)],
        # operand indices count the two scalar-prefetch arguments
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(ids.astype(jnp.int32), losses.astype(jnp.float32),
      jnp.arange(TILE, dtype=jnp.int32), _pad_rows(s, n_pad),
      _pad_rows(w, n_pad), _pad_rows(seen, n_pad))
    return s_o[:n], w_o[:n], seen_o[:n]


def _quant_score_kernel(ids_ref, gids_ref, losses_ref, slots_ref, seqs_ref,
                        ssc_ref, wsc_ref, lanes_ref, ring_lanes_ref,
                        s_in, w_in, seen_in, er_ref, et_ref, es_ref, ew_ref,
                        s_hbm, w_hbm, seen_hbm, er_out, et_out, es_out,
                        ew_out, s_buf, w_buf, seen_buf, sems, *,
                        beta1: float, beta2: float, n_updates: int):
    """Int8 scatter with in-kernel dequant -> Eq. (3.1) -> requant and
    residual-ring write-back.  Scales are FIXED here (the scale-growth
    prologue runs in XLA before the call, and each entry's two scales
    arrive in SMEM); negative ids are skipped (the per-shard masked
    dispatch).  Sequential like the f32 kernel: a duplicate id sees the
    earlier occurrence's code AND ring entry.  The (R,) ring sits whole in
    VMEM; the int8 rows move tile by tile between HBM and VMEM."""
    del s_in, w_in, seen_in            # aliased with the *_hbm outputs
    er_out[...] = er_ref[...]
    et_out[...] = et_ref[...]
    es_out[...] = es_ref[...]
    ew_out[...] = ew_ref[...]
    hbm = (s_hbm, w_hbm, seen_hbm)
    bufs = (s_buf, w_buf, seen_buf)

    def body(i, carry):
        idx = ids_ref[i]

        def apply():
            gid = gids_ref[i]
            loss = losses_ref[i]
            ssc = ssc_ref[i]
            wsc = wsc_ref[i]
            base = _tile_of(idx)
            _tile_copies(hbm, bufs, sems, base, to_hbm=False)
            hit = lanes_ref[...] == idx - base
            # newest matching residual: live stamps are unique, so the
            # masked sum picks exactly the entry core.scores._q_gather_1d
            # takes by argmax (expression order below mirrors it for
            # bit-parity with the XLA oracle)
            stamped = jnp.where(er_out[...] == gid, et_out[...], 0)
            newest = jnp.max(stamped, keepdims=True)
            resid = jnp.sum(jnp.where((stamped == newest) & (newest > 0),
                                      es_out[...], 0.0), keepdims=True)
            s_q = s_buf[...]
            w_q = w_buf[...]
            seen_q = seen_buf[...]
            s_prev = s_q.astype(jnp.float32) * ssc + resid
            w_new = beta1 * s_prev + (1.0 - beta1) * loss
            s_new = beta2 * s_prev + (1.0 - beta2) * loss
            q_s = jnp.clip(jnp.round(s_new / ssc), -127.0, 127.0)
            q_w = jnp.clip(jnp.round(w_new / wsc), -127.0, 127.0)
            s_buf[...] = jnp.where(hit, q_s.astype(jnp.int8), s_q)
            w_buf[...] = jnp.where(hit, q_w.astype(jnp.int8), w_q)
            seen_buf[...] = jnp.where(
                hit, jnp.minimum(seen_q.astype(jnp.int32) + 1,
                                 127).astype(jnp.int8), seen_q)
            _tile_copies(hbm, bufs, sems, base, to_hbm=True)
            e_s = jnp.sum(jnp.where(hit, s_new - q_s * ssc, 0.0),
                          keepdims=True)
            e_w = jnp.sum(jnp.where(hit, w_new - q_w * wsc, 0.0),
                          keepdims=True)
            # a slot >= R matches no lane: the residual is dropped
            at = ring_lanes_ref[...] == slots_ref[i]
            er_out[...] = jnp.where(at, gid, er_out[...])
            et_out[...] = jnp.where(at, seqs_ref[i], et_out[...])
            es_out[...] = jnp.where(at, e_s, es_out[...])
            ew_out[...] = jnp.where(at, e_w, ew_out[...])

        pl.when(idx >= 0)(apply)
        return carry

    jax.lax.fori_loop(0, n_updates, body, 0)


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "block",
                                             "interpret"))
def fused_quant_score_update(s_q: jax.Array, w_q: jax.Array,
                             seen_q: jax.Array, s_scale: jax.Array,
                             w_scale: jax.Array, err_rows: jax.Array,
                             err_seq: jax.Array, err_s: jax.Array,
                             err_w: jax.Array, ids: jax.Array,
                             gids: jax.Array, losses: jax.Array,
                             slots: jax.Array, seqs: jax.Array, *,
                             beta1: float, beta2: float, block: int,
                             interpret: bool = False):
    """Quantized fused score update (rows in HBM, ring in VMEM).

    s_q/w_q/seen_q: (n,) int8 codes; s_scale/w_scale: (nb,) f32 per-block
    scales (FIXED — callers run the grow/recode prologue first);
    err_*: the (R,) residual ring; ids: (B,) LOCAL rows (-1 = dropped,
    the shared masking rule); gids: (B,) global row ids recorded in the
    ring; slots/seqs: precomputed ring slot assignment + recency stamps
    (``core.scores._q_ring_slots``; slot >= R drops the residual).

    Returns the 7 mutated leaves (codes, seen, ring) — scales pass
    through untouched.  Matches ``ref.quant_score_update_ref`` on
    unique-id batches: integer leaves bitwise, residuals to FMA slack
    (see ref.py for the exact contract and duplicate/eviction caveats).
    """
    n = s_q.shape[0]
    n_pad = pl.cdiv(n, TILE) * TILE
    R = err_rows.shape[0]
    blk = jnp.maximum(ids, 0) // block
    kernel = functools.partial(_quant_score_kernel, beta1=beta1,
                               beta2=beta2, n_updates=ids.shape[0])
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(),
        in_specs=[vmem, vmem, hbm, hbm, hbm, vmem, vmem, vmem, vmem],
        out_specs=[hbm, hbm, hbm, vmem, vmem, vmem, vmem],
        scratch_shapes=[pltpu.VMEM((TILE,), jnp.int8),
                        pltpu.VMEM((TILE,), jnp.int8),
                        pltpu.VMEM((TILE,), jnp.int8),
                        pltpu.SemaphoreType.DMA((3,))])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.int8),
                   jax.ShapeDtypeStruct((n_pad,), jnp.int8),
                   jax.ShapeDtypeStruct((n_pad,), jnp.int8),
                   jax.ShapeDtypeStruct((R,), jnp.int32),
                   jax.ShapeDtypeStruct((R,), jnp.int32),
                   jax.ShapeDtypeStruct((R,), jnp.float32),
                   jax.ShapeDtypeStruct((R,), jnp.float32)],
        # operand indices count the seven scalar-prefetch arguments
        input_output_aliases={9: 0, 10: 1, 11: 2, 12: 3, 13: 4, 14: 5,
                              15: 6},
        interpret=interpret,
    )(ids.astype(jnp.int32), gids.astype(jnp.int32),
      losses.astype(jnp.float32), slots.astype(jnp.int32),
      seqs.astype(jnp.int32), s_scale[blk], w_scale[blk],
      jnp.arange(TILE, dtype=jnp.int32), jnp.arange(R, dtype=jnp.int32),
      _pad_rows(s_q, n_pad), _pad_rows(w_q, n_pad),
      _pad_rows(seen_q, n_pad), err_rows, err_seq, err_s, err_w)
    s_o, w_o, seen_o = (x[:n] for x in out[:3])
    return (s_o, w_o, seen_o) + tuple(out[3:])
