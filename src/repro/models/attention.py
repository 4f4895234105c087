"""GQA attention: training/prefill, cross attention and decode.

Layouts
  q:        (B, S, H, hd)   grouped internally to (B, S, K, G, hd), G = H/K
  k, v:     (B, S, K, hd)
  kv cache: (B, S_max, K, hd) per layer (stacked over layers by the caller)

``mha`` has two paths, chosen from its input and the run's devices
(``use_flash``):

* the Pallas flash kernel (``kernels/flash_attn``), forward and backward,
  on a run of one TPU chip for unpacked rows (no ``segment_ids`` or
  ``positions``) without a mesh, when S and the heads fit its blocks: it
  reads q/k/v in this layout, scores stay in VMEM and only the per-row
  log-sum-exp is saved for the backward;
* otherwise an XLA path that scans over query chunks of ``chunk_q``,
  building the (B, K, G, qc, S) f32 scores of one chunk at a time (the
  whole S x S block when ``chunk_q`` is 0 or does not divide S).  It serves
  the CPU, packed rows, meshes, runs of several chips (a Mosaic kernel in a
  jit over several devices must sit in a ``shard_map``, which ``mha`` does
  not build) and odd shapes, and is the kernel's reference in the tests.
  ``prefill_attn``, ``decode_attn`` and ``cross_attn`` always take XLA.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.flash_attn.flash_attn import block_sizes as flash_blocks
from ..kernels.flash_attn.ops import gqa_flash_attention
from .layers import Params, Axes, ShardCtx, winit, zeros, rope_angles, apply_rope

NEG_INF = -1e30


def init_attn(key: jax.Array, d: int, n_heads: int, n_kv: int, head_dim: int,
              qkv_bias: bool = False, stacked: Tuple[int, ...] = ()) -> Tuple[Params, Axes]:
    lead = tuple(stacked)
    lead_ax = tuple("layers" for _ in stacked)
    kq, kk, kv, ko = jax.random.split(key, 4)
    qdim, kvdim = n_heads * head_dim, n_kv * head_dim
    params: Params = {
        "wq": winit(kq, lead + (d, qdim)),
        "wk": winit(kk, lead + (d, kvdim)),
        "wv": winit(kv, lead + (d, kvdim)),
        "wo": winit(ko, lead + (qdim, d)),
    }
    axes: Axes = {
        "wq": lead_ax + ("embed", "heads"),
        "wk": lead_ax + ("embed", "kv_heads"),
        "wv": lead_ax + ("embed", "kv_heads"),
        "wo": lead_ax + ("heads", "embed"),
    }
    if qkv_bias:
        params.update({"bq": zeros(lead + (qdim,)), "bk": zeros(lead + (kvdim,)),
                       "bv": zeros(lead + (kvdim,))})
        axes.update({"bq": lead_ax + ("heads",), "bk": lead_ax + ("kv_heads",),
                     "bv": lead_ax + ("kv_heads",)})
    return params, axes


def _project_qkv(params: Params, x: jax.Array, xkv: jax.Array,
                 n_heads: int, n_kv: int, head_dim: int,
                 ctx: ShardCtx) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, Sq, d) queries source; xkv: (B, Sk, d) key/value source."""
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", xkv, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", xkv, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    B, Sq, _ = x.shape
    Sk = xkv.shape[1]
    q = q.reshape(B, Sq, n_heads, head_dim)
    k = k.reshape(B, Sk, n_kv, head_dim)
    v = v.reshape(B, Sk, n_kv, head_dim)
    q = ctx.constrain(q, "batch", None, "heads", None)
    k = ctx.constrain(k, "batch", None, "kv_heads", None)
    v = ctx.constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def _grouped_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: Optional[jax.Array]) -> jax.Array:
    """Exact attention on one query block.

    q: (B, Sq, K, G, hd), k/v: (B, Sk, K, hd), mask: (Sq, Sk) or (B, Sq, Sk)
    additive (0 / NEG_INF). Returns (B, Sq, K, G, hd).
    """
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if mask is not None:
        if mask.ndim == 2:
            scores = scores + mask[None, None, None, :, :]
        else:
            scores = scores + mask[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """Additive causal mask from absolute positions. (Sq,), (Sk,) -> (Sq, Sk)."""
    ok = q_pos[:, None] >= k_pos[None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def segment_causal_mask(q_pos: jax.Array, k_pos: jax.Array,
                        q_seg: jax.Array, k_seg: jax.Array) -> jax.Array:
    """Segment-isolated causal mask for packed rows.

    q_pos/q_seg: (B, Sq), k_pos/k_seg: (B, Sk) -> (B, Sq, Sk) additive.
    A query attends to a key iff both live in the same non-padding segment
    (segment id 0 = padding) and the key is causally prior *within* the
    segment — documents packed into one row never see each other.  Padding
    queries have every key masked; softmax degrades to uniform there, which
    is harmless because their labels are -1 and their hidden states feed
    nothing that is not itself masked.
    """
    ok = ((q_pos[:, :, None] >= k_pos[:, None, :])
          & (q_seg[:, :, None] == k_seg[:, None, :])
          & (q_seg[:, :, None] > 0))
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def use_flash(backend: str, n_devices: int, seq_len: int, n_heads: int,
              head_dim: int, ctx: ShardCtx,
              positions: Optional[jax.Array] = None,
              segment_ids: Optional[jax.Array] = None) -> bool:
    """Whether ``mha`` takes the flash kernel: on a TPU run of one device
    with no mesh (a jit over several devices, as a run with a row-sharded
    score store or several hosts compiles, cannot partition the kernel),
    for rows that are not packed (no ``segment_ids``, positions 0..S-1),
    where the kernel has blocks (S a multiple of 128, an even number of
    heads of 64 or 128)."""
    return (backend == "tpu" and n_devices == 1 and ctx.mesh is None
            and segment_ids is None and positions is None
            and flash_blocks(seq_len, n_heads, head_dim) is not None)


def mha(params: Params, x: jax.Array, *, n_heads: int, n_kv: int,
        head_dim: int, rope_theta: float, ctx: ShardCtx,
        chunk_q: int = 0, causal: bool = True,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Full self-attention over x: (B, S, d) -> (B, S, d).

    ``segment_ids`` (B, S) switches on packed-row masking: attention is
    causal *within* each segment and zero across segments/padding;
    ``positions`` must then be the per-segment (B, S) local positions so
    RoPE restarts per document.  ``chunk_q`` sets the XLA path's query
    chunk; the flash kernel chooses its own blocks.
    """
    B, S, _ = x.shape
    flash = use_flash(jax.default_backend(), jax.device_count(), S, n_heads,
                      head_dim, ctx, positions, segment_ids)
    if positions is None:
        positions = jnp.arange(S)
    if segment_ids is not None:
        assert positions.ndim == 2, \
            "segment_ids needs per-row (B, S) positions"
    q, k, v = _project_qkv(params, x, x, n_heads, n_kv, head_dim, ctx)
    cos, sin = rope_angles(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if flash:
        out = gqa_flash_attention(q, k, v, causal=causal)
    else:
        out = _chunked_attn(q, k, v, positions, segment_ids, chunk_q, causal)

    out = ctx.constrain(out, "batch", None, "heads", None)
    out = out.reshape(B, S, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(x.dtype))


def _chunked_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array, segment_ids: Optional[jax.Array],
                  chunk_q: int, causal: bool) -> jax.Array:
    """The XLA path of ``mha``: q (B, S, H, hd), k/v (B, S, K, hd) ->
    (B, S, H, hd), one query chunk of ``chunk_q`` at a time."""
    B, S, n_heads, head_dim = q.shape
    n_kv = k.shape[2]
    G = n_heads // n_kv
    q = q.reshape(B, S, n_kv, G, head_dim)

    if chunk_q and S > chunk_q and S % chunk_q == 0:
        n_chunks = S // chunk_q
        qc = q.reshape(B, n_chunks, chunk_q, n_kv, G, head_dim)
        qc = jnp.moveaxis(qc, 1, 0)  # (n_chunks, B, qc, K, G, hd)
        if positions.ndim == 2:
            pos_c = jnp.moveaxis(
                positions.reshape(B, n_chunks, chunk_q), 1, 0)
        else:
            pos_c = positions.reshape(n_chunks, chunk_q)
        chunked = (qc, pos_c)
        if segment_ids is not None:
            chunked += (jnp.moveaxis(
                segment_ids.reshape(B, n_chunks, chunk_q), 1, 0),)

        def body(_, inputs):
            if segment_ids is not None:
                q_blk, qp, qs = inputs
                m = segment_causal_mask(qp, positions, qs, segment_ids)
            else:
                q_blk, qp = inputs
                m = causal_mask(qp, positions) if causal else None
            return None, _grouped_attn(q_blk, k, v, m)

        _, out = jax.lax.scan(body, None, chunked)
        return jnp.moveaxis(out, 0, 1).reshape(B, S, n_heads, head_dim)
    if segment_ids is not None:
        m: Optional[jax.Array] = segment_causal_mask(
            positions, positions, segment_ids, segment_ids)
    else:
        m = causal_mask(positions, positions) if causal else None
    return _grouped_attn(q, k, v, m).reshape(B, S, n_heads, head_dim)


def cross_attn(params: Params, x: jax.Array, memory: jax.Array, *,
               n_heads: int, n_kv: int, head_dim: int, ctx: ShardCtx) -> jax.Array:
    """Cross attention: queries from x (B, Sq, d), kv from memory (B, Sk, d)."""
    B, Sq, _ = x.shape
    q, k, v = _project_qkv(params, x, memory, n_heads, n_kv, head_dim, ctx)
    G = n_heads // n_kv
    q = q.reshape(B, Sq, n_kv, G, head_dim)
    out = _grouped_attn(q, k, v, None).reshape(B, Sq, n_heads, head_dim)
    out = out.reshape(B, Sq, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# KV-cache paths (prefill / decode)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, stacked: Tuple[int, ...] = ()) -> Dict[str, jax.Array]:
    shape = tuple(stacked) + (batch, max_len, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_axes(stacked: Tuple[int, ...] = (), seq_axis: Optional[str] = "cache_seq") -> Dict[str, Any]:
    lead = tuple("layers" for _ in stacked)
    ax = lead + ("batch", seq_axis, "kv_heads", None)
    return {"k": ax, "v": ax}


def prefill_attn(params: Params, x: jax.Array, cache: Dict[str, jax.Array], *,
                 n_heads: int, n_kv: int, head_dim: int, rope_theta: float,
                 ctx: ShardCtx, chunk_q: int = 0
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal self-attn over prompt, writing K/V into cache[:, :S]."""
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _project_qkv(params, x, x, n_heads, n_kv, head_dim, ctx)
    cos, sin = rope_angles(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    new_cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }
    G = n_heads // n_kv
    qg = q.reshape(B, S, n_kv, G, head_dim)
    if chunk_q and S > chunk_q and S % chunk_q == 0:
        n_chunks = S // chunk_q
        qc = jnp.moveaxis(qg.reshape(B, n_chunks, chunk_q, n_kv, G, head_dim), 1, 0)
        pos_c = positions.reshape(n_chunks, chunk_q)

        def body(_, inputs):
            q_blk, qp = inputs
            return None, _grouped_attn(q_blk, k, v, causal_mask(qp, positions))

        _, out = jax.lax.scan(body, None, (qc, pos_c))
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, n_heads, head_dim)
    else:
        out = _grouped_attn(qg, k, v, causal_mask(positions, positions))
        out = out.reshape(B, S, n_heads, head_dim)
    out = out.reshape(B, S, n_heads * head_dim)
    y = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


def decode_attn(params: Params, x: jax.Array, cache: Dict[str, jax.Array],
                pos: jax.Array, *, n_heads: int, n_kv: int, head_dim: int,
                rope_theta: float, ctx: ShardCtx
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, d); pos: scalar int (current position)."""
    B, _, _ = x.shape
    S_max = cache["k"].shape[1]
    q, k, v = _project_qkv(params, x, x, n_heads, n_kv, head_dim, ctx)
    pos_arr = jnp.asarray(pos)[None]
    cos, sin = rope_angles(pos_arr, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    new_cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0)),
    }
    kc, vc = new_cache["k"].astype(x.dtype), new_cache["v"].astype(x.dtype)
    G = n_heads // n_kv
    qg = q.reshape(B, 1, n_kv, G, head_dim)
    # mask out cache positions beyond `pos`
    valid = jnp.arange(S_max) <= pos
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[None, :]  # (1, S_max)
    out = _grouped_attn(qg, kc, vc, mask).reshape(B, 1, n_heads * head_dim)
    y = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache
