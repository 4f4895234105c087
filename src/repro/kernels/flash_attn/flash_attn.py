"""Blocked flash attention, forward and backward, as Pallas TPU kernels.

q/k/v tiles stream HBM->VMEM; each score tile lives only in VMEM and the
softmax is online (running max and sum in f32 scratch), so the O(S^2)
scores never reach HBM.  The forward saves only the per-row log-sum-exp;
the backward recomputes each probability tile from it.

Layout: the model's own, q/k/v (B, S, H, hd), read as (B, S, H*hd), so no
transpose runs before or after the kernels.  The kernel takes heads of 64
or 128 lanes, an even number of them: a grid step takes two heads
(``block_sizes``), a lane-dense slice of H*hd.  Two heads of 64 share a
128-lane slab; each head's products then run on the whole slab with the
other head's lanes of one operand zeroed, which costs the MXU no more
passes than a 64-wide head would, and its results land in its own lanes.

Three ``pallas_call``s, named for the device trace:

* ``flash_attn_fwd``: grid (batch x head steps, q tiles, kv tiles); o and,
  when the output is differentiated, the log-sum-exp of each head's rows as
  a row, (B, H/block_h, block_h, S).
* ``flash_attn_dq``: grid (batch x head steps, q tiles, kv tiles); dQ, and
  first di = rowsum(dO * O) of each q tile, written out like the lse.
* ``flash_attn_dkv``: grid (batch x head steps, kv tiles, q tiles); works
  on transposed tiles (keys on sublanes, queries on lanes) so the lse and
  di broadcast as rows and dK, dV need no transpose.

Causal: q and kv tiles are square, so a tile is above, on or below the
diagonal.  Tiles above are skipped with ``pl.when`` and their block index
is clamped to a tile already in VMEM, so they cost no DMA.  A tile on the
diagonal is worked in strips of ``STRIP`` rows, each only as far as the
diagonal, and masked element by element; tiles below are plain.  So the
work is the causal half to within one strip, whatever the tile size.

MXU operands keep the input dtype (bf16 in the models) with f32
accumulation; the softmax statistics, the log-sum-exp and the accumulators
are f32, and probabilities are cast to v's dtype before the PV product.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128                              # a slab: one head of 128, two of 64
STRIP = 128                              # rows of a diagonal tile per pass
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


def block_sizes(seq_len: int, n_heads: int,
                head_dim: int) -> Optional[Tuple[int, int]]:
    """(block, block_h): the square sequence tile (the largest of 128 to
    1024 that divides S) and the heads per grid step (two: at head 64 one
    128-lane slab, at head 128 two), or None where the kernel does not
    apply: S not a multiple of 128, a head other than 64 or 128, or an odd
    number of heads."""
    if seq_len % 128 or head_dim not in (64, 128) or n_heads % 2:
        return None
    block = max(b for b in (128, 256, 512, 1024) if seq_len % b == 0)
    return block, 2


def _mask(q0, k0, shape, transposed: bool) -> jax.Array:
    """Causal keep-mask of a tile whose first query and key positions are
    q0 and k0: query position >= key position."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if transposed:                      # keys on rows, queries on columns
        return cols + q0 >= rows + k0
    return rows + q0 >= cols + k0


def _tiles(causal: bool, block: int, q0, k0, update, kv_rows: bool = False):
    """Drive ``update(r0, nr, c0, nc, masked)`` over one tile: rows r0 to
    r0+nr of the tile's own side (q rows, or kv rows in the transposed
    dK/dV tile) against columns c0 to c0+nc of the other side."""
    if not causal:
        update(0, block, 0, block, False)
        return

    @pl.when(q0 > k0)
    def _below():
        update(0, block, 0, block, False)

    @pl.when(q0 == k0)
    def _diagonal():
        strip = min(STRIP, block)
        for r in range(block // strip):
            if kv_rows:     # keys of strip r are seen by queries from r*strip
                update(r * strip, strip, r * strip, block - r * strip, True)
            else:           # queries of strip r see keys up to (r+1)*strip
                update(r * strip, strip, 0, (r + 1) * strip, True)


def _heads(block_h: int, head_dim: int):
    """(head, its slab's lane slice, a keep-mask of its lanes in the slab or
    None when the head fills the slab) for each head of a grid step."""
    per_slab = LANES // head_dim
    out = []
    for t in range(block_h):
        slab = pl.ds((t // per_slab) * LANES, LANES)
        hm = None
        if per_slab > 1:
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
            hm = lane // head_dim == t % per_slab
        out.append((t, slab, hm))
    return out


def _only(mask, x):
    """x on the lanes of ``mask``, zero elsewhere (x itself for no mask)."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _row_to_col(row: jax.Array) -> jax.Array:
    """(1, n) -> (n, 1) through a full-tile transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _col_to_row(col: jax.Array) -> jax.Array:
    """(n, 1) -> (1, n) through a full-tile transpose."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                causal: bool, block: int, n_q: int, block_h: int,
                head_dim: int, save_lse: bool):
    lse_ref = rest[0] if save_lse else None
    m_scr, l_scr, acc_scr = rest[-3:]
    i, j = pl.program_id(1), pl.program_id(2)
    q0, k0 = i * block, j * block
    heads = _heads(block_h, head_dim)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def update(r0, nr, c0, nc, masked):
        rows, cols = pl.ds(r0, nr), pl.ds(c0, nc)
        for t, slab, hm in heads:
            v = _only(hm, v_ref[0, cols, slab])
            s = jax.lax.dot_general(q_ref[0, rows, slab],
                                    _only(hm, k_ref[0, cols, slab]), _NT,
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if masked:
                s = jnp.where(_mask(q0 + r0, k0 + c0, s.shape, False), s,
                              NEG_INF)
            m_prev = m_scr[t, rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[t, rows, :] = (corr * l_scr[t, rows, :]
                                 + jnp.sum(p, axis=1, keepdims=True))
            acc = acc_scr[rows, slab]
            acc = acc * corr if hm is None else jnp.where(hm, acc * corr, acc)
            acc_scr[rows, slab] = acc + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[t, rows, :] = m_new

    _tiles(causal, block, q0, k0, update)

    @pl.when(j == n_q - 1)
    def _finish():
        for t, slab, hm in heads:
            l = l_scr[t]
            o = acc_scr[:, slab] / l
            if hm is not None:          # the slab's other heads keep theirs
                o = jnp.where(hm, o, acc_scr[:, slab])
            acc_scr[:, slab] = o
            if save_lse:
                lse_ref[0, 0, pl.ds(t, 1), :] = _col_to_row(
                    m_scr[t] + jnp.log(l))
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, di_ref,
               dq_scr, lse_scr, di_scr, *, scale: float, causal: bool,
               block: int, n_q: int, block_h: int, head_dim: int):
    i, j = pl.program_id(1), pl.program_id(2)
    q0, k0 = i * block, j * block
    heads = _heads(block_h, head_dim)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        for t, slab, hm in heads:
            lse_scr[t] = _row_to_col(lse_ref[0, 0, pl.ds(t, 1), :])
            prod = (do_ref[0, :, slab].astype(jnp.float32)
                    * o_ref[0, :, slab].astype(jnp.float32))
            di = jnp.sum(_only(hm, prod), axis=1, keepdims=True)
            di_scr[t] = di
            di_ref[0, 0, pl.ds(t, 1), :] = _col_to_row(di)

    def update(r0, nr, c0, nc, masked):
        rows, cols = pl.ds(r0, nr), pl.ds(c0, nc)
        for t, slab, hm in heads:
            k = _only(hm, k_ref[0, cols, slab])
            s = jax.lax.dot_general(q_ref[0, rows, slab], k, _NT,
                                    preferred_element_type=jnp.float32)
            s = s * scale                                   # (rows, cols)
            if masked:
                s = jnp.where(_mask(q0 + r0, k0 + c0, s.shape, False), s,
                              NEG_INF)
            p = jnp.exp(s - lse_scr[t, rows, :])
            dp = jax.lax.dot_general(do_ref[0, rows, slab],
                                     _only(hm, v_ref[0, cols, slab]), _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - di_scr[t, rows, :])
            dq_scr[rows, slab] += jnp.dot(ds.astype(k.dtype), k,
                                          preferred_element_type=jnp.float32)

    _tiles(causal, block, q0, k0, update)

    @pl.when(j == n_q - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale: float, causal: bool, block: int,
                n_q: int, block_h: int, head_dim: int):
    j, i = pl.program_id(1), pl.program_id(2)
    q0, k0 = i * block, j * block
    heads = _heads(block_h, head_dim)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def update(r0, nr, c0, nc, masked):
        keys, qs = pl.ds(r0, nr), pl.ds(c0, nc)
        for t, slab, hm in heads:
            q = _only(hm, q_ref[0, qs, slab])
            do = _only(hm, do_ref[0, qs, slab])
            s_t = jax.lax.dot_general(k_ref[0, keys, slab], q, _NT,
                                      preferred_element_type=jnp.float32)
            s_t = s_t * scale                               # (keys, qs)
            if masked:
                s_t = jnp.where(_mask(q0 + c0, k0 + r0, s_t.shape, True),
                                s_t, NEG_INF)
            p_t = jnp.exp(s_t - lse_ref[0, 0, pl.ds(t, 1), qs])
            dv_scr[keys, slab] += jnp.dot(p_t.astype(do.dtype), do,
                                          preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(v_ref[0, keys, slab], do, _NT,
                                       preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - di_ref[0, 0, pl.ds(t, 1), qs])
            dk_scr[keys, slab] += jnp.dot(ds_t.astype(q.dtype), q,
                                          preferred_element_type=jnp.float32)

    _tiles(causal, block, q0, k0, update, kv_rows=True)

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_calls
# ---------------------------------------------------------------------------

class _Plan:
    """Shapes, blocks, the lane layout and the block index maps.  ``block``
    and ``block_h`` default to ``block_sizes``; the block-size sweep sets
    them."""

    def __init__(self, q, causal: bool, block: Optional[int] = None,
                 block_h: Optional[int] = None):
        B, S, H, hd = q.shape
        blocks = block_sizes(S, H, hd)
        if blocks is None:
            raise ValueError(f"flash attention takes S a multiple of 128 and "
                             f"an even number of heads of 64 or 128, not "
                             f"(B, S, H, hd) = {q.shape}")
        self.b, self.bh = block or blocks[0], block_h or blocks[1]
        assert self.b % 128 == 0 and S % self.b == 0, (q.shape, self.b)
        assert H % self.bh == 0 and (self.bh * hd) % LANES == 0, \
            (q.shape, self.bh)
        self.B, self.S, self.H, self.hd, self.causal = B, S, H, hd, causal
        self.n_q = S // self.b
        self.n_h = H // self.bh                 # head steps per row
        self.width = self.bh * hd               # lanes per grid step
        self.scale = 1.0 / (hd ** 0.5)

    def kernel(self, fn, **kw):
        return functools.partial(
            fn, scale=self.scale, causal=self.causal, block=self.b,
            n_q=self.n_q, block_h=self.bh, head_dim=self.hd, **kw)

    def spec(self, tile):
        """A (1, block, width) block of (B, S, H*hd) whose sequence tile at
        grid step (n, a, c) is ``tile(a, c)``."""
        return pl.BlockSpec((1, self.b, self.width),
                            lambda n, a, c: (n // self.n_h, tile(a, c),
                                             n % self.n_h))

    def row_spec(self, tile):
        """A (1, 1, block_h, block) block of the (B, H/bh, bh, S) rows."""
        return pl.BlockSpec((1, 1, self.bh, self.b),
                            lambda n, a, c: (n // self.n_h, n % self.n_h, 0,
                                             tile(a, c)))

    def flat(self, dtype):
        return jax.ShapeDtypeStruct((self.B, self.S, self.H * self.hd), dtype)

    def rows(self):
        return jax.ShapeDtypeStruct((self.B, self.n_h, self.bh, self.S),
                                    jnp.float32)

    def seen_k(self, i, j):
        """kv tile ``j``, or the last that q tile ``i`` sees."""
        return jnp.minimum(j, i) if self.causal else j

    def seen_q(self, j, i):
        """q tile ``i``, or the first that sees kv tile ``j``."""
        return jnp.maximum(i, j) if self.causal else i

    def call(self, kernel, name, in_specs, out_specs, out_shape, scratch,
             interpret, *args):
        model = (self.B, self.S, self.H, self.hd)
        flat = [a.reshape(self.B, self.S, -1) if a.shape == model else a
                for a in args]
        return pl.pallas_call(
            kernel, name=name, grid=(self.B * self.n_h, self.n_q, self.n_q),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*flat)


def _fwd(q, k, v, plan: _Plan, save_lse: bool, interpret: bool):
    own = plan.spec(lambda i, j: i)
    seen = plan.spec(plan.seen_k)
    out_specs = [own] + ([plan.row_spec(lambda i, j: i)] if save_lse else [])
    out_shape = [plan.flat(q.dtype)] + ([plan.rows()] if save_lse else [])
    out = plan.call(plan.kernel(_fwd_kernel, save_lse=save_lse),
                    "flash_attn_fwd", [own, seen, seen], out_specs, out_shape,
                    [pltpu.VMEM((plan.bh, plan.b, 1), jnp.float32),
                     pltpu.VMEM((plan.bh, plan.b, 1), jnp.float32),
                     pltpu.VMEM((plan.b, plan.width), jnp.float32)],
                    interpret, q, k, v)
    o = out[0].reshape(q.shape)
    return (o, out[1]) if save_lse else o


def _dq(q, k, v, do, o, lse, plan: _Plan, interpret: bool):
    """dq, and di = rowsum(dO * O) as rows like the lse, for ``_dkv``."""
    own = plan.spec(lambda i, j: i)
    seen = plan.spec(plan.seen_k)
    rows = plan.row_spec(lambda i, j: i)
    dq, di = plan.call(plan.kernel(_dq_kernel), "flash_attn_dq",
                       [own, seen, seen, own, own, rows], [own, rows],
                       [plan.flat(q.dtype), plan.rows()],
                       [pltpu.VMEM((plan.b, plan.width), jnp.float32),
                        pltpu.VMEM((plan.bh, plan.b, 1), jnp.float32),
                        pltpu.VMEM((plan.bh, plan.b, 1), jnp.float32)],
                       interpret, q, k, v, do, o, lse)
    return dq.reshape(q.shape), di


def _dkv(q, k, v, do, lse, di, plan: _Plan, interpret: bool):
    own = plan.spec(lambda j, i: j)
    seen = plan.spec(plan.seen_q)
    rows = plan.row_spec(plan.seen_q)
    dk, dv = plan.call(plan.kernel(_dkv_kernel), "flash_attn_dkv",
                       [seen, own, own, seen, rows, rows], [own, own],
                       [plan.flat(k.dtype), plan.flat(v.dtype)],
                       [pltpu.VMEM((plan.b, plan.width), jnp.float32),
                        pltpu.VMEM((plan.b, plan.width), jnp.float32)],
                       interpret, q, k, v, do, lse, di)
    return dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------

def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block: Optional[int] = None,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """The forward kernel alone: o and the f32 log-sum-exp of each query
    row's scaled scores, (B, H/block_h, block_h, S), which the backward
    reads."""
    return _fwd(q, k, v, _Plan(q, causal, block), True, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Differentiable attention over q/k/v (B, S, H, hd) -> (B, S, H, hd),
    query head h reading kv head h.  ``block`` defaults to ``block_sizes``;
    an output that is not differentiated skips the log-sum-exp."""
    return _fwd(q, k, v, _Plan(q, causal, block), False, interpret)


def _vjp_fwd(q, k, v, causal, block, interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, block=block,
                                 interpret=interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, block, interpret, res, do):
    q, k, v, o, lse = res
    plan = _Plan(q, causal, block)
    dq, di = _dq(q, k, v, do, o, lse, plan, interpret)
    dk, dv = _dkv(q, k, v, do, lse, di, plan, interpret)
    return dq, dk, dv


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
