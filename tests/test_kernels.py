"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.xent.ops import per_token_xent_fused, per_sample_xent_fused
from repro.kernels.xent.ref import xent_ref
from repro.kernels.flash_attn.flash_attn import (flash_attention,
                                                 flash_attention_fwd)
from repro.kernels.flash_attn.ops import gqa_flash_attention
from repro.kernels.flash_attn.ref import attention_ref, lse_ref
from repro.kernels.score_update.score_update import fused_score_update
from repro.kernels.score_update.ops import update_scores_fused
from repro.kernels.score_update.ref import score_update_ref
from repro.core.scores import init_scores, update_scores


# ---------------------------------------------------------------------------
# xent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,d,V", [(128, 64, 512), (256, 128, 1024),
                                   (128, 96, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xent_kernel_matches_oracle(M, d, V, dtype):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    h = jax.random.normal(k1, (M, d)).astype(dtype)
    w = (jax.random.normal(k2, (d, V)) * 0.05).astype(dtype)
    labels = jax.random.randint(k3, (M,), 0, V)
    got = per_token_xent_fused(h, w, labels, interpret=True)
    want = xent_ref(h, w, labels)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("M,V", [(100, 500), (130, 777),
                                 (192, 500), (300, 640)])
def test_xent_kernel_padding_paths(M, V):
    """Non-multiple M and V exercise the row/vocab padding paths exactly.

    192 and 300 straddle the block_m=128 row tile (1.5 and 2.34 blocks) —
    the packed path flattens (B, S) to M = B*S, which is rarely a tile
    multiple, so the ragged final block must mask exactly."""
    key = jax.random.PRNGKey(1)
    h = jax.random.normal(key, (M, 64))
    w = jax.random.normal(key, (64, V)) * 0.1
    labels = jax.random.randint(key, (M,), 0, V)
    got = per_token_xent_fused(h, w, labels, interpret=True)
    want = xent_ref(h, w, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_xent_per_sample_masking():
    key = jax.random.PRNGKey(2)
    B, S, d, V = 4, 32, 64, 512
    h = jax.random.normal(key, (B, S, d))
    w = jax.random.normal(key, (d, V)) * 0.1
    labels = jax.random.randint(key, (B, S), 0, V)
    labels = labels.at[:, -8:].set(-1)            # masked tail
    ps, mean = per_sample_xent_fused(h, w, labels, interpret=True)
    # oracle through the model's XLA path
    from repro.models.losses import per_sample_xent
    from repro.models.layers import ShardCtx
    ps_ref, mean_ref = per_sample_xent(h, w, labels, ctx=ShardCtx(),
                                       seq_chunk=0)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(ps_ref), atol=1e-4)
    np.testing.assert_allclose(float(mean), float(mean_ref), atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,hd,block", [(256, 64, 128), (256, 64, 256),
                                        (128, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(S, hd, block, causal):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, S, hd))
    k = jax.random.normal(ks[1], (2, S, hd))
    v = jax.random.normal(ks[2], (2, S, hd))
    # the kernel reads (B, S, H, hd): these rows are the H = 2 heads of B = 1
    heads = lambda x: x.transpose(1, 0, 2)[None]  # noqa: E731
    got = flash_attention(heads(q), heads(k), heads(v), causal=causal,
                          block=block, interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got[0].transpose(1, 0, 2)),
                               np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_gqa_wrapper(dtype):
    key = jax.random.PRNGKey(1)
    B, S, H, K, hd = 2, 128, 8, 2, 64
    q = jax.random.normal(key, (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(key, (B, S, K, hd)).astype(dtype)
    v = jax.random.normal(key, (B, S, K, hd)).astype(dtype)
    got = gqa_flash_attention(q, k, v, block=128, interpret=True)
    # oracle: repeat kv
    G = H // K
    kr = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vr = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = attention_ref(qr, kr, vr).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def _qkv(key, shape, dtype):
    ks = jax.random.split(key, 4)
    return [jax.random.normal(k, shape).astype(dtype) for k in ks]


def _heads_ref(q, k, v, causal):
    """``attention_ref`` on (B, S, H, hd), a head at a time."""
    B, S, H, hd = q.shape
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)  # noqa
    o = attention_ref(flat(q), flat(k), flat(v), causal)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# relative to the largest entry: f32 inputs keep f32 MXU operands; bf16
# inputs round q, k, v, p and dS to bf16 (8 bits of mantissa)
REL_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 3e-2}


def _flash_cases(S, block):
    """(dtype, causal, hd, S, block): S = 512 in blocks of 256 at every
    dtype, causal or not, and head (ids without S), then the causal cases
    at ``S`` and ``block`` (ids ending in their S)."""
    cases = [pytest.param(dtype, causal, hd, 512, 256,
                          id=f"{dtype.__name__}-{causal}-{hd}")
             for dtype in (jnp.float32, jnp.bfloat16)
             for causal in (True, False) for hd in (64, 128)]
    return cases + [pytest.param(dtype, True, hd, S, block,
                                 id=f"{dtype.__name__}-True-{hd}-S{S}")
                    for dtype in (jnp.float32, jnp.bfloat16)
                    for hd in (64, 128)]


# two heads: at head 64 they share one 128-lane slab, at 128 one slab each
@pytest.mark.parametrize("dtype,causal,hd,S,block", _flash_cases(1024, 1024))
def test_flash_attention_fwd_and_lse(dtype, causal, hd, S, block):
    """Forward output and the saved log-sum-exp.  S of two blocks of 256
    works each diagonal tile in two strips and a row's second kv tile
    rescales what its first set; S = block = 1024, the cells' tiling, works
    one tile in eight strips, each row's first and only kv tile."""
    q, k, v, _ = _qkv(jax.random.PRNGKey(2), (1, S, 2, hd), dtype)
    o, lse = flash_attention_fwd(q, k, v, causal=causal, block=block,
                                 interpret=True)
    assert o.dtype == dtype and o.shape == q.shape
    assert _rel_err(o, _heads_ref(q, k, v, causal)) < REL_TOL[dtype]
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(2, S, hd)  # noqa
    want = lse_ref(flat(q), flat(k), causal)                     # (H, S)
    np.testing.assert_allclose(np.asarray(lse).reshape(2, S),
                               np.asarray(want), rtol=REL_TOL[dtype],
                               atol=REL_TOL[dtype])


@pytest.mark.parametrize("dtype,causal,hd,S,block", _flash_cases(512, 512))
def test_flash_attention_grads(dtype, causal, hd, S, block):
    """dq, dk, dv of the dQ and dK/dV kernels against ``jax.vjp`` of the
    oracle.  S = 512 in two blocks of 256 has tiles below, on (in two
    strips) and above the diagonal; in one block of 512, one tile in four
    strips of 128 to 512 columns."""
    q, k, v, do = _qkv(jax.random.PRNGKey(3), (1, S, 2, hd), dtype)
    f = lambda q, k, v: flash_attention(q, k, v, causal=causal,  # noqa: E731
                                        block=block, interpret=True)
    got = jax.vjp(f, q, k, v)[1](do)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    want = jax.vjp(lambda q, k, v: _heads_ref(q, k, v, causal),
                   *f32[:3])[1](f32[3])
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == dtype, name
        assert _rel_err(g, w) < REL_TOL[dtype], name


def _strips_oracle(q, k, v, do):
    """o, the lse (H, S), dq, dk, dv of causal attention over one tile of
    S = n*128 rows holding two heads of 64 in one 128-lane slab, worked
    strip by strip in the kernel's plain formulation: the other head's
    lanes zeroed on the operand with the strip's columns (k and v; q and dO
    for dK/dV), and the online softmax rescaled from its initial state."""
    S, H, hd = q.shape[1:]
    q, k, v, do = (x[0].reshape(S, H * hd) for x in (q, k, v, do))
    f32, neg, scale = jnp.float32, -1e30, 1.0 / hd ** 0.5
    nt = lambda a, b: jax.lax.dot_general(  # noqa: E731
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    nn = lambda a, b: jnp.dot(a.astype(b.dtype), b,  # noqa: E731
                              preferred_element_type=f32)
    keep = lambda nr, nc, r0: (jnp.arange(nr)[:, None] + r0  # noqa: E731
                               >= jnp.arange(nc)[None])
    lane = jnp.arange(H * hd)[None]
    o, dq, dk, dv = (jnp.zeros((S, H * hd), f32) for _ in range(4))
    lse = []
    for h in range(H):
        hm = lane // hd == h
        only = lambda x: jnp.where(hm, x, jnp.zeros_like(x))  # noqa: E731
        m_l = []
        for r0 in range(0, S, 128):
            rows, nc = slice(r0, r0 + 128), r0 + 128
            s = jnp.where(keep(128, nc, r0), nt(q[rows], only(k[:nc]))
                          * scale, neg)
            m = jnp.maximum(jnp.full((128, 1), neg, f32),
                            jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m)
            corr = jnp.exp(neg - m)
            l = corr * jnp.zeros((128, 1), f32) + p.sum(axis=1, keepdims=True)
            acc = (jnp.where(hm, jnp.zeros((128, H * hd), f32) * corr, 0.0)
                   + nn(p, only(v[:nc])))
            o = o.at[rows].set(jnp.where(hm, acc / l, o[rows]))
            m_l.append(m + jnp.log(l))
        lse.append(jnp.concatenate(m_l)[:, 0])
    o = o.astype(q.dtype)
    for h in range(H):
        hm = lane // hd == h
        only = lambda x: jnp.where(hm, x, jnp.zeros_like(x))  # noqa: E731
        di = jnp.sum(only(do.astype(f32) * o.astype(f32)), axis=1)
        for r0 in range(0, S, 128):
            rows, nc, qs = slice(r0, r0 + 128), r0 + 128, slice(r0, S)
            s = jnp.where(keep(128, nc, r0), nt(q[rows], only(k[:nc]))
                          * scale, neg)
            p = jnp.exp(s - lse[h][rows, None])
            ds = p * (nt(do[rows], only(v[:nc])) - di[rows, None])
            dq = dq.at[rows].add(nn(ds, only(k[:nc])))
            s_t = jnp.where(keep(S - r0, 128, 0).T,
                            nt(k[rows], only(q[qs])) * scale, neg)
            p_t = jnp.exp(s_t - lse[h][None, qs])
            dv = dv.at[rows].add(nn(p_t, only(do[qs])))
            ds_t = p_t * (nt(v[rows], only(do[qs])) - di[None, qs])
            dk = dk.at[rows].add(nn(ds_t, only(q[qs])))
    out = lambda x: x.astype(q.dtype).reshape(1, S, H, hd)  # noqa: E731
    return out(o), jnp.stack(lse), out(dq * scale), out(dk * scale), out(dv)


@pytest.mark.parametrize("S", [512, 1024])
def test_flash_attention_exact_at_head_64(S):
    """At head 64 with f32 inputs and one tile (S = block, the cells'
    tiling), the kernel's o, lse, dq, dk and dv equal, bit for bit, the
    plain formulation of ``_strips_oracle``: zeroing the other head's lanes
    on the strip's rows and keeping the head's lanes of each product, and
    setting the softmax state from a row's first kv tile, are exact."""
    q, k, v, do = _qkv(jax.random.PRNGKey(5), (1, S, 2, 64), jnp.float32)
    o, lse = flash_attention_fwd(q, k, v, block=S, interpret=True)
    f = lambda q, k, v: flash_attention(q, k, v, True, S, True)  # noqa
    got = [o, lse.reshape(2, S), *jax.vjp(f, q, k, v)[1](do)]
    for name, g, w in zip(("o", "lse", "dq", "dk", "dv"), got,
                          _strips_oracle(q, k, v, do)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


@pytest.mark.parametrize("B,H,K", [(2, 4, 2), (1, 4, 1)])
def test_flash_attention_gqa_grads(B, H, K):
    """Grouped kv heads repeated to the query heads: every kv head gathers
    the gradients of its G query heads (G = 2, and G = 4 onto one kv
    head)."""
    S, hd = 256, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    do = jax.random.normal(ks[3], (B, S, H, hd))

    def oracle(q, k, v):
        G = H // K
        return _heads_ref(q, jnp.repeat(k, G, axis=2),
                          jnp.repeat(v, G, axis=2), True)

    f = lambda q, k, v: gqa_flash_attention(q, k, v, block=128,  # noqa
                                            interpret=True)
    got = jax.vjp(f, q, k, v)[1](do)
    want = jax.vjp(oracle, q, k, v)[1](do)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape, name
        assert _rel_err(g, w) < 1e-5, name


# ---------------------------------------------------------------------------
# score update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,B", [(64, 16), (256, 64), (1024, 32)])
def test_score_update_kernel_unique_ids(n, B):
    key = jax.random.PRNGKey(0)
    s = jnp.abs(jax.random.normal(key, (n,)))
    w = jnp.abs(jax.random.normal(key, (n,)))
    seen = jnp.zeros((n,), jnp.int32)
    ids = jnp.asarray(np.random.default_rng(0).choice(n, B, replace=False),
                      jnp.int32)
    losses = jnp.abs(jax.random.normal(key, (B,)))
    got = fused_score_update(s, w, seen, ids, losses, beta1=0.2, beta2=0.9,
                             interpret=True)
    want = score_update_ref(s, w, seen, ids, losses, beta1=0.2, beta2=0.9)
    for g, x in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), atol=1e-6)


def test_score_update_ops_wrapper_matches_core():
    scores = init_scores(128)
    ids = jnp.asarray([3, 77, 100], jnp.int32)
    losses = jnp.asarray([0.5, 2.0, 0.1])
    got = update_scores_fused(scores, ids, losses, 0.2, 0.9, interpret=True)
    want = update_scores(scores, ids, losses, 0.2, 0.9)
    np.testing.assert_allclose(np.asarray(got.s), np.asarray(want.s))
    np.testing.assert_allclose(np.asarray(got.w), np.asarray(want.w))
    np.testing.assert_allclose(np.asarray(got.seen), np.asarray(want.seen))


@pytest.mark.parametrize("n,B,b1,b2", [(1024, 128, 0.2, 0.9),
                                       (4096, 256, 0.0, 0.0),
                                       (2048, 64, 0.5, 0.8)])
def test_score_update_kernel_sweep_vs_ref(n, B, b1, b2):
    """Wider shape/beta sweep of the fused kernel against ref.py, at the
    store sizes the train path actually uses."""
    key = jax.random.PRNGKey(42)
    k1, k2, k3 = jax.random.split(key, 3)
    s = jnp.abs(jax.random.normal(k1, (n,)))
    w = jnp.abs(jax.random.normal(k2, (n,)))
    seen = jax.random.randint(k3, (n,), 0, 5)
    ids = jnp.asarray(np.random.default_rng(1).choice(n, B, replace=False),
                      jnp.int32)
    losses = jnp.abs(jax.random.normal(k1, (B,)))
    got = fused_score_update(s, w, seen, ids, losses, beta1=b1, beta2=b2,
                             interpret=True)
    want = score_update_ref(s, w, seen, ids, losses, beta1=b1, beta2=b2)
    for g, x in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), atol=1e-6)


def test_score_update_untouched_rows_unchanged():
    """Rows outside ``ids`` pass through the kernel bit-identically."""
    n, B = 512, 32
    scores = init_scores(n)
    ids = jnp.arange(0, 2 * B, 2, dtype=jnp.int32)       # even rows only
    losses = jnp.linspace(0.1, 2.0, B)
    out = update_scores_fused(scores, ids, losses, 0.2, 0.9, interpret=True)
    mask = np.ones(n, bool)
    mask[np.asarray(ids)] = False
    np.testing.assert_array_equal(np.asarray(out.s)[mask],
                                  np.asarray(scores.s)[mask])
    np.testing.assert_array_equal(np.asarray(out.w)[mask],
                                  np.asarray(scores.w)[mask])
    assert np.asarray(out.seen)[mask].sum() == 0


def test_score_update_duplicate_id_semantics_pinned():
    """Kernel: sequential recursion for duplicates (the correct Eq. 3.1
    semantics); oracle scatter: last-write-wins from original s.  Pinned so
    a behaviour change is caught."""
    s = jnp.asarray([1.0])
    w = jnp.asarray([1.0])
    seen = jnp.zeros((1,), jnp.int32)
    ids = jnp.asarray([0, 0], jnp.int32)
    losses = jnp.asarray([2.0, 4.0])
    b1, b2 = 0.5, 0.5
    ks, kw, kseen = fused_score_update(s, w, seen, ids, losses, beta1=b1,
                                       beta2=b2, interpret=True)
    # sequential: s=0.5*1+0.5*2=1.5 then s=0.5*1.5+0.5*4=2.75
    np.testing.assert_allclose(float(ks[0]), 2.75)
    assert int(kseen[0]) == 2
    rs, rw, rseen = score_update_ref(s, w, seen, ids, losses, beta1=b1,
                                     beta2=b2)
    np.testing.assert_allclose(float(rs[0]), 2.5)   # last write, original s


# ---------------------------------------------------------------------------
# quantized score update (int8 + error-feedback ring)
# ---------------------------------------------------------------------------

def _quant_setup(n, B, R=256, block=64, seed=0, steps=1):
    """A quantized store advanced ``steps`` times plus one fresh batch —
    the kernel/oracle comparison inputs (ids unique, clean ring)."""
    from repro.core.scores import make_store
    st = make_store(None, quantize=True, block=block, residual_rows=R)
    qs = st.init_leaf(n)
    rng = np.random.default_rng(seed)
    for _ in range(steps - 1):
        ids = jnp.asarray(rng.choice(n, B, replace=False), jnp.int32)
        losses = jnp.asarray(rng.uniform(0.1, 2.0, B), jnp.float32)
        qs = st.update(qs, ids, losses, 0.2, 0.9)
    ids = jnp.asarray(rng.choice(n, B, replace=False), jnp.int32)
    losses = jnp.asarray(rng.uniform(0.1, 2.0, B), jnp.float32)
    return st, qs, ids, losses


def _quant_kernel_vs_ref(qs, ids, gids, losses, block):
    """Run both sides from identical post-prologue state; return outputs."""
    from repro.core.scores import _q_grow_scales, _q_ring_slots
    from repro.kernels.score_update.score_update import (
        fused_quant_score_update)
    from repro.kernels.score_update.ref import quant_score_update_ref
    n = qs.s_q.shape[0]
    mask = (ids >= 0) & (ids < n)
    pos = jnp.where(mask, ids, 0)
    mgids = jnp.where(mask, gids, -1)
    qs = _q_grow_scales(qs, pos, mask, mgids, losses, 0.2, 0.9, block)
    slots, seqs = _q_ring_slots(qs.err_seq, mask)
    lids = jnp.where(mask, pos, -1)
    args = (qs.s_q, qs.w_q, qs.seen_q, qs.s_scale, qs.w_scale,
            qs.err_rows, qs.err_seq, qs.err_s, qs.err_w,
            lids, mgids, losses, slots, seqs)
    got = fused_quant_score_update(*args, beta1=0.2, beta2=0.9, block=block,
                                   interpret=True)
    want = quant_score_update_ref(*args, beta1=0.2, beta2=0.9, block=block)
    return got, want


def _assert_quant_contract(got, want):
    """Integer leaves bitwise; residuals to FMA slack (see ref.py)."""
    names = ("s_q", "w_q", "seen_q", "err_rows", "err_seq", "err_s", "err_w")
    for name, g, x in zip(names, got, want):
        if name in ("err_s", "err_w"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(x),
                                       atol=1e-7, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x),
                                          err_msg=name)


@pytest.mark.parametrize("n,B", [(256, 32), (1024, 64), (512, 17)])
def test_quant_score_kernel_matches_oracle(n, B):
    _, qs, ids, losses = _quant_setup(n, B)
    got, want = _quant_kernel_vs_ref(qs, ids, ids, losses, 64)
    _assert_quant_contract(got, want)


def test_quant_score_kernel_masked_ids_skipped():
    """Per-shard dispatch: -1 ids leave codes, seen AND ring untouched on
    both sides (oob entries take the sentinel ring slot)."""
    n, B = 256, 32
    _, qs, ids, losses = _quant_setup(n, B, steps=2)
    ids = ids.at[::2].set(-1)                       # drop half the batch
    got, want = _quant_kernel_vs_ref(qs, ids, ids, losses, 64)
    _assert_quant_contract(got, want)
    # dropped rows' codes unchanged past the (shared, XLA) grow prologue
    from repro.core.scores import _q_grow_scales
    mask_b = (ids >= 0) & (ids < n)
    grown = _q_grow_scales(qs, jnp.where(mask_b, ids, 0), mask_b,
                           jnp.where(mask_b, ids, -1), losses, 0.2, 0.9, 64)
    touched = np.asarray(ids)[np.asarray(ids) >= 0]
    mask = np.ones(n, bool)
    mask[touched] = False
    np.testing.assert_array_equal(np.asarray(got[0])[mask],
                                  np.asarray(grown.s_q)[mask])


def test_quant_score_kernel_warm_ring_hits():
    """Second update of the SAME rows: the kernel must find and apply the
    ring residuals written by the first (the dequant+resid gather path)."""
    n, B = 512, 48
    st, qs, ids, losses = _quant_setup(n, B, steps=3)
    got, want = _quant_kernel_vs_ref(qs, ids, ids, losses, 64)
    _assert_quant_contract(got, want)
    assert int(np.asarray(got[4]).max()) > 0        # ring actually stamped


def test_quant_store_update_fused_matches_scatter():
    """Store-level: update(fused=True, interpret) == update(fused=False)
    under the same contract (codes bitwise, residuals to FMA slack)."""
    from repro.core.scores import make_store
    st, qs, ids, losses = _quant_setup(512, 64, steps=2)
    a = st.update(qs, ids, losses, 0.2, 0.9, fused=True, interpret=True)
    b = st.update(qs, ids, losses, 0.2, 0.9, fused=False)
    for f in ("s_q", "w_q", "seen_q", "s_scale", "w_scale", "err_rows",
              "err_seq"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    for f in ("err_s", "err_w"):
        np.testing.assert_allclose(np.asarray(getattr(a, f)),
                                   np.asarray(getattr(b, f)), atol=1e-7,
                                   err_msg=f)


def test_quant_store_fused_falls_back_off_tpu():
    """fused=True with interpret unset on CPU routes to the XLA scatter
    (no Pallas compile attempt) — identical to fused=False."""
    from repro.core.scores import make_store
    st, qs, ids, losses = _quant_setup(256, 32)
    a = st.update(qs, ids, losses, 0.2, 0.9, fused=True)   # CPU: falls back
    b = st.update(qs, ids, losses, 0.2, 0.9, fused=False)
    np.testing.assert_array_equal(np.asarray(a.s_q), np.asarray(b.s_q))
    np.testing.assert_array_equal(np.asarray(a.err_s), np.asarray(b.err_s))
