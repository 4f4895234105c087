"""Property suite: ReplicatedStore vs ShardedStore bit-parity (ISSUE 5).

The ``ScoreStore`` contract is that placement is invisible: for ANY id
stream — duplicates, out-of-range entries (dropped by every backend,
the shared masking rule), partial batches — the sharded backend's
update/gather/select/prune are bit-identical to the replicated
reference.  The sharded mesh spans every device of the backend (1 on
plain tier-1 runs, 8 on the CI multi-device matrix cell; the multi-host
parity lives in tests/test_multihost.py).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # hermetic fallback
    from _hypothesis_fallback import given, settings, st

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.pruning import prune_epoch  # noqa: E402
from repro.core.scores import (ReplicatedStore, ScoreSharding,  # noqa: E402
                               ShardedStore)

_B1, _B2 = 0.2, 0.9


def _stores():
    D = jax.device_count()
    mesh = jax.make_mesh((D,), ("data",))
    return ReplicatedStore(), ShardedStore(ScoreSharding(mesh, ("data",)))


def _assert_scores_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.s), np.asarray(b.s))
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.seen), np.asarray(b.seen))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 6), st.integers(1, 24))
def test_update_gather_parity_duplicates_oob_partial(seed, per_shard, B):
    """Random id streams: duplicate ids in one batch, ids outside [0, n)
    (both backends drop them), and B of any size (incl. not divisible by
    the shard count) must leave both stores bit-identical.  Run time:
    each example draws a new store size and batch size, and each gather
    a new id count, so the store ops compile once per draw."""
    rep_store, shd_store = _stores()
    D = jax.device_count()
    n = per_shard * D
    rng = np.random.default_rng(seed)
    rep = rep_store.init_leaf(n)
    shd = shd_store.init_leaf(n)
    for _ in range(3):
        # duplicates (replace=True) + out-of-range entries on both sides
        ids = rng.integers(-3, n + 3, size=B)
        losses = rng.uniform(0.05, 3.0, B).astype(np.float32)
        jids = jnp.asarray(ids, jnp.int32)
        jlosses = jnp.asarray(losses)
        rep = rep_store.update(rep, jids, jlosses, _B1, _B2)
        shd = shd_store.update(shd, jids, jlosses, _B1, _B2)
        _assert_scores_equal(rep, shd)
        # gathers agree on every in-range id (out-of-range rows have no
        # owner in a sharded store: the gather contract is in-range only)
        valid = ids[(ids >= 0) & (ids < n)]
        if len(valid):
            vids = jnp.asarray(valid, jnp.int32)
            s_r, w_r = rep_store.gather(rep, vids)
            s_s, w_s = shd_store.gather(shd, vids)
            np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_s))
            np.testing.assert_array_equal(np.asarray(w_r), np.asarray(w_s))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 48))
def test_select_parity_any_batch_size(seed, B):
    """Gumbel selection from the sharded backend == the replicated
    reference for every batch size — divisible batches go through the
    per-shard candidate merge, partial ones through the (bit-equal)
    replicated form.  Run time: each example draws a new (B, k), so
    both selects compile once per draw."""
    rep_store, shd_store = _stores()
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.uniform(0.01, 5.0, B), jnp.float32)
    k = int(rng.integers(1, B + 1))
    key = jax.random.PRNGKey(seed % (2 ** 31))
    np.testing.assert_array_equal(
        np.asarray(rep_store.select(key, w, k)),
        np.asarray(shd_store.select(key, w, k)))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 5),
       st.sampled_from(["eswp", "infobatch", "ucb", "ka", "random", "none"]))
def test_prune_parity_from_backend_snapshots(seed, per_shard, method):
    """``ScoreStore.prune_epoch`` (snapshot + exact global reductions)
    returns the same kept-set, grad rescale and s-snapshot from both
    backends — and matches the full-array ``prune_epoch`` reference.
    Run time: each example compiles both updates for its drawn size."""
    rep_store, shd_store = _stores()
    D = jax.device_count()
    n = per_shard * D * 4
    rng = np.random.default_rng(seed)
    rep = rep_store.init_leaf(n)
    shd = shd_store.init_leaf(n)
    # first pass touches every row (distinct s: the parity contract for
    # threshold methods is exactness up to float ties), then a random one
    for ids in (rng.permutation(n), rng.choice(n, n // 2, replace=False)):
        ids = jnp.asarray(ids, jnp.int32)
        losses = jnp.asarray(rng.uniform(0.05, 3.0, len(ids)), jnp.float32)
        rep = rep_store.update(rep, ids, losses, _B1, _B2)
        shd = shd_store.update(shd, ids, losses, _B1, _B2)
    prev = rng.uniform(0.05, 3.0, n).astype(np.float32)
    res_r, s_r = rep_store.prune_epoch(method, np.random.default_rng(seed),
                                       rep, prev_losses=prev, ratio=0.25)
    res_s, s_s = shd_store.prune_epoch(method, np.random.default_rng(seed),
                                       shd, prev_losses=prev, ratio=0.25)
    np.testing.assert_array_equal(np.sort(res_r.kept), np.sort(res_s.kept))
    np.testing.assert_array_equal(s_r, s_s)
    if res_r.grad_scale is None:
        assert res_s.grad_scale is None
    else:
        np.testing.assert_array_equal(res_r.grad_scale, res_s.grad_scale)
    # the reference full-array entry point agrees
    ref = prune_epoch(method, np.random.default_rng(seed),
                      weights=np.asarray(rep.w), losses=np.asarray(rep.s),
                      prev_losses=prev, seen=np.asarray(rep.seen),
                      ratio=0.25)
    np.testing.assert_array_equal(np.sort(ref.kept), np.sort(res_s.kept))


# ---------------------------------------------------------------------------
# QuantizedStore properties (ISSUE 7 satellite): the int8 + error-feedback
# invariants that must hold for ANY id/loss stream
# ---------------------------------------------------------------------------

from repro.core.scores import make_store  # noqa: E402
from repro.distributed.compression import (  # noqa: E402
    dequantize_int8_blocks, quantize_int8_blocks)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 64),
       st.sampled_from([16, 64, 256]))
def test_quantize_blocks_grid_point_idempotence(seed, nb, block):
    """Values already ON the int8 grid re-quantize to the same codes and
    dequantize bit-identically (quant o dequant == identity on the grid).
    The property needs the scale to be recoverable, i.e. each block holds
    a full-range code — otherwise re-quantization legitimately picks a
    tighter grid.  Run time: each example draws a new (nb, block), and
    the eager quantize ops compile once per shape."""
    rng = np.random.default_rng(seed)
    q0 = rng.integers(-127, 128, size=(nb, block))
    q0[:, 0] = 127                        # pin the block max: amax/127 == s0
    q0 = q0.reshape(-1).astype(np.int8)
    s0 = rng.uniform(1e-6, 2.0, nb).astype(np.float32)
    x = dequantize_int8_blocks(jnp.asarray(q0), jnp.asarray(s0), block)
    q1, s1 = quantize_int8_blocks(x, block)
    x1 = dequantize_int8_blocks(q1, s1, block)
    np.testing.assert_array_equal(q0, np.asarray(q1))   # codes exact
    # values: the recovered scale fl(fl(127*s)/127) may sit 1 ulp off s
    np.testing.assert_allclose(np.asarray(x), np.asarray(x1), rtol=3e-7)


def test_quantize_blocks_scale_floor_on_zero():
    """All-zero input: scales clamp to the floor (no divide-by-zero, no
    NaN) and the round trip returns exact zeros."""
    q, s = quantize_int8_blocks(jnp.zeros((512,)), 128)
    assert float(jnp.min(s)) > 0.0
    out = np.asarray(dequantize_int8_blocks(q, s, 128))
    np.testing.assert_array_equal(out, np.zeros(512, np.float32))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 24))
def test_quant_fresh_residual_bounded_by_half_scale(seed, B):
    """Right after an update (no intervening growth), every ring residual
    obeys |e| <= scale/2 — requant rounds to the nearest grid point.
    Run time: the update compiles once per drawn batch size."""
    n = 256
    store = make_store(None, quantize=True, block=64, residual_rows=512)
    qs = store.init_leaf(n)
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.choice(n, B, replace=False), jnp.int32)
    losses = jnp.asarray(rng.uniform(0.05, 3.0, B), jnp.float32)
    qs = store.update(qs, ids, losses, _B1, _B2)
    live = np.asarray(qs.err_seq) > 0
    blk = np.asarray(qs.err_rows)[live] // 64
    np.testing.assert_array_less(
        np.abs(np.asarray(qs.err_s)[live]),
        np.asarray(qs.s_scale)[blk] * 0.5 + 1e-9)
    np.testing.assert_array_less(
        np.abs(np.asarray(qs.err_w)[live]),
        np.asarray(qs.w_scale)[blk] * 0.5 + 1e-9)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 32))
def test_quant_update_gather_roundtrip_vs_f32(seed, B):
    """Shuffled, duplicate and out-of-range id streams: the quantized
    store's gathers track the f32 recursion within the EF bound
    (scale/2 geometric sum over the beta2 EMA), and out-of-range ids are
    dropped exactly like the f32 backends.  Run time: the updates
    compile once per drawn batch size."""
    from repro.core.scores import init_scores, update_scores
    n = 128
    store = make_store(None, quantize=True, block=32, residual_rows=1024)
    qs = store.init_leaf(n)
    ref = init_scores(n)
    rng = np.random.default_rng(seed)
    for _ in range(3):
        ids = rng.integers(-3, n + 3, size=B)          # dups + oob
        losses = rng.uniform(0.05, 3.0, B).astype(np.float32)
        jids = jnp.asarray(ids, jnp.int32)
        jlosses = jnp.asarray(losses)
        qs = store.update(qs, jids, jlosses, _B1, _B2)
        ref = update_scores(ref, jids, jlosses, _B1, _B2)
    valid = np.unique(np.arange(n))
    s, w = store.gather(qs, jnp.asarray(valid, jnp.int32))
    geo = 1.0 / (1.0 - _B2)
    tol_s = float(jnp.max(qs.s_scale)) * 0.5 * geo + 1e-7
    tol_w = (float(jnp.max(qs.w_scale)) * 0.5
             + float(jnp.max(qs.s_scale)) * 0.5 * geo) + 1e-7
    np.testing.assert_allclose(np.asarray(s), np.asarray(ref.s)[valid],
                               atol=tol_s)
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref.w)[valid],
                               atol=tol_w)
    # seen counts match exactly (int path, saturating far above 3*B hits)
    np.testing.assert_array_equal(
        np.asarray(qs.seen_q).astype(np.int32),
        np.minimum(np.asarray(ref.seen), 127))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 5), st.integers(4, 24))
def test_quant_sharded_parity_any_stream(seed, per_shard, B):
    """Quantized placement invariance under hypothesis streams (dups,
    oob, any B): sharded-quant leaves bit-equal replicated-quant with a
    roomy ring.  Run time: each example draws a new store and batch
    size, and each gather a new id count, so the quantized ops of both
    placements compile once per draw."""
    D = jax.device_count()
    n = per_shard * D * 4
    mesh = jax.make_mesh((D,), ("data",))
    rep = make_store(None, quantize=True, block=per_shard,
                     residual_rows=4096)
    shd = make_store(ScoreSharding(mesh, ("data",)), quantize=True,
                     block=per_shard, residual_rows=4096)
    q_r, q_s = rep.init_leaf(n), shd.init_leaf(n)
    rng = np.random.default_rng(seed)
    for _ in range(3):
        ids = rng.integers(-3, n + 3, size=B)
        losses = rng.uniform(0.05, 3.0, B).astype(np.float32)
        jids = jnp.asarray(ids, jnp.int32)
        jlosses = jnp.asarray(losses)
        q_r = rep.update(q_r, jids, jlosses, _B1, _B2)
        q_s = shd.update(q_s, jids, jlosses, _B1, _B2)
        np.testing.assert_array_equal(np.asarray(q_r.s_q),
                                      np.asarray(q_s.s_q))
        np.testing.assert_array_equal(np.asarray(q_r.w_q),
                                      np.asarray(q_s.w_q))
        np.testing.assert_array_equal(np.asarray(q_r.seen_q),
                                      np.asarray(q_s.seen_q))
        valid = np.unique(ids[(ids >= 0) & (ids < n)])
        if len(valid):
            vids = jnp.asarray(valid, jnp.int32)
            s_r, w_r = rep.gather(q_r, vids)
            s_s, w_s = shd.gather(q_s, vids)
            np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_s))
            np.testing.assert_array_equal(np.asarray(w_r), np.asarray(w_s))
