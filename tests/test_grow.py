"""Property suite: ``ScoreStore.grow`` parity + round-trips (ISSUE 8).

``grow(scores, n_new)`` is the store-side half of the online scoring
service: the contract is that pre-grow rows are BITWISE preserved, new
rows start at the 1/n' prior with ``seen == 0``, and placement stays
invisible — a grown sharded store is bit-equal to a grown replicated
one, and a grow-then-checkpoint-then-restore round-trip reproduces the
original rows exactly (so a grown run stays bit-equal to an ungrown one
on the original population).
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # hermetic fallback
    from _hypothesis_fallback import given, settings, st

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.checkpoint.checkpointer import Checkpointer  # noqa: E402
from repro.core.scores import (ReplicatedStore, ScoreSharding,  # noqa: E402
                               ShardedStore, make_store)

_B1, _B2 = 0.2, 0.9


def _stores():
    D = jax.device_count()
    mesh = jax.make_mesh((D,), ("data",))
    return ReplicatedStore(), ShardedStore(ScoreSharding(mesh, ("data",)))


def _assert_scores_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.s), np.asarray(b.s))
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.seen), np.asarray(b.seen))


def _touch(store, leaf, rng, n, B=16, rounds=2):
    """Dirty a store with a random id/loss stream (dups + oob included)."""
    for _ in range(rounds):
        ids = jnp.asarray(rng.integers(-2, n + 2, size=B), jnp.int32)
        losses = jnp.asarray(rng.uniform(0.05, 3.0, B), jnp.float32)
        leaf = store.update(leaf, ids, losses, _B1, _B2)
    return leaf


# ---------------------------------------------------------------------------
# grow() contract: bitwise prefix, 1/n' prior tail, placement parity
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 4), st.integers(1, 2))
def test_grow_parity_prefix_bitwise_tail_prior(seed, per_shard, grow_shards):
    """For any update stream then any (shard-divisible) growth: both
    backends bitwise-preserve the pre-grow rows, initialise the new tail
    at 1/n_total with seen == 0, and stay bit-equal to each other.  Run
    time: the store ops compile once per drawn size, before and after
    the grow."""
    rep_store, shd_store = _stores()
    D = jax.device_count()
    n = per_shard * D
    n_new = grow_shards * D * per_shard
    rng = np.random.default_rng(seed)
    rep = _touch(rep_store, rep_store.init_leaf(n), rng, n)
    rng = np.random.default_rng(seed)                  # same stream
    shd = _touch(shd_store, shd_store.init_leaf(n), rng, n)
    pre_s, pre_w, pre_seen = (np.asarray(rep.s), np.asarray(rep.w),
                              np.asarray(rep.seen))

    rep_store2, rep2 = rep_store.grow(rep, n_new)
    shd_store2, shd2 = shd_store.grow(shd, n_new)
    _assert_scores_equal(rep2, shd2)
    # bitwise prefix
    np.testing.assert_array_equal(np.asarray(rep2.s)[:n], pre_s)
    np.testing.assert_array_equal(np.asarray(rep2.w)[:n], pre_w)
    np.testing.assert_array_equal(np.asarray(rep2.seen)[:n], pre_seen)
    # 1/n' prior tail, unseen
    prior = np.float32(1.0 / (n + n_new))
    np.testing.assert_array_equal(np.asarray(rep2.s)[n:],
                                  np.full(n_new, prior))
    np.testing.assert_array_equal(np.asarray(rep2.w)[n:],
                                  np.full(n_new, prior))
    np.testing.assert_array_equal(np.asarray(rep2.seen)[n:],
                                  np.zeros(n_new, np.int32))
    # the grown stores keep full update/gather parity
    rng = np.random.default_rng(seed + 1)
    rep3 = _touch(rep_store2, rep2, rng, n + n_new)
    rng = np.random.default_rng(seed + 1)
    shd3 = _touch(shd_store2, shd2, rng, n + n_new)
    _assert_scores_equal(rep3, shd3)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 3), st.integers(1, 2))
def test_grow_quantized_parity_and_prior(seed, per_shard, grow_mult):
    """Quantized growth: codes/scales/ring grow consistently on both
    placements — grown sharded-quant stays bit-equal to grown
    replicated-quant, old codes are bitwise-preserved, and the new tail
    dequantizes to the 1/n' prior.  Run time: the quantized ops of both
    placements compile once per drawn size, before and after the grow."""
    D = jax.device_count()
    n = per_shard * D * 2
    n_new = per_shard * D * 2 * grow_mult
    mesh = jax.make_mesh((D,), ("data",))
    rep = make_store(None, quantize=True, block=per_shard,
                     residual_rows=4096)
    shd = make_store(ScoreSharding(mesh, ("data",)), quantize=True,
                     block=per_shard, residual_rows=4096)
    rng = np.random.default_rng(seed)
    q_r = _touch(rep, rep.init_leaf(n), rng, n)
    rng = np.random.default_rng(seed)
    q_s = _touch(shd, shd.init_leaf(n), rng, n)
    pre_sq = np.asarray(q_r.s_q).copy()

    rep2, q_r2 = rep.grow(q_r, n_new)
    shd2, q_s2 = shd.grow(q_s, n_new)
    np.testing.assert_array_equal(np.asarray(q_r2.s_q), np.asarray(q_s2.s_q))
    np.testing.assert_array_equal(np.asarray(q_r2.w_q), np.asarray(q_s2.w_q))
    np.testing.assert_array_equal(np.asarray(q_r2.seen_q),
                                  np.asarray(q_s2.seen_q))
    np.testing.assert_array_equal(np.asarray(q_r2.s_q)[:n], pre_sq)
    np.testing.assert_array_equal(np.asarray(q_r2.seen_q)[n:],
                                  np.zeros(n_new, np.int8))
    # tail dequantizes to the prior (scale chosen so 1/n' is on-grid)
    ids = jnp.arange(n, n + n_new, dtype=jnp.int32)
    s_tail, w_tail = rep2.gather(q_r2, ids)
    np.testing.assert_allclose(np.asarray(s_tail),
                               np.full(n_new, 1.0 / (n + n_new)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_tail),
                               np.full(n_new, 1.0 / (n + n_new)), rtol=1e-6)
    # gathers stay bit-equal after more updates on the grown stores
    rng = np.random.default_rng(seed + 1)
    q_r3 = _touch(rep2, q_r2, rng, n + n_new)
    rng = np.random.default_rng(seed + 1)
    q_s3 = _touch(shd2, q_s2, rng, n + n_new)
    vids = jnp.arange(n + n_new, dtype=jnp.int32)
    s_r, w_r = rep2.gather(q_r3, vids)
    s_s, w_s = shd2.gather(q_s3, vids)
    np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_s))
    np.testing.assert_array_equal(np.asarray(w_r), np.asarray(w_s))


def test_grow_rejects_bad_n_and_misaligned_block():
    rep = ReplicatedStore()
    leaf = rep.init_leaf(8)
    with pytest.raises(ValueError):
        rep.grow(leaf, 0)
    # quantized: a block wider than the pre-grow rows can't stay aligned
    q = make_store(None, quantize=True, block=64, residual_rows=128)
    qleaf = q.init_leaf(16)
    with pytest.raises(ValueError):
        q.grow(qleaf, 16)


# ---------------------------------------------------------------------------
# grow -> checkpoint -> restore round-trips (incl. across process counts,
# via the offset-tagged block format the cluster path uses)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,per_shard", [(0, 2), (7, 3), (123, 5)])
def test_grow_checkpoint_restore_roundtrip(tmp_path, seed, per_shard):
    """Grown leaves survive a checkpoint round-trip bitwise — on both
    placements, with the grown template driving the restore (the trainer
    grows the template BEFORE the template-driven restore)."""
    tmp = tmp_path
    rep_store, shd_store = _stores()
    D = jax.device_count()
    n, n_new = per_shard * D, per_shard * D
    rng = np.random.default_rng(seed)
    rep = _touch(rep_store, rep_store.init_leaf(n), rng, n)
    rep_store2, rep2 = rep_store.grow(rep, n_new)
    ck = Checkpointer(tmp / "rep")
    ck.save({"scores": rep2}, step=1,
            partition=rep_store2.checkpoint_partition())
    restored = ck.restore({"scores": rep_store2.init_leaf(n + n_new)},
                          step=1,
                          partition=rep_store2.checkpoint_partition())
    _assert_scores_equal(restored["scores"], rep2)

    rng = np.random.default_rng(seed)
    shd = _touch(shd_store, shd_store.init_leaf(n), rng, n)
    shd_store2, shd2 = shd_store.grow(shd, n_new)
    ck2 = Checkpointer(tmp / "shd")
    ck2.save({"scores": shd2}, step=1,
             partition=shd_store2.checkpoint_partition())
    restored2 = ck2.restore({"scores": shd_store2.init_leaf(n + n_new)},
                            step=1,
                            partition=shd_store2.checkpoint_partition())
    _assert_scores_equal(restored2["scores"], shd2)
    _assert_scores_equal(restored["scores"], restored2["scores"])


def test_grow_checkpoint_across_process_counts(tmp_path):
    """The cross-process-count resume: a checkpoint written as 2 offset-
    tagged half-blocks of a GROWN store (the 2-process layout) restores
    into a 1-process full template, and a full checkpoint slices down to
    either half — original rows bitwise in every direction."""
    n, n_new = 8, 8
    store = ReplicatedStore()
    rng = np.random.default_rng(0)
    leaf = _touch(store, store.init_leaf(n), rng, n)
    _, grown = store.grow(leaf, n_new)
    g = {"s": np.asarray(grown.s), "w": np.asarray(grown.w),
         "seen": np.asarray(grown.seen)}
    n_tot = n + n_new

    # write the grown state in the 2-process cluster layout: process 0's
    # blocks via save(), process 1's as arrays.part1.npz (what
    # _write_cluster produces on a real 2-process run)
    ck = Checkpointer(tmp_path)
    half = n_tot // 2
    part0 = {"prefixes": ("scores/",), "offset": 0, "n_global": n_tot}
    low = dataclasses.replace(grown,
                              s=jnp.asarray(g["s"][:half]),
                              w=jnp.asarray(g["w"][:half]),
                              seen=jnp.asarray(g["seen"][:half]))
    ck.save({"scores": low}, step=1, partition=part0)
    np.savez(ck.step_dir(1) / "arrays.part1.npz",
             **{f"scores/{k}#{half:012d}": g[k][half:]
                for k in ("s", "w", "seen")})
    assert "scores/s#000000000000" in ck.manifest(1)["leaves"]

    # 1-process (full) template reassembles the blocks
    r = ck.restore({"scores": store.init_leaf(n_tot)}, step=1)
    _assert_scores_equal(r["scores"], grown)
    # ... and the original-row prefix is bitwise the pre-grow state
    np.testing.assert_array_equal(np.asarray(r["scores"].s)[:n],
                                  np.asarray(leaf.s))

    # a full checkpoint slices down to either half-template
    ck2 = Checkpointer(tmp_path / "full")
    ck2.save({"scores": grown}, step=2)
    for rank in (0, 1):
        lo, hi = rank * n_tot // 2, (rank + 1) * n_tot // 2
        part = {"prefixes": ("scores/",), "offset": lo, "n_global": n_tot}
        tmpl = dataclasses.replace(grown,
                                   s=jnp.zeros(hi - lo, jnp.float32),
                                   w=jnp.zeros(hi - lo, jnp.float32),
                                   seen=jnp.zeros(hi - lo, jnp.int32))
        rr = ck2.restore({"scores": tmpl}, step=2, partition=part)
        np.testing.assert_array_equal(np.asarray(rr["scores"].s),
                                      g["s"][lo:hi])
