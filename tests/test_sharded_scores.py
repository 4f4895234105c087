"""Sharded ES score store: multi-device parity harness (ISSUE 3 tentpole).

Contracts:
  * with a ``ScoreSharding`` over the 8-device CPU mesh, each device
    materializes only n/8 score rows (asserted via sharding specs and
    per-device shard shapes);
  * the routed gather/scatter ops, Gumbel selection, and the whole k=1
    engine step match the replicated path bit-close (fp32 tolerance);
  * set-level pruning kept-sets computed from device-local shards equal
    the replicated kept-sets (incl. the InfoBatch grad rescale);
  * sharded score leaves checkpoint round-trip, including restore onto a
    DIFFERENT mesh shape and onto a replicated template (and vice versa).

The ``cpu_mesh8``-gated tests run in-process when the suite is launched
with ``REPRO_CPU_DEVICES=8`` (the CI multi-device job); the subprocess
tests cover the same paths on plain 1-device tier-1 runs.
"""
import textwrap

import numpy as np
import pytest
from conftest import run_multidevice

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.pruning import (PruneSnapshot, prune_epoch,  # noqa: E402
                                prune_epoch_snapshot)
from repro.core.scores import (ReplicatedStore, ScoreSharding,  # noqa: E402
                               ShardedStore, init_scores, update_scores)

# the replicated reference, compiled like the sharded ops it anchors
# (compiled XLA contracts multiply-adds, op-by-op dispatch does not)
_REF = ReplicatedStore()


def _ss(mesh) -> ScoreSharding:
    return ScoreSharding(mesh, ("data",))


def _store(mesh) -> ShardedStore:
    return ShardedStore(_ss(mesh))


def _snap(w_blocks, l_blocks, seen_blocks=None) -> PruneSnapshot:
    """A PruneSnapshot over explicit row blocks (what
    ``ShardedStore.prune_snapshot`` assembles from addressable shards)."""
    lens = [len(b) for b in w_blocks]
    offs = np.concatenate([[0], np.cumsum(lens)])[:-1].astype(np.int64)
    return PruneSnapshot(
        weights=list(w_blocks), losses=list(l_blocks),
        seen=None if seen_blocks is None else list(seen_blocks),
        offsets=offs, n=int(sum(lens)))


# ---------------------------------------------------------------------------
# sharding specs: each device holds only n/8 score rows
# ---------------------------------------------------------------------------

def test_init_scores_sharded_specs(cpu_mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P
    ss = _ss(cpu_mesh8)
    n = 64
    scores = init_scores(n, ss)
    want = NamedSharding(cpu_mesh8, P(("data",)))
    for leaf in (scores.s, scores.w, scores.seen):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
        shards = leaf.addressable_shards
        assert len(shards) == 8
        for sh in shards:
            assert sh.data.shape == (n // 8,)   # n/8 rows per device

    with pytest.raises(ValueError):
        init_scores(n + 1, ss)                  # indivisible store


def test_update_and_gather_bit_parity(cpu_mesh8):
    ss = _ss(cpu_mesh8)
    store = _store(cpu_mesh8)
    n, B = 64, 16
    rep, shd = init_scores(n), init_scores(n, ss)
    rng = np.random.default_rng(0)
    for _ in range(6):
        ids = jnp.asarray(rng.choice(n, B, replace=False), jnp.int32)
        losses = jnp.asarray(rng.uniform(0.1, 3.0, B), jnp.float32)
        s_g, w_g = store.gather(shd, ids)
        np.testing.assert_array_equal(np.asarray(s_g),
                                      np.asarray(rep.s[ids]))
        np.testing.assert_array_equal(np.asarray(w_g),
                                      np.asarray(rep.w[ids]))
        rep = _REF.update(rep, ids, losses, 0.2, 0.9)
        shd = store.update(shd, ids, losses, 0.2, 0.9)
    for a, b in ((shd.s, rep.s), (shd.w, rep.w), (shd.seen, rep.seen)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    from jax.sharding import NamedSharding, PartitionSpec as P
    assert shd.s.sharding.is_equivalent_to(
        NamedSharding(cpu_mesh8, P(("data",))), 1)


def test_fused_ops_dispatch_per_shard(cpu_mesh8):
    """kernels/score_update/ops.py with a ScoreSharding: off-TPU it must
    route through the masked sharded scatter and stay bit-equal."""
    from repro.kernels.score_update.ops import update_scores_fused
    ss = _ss(cpu_mesh8)
    n, B = 64, 16
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.choice(n, B, replace=False), jnp.int32)
    losses = jnp.asarray(rng.uniform(0.1, 3.0, B), jnp.float32)
    rep = _REF.update(init_scores(n), ids, losses, 0.2, 0.9)
    shd = update_scores_fused(init_scores(n, ss), ids, losses, 0.2, 0.9,
                              sharding=ss)
    np.testing.assert_array_equal(np.asarray(shd.s), np.asarray(rep.s))
    np.testing.assert_array_equal(np.asarray(shd.seen), np.asarray(rep.seen))
    assert len(shd.s.addressable_shards) == 8


def test_scores_logical_axis_and_store_sharding_builder(cpu_mesh8):
    """distributed/sharding: the ``scores`` logical axis maps to the DP
    axes, ``score_store_sharding`` builds the trainer's ScoreSharding from
    a mesh, and ``abstract_train_state(shard_scores=True)`` emits the
    row-sharded specs for the three score leaves."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.configs.registry import get_smoke_config
    from repro.core.engine import ESConfig
    from repro.distributed.sharding import (make_ctx, make_rules,
                                            score_store_sharding)
    from repro.launch.inputs import abstract_train_state
    from repro.optim.adamw import OptConfig

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                ("data", "model"))
    cfg = get_smoke_config("qwen1.5-0.5b")
    assert dict(make_rules(cfg, mesh))["scores"] == ("data",)

    ss = score_store_sharding(mesh)
    assert ss.axes == ("data",) and ss.n_shards == 4
    assert score_store_sharding(
        Mesh(np.array(jax.devices()[:8]).reshape(1, 8),
             ("data", "model"))) is None    # no DP extent: stay replicated

    ctx = make_ctx(cfg, mesh, "train")
    _, sh = abstract_train_state(cfg, ESConfig(n_train=64, seq_chunk=0),
                                 OptConfig(), 16, ctx, shard_scores=True)
    for leaf in (sh.scores.s, sh.scores.w, sh.scores.seen):
        assert leaf.spec == P(("data",))
    assert sh.pending_w.spec == P()         # batch weights stay replicated


def test_sharded_gumbel_topk_matches_replicated(cpu_mesh8):
    store = _store(cpu_mesh8)
    rng = np.random.default_rng(2)
    for trial in range(4):
        w = jnp.asarray(rng.uniform(0.01, 5.0, 32), jnp.float32)
        key = jax.random.PRNGKey(trial)
        np.testing.assert_array_equal(
            np.asarray(_REF.select(key, w, 6)),
            np.asarray(store.select(key, w, 6)))


# ---------------------------------------------------------------------------
# masked fused kernel (interpret mode): negative id = dropped
# ---------------------------------------------------------------------------

def test_masked_kernel_skips_negative_ids():
    from repro.kernels.score_update.score_update import fused_score_update
    n = 16
    scores = init_scores(n)
    ids = jnp.asarray([2, -1, 5, -1], jnp.int32)
    losses = jnp.asarray([1.0, 9.0, 2.0, 9.0], jnp.float32)
    s, w, seen = fused_score_update(scores.s, scores.w, scores.seen, ids,
                                    losses, beta1=0.2, beta2=0.9,
                                    interpret=True, masked=True)
    ref = update_scores(scores, jnp.asarray([2, 5], jnp.int32),
                        jnp.asarray([1.0, 2.0], jnp.float32), 0.2, 0.9)
    np.testing.assert_allclose(np.asarray(s), np.asarray(ref.s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref.w), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(seen), np.asarray(ref.seen))


# ---------------------------------------------------------------------------
# engine: sharded-store k=1 training == replicated path (fp32 tolerance)
# ---------------------------------------------------------------------------

def test_engine_sharded_k1_matches_replicated(cpu_mesh8):
    from conftest import smoke_engine_setup
    from repro.core.engine import ESEngine, init_train_state
    ss = _ss(cpu_mesh8)
    eng_r, s_r, batches = smoke_engine_setup(n=128, meta_batch=16,
                                             minibatch=4)
    eng_s = ESEngine(eng_r.model_cfg, eng_r.es_cfg, eng_r.opt_cfg,
                     eng_r.schedule, eng_r.ctx, score_sharding=ss)
    s_s = init_train_state(eng_r.model_cfg, eng_r.es_cfg, eng_r.opt_cfg,
                           jax.random.PRNGKey(0), 16, score_sharding=ss)
    step_r, step_s = jax.jit(eng_r.es_step), jax.jit(eng_s.es_step)
    for i in range(6):
        b = batches[i % len(batches)]
        s_r, m_r = step_r(s_r, b)
        s_s, m_s = step_s(s_s, b)
        for k in ("loss", "sel_loss", "w_mean", "w_max"):  # selection parity
            np.testing.assert_allclose(float(m_r[k]), float(m_s[k]),
                                       rtol=1e-6)
    # the store never left its shards
    assert len(s_s.scores.s.addressable_shards) == 8
    np.testing.assert_allclose(np.asarray(s_s.scores.s),
                               np.asarray(s_r.scores.s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_s.scores.w),
                               np.asarray(s_r.scores.w), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s_s.scores.seen),
                                  np.asarray(s_r.scores.seen))
    for x, y in zip(jax.tree.leaves(s_r.params),
                    jax.tree.leaves(s_s.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_engine_sharded_decimated_and_pipelined_parity(cpu_mesh8):
    """The sharded store composes with the other scoring policies: the
    decimated ``lax.cond`` carries the routed shard_map ops in BOTH
    branches, and the pipelined prime/carry/flush protocol matches the
    replicated trajectory."""
    from conftest import smoke_engine_setup
    from repro.core.engine import ESEngine, init_train_state
    from repro.core.frequency import FreqSchedule
    ss = _ss(cpu_mesh8)
    freq = FreqSchedule(kind="fixed", k=2)
    eng_r, s_r, batches = smoke_engine_setup(n=64, meta_batch=16,
                                             minibatch=4, freq=freq)
    eng_s = ESEngine(eng_r.model_cfg, eng_r.es_cfg, eng_r.opt_cfg,
                     eng_r.schedule, eng_r.ctx, freq=freq,
                     score_sharding=ss)

    def fresh(sharding=None):
        return init_train_state(eng_r.model_cfg, eng_r.es_cfg,
                                eng_r.opt_cfg, jax.random.PRNGKey(0), 16,
                                score_sharding=sharding)

    s_r, s_s = fresh(), fresh(ss)
    sched_r = jax.jit(eng_r.scheduled_step)
    sched_s = jax.jit(eng_s.scheduled_step)
    for i in range(4):
        b = batches[i % len(batches)]
        s_r, m_r = sched_r(s_r, b)
        s_s, m_s = sched_s(s_s, b)
        assert float(m_r["scored"]) == float(m_s["scored"])
        np.testing.assert_allclose(float(m_r["loss"]), float(m_s["loss"]),
                                   rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_s.scores.s),
                               np.asarray(s_r.scores.s), rtol=1e-6)

    s_r, s_s = fresh(), fresh(ss)
    sess_r, sess_s = eng_r.session(True, True), eng_s.session(True, True)
    for b in batches:
        s_r, _ = sess_r.step(s_r, b)
        s_s, _ = sess_s.step(s_s, b)
    s_r, _ = sess_r.finish(s_r)
    s_s, _ = sess_s.finish(s_s)
    np.testing.assert_allclose(np.asarray(s_s.scores.s),
                               np.asarray(s_r.scores.s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_s.scores.w),
                               np.asarray(s_r.scores.w), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s_s.scores.seen),
                                  np.asarray(s_r.scores.seen))


# ---------------------------------------------------------------------------
# pruning kept-sets from device-local shards (host-side: runs everywhere)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["eswp", "infobatch", "ucb", "ka",
                                    "random", "none"])
def test_prune_from_shards_matches_replicated(method):
    rng = np.random.default_rng
    n = 96
    w = rng(3).uniform(0.01, 2.0, n).astype(np.float32)
    losses = rng(4).uniform(0.05, 3.0, n).astype(np.float32)
    prev = rng(5).uniform(0.05, 3.0, n).astype(np.float32)
    seen = rng(6).integers(1, 9, n)
    a = prune_epoch(method, rng(42), weights=w, losses=losses,
                    prev_losses=prev, seen=seen, ratio=0.25)
    b = prune_epoch_snapshot(
        method, rng(42),
        _snap(np.split(w, 8), np.split(losses, 8), np.split(seen, 8)),
        prev_losses=prev, ratio=0.25)
    np.testing.assert_array_equal(np.sort(a.kept), np.sort(b.kept))
    if a.grad_scale is None:
        assert b.grad_scale is None
    else:
        np.testing.assert_array_equal(a.grad_scale, b.grad_scale)


def test_infobatch_shard_mean_unbiased():
    """The kept-set statistic (global mean) from shard sums is exact, so
    the 1/(1-r) rescale stays unbiased regardless of the shard layout."""
    n = 128
    losses = np.random.default_rng(7).uniform(0.0, 4.0, n).astype(np.float32)
    for d in (2, 4, 8):
        res = prune_epoch_snapshot(
            "infobatch", np.random.default_rng(0),
            _snap(np.split(losses, d), np.split(losses, d)), ratio=0.25)
        kept_scale = res.grad_scale[res.kept]
        # E[scale * kept] reconstructs the full-set mean gradient weight
        assert abs(float(kept_scale.sum()) - n) / n < 0.1


# ---------------------------------------------------------------------------
# checkpoint: sharded leaves round-trip + cross-mesh restore
# ---------------------------------------------------------------------------

def test_checkpoint_sharded_roundtrip_and_cross_mesh(cpu_mesh8, tmp_path):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.checkpoint.checkpointer import Checkpointer
    ss = _ss(cpu_mesh8)
    n = 64
    scores = update_scores(init_scores(n, ss),
                           jnp.arange(16, dtype=jnp.int32),
                           jnp.linspace(0.1, 2.0, 16), 0.2, 0.9)
    ck = Checkpointer(tmp_path)
    ck.save({"scores": scores}, step=1)
    # manifest records the mesh/spec of each sharded leaf
    leaves = ck.manifest(1)["leaves"]
    assert leaves["scores/s"]["sharding"]["mesh"] == {"data": 8}

    # restore onto the SAME mesh shape
    r8 = ck.restore({"scores": init_scores(n, ss)}, step=1)
    np.testing.assert_array_equal(np.asarray(r8["scores"].s),
                                  np.asarray(scores.s))
    assert len(r8["scores"].s.addressable_shards) == 8

    # restore onto a DIFFERENT mesh shape (8-way checkpoint -> 4-way mesh)
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("data",))
    ss4 = ScoreSharding(mesh4, ("data",))
    r4 = ck.restore({"scores": init_scores(n, ss4)}, step=1)
    np.testing.assert_array_equal(np.asarray(r4["scores"].s),
                                  np.asarray(scores.s))
    assert r4["scores"].s.sharding.is_equivalent_to(
        NamedSharding(mesh4, P(("data",))), 1)
    assert len(r4["scores"].s.addressable_shards) == 4

    # sharded checkpoint -> replicated template (and back)
    rr = ck.restore({"scores": init_scores(n)}, step=1)
    np.testing.assert_array_equal(np.asarray(rr["scores"].w),
                                  np.asarray(scores.w))
    ck.save({"scores": rr["scores"]}, step=2)
    assert "sharding" not in ck.manifest(2)["leaves"]["scores/s"]
    r_back = ck.restore({"scores": init_scores(n, ss)}, step=2)
    np.testing.assert_array_equal(np.asarray(r_back["scores"].s),
                                  np.asarray(scores.s))
    assert len(r_back["scores"].s.addressable_shards) == 8


# ---------------------------------------------------------------------------
# subprocess harness: the same contracts on plain 1-device tier-1 runs
# ---------------------------------------------------------------------------

def test_multidevice_parity_subprocess():
    """End-to-end on 8 forced CPU devices: shard specs, engine k=1 parity
    vs replicated, checkpoint round-trip across mesh shapes."""
    code = textwrap.dedent("""
        import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from conftest import smoke_engine_setup
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.core.engine import ESEngine, init_train_state
        from repro.core.scores import ScoreSharding, init_scores

        assert jax.device_count() == 8, jax.devices()
        mesh = jax.make_mesh((8,), ("data",))
        ss = ScoreSharding(mesh, ("data",))

        eng_r, s_r, batches = smoke_engine_setup(n=64, meta_batch=16,
                                                 minibatch=4)
        eng_s = ESEngine(eng_r.model_cfg, eng_r.es_cfg, eng_r.opt_cfg,
                         eng_r.schedule, eng_r.ctx, score_sharding=ss)
        s_s = init_train_state(eng_r.model_cfg, eng_r.es_cfg, eng_r.opt_cfg,
                               jax.random.PRNGKey(0), 16, score_sharding=ss)
        # each device materializes only n/8 = 8 score rows
        for leaf in (s_s.scores.s, s_s.scores.w, s_s.scores.seen):
            shards = leaf.addressable_shards
            assert len(shards) == 8 and shards[0].data.shape == (8,), shards
        step_r, step_s = jax.jit(eng_r.es_step), jax.jit(eng_s.es_step)
        for i in range(4):
            b = batches[i % len(batches)]
            s_r, m_r = step_r(s_r, b)
            s_s, m_s = step_s(s_s, b)
            for k in ("loss", "sel_loss", "w_mean", "w_max"):
                np.testing.assert_allclose(float(m_r[k]), float(m_s[k]),
                                           rtol=1e-6)
        assert len(s_s.scores.s.addressable_shards) == 8
        np.testing.assert_allclose(np.asarray(s_s.scores.s),
                                   np.asarray(s_r.scores.s), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s_s.scores.w),
                                   np.asarray(s_r.scores.w), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(s_s.scores.seen),
                                      np.asarray(s_r.scores.seen))
        for x, y in zip(jax.tree.leaves(s_r.params),
                        jax.tree.leaves(s_s.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6)

        # checkpoint round-trip: 8-way save -> 4-way and replicated restore
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save({"scores": s_s.scores}, step=1)
            assert ck.manifest(1)["leaves"]["scores/s"]["sharding"][
                "mesh"] == {"data": 8}
            mesh4 = Mesh(np.array(jax.devices()[:4]), ("data",))
            r4 = ck.restore({"scores": init_scores(
                64, ScoreSharding(mesh4, ("data",)))}, step=1)
            np.testing.assert_array_equal(np.asarray(r4["scores"].s),
                                          np.asarray(s_s.scores.s))
            assert len(r4["scores"].s.addressable_shards) == 4
            rr = ck.restore({"scores": init_scores(64)}, step=1)
            np.testing.assert_array_equal(np.asarray(rr["scores"].w),
                                          np.asarray(s_s.scores.w))
        print("OK")
    """)
    run_multidevice(code)


def test_trainer_shard_scores_flag_subprocess():
    """--shard-scores end to end: sharded ESWP training with per-shard
    pruning matches the replicated trainer's full trajectory."""
    code = textwrap.dedent("""
        import sys; sys.path.insert(0, "src")
        import numpy as np
        from repro.launch.train import Trainer, TrainerConfig

        kw = dict(arch="qwen1.5-0.5b", method="eswp", epochs=2,
                  meta_batch=16, minibatch=4, n_samples=64, seq_len=32,
                  anneal_ratio=0.0, lr=3e-3)
        tr_s = Trainer(TrainerConfig(shard_scores=True, **kw))
        assert tr_s.score_sharding is not None
        out_s = tr_s.train()
        assert out_s["score_store_sharded"]
        tr_r = Trainer(TrainerConfig(**kw))
        out_r = tr_r.train()
        assert out_s["steps"] == out_r["steps"]
        for m_s, m_r in zip(out_s["metrics"], out_r["metrics"]):
            np.testing.assert_allclose(m_s["loss"], m_r["loss"], rtol=1e-4)
        # kept-sets from device-local shards == replicated kept-sets
        np.testing.assert_array_equal(tr_s.loader._kept, tr_r.loader._kept)
        assert all("epochs_since_prune" in m for m in out_s["metrics"])
        print("OK")
    """)
    run_multidevice(code)


# ---------------------------------------------------------------------------
# eager store ops compile once per shape
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "int8"])
def test_sharded_store_ops_compile_once(quantize):
    """Called eagerly three times with one shape, each sharded store op
    compiles on the first call only (counted on JAX's backend-compile
    event), so a service that updates the store between steps pays no
    compile per call."""
    from repro.core.scores import make_store
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    store = make_store(_ss(mesh), quantize=quantize, block=8,
                       residual_rows=64)
    n = 16 * jax.device_count()
    leaf = store.init_leaf(n)
    ids = jnp.asarray([1, 5, 5, n + 2, -1, 3], jnp.int32)
    gather_ids = jnp.asarray([1, 5, 3, n - 1], jnp.int32)
    losses = jnp.linspace(0.1, 2.0, 6, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    compiles = []

    def on_event(event, secs, **kwargs):
        if event == _COMPILE_EVENT:
            compiles.append(secs)

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    counts = {"update": [], "gather": [], "select": []}
    try:
        for _ in range(3):
            before = len(compiles)
            leaf = jax.block_until_ready(
                store.update(leaf, ids, losses, 0.2, 0.9))
            counts["update"].append(len(compiles) - before)
            before = len(compiles)
            jax.block_until_ready(store.gather(leaf, gather_ids))
            counts["gather"].append(len(compiles) - before)
            before = len(compiles)
            jax.block_until_ready(store.select(key, losses, 3))
            counts["select"].append(len(compiles) - before)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert counts == {op: [1, 0, 0] for op in counts}, counts
