"""Checkpointer: roundtrip, async, atomicity, keep-K, restore semantics."""
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.scores import ScoreSharding, init_scores


def _state(seed=0):
    key = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(key, (8, 4)),
                   "b": jnp.zeros((4,))},
        "scores": init_scores(16),
        "step": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    state = _state()
    ck.save(state, step=7, metadata={"epoch": 1})
    restored = ck.restore(_state(seed=99), step=7)
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(state["params"]["w"]))
    np.testing.assert_allclose(np.asarray(restored["scores"].s),
                               np.asarray(state["scores"].s))
    assert int(restored["step"]) == 7
    assert ck.manifest(7)["metadata"]["epoch"] == 1


def test_async_save_and_wait(tmp_path):
    ck = Checkpointer(tmp_path)
    state = _state()
    ck.save_async(state, step=3)
    ck.wait()
    assert ck.latest_step() == 3
    restored = ck.restore(_state(seed=1), step=3)
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(state["params"]["w"]))


def test_keep_k_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(_state(s), step=s)
    assert ck.all_steps() == [3, 4]


def test_no_tmp_dirs_left_behind(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(_state(), step=1)
    assert not any(p.name.endswith(".tmp") for p in ck.dir.iterdir())


def test_restore_latest_by_default(tmp_path):
    ck = Checkpointer(tmp_path, keep=5)
    for s in (10, 20):
        ck.save(_state(s), step=s)
    restored = ck.restore(_state(0))
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(_state(20)["params"]["w"]))


def test_restore_casts_to_template_dtype(tmp_path):
    """Elastic/precision-change restore: leaves adopt the template dtype."""
    ck = Checkpointer(tmp_path)
    ck.save({"w": jnp.ones((4,), jnp.float32)}, step=1)
    template = {"w": jnp.zeros((4,), jnp.bfloat16)}
    restored = ck.restore(template, step=1)
    assert restored["w"].dtype == jnp.bfloat16


def _mesh1() -> ScoreSharding:
    """1-device ('data',) mesh: the sharded-restore API surface without a
    multi-device backend (8-device coverage: tests/test_sharded_scores)."""
    return ScoreSharding(Mesh(np.array(jax.devices()[:1]), ("data",)),
                         ("data",))


def test_restore_replicated_ckpt_into_sharded_template(tmp_path):
    """An older replicated checkpoint loads into a sharded-store config:
    restore reshards to the template's NamedSharding."""
    ck = Checkpointer(tmp_path)
    state = {"scores": init_scores(16), "step": jnp.asarray(3, jnp.int32)}
    ck.save(state, step=3)
    ss = _mesh1()
    restored = ck.restore({"scores": init_scores(16, ss),
                           "step": jnp.asarray(0, jnp.int32)}, step=3)
    np.testing.assert_array_equal(np.asarray(restored["scores"].s),
                                  np.asarray(state["scores"].s))
    assert restored["scores"].s.sharding.is_equivalent_to(
        ss.named_sharding(), 1)


def test_restore_sharded_ckpt_into_replicated_template(tmp_path):
    """...and vice versa: a sharded-store checkpoint loads into a
    replicated config, manifest carrying the original mesh/spec."""
    ck = Checkpointer(tmp_path)
    ss = _mesh1()
    sharded = init_scores(16, ss)
    ck.save({"scores": sharded}, step=1)
    md = ck.manifest(1)["leaves"]["scores/s"]
    # JAX >= 0.8 normalizes P(("data",)) to P("data")
    assert md["sharding"] == {"spec": ["data"], "mesh": {"data": 1}}
    restored = ck.restore({"scores": init_scores(16)}, step=1)
    np.testing.assert_array_equal(np.asarray(restored["scores"].w),
                                  np.asarray(sharded.w))
    assert getattr(restored["scores"].s.sharding, "mesh", None) is None \
        or restored["scores"].s.sharding.is_fully_replicated


def test_restore_missing_score_leaf_keeps_sharded_template_init(tmp_path):
    """A checkpoint written before a (sharded) leaf existed restores
    cleanly: the absent leaf keeps the template init AND its sharding."""
    ck = Checkpointer(tmp_path)
    ck.save({"scores": {"s": jnp.ones((16,), jnp.float32)}}, step=1)
    ss = _mesh1()
    full = init_scores(16, ss)
    template = {"scores": {"s": full.s, "seen": full.seen}}
    restored = ck.restore(template, step=1)
    np.testing.assert_array_equal(np.asarray(restored["scores"]["s"]),
                                  np.ones(16, np.float32))
    np.testing.assert_array_equal(np.asarray(restored["scores"]["seen"]),
                                  np.zeros(16, np.int32))   # template init
    assert restored["scores"]["seen"].sharding.is_equivalent_to(
        ss.named_sharding(), 1)


def test_partitioned_block_save_and_cross_slice_restore(tmp_path):
    """The multi-host block format, exercised without a cluster: leaves
    under a partitioned prefix are stored as offset-tagged row blocks and
    restore reassembles them — or slices a full checkpoint down to a
    partitioned template's row range.  (The real 2-process round-trip
    lives in tests/test_multihost.py.)"""
    ck = Checkpointer(tmp_path)
    full = np.arange(16, dtype=np.float32)
    # a "process 1 of 2" view: rows [8, 16) only
    part = {"prefixes": ("scores/",), "offset": 8, "n_global": 16}
    ck.save({"scores": {"s": jnp.asarray(full[8:])},
             "step": jnp.asarray(3, jnp.int32)}, step=1, partition=part)
    leaves = ck.manifest(1)["leaves"]
    assert "scores/s#000000000008" in leaves          # block-keyed
    assert "step" in leaves                           # unpartitioned leaf

    # partitioned template restores its own block back
    r = ck.restore({"scores": {"s": jnp.zeros(8, jnp.float32)},
                    "step": jnp.asarray(0, jnp.int32)},
                   step=1, partition=part)
    np.testing.assert_array_equal(np.asarray(r["scores"]["s"]), full[8:])
    assert int(r["step"]) == 3

    # a full (replicated) checkpoint slices down to a partitioned template
    ck2 = Checkpointer(tmp_path / "full")
    ck2.save({"scores": {"s": jnp.asarray(full)}}, step=2)
    r2 = ck2.restore({"scores": {"s": jnp.zeros(8, jnp.float32)}},
                     step=2, partition=part)
    np.testing.assert_array_equal(np.asarray(r2["scores"]["s"]), full[8:])


def test_overwrite_same_step_is_atomic(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(_state(1), step=5)
    ck.save(_state(2), step=5)
    restored = ck.restore(_state(0), step=5)
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(_state(2)["params"]["w"]))
