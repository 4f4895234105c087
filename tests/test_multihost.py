"""Multi-host ScoreStore parity (ISSUE 5 tentpole).

A real 2-process ``jax.distributed`` CPU cluster (``run_cluster``: own
interpreters, coordinator, KV-store host collectives) drives the
``ShardedStore`` in per-process row-ownership mode — each process's
arrays hold only its n/P rows over its local 4-device mesh — and must be
BIT-IDENTICAL to the single-process 8-device mesh run on the same seed:

  * score stores: each process's rows equal the replicated reference's
    row range; the allgathered union digests equal to the 8-device run;
  * gathers: the in-jit local psum completed by the host collective
    equals the replicated direct load;
  * selections: identical indices (the per-process weights are already
    complete, and the candidate-merge form is bit-equal by construction);
  * kept-sets: ``prune_snapshot`` sees only host-local addressable shards
    and every method's global stats come from allreduced candidate lists
    / f64 sums — kept ids, grad rescale and the s-snapshot all match;
  * checkpoints: the 2-process partitioned manifest restores onto 1
    process (replicated and 8-device sharded templates), and a
    single-process checkpoint restores into the 2-process run.

The shared id/loss stream is seeded, so the parent compares digests
across topologies without moving arrays between them.  The replicated
reference is the compiled ``ReplicatedStore``, like the backends it
anchors: compiled XLA contracts multiply-adds, op-by-op dispatch does
not, so only like-compiled sides can be bit-compared.
"""
import textwrap

import numpy as np
import pytest
from conftest import run_cluster, run_multidevice

jax = pytest.importorskip("jax")

# the seeded workload every topology replays: 5 update/gather rounds on a
# 64-row store, one selection, every pruning method, digest of the result
_WORKLOAD = textwrap.dedent("""
    import hashlib
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.pruning import prune_epoch
    from repro.core.scores import ReplicatedStore, init_scores

    N, B, T = 64, 16, 5
    REF = ReplicatedStore()    # the reference, compiled like the store ops

    def stream():
        rng = np.random.default_rng(0)
        for _ in range(T):
            ids = rng.choice(N, B, replace=False)
            losses = rng.uniform(0.1, 3.0, B).astype(np.float32)
            yield (jnp.asarray(ids, jnp.int32), jnp.asarray(losses))

    def prev_losses():
        return np.random.default_rng(1).uniform(
            0.05, 3.0, N).astype(np.float32)

    def digest(*arrays):
        h = hashlib.sha1()
        for a in arrays:
            h.update(np.ascontiguousarray(np.asarray(a)))
        return h.hexdigest()[:16]

    def run_workload(store):
        ref = init_scores(N)
        scores = store.init_leaf(N)
        for ids, losses in stream():
            s_g, w_g = store.gather(scores, ids)
            np.testing.assert_array_equal(np.asarray(s_g),
                                          np.asarray(ref.s[ids]))
            np.testing.assert_array_equal(np.asarray(w_g),
                                          np.asarray(ref.w[ids]))
            scores = store.update(scores, ids, losses, 0.2, 0.9)
            ref = REF.update(ref, ids, losses, 0.2, 0.9)
        key = jax.random.PRNGKey(7)
        wsel = store.gather(scores, jnp.arange(B, dtype=jnp.int32))[1]
        sel = store.select(key, wsel, 6)
        np.testing.assert_array_equal(
            np.asarray(sel), np.asarray(REF.select(key, wsel, 6)))
        kept_digs = []
        prev = prev_losses()
        for method in ("eswp", "infobatch", "ucb", "ka", "random"):
            res, s_full = store.prune_epoch(
                method, np.random.default_rng(3), scores,
                prev_losses=prev, ratio=0.25)
            ref_res = prune_epoch(
                method, np.random.default_rng(3),
                weights=np.asarray(ref.w), losses=np.asarray(ref.s),
                prev_losses=prev, seen=np.asarray(ref.seen), ratio=0.25)
            np.testing.assert_array_equal(np.sort(res.kept),
                                          np.sort(ref_res.kept))
            np.testing.assert_array_equal(s_full, np.asarray(ref.s))
            if ref_res.grad_scale is not None:
                np.testing.assert_array_equal(res.grad_scale,
                                              ref_res.grad_scale)
            kept_digs.append(digest(np.sort(res.kept)))
        return ref, scores, sel, kept_digs
""")


def _parse(line_tag, out):
    for line in out.splitlines():
        if line.startswith(line_tag + " "):
            return line[len(line_tag) + 1:].strip()
    raise AssertionError(f"no {line_tag!r} line in:\n{out}")


def _single_process_digests():
    """The 8-device single-process mesh run's digests (the anchor)."""
    code = _WORKLOAD + textwrap.dedent("""
        from jax.sharding import Mesh
        from repro.core.scores import ScoreSharding, ShardedStore
        assert jax.device_count() == 8
        mesh = Mesh(np.array(jax.devices()), ("data",))
        store = ShardedStore(ScoreSharding(mesh, ("data",)))
        ref, scores, sel, kept_digs = run_workload(store)
        print("STORE", digest(scores.s, scores.w, scores.seen))
        print("SEL", digest(sel))
        print("KEPT", ",".join(kept_digs))
        print("OK")
    """)
    r = run_multidevice(code)
    return (_parse("STORE", r.stdout), _parse("SEL", r.stdout),
            _parse("KEPT", r.stdout))


_CLUSTER_STORE = textwrap.dedent("""
    from jax.sharding import Mesh
    from repro.core.scores import ScoreSharding, ShardedStore
    from repro.distributed.hostcomm import get_comm

    P, pid = jax.process_count(), jax.process_index()
    assert P == 2 and jax.local_device_count() == 4
    comm = get_comm()
    assert comm is not None and comm.process_count == 2
    n_local = N // P
    mesh = Mesh(np.array(jax.local_devices()), ("data",))
    store = ShardedStore(ScoreSharding(mesh, ("data",), n_global=N,
                                       offset=pid * n_local))
    store.validate(N)
""")


def test_cluster_matches_single_process_8dev_bitwise():
    """The acceptance anchor: 2-process CPU-cluster score stores,
    selections and kept-sets == the single-process 8-device mesh run."""
    store_d, sel_d, kept_d = _single_process_digests()
    code = _WORKLOAD + _CLUSTER_STORE + textwrap.dedent("""
        ref, scores, sel, kept_digs = run_workload(store)
        # per-process rows == the reference's row range (run_workload
        # already pinned gathers/selection/prunes to the reference)
        lo = pid * n_local
        np.testing.assert_array_equal(np.asarray(scores.s),
                                      np.asarray(ref.s)[lo:lo + n_local])
        np.testing.assert_array_equal(np.asarray(scores.seen),
                                      np.asarray(ref.seen)[lo:lo + n_local])
        # each device holds only n/8 global rows
        assert len(scores.s.addressable_shards) == 4
        assert scores.s.addressable_shards[0].data.shape == (N // 8,)
        # the allgathered union is THE global store: digest it like the
        # single-process topology digests its device arrays
        gs = np.concatenate(comm.allgather(np.asarray(scores.s)))
        gw = np.concatenate(comm.allgather(np.asarray(scores.w)))
        gseen = np.concatenate(comm.allgather(np.asarray(scores.seen)))
        print("STORE", digest(gs, gw, gseen))
        print("SEL", digest(sel))
        print("KEPT", ",".join(kept_digs))
        print("OK")
    """)
    outs = run_cluster(code)
    for out in outs:
        assert _parse("STORE", out) == store_d
        assert _parse("SEL", out) == sel_d
        assert _parse("KEPT", out) == kept_d


def test_cluster_checkpoint_restores_across_process_counts(tmp_path):
    """2-process partitioned manifest -> 1-process restore (replicated
    AND 8-device sharded templates), and 1-process checkpoint ->
    2-process partitioned restore."""
    import jax.numpy as jnp
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.core.scores import ReplicatedStore, init_scores

    # the single-process truth of the same workload
    def run_ref():
        ref = init_scores(64)
        rng = np.random.default_rng(0)
        for _ in range(5):
            ids = jnp.asarray(rng.choice(64, 16, replace=False), jnp.int32)
            losses = jnp.asarray(rng.uniform(0.1, 3.0, 16), jnp.float32)
            ref = ReplicatedStore().update(ref, ids, losses, 0.2, 0.9)
        return ref

    # 1) replicated single-process checkpoint for the cluster to restore
    ck = Checkpointer(tmp_path / "from_single")
    ck.save({"scores": run_ref()}, step=1)

    code = _WORKLOAD + _CLUSTER_STORE + textwrap.dedent("""
        import os
        from repro.checkpoint.checkpointer import Checkpointer
        ref, scores, sel, kept_digs = run_workload(store)
        part = store.checkpoint_partition()
        assert part is not None and part["n_global"] == N
        spec = store.checkpoint_spec()
        assert spec["process_count"] == 2

        # 2-process partitioned save: block entries + union manifest
        ck = Checkpointer(os.environ["REPRO_CKPT_TO"])
        ck.save({"scores": scores}, step=7,
                metadata={"probe": pid}, partition=part)
        # ...restores back into THIS topology
        r = ck.restore({"scores": store.init_leaf(N)}, step=7,
                       partition=part)
        np.testing.assert_array_equal(np.asarray(r["scores"].s),
                                      np.asarray(scores.s))

        # single-process replicated checkpoint -> partitioned restore
        ck1 = Checkpointer(os.environ["REPRO_CKPT_FROM"])
        r1 = ck1.restore({"scores": store.init_leaf(N)}, step=1,
                         partition=part)
        lo = pid * n_local
        np.testing.assert_array_equal(np.asarray(r1["scores"].s),
                                      np.asarray(ref.s)[lo:lo + n_local])
        print("OK")
    """)
    run_cluster(code, extra_env={
        "REPRO_CKPT_TO": str(tmp_path / "from_cluster"),
        "REPRO_CKPT_FROM": str(tmp_path / "from_single")})

    # 2) the 2-process manifest restores on ONE process
    ck2 = Checkpointer(tmp_path / "from_cluster")
    md = ck2.manifest(7)["metadata"]
    assert md["process_count"] == 2
    assert md["partitioned"]["n_global"] == 64
    leaves = ck2.manifest(7)["leaves"]
    assert any("#" in k for k in leaves), leaves.keys()
    ref = run_ref()
    # replicated template: blocks reassemble to the full store
    r = ck2.restore({"scores": init_scores(64)}, step=7)
    np.testing.assert_array_equal(np.asarray(r["scores"].s),
                                  np.asarray(ref.s))
    np.testing.assert_array_equal(np.asarray(r["scores"].seen),
                                  np.asarray(ref.seen))


def test_cluster_checkpoint_restores_onto_8dev_mesh(tmp_path):
    """2-process manifest -> single-process 8-device sharded template
    (the elastic pod-resize path), via the subprocess mesh harness."""
    code = _WORKLOAD + _CLUSTER_STORE + textwrap.dedent("""
        import os
        from repro.checkpoint.checkpointer import Checkpointer
        ref, scores, sel, kept_digs = run_workload(store)
        ck = Checkpointer(os.environ["REPRO_CKPT_DIR"])
        ck.save({"scores": scores}, step=3,
                partition=store.checkpoint_partition())
        print("OK")
    """)
    run_cluster(code, extra_env={"REPRO_CKPT_DIR": str(tmp_path)})
    code8 = _WORKLOAD + textwrap.dedent("""
        import os
        from jax.sharding import Mesh
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.core.scores import ScoreSharding, ShardedStore
        mesh = Mesh(np.array(jax.devices()), ("data",))
        store = ShardedStore(ScoreSharding(mesh, ("data",)))
        ref, scores, sel, kept_digs = run_workload(store)
        ck = Checkpointer(os.environ["REPRO_CKPT_DIR"])
        r = ck.restore({"scores": store.init_leaf(N)}, step=3)
        np.testing.assert_array_equal(np.asarray(r["scores"].s),
                                      np.asarray(scores.s))
        assert len(r["scores"].s.addressable_shards) == 8
        print("OK")
    """)
    import os
    env_saved = os.environ.get("REPRO_CKPT_DIR")
    os.environ["REPRO_CKPT_DIR"] = str(tmp_path)
    try:
        run_multidevice(code8)
    finally:
        if env_saved is None:
            os.environ.pop("REPRO_CKPT_DIR", None)
        else:
            os.environ["REPRO_CKPT_DIR"] = env_saved


# ---------------------------------------------------------------------------
# Quantized store (ISSUE 7): process-local int8 rows + compressed host legs
# ---------------------------------------------------------------------------

_QUANT_WORKLOAD = textwrap.dedent("""
    import hashlib
    import numpy as np
    import jax, jax.numpy as jnp

    N, B, T = 64, 16, 5
    QKW = dict(quantize=True, block=8, residual_rows=1024)

    def stream():
        rng = np.random.default_rng(0)
        for _ in range(T):
            ids = rng.choice(N, B, replace=False)
            losses = rng.uniform(0.1, 3.0, B).astype(np.float32)
            yield (jnp.asarray(ids, jnp.int32), jnp.asarray(losses))

    def digest(*arrays):
        h = hashlib.sha1()
        for a in arrays:
            h.update(np.ascontiguousarray(np.asarray(a)))
        return h.hexdigest()[:16]

    def run_quant(store):
        qs = store.init_leaf(N)
        gather_digs = []
        for ids, losses in stream():
            qs = store.update(qs, ids, losses, 0.2, 0.9)
            s_g, w_g = store.gather(qs, ids)
            gather_digs.append(digest(s_g, w_g))
        return qs, gather_digs
""")


def _quant_reference_digests():
    """Single-process replicated-quant digests + full losses (the anchor;
    the parent's 1-device backend runs it in-process)."""
    mod = {}
    exec(compile(_QUANT_WORKLOAD, "<quant_workload>", "exec"), mod)
    from repro.core.scores import make_store
    store = make_store(None, **mod["QKW"])
    qs, gather_digs = mod["run_quant"](store)
    codes_dig = mod["digest"](qs.s_q, qs.w_q, qs.seen_q,
                              qs.s_scale, qs.w_scale)
    losses_full = store.prune_snapshot(qs).full_losses()
    return qs, codes_dig, gather_digs, losses_full


def test_cluster_quantized_store_matches_single_process():
    """2-process per-process-rows QuantizedStore: int8 codes, scales,
    gathers and assembled prune losses all bit-equal the 1-process
    replicated-quant run (wire=False), and the int8-wire gather stays
    within one grid step."""
    _, codes_dig, gather_digs, losses_full = _quant_reference_digests()
    code = _QUANT_WORKLOAD + textwrap.dedent("""
        import dataclasses
        from jax.sharding import Mesh
        from repro.core.scores import ScoreSharding, make_store
        from repro.distributed.hostcomm import get_comm

        P, pid = jax.process_count(), jax.process_index()
        comm = get_comm()
        n_local = N // P
        mesh = Mesh(np.array(jax.local_devices()), ("data",))
        store = make_store(ScoreSharding(mesh, ("data",), n_global=N,
                                         offset=pid * n_local), **QKW)
        store.validate(N)
        qs, gather_digs = run_quant(store)
        gs = np.concatenate(comm.allgather(np.asarray(qs.s_q)))
        gw = np.concatenate(comm.allgather(np.asarray(qs.w_q)))
        gseen = np.concatenate(comm.allgather(np.asarray(qs.seen_q)))
        gss = np.concatenate(comm.allgather(np.asarray(qs.s_scale)))
        gws = np.concatenate(comm.allgather(np.asarray(qs.w_scale)))
        print("CODES", digest(gs, gw, gseen, gss, gws))
        print("GATHERS", ",".join(gather_digs))
        snap = store.prune_snapshot(qs)
        full = snap.full_losses()
        print("LOSSES", digest(full))
        # the int8 wire completion stays within one grid step of exact
        wired = dataclasses.replace(store, wire=True)
        ids = jnp.arange(N, dtype=jnp.int32)
        s_e, w_e = store.gather(qs, ids)
        s_w, w_w = wired.gather(qs, ids)
        tol = float(jnp.max(jnp.abs(s_e))) / 127.0 + 1e-7
        assert float(jnp.max(jnp.abs(s_w - s_e))) <= tol
        print("OK")
    """)
    outs = run_cluster(code)
    ref_losses_dig = None
    for out in outs:
        assert _parse("CODES", out) == codes_dig
        assert _parse("GATHERS", out) == ",".join(gather_digs)
        ref_losses_dig = _parse("LOSSES", out)
    # assembled prune losses equal the single-process snapshot
    import hashlib
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(losses_full))
    assert ref_losses_dig == h.hexdigest()[:16]


def test_cluster_quantized_checkpoint_restores_on_one_process(tmp_path):
    """2-process per-leaf partitioned quantized checkpoint -> 1-process
    replicated-quant restore: codes and scales bitwise, gathers exact
    (every live residual rides along in the ring blocks)."""
    ref_qs, _, _, ref_losses = _quant_reference_digests()
    code = _QUANT_WORKLOAD + textwrap.dedent("""
        import os
        from jax.sharding import Mesh
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.core.scores import ScoreSharding, make_store

        P, pid = jax.process_count(), jax.process_index()
        n_local = N // P
        mesh = Mesh(np.array(jax.local_devices()), ("data",))
        store = make_store(ScoreSharding(mesh, ("data",), n_global=N,
                                         offset=pid * n_local), **QKW)
        qs, _ = run_quant(store)
        part = store.checkpoint_partition()
        assert part is not None and part["per_leaf"] and part["rank"] == pid
        spec = store.checkpoint_spec()
        assert spec["kind"] == "quantized" and spec["block"] == 8
        ck = Checkpointer(os.environ["REPRO_CKPT_DIR"])
        ck.save({"scores": qs}, step=9, metadata={}, partition=part)
        # restores back into THIS topology
        r = ck.restore({"scores": store.init_leaf(N)}, step=9,
                       partition=part)
        np.testing.assert_array_equal(np.asarray(r["scores"].s_q),
                                      np.asarray(qs.s_q))
        np.testing.assert_array_equal(np.asarray(r["scores"].err_s),
                                      np.asarray(qs.err_s))
        print("OK")
    """)
    run_cluster(code, extra_env={"REPRO_CKPT_DIR": str(tmp_path)})
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.core.scores import make_store
    repl = make_store(None, quantize=True, block=8, residual_rows=1024)
    ck = Checkpointer(tmp_path)
    r = ck.restore({"scores": repl.init_leaf(64)}, step=9)
    got = r["scores"]
    np.testing.assert_array_equal(np.asarray(got.s_q),
                                  np.asarray(ref_qs.s_q))
    np.testing.assert_array_equal(np.asarray(got.s_scale),
                                  np.asarray(ref_qs.s_scale))
    np.testing.assert_array_equal(np.asarray(got.seen_q),
                                  np.asarray(ref_qs.seen_q))
    # assembled losses (residual-corrected) equal the reference's
    np.testing.assert_array_equal(repl.prune_snapshot(got).full_losses(),
                                  ref_losses)
