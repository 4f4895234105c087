"""Evolved Sampling score state — paper Eq. (3.1) / Prop. 3.1.

The recursion

    w_i(t) = beta1 * s_i(t-1) + (1-beta1) * l_i(theta(t))
    s_i(t) = beta2 * s_i(t-1) + (1-beta2) * l_i(theta(t))

implicitly augments the loss EMA with (beta2-beta1)-weighted loss
*differences* (Eq. 3.2) at O(n) memory: two scalars per sample.  All updates
here are pure-JAX scatter ops so they live *inside* the jitted train step
(no host round-trip).  ``explicit_weights`` implements the unrolled Eq. (3.2)
expansion and is used by property tests to verify the equivalence.

The score triple is the system's only O(n_train) state, so its PLACEMENT
is a backend decision behind one protocol — ``ScoreStore`` — and invisible
to every consumer (engine legs, selection, trainer, checkpointer):

  ``ReplicatedStore``   every device holds the full (n,) arrays; updates
                        are direct masked scatters, gathers direct loads.
  ``ShardedStore``      row blocks over the mesh axes of a ``ScoreSharding``
                        (device d owns rows [d*n/D, (d+1)*n/D)).  Sample
                        ids are routed to the owning device inside
                        ``shard_map``: the (tiny, (B,)) ids/losses are
                        broadcast, each shard applies a masked scatter to
                        the rows it owns, and gathers come back via a
                        masked-contribution ``psum``.  Gumbel selection
                        merges per-shard candidates (O(k*D) exchanged, not
                        O(B)); set-level pruning works from host-local
                        shard snapshots with exact global stat reductions.
                        No device ever materializes a full (n,) array.

Multi-host: on pod backends the mesh simply spans processes
(``jax.make_mesh(jax.devices())``) and the in-jit shard_map ops already
route across hosts.  ``ScoreSharding.n_global``/``offset`` additionally
support per-PROCESS row ownership (each process's arrays cover only its
row range — the CPU-cluster topology, where XLA cannot run multiprocess
computations): device-level ops then run on the local rows and the
epoch-boundary legs (gather completion, candidate merges, pruning stats,
checkpoint assembly) reduce across processes host-side via the exact
KV-store collectives in ``distributed.hostcomm``, bit-identical to the
single-process path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ESScores:
    """Per-sample score state (replicated, or row-sharded over DP axes).

    s: EMA of losses (Eq. 3.1 second line).
    w: sampling weights (Eq. 3.1 first line).
    seen: times each sample was scored (diagnostics / KA-style policies).
    """
    s: jax.Array      # (n,) f32
    w: jax.Array      # (n,) f32
    seen: jax.Array   # (n,) i32


@dataclasses.dataclass(frozen=True)
class ScoreSharding:
    """Row-layout of the score store over data-parallel mesh axes.

    ``axes`` are the mesh axes the row dimension is split over (axis order
    = shard order, row-major over the axes, matching
    ``PartitionSpec((axes,))``).  Shards are contiguous row blocks: device
    d owns rows ``[d*n/D, (d+1)*n/D)``.

    ``n_global``/``offset`` describe per-PROCESS ownership: when set, this
    process's arrays hold only rows ``[offset, offset + local_n)`` of an
    ``n_global``-row logical store (the CPU-cluster topology; on pod
    backends the mesh itself spans processes and both stay at their
    defaults).
    """
    mesh: Mesh
    axes: Tuple[str, ...] = ("data",)
    n_global: Optional[int] = None   # logical store rows (None: local == global)
    offset: int = 0                  # first global row owned by this process

    def __post_init__(self):
        # the routed shard_map ops assume Auto axes: on an Explicit mesh
        # (``jax.make_mesh``'s default) the store's sharding would type
        # the whole jitted step, and the replicated model's ops would no
        # longer match the single-device run
        auto = (AxisType.Auto,) * len(self.mesh.axis_names)
        if tuple(self.mesh.axis_types) != auto:
            object.__setattr__(self, "mesh", Mesh(
                self.mesh.devices, self.mesh.axis_names, axis_types=auto))

    @property
    def n_shards(self) -> int:
        out = 1
        for a in self.axes:
            out *= self.mesh.shape[a]
        return out

    def spec(self) -> P:
        return P(self.axes)

    def named_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec())

    def shard_size(self, n: int) -> int:
        if n % self.n_shards != 0:
            raise ValueError(
                f"score store size {n} not divisible by the {self.n_shards}"
                f"-way shard over mesh axes {self.axes}")
        return n // self.n_shards

    def shard_index(self) -> jax.Array:
        """Traced linear shard index — only valid inside ``shard_map``."""
        idx = jnp.zeros((), jnp.int32)
        for a in self.axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx


def init_scores(n: int, sharding: Optional[ScoreSharding] = None) -> ESScores:
    """Replicated (n,) init, or the ``sharding``'s placement (its
    ``n_global`` — set for per-process ownership — scales the 1/n init)."""
    n_logical = n if sharding is None or sharding.n_global is None \
        else sharding.n_global
    scores = ESScores(s=jnp.full((n,), 1.0 / n_logical, jnp.float32),
                      w=jnp.full((n,), 1.0 / n_logical, jnp.float32),
                      seen=jnp.zeros((n,), jnp.int32))
    if sharding is not None:
        sharding.shard_size(n)          # validate divisibility
        ns = sharding.named_sharding()
        scores = jax.tree.map(lambda x: jax.device_put(x, ns), scores)
    return scores


def weights_from_prev(s_prev: jax.Array, losses: jax.Array,
                      beta1: float) -> jax.Array:
    """Eq. (3.1) first line from the pre-update s — the one weight rule."""
    return beta1 * s_prev + (1.0 - beta1) * losses.astype(jnp.float32)


def update_scores(scores: ESScores, sample_ids: jax.Array,
                  losses: jax.Array, beta1: float, beta2: float) -> ESScores:
    """Scatter the Eq. (3.1) update for one meta-batch (the replicated
    reference all backends are pinned to).

    sample_ids: (B,) int32 indices into the score store; losses: (B,) f32.
    Ids outside ``[0, n)`` are DROPPED (the backends' shared masking rule —
    a negative id marks an entry some other owner will apply).
    Note: ``w`` uses s(t-1) (the *pre*-update s), per the paper.
    """
    n = scores.s.shape[0]
    losses = losses.astype(jnp.float32)
    mask = (sample_ids >= 0) & (sample_ids < n)
    pos = jnp.where(mask, sample_ids, 0)
    s_prev = scores.s[pos]
    w_new = weights_from_prev(s_prev, losses, beta1)
    s_new = beta2 * s_prev + (1.0 - beta2) * losses
    oob = jnp.where(mask, sample_ids, n)      # out-of-range: point past the
    return ESScores(                          # end and drop
        s=scores.s.at[oob].set(s_new, mode="drop"),
        w=scores.w.at[oob].set(w_new, mode="drop"),
        seen=scores.seen.at[oob].add(mask.astype(scores.seen.dtype),
                                     mode="drop"),
    )


def batch_weights(scores: ESScores, sample_ids: jax.Array,
                  losses: jax.Array, beta1: float, beta2: float) -> jax.Array:
    """The w(t) of Eq. (3.1) for a meta-batch, without mutating state."""
    return weights_from_prev(scores.s[sample_ids], losses, beta1)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# ScoreStore protocol: one backend interface for every consumer
# ---------------------------------------------------------------------------

class ScoreStore:
    """Placement backend for the (n,) score triple.

    Consumers (``ESEngine`` legs, ``select_minibatch``, the trainer's
    pruning hook, ``launch/inputs`` and the checkpointer) speak only this
    interface; whether the rows live replicated, sharded over a mesh, or
    split across processes is a backend detail.

    Device ops (inside the jitted step, or eagerly between steps):
      ``update(scores, ids, losses, beta1, beta2, fused=...)``
      ``gather(scores, ids) -> (s[ids], w[ids])``
      ``select(key, weights, k) -> (k,) indices``  (Gumbel top-k)
    Each backend compiles its device part through ``device_op``.
    Host ops (epoch boundary):
      ``prune_snapshot(scores)``  host-local row blocks + global offsets
      ``prune_epoch(...)``        set-level kept-set from the snapshot
    Placement plumbing:
      ``init_leaf(n)``, ``leaf_sharding()``, ``checkpoint_spec()``,
      ``checkpoint_partition()``
    """

    sharding: Optional[ScoreSharding] = None

    @staticmethod
    def device_op(*static: str):
        """Decorator for a backend's device op: ``jax.jit`` with the store
        and the named Python scalars (``beta1``, ``k``, ...) static.

        Stores are frozen dataclasses, so equal stores hash equal and
        share one compile per shape: an eager call between steps compiles
        once instead of re-lowering its ``shard_map`` on every call, and
        inside the trainer's jitted step the nested jit is inlined.  Both
        sides of a replicated-vs-sharded parity check run compiled, so
        they stay bit-comparable.  Host steps (the cross-process
        completion of a per-process gather) stay outside, in Python.
        """
        return functools.partial(jax.jit,
                                 static_argnames=("self",) + static)

    # -- device ops -----------------------------------------------------
    def init_leaf(self, n: int) -> ESScores:
        raise NotImplementedError

    def update(self, scores: ESScores, ids: jax.Array, losses: jax.Array,
               beta1: float, beta2: float, *, fused: bool = False,
               interpret: Optional[bool] = None) -> ESScores:
        raise NotImplementedError

    def gather(self, scores: ESScores, ids: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def select(self, key: jax.Array, weights: jax.Array, k: int) -> jax.Array:
        raise NotImplementedError

    # -- host ops -------------------------------------------------------
    def prune_snapshot(self, scores: ESScores):
        raise NotImplementedError

    def prune_epoch(self, method: str, rng: np.random.Generator,
                    scores: ESScores, *, prev_losses=None, ratio: float = 0.2,
                    ucb_c: float = 1.0, ka_tau: float = 1.0):
        """Set-level kept-set for the next epoch -> (PruneResult, s_full).

        One implementation for every backend: the snapshot carries the
        host-local blocks (plus the cross-process comm when rows are
        process-owned) and ``core.pruning`` computes the kept-set from
        exact global reductions.  ``s_full`` is the assembled (n,) s-EMA
        snapshot the trainer keeps as ``prev_epoch_losses``.
        """
        from .pruning import prune_epoch_snapshot
        snap = self.prune_snapshot(scores)
        res = prune_epoch_snapshot(method, rng, snap,
                                   prev_losses=prev_losses, ratio=ratio,
                                   ucb_c=ucb_c, ka_tau=ka_tau)
        return res, snap.full_losses()

    # -- growth ---------------------------------------------------------
    def grow(self, scores, n_new: int) -> Tuple["ScoreStore", object]:
        """Extend the logical store by ``n_new`` NEW rows -> (store, leaf).

        Pre-grow rows are preserved BITWISE (global row ids are stable);
        the new rows start at the fresh-sample prior ``1/n_total`` with
        ``seen == 0`` — exactly what ``init_leaf(n_total)`` would give
        them.  Host-side op (epoch/admission boundary, not per-step): the
        returned leaf has a new shape, so the next jitted step recompiles
        once.  The returned store may be a NEW instance — per-process
        ownership (``ScoreSharding.n_global``/``offset``) is frozen and
        must be rebuilt when the row ranges shift; callers must swap both.
        """
        raise NotImplementedError

    # -- placement plumbing ---------------------------------------------
    def validate(self, n: int) -> None:
        pass

    def leaf_sharding(self) -> Optional[NamedSharding]:
        return None

    def checkpoint_spec(self) -> dict:
        raise NotImplementedError

    def checkpoint_partition(self) -> Optional[dict]:
        """Non-None when this process's score leaves cover only a row
        range of the logical store (per-process ownership): the
        checkpointer then writes/reads block entries (see
        ``Checkpointer``)."""
        return None


@dataclasses.dataclass(frozen=True)
class ReplicatedStore(ScoreStore):
    """Full (n,) arrays on every device — the default, off-mesh backend."""

    sharding: Optional[ScoreSharding] = None     # always None; protocol slot

    def init_leaf(self, n: int) -> ESScores:
        return init_scores(n)

    @ScoreStore.device_op("beta1", "beta2", "fused", "interpret")
    def update(self, scores, ids, losses, beta1, beta2, *, fused=False,
               interpret=None):
        # interpret=None: kernel only where it compiles (TPU); an explicit
        # True/False forces the kernel in interpret/compiled mode
        if fused and (interpret is not None or _on_tpu()):
            from ..kernels.score_update.score_update import fused_score_update
            n = scores.s.shape[0]
            # the shared masking rule: out-of-range ids become -1 and the
            # masked kernel drops them, matching the scatter path
            ids = jnp.where((ids >= 0) & (ids < n), ids, -1)
            s, w, seen = fused_score_update(
                scores.s, scores.w, scores.seen, ids, losses,
                beta1=beta1, beta2=beta2, interpret=bool(interpret),
                masked=True)
            return ESScores(s=s, w=w, seen=seen)
        return update_scores(scores, ids, losses, beta1, beta2)

    @ScoreStore.device_op()
    def gather(self, scores, ids):
        return scores.s[ids], scores.w[ids]

    @ScoreStore.device_op("k")
    def select(self, key, weights, k):
        from .selection import gumbel_topk_select
        return gumbel_topk_select(key, weights, k)

    def prune_snapshot(self, scores):
        from .pruning import PruneSnapshot
        return PruneSnapshot(
            weights=[np.asarray(scores.w)], losses=[np.asarray(scores.s)],
            seen=[np.asarray(scores.seen)],
            offsets=np.asarray([0], np.int64), n=int(scores.s.shape[0]))

    def grow(self, scores, n_new: int) -> Tuple[ScoreStore, ESScores]:
        """Pad-and-concat: old rows bitwise, new rows at the 1/n' prior."""
        if n_new <= 0:
            raise ValueError(f"grow needs n_new > 0, got {n_new}")
        n_tot = int(scores.s.shape[0]) + int(n_new)
        prior = jnp.full((n_new,), 1.0 / n_tot, jnp.float32)
        leaf = ESScores(
            s=jnp.concatenate([scores.s, prior]),
            w=jnp.concatenate([scores.w, prior]),
            seen=jnp.concatenate([scores.seen,
                                  jnp.zeros((n_new,), jnp.int32)]))
        return self, leaf

    def checkpoint_spec(self) -> dict:
        return {"kind": "replicated"}


@dataclasses.dataclass(frozen=True)
class ShardedStore(ScoreStore):
    """Row blocks over the ``ScoreSharding``'s mesh axes.

    Absorbs the routed shard_map scatter/gather, the per-shard masked
    kernel dispatch, the candidate-merge Gumbel selection and the
    shard-snapshot pruning stats behind the one ``ScoreStore`` interface.
    With per-process ownership (``sharding.n_global`` set) the
    epoch-boundary legs complete across processes via
    ``distributed.hostcomm``; ``gather``/``select`` then finish host-side
    and are driven eagerly between steps rather than inside one jit.
    """

    sharding: ScoreSharding = None

    # -- layout helpers --------------------------------------------------
    @property
    def is_process_local(self) -> bool:
        """Per-process row ownership: this process's arrays cover only its
        row range (CPU-cluster topology).  False on a pod's global mesh,
        where the arrays are global and span processes."""
        return self.sharding.n_global is not None

    @staticmethod
    def _comm():
        """The cross-process host collective of this run, or None outside
        a multi-process run.  Needed by the epoch-boundary legs in BOTH
        multi-host topologies: with per-process rows AND on a global pod
        mesh, ``prune_snapshot`` sees only host-local addressable shards,
        so the pruning stats always reduce across processes."""
        from ..distributed.hostcomm import get_comm
        return get_comm()

    def validate(self, n: int) -> None:
        local = n
        if self.is_process_local:
            comm = self._comm()
            nproc = comm.process_count if comm else 1
            if n % nproc != 0:
                raise ValueError(f"store size {n} not divisible by "
                                 f"{nproc} processes")
            local = n // nproc
        self.sharding.shard_size(local)

    def init_leaf(self, n: int) -> ESScores:
        if not self.is_process_local:
            return init_scores(n, self.sharding)
        assert n == self.sharding.n_global, (n, self.sharding.n_global)
        comm = self._comm()
        nproc = comm.process_count if comm else 1
        return init_scores(n // nproc, self.sharding)

    # -- device ops ------------------------------------------------------
    @ScoreStore.device_op("beta1", "beta2", "fused", "interpret")
    def update(self, scores, ids, losses, beta1, beta2, *, fused=False,
               interpret=None):
        ss = self.sharding
        shard = ss.shard_size(scores.s.shape[0])
        base = ss.offset
        losses = losses.astype(jnp.float32)
        # interpret=None: kernel only where it compiles (TPU); an explicit
        # True/False forces the kernel in interpret/compiled mode
        use_kernel = fused and (interpret is not None or _on_tpu())
        b1, b2 = beta1, beta2

        if use_kernel:
            from ..kernels.score_update.score_update import fused_score_update

            def body(s, w, seen, ids_, ls):
                local = ids_ - (base + ss.shard_index() * shard)
                mask = (local >= 0) & (local < shard)
                local = jnp.where(mask, local, -1)   # masked kernel: skip
                return fused_score_update(s, w, seen, local, ls, beta1=b1,
                                          beta2=b2,
                                          interpret=bool(interpret),
                                          masked=True)
        else:
            def body(s, w, seen, ids_, ls):
                local = ids_ - (base + ss.shard_index() * shard)
                mask = (local >= 0) & (local < shard)
                pos = jnp.where(mask, local, 0)
                s_prev = s[pos]
                w_new = weights_from_prev(s_prev, ls, b1)
                s_new = b2 * s_prev + (1.0 - b2) * ls
                # foreign/out-of-range ids point past the block: dropped
                oob = jnp.where(mask, local, shard)
                return (s.at[oob].set(s_new, mode="drop"),
                        w.at[oob].set(w_new, mode="drop"),
                        seen.at[oob].add(mask.astype(seen.dtype),
                                         mode="drop"))

        sp = ss.spec()
        s, w, seen = jax.shard_map(body, mesh=ss.mesh,
                                   in_specs=(sp, sp, sp, P(), P()),
                                   out_specs=(sp, sp, sp), check_vma=False)(
                                       scores.s, scores.w, scores.seen,
                                       ids, losses)
        return ESScores(s=s, w=w, seen=seen)

    def gather(self, scores, ids):
        """(s[ids], w[ids]) routed from the owning shards, (B,) replicated.

        Each shard contributes its owned rows (zeros elsewhere); the
        cross-shard ``psum`` assembles the full gather — the only
        collective is over the tiny (B,) batch vectors, never the (n,)
        store.  With per-process rows the mesh psum covers only the local
        range and the host collective completes the sum across processes
        (exact: every global row has exactly one owner).
        """
        return self._complete_gather(*self._gather_shards(scores, ids))

    def _complete_gather(self, s_v, w_v, compressed: bool = False):
        """Host completion of a gather: only per-process rows need it (a
        process-spanning mesh already psums over every shard inside the
        jitted op); ``compressed`` ships the int8 wire payload."""
        comm = self._comm() if self.is_process_local else None
        if comm is None:
            return s_v, w_v
        reduce = (comm.allreduce_sum_compressed if compressed
                  else comm.allreduce_sum)
        return (jnp.asarray(reduce(np.asarray(s_v))),
                jnp.asarray(reduce(np.asarray(w_v))))

    @ScoreStore.device_op()
    def _gather_shards(self, scores, ids):
        ss = self.sharding
        shard = ss.shard_size(scores.s.shape[0])
        base = ss.offset

        def body(s, w, ids_):
            local = ids_ - (base + ss.shard_index() * shard)
            mask = (local >= 0) & (local < shard)
            pos = jnp.where(mask, local, 0)
            s_v = jnp.where(mask, s[pos], 0.0)
            w_v = jnp.where(mask, w[pos], 0.0)
            return (jax.lax.psum(s_v, ss.axes), jax.lax.psum(w_v, ss.axes))

        sp = ss.spec()
        return jax.shard_map(body, mesh=ss.mesh, in_specs=(sp, sp, P()),
                             out_specs=(P(), P()), check_vma=False)(
                                 scores.s, scores.w, ids)

    def select(self, key, weights, k):
        """Gumbel top-k from device-local weight shards.

        weights: (B,).  Each device computes Gumbel keys for its slice
        (drawn by GLOBAL position from the shared ``key``), keeps its
        local top-min(k, B/D) candidates, and only those (key, global
        index) pairs are all-gathered for the global top-k — an exchange
        of O(k*D) scalars instead of O(B).  Exactness: the global top-k
        can contain at most k entries from any one shard, so merging
        per-shard top-k candidates loses nothing; per-element keys are
        drawn by global position, so the result is bit-identical to the
        replicated Gumbel top-k (up to float ties).  With per-process rows
        the (B,) weights are already complete on every process (the
        gather's cross-process psum), so the replicated form IS the
        sharded result.
        """
        if self.is_process_local or weights.shape[0] % self.sharding.n_shards:
            return ReplicatedStore().select(key, weights, k)
        return self._select_shards(key, weights, k)

    @ScoreStore.device_op("k")
    def _select_shards(self, key, weights, k):
        B = weights.shape[0]
        ss = self.sharding
        n_local = B // ss.n_shards
        m = min(k, n_local)

        def body(w_local):
            lo = ss.shard_index() * n_local
            # same (B,) draw on every device, sliced to this shard's
            # positions: bit-parity with the replicated per-element keys
            g = jax.random.gumbel(key, (B,), jnp.float32)
            g_local = jax.lax.dynamic_slice(g, (lo,), (n_local,))
            logw = jnp.log(jnp.maximum(w_local.astype(jnp.float32), 1e-20))
            kv, ki = jax.lax.top_k(logw + g_local, m)
            cand_keys = jax.lax.all_gather(kv, ss.axes, tiled=True)
            cand_ids = jax.lax.all_gather(ki + lo, ss.axes, tiled=True)
            _, sel = jax.lax.top_k(cand_keys, k)
            return cand_ids[sel].astype(jnp.int32)

        return jax.shard_map(body, mesh=ss.mesh, in_specs=ss.spec(),
                             out_specs=P(), check_vma=False)(weights)

    # -- host ops --------------------------------------------------------
    def _local_blocks(self, arr) -> Tuple[List[np.ndarray], List[int]]:
        """Host-local addressable row blocks + their GLOBAL offsets.

        Dedups by row range: on a multi-axis mesh the store is replicated
        over non-DP axes, so several addressable shards carry the same
        rows — keep one copy per range.  Only addressable shards are
        touched: on a process-spanning mesh each host snapshots just its
        own rows.
        """
        by_start = {sh.index[0].start or 0: sh
                    for sh in arr.addressable_shards}
        starts = sorted(by_start)
        blocks = [np.asarray(by_start[s].data) for s in starts]
        return blocks, [self.sharding.offset + s for s in starts]

    def prune_snapshot(self, scores):
        from .pruning import PruneSnapshot
        w_blocks, offs = self._local_blocks(scores.w)
        s_blocks, _ = self._local_blocks(scores.s)
        seen_blocks, _ = self._local_blocks(scores.seen)
        n = self.sharding.n_global if self.is_process_local \
            else int(scores.s.shape[0])
        comm = self._comm()
        covers = sum(len(b) for b in s_blocks) == n
        if comm is not None and not self.is_process_local and covers:
            # a process-LOCAL mesh inside a distributed run: every process
            # holds the whole store, so a cross-process merge would double
            # every candidate — each process prunes the full view alone
            # (identical result everywhere, same rng)
            comm = None
        if comm is None and not covers:
            # partial view with no cross-process reduction would compute
            # silently-wrong global stats — fail loudly instead
            raise AssertionError(
                f"prune_snapshot: local blocks cover "
                f"{sum(len(b) for b in s_blocks)} of {n} rows but no "
                "host collective is available (jax.distributed not "
                "initialized?)")
        return PruneSnapshot(weights=w_blocks, losses=s_blocks,
                             seen=seen_blocks,
                             offsets=np.asarray(offs, np.int64), n=int(n),
                             comm=comm)

    # -- growth ----------------------------------------------------------
    def _assemble_global(self, arr) -> np.ndarray:
        """The FULL logical array host-side, identical on every process.

        Local addressable shards concatenate in row order; with
        per-process ownership the rank-ordered host allgather completes
        the global view (row ranges tile ``[0, n_global)`` in rank
        order), and on a process-spanning pod mesh the non-addressable
        rows come back via ``process_allgather``.
        """
        by_start = {sh.index[0].start or 0: sh
                    for sh in arr.addressable_shards}
        local = np.concatenate(
            [np.asarray(by_start[s].data) for s in sorted(by_start)])
        if self.is_process_local:
            comm = self._comm()
            if comm is not None:
                return np.concatenate(comm.allgather(local))
            return local
        if not arr.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True))
        return local

    def grow(self, scores, n_new: int) -> Tuple[ScoreStore, ESScores]:
        """Re-slice the grown row space over the same mesh.

        Global row ids are stable (new rows append at the end), but the
        contiguous-block layout means every shard/process boundary moves:
        the old rows are assembled host-side (offset-ordered blocks, the
        same layout the checkpoint block format tags), the 1/n' prior is
        appended, and each process re-slices its NEW ``[offset',
        offset'+local')`` range back onto the mesh.  Returns a rebuilt
        store when per-process ownership shifts.
        """
        if n_new <= 0:
            raise ValueError(f"grow needs n_new > 0, got {n_new}")
        ss = self.sharding
        n_old = int(ss.n_global) if self.is_process_local \
            else int(scores.s.shape[0])
        n_tot = n_old + int(n_new)
        comm = self._comm() if self.is_process_local else None
        nproc = comm.process_count if comm else 1
        rank = comm.process_index if comm else 0
        if self.is_process_local and n_tot % nproc != 0:
            raise ValueError(f"grown store size {n_tot} not divisible by "
                             f"{nproc} processes")
        local_n = n_tot // nproc
        off = rank * local_n
        new_store = self
        if self.is_process_local:
            new_store = dataclasses.replace(
                self, sharding=dataclasses.replace(
                    ss, n_global=n_tot, offset=off))
        new_store.validate(n_tot)          # shard divisibility, loudly

        prior = np.full((n_new,), np.float32(1.0 / n_tot), np.float32)
        ns = new_store.sharding.named_sharding()

        def regrow(arr, new_tail):
            full = np.concatenate([self._assemble_global(arr), new_tail])
            if self.is_process_local:
                return jax.device_put(full[off:off + local_n], ns)
            # pod mesh: each process materializes only its addressable
            # shards of the global array
            return jax.make_array_from_callback(
                (n_tot,), ns, lambda idx: full[idx])

        leaf = ESScores(
            s=regrow(scores.s, prior),
            w=regrow(scores.w, prior),
            seen=regrow(scores.seen, np.zeros((n_new,), np.int32)))
        return new_store, leaf

    # -- placement plumbing ----------------------------------------------
    def leaf_sharding(self) -> Optional[NamedSharding]:
        return self.sharding.named_sharding()

    def checkpoint_spec(self) -> dict:
        comm = self._comm()
        return {"kind": "sharded",
                "axes": list(self.sharding.axes),
                "mesh": {str(a): int(self.sharding.mesh.shape[a])
                         for a in self.sharding.mesh.axis_names},
                "n_global": self.sharding.n_global,
                "offset": int(self.sharding.offset),
                "process_count": comm.process_count if comm else 1}

    def checkpoint_partition(self) -> Optional[dict]:
        if not self.is_process_local:
            # global-mesh leaves checkpoint as full arrays (save
            # allgathers the non-addressable rows) — nothing to partition
            return None
        return {"prefixes": ("scores/",),
                "offset": int(self.sharding.offset),
                "n_global": int(self.sharding.n_global),
                "comm": self._comm()}


# ---------------------------------------------------------------------------
# QuantizedStore: int8 score state with per-block scales + error feedback
# ---------------------------------------------------------------------------

_QMAX = 127.0
_SCALE_FLOOR = 1e-12


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedScores:
    """Int8 form of the score triple + the state that makes it lossless
    enough: per-block f32 scales and an error-feedback residual ring.

    Rows (replicated (n,), or this slice's rows when sharded):
      s_q/w_q: symmetric int8 on a per-block grid (row r uses scale
        ``*_scale[r // block]``); seen_q saturates at 127 (the UCB/KA
        consumers only need coarse visit counts — this is what buys the
        3rd byte of the 4x memory cut).
    Scales: one f32 per ``block`` rows, grow-only (monotone max of
      incoming |value|/127; growth rescales the stored int8 codes once,
      under a ``lax.cond`` so steady-state steps skip the O(n) pass).
    Residual ring (the error feedback, Karimireddy-style): the f32
      quantization errors of the MOST RECENTLY updated rows only —
      ``err_rows`` holds global row ids (-1 empty), ``err_seq`` recency
      stamps (0 empty; gathers resolve duplicates to the newest entry),
      ``err_s``/``err_w`` the residuals.  A gather returns
      ``q * scale + newest residual`` — exact for any row still in the
      ring, within scale/2 after eviction.  Ring eviction overwrites the
      oldest stamps, so hot rows (the ones ES keeps re-scoring) stay
      exact and only long-cold rows pay the grid error.
    """
    s_q: jax.Array       # (rows,) int8
    w_q: jax.Array       # (rows,) int8
    seen_q: jax.Array    # (rows,) int8, saturating at 127
    s_scale: jax.Array   # (n_blocks,) f32
    w_scale: jax.Array   # (n_blocks,) f32
    err_rows: jax.Array  # (R,) int32 global row ids, -1 = empty
    err_seq: jax.Array   # (R,) int32 recency stamps, 0 = empty
    err_s: jax.Array     # (R,) f32
    err_w: jax.Array     # (R,) f32


def _q_init_leaf(rows: int, n_blocks: int, ring: int,
                 n_logical: int) -> QuantizedScores:
    # 1/n init encoded as code 127 on a (1/n)/127 grid: within 2 ulp of
    # the f32 store's exact 1/n (the residual ring starts empty)
    scale0 = jnp.float32((1.0 / n_logical) / _QMAX)
    return QuantizedScores(
        s_q=jnp.full((rows,), 127, jnp.int8),
        w_q=jnp.full((rows,), 127, jnp.int8),
        seen_q=jnp.zeros((rows,), jnp.int8),
        s_scale=jnp.full((n_blocks,), scale0, jnp.float32),
        w_scale=jnp.full((n_blocks,), scale0, jnp.float32),
        err_rows=jnp.full((ring,), -1, jnp.int32),
        err_seq=jnp.zeros((ring,), jnp.int32),
        err_s=jnp.zeros((ring,), jnp.float32),
        err_w=jnp.zeros((ring,), jnp.float32))


def _per_row(scales: jax.Array, blk: jax.Array) -> jax.Array:
    """``scales[blk]``, kept a gather when the array holds one block.

    A shard holding one scale block would have the lookup folded into a
    broadcast, which XLA compiles with other multiply-add roundings than
    the per-row gather of every other layout; the padding slot (never
    indexed) keeps the compiled op, and so its bits, layout-invariant.
    """
    return jnp.pad(scales, (0, 1))[blk]


def _q_gather_1d(q: jax.Array, scales: jax.Array, block: int,
                 err_rows: jax.Array, err_seq: jax.Array, err_val: jax.Array,
                 pos: jax.Array, gids: jax.Array) -> jax.Array:
    """Dequantized values for local rows ``pos``, corrected by the NEWEST
    ring residual whose global id matches ``gids`` (-1 never matches)."""
    deq = q[pos].astype(jnp.float32) * _per_row(scales, pos // block)
    hit = err_rows[None, :] == gids[:, None]            # (B, R)
    stamped = jnp.where(hit, err_seq[None, :], 0)
    newest = jnp.argmax(stamped, axis=1)
    has = jnp.max(stamped, axis=1) > 0
    return deq + jnp.where(has, err_val[newest], 0.0)


def _q_grow_scales(qs: QuantizedScores, pos: jax.Array, mask: jax.Array,
                   gids: jax.Array, losses: jax.Array, beta1: float,
                   beta2: float, block: int) -> QuantizedScores:
    """Grow the touched blocks' scales to fit the incoming Eq. (3.1)
    values (grow-only: max of old and amax/127).  When any block grows,
    one ``lax.cond``-gated pass re-codes the stored int8 onto the new
    grid (ratio-1 blocks re-code exactly); steady-state steps take the
    no-op branch.  Stale ring residuals of re-coded rows stay bounded by
    the new grid's scale/2 — never wrong, just no longer exact."""
    s_prev = _q_gather_1d(qs.s_q, qs.s_scale, block, qs.err_rows,
                          qs.err_seq, qs.err_s, pos, gids)
    w_new = weights_from_prev(s_prev, losses, beta1)
    s_new = beta2 * s_prev + (1.0 - beta2) * losses
    blk = pos // block
    nb = qs.s_scale.shape[0]
    need_s = jnp.zeros((nb,), jnp.float32).at[blk].max(
        jnp.where(mask, jnp.abs(s_new), 0.0) / _QMAX)
    need_w = jnp.zeros((nb,), jnp.float32).at[blk].max(
        jnp.where(mask, jnp.abs(w_new), 0.0) / _QMAX)
    new_ss = jnp.maximum(qs.s_scale, need_s)
    new_ws = jnp.maximum(qs.w_scale, need_w)
    grew = jnp.any(new_ss > qs.s_scale) | jnp.any(new_ws > qs.w_scale)
    row_blk = jnp.arange(qs.s_q.shape[0], dtype=jnp.int32) // block

    def recode():
        rs = _per_row(qs.s_scale / new_ss, row_blk)   # <= 1: no clipping
        rw = _per_row(qs.w_scale / new_ws, row_blk)
        return (jnp.round(qs.s_q.astype(jnp.float32) * rs).astype(jnp.int8),
                jnp.round(qs.w_q.astype(jnp.float32) * rw).astype(jnp.int8))

    s_q, w_q = jax.lax.cond(grew, recode, lambda: (qs.s_q, qs.w_q))
    return dataclasses.replace(qs, s_q=s_q, w_q=w_q,
                               s_scale=new_ss, w_scale=new_ws)


def _q_ring_slots(err_seq: jax.Array, mask: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Assign ring slots + recency stamps to a batch: the oldest slots
    are recycled, owned entries take the OLDEST of the recycled slots
    (masked entries draw the sentinel ranks and the newer candidates —
    their writes are dropped, so those slots keep their residuals), and
    stamps increase with batch position so within-batch duplicates
    resolve last-wins."""
    B = mask.shape[0]
    R = err_seq.shape[0]
    k = min(B, R)
    oldest = jnp.argsort(err_seq).astype(jnp.int32)
    base = jnp.max(err_seq) + 1
    # stable sort: masked entries first (they draw the dropped ranks),
    # owned entries keep batch order among themselves
    perm = jnp.argsort(mask.astype(jnp.int32))
    # sentinels first, then the k recycle candidates NEWEST-first: the
    # masked entries (front ranks) soak up the sentinels and the newer
    # candidates, the owned entries (back ranks) land on the truly
    # oldest slots — a small per-shard ring evicts cold entries, never
    # the freshest live residuals
    by_rank_slot = jnp.concatenate(
        [jnp.full((B - k,), R, jnp.int32), oldest[:k][::-1]])
    by_rank_seq = base + jnp.arange(B, dtype=jnp.int32)
    slots = jnp.zeros((B,), jnp.int32).at[perm].set(by_rank_slot)
    seqs = jnp.zeros((B,), jnp.int32).at[perm].set(by_rank_seq)
    return slots, seqs


def _q_apply_fixed(qs: QuantizedScores, pos: jax.Array, mask: jax.Array,
                   gids: jax.Array, losses: jax.Array, beta1: float,
                   beta2: float, block: int, slots: jax.Array,
                   seqs: jax.Array) -> QuantizedScores:
    """Fixed-scale dequant -> Eq. (3.1) -> requant + residual ring write,
    in XLA scatter form — the oracle semantics the Pallas kernel is
    pinned to (expression order kept identical for bit-parity on
    unique-id batches)."""
    n = qs.s_q.shape[0]
    blk = pos // block
    ssc = _per_row(qs.s_scale, blk)
    wsc = _per_row(qs.w_scale, blk)
    s_prev = _q_gather_1d(qs.s_q, qs.s_scale, block, qs.err_rows,
                          qs.err_seq, qs.err_s, pos, gids)
    w_new = weights_from_prev(s_prev, losses, beta1)
    s_new = beta2 * s_prev + (1.0 - beta2) * losses
    q_s = jnp.clip(jnp.round(s_new / ssc), -_QMAX, _QMAX)
    q_w = jnp.clip(jnp.round(w_new / wsc), -_QMAX, _QMAX)
    e_s = s_new - q_s * ssc
    e_w = w_new - q_w * wsc
    oob = jnp.where(mask, pos, n)
    adds = jnp.zeros((n,), jnp.int32).at[oob].add(1, mode="drop")
    slot = jnp.where(mask, slots, qs.err_rows.shape[0])
    return dataclasses.replace(
        qs,
        s_q=qs.s_q.at[oob].set(q_s.astype(jnp.int8), mode="drop"),
        w_q=qs.w_q.at[oob].set(q_w.astype(jnp.int8), mode="drop"),
        seen_q=jnp.minimum(qs.seen_q.astype(jnp.int32) + adds,
                           127).astype(jnp.int8),
        err_rows=qs.err_rows.at[slot].set(gids, mode="drop"),
        err_seq=qs.err_seq.at[slot].set(seqs, mode="drop"),
        err_s=qs.err_s.at[slot].set(e_s, mode="drop"),
        err_w=qs.err_w.at[slot].set(e_w, mode="drop"))


def _q_update_local(qs: QuantizedScores, local_ids: jax.Array,
                    gids: jax.Array, losses: jax.Array, beta1: float,
                    beta2: float, block: int, use_kernel: bool,
                    interpret: Optional[bool]) -> QuantizedScores:
    """One slice's full update: mask out-of-range rows, grow scales,
    assign ring slots, then apply via the fused kernel or XLA scatters."""
    n = qs.s_q.shape[0]
    mask = (local_ids >= 0) & (local_ids < n)
    pos = jnp.where(mask, local_ids, 0)
    mgids = jnp.where(mask, gids, -1)
    qs = _q_grow_scales(qs, pos, mask, mgids, losses, beta1, beta2, block)
    # compiled, XLA would share the scatter path's ring lookups with this
    # prologue and fuse (and round) it unlike the kernel path's: the
    # barrier keeps the prologue, and so the scales, one program for both
    qs, pos, mask, mgids, losses = jax.lax.optimization_barrier(
        (qs, pos, mask, mgids, losses))
    slots, seqs = _q_ring_slots(qs.err_seq, mask)
    if use_kernel:
        from ..kernels.score_update.score_update import (
            fused_quant_score_update)
        lids = jnp.where(mask, pos, -1)       # masked kernel: -1 skipped
        out = fused_quant_score_update(
            qs.s_q, qs.w_q, qs.seen_q, qs.s_scale, qs.w_scale,
            qs.err_rows, qs.err_seq, qs.err_s, qs.err_w,
            lids, mgids, losses, slots, seqs,
            beta1=beta1, beta2=beta2, block=block,
            interpret=bool(interpret))
        s_q, w_q, seen_q, e_r, e_t, e_s, e_w = out
        return dataclasses.replace(qs, s_q=s_q, w_q=w_q, seen_q=seen_q,
                                   err_rows=e_r, err_seq=e_t,
                                   err_s=e_s, err_w=e_w)
    return _q_apply_fixed(qs, pos, mask, mgids, losses, beta1, beta2,
                          block, slots, seqs)


@dataclasses.dataclass(frozen=True)
class QuantizedStore(ScoreStore):
    """Int8 decorator over a Replicated/Sharded backend: same protocol,
    ~4x smaller state (3 int8 rows + per-block scales + a fixed-size
    residual ring vs 12 B/row), optional int8 wire for the cross-shard
    legs.

    Placement is delegated to ``inner`` (row routing, mesh, per-process
    ownership); the quantized leaf layout, the grow-only per-``block``
    scales, and the error-feedback ring are this class's concern.  With
    ``wire=True`` the sharded gather psum and the candidate-merge select
    also ship int8+scale payloads (``distributed.compression``) — off by
    default so the sharded backend stays bit-identical to the replicated
    one and only the storage grid is lossy.  (The bitwise claim holds
    while no LIVE residual is evicted: the ring is partitioned per shard,
    so once the working set overflows it, which rows fall back to the
    grid differs between layouts — both stay within scale/2 of the f32
    recursion either way.)
    """

    inner: ScoreStore = None
    block: int = 1024           # rows per scale (clamped to the shard)
    residual_rows: int = 1024   # error-feedback ring size (global)
    wire: bool = False

    @property
    def sharding(self) -> Optional[ScoreSharding]:       # protocol slot
        return self.inner.sharding

    @property
    def is_process_local(self) -> bool:
        return getattr(self.inner, "is_process_local", False)

    # -- layout ----------------------------------------------------------
    def _layout(self, rows_local: int) -> Tuple[int, int, int]:
        """(eff_block, n_blocks, ring_rows) for THIS process's leaves."""
        if isinstance(self.inner, ShardedStore):
            ss = self.inner.sharding
            shard = ss.shard_size(rows_local)
            blk = min(self.block, shard)
            if shard % blk != 0:
                raise ValueError(
                    f"quant block {self.block} does not divide the "
                    f"{shard}-row shard; pick a divisor")
            nb = ss.n_shards * (shard // blk)
            nproc = self._nproc()
            per_shard = -(-self.residual_rows // (nproc * ss.n_shards))
            return blk, nb, max(1, per_shard) * ss.n_shards
        blk = min(self.block, rows_local)
        return blk, -(-rows_local // blk), self.residual_rows

    def _nproc(self) -> int:
        if self.is_process_local:
            comm = ShardedStore._comm()
            return comm.process_count if comm else 1
        return 1

    def _rows_local(self, n: int) -> int:
        return n // self._nproc() if self.is_process_local else n

    def validate(self, n: int) -> None:
        self.inner.validate(n)
        self._layout(self._rows_local(n))

    def init_leaf(self, n: int) -> QuantizedScores:
        self.inner.validate(n)
        rows = self._rows_local(n)
        blk, nb, ring = self._layout(rows)
        ss = self.inner.sharding
        n_logical = n if ss is None or ss.n_global is None else ss.n_global
        qs = _q_init_leaf(rows, nb, ring, n_logical)
        if ss is not None:
            ns = ss.named_sharding()
            qs = jax.tree.map(lambda x: jax.device_put(x, ns), qs)
        return qs

    # -- device ops ------------------------------------------------------
    @ScoreStore.device_op("beta1", "beta2", "fused", "interpret")
    def update(self, qs, ids, losses, beta1, beta2, *, fused=False,
               interpret=None):
        losses = losses.astype(jnp.float32)
        use_kernel = fused and (interpret is not None or _on_tpu())
        if not isinstance(self.inner, ShardedStore):
            blk, _, _ = self._layout(qs.s_q.shape[0])
            return _q_update_local(qs, ids, ids, losses, beta1, beta2,
                                   blk, use_kernel, interpret)
        ss = self.inner.sharding
        shard = ss.shard_size(qs.s_q.shape[0])
        blk, _, _ = self._layout(qs.s_q.shape[0])
        base = ss.offset
        b1, b2 = beta1, beta2

        def body(qs_local, ids_, ls):
            row0 = base + ss.shard_index() * shard
            local = ids_ - row0
            return _q_update_local(qs_local, local, ids_, ls, b1, b2,
                                   blk, use_kernel, interpret)

        sp = ss.spec()
        spec_tree = jax.tree.map(lambda _: sp, qs)
        return jax.shard_map(body, mesh=ss.mesh,
                             in_specs=(spec_tree, P(), P()),
                             out_specs=spec_tree, check_vma=False)(
                                 qs, ids, losses)

    def gather(self, qs, ids):
        s_v, w_v = self._gather_device(qs, ids)
        if not self.is_process_local:
            return s_v, w_v
        return self.inner._complete_gather(s_v, w_v, compressed=self.wire)

    @ScoreStore.device_op()
    def _gather_device(self, qs, ids):
        if not isinstance(self.inner, ShardedStore):
            n = qs.s_q.shape[0]
            blk, _, _ = self._layout(n)
            pos = jnp.clip(ids, 0, n - 1)
            s = _q_gather_1d(qs.s_q, qs.s_scale, blk, qs.err_rows,
                             qs.err_seq, qs.err_s, pos, ids)
            w = _q_gather_1d(qs.w_q, qs.w_scale, blk, qs.err_rows,
                             qs.err_seq, qs.err_w, pos, ids)
            return s, w
        ss = self.inner.sharding
        shard = ss.shard_size(qs.s_q.shape[0])
        blk, _, _ = self._layout(qs.s_q.shape[0])
        base = ss.offset
        wire = self.wire and len(ss.axes) == 1

        def body(qs_local, ids_):
            row0 = base + ss.shard_index() * shard
            local = ids_ - row0
            mask = (local >= 0) & (local < shard)
            pos = jnp.where(mask, local, 0)
            mgids = jnp.where(mask, ids_, -1)
            s_v = jnp.where(mask, _q_gather_1d(
                qs_local.s_q, qs_local.s_scale, blk, qs_local.err_rows,
                qs_local.err_seq, qs_local.err_s, pos, mgids), 0.0)
            w_v = jnp.where(mask, _q_gather_1d(
                qs_local.w_q, qs_local.w_scale, blk, qs_local.err_rows,
                qs_local.err_seq, qs_local.err_w, pos, mgids), 0.0)
            if wire:
                from ..distributed.compression import compressed_psum_sum
                return (compressed_psum_sum(s_v, ss.axes[0], ss.n_shards),
                        compressed_psum_sum(w_v, ss.axes[0], ss.n_shards))
            return (jax.lax.psum(s_v, ss.axes), jax.lax.psum(w_v, ss.axes))

        sp = ss.spec()
        spec_tree = jax.tree.map(lambda _: sp, qs)
        return jax.shard_map(body, mesh=ss.mesh, in_specs=(spec_tree, P()),
                             out_specs=(P(), P()), check_vma=False)(qs, ids)

    def select(self, key, weights, k):
        if not self.wire or not isinstance(self.inner, ShardedStore):
            return self.inner.select(key, weights, k)
        return self._select_wire(key, weights, k)

    def _select_wire(self, key, weights, k):
        """Candidate-merge Gumbel top-k with an int8 wire: each shard
        ships its top-m keys affine-quantized to int8 (per-shard offset +
        scale, 127 steps over the shard's candidate span) and int16
        in-shard positions — 3 B/candidate + 8 B/shard instead of 8
        B/candidate.  Selection runs on the dequantized keys, so merges
        can differ from the exact path within one key-grid step (flagged
        mode; ``wire=False`` keeps the bit-exact merge)."""
        ss = self.inner.sharding
        B = weights.shape[0]
        if (self.is_process_local or B % ss.n_shards != 0
                or len(ss.axes) != 1 or B // ss.n_shards > 32767):
            return ReplicatedStore().select(key, weights, k)
        return self._select_wire_shards(key, weights, k)

    @ScoreStore.device_op("k")
    def _select_wire_shards(self, key, weights, k):
        ss = self.inner.sharding
        B = weights.shape[0]
        n_local = B // ss.n_shards
        m = min(k, n_local)
        ax = ss.axes[0]

        def body(w_local):
            lo = ss.shard_index() * n_local
            g = jax.random.gumbel(key, (B,), jnp.float32)
            g_local = jax.lax.dynamic_slice(g, (lo,), (n_local,))
            logw = jnp.log(jnp.maximum(w_local.astype(jnp.float32), 1e-20))
            kv, ki = jax.lax.top_k(logw + g_local, m)
            off = kv[0]                       # shard max (top_k is sorted)
            sc = jnp.maximum((off - kv[m - 1]) / _QMAX, _SCALE_FLOOR)
            q = jnp.clip(jnp.round((kv - off) / sc), -_QMAX, 0.0
                         ).astype(jnp.int8)
            q_all = jax.lax.all_gather(q, ax, tiled=True)
            id_all = jax.lax.all_gather(ki.astype(jnp.int16), ax, tiled=True)
            off_all = jax.lax.all_gather(off[None], ax, tiled=True)
            sc_all = jax.lax.all_gather(sc[None], ax, tiled=True)
            src = jnp.arange(ss.n_shards * m, dtype=jnp.int32) // m
            keys_deq = off_all[src] + q_all.astype(jnp.float32) * sc_all[src]
            _, sel = jax.lax.top_k(keys_deq, k)
            gids = id_all.astype(jnp.int32) + src * n_local
            return gids[sel]

        return jax.shard_map(body, mesh=ss.mesh, in_specs=ss.spec(),
                             out_specs=P(), check_vma=False)(weights)

    # -- host ops --------------------------------------------------------
    @staticmethod
    def _dequant_blocks_host(q_blocks, scale_blocks, block, ring_np,
                             offsets):
        """Host-side dequant of row blocks + newest-wins residual
        application (entries applied in recency order; rows outside the
        blocks are ignored — they belong to another owner)."""
        rows_all, seq_all, val_all = ring_np
        order = np.argsort(seq_all, kind="stable")
        rows_o, seq_o, val_o = (rows_all[order], seq_all[order],
                                val_all[order])
        live = seq_o > 0
        rows_o, val_o = rows_o[live], val_o[live]
        out = []
        for q, sc, off in zip(q_blocks, scale_blocks, offsets):
            L = len(q)
            nb = len(sc)
            blk = -(-L // nb) if nb else block
            pad = nb * blk - L
            deq = (np.pad(q.astype(np.float32), (0, pad)).reshape(nb, blk)
                   * sc[:, None]).reshape(-1)[:L]
            here = (rows_o >= off) & (rows_o < off + L)
            for r, v in zip(rows_o[here], val_o[here]):
                deq[r - off] = deq[r - off] + v      # newest wins (sorted)
            out.append(deq)
        return out

    def prune_snapshot(self, qs):
        from .pruning import QuantPruneSnapshot
        blk, _, _ = self._layout(qs.s_q.shape[0])
        if not isinstance(self.inner, ShardedStore):
            ring_s = (np.asarray(qs.err_rows), np.asarray(qs.err_seq),
                      np.asarray(qs.err_s))
            ring_w = (np.asarray(qs.err_rows), np.asarray(qs.err_seq),
                      np.asarray(qs.err_w))
            offs = [0]
            losses = self._dequant_blocks_host(
                [np.asarray(qs.s_q)], [np.asarray(qs.s_scale)], blk,
                ring_s, offs)
            weights = self._dequant_blocks_host(
                [np.asarray(qs.w_q)], [np.asarray(qs.w_scale)], blk,
                ring_w, offs)
            return QuantPruneSnapshot(
                weights=weights, losses=losses,
                seen=[np.asarray(qs.seen_q).astype(np.int32)],
                offsets=np.asarray(offs, np.int64),
                n=int(qs.s_q.shape[0]),
                q_losses=[np.asarray(qs.s_q)],
                q_scales=[np.asarray(qs.s_scale)], q_block=blk)
        inner = self.inner
        sq_blocks, offs = inner._local_blocks(qs.s_q)
        wq_blocks, _ = inner._local_blocks(qs.w_q)
        seen_blocks, _ = inner._local_blocks(qs.seen_q)
        ssc_blocks, _ = inner._local_blocks(qs.s_scale)
        wsc_blocks, _ = inner._local_blocks(qs.w_scale)
        er_blocks, _ = inner._local_blocks(qs.err_rows)
        et_blocks, _ = inner._local_blocks(qs.err_seq)
        es_blocks, _ = inner._local_blocks(qs.err_s)
        ew_blocks, _ = inner._local_blocks(qs.err_w)
        ring_rows = np.concatenate(er_blocks)
        ring_seq = np.concatenate(et_blocks)
        losses = self._dequant_blocks_host(
            sq_blocks, ssc_blocks, blk,
            (ring_rows, ring_seq, np.concatenate(es_blocks)), offs)
        weights = self._dequant_blocks_host(
            wq_blocks, wsc_blocks, blk,
            (ring_rows, ring_seq, np.concatenate(ew_blocks)), offs)
        n = inner.sharding.n_global if self.is_process_local \
            else int(qs.s_q.shape[0])
        comm = ShardedStore._comm()
        covers = sum(len(b) for b in sq_blocks) == n
        if comm is not None and not self.is_process_local and covers:
            comm = None           # full local view: prune alone, same rng
        if comm is None and not covers:
            raise AssertionError(
                f"prune_snapshot: local blocks cover "
                f"{sum(len(b) for b in sq_blocks)} of {n} rows but no "
                "host collective is available")
        return QuantPruneSnapshot(
            weights=weights, losses=losses,
            seen=[b.astype(np.int32) for b in seen_blocks],
            offsets=np.asarray(offs, np.int64), n=int(n), comm=comm,
            q_losses=sq_blocks, q_scales=ssc_blocks, q_block=blk,
            wire=self.wire)

    # -- growth ----------------------------------------------------------
    @staticmethod
    def _new_row_codes(n_tot: int, new_blk: np.ndarray,
                       scales: np.ndarray) -> np.ndarray:
        """Int8 codes for the 1/n' prior of the appended rows: exact code
        127 on fresh blocks (their scale is (1/n')/127), nearest grid
        point when a new row lands in an old partial tail block."""
        q = np.round((1.0 / n_tot) / scales[new_blk])
        return np.clip(q, -_QMAX, _QMAX).astype(np.int8)

    def grow(self, qs, n_new: int) -> Tuple[ScoreStore, QuantizedScores]:
        """Grow codes, per-block scales and the residual ring together.

        Old blocks keep their codes AND scales bitwise (pre-grow gathers
        are preserved exactly); appended blocks start on the fresh
        (1/n')/127 grid.  The effective block size must not change across
        the grow — block boundaries would shift and every old row would
        re-code — so a ``block`` larger than the pre-grow shard (or the
        pre-grow replicated row count) raises instead of silently
        re-gridding.  Sharded: ring entries are re-dealt to the shard
        that owns their row under the new layout, newest-first dedup per
        row, oldest evicted when a shard ring overflows.
        """
        if n_new <= 0:
            raise ValueError(f"grow needs n_new > 0, got {n_new}")
        rows_old = int(qs.s_q.shape[0])
        blk, nb_local, ring = self._layout(rows_old)
        if not isinstance(self.inner, ShardedStore):
            n_tot = rows_old + int(n_new)
            blk2, nb2, _ = self._layout(n_tot)
            if blk2 != blk:
                raise ValueError(
                    f"quant block changes across grow ({blk} -> {blk2}): "
                    f"construct the store with block <= the pre-grow row "
                    f"count so block boundaries are stable")
            scale0 = np.float32((1.0 / n_tot) / _QMAX)
            s_scale = np.concatenate([np.asarray(qs.s_scale),
                                      np.full((nb2 - nb_local,), scale0,
                                              np.float32)])
            w_scale = np.concatenate([np.asarray(qs.w_scale),
                                      np.full((nb2 - nb_local,), scale0,
                                              np.float32)])
            new_blk = np.arange(rows_old, n_tot, dtype=np.int64) // blk
            leaf = dataclasses.replace(
                qs,
                s_q=jnp.concatenate([qs.s_q, jnp.asarray(
                    self._new_row_codes(n_tot, new_blk, s_scale))]),
                w_q=jnp.concatenate([qs.w_q, jnp.asarray(
                    self._new_row_codes(n_tot, new_blk, w_scale))]),
                seen_q=jnp.concatenate([qs.seen_q,
                                        jnp.zeros((n_new,), jnp.int8)]),
                s_scale=jnp.asarray(s_scale), w_scale=jnp.asarray(w_scale))
            return self, leaf
        return self._grow_sharded(qs, int(n_new), blk, ring)

    def _grow_sharded(self, qs, n_new: int, blk: int, ring: int):
        """Sharded grow: assemble the global code/scale/ring view (the
        same offset-ordered block layout the checkpointer tags), append,
        re-deal, and re-slice to the new per-process/per-shard ranges."""
        inner: ShardedStore = self.inner
        ss = inner.sharding
        rows_old = int(qs.s_q.shape[0])
        n_old = int(ss.n_global) if inner.is_process_local else rows_old
        n_tot = n_old + n_new
        comm = ShardedStore._comm() if inner.is_process_local else None
        nproc = comm.process_count if comm else 1
        rank = comm.process_index if comm else 0
        if inner.is_process_local and n_tot % nproc != 0:
            raise ValueError(f"grown store size {n_tot} not divisible by "
                             f"{nproc} processes")
        local_n = n_tot // nproc
        new_inner = inner
        if inner.is_process_local:
            new_inner = dataclasses.replace(
                inner, sharding=dataclasses.replace(
                    ss, n_global=n_tot, offset=rank * local_n))
        new_self = dataclasses.replace(self, inner=new_inner)
        new_self.validate(n_tot)
        blk2, _, ring2 = new_self._layout(local_n)
        if blk2 != blk:
            raise ValueError(
                f"quant block changes across grow ({blk} -> {blk2}): "
                f"construct the store with block <= the pre-grow shard "
                f"so block boundaries are stable")
        assert ring2 == ring, (ring, ring2)    # nproc/n_shards unchanged

        ag = inner._assemble_global
        # global views: rows in row order, scales in global block order
        # (aligned boundaries: blk divides both old and new shards), ring
        # in global shard order
        s_q_g = ag(qs.s_q)
        w_q_g = ag(qs.w_q)
        seen_g = ag(qs.seen_q)
        s_sc_g = ag(qs.s_scale)
        w_sc_g = ag(qs.w_scale)
        er_g, et_g = ag(qs.err_rows), ag(qs.err_seq)
        es_g, ew_g = ag(qs.err_s), ag(qs.err_w)

        scale0 = np.float32((1.0 / n_tot) / _QMAX)
        nb_g_new = n_tot // blk
        s_sc_g = np.concatenate([s_sc_g, np.full(
            (nb_g_new - len(s_sc_g),), scale0, np.float32)])
        w_sc_g = np.concatenate([w_sc_g, np.full(
            (nb_g_new - len(w_sc_g),), scale0, np.float32)])
        new_blk = np.arange(n_old, n_tot, dtype=np.int64) // blk
        s_q_g = np.concatenate(
            [s_q_g, self._new_row_codes(n_tot, new_blk, s_sc_g)])
        w_q_g = np.concatenate(
            [w_q_g, self._new_row_codes(n_tot, new_blk, w_sc_g)])
        seen_g = np.concatenate([seen_g, np.zeros((n_new,), np.int8)])

        # re-deal the ring: newest entry per live row, to its new owner
        shard_new = local_n // ss.n_shards
        per_shard = ring // ss.n_shards
        order = np.argsort(-et_g, kind="stable")   # newest first
        live = et_g[order] > 0
        rows_o, seq_o = er_g[order][live], et_g[order][live]
        es_o, ew_o = es_g[order][live], ew_g[order][live]
        _, first = np.unique(rows_o, return_index=True)  # newest per row
        keep = np.sort(first)
        rows_o, seq_o = rows_o[keep], seq_o[keep]
        es_o, ew_o = es_o[keep], ew_o[keep]
        G = nproc * ss.n_shards
        er_n = np.full((G * per_shard,), -1, np.int32)
        et_n = np.zeros((G * per_shard,), np.int32)
        es_n = np.zeros((G * per_shard,), np.float32)
        ew_n = np.zeros((G * per_shard,), np.float32)
        owner = rows_o // shard_new
        for g in range(G):
            here = np.nonzero(owner == g)[0][:per_shard]  # newest-first
            lo = g * per_shard
            er_n[lo:lo + len(here)] = rows_o[here]
            et_n[lo:lo + len(here)] = seq_o[here]
            es_n[lo:lo + len(here)] = es_o[here]
            ew_n[lo:lo + len(here)] = ew_o[here]

        ns = new_inner.sharding.named_sharding()
        nb_local_new = local_n // blk
        off = rank * local_n

        def put(full, lo, ln):
            if inner.is_process_local:
                return jax.device_put(full[lo:lo + ln], ns)
            return jax.make_array_from_callback(
                (len(full),), ns, lambda idx: full[idx])

        leaf = QuantizedScores(
            s_q=put(s_q_g, off, local_n),
            w_q=put(w_q_g, off, local_n),
            seen_q=put(seen_g, off, local_n),
            s_scale=put(s_sc_g, rank * nb_local_new, nb_local_new),
            w_scale=put(w_sc_g, rank * nb_local_new, nb_local_new),
            err_rows=put(er_n, rank * ring, ring),
            err_seq=put(et_n, rank * ring, ring),
            err_s=put(es_n, rank * ring, ring),
            err_w=put(ew_n, rank * ring, ring))
        return new_self, leaf

    # -- placement plumbing ----------------------------------------------
    def leaf_sharding(self) -> Optional[NamedSharding]:
        return self.inner.leaf_sharding()

    def checkpoint_spec(self) -> dict:
        return {"kind": "quantized", "block": int(self.block),
                "residual_rows": int(self.residual_rows),
                "wire": bool(self.wire),
                "inner": self.inner.checkpoint_spec()}

    def checkpoint_partition(self) -> Optional[dict]:
        part = self.inner.checkpoint_partition()
        if part is None:
            return None
        # quantized leaves have heterogeneous lengths (rows vs scale
        # blocks vs ring slots), all split evenly across processes: the
        # block offset of every leaf is rank * local length
        part = dict(part)
        part["per_leaf"] = True
        part["rank"] = part["comm"].process_index
        return part


def make_store(sharding: Optional[ScoreSharding] = None, *,
               quantize: bool = False, block: int = 1024,
               residual_rows: int = 1024, wire: bool = False) -> ScoreStore:
    """The backend for a row layout: ``ShardedStore`` over a
    ``ScoreSharding``, else the replicated default; ``quantize=True``
    wraps either in the int8 ``QuantizedStore`` (``block`` rows per
    scale, ``residual_rows`` error-feedback slots, ``wire=True`` for
    int8 cross-shard payloads)."""
    inner: ScoreStore = ReplicatedStore() if sharding is None \
        else ShardedStore(sharding)
    if not quantize:
        return inner
    return QuantizedStore(inner, block=block, residual_rows=residual_rows,
                          wire=wire)


# ---------------------------------------------------------------------------
# Explicit (unrolled) forms — used by tests and theory benchmarks only
# ---------------------------------------------------------------------------

def explicit_weights(loss_history: jax.Array, beta1: float, beta2: float,
                     s0: float) -> jax.Array:
    """Unrolled Eq. (3.1): loss_history (T,) -> w(T) exactly.

    w(t) = beta1 * s(t-1) + (1-beta1) * l(t) with
    s(t) = beta2^t s0 + (1-beta2) sum_k beta2^{t-k} l(k).
    """
    T = loss_history.shape[0]
    s = s0
    w = s0
    for t in range(T):
        w = beta1 * s + (1.0 - beta1) * loss_history[t]
        s = beta2 * s + (1.0 - beta2) * loss_history[t]
    return w


def expansion_weights(loss_history: jax.Array, beta1: float, beta2: float,
                      s0: float) -> jax.Array:
    """Eq. (3.2): EMA-of-losses + (beta2-beta1)-weighted EMA of differences.

    w(t) = (1-b2) sum_{k=1..t} b2^{t-k} l(k)
         + (b2-b1) sum_{k=1..t-1} b2^{t-1-k} (l(k+1)-l(k))
         + [b1 b2^{t-1} s0 + (b2-b1) b2^{t-1} l(1)]          (exact tail)
    The bracketed tail is the O(beta2^t) term of the proposition, kept exact
    here so tests can assert equality rather than asymptotics.
    """
    lh = loss_history
    T = lh.shape[0]
    t = T  # steps are 1-indexed in the paper
    ema = (1 - beta2) * sum(beta2 ** (t - k) * lh[k - 1] for k in range(1, t + 1))
    dif = (beta2 - beta1) * sum(beta2 ** (t - 1 - k) * (lh[k] - lh[k - 1])
                                for k in range(1, t))
    tail = beta1 * beta2 ** (t - 1) * s0 + (beta2 - beta1) * beta2 ** (t - 1) * lh[0]
    return ema + dif + tail


def transfer_function(beta1: float, beta2: float, omega: jax.Array) -> jax.Array:
    """|H(i w)| of Thm. 3.2 — the frequency response of the ES weight signal."""
    num = (beta2 - beta1) ** 2 * omega ** 2 + (1 - beta2) ** 2
    den = omega ** 2 + (1 - beta2) ** 2
    return jnp.sqrt(num / den)
