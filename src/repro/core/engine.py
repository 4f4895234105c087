"""Composable ES engine — one step builder for every ES(WP) flavour.

The paper frames Evolved Sampling as a plug-and-play framework: batch-level
selection (§3.1), frequency tuning (§3.3), and set-level ESWP pruning
compose freely.  ``ESEngine`` makes that literal by assembling ONE jitted
train step from three orthogonal policies:

  scoring policy   : how/when the meta-batch scoring forward runs —
                       ``baseline``  scoring rides the training forward (free)
                       ``inline``    serial ES, decimated by the cadence
                       ``pipelined`` beyond-paper overlap: score meta-batch
                                     t+1 concurrently with the grad step on
                                     the mini-batch selected from t; the
                                     scoring leg honors the same decimation
                     All decimation goes through the one ``lax.cond`` in
                     ``scheduled_step``/``pipelined_step``, so skipped steps
                     never pay the meta-batch forward.
  selection policy : which mini-batch b of B trains —
                     ``core.selection.select_minibatch`` (gumbel / top-k /
                     uniform), unchanged.
  cadence policy   : when scoring (and set-level pruning) fires —
                       ``static`` the host-side ``FreqSchedule`` (fixed /
                                  warmup / Thm. 3.2 adaptive passband)
                       ``drift``  observed-signal adaptive: a ``CadenceState``
                                  carried in ``TrainState`` tracks an EMA of
                                  the relative per-step score-store scatter
                                  deltas (|Δs|, |Δw|) and servoes the scoring
                                  period (AIMD: double when the store has
                                  gone quiet, halve when it is moving);
                                  the same drift signal drives the ESWP
                                  epoch-pruning cadence host-side
                                  (``should_prune``).

The four step flavours of the former ``core.es_step`` module are thin
wrappers built by this engine (``make_steps``); with a k=1 schedule the
scheduled step is bit-identical to serial ``es_step`` by construction
(asserted by the parity suite in ``tests/test_engine.py``).

Host-side, ``ESEngine.session`` is the single trainer entry point: it owns
the per-epoch pipelined protocol (prime the first meta-batch's weights at
epoch start, carry, FLUSH the held meta-batch at epoch end — no batch is
ever dropped at an epoch boundary) and caches one jitted function per step
kind.

Score-store placement is a ``ScoreStore`` backend (``core.scores``), not
an engine concern: every leg talks to ``self.store`` —
``ReplicatedStore`` (full arrays, direct scatters; the default) or
``ShardedStore`` (rows over the DP mesh axes: ids routed to the owning
device inside shard_map, per-shard masked kernel dispatch, candidate-merge
Gumbel selection — no device materializes a full ``(n,)`` array).  The
fused Pallas ``score_update`` kernel rides the same backend (TPU-compiled;
off-TPU the backends fall back to the XLA scatter;
``ESConfig.fused_scores=False`` forces the scatter path everywhere).

Batch dict: tokens (B,S) i32, labels (B,S) i32 (-1 = masked),
sample_ids (B,) i32, optional grad_scale (B,) f32 (InfoBatch rescale),
optional frames / image_embeds (modality stubs).  PackedSource batches
additionally carry segment_ids/positions (B,S), doc_ids (B,M) and
doc_grad_scale (B,M); ``EpochSession`` routes them to the ``packed``
step flavours, where ES identity is the document, not the row.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models.layers import ShardCtx
from ..models.transformer import lm_per_sample_loss, lm_per_segment_loss
from ..optim.adamw import OptConfig, OptState, init_opt_state, apply_updates
from .frequency import FreqSchedule
from .scores import (ESScores, ScoreSharding, ScoreStore, make_store,
                     weights_from_prev)
from .selection import masked_select_kept, select_minibatch

PyTree = Any
Batch = Dict[str, jax.Array]

_EPS = 1e-12
_NEVER_SCORED = -(1 << 20)   # CadenceState.last_scored init: step 0 fires

STEP_KINDS = ("baseline", "es", "scheduled", "pipelined", "prime", "flush",
              "packed", "packed_baseline")


@dataclasses.dataclass(frozen=True)
class ESConfig:
    method: str = "es"            # es | eswp | loss | order | baseline
    beta1: float = 0.2
    beta2: float = 0.9
    minibatch: int = 64           # b  (selected for BP)
    n_train: int = 1 << 20        # score-store size
    pipelined: bool = False       # beyond-paper overlap variant
    seq_chunk: int = 1024         # xent seq chunking
    fused_scores: bool = True     # Pallas score_update kernel vs XLA scatter


@dataclasses.dataclass(frozen=True)
class CadenceConfig:
    """Cadence policy: when scoring and set-level pruning fire.

    ``static`` delegates the scoring period entirely to the engine's
    ``FreqSchedule`` (fixed / warmup / Thm. 3.2 adaptive) and prunes every
    epoch — exactly the pre-engine behaviour.  ``drift`` replaces both
    static heuristics with the observed training signal: the EMA of the
    relative score-store scatter deltas.
    """
    kind: str = "static"          # static | drift
    rho: float = 0.8              # drift EMA decay
    target: float = 0.05          # relative |Δs| drift the servo tracks
    band: float = 2.0             # hysteresis: grow < target/band,
    #                               shrink > target*band
    k_cap: int = 64               # drift: max scoring period
    prune_kind: str = "epoch"     # epoch (every epoch) | drift
    prune_drift_floor: float = 0.25   # drift: accumulated rel drift that
    #                                   re-arms set-level pruning
    prune_max_interval: int = 4   # drift: prune at least every N epochs

    def __post_init__(self):
        if self.kind not in ("static", "drift"):
            raise ValueError(f"unknown cadence kind {self.kind!r}")
        if self.prune_kind not in ("epoch", "drift"):
            raise ValueError(f"unknown prune cadence {self.prune_kind!r}")
        if self.k_cap < 1:
            raise ValueError(f"k_cap must be >= 1, got {self.k_cap}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CadenceState:
    """Observed score-store drift, carried in ``TrainState``.

    Updated inside the jitted step on every scoring firing; read host-side
    by the trainer for the epoch-pruning cadence.  All leaves are scalars,
    so it checkpoints with the rest of the state for free.
    """
    drift_s: jax.Array     # () f32  EMA of mean |Δs| / mean |s| per firing
    drift_w: jax.Array     # () f32  EMA of mean |Δw| / mean |w| per firing
    period: jax.Array      # () i32  current scoring period
    last_scored: jax.Array  # () i32 opt step of the last scoring firing
    since_prune: jax.Array  # () f32 rel drift accumulated since last prune


def init_cadence() -> CadenceState:
    return CadenceState(
        drift_s=jnp.zeros((), jnp.float32),
        drift_w=jnp.zeros((), jnp.float32),
        period=jnp.ones((), jnp.int32),
        last_scored=jnp.full((), _NEVER_SCORED, jnp.int32),
        since_prune=jnp.zeros((), jnp.float32),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt: OptState
    scores: ESScores
    rng: jax.Array
    pending_w: jax.Array   # (B,) pipelined-ES carried selection weights
    grad_err: PyTree = None  # error-feedback residuals (grad compression)
    cadence: CadenceState = None  # score-store drift (see CadenceState)


def init_train_state(model_cfg: ModelConfig, es_cfg: ESConfig,
                     opt_cfg: OptConfig, key: jax.Array,
                     meta_batch: int,
                     score_sharding: Optional[ScoreSharding] = None,
                     store: Optional[ScoreStore] = None) -> TrainState:
    from ..models.transformer import init_lm
    if store is None:
        store = make_store(score_sharding)
    pkey, rkey = jax.random.split(key)
    params, _ = init_lm(model_cfg, pkey)
    if model_cfg.param_dtype != "float32":
        dt = jnp.dtype(model_cfg.param_dtype)
        params = jax.tree.map(lambda p: p.astype(dt), params)
    grad_err = None
    if getattr(opt_cfg, "compress_grads", False):
        from ..distributed.compression import ErrorFeedbackState
        grad_err = ErrorFeedbackState.init(params)
    return TrainState(
        params=params,
        opt=init_opt_state(opt_cfg, params),
        scores=store.init_leaf(es_cfg.n_train),
        rng=rkey,
        pending_w=jnp.full((meta_batch,), 1.0, jnp.float32),
        grad_err=grad_err,
        cadence=init_cadence(),
    )


def _gather_batch(batch: Batch, idx: jax.Array,
                  keys=("tokens", "labels", "sample_ids", "grad_scale",
                        "frames", "image_embeds")) -> Batch:
    return {k: v[idx] for k, v in batch.items() if k in keys}


class ESEngine:
    """Assemble jitted ES(WP) train steps from orthogonal policies.

    One engine == one compiled family: the scoring policy picks the step
    builder, the selection policy is ``es_cfg.method``, and the cadence
    policy (static FreqSchedule vs drift CadenceState) governs every
    decimated scoring leg AND the set-level pruning cadence.  Policies that
    don't compose by definition (set-level-only ESWP fuses scoring into the
    training forward, so there is nothing to decimate) degrade explicitly
    to the baseline step.
    """

    def __init__(self, model_cfg: ModelConfig, es_cfg: ESConfig,
                 opt_cfg: OptConfig, schedule: Callable, ctx: ShardCtx,
                 freq: Optional[FreqSchedule] = None,
                 cadence: Optional[CadenceConfig] = None,
                 score_sharding: Optional[ScoreSharding] = None,
                 store: Optional[ScoreStore] = None):
        self.model_cfg = model_cfg
        self.es_cfg = es_cfg
        self.opt_cfg = opt_cfg
        self.schedule = schedule
        self.ctx = ctx
        # the one placement decision: every leg goes through this backend
        # (``score_sharding`` kept as a convenience spelling of the
        # sharded backend)
        self.store = store if store is not None else make_store(score_sharding)
        self.store.validate(es_cfg.n_train)
        if getattr(self.store, "is_process_local", False):
            raise NotImplementedError(
                "a per-process-rows ShardedStore (ScoreSharding.n_global "
                "set) completes gather/select host-side between steps and "
                "cannot run inside the jitted engine legs; training on "
                "multi-host meshes uses the global-mesh form "
                "(jax.make_mesh over jax.devices()), the process-local "
                "form drives store-level ops and the CPU-cluster harness")
        self.freq = freq or FreqSchedule()     # default: score every step
        if cadence is None:
            # a drift FreqSchedule implies the drift cadence; its k is the
            # period cap.  A cap of 1 (the FreqSchedule default) would pin
            # the servo to period 1 and silently disable the feature, so —
            # like make_schedule — it opens to the default cap; pass an
            # explicit CadenceConfig(k_cap=1) to really pin it.
            if self.freq.kind == "drift":
                from .frequency import ADAPTIVE_DEFAULT_CAP
                cap = self.freq.target_period
                if cap <= 1:
                    cap = ADAPTIVE_DEFAULT_CAP
                cadence = CadenceConfig(kind="drift", k_cap=cap)
            else:
                cadence = CadenceConfig()
        self.cadence = cadence
        self._loss_fn = self._make_loss_fn()
        self._grad_fn = jax.value_and_grad(self._loss_fn, has_aux=True)
        self._jitted: Dict[str, Callable] = {}

    # ------------------------------------------------------------------
    # shared legs
    # ------------------------------------------------------------------
    def _make_loss_fn(self):
        model_cfg, es_cfg, ctx = self.model_cfg, self.es_cfg, self.ctx

        def fn(params, batch):
            per_sample, _ = lm_per_sample_loss(model_cfg, params, batch, ctx,
                                               seq_chunk=es_cfg.seq_chunk)
            scale = batch.get("grad_scale")
            if scale is not None:
                mean = jnp.mean(per_sample * scale.astype(jnp.float32))
            else:
                mean = jnp.mean(per_sample)
            return mean, per_sample
        return fn

    def _update_scores(self, scores: ESScores, ids: jax.Array,
                       losses: jax.Array) -> ESScores:
        return self.store.update(scores, ids, losses, self.es_cfg.beta1,
                                 self.es_cfg.beta2,
                                 fused=self.es_cfg.fused_scores)

    def _prev_sw(self, scores: ESScores, ids: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """(s[ids], w[ids]) — the backend's gather (direct load, or the
        routed psum-gather when the store is row-sharded)."""
        return self.store.gather(scores, ids)

    def _observe(self, cad: CadenceState, s_prev: jax.Array,
                 w_prev: jax.Array, losses: jax.Array, w_new: jax.Array,
                 step: jax.Array) -> CadenceState:
        """Fold one scoring firing into the drift EMAs; servo the period.

        ``w_new`` is the Eq. (3.1) weight the caller already computed from
        ``s_prev`` (one source of truth for the weight rule);
        ``s_prev``/``w_prev`` are the caller's pre-update gathers, so the
        sharded store pays its routed gather once.  The s-delta follows
        from Eq. (3.1) without a second gather: Δs = (1-β2)(l - s_prev).
        ``rel`` normalizes by the store scale so the servo is loss-scale
        free, and the EMAs fold the PER-STEP drift — the observed rel
        divided by the steps since the last firing — so
        ``CadenceConfig.target`` means the same thing at any scoring
        period k (a store scored every 4th step legitimately moves ~4x
        more per firing; without the normalization the servo would read
        that as 4x the drift and never grow the period).  At k=1 the
        divisor is exactly 1: pre-normalization behaviour, pinned by the
        regression suite.  In drift mode the period is AIMD-adapted
        inside the band; in static mode it just mirrors the FreqSchedule
        for observability.
        """
        c = self.cadence
        b2 = self.es_cfg.beta2
        d_s = jnp.mean(jnp.abs((1.0 - b2) * (losses - s_prev)))
        d_w = jnp.mean(jnp.abs(w_new - w_prev))
        rel_s = d_s / (jnp.mean(jnp.abs(s_prev)) + _EPS)
        rel_w = d_w / (jnp.mean(jnp.abs(w_prev)) + _EPS)
        # steps since the last firing (1 on the very first firing: the
        # sentinel init would otherwise divide the first observation away)
        never = cad.last_scored <= _NEVER_SCORED // 2
        k_eff = jnp.where(never, 1,
                          jnp.maximum(step - cad.last_scored, 1)
                          ).astype(jnp.float32)
        drift_s = c.rho * cad.drift_s + (1.0 - c.rho) * rel_s / k_eff
        drift_w = c.rho * cad.drift_w + (1.0 - c.rho) * rel_w / k_eff
        if c.kind == "drift":
            grow = drift_s < c.target / c.band
            shrink = drift_s > c.target * c.band
            period = jnp.where(grow, cad.period * 2,
                               jnp.where(shrink, cad.period // 2,
                                         cad.period))
            period = jnp.clip(period, 1, c.k_cap).astype(jnp.int32)
        else:
            period = self.freq.period_at(step).astype(jnp.int32)
        return CadenceState(
            drift_s=drift_s, drift_w=drift_w, period=period,
            last_scored=jnp.asarray(step, jnp.int32),
            since_prune=cad.since_prune + rel_s,
        )

    def _fire(self, state: TrainState) -> jax.Array:
        """Bool: does this step run the (decimated) scoring forward?"""
        if self.cadence.kind == "drift":
            return (state.opt.step - state.cadence.last_scored) \
                >= state.cadence.period
        return self.freq.should_score(state.opt.step)

    def _score_leg(self, state: TrainState, batch: Batch
                   ) -> Tuple[jax.Array, ESScores, CadenceState, jax.Array]:
        """Scoring forward + Eq. (3.1) + cadence bookkeeping.

        -> (weights, new scores, new cadence, meta loss).  Shared by every
        scoring policy so inline / pipelined / prime stay bit-identical at
        scoring steps.
        """
        meta_losses, _ = lm_per_sample_loss(
            self.model_cfg, jax.lax.stop_gradient(state.params), batch,
            self.ctx, seq_chunk=self.es_cfg.seq_chunk)
        meta_losses = jax.lax.stop_gradient(meta_losses)
        ids = batch["sample_ids"]
        s_prev, w_prev = self._prev_sw(state.scores, ids)
        w = weights_from_prev(s_prev, meta_losses, self.es_cfg.beta1)
        cad = self._observe(state.cadence, s_prev, w_prev, meta_losses,
                            w, state.opt.step)
        new_scores = self._update_scores(state.scores, ids, meta_losses)
        return w, new_scores, cad, jnp.mean(meta_losses)

    def _stale_leg(self, state: TrainState, batch: Batch
                   ) -> Tuple[jax.Array, ESScores, CadenceState, jax.Array]:
        """Skipped scoring: reuse the last Eq. (3.1) weights for this
        batch's samples; store and cadence are untouched."""
        ids = batch["sample_ids"]
        s_prev, w_prev = self._prev_sw(state.scores, ids)
        return w_prev, state.scores, state.cadence, jnp.mean(s_prev)

    def _optim(self, state: TrainState, grads: PyTree,
               metrics: Dict[str, jax.Array]):
        new_err = state.grad_err
        if getattr(self.opt_cfg, "compress_grads", False):
            # int8 quantize->dequantize with error feedback: models the
            # lossy leg of the compressed DP all-reduce on the same
            # per-block grid as the wire (distributed/compression.
            # _compressed_reduce_1d under shard_map)
            from ..distributed.compression import compress_decompress
            pairs = jax.tree.map(compress_decompress, grads, state.grad_err)
            grads = jax.tree.map(lambda t: t[0], pairs,
                                 is_leaf=lambda t: isinstance(t, tuple))
            new_err = jax.tree.map(lambda t: t[1], pairs,
                                   is_leaf=lambda t: isinstance(t, tuple))
        lr_scale = self.schedule(state.opt.step)
        new_params, new_opt, opt_metrics = apply_updates(
            self.opt_cfg, state.params, grads, state.opt, lr_scale)
        metrics.update(opt_metrics)
        metrics["lr_scale"] = lr_scale
        return new_params, new_opt, new_err

    # ------------------------------------------------------------------
    # step flavours (all pjit-able, static shapes, no host sync)
    # ------------------------------------------------------------------
    def baseline_step(self, state: TrainState, batch: Batch
                      ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Standard batched training; still updates the score store (and
        the drift EMAs) from the free per-sample losses of the training
        forward — the paper's "can be omitted" remark (§3.3)."""
        (mean, per_sample), grads = self._grad_fn(state.params, batch)
        metrics = {"loss": mean, "bp_samples": jnp.asarray(
            batch["tokens"].shape[0], jnp.float32),
            # scoring rides the training forward: no dedicated forward ran
            "scored": jnp.zeros((), jnp.float32)}
        new_params, new_opt, new_err = self._optim(state, grads, metrics)
        losses = jax.lax.stop_gradient(per_sample)
        ids = batch["sample_ids"]
        s_prev, w_prev = self._prev_sw(state.scores, ids)
        w_new = weights_from_prev(s_prev, losses, self.es_cfg.beta1)
        cad = self._observe(state.cadence, s_prev, w_prev, losses,
                            w_new, state.opt.step)
        scores = self._update_scores(state.scores, ids, losses)
        return dataclasses.replace(state, params=new_params, opt=new_opt,
                                   scores=scores, grad_err=new_err,
                                   cadence=cad), metrics

    # ------------------------------------------------------------------
    def es_step(self, state: TrainState, batch: Batch
                ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Paper-faithful serial ES: scoring forward on the meta-batch,
        Eq. (3.1) update, Gumbel top-k selection, fwd+bwd on the
        mini-batch.  Never decimated (the ``es`` flavour is the k=1
        anchor the parity suite pins everything else to)."""
        B = batch["tokens"].shape[0]
        b = min(self.es_cfg.minibatch, B)
        if b >= B:
            # set-level-only ESWP: fuse scoring into the training forward
            return self.baseline_step(state, batch)

        # (1)+(2) scoring forward + Eq. (3.1) weight/score update
        w, scores, cad, meta_loss = self._score_leg(state, batch)

        # (3) mini-batch selection (replicated PRNG: same on all hosts)
        rng, sel_key = jax.random.split(state.rng)
        idx = select_minibatch(self.es_cfg.method, sel_key, w, b,
                               store=self.store)
        sel = _gather_batch(batch, idx)

        # (4) grad step on the mini-batch
        (mean, _), grads = self._grad_fn(state.params, sel)
        metrics = {
            "loss": meta_loss,
            "sel_loss": mean,
            "bp_samples": jnp.asarray(b, jnp.float32),
            "w_mean": jnp.mean(w),
            "w_max": jnp.max(w),
            "scored": jnp.ones((), jnp.float32),
            "sel_ids": idx,
        }
        new_params, new_opt, new_err = self._optim(state, grads, metrics)
        return dataclasses.replace(state, params=new_params, opt=new_opt,
                                   scores=scores, rng=rng, grad_err=new_err,
                                   cadence=cad), metrics

    # ------------------------------------------------------------------
    def scheduled_step(self, state: TrainState, batch: Batch
                       ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Cadence-decimated ES: run the scoring forward only when the
        cadence fires (static FreqSchedule or drift servo); in between,
        select with the stale store weights.  The branch is a runtime
        ``lax.cond``, so one compiled graph serves both phases and skipped
        steps never pay the meta-batch forward."""
        B = batch["tokens"].shape[0]
        b = min(self.es_cfg.minibatch, B)
        if b >= B:
            # set-level-only ESWP: scoring rides the training forward for
            # free, so there is nothing to decimate
            return self.baseline_step(state, batch)
        if self.cadence.kind != "drift" and self.freq.always_scores():
            return self.es_step(state, batch)  # k=1: decimation is a no-op

        do_score = self._fire(state)
        w, scores, cad, meta_loss = jax.lax.cond(
            do_score,
            lambda _: self._score_leg(state, batch),
            lambda _: self._stale_leg(state, batch),
            None)

        rng, sel_key = jax.random.split(state.rng)
        idx = select_minibatch(self.es_cfg.method, sel_key, w, b,
                               store=self.store)
        sel = _gather_batch(batch, idx)

        (mean, _), grads = self._grad_fn(state.params, sel)
        metrics = {
            # skipped steps have no meta loss; log the measured sel loss
            "loss": jnp.where(do_score, meta_loss, mean),
            "sel_loss": mean,
            "bp_samples": jnp.asarray(b, jnp.float32),
            "w_mean": jnp.mean(w),
            "w_max": jnp.max(w),
            "scored": do_score.astype(jnp.float32),
            "cad_period": cad.period.astype(jnp.float32),
            "sel_ids": idx,
        }
        new_params, new_opt, new_err = self._optim(state, grads, metrics)
        return dataclasses.replace(state, params=new_params, opt=new_opt,
                                   scores=scores, rng=rng, grad_err=new_err,
                                   cadence=cad), metrics

    # ------------------------------------------------------------------
    def pipelined_step(self, state: TrainState,
                       batches: Tuple[Batch, Batch]
                       ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """batches = (current, next).  Train on `current` using weights
        scored LAST step (state.pending_w); score `next` with pre-update
        params (1-step staleness).  The two subgraphs are independent, so
        XLA overlaps them.  The scoring leg honors the cadence: on skipped
        steps `next`'s weights come from the (stale) store instead."""
        cur, nxt = batches
        B = cur["tokens"].shape[0]
        b = min(self.es_cfg.minibatch, B)
        if b >= B:
            # set-level-only ESWP: no sub-selection, so scoring rides the
            # training forward for free (`nxt` is scored when it becomes
            # current) — an overlap scoring leg would double the cost
            return self.baseline_step(state, cur)

        # train on current meta-batch with carried weights
        rng, sel_key = jax.random.split(state.rng)
        idx = select_minibatch(self.es_cfg.method, sel_key, state.pending_w,
                               b, store=self.store)
        sel = _gather_batch(cur, idx)
        (mean, _), grads = self._grad_fn(state.params, sel)

        if self.cadence.kind != "drift" and self.freq.always_scores():
            do_score = jnp.ones((), bool)
            w_next, scores, cad, nxt_loss = self._score_leg(state, nxt)
        else:
            do_score = self._fire(state)
            w_next, scores, cad, nxt_loss = jax.lax.cond(
                do_score,
                lambda _: self._score_leg(state, nxt),
                lambda _: self._stale_leg(state, nxt),
                None)

        metrics = {
            # skipped steps have no meta loss (the stale leg returns the
            # store EMA, ~1/n for unseen ids); log the measured sel loss
            "loss": jnp.where(do_score, nxt_loss, mean),
            "sel_loss": mean,
            "bp_samples": jnp.asarray(b, jnp.float32),
            "scored": do_score.astype(jnp.float32),
            "cad_period": cad.period.astype(jnp.float32)}
        new_params, new_opt, new_err = self._optim(state, grads, metrics)
        return dataclasses.replace(state, params=new_params, opt=new_opt,
                                   scores=scores, rng=rng, pending_w=w_next,
                                   grad_err=new_err, cadence=cad), metrics

    # ------------------------------------------------------------------
    def prime_step(self, state: TrainState, batch: Batch) -> TrainState:
        """Scoring-only step (pipelined epoch start): fill ``pending_w``
        for the first meta-batch so its training step selects with weights
        scored for IT, not for the previous epoch's tail.  No optimizer
        update, so the step counter is untouched.

        The prime runs at the same optimizer step as the first pipelined
        step; its firing is backdated one slot so a period-1 cadence still
        scores that first step (``step - last_scored == 1 >= 1``) instead
        of being suppressed by its own prime."""
        B = batch["tokens"].shape[0]
        if min(self.es_cfg.minibatch, B) >= B:
            # set-level-only ESWP pipelines as baseline steps: scoring is
            # fused into each training forward, nothing to prime
            return state
        w, scores, cad, _ = self._score_leg(state, batch)
        cad = dataclasses.replace(
            cad, last_scored=jnp.asarray(state.opt.step - 1, jnp.int32))
        return dataclasses.replace(state, scores=scores, pending_w=w,
                                   cadence=cad)

    def flush_step(self, state: TrainState, batch: Batch
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Train-only step (pipelined epoch end): drain the held meta-batch
        with its carried weights.  No next batch exists, so there is no
        scoring leg."""
        B = batch["tokens"].shape[0]
        b = min(self.es_cfg.minibatch, B)
        if b >= B:
            # set-level-only ESWP: the held batch trains (and scores) as a
            # plain fused baseline step
            return self.baseline_step(state, batch)
        rng, sel_key = jax.random.split(state.rng)
        idx = select_minibatch(self.es_cfg.method, sel_key, state.pending_w,
                               b, store=self.store)
        sel = _gather_batch(batch, idx)
        (mean, _), grads = self._grad_fn(state.params, sel)
        metrics = {"loss": mean, "sel_loss": mean,
                   "bp_samples": jnp.asarray(b, jnp.float32),
                   "scored": jnp.zeros((), jnp.float32)}
        new_params, new_opt, new_err = self._optim(state, grads, metrics)
        return dataclasses.replace(state, params=new_params, opt=new_opt,
                                   rng=rng, grad_err=new_err), metrics

    # ------------------------------------------------------------------
    def _packed_impl(self, state: TrainState, batch: Batch, select: bool
                     ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Segment-granular ES on a ``PackedSource`` batch.

        One forward serves both scoring and training: dropped segments
        share their rows with kept ones, so a dedicated scoring forward
        would recompute the identical hidden states.  Inside ``loss_fn``
        the stop-gradiented per-segment NLLs feed Eq. (3.1) against the
        gathered prior scores, the (masked) Gumbel top-k keeps b of the
        valid document slots, and the training loss is the kept-slot mean
        — a dropped document's loss term is multiplied by exactly zero, so
        it contributes nothing to the gradient.  The score store is keyed
        by global DOCUMENT ids (``batch["doc_ids"]``); empty/pruned slots
        carry id -1, which the backends' shared masking rule drops.
        """
        doc_ids = batch["doc_ids"]                       # (B, M)
        B, M = doc_ids.shape
        n = B * M
        flat_ids = doc_ids.reshape(n)
        valid = flat_ids >= 0
        validf = valid.astype(jnp.float32)
        safe = jnp.where(valid, flat_ids, 0)             # clamp for gather
        s_prev, w_prev = self._prev_sw(state.scores, safe)
        b = min(self.es_cfg.minibatch, n)
        select = select and b < n
        rng, sel_key = jax.random.split(state.rng)
        gs = batch.get("doc_grad_scale")
        scale = gs.reshape(n) if gs is not None else jnp.ones((n,), jnp.float32)

        def loss_fn(params):
            per_seg, _ = lm_per_segment_loss(
                self.model_cfg, params, batch, self.ctx,
                seq_chunk=self.es_cfg.seq_chunk)
            losses = jax.lax.stop_gradient(per_seg.reshape(n))
            w = jnp.where(valid,
                          weights_from_prev(s_prev, losses,
                                            self.es_cfg.beta1), 0.0)
            if select:
                kept = masked_select_kept(self.es_cfg.method, sel_key, w,
                                          valid, b)
            else:
                kept = valid
            kf = kept.astype(jnp.float32)
            mean = (jnp.sum(per_seg.reshape(n) * kf * scale)
                    / jnp.maximum(jnp.sum(kf), 1.0))
            return mean, (losses, w, kept)

        (mean, (losses, w, kept)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)

        n_valid = jnp.maximum(jnp.sum(validf), 1.0)
        metrics = {
            "loss": jnp.sum(losses * validf) / n_valid,
            "sel_loss": mean,
            "bp_samples": jnp.sum(kept.astype(jnp.float32)),
            "seg_valid": jnp.sum(validf),
            "w_mean": jnp.sum(w) / n_valid,
            "w_max": jnp.max(w),
            # scoring rides the training forward: no dedicated forward ran
            "scored": jnp.zeros((), jnp.float32),
        }
        new_params, new_opt, new_err = self._optim(state, grads, metrics)
        # invalid slots observe zero drift and update nothing (-1 drops)
        losses_obs = jnp.where(valid, losses, s_prev)
        w_obs = jnp.where(valid, w, w_prev)
        cad = self._observe(state.cadence, s_prev, w_prev, losses_obs,
                            w_obs, state.opt.step)
        scores = self._update_scores(state.scores,
                                     jnp.where(valid, flat_ids, -1), losses)
        return dataclasses.replace(state, params=new_params, opt=new_opt,
                                   scores=scores, rng=rng, grad_err=new_err,
                                   cadence=cad), metrics

    def packed_step(self, state: TrainState, batch: Batch
                    ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Packed batch with segment-level selection (fused scoring)."""
        return self._packed_impl(state, batch, select=True)

    def packed_baseline_step(self, state: TrainState, batch: Batch
                             ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Packed batch, selection off: every valid document trains; the
        store still updates from the free per-segment losses (set-level
        ESWP pruning over documents rides on top via the source's
        kept-docs mask)."""
        return self._packed_impl(state, batch, select=False)

    # ------------------------------------------------------------------
    # host-side API
    # ------------------------------------------------------------------
    def build_step(self, kind: str) -> Callable:
        """The (unjitted) step function for one scoring policy."""
        if kind not in STEP_KINDS:
            raise ValueError(f"unknown step kind {kind!r}; "
                             f"expected one of {STEP_KINDS}")
        return getattr(self, f"{kind}_step")

    def jitted(self, kind: str) -> Callable:
        """Jitted (donating) step, cached per kind — one compile each."""
        if kind not in self._jitted:
            self._jitted[kind] = jax.jit(self.build_step(kind),
                                         donate_argnums=0)
        return self._jitted[kind]

    def make_steps(self) -> Dict[str, Callable]:
        """Legacy ``core.es_step.make_steps`` surface: the four flavours."""
        return {"baseline_step": self.baseline_step,
                "es_step": self.es_step,
                "scheduled_step": self.scheduled_step,
                "pipelined_step": self.pipelined_step}

    def session(self, selection_on: bool, pipelined: bool) -> "EpochSession":
        """One epoch's driver (see ``EpochSession``)."""
        return EpochSession(self, selection_on, pipelined)

    # -- set-level (epoch) pruning cadence ------------------------------
    def prune_decision(self, cad: Optional[CadenceState],
                       epochs_since_prune: int) -> Tuple[bool, str]:
        """Host-side: does set-level pruning re-run before this epoch?

        Returns (fired, reason) — the reason string is surfaced in the
        trainer's metrics log for ESWP stale-``grad_scale`` auditing.

        ``epoch`` cadence: always (the pre-engine behaviour).  ``drift``
        cadence: only once the accumulated relative score drift since the
        last prune crosses the floor — a converged store keeps its kept-set
        — with a max-interval backstop bounding the InfoBatch-style bias of
        a stale kept-set.  ``epochs_since_prune`` counts inclusively of the
        epoch being gated: with ``prune_max_interval = N`` a prune happens
        at least every N epochs.
        """
        if self.cadence.prune_kind == "epoch":
            return True, "epoch-cadence"
        if epochs_since_prune >= self.cadence.prune_max_interval:
            return True, "max-interval"
        if cad is None:
            return True, "no-cadence-state"
        if float(cad.since_prune) >= self.cadence.prune_drift_floor:
            return True, "drift"
        return False, "drift-below-floor"

    def should_prune(self, cad: Optional[CadenceState],
                     epochs_since_prune: int) -> bool:
        return self.prune_decision(cad, epochs_since_prune)[0]

    def reset_prune_drift(self, state: TrainState) -> TrainState:
        """Zero the accumulated drift after a prune (host-side)."""
        cad = dataclasses.replace(state.cadence,
                                  since_prune=jnp.zeros((), jnp.float32))
        return dataclasses.replace(state, cadence=cad)


class EpochSession:
    """Per-epoch host driver: one entry point for every scoring policy.

    Dispatches each loader batch to the engine's jitted step and owns the
    pipelined prime/carry/flush protocol:

      * first batch: ``prime_step`` scores it (fills ``pending_w``) and the
        batch is held — ``step`` returns ``(state, None)``;
      * subsequent batches: ``pipelined_step`` trains the held batch while
        scoring the new one;
      * ``finish`` drains the held batch with ``flush_step`` so the last
        meta-batch of the epoch is trained, not dropped.

    Non-pipelined sessions route to ``scheduled_step`` (which inlines
    serial ES at k=1) or ``baseline_step`` when selection is annealed off.
    """

    def __init__(self, engine: ESEngine, selection_on: bool,
                 pipelined: bool):
        self.engine = engine
        self.selection_on = selection_on
        self.pipelined = pipelined and selection_on
        self._held: Optional[Batch] = None
        # dedicated scoring forwards run by prime steps (not visible in
        # step metrics — the trainer folds this into scoring_steps_total)
        self.scoring_primes = 0

    @property
    def has_held(self) -> bool:
        """True when a pipelined meta-batch is primed but not yet trained
        (recorded in the checkpoint cursor so resume can rebuild it)."""
        return self._held is not None

    def resume_held(self, batch: Batch) -> None:
        """Reinstall the held meta-batch after a mid-epoch restore.

        The restored ``TrainState.pending_w`` already carries the weights
        scored for this batch before the checkpoint, so no re-prime runs —
        the resumed trajectory stays bit-identical to the uninterrupted
        one (a re-prime would re-score with post-restore params)."""
        assert self.pipelined and self._held is None
        self._held = batch

    def run(self, state: TrainState, stream, on_metrics=None) -> TrainState:
        """Drive one epoch from a batch stream (the data pipeline's
        ``Prefetcher``/``SyncStream`` or any iterable of device batches).

        Steps every batch — pipelined primes included — and returns the
        final state.  ``on_metrics(metrics)`` fires after each *trained*
        step; returning truthy stops the epoch early.  The caller still
        invokes ``finish`` to drain a pipelined carry.
        """
        for batch in stream:
            state, m = self.step(state, batch)
            if m is not None and on_metrics is not None and on_metrics(m):
                break
        return state

    def step(self, state: TrainState, batch: Batch
             ) -> Tuple[TrainState, Optional[Dict[str, jax.Array]]]:
        eng = self.engine
        if "doc_ids" in batch:
            # packed batches: scoring is fused into the training forward,
            # so there is no separate scoring leg to decimate or overlap —
            # pipelined sessions run the packed step serially
            kind = "packed" if self.selection_on else "packed_baseline"
            return eng.jitted(kind)(state, batch)
        if not self.selection_on:
            return eng.jitted("baseline")(state, batch)
        if not self.pipelined:
            return eng.jitted("scheduled")(state, batch)
        if self._held is None:
            B = batch["tokens"].shape[0]
            if min(eng.es_cfg.minibatch, B) < B:
                self.scoring_primes += 1   # b >= B primes are no-ops
            state = eng.jitted("prime")(state, batch)
            self._held = batch
            return state, None
        state, m = eng.jitted("pipelined")(state, (self._held, batch))
        self._held = batch
        return state, m

    def finish(self, state: TrainState
               ) -> Tuple[TrainState, Optional[Dict[str, jax.Array]]]:
        if self._held is None:
            return state, None
        held, self._held = self._held, None
        return self.engine.jitted("flush")(state, held)


def make_steps(model_cfg: ModelConfig, es_cfg: ESConfig, opt_cfg: OptConfig,
               schedule: Callable, ctx: ShardCtx,
               freq: Optional[FreqSchedule] = None,
               cadence: Optional[CadenceConfig] = None
               ) -> Dict[str, Callable]:
    """Build {baseline_step, es_step, scheduled_step, pipelined_step}.

    Compatibility wrapper over ``ESEngine`` — existing callers keep
    working; new code should construct the engine directly (it also
    exposes ``prime``/``flush`` and the per-epoch ``session`` driver).
    """
    return ESEngine(model_cfg, es_cfg, opt_cfg, schedule, ctx,
                    freq=freq, cadence=cadence).make_steps()
