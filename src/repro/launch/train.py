"""End-to-end ES(WP) trainer: annealing, epoch pruning, checkpoint/resume,
preemption handling, straggler monitoring, metrics logging.

The step layer is the composable ``ESEngine`` (``core/engine.py``): the
trainer builds ONE engine and drives every epoch through its
``EpochSession``.  The data layer is the streaming pipeline
(``data/pipeline``): a pluggable ``Source`` (synthetic LM, memory-mapped
token bins, sharded files, packed SFT) feeds an ES-aware resumable
sampler, and an async double-buffered prefetcher builds + device-places
batch t+1 while the device runs step t, so the host data path no longer
serializes against the train step.  The sampler cursor (epoch, step,
kept-set digest) rides the checkpoint manifest — with the kept-set and
grad-scale arrays in the checkpoint's extras channel — making mid-epoch
resume bit-exact: the restored run sees exactly the remaining batch ids,
kept-set and grad scales of the uninterrupted one.

CPU-runnable with the smoke configs; the same code path drives the pod
meshes (mesh selection is by device count).  Usage:

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --method eswp --epochs 6 --meta-batch 32 --minibatch 8
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..configs.registry import get_config, get_smoke_config, list_archs
from ..core.annealing import AnnealSchedule
from ..core.engine import CadenceConfig, ESConfig, ESEngine, init_train_state
from ..core.frequency import make_schedule
from ..core.scores import ScoreStore, make_store
from ..checkpoint.checkpointer import Checkpointer
from ..data.pipeline import DataPipeline, SyntheticSource, get_source
from ..data.synthetic import SyntheticConfig, SyntheticLM
from ..distributed.fault_tolerance import PreemptionHandler, StragglerMonitor
from ..models.layers import ShardCtx
from ..optim.adamw import OptConfig
from ..optim.schedule import get_schedule
from .compile_cache import use_compile_cache
from .inputs import host_batch_placer


@dataclasses.dataclass
class TrainerConfig:
    arch: str = "llama3-8b"
    smoke: bool = True
    method: str = "es"            # es | eswp | loss | order | baseline |
    #                               infobatch | ucb | ka | random
    epochs: int = 4
    meta_batch: int = 32
    minibatch: int = 8
    beta1: float = 0.2
    beta2: float = 0.9
    pruning_ratio: float = 0.2
    anneal_ratio: float = 0.05
    n_samples: int = 1024
    seq_len: int = 64
    lr: float = 1e-3
    schedule: str = "cosine"
    optimizer: str = "adamw"
    seed: int = 0
    pipelined: bool = False
    score_every: int = 1          # k: scoring forward every k-th step (§3.3)
    freq_schedule: str = "fixed"  # fixed | warmup | adaptive | drift
    gain_floor: float = 0.5       # adaptive: retained Thm. 3.2 passband
    drift_target: float = 0.05    # drift: relative |Δs| the servo tracks
    prune_cadence: str = "epoch"  # epoch | drift (set-level re-prune gate)
    prune_max_interval: int = 4   # drift prune cadence: epochs backstop
    fused_scores: bool = True     # Pallas score_update kernel in the step
    shard_scores: bool = False    # row-shard ESScores over the DP devices
    quant_scores: bool = False    # int8 score store with error feedback
    quant_block: int = 1024       # rows per int8 scale block
    quant_wire: bool = False      # int8 cross-shard gather/select payloads
    host_id: Optional[int] = None    # data-slicing host id; default:
    #                                  jax.process_index() (test override)
    num_hosts: Optional[int] = None  # default: jax.process_count()
    grad_compression: bool = False   # int8 EF gradient compression
    source: str = "synthetic"     # synthetic | tokens | sharded | sft | packed
    data_path: Optional[str] = None  # bin / glob / jsonl for real sources
    pack: bool = False            # sequence packing: --source packed shortcut
    max_segments: int = 4         # packed: max documents per row
    prefetch: bool = True         # async double-buffered host data path
    prefetch_depth: int = 2
    drop_last: bool = True        # False: train the partial final batch
    ckpt_dir: Optional[str] = None
    ckpt_every_steps: int = 50
    log_path: Optional[str] = None
    max_steps: Optional[int] = None   # early stop (for tests/benchmarks)


SET_LEVEL = {"eswp", "infobatch", "ucb", "ka", "random"}
BATCH_LEVEL = {"es", "eswp", "loss", "order"}


class Trainer:
    def __init__(self, tc: TrainerConfig,
                 model_cfg: Optional[ModelConfig] = None,
                 dataset: Optional[SyntheticLM] = None,
                 source=None):
        self.tc = tc
        self.model_cfg = model_cfg or (
            get_smoke_config(tc.arch) if tc.smoke else get_config(tc.arch))
        vocab = self.model_cfg.vocab_size
        if tc.pack and tc.source not in ("packed",):
            tc = self.tc = dataclasses.replace(tc, source="packed")
        if source is None:
            if dataset is not None:
                source = SyntheticSource(dataset)
            elif tc.source == "synthetic":
                source = SyntheticSource(SyntheticLM(SyntheticConfig(
                    n_samples=tc.n_samples, seq_len=tc.seq_len,
                    vocab_size=min(vocab, 64), seed=tc.seed)))
            else:
                source = get_source(tc.source, path=tc.data_path,
                                    n_samples=tc.n_samples,
                                    seq_len=tc.seq_len,
                                    vocab_size=min(vocab, 64), seed=tc.seed,
                                    max_segments=tc.max_segments)
        self.source = source
        # packed sources: ES identity (score rows, selection, pruning) is
        # the DOCUMENT; the sampler/meta-batch dimension stays the row
        self.doc_level = hasattr(source, "set_kept_docs")
        self.n_train = source.n_docs if self.doc_level else len(source)
        # the underlying dataset where one exists (synthetic introspection)
        self.ds = getattr(source, "ds", source)
        self.ctx = ShardCtx()
        self._placer = host_batch_placer(self.ctx)
        # real host identity: each host loads only its rows of every
        # global batch (hardcoding 0/1 here would train every row on every
        # host of a multi-process run); tc overrides exist for tests
        self.host_id = tc.host_id if tc.host_id is not None \
            else jax.process_index()
        self.num_hosts = tc.num_hosts if tc.num_hosts is not None \
            else jax.process_count()
        self.pipeline = DataPipeline(self.source, tc.meta_batch,
                                     seed=tc.seed,
                                     host_id=self.host_id,
                                     num_hosts=self.num_hosts,
                                     drop_last=tc.drop_last,
                                     prefetch=tc.prefetch,
                                     depth=tc.prefetch_depth,
                                     place=self._placer)
        self.loader = self.pipeline   # legacy alias (pruning hook, _kept)

        beta1, beta2 = tc.beta1, tc.beta2
        if tc.method == "loss":
            beta1 = beta2 = 0.0            # paper Eq. (2.3)
        if tc.method == "eswp":
            beta2 = min(beta2, 0.8)        # paper default for ESWP
        sel_method = tc.method if tc.method in BATCH_LEVEL else "baseline"
        minibatch = tc.minibatch if tc.method in BATCH_LEVEL else tc.meta_batch
        self.es_cfg = ESConfig(method=sel_method if sel_method != "baseline"
                               else "es",
                               beta1=beta1, beta2=beta2,
                               minibatch=minibatch,
                               n_train=self.n_train,
                               pipelined=tc.pipelined,
                               seq_chunk=0, fused_scores=tc.fused_scores)
        self.sel_method = sel_method
        self.opt_cfg = OptConfig(kind=tc.optimizer, lr=tc.lr,
                                 state_dtype=self.model_cfg.optimizer_dtype,
                                 compress_grads=tc.grad_compression)
        self.anneal = AnnealSchedule.from_ratio(tc.epochs, tc.anneal_ratio)
        # pruning-aware step horizons: an ESWP epoch runs over the KEPT
        # set, so the lr schedule total and the warmup/frequency horizon
        # are computed from the planned per-epoch step counts, not from
        # the unpruned n_samples (they'd overshoot by pruning_ratio)
        steps_first = self.planned_steps_per_epoch(0)
        total_steps = sum(
            self.planned_steps_per_epoch(pruned=p) * c
            for p, c in self._epoch_counts())
        self.schedule = get_schedule(tc.schedule, max(total_steps, 1),
                                     warmup_steps=steps_first // 2)
        self.freq = make_schedule(tc.freq_schedule, tc.score_every,
                                  steps_per_epoch=steps_first,
                                  beta1=beta1, beta2=beta2,
                                  gain_floor=tc.gain_floor)
        self.score_sharding = self._make_score_sharding() \
            if tc.shard_scores else None
        # the one placement decision: every consumer (engine legs, state
        # init, pruning, checkpoint) goes through this backend
        self.score_store: ScoreStore = make_store(
            self.score_sharding, quantize=tc.quant_scores,
            block=tc.quant_block, wire=tc.quant_wire)
        cadence = CadenceConfig(
            kind="drift" if tc.freq_schedule == "drift" else "static",
            target=tc.drift_target,
            k_cap=self.freq.target_period,
            prune_kind=tc.prune_cadence,
            prune_max_interval=tc.prune_max_interval)
        # the single step-layer entry point: every flavour (baseline /
        # serial / decimated / pipelined + prime/flush) is engine-built
        self.engine = ESEngine(self.model_cfg, self.es_cfg, self.opt_cfg,
                               self.schedule, self.ctx, freq=self.freq,
                               cadence=cadence, store=self.score_store)
        self.ckpt = Checkpointer(tc.ckpt_dir) if tc.ckpt_dir else None
        self.preempt = PreemptionHandler().install()
        self.straggler = StragglerMonitor()
        self.metrics_log: list = []
        self.prune_events: list = []
        self.epoch_log: list = []
        self.bp_samples_total = 0.0
        self.scoring_steps_total = 0.0
        self.prev_epoch_losses: Optional[np.ndarray] = None
        self.epochs_since_prune = 0
        self._pruned_in_process = False
        self._eval_fn = None
        self._cur_sess = None
        self._epoch_consumed = 0
        # called as hook(trainer, epoch) after every trained step — the
        # online scoring service polls admission here, interleaved
        # deterministically with training
        self.step_hooks: list = []

        key = jax.random.PRNGKey(tc.seed)
        self.state = init_train_state(self.model_cfg, self.es_cfg,
                                      self.opt_cfg, key, tc.meta_batch,
                                      store=self.score_store)
        self.global_step = 0
        self.start_epoch = 0
        self._resume_step = 0          # consumed meta-batches mid-epoch
        self._resume_held = False      # pipelined carry at checkpoint time
        if self.ckpt and self.ckpt.latest_step() is not None:
            self._resume()

    # ------------------------------------------------------------------
    def _steps_for(self, n: int) -> int:
        mb = self.tc.meta_batch
        return max(1, n // mb if self.tc.drop_last else -(-n // mb))

    def planned_steps_per_epoch(self, epoch: int = 0,
                                pruned: Optional[bool] = None) -> int:
        """Step horizon of ``epoch`` as planned at init: the kept-set size
        for set-level methods inside the annealing window, full n outside.
        The *actual* per-epoch count is re-read from the sampler at each
        epoch start (``epoch_log``) — they agree except when a drift-gated
        prune skips (the kept-set carries over, same size)."""
        if pruned is None:
            pruned = (self.tc.method in SET_LEVEL
                      and self.anneal.selection_active(epoch))
        n = len(self.source)
        # doc-level pruning drops documents *inside* rows: every row still
        # streams, so the step horizon is the unpruned row count
        if pruned and not self.doc_level:
            n = max(1, int(round((1.0 - self.tc.pruning_ratio) * n)))
        return self._steps_for(n)

    def _epoch_counts(self):
        """[(pruned?, epoch count)] over the whole run — no epoch loop, so
        examples that bound by max_steps with epochs=10**6 stay O(1)."""
        e = self.tc.epochs
        if self.tc.method not in SET_LEVEL:
            return [(False, e)]
        lo, hi = self.anneal.start_epochs, e - self.anneal.end_epochs
        active = max(0, hi - lo)
        return [(True, active), (False, e - active)]

    # ------------------------------------------------------------------
    def _make_score_sharding(self):
        """Row-shard the ES score store over every device of the run
        (``jax.make_mesh`` draws from ``jax.devices()``, so on a pod the
        mesh — and the store — spans hosts).

        Flag-gated (``--shard-scores``); replicated remains the default.
        Raises when the flag cannot be honoured (one device, or a store
        that does not divide evenly), so a run never reports a sharded
        store it does not have.
        """
        n_dev = len(jax.devices())
        if n_dev < 2:
            raise ValueError("--shard-scores needs more than one device; "
                             f"this run has {n_dev}")
        n = self.n_train
        if n % n_dev != 0:
            raise ValueError(f"--shard-scores: n_train={n} is not divisible "
                             f"by the {n_dev} devices")
        from ..distributed.sharding import score_store_sharding
        return score_store_sharding(jax.make_mesh((n_dev,), ("data",)))

    # ------------------------------------------------------------------
    def _grow_store(self, n_new: int) -> None:
        """Grow the score store + engine + train state by ``n_new`` rows
        (old rows bitwise-preserved, new rows at the 1/n' prior)."""
        new_store, new_scores = self.score_store.grow(self.state.scores,
                                                      n_new)
        self.score_store = new_store
        self.engine.store = new_store
        self.state = dataclasses.replace(self.state, scores=new_scores)
        self.n_train += n_new
        self.es_cfg = dataclasses.replace(self.es_cfg,
                                          n_train=self.n_train)
        self.engine.es_cfg = self.es_cfg
        if self.prev_epoch_losses is not None:
            # 0.0: the KA move-back rule always re-admits rows that have
            # no previous-epoch loss yet
            self.prev_epoch_losses = np.concatenate(
                [self.prev_epoch_losses, np.zeros(n_new, np.float32)])

    def grow(self, n_new: int, epoch: int) -> None:
        """Admit ``n_new`` rows the source has already appended: the
        score store grows NOW (the next jitted step recompiles once for
        the new shape); the sampler walks the rows from the next epoch
        boundary, so the current epoch's permutation stays bit-stable.

        The pipeline grows first: it validates the source really holds
        the appended rows, so a missing ``append`` leaves the run
        untouched instead of half-grown."""
        self.pipeline.grow(n_new, epoch)
        self._grow_store(n_new)

    def _resume(self) -> None:
        step = self.ckpt.latest_step()
        md = self.ckpt.manifest(step)["metadata"]
        cur_pre = md.get("data")
        if cur_pre is not None:
            # a grown checkpoint: extend the template scores to the
            # checkpointed population BEFORE the template-driven restore
            growth = cur_pre.get("growth") or []
            if growth and int(growth[-1][1]) > self.n_train:
                self._grow_store(int(growth[-1][1]) - self.n_train)
        self.state = self.ckpt.restore(
            self.state, step,
            partition=self.score_store.checkpoint_partition())
        self.global_step = md.get("global_step", step)
        self.start_epoch = md.get("epoch", 0)
        self.bp_samples_total = md.get("bp_samples_total", 0.0)
        self.scoring_steps_total = md.get("scoring_steps_total", 0.0)
        self.epochs_since_prune = md.get("epochs_since_prune", 0)
        cur = md.get("data")
        if cur is not None:
            extras = self.ckpt.extras(step)
            self.pipeline.load_state(extras, cur)
            if "prev_epoch_losses" in extras:
                self.prev_epoch_losses = extras["prev_epoch_losses"]
            self._pruned_in_process = self.pipeline.has_pruning
            self._resume_step = cur.get("step", 0)
            self._resume_held = cur.get("held", False)
            # a cursor at the epoch's end (and no pipelined carry) means
            # the epoch finished: resume at the NEXT epoch, not a re-run
            if (not self._resume_held and self._resume_step
                    >= self.pipeline.steps_per_epoch(self.start_epoch)):
                self.start_epoch += 1
                self._resume_step = 0
        print(f"[resume] step={self.global_step} epoch={self.start_epoch}"
              f" epoch_step={self._resume_step}"
              f"{' +held' if self._resume_held else ''}")

    def _checkpoint(self, epoch: int, final: bool = False) -> None:
        if not self.ckpt:
            return
        cad = self.state.cadence
        cursor = self.pipeline.cursor(epoch, self._epoch_consumed)
        cursor["held"] = bool(self._cur_sess is not None
                              and self._cur_sess.has_held)
        md = {"global_step": self.global_step, "epoch": epoch,
              "bp_samples_total": self.bp_samples_total,
              "scoring_steps_total": self.scoring_steps_total,
              "epochs_since_prune": self.epochs_since_prune,
              "method": self.tc.method,
              # backend provenance (restore is template-driven; this is
              # for runbooks and cross-topology sanity checks)
              "score_store": self.score_store.checkpoint_spec(),
              # sampler cursor: mid-epoch bit-exact resume (the kept-set /
              # grad-scale arrays ride the extras channel of arrays.npz)
              "data": cursor,
              # CadenceState snapshot: human-readable in the manifest (the
              # authoritative values ride in arrays.npz with the state)
              "cadence": {"kind": self.engine.cadence.kind,
                          "period": int(cad.period),
                          "drift_s": float(cad.drift_s),
                          "drift_w": float(cad.drift_w),
                          "since_prune": float(cad.since_prune)}}
        extras = self.pipeline.state_arrays()
        if self.prev_epoch_losses is not None:
            extras["prev_epoch_losses"] = self.prev_epoch_losses
        partition = self.score_store.checkpoint_partition()
        if final:
            self.ckpt.save(self.state, self.global_step, md, extras,
                           partition=partition)
        else:
            self.ckpt.save_async(self.state, self.global_step, md, extras,
                                 partition=partition)

    # ------------------------------------------------------------------
    def _prune_for_epoch(self, epoch: int) -> None:
        """Set-level selection (ESWP / InfoBatch / UCB / KA / Random),
        gated by the engine's pruning cadence (every epoch, or drift)."""
        if self.tc.method not in SET_LEVEL \
                or not self.anneal.selection_active(epoch):
            self.pipeline.apply_pruning(None)
            return
        # count this epoch (inclusive) so prune_max_interval=N really
        # bounds the gap between prunes at N epochs
        self.epochs_since_prune += 1
        # skipping a re-prune is only sound while the sampler still holds
        # the previous kept-set; a pre-cursor resume restores none, so the
        # first eligible epoch must then always prune
        if not self._pruned_in_process:
            fired, reason = True, "first-prune"
        else:
            fired, reason = self.engine.prune_decision(
                self.state.cadence, self.epochs_since_prune)
        cad = self.state.cadence
        self.prune_events.append({
            "epoch": epoch, "fired": fired, "reason": reason,
            "epochs_since_prune": self.epochs_since_prune,
            "since_prune_drift": float(cad.since_prune)
            if cad is not None else 0.0})
        if not fired:
            return                         # keep the previous kept-set
        # one path for every backend: the store snapshots its host-local
        # row blocks and the kept-set comes from exact global reductions
        rng = np.random.default_rng((self.tc.seed, epoch, 17))
        res, s_host = self.score_store.prune_epoch(
            self.tc.method, rng, self.state.scores,
            prev_losses=self.prev_epoch_losses,
            ratio=self.tc.pruning_ratio)
        self.pipeline.apply_pruning(res.kept, res.grad_scale)
        self.prev_epoch_losses = s_host.copy()
        self.epochs_since_prune = 0
        self._pruned_in_process = True
        self.state = self.engine.reset_prune_drift(self.state)

    # ------------------------------------------------------------------
    def _record(self, epoch: int, m: Dict[str, Any], dur: float) -> bool:
        """Book one trained step; returns True when training should stop."""
        self.straggler.record(self.global_step, dur)
        self.global_step += 1
        self.bp_samples_total += float(m["bp_samples"])
        scored = float(m.get("scored", 1.0))
        self.scoring_steps_total += scored
        rec = {"step": self.global_step, "epoch": epoch,
               "loss": float(m["loss"]),
               "scored": scored,
               "bp_samples_total": self.bp_samples_total,
               # ESWP stale-grad_scale audit: how old this epoch's kept-set
               # (and its InfoBatch rescale) is, in epochs (0 = re-pruned
               # before this epoch; see prune_events for the gate decision)
               "epochs_since_prune": self.epochs_since_prune,
               "step_time": dur}
        if "sel_ids" in m:
            # meta-batch rows the step trained on (batch-level selection)
            rec["sel_ids"] = np.asarray(m["sel_ids"]).tolist()
        self.metrics_log.append(rec)
        if self.ckpt and self.global_step % self.tc.ckpt_every_steps == 0:
            self._checkpoint(epoch)
        if self.preempt.preemption_requested:
            print("[preempt] checkpoint-and-exit")
            self._checkpoint(epoch, final=True)
            return True
        if self.tc.max_steps and self.global_step >= self.tc.max_steps:
            return True
        return False

    def train(self) -> Dict[str, Any]:
        tc = self.tc
        t_start = time.time()
        stop = False
        epoch = self.start_epoch
        for epoch in range(self.start_epoch, tc.epochs):
            start_step = self._resume_step if epoch == self.start_epoch \
                else 0
            resume_held = self._resume_held if epoch == self.start_epoch \
                else False
            if start_step == 0 and not resume_held:
                self._prune_for_epoch(epoch)
            # else: mid-epoch resume — the kept-set (and its grad scales)
            # was restored from the checkpoint; re-pruning here would use
            # mid-epoch scores and diverge from the uninterrupted run
            selection_on = (self.anneal.selection_active(epoch)
                            and self.sel_method != "baseline")
            # the actual horizon, re-read from the sampler now that the
            # kept-set for this epoch is installed (satellite: the static
            # n_samples-derived count ignored pruning)
            spe = self.pipeline.steps_per_epoch(epoch)
            self.epoch_log.append({"epoch": epoch, "steps_per_epoch": spe,
                                   "selection_on": selection_on})
            sess = self.engine.session(selection_on, tc.pipelined)
            self._cur_sess = sess
            self._epoch_consumed = start_step
            if resume_held and start_step > 0 and sess.pipelined:
                # rebuild the checkpointed pipelined carry: the restored
                # pending_w was scored for THIS batch, so no re-prime runs
                held = self.pipeline.batch_at(epoch, start_step - 1)
                sess.resume_held(self._placer(held))
            stream = self.pipeline.epoch(epoch, start_step)
            t0 = time.time()
            primes_folded = 0
            with stream:
                for jb in stream:
                    self._epoch_consumed += 1
                    self.state, m = sess.step(self.state, jb)
                    if m is None:   # pipelined prime: batch held, no train
                        # fold the prime's scoring forward in NOW so a
                        # mid-epoch checkpoint (and its resume, which
                        # never re-primes) carries the same count as the
                        # uninterrupted run
                        self.scoring_steps_total += \
                            sess.scoring_primes - primes_folded
                        primes_folded = sess.scoring_primes
                        t0 = time.time()
                        continue
                    stop = self._record(epoch, m, time.time() - t0)
                    for hook in self.step_hooks:
                        hook(self, epoch)
                    t0 = time.time()
                    if stop:
                        break
            if stop:
                break
            # drain the pipelined carry so the epoch's last meta-batch
            # trains instead of being dropped at the boundary
            t0 = time.time()
            self.state, m = sess.finish(self.state)
            if m is not None and self._record(epoch, m, time.time() - t0):
                break
        self._checkpoint(epoch, final=True)
        self._cur_sess = None
        if self.ckpt:
            self.ckpt.wait()
        out = {
            "final_loss": self.metrics_log[-1]["loss"]
            if self.metrics_log else float("nan"),
            "steps": self.global_step,
            "bp_samples_total": self.bp_samples_total,
            "scoring_steps_total": self.scoring_steps_total,
            "wall_time": time.time() - t_start,
            "straggler_reports": len(self.straggler.reports),
            "score_store_sharded": self.score_sharding is not None,
            "prune_events": self.prune_events,
            "epoch_log": self.epoch_log,
            "metrics": self.metrics_log,
        }
        if tc.log_path:
            Path(tc.log_path).parent.mkdir(parents=True, exist_ok=True)
            Path(tc.log_path).write_text(json.dumps(out, indent=1))
        return out

    # ------------------------------------------------------------------
    def eval_mean_loss(self, n: int = 256, batch: int = 32) -> float:
        """Mean per-sample loss over the first n samples (no selection).

        One jitted eval step (padded to a fixed batch shape, masked), fed
        through the pipeline's prefetcher with the same DP-mesh placement
        as train batches.
        """
        from ..data.pipeline import Prefetcher, SyncStream
        from ..models.transformer import lm_per_sample_loss
        if self._eval_fn is None:
            model_cfg, ctx = self.model_cfg, self.ctx

            def fn(params, eb, mask):
                ps, _ = lm_per_sample_loss(model_cfg, params, eb, ctx,
                                           seq_chunk=0)
                return jnp.sum(ps * mask), jnp.sum(mask)
            self._eval_fn = jax.jit(fn)
        n = min(n, len(self.source))

        def host_batches():
            for lo in range(0, n, batch):
                ids = np.arange(lo, min(lo + batch, n))
                mask = np.ones(batch, np.float32)
                if len(ids) < batch:      # pad: one compiled shape
                    mask[len(ids):] = 0.0
                    ids = np.concatenate(
                        [ids, np.full(batch - len(ids), ids[-1])])
                eb = self.source.batch(ids)
                eb["eval_mask"] = mask
                yield eb

        stream_cls = Prefetcher if self.tc.prefetch else SyncStream
        total, cnt = 0.0, 0.0
        with stream_cls(host_batches(), place=self._placer) as stream:
            for jb in stream:
                mask = jb.pop("eval_mask")
                s, c = self._eval_fn(self.state.params, jb, mask)
                total += float(s)
                cnt += float(c)
        return total / max(cnt, 1.0)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--method", default="es")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--meta-batch", type=int, default=32)
    ap.add_argument("--minibatch", type=int, default=8)
    ap.add_argument("--n-samples", type=int, default=1024)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--pipelined", action="store_true")
    ap.add_argument("--score-every", type=int, default=1,
                    help="k: run the scoring forward every k-th step (§3.3)")
    ap.add_argument("--freq-schedule", default="fixed",
                    choices=["fixed", "warmup", "adaptive", "drift"],
                    help="scoring-frequency schedule (core/frequency.py); "
                         "adaptive/drift treat --score-every as the period "
                         "cap (64 when left at 1); drift servoes the period "
                         "from the observed score-store deltas at runtime")
    ap.add_argument("--gain-floor", type=float, default=0.5,
                    help="adaptive schedule: retained Thm. 3.2 passband")
    ap.add_argument("--drift-target", type=float, default=0.05,
                    help="drift schedule: relative |Δs| the servo tracks")
    ap.add_argument("--prune-cadence", default="epoch",
                    choices=["epoch", "drift"],
                    help="set-level (ESWP) re-prune gate: every epoch, or "
                         "when the observed score drift re-arms it")
    ap.add_argument("--no-fused-scores", dest="fused_scores",
                    action="store_false",
                    help="use XLA scatter instead of the Pallas score kernel")
    ap.add_argument("--shard-scores", action="store_true",
                    help="row-shard the ES score store over the run's "
                         "devices (each holds n/D score rows; on a pod "
                         "the mesh spans hosts; replicated is the default)")
    ap.add_argument("--quant-scores", action="store_true",
                    help="int8 score store: the (s, w, seen) triple as "
                         "int8 codes with per-block scales and an error-"
                         "feedback residual ring (~4x smaller state; "
                         "composes with --shard-scores)")
    ap.add_argument("--quant-block", type=int, default=1024,
                    help="quantized store: rows per scale block (must "
                         "divide the shard when --shard-scores)")
    ap.add_argument("--quant-wire", action="store_true",
                    help="quantized store: also ship int8+scale payloads "
                         "on the cross-shard gather/select legs (lossy by "
                         "one grid step; off = storage-only quantization)")
    ap.add_argument("--grad-compression", action="store_true",
                    help="int8 error-feedback gradient compression on the "
                         "DP reduce (distributed/compression.py)")
    ap.add_argument("--host-id", type=int, default=None,
                    help="data-slicing host id override (default: "
                         "jax.process_index(); tests use this to emulate "
                         "one host of a larger run)")
    ap.add_argument("--num-hosts", type=int, default=None,
                    help="data-slicing host count override (default: "
                         "jax.process_count())")
    ap.add_argument("--source", default="synthetic",
                    choices=["synthetic", "tokens", "sharded", "sft",
                             "packed"],
                    help="data source: in-memory synthetic LM, memory-"
                         "mapped token bin, sharded token-bin files, "
                         "packed SFT (prompt/response with loss masks), or "
                         "document-packed rows (token-level ES)")
    ap.add_argument("--pack", action="store_true",
                    help="sequence packing: multiple documents per row "
                         "with segment-granular ES (shortcut for "
                         "--source packed)")
    ap.add_argument("--max-segments", type=int, default=4,
                    help="packed: max documents per row (the ES selection "
                         "pool is meta_batch * max_segments document slots)")
    ap.add_argument("--data-path", default=None,
                    help="tokens: .bin path; sharded: glob pattern; "
                         "sft: JSONL path (omit for the synthetic SFT set)")
    ap.add_argument("--no-prefetch", dest="prefetch", action="store_false",
                    help="build+place batches inline on the train thread "
                         "(the synchronous pre-pipeline data path)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="prefetch queue depth (2 = double buffering)")
    ap.add_argument("--keep-partial", dest="drop_last",
                    action="store_false",
                    help="train the partial final meta-batch of each epoch")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log", dest="log_path", default=None)
    ap.add_argument("--max-steps", type=int, default=None)
    args = ap.parse_args()
    tc = TrainerConfig(arch=args.arch, smoke=args.smoke, method=args.method,
                       epochs=args.epochs, meta_batch=args.meta_batch,
                       minibatch=args.minibatch, n_samples=args.n_samples,
                       seq_len=args.seq_len, lr=args.lr,
                       pipelined=args.pipelined, ckpt_dir=args.ckpt_dir,
                       score_every=args.score_every,
                       freq_schedule=args.freq_schedule,
                       gain_floor=args.gain_floor,
                       drift_target=args.drift_target,
                       prune_cadence=args.prune_cadence,
                       fused_scores=args.fused_scores,
                       shard_scores=args.shard_scores,
                       quant_scores=args.quant_scores,
                       quant_block=args.quant_block,
                       quant_wire=args.quant_wire,
                       grad_compression=args.grad_compression,
                       host_id=args.host_id, num_hosts=args.num_hosts,
                       source=args.source, data_path=args.data_path,
                       pack=args.pack, max_segments=args.max_segments,
                       prefetch=args.prefetch,
                       prefetch_depth=args.prefetch_depth,
                       drop_last=args.drop_last,
                       log_path=args.log_path, max_steps=args.max_steps)
    out = Trainer(tc).train()
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("metrics", "epoch_log")}, indent=1))


if __name__ == "__main__":
    main()
