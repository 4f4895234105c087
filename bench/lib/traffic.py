"""The benchmark's rows: a synthetic pre-training corpus drawn from the seed.

Every row is a full window of ``seq_len + 1`` tokens, as concatenate-and-chunk
pre-training cuts them, so all ``seq_len`` labels are supervised. A row is one
of four difficulty classes, in the proportions the traffic file gives:

  motif   a short random motif repeated (period drawn per row);
  walk    a walk on a shared successor table: each token has ``successors``
          next tokens, drawn from the seed over the whole vocabulary;
  rotated the same walk with every token shifted by a per-row offset;
  noise   uniform tokens.

A row is a pure function of (seed, row id), so any batch can be rebuilt by the
reference after the window. The successor table is (vocab, successors), never
a vocab x vocab matrix.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np


class Rows:
    """A ``Source`` in the program's sense: ``len`` and ``batch(ids)``."""

    def __init__(self, *, vocab: int, seq_len: int, n_rows: int, seed: int,
                 class_fracs, successors: int, motif_period):
        self.vocab, self.seq_len, self.n_rows = vocab, seq_len, n_rows
        self.seed = int(seed)
        self.cum = np.cumsum(np.asarray(class_fracs, np.float64))
        self.cum /= self.cum[-1]
        self.period = tuple(motif_period)
        rng = np.random.default_rng([self.seed, 0x7AB1E])
        self.succ = rng.integers(0, vocab, (vocab, successors), np.int64)
        self.requested: List[np.ndarray] = []   # ids of each batch() call

    @classmethod
    def for_cell(cls, cfg: dict, traffic: dict, seed: int) -> "Rows":
        r = traffic["rows"]
        return cls(vocab=cfg["vocab_size"], seq_len=traffic["seq_len"],
                   n_rows=traffic["n_samples"], seed=seed,
                   class_fracs=r["class_fracs"], successors=r["successors"],
                   motif_period=r["motif_period"])

    def __len__(self) -> int:
        return self.n_rows

    def windows(self, ids) -> np.ndarray:
        """(len(ids), seq_len + 1) int64 tokens."""
        ids = np.asarray(ids, np.int64)
        B, W, V = len(ids), self.seq_len + 1, self.vocab
        S = self.succ.shape[1]
        cls = np.empty(B, np.int64)
        first = np.empty(B, np.int64)
        shift = np.empty(B, np.int64)
        choice = np.empty((B, W), np.int64)
        flat = np.empty((B, W), np.int64)
        motif = np.empty((B, W), np.int64)
        for j, i in enumerate(ids):
            r = np.random.default_rng([self.seed, int(i)])
            cls[j] = np.searchsorted(self.cum, r.random(), side="right")
            first[j] = r.integers(0, V)
            shift[j] = r.integers(1, V)
            choice[j] = r.integers(0, S, W)
            flat[j] = r.integers(0, V, W)
            p = int(r.integers(self.period[0], self.period[1]))
            motif[j] = np.resize(flat[j, :p], W)
        walk = np.empty((B, W), np.int64)
        walk[:, 0] = first
        rot = np.where(cls == 2, shift, 0)
        for k in range(1, W):
            walk[:, k] = (self.succ[walk[:, k - 1], choice[:, k]] + rot) % V
        return np.where((cls == 0)[:, None], motif,
                        np.where((cls == 3)[:, None], flat, walk))

    def batch(self, ids) -> Dict[str, np.ndarray]:
        ids = np.asarray(ids)
        self.requested.append(ids.copy())
        win = self.windows(ids)
        return {"tokens": win[:, :-1].astype(np.int32),
                "labels": win[:, 1:].astype(np.int32),
                "sample_ids": ids.astype(np.int32)}


def batch_ms(rows: Rows, batch: int, n: int = 8) -> float:
    """Host milliseconds per ``batch`` of a fresh draw (not recorded)."""
    t0 = time.perf_counter()
    for k in range(n):
        rows.windows(np.arange(k * batch, (k + 1) * batch))
    return (time.perf_counter() - t0) / n * 1e3
