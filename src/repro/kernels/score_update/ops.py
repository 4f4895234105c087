"""Jitted wrapper with backend + shard dispatch for the fused score update.

On TPU the fused Pallas kernel replaces the three XLA scatters with one
in-place pass over the touched HBM tiles.  Off-TPU there is no compiled Pallas path and the
interpret-mode emulation of the serial update loop is an order of magnitude
SLOWER than the scatters it fuses, so the store backends fall back to the
pure-JAX scatter instead; interpret mode must be requested explicitly
(``interpret=True`` — tests do, to pin kernel semantics).  The two paths
agree exactly on the train path's unique-id batches (see ``ref.py`` for
the duplicate-id divergence, covered by tests).

This module is a compatibility shim: the whole dispatch — backend pick,
per-shard masked-kernel rewrite (foreign ids become -1 inside
``shard_map``), scatter fallback — now lives in the ``ScoreStore``
backends (``core.scores.ReplicatedStore`` / ``ShardedStore``), one code
path for every consumer.  ``update_scores_fused`` keeps the historical
signature for tests and benchmarks.
"""
from __future__ import annotations

import jax

from ...core.scores import ESScores, ScoreSharding, make_store


def update_scores_fused(scores: ESScores, ids: jax.Array, losses: jax.Array,
                        beta1: float, beta2: float,
                        interpret: bool | None = None,
                        sharding: ScoreSharding | None = None) -> ESScores:
    return make_store(sharding).update(scores, ids, losses, beta1, beta2,
                                       fused=True, interpret=interpret)
