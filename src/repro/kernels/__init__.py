"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel ships three files: <name>.py (pl.pallas_call + BlockSpec
tiling), ops.py (jitted wrapper + backend dispatch), ref.py (pure-jnp
oracle).  On non-TPU backends the wrappers run interpret mode
(correctness) — except score_update, whose store path takes the XLA
scatter there; tests sweep shapes/dtypes against the oracles.  On the
training path: score_update (the store) and flash_attn (``models/
attention.py:mha`` on a TPU); xent and segsum are called by no path.
"""
from .xent.ops import per_sample_xent_fused, per_token_xent_fused
from .segsum.ops import per_segment_xent_fused, segment_sum_fused
from .flash_attn.ops import gqa_flash_attention
from .score_update.ops import update_scores_fused
