"""Work a step requires, counted from the configuration's shapes alone.

The convention (PaLM, appendix B):
  * a token's forward is what the configuration's reference module counts
    (``forward_flops_per_token``: for a dense decoder, 2 * N_matmul plus
    causal attention's two S x S products, halved by the mask);
  * a token's backward costs twice its forward;
  * the rows a step puts through the forward and the backward are what the
    traffic's steps module counts (``work_rows``): an ES step that scores
    counts the forward over the B meta-batch rows and the backward over the b
    selected rows (the selected rows' training forward is not counted again);
    a step that skips scoring counts forward and backward over b rows; plain
    training counts forward and backward over its batch;
  * recomputation never counts.
Nothing here depends on how the program implements the step.
"""
from __future__ import annotations

from .spec import module


def forward_per_token(cfg: dict, seq_len: int) -> int:
    return module("references", cfg["reference"]).forward_flops_per_token(
        cfg, seq_len)


def step_flops(cfg: dict, traffic: dict, step: int) -> int:
    """Required FLOPs of 0-based step ``step`` of ``traffic`` on ``cfg``."""
    S = traffic["seq_len"]
    fwd_rows, bwd_rows = module("steps", traffic["steps"]).work_rows(
        traffic, step)
    return forward_per_token(cfg, S) * (fwd_rows + 2 * bwd_rows) * S


def score_update_bytes(n_ids: int) -> int:
    """Required HBM bytes of one Eq. (3.1) store update over ``n_ids`` rows:
    (s, w, seen), 4 bytes each, read and written, plus each id and loss."""
    return n_ids * (2 * 3 * 4 + 4 + 4)
