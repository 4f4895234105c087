"""The benchmark's work counts against counts worked by hand.

Convention (lib/flops.py): a token's forward is 2 * N_matmul + 2 * S * d * L
(N_matmul: every matrix product's weights, the output head included); its
backward twice that; an ES step that scores is the forward over B rows plus
the backward over b; a step that skips scoring, and plain training, forward
plus backward over the rows they train.
"""
import json

import pytest

from lib import flops, peaks
from lib.spec import module
from conftest import BENCH

n_matmul = module("references", "dense_decoder").n_matmul


def _load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def test_qwen_counts_by_hand():
    cfg = _load("configs", "qwen1.5-0.5b")
    # per layer: q, k, v, o 4 * 1024^2 + SwiGLU 3 * 1024 * 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816          # 12_845_056
    n = 24 * per_layer + 1024 * 151936                      # tied head
    assert n_matmul(cfg) == n == 463_863_808
    fwd = 2 * n + 2 * 1024 * 1024 * 24                       # S=1024
    assert flops.forward_per_token(cfg, 1024) == fwd == 978_059_264
    es = _load("traffic", "es_B16_b4_k1")
    assert flops.step_flops(cfg, es, 0) == fwd * (16 + 2 * 4) * 1024
    assert flops.step_flops(cfg, es, 1) == 24_036_784_472_064
    base = _load("traffic", "baseline_b4")
    assert flops.step_flops(cfg, base, 0) == 3 * fwd * 4 * 1024


def test_olmo_counts_by_hand():
    cfg = _load("configs", "olmo-1b")
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192           # 67_108_864
    n = 8 * per_layer + 2048 * 50304
    assert n_matmul(cfg) == n == 639_893_504
    fwd = 2 * n + 2 * 1024 * 2048 * 8
    assert flops.forward_per_token(cfg, 1024) == fwd == 1_313_341_440
    k4 = _load("traffic", "es_B16_b4_k4")
    # scoring on steps 0, 4, 8, ...; the others train on the stale weights
    assert [flops.step_flops(cfg, k4, s) for s in range(5)] == \
        [fwd * (16 + 8) * 1024] + 3 * [fwd * (4 + 8) * 1024] + \
        [fwd * (16 + 8) * 1024]


def test_score_update_bytes():
    # s, w, seen (4 bytes each) read and written, plus an id and a loss
    assert flops.score_update_bytes(16) == 16 * (24 + 8) == 512


def test_peaks_known_and_unknown():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
