"""Batch-level selection invariants (Gumbel top-k, Order, uniform)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # hermetic env: deterministic shim
    from _hypothesis_fallback import given, settings, st

from repro.core.selection import (gumbel_topk_select, topk_select,
                                  select_minibatch, selection_probs)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 64), st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
def test_selection_without_replacement(n, k, seed):
    """Gumbel top-k returns k distinct in-range indices.  Run time: each
    example draws a new (n, k), and the eager ops compile once per shape."""
    k = min(k, n)
    key = jax.random.PRNGKey(seed)
    w = jnp.abs(jax.random.normal(key, (n,))) + 0.01
    idx = np.asarray(gumbel_topk_select(key, w, k))
    assert len(idx) == k
    assert len(set(idx.tolist())) == k          # no replacement
    assert (idx >= 0).all() and (idx < n).all()


def test_order_is_deterministic_topk():
    w = jnp.asarray([0.1, 5.0, 0.3, 2.0, 4.0])
    idx = np.asarray(topk_select(w, 3))
    assert set(idx.tolist()) == {1, 4, 3}


def test_gumbel_matches_weights_distribution():
    """Higher-weight items must be selected (first) proportionally more —
    Gumbel top-1 frequencies converge to p_i ∝ w_i."""
    key = jax.random.PRNGKey(0)
    w = jnp.asarray([1.0, 2.0, 4.0, 8.0])
    counts = np.zeros(4)
    trials = 4000
    keys = jax.random.split(key, trials)
    sel = jax.vmap(lambda k: gumbel_topk_select(k, w, 1)[0])(keys)
    for i in np.asarray(sel):
        counts[i] += 1
    freq = counts / trials
    expect = np.asarray(w) / float(np.sum(np.asarray(w)))
    np.testing.assert_allclose(freq, expect, atol=0.03)


def test_select_minibatch_dispatch():
    key = jax.random.PRNGKey(3)
    w = jnp.abs(jax.random.normal(key, (16,))) + 0.1
    for method in ("es", "eswp", "loss", "order", "uniform"):
        idx = select_minibatch(method, key, w, 4)
        assert idx.shape == (4,)
    with pytest.raises(ValueError):
        select_minibatch("nope", key, w, 4)


def test_select_all_when_k_ge_n():
    key = jax.random.PRNGKey(0)
    w = jnp.ones(8)
    idx = np.asarray(select_minibatch("es", key, w, 8))
    assert (np.sort(idx) == np.arange(8)).all()


def test_selection_probs_normalized_and_safe():
    p = selection_probs(jnp.asarray([0.0, 1.0, 3.0]))
    assert abs(float(jnp.sum(p)) - 1.0) < 1e-6
    assert (np.asarray(p) >= 0).all()
    # zero/negative weights do not produce NaNs
    p = selection_probs(jnp.asarray([-1.0, 0.0, 0.0]))
    assert np.isfinite(np.asarray(p)).all()
