"""Pure-jnp oracle for the flash attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _scores(q: jax.Array, k: jax.Array, causal: bool) -> jax.Array:
    S, hd = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None], s, -1e30)
    return s


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True) -> jax.Array:
    """q/k/v: (BH, S, hd) -> (BH, S, hd), exact softmax attention."""
    p = jax.nn.softmax(_scores(q, k, causal), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def lse_ref(q: jax.Array, k: jax.Array, causal: bool = True) -> jax.Array:
    """(BH, S): log-sum-exp of each query row's scaled, masked scores."""
    return jax.nn.logsumexp(_scores(q, k, causal), axis=-1)
