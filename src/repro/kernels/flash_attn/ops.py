"""The model-facing entry point of the flash attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attn import flash_attention


def gqa_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, S, K, hd), H = K*G -> (B, S, H, hd).

    Query head h = k*G + g reads kv head k.  The kernel reads the model's
    layout, a lane-dense slice of heads per grid step, so grouped kv heads
    are repeated to H here (their gradients sum back through the repeat).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G = q.shape[2] // k.shape[2]
    if G > 1:
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    return flash_attention(q, k, v, causal, block, interpret)
