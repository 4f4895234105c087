"""The benchmark's own weights, and what the check reads of a tree of them.

The weights are the benchmark's: one jitted call turns the seed into every
leaf, in f32, on the device. The program is handed them in place of its own
initialisation, and the plain reference regenerates the same leaves from the
same seed, so the reference never takes a weight that the program made.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from .spec import module

SKETCH = 8   # fixed +-1 directions a leaf is projected on


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return out


def _leaf(key: jax.Array, i: int, shape, init: str, std: float) -> jax.Array:
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    return std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32)


def weights_key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0x77)


def selection_key(seed: int) -> jax.Array:
    """The key the benchmark gives the train state's selection RNG."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0x5E1)


def make_weights(cfg: dict, key: jax.Array) -> dict:
    """Every weight from ``key``; call under ``jax.jit`` (one device call)."""
    std = float(cfg["init_std"])
    return _nest({path: _leaf(key, i, shape, init, std)
                  for i, (path, shape, init) in enumerate(leaf_specs(cfg))})


def leaf_specs(cfg: dict) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], str]]:
    """(path, shape, init) of every weight, as the configuration's reference
    lays them out."""
    return module("references", cfg["reference"]).leaf_specs(cfg)


def weights_fn(cfg: dict):
    """Jitted ``key -> weights`` for ``cfg``."""
    return jax.jit(lambda key: make_weights(cfg, key))


def change_norms_fn(cfg: dict):
    """Jitted ``(params, key) -> {path: |params - weights(key)|}``.

    The initial weights are regenerated inside the call, so no copy of them
    is kept on the device while the train state lives.
    """
    def fn(params, key):
        p0 = make_weights(cfg, key)
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b))), params, p0)
    return jax.jit(fn)


def leaf_norms(tree) -> jax.Array:
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def _sketch_leaf(x: jax.Array) -> jax.Array:
    flat = x.reshape(-1).astype(jnp.float32)
    h = jax.lax.iota(jnp.uint32, flat.shape[0]) * jnp.uint32(0x9E3779B1)
    for shift, mul in ((16, 0x85EBCA6B), (13, 0xC2B2AE35)):
        h = (h ^ (h >> shift)) * jnp.uint32(mul)
    h = h ^ (h >> 16)
    return jnp.stack([jnp.sum(jnp.where(((h >> j) & 1) == 1, flat, -flat))
                      for j in range(SKETCH)])


def sketch(tree):
    """Each leaf's products with ``SKETCH`` fixed directions of +-1 (bits of a
    hash of the element's index): a leaf's gap of sketches, over its sketch's
    norm, reads how far its direction differs, where a gap of norms does not."""
    return jax.tree.map(_sketch_leaf, tree)


def flat_paths(tree) -> Dict[str, object]:
    """``{"layers/attn/wq": value}`` for a tree of host scalars or vectors
    (a vector becomes a list)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(getattr(p, "key", str(p)) for p in path)
        out[name] = float(x) if getattr(x, "ndim", 0) == 0 else \
            [float(v) for v in x]
    return out
