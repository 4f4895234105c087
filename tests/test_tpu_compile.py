"""The score_update and flash attention kernels compile for a TPU v5e that
is described, not attached: Mosaic's alignment and VMEM checks run here,
without a chip.

The topology is described inside a fixture (never at import time): only one
process may load the TPU compiler library, and each pytest worker imports
every test file.  Keep these tests in this one file.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.scores import ESScores, ScoreSharding, ShardedStore
from repro.kernels.flash_attn.ops import gqa_flash_attention
from repro.kernels.score_update.score_update import (
    fused_quant_score_update, fused_score_update)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [4096, 1 << 20])
@pytest.mark.parametrize("masked", [False, True])
def test_f32_score_kernel_compiles(one_chip, n, masked):
    B = 64
    fn = functools.partial(fused_score_update, beta1=0.2, beta2=0.9,
                           masked=masked)
    _assert_kernel(fn, _sds((n,), jnp.float32, one_chip),
                   _sds((n,), jnp.float32, one_chip),
                   _sds((n,), jnp.int32, one_chip),
                   _sds((B,), jnp.int32, one_chip),
                   _sds((B,), jnp.float32, one_chip))


def test_quant_score_kernel_compiles(one_chip):
    n, block, R, B = 1 << 20, 1024, 1024, 64
    nb = n // block
    i8 = [_sds((n,), jnp.int8, one_chip)] * 3
    scales = [_sds((nb,), jnp.float32, one_chip)] * 2
    ring = ([_sds((R,), jnp.int32, one_chip)] * 2
            + [_sds((R,), jnp.float32, one_chip)] * 2)
    batch = ([_sds((B,), jnp.int32, one_chip)] * 2
             + [_sds((B,), jnp.float32, one_chip)]
             + [_sds((B,), jnp.int32, one_chip)] * 2)
    fn = functools.partial(fused_quant_score_update, beta1=0.2, beta2=0.9,
                           block=block)
    _assert_kernel(fn, *i8, *scales, *ring, *batch)


def test_sharded_store_update_compiles(topo):
    """The --shard-scores update: the masked kernel inside shard_map, one
    row block per chip of a 4-chip mesh."""
    n, B = 1 << 20, 64
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    store = ShardedStore(ScoreSharding(mesh))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    scores = ESScores(s=_sds((n,), jnp.float32, rows),
                      w=_sds((n,), jnp.float32, rows),
                      seen=_sds((n,), jnp.int32, rows))

    def update(scores, ids, losses):
        # interpret=False: the compiled kernel, as on a TPU backend
        return store.update(scores, ids, losses, 0.2, 0.9, fused=True,
                            interpret=False)

    _assert_kernel(update, scores, _sds((B,), jnp.int32, rep),
                   _sds((B,), jnp.float32, rep))


def _assert_flash_compiles(shape, sharding):
    """The forward and ``jax.grad`` through the dK/dV and dQ kernels."""
    attn = functools.partial(gqa_flash_attention, interpret=False)
    x = [_sds(shape, jnp.bfloat16, sharding)] * 3
    _assert_kernel(attn, *x)

    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*x).compile()
    text = compiled.as_text()
    for name in ("flash_attn_fwd", "flash_attn_dkv", "flash_attn_dq"):
        assert name in text, name
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,hd", [(16, 64), (4, 64), (4, 128)])
def test_flash_attention_compiles(one_chip, B, hd):
    """The cells' shapes (S = 1024, 16 heads, one 1024 block): qwen1.5-0.5b's
    scoring forward (B = 16) and training (B = 4), olmo-1b's training (head
    128)."""
    _assert_flash_compiles((B, 1024, 16, hd), one_chip)


@pytest.mark.parametrize("hd", [64, 128])
def test_flash_attention_compiles_three_blocks(one_chip, hd):
    """S = 384: three 128-row blocks, so the causal kernels skip and clamp
    the tiles above the diagonal, at the smallest block the kernel takes."""
    _assert_flash_compiles((2, 384, 4, hd), one_chip)
