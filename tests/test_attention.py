"""``mha``'s two paths: the flash kernel (interpret mode here) against the
q-chunked XLA path, and the rule that chooses between them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.models import attention
from repro.models.layers import ShardCtx

B, S, D, HD = 2, 256, 128, 64


def _mha(params, x, *, n_kv, causal, flash, monkeypatch):
    monkeypatch.setattr(attention, "use_flash", lambda *a, **k: flash)
    return attention.mha(params, x, n_heads=4, n_kv=n_kv, head_dim=HD,
                         rope_theta=10000.0, ctx=ShardCtx(), chunk_q=128,
                         causal=causal)


@pytest.mark.parametrize("n_kv,causal", [(4, True), (2, True), (4, False)])
def test_mha_flash_path_matches_xla_path(n_kv, causal, monkeypatch):
    """Output and the gradients of every projection's weights and biases."""
    kp, kx, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    params, _ = attention.init_attn(kp, D, 4, n_kv, HD, qkv_bias=True)
    # non-zero biases, so their gradients are checked through the kernel
    for i, name in enumerate(("bq", "bk", "bv")):
        params[name] = 0.1 * jax.random.normal(jax.random.fold_in(kb, i),
                                               params[name].shape)
    x = jax.random.normal(kx, (B, S, D))

    def loss(p, flash):
        y = _mha(p, x, n_kv=n_kv, causal=causal, flash=flash,
                 monkeypatch=monkeypatch)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y_k), g_k = jax.value_and_grad(loss, has_aux=True)(params, True)
    (_, y_x), g_x = jax.value_and_grad(loss, has_aux=True)(params, False)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x), atol=1e-5,
                               rtol=1e-5)
    assert set(g_k) == {"wq", "wk", "wv", "wo", "bq", "bk", "bv"}
    for name in g_k:
        g, w = np.asarray(g_k[name]), np.asarray(g_x[name])
        assert np.abs(g - w).max() <= 1e-5 * np.abs(w).max(), name


def _ctx_with_mesh():
    return ShardCtx(mesh=Mesh(np.asarray(jax.devices()[:1]), ("data",)),
                    rules=(("batch", "data"),))


_SEG = jnp.ones((1, 1024), jnp.int32)
_POS = jnp.arange(1024)[None]


@pytest.mark.parametrize(
    "backend,devices,seq,heads,hd,ctx,positions,segs,want", [
        ("tpu", 1, 1024, 16, 64, None, None, None, True),   # the qwen cells
        ("tpu", 1, 1024, 16, 128, None, None, None, True),  # the olmo cells
        ("tpu", 1, 2048, 8, 128, None, None, None, True),   # S of 2 blocks
        ("tpu", 1, 256, 2, 64, None, None, None, True),     # one slab
        ("cpu", 1, 1024, 16, 64, None, None, None, False),  # CPU: tier-1
        ("tpu", 4, 1024, 16, 64, None, None, None, False),  # several chips
        ("tpu", 1, 1024, 16, 64, None, _POS, _SEG, False),  # packed rows
        ("tpu", 1, 1024, 16, 64, None, _POS, None, False),  # own positions
        ("tpu", 1, 1024, 16, 64, "mesh", None, None, False),  # a mesh
        ("tpu", 1, 1000, 16, 64, None, None, None, False),  # S % 128 != 0
        ("tpu", 1, 1024, 16, 96, None, None, None, False),  # head 96
        ("tpu", 1, 2048, 8, 256, None, None, None, False),  # head 256
        ("tpu", 1, 1024, 3, 64, None, None, None, False),   # odd heads
        ("tpu", 1, 256, 1, 64, None, None, None, False),    # one head
    ])
def test_flash_dispatch_rule(backend, devices, seq, heads, hd, ctx,
                             positions, segs, want):
    """The kernel runs on one TPU chip, unpacked rows, no mesh, and the
    shapes it is compiled for (``test_tpu_compile``); all else takes XLA."""
    ctx = _ctx_with_mesh() if ctx == "mesh" else ShardCtx()
    assert attention.use_flash(backend, devices, seq, heads, hd, ctx,
                               positions, segs) is want
