"""Plain reference of a dense decoder LM, as its published description reads.

Pre-norm blocks: x += attn(norm(x)); x += mlp(norm(x)); then a final norm and
the output head. Attention is causal multi-head attention with rotary
positions (rotate-half: the first and second halves of each head pair up,
frequency base ``rope_theta``) and scores scaled by 1/sqrt(head_dim). The MLP
is SwiGLU, down(silu(gate(h)) * up(h)). The norm is RMSNorm with a learned
scale (``rmsnorm``, eps ``rms_norm_eps``) or LayerNorm without scale or bias
(``layernorm_no_affine``, eps ``norm_eps``). With tied embeddings the head is
the transpose of the token embedding. The loss of a row is the mean negative
log-likelihood of its labels.

Everything is float32 and every product is taken at ``Precision.HIGHEST``.
``fp8=True`` is the control, the precision below the bf16 compute the
configurations state, as fp8 training takes it: each matrix product's two
operands are rounded to float8_e4m3fn, and the gradient flowing back to each
operand to float8_e5m2, each with one scale per tensor (its largest magnitude
to the format's largest).

Rows go one at a time (``lax.map``) and each layer is rematerialised, so the
(S, vocab) logits of one row are the largest live array.

The module also states the weights' layout (``leaf_specs``), from which the
benchmark makes the weights both sides start from, and the work a token's
forward requires (``forward_flops_per_token``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def leaf_specs(cfg: dict):
    """(path, shape, init) of every weight, in a fixed order.

    ``init`` is ``normal`` (a matrix), ``zeros`` (a bias) or ``ones`` (a norm
    scale). Layers are stacked on a leading axis.
    """
    L, d, f, V = (cfg["num_hidden_layers"], cfg["hidden_size"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    hd = d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    specs = [(("embed", "tok"), (V, d), "normal")]
    if not cfg["tie_word_embeddings"]:
        specs.append((("embed", "head"), (d, V), "normal"))
    if cfg["norm"] == "rmsnorm":
        specs.append((("final_norm", "scale"), (d,), "ones"))
        specs += [(("layers", ln, "scale"), (L, d), "ones")
                  for ln in ("ln1", "ln2")]
    specs += [(("layers", "attn", "wq"), (L, d, q), "normal"),
              (("layers", "attn", "wk"), (L, d, kv), "normal"),
              (("layers", "attn", "wv"), (L, d, kv), "normal"),
              (("layers", "attn", "wo"), (L, q, d), "normal")]
    if cfg["qkv_bias"]:
        specs += [(("layers", "attn", "bq"), (L, q), "zeros"),
                  (("layers", "attn", "bk"), (L, kv), "zeros"),
                  (("layers", "attn", "bv"), (L, kv), "zeros")]
    specs += [(("layers", "mlp", "w_gate"), (L, d, f), "normal"),
              (("layers", "mlp", "w_up"), (L, d, f), "normal"),
              (("layers", "mlp", "w_down"), (L, f, d), "normal")]
    return specs


def n_matmul(cfg: dict) -> int:
    """Weights of every matrix product: attention q, k, v, o; the MLP's gate,
    up and down; the output head, also when it is tied to the embedding."""
    d, f, V, L = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["vocab_size"], cfg["num_hidden_layers"])
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return L * per_layer + d * V


def forward_flops_per_token(cfg: dict, seq_len: int) -> int:
    """2 * N_matmul, plus 2 * S * d * L for causal attention's two S x S
    products, halved by the mask (PaLM, appendix B)."""
    return (2 * n_matmul(cfg)
            + 2 * seq_len * cfg["hidden_size"] * cfg["num_hidden_layers"])


def _round(x: jax.Array, dtype) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x: jax.Array) -> jax.Array:
    return _round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(eq: str, a: jax.Array, b: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(cfg: dict, x: jax.Array, scale) -> jax.Array:
    if cfg["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + cfg["rms_norm_eps"])
        return y * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"])


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (S, H, hd) rotated by position, rotate-half pairing."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg: dict, fp8: bool, x: jax.Array, p: dict) -> jax.Array:
    S, d = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    a = p["attn"]
    h = _norm(cfg, x, p["ln1"]["scale"] if "ln1" in p else None)
    q = _mm("sd,dh->sh", h, a["wq"], fp8)
    k = _mm("sd,dh->sh", h, a["wk"], fp8)
    v = _mm("sd,dh->sh", h, a["wv"], fp8)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, H, hd), cfg["rope_theta"])
    k = _rope(k.reshape(S, K, hd), cfg["rope_theta"])
    v = v.reshape(S, K, hd)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = _mm("qhd,khd->hqk", q, k, fp8) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = _mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v, fp8)
    x = x + _mm("sh,hd->sd", o.reshape(S, H * hd), a["wo"], fp8)
    m = p["mlp"]
    h = _norm(cfg, x, p["ln2"]["scale"] if "ln2" in p else None)
    g = _mm("sd,df->sf", h, m["w_gate"], fp8)
    u = _mm("sd,df->sf", h, m["w_up"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"], fp8)


def row_loss(cfg: dict, params: dict, tokens: jax.Array, labels: jax.Array,
             fp8: bool = False) -> jax.Array:
    """Mean NLL of one row: tokens, labels (S,) int32."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    block = jax.checkpoint(lambda x, p: (_block(cfg, fp8, x, p), None))
    x, _ = jax.lax.scan(block, x, params["layers"])
    fn = params.get("final_norm", {}).get("scale")
    h = _norm(cfg, x, fn)
    head = params["embed"]["head"] if "head" in params["embed"] \
        else params["embed"]["tok"].T
    logits = _mm("sd,dv->sv", h, head, fp8)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def row_losses(cfg: dict, params: dict, tokens: jax.Array, labels: jax.Array,
               fp8: bool = False) -> jax.Array:
    """(R,) row losses, one row at a time."""
    return jax.lax.map(lambda tl: row_loss(cfg, params, tl[0], tl[1], fp8),
                       (tokens, labels))


def mean_loss_and_grads(cfg: dict, params: dict, tokens: jax.Array,
                        labels: jax.Array, fp8: bool = False):
    """(mean row loss, gradient of it) over R rows, one row at a time."""
    R = tokens.shape[0]

    def total(p):
        def one(carry, tl):
            loss = jax.checkpoint(
                lambda p, t, l: row_loss(cfg, p, t, l, fp8))(p, tl[0], tl[1])
            return carry + loss, None
        s, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (tokens, labels))
        return s / R

    return jax.value_and_grad(total)(params)
