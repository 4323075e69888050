"""Plain reference of Qwen3 (Qwen/Qwen3-1.7B's published architecture)
in float32 jax.numpy, written from the architecture's description and
importing nothing of the program under test.

Per layer: RMSNorm, grouped-query attention (16 query heads, 8 key/value
heads, head size 128) with a per-head RMSNorm on queries and keys and
rotary embeddings in the rotate-half form, a causal softmax, the output
projection and the residual; then RMSNorm, a SwiGLU MLP and the residual.
A final RMSNorm and the tied embedding matrix give the logits.

Weights are the node-stacked parameters in the layout the benchmark
makes them (``embed``, ``layers_0/{ln1,attn,ln2,mlp}`` stacked over nodes
and then layers, ``ln_f``), stored in bfloat16 and read one node and one
layer at a time. ``precision="f32"`` computes every product at float32
'highest'; ``precision="fp8"`` rounds both operands of every product to
float8_e4m3 with a per-tensor scale (the control that must fail).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.checks import fp8

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq, a, b, precision):
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half rotary embedding over positions 0..S-1; x (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _layer(x, layers, node, li, *, cfg, precision):
    """One decoder layer of node ``node`` (layer ``li`` of the stacked
    weights, read in place) over hidden states ``x (B, S, d)``."""
    cfg = dict(cfg)
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    B, S, _ = x.shape
    lp = jax.tree.map(lambda t: t[node, li], layers)
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _mm("bsd,de->bse", h, a["wq"], precision).reshape(B, S, H, hd)
    k = _mm("bsd,de->bse", h, a["wk"], precision).reshape(B, S, KVH, hd)
    v = _mm("bsd,de->bse", h, a["wv"], precision).reshape(B, S, KVH, hd)
    q = _rope(_rms(q, a["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, a["k_norm"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, H // KVH, axis=2)              # head h reads kv h // G
    v = jnp.repeat(v, H // KVH, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) / jnp.sqrt(float(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, precision).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, a["wo"], precision)
    h = _rms(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    g = _mm("bsd,df->bsf", h, m["wg"], precision)
    u = _mm("bsd,df->bsf", h, m["wi"], precision)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"], precision)


@jax.jit
def _embed(embed, node, tokens):
    return embed[node].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, node, *, eps):
    return _rms(x, scale[node], eps)


def hidden(params, node, tokens, *, cfg, precision="f32"):
    """Final-norm hidden states (B, S, d) of node ``node`` over token ids
    (B, S), one layer at a time, so that only one layer's weights are
    ever widened to float32."""
    c = dict(cfg)
    x = _embed(params["embed"], node, tokens)
    for li in range(c["num_hidden_layers"]):
        x = _layer(x, params["layers_0"], node, li, cfg=cfg,
                   precision=precision)
    return _final_norm(x, params["ln_f"]["scale"], node,
                       eps=c["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def head_block(h, embed, node, picks, *, k, precision="f32"):
    """For rows ``h (R, d)`` through node ``node``'s tied head: the
    per-row maximum softmax probability (temperature 1), the k best
    logits in descending order with their indices, and the logits at
    each given ``picks (G, R, k)``."""
    logits = _mm("rd,vd->rv", h, embed[node], precision)
    m = logits.max(-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(logits - m), -1)
    top_v, top_i = jax.lax.top_k(logits, k)
    at = jax.vmap(lambda pk: jnp.take_along_axis(logits, pk, -1))(picks)
    return conf, top_v, top_i, at
