"""Plain reference of Moonlight-16B-A3B (moonshotai/Moonlight-16B-A3B,
``model_type`` deepseek_v3) in float32 jax.numpy, written from the
architecture's description and importing nothing of the program under
test; one chip's share of an expert- and vocab-parallel deployment.

Per layer: RMSNorm; multi-head latent attention in its expanded form
(full-rank q projection to 16 heads of 128 + 64; the kv projection to a
512-wide latent, RMS-normed, plus one shared 64-wide rope key; the latent
expanded to per-head 128-wide keys and values), rotary embeddings on the
64 rope dimensions, a causal softmax at scale 1/sqrt(192), the output
projection and the residual; then RMSNorm and either the dense SwiGLU MLP
(the first layer) or the expert layer, and the residual. The expert
layer routes in float32 over all published experts: sigmoid scores,
ranked by score plus the correction bias, the top 6 weighed by their
unbiased scores normalized over the 6 and scaled by 2.446; it applies
each held expert densely to every token, masked by the weights of the
assignments that pick it, and adds the 2 shared experts (one SwiGLU of
width 2 x 1408). A final RMSNorm and the untied head's slice give the
logits over the vocabulary slice.

Departures from the HF modelling code (``modeling_deepseek.py``):

* RoPE pairs dimension i with i + 32 (rotate-half) over the 64 rope
  dimensions; the HF code pairs 2i with 2i + 1. The two are the same
  map up to a fixed permutation of the rope columns of the q and kv
  projections, which random weights do not see.
* The kv latent's RMSNorm takes ``rms_norm_eps`` (1e-5), as the program
  does; the HF code builds that norm with its default 1e-6. The
  difference is below a bf16 rounding of the latent.
* The routing weights are normalized by their sum (the HF code adds
  1e-20 to it); sigmoid scores keep the sum far from 0.
* Only the held experts' part of the expert layer is computed, and only
  the vocabulary slice: the absent experts' and columns' share of the
  deployment is left out here as in the program.

Weights are the node-stacked parameters in the layout the benchmark
makes them (``embed``, ``head``, ``layers_0`` (dense) and ``layers_1``
(expert layers), each stacked over nodes and then layers, ``ln_f``),
stored in bfloat16 and read one node and one layer at a time.
``precision="f32"`` computes every product at float32 'highest';
``"fp8"`` rounds both operands of every product to float8_e4m3 with a
per-tensor scale (the control that must fail); ``"bf16"`` rounds them to
bfloat16 (the harness's router fit). Routing is float32 at 'highest'
in every mode.
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
    elif precision == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
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


def _swiglu(h, m, precision):
    g = _mm("bsd,df->bsf", h, m["wg"], precision)
    u = _mm("bsd,df->bsf", h, m["wi"], precision)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def attention(x, layers, node, li, *, cfg, precision):
    """``x`` plus layer ``li``'s latent attention, node ``node``'s
    weights read in place; x (B, S, d)."""
    c = dict(cfg)
    H, eps = c["num_attention_heads"], c["rms_norm_eps"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    vd, lat = c["v_head_dim"], c["kv_lora_rank"]
    B, S, _ = x.shape
    a = jax.tree.map(lambda t: t[node, li], layers["attn"])
    ln1 = layers["ln1"]["scale"][node, li]
    h = _rms(x, ln1, eps)
    q = _mm("bsd,de->bse", h, a["wq"], precision).reshape(
        B, S, H, nope + rope)
    kv = _mm("bsd,de->bse", h, a["wkv_a"], precision)
    latent = _rms(kv[..., :lat], a["kv_norm"], eps)
    k_nope = _mm("bsc,ce->bse", latent, a["w_uk"], precision).reshape(
        B, S, H, nope)
    v = _mm("bsc,ce->bse", latent, a["w_uv"], precision).reshape(
        B, S, H, vd)
    theta = c["rope_theta"]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k_rope = _rope(kv[..., None, lat:], theta)           # one shared head
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) / jnp.sqrt(
        float(nope + rope))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, precision).reshape(B, S, H * vd)
    return x + _mm("bse,ed->bsd", o, a["wo"], precision)


@functools.partial(jax.jit, static_argnames=("cfg",))
def router_scores(x, layers, node, li, *, cfg):
    """Sigmoid scores (B, S, E) over all published experts of expert
    layer ``li`` on ``x`` after its attention, float32 at 'highest'."""
    c = dict(cfg)
    h = _rms(x, layers["ln2"]["scale"][node, li], c["rms_norm_eps"])
    router = layers["moe"]["router"][node, li].astype(jnp.float32)
    return jax.nn.sigmoid(jnp.einsum("bsd,de->bse", h, router,
                                     precision=HIGHEST))


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def dense_mlp(x, layers, node, li, *, cfg, precision):
    """``x`` plus the dense layer's SwiGLU MLP."""
    c = dict(cfg)
    h = _rms(x, layers["ln2"]["scale"][node, li], c["rms_norm_eps"])
    m = jax.tree.map(lambda t: t[node, li], layers["mlp"])
    return x + _swiglu(h, m, precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def expert_layer(x, layers, node, li, bias, *, cfg, precision):
    """``x`` plus expert layer ``li``'s held part and shared experts,
    routed with the correction ``bias`` (E,)."""
    c = dict(cfg)
    k, first = c["num_experts_per_tok"], c["first_held_expert"]
    h = _rms(x, layers["ln2"]["scale"][node, li], c["rms_norm_eps"])
    moe = jax.tree.map(lambda t: t[node, li], layers["moe"])
    scores = router_scores(x, layers, node, li, cfg=cfg)
    _, ids = jax.lax.top_k(scores + bias, k)             # bias only ranks
    w = jnp.take_along_axis(scores, ids, -1)
    w = w / w.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    out = _swiglu(h, moe["shared"], precision)
    for e in range(c["n_routed_experts"]):
        pick = jnp.sum(jnp.where(ids == first + e, w, 0.0), -1)
        expert = jax.tree.map(lambda t: t[e], moe["experts"])
        out = out + pick[..., None] * _swiglu(h, expert, precision)
    return x + out


@jax.jit
def _embed(embed, node, tokens):
    return embed[node].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, node, *, eps):
    return _rms(x, scale[node], eps)


def hidden(params, node, tokens, biases, *, cfg, precision="f32"):
    """Final-norm hidden states (B, S, d) of node ``node`` over token ids
    (B, S), one layer at a time, so that only one layer's weights are
    ever widened to float32; ``biases (L_moe, E)`` are the node's
    correction biases."""
    c = dict(cfg)
    x = _embed(params["embed"], node, tokens)
    dense, moe = params["layers_0"], params["layers_1"]
    for li in range(c["first_k_dense_replace"]):
        x = attention(x, dense, node, li, cfg=cfg, precision=precision)
        x = dense_mlp(x, dense, node, li, cfg=cfg, precision=precision)
    for li in range(c["num_hidden_layers"] - c["first_k_dense_replace"]):
        x = attention(x, moe, node, li, cfg=cfg, precision=precision)
        x = expert_layer(x, moe, node, li, biases[li], cfg=cfg,
                         precision=precision)
    return _final_norm(x, params["ln_f"]["scale"], node,
                       eps=c["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def head_block(h, head, node, picks, *, k, precision="f32"):
    """For rows ``h (R, d)`` through node ``node``'s head slice ``(d,
    V)``: the per-row maximum softmax probability (temperature 1), the k
    best logits in descending order with their indices, and the logits
    at each given ``picks (G, R, k)``."""
    logits = _mm("rd,dv->rv", h, head[node], precision)
    m = logits.max(-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(logits - m), -1)
    top_v, top_i = jax.lax.top_k(logits, k)
    at = jax.vmap(lambda pk: jnp.take_along_axis(logits, pk, -1))(picks)
    return conf, top_v, top_i, at
