"""Mixture-of-experts layer with sort-based (argsort-by-expert) dispatch.

TPU-native design: instead of a GShard one-hot dispatch einsum (whose
(tokens × experts × capacity) tensor is prohibitive at 256 experts), tokens
are argsorted by routed expert id and scattered into per-expert capacity
buffers (E, C, d). The per-expert FFN is then one block einsum on the MXU.
Experts are sharded over the ``model`` mesh axis; XLA inserts the
all-to-alls at the token→expert buffer boundary.

Supports: top-k softmax routing (Arctic), sigmoid routing with bias-based
aux-free balancing (DeepSeek-V3), shared experts, Arctic's dense-residual
parallel branch, and an optional load-balance auxiliary loss.

Held experts (``MoEConfig.held_experts``): a layer told that it holds
routed experts ``[first, first + count)`` (a chip's share under expert
parallelism) keeps its router at the published width, routes every
token over all ``num_experts``, and computes, without dropping any
assignment, only the part of the output its own experts give, plus the
shared experts for every token (``kernels/expert_ffn``). What the absent
experts would add is left out; their exchange is not modelled. Its
operations carry the name scopes ``moe_route``, ``moe_dispatch``,
``expert_ffn`` and ``moe_combine``, and it counts ``held_assignments``
and ``expert_tiles`` (:func:`moe_layer`).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.expert_ffn import expert_ffn
from repro.models.layers import apply_mlp, dense_init, init_mlp


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    glu = cfg.mlp_type in ("swiglu", "geglu")

    def expert_bank(k, n, dff):
        kk = jax.random.split(k, 3)
        p = {"wi": jax.vmap(lambda q: dense_init(q, d, dff, dtype))(
                jax.random.split(kk[0], n)),
             "wo": jax.vmap(lambda q: dense_init(q, dff, d, dtype))(
                jax.random.split(kk[1], n))}
        if glu:
            p["wg"] = jax.vmap(lambda q: dense_init(q, d, dff, dtype))(
                jax.random.split(kk[2], n))
        return p

    p = {"router": dense_init(ks[0], d, m.num_experts, dtype, scale=0.02),
         "router_bias": jnp.zeros((m.num_experts,), jnp.float32),
         "experts": expert_bank(ks[1], m.held_range[1], m.moe_d_ff)}
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[2], cfg, d, m.num_shared_experts * m.moe_d_ff,
                               dtype)
    if m.dense_residual_ff:
        p["dense_residual"] = init_mlp(ks[3], cfg, d, m.dense_residual_ff, dtype)
    return p


def _route(params, x, cfg: ModelConfig):
    """Router: returns (expert_ids (T,k), weights (T,k), aux_loss).

    Sigmoid scores (DeepSeek-V3) rank by ``score + router_bias`` and
    weigh by the unbiased score; the top-k weights are normalized and
    scaled by ``routed_scaling_factor``. ``router_f32``: the logits
    from float32 operands at 'highest', as DeepSeek-V3's gate computes
    them."""
    m = cfg.moe
    if m.router_f32:
        logits = jnp.dot(x.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    else:
        logits = (x @ params["router"]).astype(jnp.float32)   # (T, E)
    if m.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + params["router_bias"]               # bias only ranks
        _, ids = jax.lax.top_k(biased, m.num_experts_per_tok)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, m.num_experts_per_tok)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    w = w * m.routed_scaling_factor
    # Switch-style load-balance aux loss (density * mean prob per expert).
    probs = jax.nn.softmax(logits, axis=-1)
    density = jnp.mean(
        jax.nn.one_hot(ids[:, 0], m.num_experts, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(density * mean_prob)
    return ids, w, aux


def _expert_ffn(bank, xb, cfg: ModelConfig):
    """xb: (E, C, d) -> (E, C, d) via per-expert GLU MLP."""
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, bank["wg"])) * \
            jnp.einsum("ecd,edf->ecf", xb, bank["wi"])
    elif cfg.mlp_type == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xb, bank["wg"])) * \
            jnp.einsum("ecd,edf->ecf", xb, bank["wi"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xb, bank["wi"]))
    return jnp.einsum("ecf,efd->ecd", h, bank["wo"])


def _dispatch_group(xt, ids, w, bank, cfg: ModelConfig, cap: int):
    """Sort-based dispatch for ONE token group.

    xt: (T, d); ids/w: (T, k). Local argsort by expert id → per-expert
    capacity buffers → block einsum → weighted combine."""
    m = cfg.moe
    T, d = xt.shape
    k = m.num_experts_per_tok
    E = m.num_experts
    flat_ids = ids.reshape(T * k)                             # assignment ids
    flat_w = w.reshape(T * k)
    order = jnp.argsort(flat_ids)                             # stable sort
    sorted_ids = flat_ids[order]
    # position of each assignment within its expert's buffer
    same = jnp.cumsum(jnp.ones_like(sorted_ids)) - 1
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(E))   # (E,)
    pos_in_expert = same - seg_start[sorted_ids]
    keep = pos_in_expert < cap
    token_of = order // k                                     # source token
    # scatter tokens into (E*cap, d) buffers (last row = dropped slot)
    dest = jnp.where(keep, sorted_ids * cap + pos_in_expert, E * cap)
    buf = jnp.zeros((E * cap + 1, d), xt.dtype)
    buf = buf.at[dest].set(xt[token_of])
    yb = _expert_ffn(bank, buf[:-1].reshape(E, cap, d), cfg)
    yb = jnp.concatenate([yb.reshape(E * cap, d),
                          jnp.zeros((1, d), xt.dtype)])
    y_assign = yb[dest] * (flat_w[order] * keep)[:, None]
    return jnp.zeros((T, d), xt.dtype).at[token_of].add(y_assign)


def moe_forward(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss); :func:`moe_layer` without its
    counts."""
    out, aux, _ = moe_layer(params, x, cfg)
    return out, aux


def moe_layer(params, x, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (out, aux_loss, counts).

    Held experts (``held_experts`` set): dropless, through
    ``kernels/expert_ffn``; ``counts`` are its ``held_assignments`` and
    ``expert_tiles`` (int32). Otherwise the capacity path below and no
    counts.

    With ``dispatch_groups == 1`` the argsort spans all tokens (simple but
    unshardable: GSPMD must all-gather every token — see EXPERIMENTS §Perf).
    With G > 1 tokens are split into G groups (aligned with the data
    shards), each group sorts locally with capacity cap/G, and the
    group→expert movement lowers to all-to-alls."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        ids, w, aux = _route(params, xt, cfg)                 # (T,k)
    if m.held_experts is not None:
        base = (apply_mlp(params["shared"], xt, cfg) if m.num_shared_experts
                else jnp.zeros_like(xt))
        bank = params["experts"]
        out, counts = expert_ffn(xt, bank["wg"], bank["wi"], bank["wo"],
                                 ids - m.held_experts[0], w, base)
        return out.reshape(B, S, d), aux, counts
    w = w.astype(x.dtype)
    k = m.num_experts_per_tok
    E = m.num_experts
    G = max(1, m.dispatch_groups)
    if T % G:
        G = 1
    cap = int(m.capacity_factor * (T // G) * k / E) + 1
    if G == 1:
        out = _dispatch_group(xt, ids, w, params["experts"], cfg, cap)
    else:
        xg = xt.reshape(G, T // G, d)
        idg = ids.reshape(G, T // G, k)
        wg = w.reshape(G, T // G, k)
        out = jax.vmap(lambda a, b, c: _dispatch_group(
            a, b, c, params["experts"], cfg, cap))(xg, idg, wg)
        out = out.reshape(T, d)
    if m.num_shared_experts:
        out = out + apply_mlp(params["shared"], xt, cfg)
    if m.dense_residual_ff:
        out = out + apply_mlp(params["dense_residual"], xt, cfg)
    return out.reshape(B, S, d), aux, {}
