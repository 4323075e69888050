"""Composable decoder stack covering all assigned architectures.

Layer params are stacked (L, ...) pytrees consumed by ``lax.scan`` so HLO
size is O(1) in depth (61-layer DeepSeek-V3 lowers in seconds). Per-layer
heterogeneity (Hymba's global-vs-sliding-window layers) rides along the
scan as a (L,) window array; MoE-with-leading-dense stacks (DeepSeek) are
split into two scanned segments.

Public surface (used by the trainer, server, dry-run and IDKD):

    model = DecoderModel(cfg)
    params = model.init(key)
    logits, aux = model.forward(params, batch)
    loss, metrics = model.loss(params, batch)
    state = model.init_decode_state(batch_size, context)
    logits, state = model.decode_step(params, tokens, state)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, dense_init,
                                 embed_init, init_mlp, init_norm)
from repro.models.moe import init_moe, moe_forward, moe_layer


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# §Perf hook: when set (by the launch layer under a mesh), the residual
# stream is re-constrained at every scanned layer so GSPMD cannot drift
# into batch-replicated activations inside the while body.
# Signature: h (B, S, d) -> h.
RESIDUAL_CONSTRAINT = None


def _constrain(h):
    if RESIDUAL_CONSTRAINT is not None:
        return RESIDUAL_CONSTRAINT(h)
    return h


def _remat_policy(cfg: ModelConfig):
    """``jax.checkpoint`` policy for ``cfg.remat_policy``: "nothing"
    (recompute everything — the minimum-HBM default; "full" is its
    legacy alias), "dots" (save matmul outputs, so TP all-reduces are
    not recomputed in the backward pass), "everything" (save all
    residuals — remat as a structural no-op)."""
    name = cfg.remat_policy
    if name in ("nothing", "full"):
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "everything":
        return jax.checkpoint_policies.everything_saveable
    raise ValueError(f"unknown remat_policy {name!r}: expected 'nothing', "
                     "'dots', or 'everything'")


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, kind: str, dtype):
    """kind: 'dense' | 'moe' — the FFN flavour of this layer."""
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"ln1": init_norm(cfg, cfg.d_model, dtype)}
    if cfg.mla.enabled:
        p["attn"] = attn.init_mla(ks[0], cfg, dtype)
    elif not cfg.is_attention_free:
        p["attn"] = attn.init_attention(ks[0], cfg, dtype)
    if cfg.ssm.enabled:
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg, dtype)
        if cfg.hybrid_parallel:
            p["attn_branch_norm"] = jnp.ones((cfg.d_model,), dtype)
            p["ssm_branch_norm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.cross_attention:
        p["ln_cross"] = init_norm(cfg, cfg.d_model, dtype)
        p["cross"] = attn.init_cross_attention(ks[2], cfg, dtype)
    if cfg.d_ff or kind == "moe":
        p["ln2"] = init_norm(cfg, cfg.d_model, dtype)
        if kind == "moe":
            p["moe"] = init_moe(ks[3], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[3], cfg, cfg.d_model, cfg.d_ff, dtype)
    return p


def _mix_forward(p, h, cfg: ModelConfig, window, memory):
    """Token-mixing sub-block (attention / SSM / hybrid-parallel)."""
    if cfg.hybrid_parallel:
        a = attn.attention_forward(p["attn"], h, cfg, layer_window=window)
        s = ssm_mod.ssm_forward(p["ssm"], h, cfg)

        def _rms(x, scale):
            xf = x.astype(jnp.float32)
            ms = jnp.mean(xf * xf, -1, keepdims=True)
            return (xf * jax.lax.rsqrt(ms + cfg.norm_eps)
                    * scale.astype(jnp.float32)).astype(x.dtype)
        return 0.5 * (_rms(a, p["attn_branch_norm"])
                      + _rms(s, p["ssm_branch_norm"]))
    if cfg.ssm.enabled:
        return ssm_mod.ssm_forward(p["ssm"], h, cfg)
    if cfg.mla.enabled:
        with jax.named_scope("mla"):
            return attn.mla_forward(p["attn"], h, cfg)
    return attn.attention_forward(p["attn"], h, cfg, layer_window=window)


def _layer_forward(p, x, cfg: ModelConfig, kind: str, window, memory):
    """One layer: (x, aux loss, counts); counts are the held-expert
    layer's (``moe.moe_layer``), empty for every other layer."""
    h = apply_norm(p["ln1"], x, cfg)
    x = x + _mix_forward(p, h, cfg, window, memory)
    aux = jnp.zeros((), jnp.float32)
    counts = {}
    if cfg.cross_attention and memory is not None:
        h = apply_norm(p["ln_cross"], x, cfg)
        x = x + attn.cross_attention_forward(p["cross"], h, memory, cfg)
    if "ln2" in p:
        h = apply_norm(p["ln2"], x, cfg)
        if kind == "moe":
            y, aux, counts = moe_layer(p["moe"], h, cfg)
        else:
            y = apply_mlp(p["mlp"], h, cfg)
        x = x + y
    return x, aux, counts


def _mix_decode(p, h, cfg: ModelConfig, window, layer_state):
    if cfg.hybrid_parallel:
        a, kv = attn.attention_decode(p["attn"], h, cfg, layer_state["kv"],
                                      layer_window=window)
        s, ssm_state = ssm_mod.ssm_decode(p["ssm"], h, cfg, layer_state["ssm"])

        def _rms(x, scale):
            xf = x.astype(jnp.float32)
            ms = jnp.mean(xf * xf, -1, keepdims=True)
            return (xf * jax.lax.rsqrt(ms + cfg.norm_eps)
                    * scale.astype(jnp.float32)).astype(x.dtype)
        out = 0.5 * (_rms(a, p["attn_branch_norm"])
                     + _rms(s, p["ssm_branch_norm"]))
        return out, {"kv": kv, "ssm": ssm_state}
    if cfg.ssm.enabled:
        out, st = ssm_mod.ssm_decode(p["ssm"], h, cfg, layer_state["ssm"])
        return out, {"ssm": st}
    if cfg.mla.enabled:
        out, st = attn.mla_decode(p["attn"], h, cfg, layer_state["kv"])
        return out, {"kv": st}
    out, st = attn.attention_decode(p["attn"], h, cfg, layer_state["kv"],
                                    layer_window=window)
    return out, {"kv": st}


def _layer_decode(p, x, cfg: ModelConfig, kind: str, window, layer_state,
                  memory):
    h = apply_norm(p["ln1"], x, cfg)
    mix, new_state = _mix_decode(p, h, cfg, window, layer_state)
    x = x + mix
    if cfg.cross_attention and memory is not None:
        h = apply_norm(p["ln_cross"], x, cfg)
        x = x + attn.cross_attention_forward(p["cross"], h, memory, cfg)
    if "ln2" in p:
        h = apply_norm(p["ln2"], x, cfg)
        if kind == "moe":
            y, _ = moe_forward(p["moe"], h, cfg)
        else:
            y = apply_mlp(p["mlp"], h, cfg)
        x = x + y
    return x, new_state


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Segment(NamedTuple):
    kind: str        # 'dense' | 'moe'
    num_layers: int


class DecoderModel:
    """Functional model wrapper; all methods are jit-compatible."""

    input_key = "tokens"

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.moe.enabled and cfg.moe.first_k_dense:
            self.segments = [Segment("dense", cfg.moe.first_k_dense),
                             Segment("moe", cfg.num_layers - cfg.moe.first_k_dense)]
        elif cfg.moe.enabled:
            self.segments = [Segment("moe", cfg.num_layers)]
        else:
            self.segments = [Segment("dense", cfg.num_layers)]

    # -- windows per layer (Hymba global-vs-SWA pattern) --------------------
    def layer_windows(self) -> jnp.ndarray:
        cfg = self.cfg
        L = cfg.num_layers
        if not cfg.sliding_window:
            return jnp.zeros((L,), jnp.int32)
        w = jnp.full((L,), cfg.sliding_window, jnp.int32)
        if cfg.global_attn_every:
            idx = jnp.arange(L)
            is_global = (idx % cfg.global_attn_every == 0) | (idx == L - 1)
            w = jnp.where(is_global, 0, w)
        return w

    def _segment_windows(self):
        w = self.layer_windows()
        out, off = [], 0
        for seg in self.segments:
            out.append(w[off:off + seg.num_layers])
            off += seg.num_layers
        return out

    # -- init ----------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = _dtype(cfg)
        keys = jax.random.split(key, 8)
        p: Dict[str, Any] = {}
        p["embed"] = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)
        if cfg.num_codebooks > 1:
            p["embed_cb"] = jax.vmap(
                lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dtype))(
                jax.random.split(keys[1], cfg.num_codebooks - 1))
        if not cfg.tie_embeddings:
            nheads = max(cfg.num_codebooks, 1)
            p["head"] = jax.vmap(
                lambda k: dense_init(k, cfg.d_model, cfg.vocab_size, dtype))(
                jax.random.split(keys[2], nheads)) if nheads > 1 else \
                dense_init(keys[2], cfg.d_model, cfg.vocab_size, dtype)
        if cfg.num_prefix_tokens and cfg.arch_type == "hybrid":
            # learned meta tokens (Hymba); VLM prefixes come from input_specs
            p["meta_tokens"] = (jax.random.normal(
                keys[3], (cfg.num_prefix_tokens, cfg.d_model)) * 0.02
            ).astype(dtype)
        seg_keys = jax.random.split(keys[4], len(self.segments))
        for si, seg in enumerate(self.segments):
            lkeys = jax.random.split(seg_keys[si], seg.num_layers)
            stacked = jax.vmap(
                lambda k, kind=seg.kind: _init_layer(k, cfg, kind, dtype))(lkeys)
            p[f"layers_{si}"] = stacked
        p["ln_f"] = init_norm(cfg, cfg.d_model, dtype)
        if cfg.mtp_depth:
            p["mtp_proj"] = dense_init(keys[5], 2 * cfg.d_model, cfg.d_model,
                                       dtype)
            kind = self.segments[-1].kind
            p["mtp_layer"] = _init_layer(keys[6], cfg, kind, dtype)
            p["mtp_ln"] = init_norm(cfg, cfg.d_model, dtype)
        return p

    # -- embedding / head ------------------------------------------------------
    def embed_tokens(self, params, tokens):
        cfg = self.cfg
        if cfg.num_codebooks > 1:
            # tokens: (B, S, K) — sum codebook embeddings (MusicGen)
            e = params["embed"][tokens[..., 0]]
            for i in range(cfg.num_codebooks - 1):
                e = e + params["embed_cb"][i][tokens[..., i + 1]]
            return e
        return params["embed"][tokens]

    def logits(self, params, h):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", h, params["embed"])
        if cfg.num_codebooks > 1:
            return jnp.einsum("...d,kdv->...kv", h, params["head"])
        return h @ params["head"]

    # -- forward ----------------------------------------------------------------
    def _run_stack(self, params, h, memory, nodes: int = 0):
        """(h, aux loss, counts summed over the layers).

        ``nodes > 0``: ``params``, ``h`` and ``memory`` carry a leading
        axis of that many nodes, the aux loss and counts come back per
        node, and each layer reads each node's weights by one dynamic
        slice of the node-stacked arrays. (Under a ``vmap`` over nodes
        the scan's per-layer read is a slice across the nodes, for which
        XLA lays every stacked weight out anew: a copy of the model.)"""
        cfg = self.cfg
        aux_total = jnp.zeros((nodes,) if nodes else (), jnp.float32)
        counts = {}
        seg_windows = self._segment_windows()
        for si, seg in enumerate(self.segments):
            stacked = params[f"layers_{si}"]
            windows = seg_windows[si]

            def layer(lp, x, win, mem, kind=seg.kind):
                def f(lp_, x_, win_, mem_):
                    return _layer_forward(lp_, x_, cfg, kind, win_, mem_)
                if cfg.remat:
                    f = jax.checkpoint(f, policy=_remat_policy(cfg))
                if nodes:
                    f = jax.vmap(f, in_axes=(0, 0, None,
                                             None if mem is None else 0))
                return f(lp, x, win, mem)

            def weights(li, stacked=stacked):
                if not nodes:
                    return jax.tree.map(lambda t: t[li], stacked)
                return jax.tree.map(lambda t: jnp.stack([
                    jax.lax.dynamic_slice(
                        t, (i, li) + (0,) * (t.ndim - 2),
                        (1, 1) + t.shape[2:]).reshape(t.shape[2:])
                    for i in range(nodes)]), stacked)

            def body(x, scanned, layer=layer):
                lp, win = scanned
                y, aux, c = layer(lp, x, win, memory)
                return _constrain(y), (aux, c)

            if cfg.scan_layers and seg.num_layers > 1:
                if nodes:
                    h, (auxs, cs) = jax.lax.scan(
                        lambda x, li, body=body, weights=weights,
                        windows=windows: body(x, (weights(li), windows[li])),
                        h, jnp.arange(seg.num_layers))
                else:
                    h, (auxs, cs) = jax.lax.scan(body, h, (stacked, windows))
                aux_total = aux_total + jnp.sum(auxs, 0)
                cs = {k: jnp.sum(v, 0) for k, v in cs.items()}
            else:
                cs = {}
                for li in range(seg.num_layers):
                    h, (aux, c) = body(h, (weights(li), windows[li]))
                    aux_total = aux_total + aux
                    cs = {k: cs.get(k, 0) + v for k, v in c.items()}
            counts = {k: counts.get(k, 0) + v for k, v in cs.items()}
        return h, aux_total, counts

    def _inputs(self, params, batch: Dict[str, Any]):
        """(embedded inputs with any prefix, conditioning memory)."""
        cfg = self.cfg
        h = self.embed_tokens(params, batch["tokens"])
        B = h.shape[0]
        if cfg.arch_type == "hybrid" and cfg.num_prefix_tokens:
            meta = jnp.broadcast_to(params["meta_tokens"][None],
                                    (B,) + params["meta_tokens"].shape)
            h = jnp.concatenate([meta, h], axis=1)
        if cfg.arch_type == "vlm":
            patches = batch["patch_embeddings"].astype(h.dtype)  # (B,P,d)
            h = jnp.concatenate([patches, h], axis=1)
        memory = batch.get("conditioning") if cfg.cross_attention else None
        if memory is not None:
            memory = memory.astype(h.dtype)
        return h, memory

    def _prefix_len(self, batch: Dict[str, Any]) -> int:
        cfg = self.cfg
        if cfg.arch_type == "hybrid" and cfg.num_prefix_tokens:
            return cfg.num_prefix_tokens
        if cfg.arch_type == "vlm":
            return batch["patch_embeddings"].shape[-2]
        return 0

    def _final(self, params, h, n_prefix: int):
        h = apply_norm(params["ln_f"], h, self.cfg)
        return h[..., n_prefix:, :] if n_prefix else h

    def _hidden(self, params, batch: Dict[str, Any]):
        h, memory = self._inputs(params, batch)
        h, aux, counts = self._run_stack(params, h, memory)
        return self._final(params, h, self._prefix_len(batch)), aux, counts

    def hidden(self, params, batch: Dict[str, Any]):
        """Post-stack, post-final-norm hidden states with prefixes stripped.
        Returns (h, aux)."""
        h, aux, _ = self._hidden(params, batch)
        return h, aux

    def stacked_features(self, params, batch: Dict[str, Any]):
        """:meth:`forward_features` of node-stacked ``params`` and
        ``batch`` (a leading node axis on every leaf), counters per
        node; the layer stack reads each node's weights in place
        (:meth:`_run_stack`)."""
        nodes = jax.tree.leaves(params)[0].shape[0]
        h, memory = jax.vmap(self._inputs)(params, batch)
        h, _, counts = self._run_stack(params, h, memory, nodes=nodes)
        n_prefix = self._prefix_len(jax.tree.map(lambda t: t[0], batch))
        return jax.vmap(lambda p, x: self._final(p, x, n_prefix))(
            params, h), counts

    def forward_features(self, params, batch: Dict[str, Any]):
        """(pre-head activations (B, S, d), counters): the
        streaming-labeling hook shared with ResNetModel. The counters are
        the held-expert layers' int32 counts summed over the layers
        (``held_assignments``, ``expert_tiles``), empty for a model
        without such layers."""
        h, _, counts = self._hidden(params, batch)
        return h, counts

    def head_params(self, params):
        """(unembedding (d, V), bias=None) — the matrix the streaming
        head-select kernel tiles over the vocab axis. Multi-codebook
        heads (MusicGen) emit (B, S, K, V) logits that the labeling
        engine does not model; they keep the one-shot path."""
        cfg = self.cfg
        if cfg.num_codebooks > 1:
            raise ValueError("streaming head-select supports a single "
                             "unembedding head; num_codebooks > 1 uses "
                             "the one-shot labeling path")
        if cfg.tie_embeddings:
            return params["embed"].T, None
        return params["head"], None

    def forward(self, params, batch: Dict[str, Any]):
        """Returns (logits, aux). batch['tokens']: (B,S[,K]) int32."""
        h, aux = self.hidden(params, batch)
        return self.logits(params, h), aux

    # -- loss ---------------------------------------------------------------------
    def loss(self, params, batch: Dict[str, Any]):
        cfg = self.cfg
        h, aux = self.hidden(params, batch)
        logits = self.logits(params, h)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
        nll = lse - gold
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(nll.shape, jnp.float32)
        else:
            mask = jnp.broadcast_to(mask[..., None] if mask.ndim < nll.ndim
                                    else mask, nll.shape).astype(jnp.float32)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"nll": loss, "aux": aux}
        if cfg.mtp_depth:
            loss = loss + self._mtp_loss(params, batch, h)
        if cfg.moe.enabled:
            loss = loss + cfg.moe.router_aux_coef * aux
        return loss, metrics

    def _mtp_loss(self, params, batch, h):
        """DeepSeek-V3 multi-token prediction: predict t+2 from
        [h_t ; emb(t_{+1})] through one extra layer, shared head.
        Reuses the trunk hidden states ``h`` (no stack re-run)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        h = apply_norm(params["mtp_ln"], h, cfg)
        emb_next = self.embed_tokens(params, jnp.roll(tokens, -1, axis=1))
        hcat = jnp.concatenate([h, emb_next], axis=-1) @ params["mtp_proj"]
        win = jnp.asarray(0, jnp.int32)
        hcat, _, _ = _layer_forward(params["mtp_layer"], hcat, cfg,
                                 self.segments[-1].kind, win, None)
        logits = self.logits(params, hcat)
        labels = jnp.roll(batch["labels"], -1, axis=1)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
        # mask the wrapped last position
        S = tokens.shape[1]
        mask = (jnp.arange(S) < S - 1).astype(jnp.float32)[None, :]
        mask = jnp.broadcast_to(mask, lse.shape)
        return 0.1 * jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # -- decode -------------------------------------------------------------------
    def init_decode_state(self, batch: int, context: int):
        cfg = self.cfg
        dtype = _dtype(cfg)
        states = []
        for seg in self.segments:
            st = {}
            if cfg.ssm.enabled:
                st["ssm"] = ssm_mod.make_ssm_state(cfg, batch, dtype)
            if cfg.mla.enabled:
                st["kv"] = attn.make_mla_cache(cfg, batch, context, dtype)
            elif not cfg.is_attention_free:
                # uniform cache across scanned layers: ring cap = window
                # only when *every* layer is windowed
                uniform_window = (cfg.sliding_window
                                  and not cfg.global_attn_every)
                st["kv"] = attn.make_kv_cache(
                    cfg, batch, context, dtype,
                    window_override=(cfg.sliding_window if uniform_window
                                     else 0))
            stacked = jax.tree.map(
                lambda t: jnp.broadcast_to(t[None],
                                           (seg.num_layers,) + t.shape), st)
            states.append(stacked)
        return states

    def decode_step(self, params, tokens, states, memory=None):
        """tokens: (B, 1[, K]) — returns (logits, new_states)."""
        cfg = self.cfg
        h = self.embed_tokens(params, tokens)
        seg_windows = self._segment_windows()
        new_states = []
        for si, seg in enumerate(self.segments):
            stacked = params[f"layers_{si}"]
            windows = seg_windows[si]
            st = states[si]

            def body(x, scanned, kind=seg.kind):
                lp, win, layer_state = scanned
                y, new_state = _layer_decode(lp, x, cfg, kind, win,
                                             layer_state, memory)
                return y, new_state

            if cfg.scan_layers and seg.num_layers > 1:
                h, new_st = jax.lax.scan(body, h, (stacked, windows, st))
            else:
                outs = []
                for li in range(seg.num_layers):
                    lp = jax.tree.map(lambda t: t[li], stacked)
                    lst = jax.tree.map(lambda t: t[li], st)
                    h, ns = body(h, (lp, windows[li], lst))
                    outs.append(ns)
                new_st = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            new_states.append(new_st)
        h = apply_norm(params["ln_f"], h, cfg)
        return self.logits(params, h), new_states
