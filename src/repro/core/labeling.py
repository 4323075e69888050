"""Unified IDKD labeling engine — the paper's homogenization round
(Algorithm 1, lines 5–14) as one backend-agnostic path.

One call, :func:`label_round`, owns the whole round for every consumer:

  (line 5)  soft labels     softmax(f_i(D_P) / T)
  (line 6)  t_opt           ROC-calibrated detector threshold per node
  (line 7)  D_ID^i          {p : conf_p > t_opt}
  (l. 9-13) exchange        labels-only gossip with graph neighbours
  (line 14) average         per-sample mean over contributing nodes

Three interchangeable backends (``IDKDConfig.label_backend``):

``dense``
    The jnp reference and numerical oracle. Labels are full ``(n, P, C)``
    probability tensors; the exchange is a scan over padded neighbour
    slots (``Topology.neighbor_arrays``) — O(Σ deg · P · C) work and
    O(n · P · C) memory. (The seed's ``(n, n, P)`` membership einsum was
    O(n² · P · C); it is gone.)

``fused``
    Public-set logits are read once: detector confidence *and* the top-k
    sparse soft-label payload come out of a single fused pass — the
    ``msp_select`` Pallas kernel on TPU, its jnp oracle (which XLA fuses
    the same way) elsewhere. Output is sparse, exchanged sparsely.

``sparse``
    Like ``fused`` but scored/sparsified with plain jnp ops. Labels cross
    the "wire" as :class:`repro.core.distill.SparseLabels` (top-k values +
    class indices) and are *never* densified to ``(n, P, C)``: neighbour
    averaging concatenates the contributors' payloads along the k axis
    with 1/cnt weights (exact — see DESIGN.md §2), and training consumes
    them through ``distill.sparse_kd_loss``. Exchange cost is
    O(Σ deg · P · k) instead of O(Σ deg · P · C).

Simulation (``core.simulator``) and production launch (``launch.train``)
both call this engine; classifier ``(n, P, C)`` and LM ``(n, P, S, V)``
logit stacks are handled uniformly (sequence confidence = mean over S of
the per-token detector score).

**Streaming rounds** (DESIGN.md §8). :func:`label_round` takes
pre-materialized logit stacks — O(n · P · C) HBM for the round's input
alone, the dominant cost at LLM vocab. :func:`streaming_label_round`
is the production form of the fused/sparse backends: it takes the
*models* (via their ``forward_features`` / ``head_params`` hooks) and
``lax.scan``s the public set through them in microbatches, running the
fused head-select pass (``kernels/head_select`` on TPU, its jnp oracle
elsewhere) per chunk and accumulating only ``(conf, top-k values,
top-k indices)`` — peak memory O(microbatch · C) + O(n · P · k); the
full logit stack never exists. :func:`shard_streaming_label_round` is
its ``shard_map`` twin: the scan lives inside the shard body, so
score/calibrate/select stay shard-local and only top-k payloads cross
the node axis.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import IDKDConfig
from repro.core import distill, ood
from repro.core.topology import Topology
from repro.kernels.head_select import (BLOCK_C, NEG_INF, head_row_tile,
                                       head_select, head_select_ref,
                                       head_select_stats_ref,
                                       merge_head_stats)
from repro.kernels.msp_select import msp_select, msp_select_ref
from repro.obs.trace import span

BACKENDS = ("dense", "fused", "sparse")
DEFAULT_TOPK = 8


class HomogenizedSet(NamedTuple):
    """Per-node distilled public subset, dense labels (node-stacked)."""
    labels: jax.Array        # (n, P[, S], C) averaged soft labels
    weights: jax.Array       # (n, P) 1.0 where sample is in node's D_ID∪neigh
    id_masks: jax.Array      # (n, P) the node's own D_ID mask (diagnostics)
    thresholds: jax.Array    # (n,) calibrated t_opt per node


class SparseHomogenizedSet(NamedTuple):
    """Per-node distilled public subset with top-k sparse labels.

    ``labels.values/indices`` have shape (n, P[, S], k_out) where
    k_out = (max_degree + 1) · k; duplicate indices are legal (every
    consumer — ``sparse_kd_loss``, ``densify_labels``, the histogram
    diagnostics — accumulates them).
    """
    labels: distill.SparseLabels
    weights: jax.Array       # (n, P)
    id_masks: jax.Array      # (n, P)
    thresholds: jax.Array    # (n,)
    counters: Optional[Dict[str, jax.Array]] = None
                             # streaming rounds: the models' forward
                             # counters (``forward_features``), (n,) each,
                             # summed over both passes

    def densify(self, num_classes: int) -> jax.Array:
        """Materialize (n, P[, S], C) labels — diagnostics/tests ONLY;
        production paths keep the payload sparse end to end."""
        return distill.densify_labels(self.labels, num_classes)


HomogenizedResult = Union[HomogenizedSet, SparseHomogenizedSet]


def detector_scores(logits, detector: str) -> jax.Array:
    """Per-sample detector confidence. (n, P, C) -> (n, P); LM logit
    stacks (n, P, S, V) reduce to sequence scores by the mean over S of
    the per-token score (matches ``ood.sequence_confidence`` for MSP)."""
    conf = ood.confidence(logits, detector)
    if conf.ndim == 3:
        conf = conf.mean(-1)
    return conf


def calibrate(conf_val, conf_cal) -> jax.Array:
    """Per-node ROC thresholds (line 6): val = ID class, cal = OoD."""
    return jax.vmap(ood.calibrate_threshold)(conf_val, conf_cal)


# --------------------------------------------------------------- exchange
def exchange_dense(topology: Topology, id_mask, labels
                   ) -> Tuple[jax.Array, jax.Array]:
    """Lines 9–14, dense labels: per-sample mean over the contributing
    nodes (self + neighbours whose D_ID contains the sample).

    Implemented as a scan over padded neighbour slots with a gathered
    running mean — O(Σ deg · P · C) work, O(n · P · C) memory.
    """
    nbr, valid = topology.neighbor_arrays()
    nbr = jnp.asarray(nbr)
    valid = jnp.asarray(valid)
    lf = labels.astype(jnp.float32)
    m = id_mask.astype(jnp.float32)                        # (n, P)
    extra = lf.ndim - m.ndim                               # trailing axes

    def body(carry, slot):
        num, cnt = carry
        j, ok = slot                                       # (n,), (n,)
        w = m[j] * ok[:, None]                             # (n, P)
        num = num + w.reshape(w.shape + (1,) * extra) * lf[j]
        cnt = cnt + w
        return (num, cnt), None

    init = (jnp.zeros_like(lf), jnp.zeros_like(m))
    (num, cnt), _ = jax.lax.scan(body, init, (nbr.T, valid.T))
    avg = num / jnp.maximum(cnt, 1.0).reshape(cnt.shape + (1,) * extra)
    return avg, (cnt > 0).astype(jnp.float32)


def exchange_sparse(topology: Topology, id_mask, sparse: distill.SparseLabels
                    ) -> Tuple[distill.SparseLabels, jax.Array]:
    """Lines 9–14 on top-k sparse payloads, without densifying.

    The mean over contributors ``Σ_j m_j · dense(s_j) / cnt`` distributes
    over the scatter, so it equals the *concatenation* of the
    contributors' (values · m_j / cnt, indices) pairs along the k axis.
    Output k_out = (max_degree + 1) · k with zero-valued padding slots;
    O(Σ deg · P · k) work and bytes. Its operations carry the name
    scope ``exchange``.
    """
    with jax.named_scope("exchange"):
        nbr, valid = topology.neighbor_arrays()
        nbr = jnp.asarray(nbr)
        valid = jnp.asarray(valid)
        m = id_mask.astype(jnp.float32)
        w = m[nbr] * valid[:, :, None]                     # (n, D, P)
        cnt = jnp.sum(w, axis=1)                           # (n, P)
        share = w / jnp.maximum(cnt, 1.0)[:, None, :]
        vals = sparse.values[nbr]                    # (n, D, P[, S], k)
        idx = sparse.indices[nbr]
        extra = vals.ndim - share.ndim                     # e.g. the S axis
        vals = vals * share.reshape(share.shape + (1,) * extra)
        # merge the contributor axis into k: (n, P[, S], D·k)
        vals = jnp.moveaxis(vals, 1, -2)
        idx = jnp.moveaxis(idx, 1, -2)
        vals = vals.reshape(vals.shape[:-2] + (-1,))
        idx = idx.reshape(idx.shape[:-2] + (-1,))
        return (distill.SparseLabels(vals.astype(jnp.float32),
                                     idx.astype(jnp.int32)),
                (cnt > 0).astype(jnp.float32))


# ------------------------------------------------------------ fused pass
_fused_oracle = jax.jit(
    msp_select_ref, static_argnames=("temperature", "k", "detector"))
_stream_oracle = jax.jit(
    head_select_ref, static_argnames=("temperature", "k", "detector"))


def _fused_pass(logits, cfg: IDKDConfig, k: int
                ) -> Tuple[jax.Array, distill.SparseLabels]:
    """One read of the public logits: detector confidence + top-k payload.

    TPU: the ``msp_select`` Pallas kernel (single HBM pass over the
    (rows, C) logits). Elsewhere: its jnp oracle under jit — same fused
    dataflow, so CPU tests exercise identical math. The D_ID mask is not
    computed here: the threshold is calibrated from these confidences
    downstream, so membership is one caller-owned compare.
    """
    lead, C = logits.shape[:-1], logits.shape[-1]
    flat = logits.reshape(-1, C)
    if jax.default_backend() == "tpu":
        block = cfg.select_block_rows
        pad = (-flat.shape[0]) % block
        n_rows = flat.shape[0]
        if pad:
            flat = jnp.pad(flat, ((0, pad), (0, 0)))
        conf, vals, idx = msp_select(
            flat, temperature=cfg.temperature, k=k, block_n=block,
            detector=cfg.detector)
        conf, vals, idx = conf[:n_rows], vals[:n_rows], idx[:n_rows]
    else:
        conf, vals, idx = _fused_oracle(
            flat, temperature=cfg.temperature, k=k, detector=cfg.detector)
    conf = conf.reshape(lead)
    if conf.ndim == 3:                                     # (n, P, S) tokens
        conf = conf.mean(-1)
    sparse = distill.SparseLabels(vals.reshape(lead + (k,)),
                                  idx.reshape(lead + (k,)))
    return conf, sparse


def _select(model, params_i, feats, cfg: IDKDConfig, k: int):
    """The fused head-select of one node's pre-head activations: the
    head matrix is applied *inside* the select — the ``head_select``
    Pallas kernel tiles the vocab axis on TPU, its jnp oracle forms only
    a microbatch-sized logit chunk elsewhere. Returns per-sample
    ``(conf, vals, idx)`` with LM token confidences already reduced to
    sequence scores (mean over S)."""
    w, b = model.head_params(params_i)
    lead = feats.shape[:-1]                                # (mb,) or (mb, S)
    flat = feats.reshape(-1, feats.shape[-1])
    if jax.default_backend() == "tpu":
        conf, vals, idx = head_select(
            flat, w, b, temperature=cfg.temperature, k=k,
            block_rows=cfg.select_block_rows, detector=cfg.detector)
    else:
        conf, vals, idx = _stream_oracle(
            flat, w, b, temperature=cfg.temperature, k=k,
            detector=cfg.detector)
    conf = conf.reshape(lead)
    if conf.ndim == 2:                                     # (mb, S) tokens
        conf = conf.mean(-1)
    return conf, vals.reshape(lead + (k,)), idx.reshape(lead + (k,))


def _head_pass(model, params, x, cfg: IDKDConfig, k: int):
    """The fused head-select pass of every node on one input microbatch
    (node-stacked ``params`` and ``x``): the model's
    ``stacked_features`` yields the pre-head activations (the decoder
    runs its layer stack on the node-stacked weights in place; a vmap
    over them has XLA lay every layer stack out anew, a copy of each
    node's model), then :func:`_select` per node. Returns ``(conf,
    vals, idx, counters)``, counters those of the forward."""
    feats, counters = model.stacked_features(params, {model.input_key: x})
    conf, vals, idx = jax.vmap(
        lambda p, f: _select(model, p, f, cfg, k))(params, feats)
    return conf, vals, idx, counters


def _vocab_sharded_head_pass(model, params_i, x, cfg: IDKDConfig, k: int,
                             model_axis: str, model_size: int):
    """One node's head pass on the 2-D federation mesh (DESIGN.md §10):
    each model-axis shard runs the fused select over its own vocab slice
    — ``O(mb · C / model_size)`` scores, never the full row — and the
    per-shard online-softmax stats ``(m, z)`` + top-k raw logits merge
    across the model axis with the kernel's own cross-tile streaming
    math (``merge_head_stats``). The finalizer (detector confidence,
    temperature renormalization) runs only on the merged stats, so the
    result matches the unsharded pass: indices exactly, conf/vals to
    float tolerance.

    The vocab slice is cut here (pad C to ``model_size`` equal slices;
    padded columns get a ``NEG_INF`` bias so they self-mask out of both
    ``z`` and the top-k) rather than read from the storage sharding, so
    ragged ``C % model_size != 0`` heads and replicated small heads work
    identically. Runs inside ``shard_map`` (under the node-block vmap);
    all collectives are over ``model_axis`` only.
    """
    feats, counters = model.forward_features(params_i, {model.input_key: x})
    w, b = model.head_params(params_i)
    C = w.shape[-1]
    w_sh = -(-C // model_size)
    pad_c = w_sh * model_size - C
    if b is None:
        b = jnp.zeros((C,), jnp.float32)
    if pad_c:
        w = jnp.pad(w, ((0, 0), (0, pad_c)))
        b = jnp.pad(b.astype(jnp.float32), (0, pad_c),
                    constant_values=NEG_INF)
    j = jax.lax.axis_index(model_axis)
    w_loc = jax.lax.dynamic_slice_in_dim(w, j * w_sh, w_sh, axis=1)
    b_loc = jax.lax.dynamic_slice_in_dim(b, j * w_sh, w_sh, axis=0)
    k_loc = min(k, w_sh)
    lead = feats.shape[:-1]                                # (mb,) or (mb, S)
    flat = feats.reshape(-1, feats.shape[-1])
    if jax.default_backend() == "tpu":
        ms, zs, tv, ti = head_select(
            flat, w_loc, b_loc, temperature=cfg.temperature, k=k_loc,
            block_rows=cfg.select_block_rows, detector=cfg.detector,
            raw_stats=True)
    else:
        ms, zs, tv, ti = head_select_stats_ref(flat, w_loc, b_loc, k=k_loc)
    ti = ti + j * w_sh                                     # global vocab idx
    conf, vals, idx = merge_head_stats(
        jax.lax.all_gather(ms, model_axis),
        jax.lax.all_gather(zs, model_axis),
        jax.lax.all_gather(tv, model_axis),
        jax.lax.all_gather(ti, model_axis),
        temperature=cfg.temperature, k=k, detector=cfg.detector)
    conf = conf.reshape(lead)
    if conf.ndim == 2:                                     # (mb, S) tokens
        conf = conf.mean(-1)
    return (conf, vals.reshape(lead + (k,)), idx.reshape(lead + (k,)),
            counters)


def _head_width(model, params) -> int:
    """Class/vocab count C from the head shape (no compute — eval_shape
    on one node's param slice)."""
    one = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), params)
    return jax.eval_shape(lambda p: model.head_params(p)[0], one).shape[-1]


def _microbatch(P: int, microbatch: int) -> int:
    return max(1, min(microbatch or 256, P))


def _chunk_public(public_x, microbatch: int):
    """(P, ...) -> ((num_chunks, mb, ...), P, mb). The ragged tail is
    padded by repeating row 0 (real inputs, outputs sliced off)."""
    pub = jnp.asarray(public_x)
    P = pub.shape[0]
    mb = _microbatch(P, microbatch)
    num_chunks = -(-P // mb)
    pad = num_chunks * mb - P
    if pad:
        pub = jnp.concatenate(
            [pub, jnp.broadcast_to(pub[:1], (pad,) + pub.shape[1:])])
    return pub.reshape((num_chunks, mb) + pub.shape[1:]), P, mb


def head_reads(model, params, public_x, val_x, cfg: IDKDConfig, *,
               model_size: int = 1) -> int:
    """Whole-head reads of one streaming round on TPU, summed over the
    nodes and both passes (public and calibration, as the round runs
    with ``filter_ood``): the row tiles ``head_select`` takes in each
    call (:func:`repro.kernels.head_select.head_row_tile`), from shapes
    alone (``eval_shape`` of one node's features and head). On the
    vocab-sharded mesh (``model_size > 1``) a row tile reads each
    shard's slice once, one whole head between them. Off TPU the jnp
    oracle runs instead; the count is that of the same shapes."""
    n = jax.tree.leaves(params)[0].shape[0]
    one = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), params)

    def tiles(x_shape, x_dtype):
        feats, w = jax.eval_shape(
            lambda p, x: (model.forward_features(
                p, {model.input_key: x})[0], model.head_params(p)[0]),
            one, jax.ShapeDtypeStruct(
                x_shape, jax.dtypes.canonicalize_dtype(x_dtype)))
        rows = math.prod(feats.shape[:-1])
        block_c = min(BLOCK_C, -(-w.shape[-1] // model_size))
        tile = head_row_tile(rows, feats.shape[-1], block_c,
                             cfg.select_block_rows, feats.dtype, w.dtype)
        return -(-rows // tile)

    P = public_x.shape[0]
    mb = _microbatch(P, cfg.stream_microbatch)
    return n * (-(-P // mb) * tiles((mb,) + public_x.shape[1:],
                                    public_x.dtype)
                + tiles(val_x.shape[1:], val_x.dtype))


def _stream_public(model, params, chunks, P: int, cfg: IDKDConfig, k: int,
                   head_pass=_head_pass):
    """Scan the chunked public set through the fused head pass of every
    node of a (possibly local) block at once; accumulate only (conf,
    vals, idx) and the forward's counters, summed over the chunks ((L,)
    each). ``head_pass`` swaps in the vocab-sharded pass on the 2-D
    mesh. The scan body's operations carry the name scope
    ``public_pass``.
    """
    L = jax.tree.leaves(params)[0].shape[0]

    def one_chunk(xc):                                     # (mb, ...)
        xb = jnp.broadcast_to(xc[None], (L,) + xc.shape)
        with jax.named_scope("public_pass"):
            return head_pass(model, params, xb, cfg, k)

    _, (conf, vals, idx, counters) = jax.lax.scan(
        lambda carry, xc: (carry, one_chunk(xc)), None, chunks)
    counters = {name: c.sum(0) for name, c in counters.items()}
    total = conf.shape[0] * conf.shape[2]                  # chunks · mb
    conf = jnp.moveaxis(conf, 0, 1).reshape(L, total)[:, :P]
    vals = jnp.moveaxis(vals, 0, 1)
    vals = vals.reshape((L, total) + vals.shape[3:])[:, :P]
    idx = jnp.moveaxis(idx, 0, 1)
    idx = idx.reshape((L, total) + idx.shape[3:])[:, :P]
    return conf, distill.SparseLabels(vals, idx), counters


def _stream_val_conf(model, params, val_x, cfg: IDKDConfig,
                     head_pass=_head_pass):
    """Per-node detector confidence on each node's own (small) val set,
    through the same fused head pass (k=1: only conf is consumed), and
    the forward's counters; its operations carry the name scope
    ``calibration_pass``."""
    val_x = jnp.asarray(val_x)
    with jax.named_scope("calibration_pass"):
        conf, _, _, counters = head_pass(model, params, val_x, cfg, 1)
    return conf, counters


# ------------------------------------------------------------ full round
def label_round(public_logits, val_logits, cal_logits, topology: Topology,
                cfg: IDKDConfig, *, backend: str = "dense",
                filter_ood: bool = True, active=None) -> HomogenizedResult:
    """One IDKD homogenization round on node-stacked logits.

    public_logits: (n, P, C) or (n, P, S, V) — each node on the public set
    val_logits:    (n, V, C) / (n, V, S, Vv) — each node on its private ID set
    cal_logits:    (n, K, C) / ... — each node on the OoD calibration set,
                   or None for D_C = D_P (the paper's default; the public
                   scores are reused instead of re-read — pass None rather
                   than public_logits under jit, where the identity check
                   cannot see through tracers)
    filter_ood:    False = the ``kd_mode="vanilla"`` baseline (no detector:
                   every public sample is kept, thresholds are 0)
    active:        optional (n,) availability mask (scheduler churn): a
                   down node contributes no D_ID labels to the exchange
                   and receives none (its weights come back all-zero), so
                   repeated rounds under churn only ever move labels
                   between live nodes

    Returns :class:`HomogenizedSet` (dense backend) or
    :class:`SparseHomogenizedSet` (fused / sparse backends).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown labeling backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    n = public_logits.shape[0]
    k = min(cfg.label_topk or DEFAULT_TOPK, public_logits.shape[-1])

    sparse = None
    if backend == "fused":
        conf_pub, sparse = _fused_pass(public_logits, cfg, k)
    else:
        conf_pub = detector_scores(public_logits, cfg.detector)

    if filter_ood:
        # D_C = D_P (None or the same array): reuse the public scores
        # instead of re-reading the (n, P, C) logits a second time
        conf_cal = (conf_pub
                    if cal_logits is None or cal_logits is public_logits
                    else detector_scores(cal_logits, cfg.detector))
        thresholds = calibrate(detector_scores(val_logits, cfg.detector),
                               conf_cal)
        id_mask = conf_pub > thresholds[:, None]
    else:
        thresholds = jnp.zeros((n,), jnp.float32)
        id_mask = jnp.ones(conf_pub.shape, bool)
    if active is not None:
        act = jnp.asarray(active, bool)
        id_mask = id_mask & act[:, None]

    if backend == "dense":
        labels = distill.soft_labels(public_logits, cfg.temperature)
        avg, weights = exchange_dense(topology, id_mask, labels)
        if active is not None:
            weights = weights * act[:, None]
        return HomogenizedSet(avg, weights, id_mask, thresholds)

    if sparse is None:                                     # backend == sparse
        probs = distill.soft_labels(public_logits, cfg.temperature)
        sparse = distill.sparsify_labels(probs, k)
    merged, weights = exchange_sparse(topology, id_mask, sparse)
    if active is not None:
        weights = weights * act[:, None]
    return SparseHomogenizedSet(merged, weights, id_mask, thresholds)


# ---------------------------------------------------------- streaming round
def streaming_label_round(model, params, public_x, val_x,
                          topology: Topology, cfg: IDKDConfig, *,
                          filter_ood: bool = True, active=None
                          ) -> SparseHomogenizedSet:
    """One IDKD homogenization round without ever materializing the
    public logit stack (DESIGN.md §8).

    Instead of node-stacked logits this takes the *model* (via its
    ``forward_features`` / ``head_params`` hooks) and node-stacked
    ``params``, and streams the shared public set through every node in
    microbatches of ``cfg.stream_microbatch``: one ``lax.scan`` whose
    body runs the per-node forward to pre-head activations and the
    fused head-select pass (``kernels/head_select`` on TPU, its jnp
    oracle elsewhere), accumulating only ``(conf, top-k values, top-k
    indices)``. Peak memory is O(n · microbatch · C) for the in-flight
    chunk plus O(n · P · k) for the accumulated payload — the
    O(n · P · C) tensor of :func:`label_round` never exists, which is
    what lets the public corpus scale past device memory.

    ``public_x``: (P, ...) shared public inputs (images or tokens);
    ``val_x``:    (n, V, ...) each node's own private ID inputs;
    D_C = D_P (the paper's default): the public confidences double as
    the OoD calibration scores. Numerically this is the fused backend
    of :func:`label_round` to float tolerance (online-softmax detector
    stats, blockwise top-k merge), and it always produces sparse top-k
    labels — the wire format the streaming path exists to preserve.
    ``filter_ood`` / ``active`` behave exactly as in
    :func:`label_round`.

    Its host phases are :func:`repro.obs.trace.span`s, each around the
    tracing, lowering and dispatch of its device work (none reads a
    device value back): ``idkd.public_pass``,
    ``idkd.calibration_pass``, ``idkd.threshold`` (calibrate and mask)
    and ``idkd.exchange``.
    """
    n = jax.tree.leaves(params)[0].shape[0]
    if topology.n != n:
        raise ValueError(f"param stack has {n} nodes, topology "
                         f"{topology.name!r} has {topology.n}")
    C = _head_width(model, params)
    k = min(cfg.label_topk or DEFAULT_TOPK, C)
    with span("idkd.public_pass"):
        chunks, P, _ = _chunk_public(public_x, cfg.stream_microbatch)
        conf_pub, sparse, counters = _stream_public(model, params, chunks,
                                                    P, cfg, k)

    if filter_ood:
        with span("idkd.calibration_pass"):
            conf_val, val_counters = _stream_val_conf(model, params, val_x,
                                                      cfg)
        counters = {name: c + val_counters[name]
                    for name, c in counters.items()}
    with span("idkd.threshold"):
        if filter_ood:
            thresholds = calibrate(conf_val, conf_pub)
            id_mask = conf_pub > thresholds[:, None]
        else:
            thresholds = jnp.zeros((n,), jnp.float32)
            id_mask = jnp.ones(conf_pub.shape, bool)
        if active is not None:
            act = jnp.asarray(active, bool)
            id_mask = id_mask & act[:, None]
    with span("idkd.exchange"):
        merged, weights = exchange_sparse(topology, id_mask, sparse)
        if active is not None:
            weights = weights * act[:, None]
    return SparseHomogenizedSet(merged, weights, id_mask, thresholds,
                                counters)


# ------------------------------------------------------------ sharded round
def _shard_layout(topology: Topology, n: int, mesh, axis: str):
    """Shared shard-round validation: node-count divisibility and the
    ring/complete support set. Returns (size, ring, full)."""
    from repro.core import mixing

    if topology.n != n:
        raise ValueError(f"node stack has {n} nodes, topology "
                         f"{topology.name!r} has {topology.n}")
    size = mesh.shape[axis]
    if n % size != 0:
        raise ValueError(f"node count ({n}) not divisible by the mesh "
                         f"{axis!r} axis ({size})")
    ring = mixing._is_ring(topology)
    full = mixing._is_full(topology)
    if not (ring or full):
        raise ValueError(
            f"sharded label exchange supports ring/complete graphs; "
            f"topology {topology.name!r} must use the node-stacked "
            "labeling.label_round (backend='sparse')")
    return size, ring, full


def _merge_payloads(parts_v, parts_i, parts_m):
    """Mean over contributors distributes over the scatter: concat
    contributor payloads along k with m_j/cnt weights (DESIGN.md §2)."""
    cnt = sum(parts_m)                                      # (L, P)
    share = [m / jnp.maximum(cnt, 1.0) for m in parts_m]
    extra = parts_v[0].ndim - cnt.ndim                      # e.g. the S axis
    vals = jnp.concatenate(
        [v * s.reshape(s.shape + (1,) * extra)
         for v, s in zip(parts_v, share)], axis=-1)
    idx = jnp.concatenate(parts_i, axis=-1)
    return (vals.astype(jnp.float32), idx.astype(jnp.int32),
            (cnt > 0).astype(jnp.float32))


def _shard_exchange(sp: distill.SparseLabels, m, *, axis: str, size: int,
                    n: int, ring: bool, full: bool):
    """The label exchange across the mesh node axis (inside shard_map):
    only the top-k payload (values, indices, D_ID mask) moves — ring
    neighbours swap boundary rows via ``lax.ppermute``
    (``mixing.block_ring_shift``), complete graphs ``all_gather``."""
    from repro.core import mixing

    if full and not (ring and n <= 3):
        vals_all = jax.lax.all_gather(sp.values, axis, axis=0,
                                      tiled=True)           # (n, P[, S], k)
        idx_all = jax.lax.all_gather(sp.indices, axis, axis=0, tiled=True)
        m_all = jax.lax.all_gather(m, axis, axis=0, tiled=True)
        # contributor axis consumed by _merge_payloads → (P[, S], n·k);
        # on the complete graph every node merges the same contributor
        # set, so the result broadcasts over local nodes
        vals, idx, w = _merge_payloads(list(vals_all), list(idx_all),
                                       list(m_all))
        L = m.shape[0]
        vals = jnp.broadcast_to(vals[None], (L,) + vals.shape)
        idx = jnp.broadcast_to(idx[None], (L,) + idx.shape)
        w = jnp.broadcast_to(w[None], (L,) + w.shape)
        return vals, idx, w
    if n == 1:
        return _merge_payloads([sp.values], [sp.indices], [m])

    def shifted(t, s):
        return mixing.block_ring_shift(t, axis, size, s)
    parts_v = [sp.values, shifted(sp.values, 1)]
    parts_i = [sp.indices, shifted(sp.indices, 1)]
    parts_m = [m, shifted(m, 1)]
    if n > 2:
        parts_v.append(shifted(sp.values, -1))
        parts_i.append(shifted(sp.indices, -1))
        parts_m.append(shifted(m, -1))
    return _merge_payloads(parts_v, parts_i, parts_m)


def shard_label_round(public_logits, val_logits, topology: Topology,
                      cfg: IDKDConfig, *, mesh, axis: str = "node",
                      filter_ood: bool = True) -> SparseHomogenizedSet:
    """One IDKD homogenization round under ``shard_map`` over the mesh
    node axis — the ``driver_mode="shard"`` twin of :func:`label_round`
    (DESIGN.md §7).

    Score, calibrate, and select run *shard-local*: each device computes
    detector confidences, ROC thresholds, D_ID masks, and the top-k
    sparse payload for its own block of nodes with zero communication.
    Only the label exchange crosses the node axis, and it moves nothing
    but top-k payloads: ring neighbours swap ``(values, indices, mask)``
    via boundary-row ``lax.ppermute`` (complete graphs ``all_gather``
    them), never the ``(P, C)`` dense labels. The merged payload equals
    the node-stacked sparse backend's up to a permutation along the k
    axis (contributor order is self/prev/next instead of
    self/sorted-neighbours) — every consumer accumulates duplicate
    indices, so the trained trajectories agree to float tolerance and
    the per-node payload bytes match exactly (``tests/test_shard.py``).

    Always produces sparse top-k labels (the dense backend has no
    sharded path — its wire format is the thing shard mode exists to
    avoid); churn masks are unsupported, like the rest of shard mode.
    Topologies other than rings / complete graphs raise eagerly — run
    those rounds through the node-stacked :func:`label_round`.
    """
    from jax.sharding import PartitionSpec as P

    n = public_logits.shape[0]
    size, ring, full = _shard_layout(topology, n, mesh, axis)
    k = min(cfg.label_topk or DEFAULT_TOPK, public_logits.shape[-1])
    spec = P(axis)

    def body(pub, val):
        # ---- score / calibrate / select: shard-local, zero comm
        conf_pub = detector_scores(pub, cfg.detector)
        if filter_ood:
            thresholds = calibrate(detector_scores(val, cfg.detector),
                                   conf_pub)
            id_mask = conf_pub > thresholds[:, None]
        else:
            thresholds = jnp.zeros((pub.shape[0],), jnp.float32)
            id_mask = jnp.ones(conf_pub.shape, bool)
        sp = distill.sparsify_labels(
            distill.soft_labels(pub, cfg.temperature), k)
        m = id_mask.astype(jnp.float32)
        # ---- exchange: only the top-k payload crosses the node axis
        vals, idx, w = _shard_exchange(sp, m, axis=axis, size=size, n=n,
                                       ring=ring, full=full)
        return vals, idx, w, id_mask, thresholds

    vals, idx, w, id_mask, thresholds = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, spec, spec, spec, spec), check_vma=False)(
            public_logits, val_logits)
    return SparseHomogenizedSet(distill.SparseLabels(vals, idx), w,
                                id_mask, thresholds)


def shard_streaming_label_round(model, params, public_x, val_x,
                                topology: Topology, cfg: IDKDConfig, *,
                                mesh, axis: str = "node",
                                filter_ood: bool = True
                                ) -> SparseHomogenizedSet:
    """:func:`streaming_label_round` under ``shard_map`` over the mesh
    node axis — the streaming twin of :func:`shard_label_round`.

    The public-set scan lives *inside* the shard_map body: each device
    streams the (replicated) public microbatches through its own block
    of nodes' models — forward to pre-head activations, fused
    head-select per chunk — and calibrates thresholds shard-local, so
    score/select cost zero communication and no device ever holds more
    than O(local_nodes · microbatch · C) of logits. Exactly as in
    :func:`shard_label_round`, only the top-k payload crosses the node
    axis (boundary-row ppermutes on rings, all_gather on complete
    graphs); churn masks remain unsupported in shard mode.

    On a 2-D ``("node", "model")`` federation mesh (``launch.mesh.
    make_federation_mesh``) the params arrive model-sharded
    (``launch.sharding.federation_specs``): the body all-gathers the
    weight leaves over the model axis for ``forward_features`` and runs
    the **vocab-sharded** head pass (:func:`_vocab_sharded_head_pass`) —
    each model shard scores only its own vocab slice and the stats merge
    across the model axis with the kernel's streaming math. The label
    exchange still moves top-k payloads over the node axis only, so
    label wire bytes are unchanged by model parallelism (DESIGN.md §10).
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import federation_specs, gather_model_tree

    n = jax.tree.leaves(params)[0].shape[0]
    size, ring, full = _shard_layout(topology, n, mesh, axis)
    model_axis = "model"
    model_size = dict(mesh.shape).get(model_axis, 1)
    C = _head_width(model, params)
    k = min(cfg.label_topk or DEFAULT_TOPK, C)
    chunks, P_pub, _ = _chunk_public(public_x, cfg.stream_microbatch)
    val_x = jnp.asarray(val_x)
    spec = P(axis)
    p_specs = federation_specs(params, n, mesh, axis)
    if model_size > 1:
        def head_pass(model, params, x, cfg, k):
            return jax.vmap(lambda p, x: _vocab_sharded_head_pass(
                model, p, x, cfg, k, model_axis, model_size))(params, x)
    else:
        head_pass = _head_pass

    def body(p_local, chunks_rep, val_local):
        if model_size > 1:
            p_local = gather_model_tree(p_local, p_specs, model_axis)
        # ---- stream / score / calibrate / select: shard-local
        # (the forward's counters are not among the sharded round's
        # outputs)
        conf_pub, sp, _ = _stream_public(model, p_local, chunks_rep, P_pub,
                                         cfg, k, head_pass)
        if filter_ood:
            thresholds = calibrate(
                _stream_val_conf(model, p_local, val_local, cfg,
                                 head_pass)[0],
                conf_pub)
            id_mask = conf_pub > thresholds[:, None]
        else:
            thresholds = jnp.zeros((conf_pub.shape[0],), jnp.float32)
            id_mask = jnp.ones(conf_pub.shape, bool)
        m = id_mask.astype(jnp.float32)
        # ---- exchange: only the top-k payload crosses the node axis
        vals, idx, w = _shard_exchange(sp, m, axis=axis, size=size, n=n,
                                       ring=ring, full=full)
        return vals, idx, w, id_mask, thresholds

    vals, idx, w, id_mask, thresholds = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, P(), spec),
        out_specs=(spec, spec, spec, spec, spec), check_vma=False)(
            params, chunks, val_x)
    return SparseHomogenizedSet(distill.SparseLabels(vals, idx), w,
                                id_mask, thresholds)


def neighbor_topk_overlap(indices, topology: Topology):
    """Telemetry diagnostic: how much of each node's top-k label index
    set its graph neighbours share.

    ``indices`` is the sparse payload's index tensor, shape
    (n, P[, S], k) — each node's selected class/token ids per public
    sample. For every undirected edge (i, j) the overlap is the
    fraction of node i's entries that also appear in node j's set for
    the same sample, averaged over samples (symmetric because both
    sets have the same width k). Returns ``(mean, per_edge)`` where
    ``per_edge`` maps ``"i-j"`` -> overlap fraction; mean is 0.0 on an
    edgeless graph. Host-side numpy — runs once per homogenization
    round, never inside jit.
    """
    import numpy as np

    idx = np.asarray(indices)
    n = idx.shape[0]
    flat = idx.reshape(n, -1, idx.shape[-1])            # (n, M, k)
    per_edge = {}
    for i in range(n):
        for j in topology.neighbors(i):
            if j <= i:
                continue
            a, b = flat[i], flat[j]                      # (M, k) each
            hit = (a[:, :, None] == b[:, None, :]).any(-1)
            per_edge[f"{i}-{j}"] = float(hit.mean())
    mean = float(np.mean(list(per_edge.values()))) if per_edge else 0.0
    return mean, per_edge
