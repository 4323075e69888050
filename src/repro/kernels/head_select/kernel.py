"""Pallas TPU kernel: fused, vocab-tiled head-select (streaming labeling).

The logit-free generalization of ``msp_select``: instead of reading a
precomputed ``(rows, C)`` logit tensor from HBM, it takes the final
hidden states ``(rows, D)`` and the classifier / unembedding matrix
``(D, C)`` and computes the IDKD labeling quantities — detector
confidence and the renormalized top-k sparse soft label — with the
**vocab axis tiled**: the full ``(rows, C)`` logit tensor never exists
in any memory.

Per ``(row_tile, vocab_block)`` grid cell the kernel does one MXU
matmul ``hidden[r0:r1] @ W[:, c0:c1]`` in VMEM and folds the block into
running per-row state (the same scratch-accumulator pattern as the
in-repo flash_attention kernel, whose online-softmax (m, l) carry this
reuses):

* ``m, z``   — online-softmax running max / normalizer at T=1, from
  which both detectors fall out at the final block (MSP ``1/z``,
  energy ``m + log z``);
* ``tv, ti`` — running top-k *logits* + global vocab indices, merged
  with the block by k rounds of "take the larger of the carry's and
  the block's best" (ties go to the carry, then to the lowest column —
  ``argmax`` order). Top-k of the temperature softmax equals top-k of
  the logits (softmax is monotonic), and the *renormalized* top-k
  payload depends only on the top-k logits themselves —
  ``v_j = exp(l_j/T) / Σ_{j'∈topk} exp(l_j'/T)`` — so the temperature
  enters only in the finalizer and no softmax over C is ever formed.

Mosaic layout: every per-row quantity is a ``(rows, 1)`` column and
every reduction keeps its axis, so no rank-1 block, gather, stack or
concatenate reaches the TPU compiler; argmax is a masked min over the
column iota, and reading the carry's index at a slot is a masked max.

Row tile. HBM traffic is one read of W per row tile and one read of the
hidden states, with O(rows · k) outputs instead of O(rows · C). The
rows per head read are therefore sized from the shapes, not set by the
caller: :func:`head_row_tile` takes the largest tile whose VMEM
footprint (:func:`head_vmem_bytes`) fits :data:`VMEM_BUDGET` — 256
rows for qwen3-1.7b's (2048, 151,936) bf16 head, one read of W per 256
rows (the caller's ``block_rows`` is the granule the tile is a
multiple of). Every row's arithmetic is the same whatever the tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_C = 512            # vocab columns per grid cell
# scoped VMEM a head_select grid cell is sized against: three quarters
# of the 16 MiB a v5e kernel gets by default, the rest left to Mosaic's
# own temporaries
VMEM_BUDGET = 12 * 2**20
_LANES = 128             # a (rows, 1) or (rows, k) column pads to this


def head_vmem_bytes(rows: int, dim: int, block_c: int, hidden_dtype,
                    head_dtype) -> int:
    """Scoped VMEM of one ``head_select`` grid cell at a row tile of
    ``rows``: the double-buffered hidden tile, head tile and bias row,
    the f32 score tile and the fold's working copy of it, the
    ``(m, z, tv, ti)`` carry and up to four double-buffered per-row
    outputs, each lane-padded."""
    h = jnp.dtype(hidden_dtype).itemsize
    w = jnp.dtype(head_dtype).itemsize
    return (2 * rows * dim * h                 # hidden tile
            + 2 * dim * block_c * w            # head tile
            + 2 * 8 * block_c * 4              # bias row, one f32 tile
            + 2 * rows * block_c * 4           # scores + working copy
            + 4 * rows * _LANES * 4            # carry
            + 2 * 4 * rows * _LANES * 4)       # outputs


def head_row_tile(rows: int, dim: int, block_c: int, granule: int,
                  hidden_dtype, head_dtype) -> int:
    """Rows per head read for ``rows`` hidden states against a ``(dim,
    C)`` head in ``block_c``-column blocks: the largest ``granule · 2^j``
    whose :func:`head_vmem_bytes` fits :data:`VMEM_BUDGET` (the granule
    itself where none does), no larger than the rows need, then evened
    out over the tiles it implies so padding stays under one granule a
    tile. Always a multiple of ``granule``."""
    tile = granule
    while tile < rows and head_vmem_bytes(
            2 * tile, dim, block_c, hidden_dtype, head_dtype) <= VMEM_BUDGET:
        tile *= 2
    tiles = -(-rows // tile)
    return -(-rows // (tiles * granule)) * granule


def _init_carry(m_scr, z_scr, tv_scr, ti_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    z_scr[...] = jnp.zeros_like(z_scr)
    tv_scr[...] = jnp.full_like(tv_scr, NEG_INF)
    ti_scr[...] = jnp.zeros_like(ti_scr)


def _fold_block(s, col0, num_classes: int, k: int,
                m_scr, z_scr, tv_scr, ti_scr):
    """Fold one ``(bn, bc)`` f32 block of logits whose first column is
    global vocab index ``col0`` into the running ``(m, z, tv, ti)``
    carry. Columns at or past ``num_classes`` are padding."""
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col0 + col < num_classes, s, NEG_INF)

    # ---- online-softmax detector stats at T=1 (flash-attention carry)
    m_prev = m_scr[...]                                    # (bn, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    z_scr[...] = (z_scr[...] * jnp.exp(m_prev - m_new)
                  + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_scr[...] = m_new

    # ---- streaming top-k merge of the carry with the block's logits
    tv, ti = tv_scr[...], ti_scr[...]                      # (bn, k)
    slot = jax.lax.broadcasted_iota(jnp.int32, tv.shape, 1)
    width = s.shape[1]
    out_v = jnp.full(tv.shape, NEG_INF, jnp.float32)
    out_i = jnp.zeros(ti.shape, jnp.int32)
    for j in range(k):
        cv = jnp.max(tv, axis=1, keepdims=True)
        bv = jnp.max(s, axis=1, keepdims=True)
        cp = jnp.min(jnp.where(tv == cv, slot, k), axis=1, keepdims=True)
        bp = jnp.min(jnp.where(s == bv, col, width), axis=1, keepdims=True)
        ci = jnp.max(jnp.where(slot == cp, ti, -1), axis=1, keepdims=True)
        take_c = cv >= bv                                  # carry first
        out_v = jnp.where(slot == j, jnp.where(take_c, cv, bv), out_v)
        out_i = jnp.where(slot == j, jnp.where(take_c, ci, col0 + bp), out_i)
        tv = jnp.where(take_c & (slot == cp), NEG_INF, tv)
        s = jnp.where(jnp.logical_not(take_c) & (col == bp), NEG_INF, s)
    tv_scr[...] = out_v
    ti_scr[...] = out_i


def _finalize(out_refs, m_scr, z_scr, tv_scr, ti_scr, *, temperature: float,
              detector: str, raw_stats: bool):
    """Write the outputs from the carry: the raw carry itself
    (``raw_stats``) or ``(conf, vals, idx)``."""
    if raw_stats:
        # vocab-sharded path: ship the raw carry; the caller merges
        # (m, z) and the top-k logits across model-axis shards with the
        # same streaming math (ref.merge_head_stats) and only then
        # applies the detector / temperature finalizer.
        for ref, scr in zip(out_refs, (m_scr, z_scr, tv_scr, ti_scr)):
            ref[...] = scr[...]
        return
    conf_ref, vals_ref, idx_ref = out_refs
    z = jnp.maximum(z_scr[...], 1e-30)
    if detector == "energy":
        conf_ref[...] = m_scr[...] + jnp.log(z)
    else:
        conf_ref[...] = 1.0 / z
    tv = tv_scr[...]                                       # sorted desc
    e = jnp.exp((tv - jnp.max(tv, axis=1, keepdims=True)) / temperature)
    vals_ref[...] = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
    idx_ref[...] = ti_scr[...]


def _select_kernel(*refs, scores, num_inputs: int, temperature: float,
                   k: int, detector: str, block_c: int, num_c_blocks: int,
                   num_classes: int, raw_stats: bool):
    # refs: the inputs, then the outputs — (conf, vals, idx) or, with
    # raw_stats, (m, z, tv, ti) — then the (m, z, tv, ti) VMEM carry
    in_refs, out_refs = refs[:num_inputs], refs[num_inputs:-4]
    carry = refs[-4:]
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        _init_carry(*carry)

    _fold_block(scores(*in_refs), ci * block_c, num_classes, k, *carry)

    @pl.when(ci == num_c_blocks - 1)
    def _write():
        _finalize(out_refs, *carry, temperature=temperature,
                  detector=detector, raw_stats=raw_stats)


def select_call(scores, inputs, in_specs, *, rows: int, num_classes: int,
                row_tile: int, block_c: int, k: int, temperature: float,
                detector: str, raw_stats: bool, interpret: bool):
    """The ``(row, vocab)``-grid pallas_call shared by ``head_select``
    and ``msp_select``: ``scores(*in_refs)`` yields each cell's
    ``(row_tile, block_c)`` f32 logits, folded into the carry.

    ``rows`` is a multiple of ``row_tile``. A ragged last vocab block
    reads past ``num_classes``; the fold masks those columns, so no
    input is padded along the vocab. Per-row outputs are
    ``(rows, 1)`` columns in the kernel (Mosaic takes no rank-1 block
    smaller than 128) and come back as ``(rows,)``."""
    num_c_blocks = pl.cdiv(num_classes, block_c)
    kernel = functools.partial(
        _select_kernel, scores=scores, num_inputs=len(inputs),
        temperature=temperature, k=k, detector=detector, block_c=block_c,
        num_c_blocks=num_c_blocks, num_classes=num_classes,
        raw_stats=raw_stats)
    col_spec = pl.BlockSpec((row_tile, 1), lambda i, c: (i, 0))
    topk_spec = pl.BlockSpec((row_tile, k), lambda i, c: (i, 0))
    col = jax.ShapeDtypeStruct((rows, 1), jnp.float32)
    tv = jax.ShapeDtypeStruct((rows, k), jnp.float32)
    ti = jax.ShapeDtypeStruct((rows, k), jnp.int32)
    n_cols = 2 if raw_stats else 1
    outs = pl.pallas_call(
        kernel,
        grid=(rows // row_tile, num_c_blocks),
        in_specs=in_specs,
        out_specs=(col_spec,) * n_cols + (topk_spec, topk_spec),
        out_shape=(col,) * n_cols + (tv, ti),
        scratch_shapes=[pltpu.VMEM((row_tile, 1), jnp.float32),
                        pltpu.VMEM((row_tile, 1), jnp.float32),
                        pltpu.VMEM((row_tile, k), jnp.float32),
                        pltpu.VMEM((row_tile, k), jnp.int32)],
        interpret=interpret,
    )(*inputs)
    return tuple(o[:, 0] for o in outs[:n_cols]) + tuple(outs[n_cols:])


def _head_scores(h_ref, w_ref, b_ref):
    # bf16 × bf16 products are exact in f32, so a same-dtype matmul with
    # f32 accumulation equals the f32 matmul of the upcast operands
    dt = jnp.promote_types(h_ref.dtype, w_ref.dtype)
    s = jax.lax.dot_general(h_ref[...].astype(dt), w_ref[...].astype(dt),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s + b_ref[...].astype(jnp.float32)              # (1, bc) bias


def head_select_pallas(hidden, w, bias, *, temperature: float, k: int = 8,
                       block_rows: int = 8, block_c: int = BLOCK_C,
                       interpret: bool = True, detector: str = "msp",
                       raw_stats: bool = False):
    """hidden (N, D) + head (D, C) [+ bias (C,)] ->
    (conf (N,), vals (N, k), idx (N, k)) with the vocab axis tiled.

    The rows go through in tiles of :func:`head_row_tile` rows, a
    multiple of ``block_rows``, each tile reading the head once; ``N``
    is padded here to a whole number of tiles and the outputs sliced
    back.

    ``raw_stats=True`` returns the pre-finalizer carry
    ``(m (N,), z (N,), tv (N, k), ti (N, k))`` instead — the per-shard
    half of the vocab-sharded 2-D label round, merged across the model
    axis by ``ref.merge_head_stats``."""
    N, D = hidden.shape
    C = w.shape[1]
    assert w.shape[0] == D, (w.shape, hidden.shape)
    assert k <= C, "clamp k to the class count before calling"
    assert detector in ("msp", "energy"), detector
    block_c = min(block_c, C)
    row_tile = head_row_tile(N, D, block_c, block_rows, hidden.dtype,
                             w.dtype)
    rows = -(-N // row_tile) * row_tile
    if rows != N:
        hidden = jnp.pad(hidden, ((0, rows - N), (0, 0)))
    if bias is None:
        bias = jnp.zeros((C,), jnp.float32)
    in_specs = [pl.BlockSpec((row_tile, D), lambda i, c: (i, 0)),
                pl.BlockSpec((D, block_c), lambda i, c: (0, c)),
                pl.BlockSpec((1, block_c), lambda i, c: (0, c))]
    outs = select_call(_head_scores, (hidden, w, bias.reshape(1, -1)),
                       in_specs, rows=rows, num_classes=C,
                       row_tile=row_tile, block_c=block_c, k=k,
                       temperature=temperature, detector=detector,
                       raw_stats=raw_stats, interpret=interpret)
    return tuple(o[:N] for o in outs) if rows != N else outs
