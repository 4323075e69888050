"""Pallas TPU kernel: grouped SwiGLU over the held experts' sorted rows.

The expert layer of a chip that holds ``H`` of a model's routed experts
(``models/moe.py``, held-expert path) hands this kernel the tokens'
hidden states, the held experts' weights and a dispatch plan
(:func:`repro.kernels.expert_ffn.ops.dispatch_plan`): the assignments to
held experts sorted by expert, each expert's run padded to whole row
tiles of ``tm`` rows, every row naming its token and combine weight.

Per grid step ``(node, tile)`` the kernel

* gathers the tile's ``tm`` token rows straight from HBM by one DMA per
  row (the row ids come in by blocks of :data:`ID_BLOCK` in SMEM), so
  only the held assignments' rows are ever moved;
* computes ``silu(x Wg) * (x Wi)`` and ``· Wo`` for the tile's expert,
  operands bf16 (the weights' dtype), accumulation float32, and scales
  each row by its combine weight;
* adds the rows into the output by read-modify-write DMAs: the output
  is the ``base`` input (the shared experts' part) aliased in place, and
  a token's rows of different experts land in different tiles, one
  after the other (the grid runs in order and each tile waits for its
  writes).

The grid has the static worst case of tiles. Tiles past the plan's live
count do no work, and their index maps repeat the last live block, so
no new HBM read is issued for them. A padding row names the trash row
``T`` (one row past the tokens) with weight 0, so every tile moves
exactly ``tm`` rows and no padding row touches a token.

Mosaic layout. A DMA moves whole (8, 128) tiles, so a token row of
``d`` float32 values is stored as ``(d/128, 128)``: one token is
``d/1024`` whole tiles in HBM and in VMEM, and a tile's rows are read
back as ``(tm, 128)`` column groups by strided loads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ID_BLOCK = 1024          # row ids per SMEM block (XLA's s32[] tiling)
MAX_TILE = 256           # rows per tile at most


def row_tile(rows_per_expert: int) -> int:
    """Rows per tile: the even share of one expert rounded up to a power
    of two, between 8 and :data:`MAX_TILE`."""
    tm = 8
    while tm < min(rows_per_expert, MAX_TILE):
        tm *= 2
    return tm


def vmem_bytes(tm: int, d: int, f: int, weight_dtype) -> int:
    """Scoped VMEM of one grid step: the double-buffered expert weights,
    the gathered and accumulated f32 rows, the weight column and the
    f32 temporaries of the SwiGLU."""
    w = jnp.dtype(weight_dtype).itemsize
    return (2 * 3 * d * f * w               # Wg, Wi, Wo, double-buffered
            + 2 * tm * d * 4                # gathered rows, output rows
            + 2 * tm * LANES * 4            # combine weights
            + 3 * tm * f * 4 + 2 * tm * d * 4)   # g, u, h; x, y


def _kernel(te_ref, live_ref, rows_ref, w_ref, x_hbm, wg_ref, wi_ref, wo_ref,
            base_hbm, out_hbm, xbuf, obuf, sems):
    del base_hbm                     # aliased to out_hbm
    n, i = pl.program_id(0), pl.program_id(1)
    sub = x_hbm.shape[2]
    tm = obuf.shape[0] // sub
    off = (i * tm) % ID_BLOCK        # the tile's first id in its block

    def row_copy(r, src, dst, sem):
        """One token row between HBM (``src``/``dst`` None there) and
        the tile's VMEM rows."""
        t = rows_ref[off + r]
        rows = pl.ds(r * sub, sub)
        if src is None:
            return pltpu.make_async_copy(dst.at[rows], out_hbm.at[n, t],
                                         sems.at[sem])
        return pltpu.make_async_copy(src.at[n, t], dst.at[rows],
                                     sems.at[sem])

    def each_row(fn):
        def body(j, carry):            # eight rows a step, unrolled
            for r in range(8):
                fn(j * 8 + r)
            return carry
        jax.lax.fori_loop(0, tm // 8, body, 0)

    @pl.when(i < live_ref[n])
    def _():
        each_row(lambda r: row_copy(r, x_hbm, xbuf, 0).start())
        each_row(lambda r: row_copy(r, out_hbm, obuf, 1).start())
        each_row(lambda r: row_copy(r, x_hbm, xbuf, 0).wait())
        each_row(lambda r: row_copy(r, out_hbm, obuf, 1).wait())
        x = jnp.concatenate(
            [xbuf[pl.ds(s, tm, stride=sub), :] for s in range(sub)],
            axis=-1).astype(wg_ref.dtype)
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(wo_ref.dtype)
        y = jnp.dot(h, wo_ref[...], preferred_element_type=jnp.float32)
        y = y * w_ref[...]
        for s in range(sub):
            rows = pl.ds(s, tm, stride=sub)
            obuf[rows, :] = obuf[rows, :] + y[:, s * LANES:(s + 1) * LANES]
        each_row(lambda r: row_copy(r, None, obuf, 2).start())
        each_row(lambda r: row_copy(r, None, obuf, 2).wait())


def expert_ffn_pallas(x3, wg, wi, wo, rows, row_w, tile_expert, live, base3,
                      *, tm: int, interpret: bool = False):
    """The kernel over ``N`` nodes at once (grid ``(N, tiles)``).

    ``x3``, ``base3``: ``(N, T + 1, d/128, 128)`` float32 token rows and
    the output's starting value (row ``T`` is the trash row);
    ``wg``, ``wi``: ``(N, H, d, f)``; ``wo``: ``(N, H, f, d)``;
    ``rows``: ``(N, R)`` int32 token of each sorted row; ``row_w``:
    ``(N, R, 1)`` float32 combine weight; ``tile_expert``: ``(N, G)``
    int32 held expert of each tile; ``live``: ``(N,)`` int32 live tiles.
    ``R = G · tm`` is a multiple of :data:`ID_BLOCK`. Returns ``base3``
    with every live row's weighted expert output added to its token.
    """
    N, _, sub, _ = x3.shape
    d, f = wg.shape[2], wg.shape[3]
    G = tile_expert.shape[1]
    R = rows.shape[1]
    assert R == G * tm and R % ID_BLOCK == 0 and ID_BLOCK % tm == 0
    assert sub * LANES == d

    def last(n, i, live):
        return jnp.minimum(i, jnp.maximum(live[n] - 1, 0))

    def expert_block(n, i, te, live):
        return (n, te[n * G + last(n, i, live)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(N, G),
        in_specs=[
            pl.BlockSpec((ID_BLOCK,),
                         lambda n, i, te, live:
                         ((n * R + last(n, i, live) * tm) // ID_BLOCK,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, tm, 1),
                         lambda n, i, te, live: (n, last(n, i, live), 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((None, None, d, f), expert_block),
            pl.BlockSpec((None, None, d, f), expert_block),
            pl.BlockSpec((None, None, f, d), expert_block),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((tm * sub, LANES), jnp.float32),
                        pltpu.VMEM((tm * sub, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((3,))])
    limit = max(32 * 2**20, vmem_bytes(tm, d, f, wg.dtype) + 8 * 2**20)
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(base3.shape, base3.dtype),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret, name="expert_ffn",
    )(tile_expert.reshape(N * G), live, rows.reshape(N * R), row_w, x3, wg,
      wi, wo, base3)
