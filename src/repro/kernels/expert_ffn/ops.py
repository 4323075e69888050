"""The held-expert part of an expert layer through the ``expert_ffn``
kernel: the dispatch plan (XLA), the kernel, the combine.

    out, counts = expert_ffn(x, wg, wi, wo, local, weight, base)

``local (T, k)`` names each assignment's held expert (its index in the
held bank), any value outside ``[0, H)`` an expert this chip does not
hold; ``weight (T, k)`` is its combine weight. ``out`` is ``base`` plus,
for every token, the weighted SwiGLU outputs of its held experts;
``counts`` holds ``held_assignments`` (assignments computed) and
``expert_tiles`` (live kernel tiles), both int32 on the device.

Dropless: the plan's buffers have the static worst case, ``T · min(k,
H)`` rows plus one partial tile per expert, and the kernel's grid covers
it; only the live tiles move rows. Off TPU the kernel runs in Pallas
interpret mode. Under ``vmap`` (the label round's node axis) the batch
becomes the kernel's leading grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.expert_ffn.kernel import (ID_BLOCK, LANES,
                                             expert_ffn_pallas, row_tile)


def dispatch_plan(local, weight, num_held: int, tm: int):
    """Sorted rows of the held assignments, each expert's run padded to
    whole tiles of ``tm``.

    Returns ``rows (R,)`` (token of each row; ``T``, the trash row, for
    padding), ``row_w (R, 1)`` (combine weight; 0 for padding),
    ``tile_expert (G,)``, ``live ()`` (live tiles) and ``held ()`` (held
    assignments). A row's place is its expert's start plus its rank
    among that expert's assignments in token order, so no sort runs.
    """
    T, k = local.shape
    A = T * k
    R = -(-(T * min(k, num_held) + num_held * (tm - 1)) // ID_BLOCK) \
        * ID_BLOCK
    flat = local.reshape(A)
    held = (flat >= 0) & (flat < num_held)
    e = jnp.where(held, flat, 0)
    onehot = (held[:, None] & (e[:, None] == jnp.arange(num_held)[None])
              ).astype(jnp.int32)                          # (A, H)
    count = onehot.sum(0)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), e[:, None], 1)[:, 0]
    padded = -(-count // tm) * tm
    ends = jnp.cumsum(padded)
    dest = jnp.where(held, ends[e] - padded[e] + rank - 1,
                     R + jnp.arange(A))                    # unique, OOB
    rows = jnp.full((R,), T, jnp.int32).at[dest].set(
        jnp.arange(A, dtype=jnp.int32) // k, mode="drop",
        unique_indices=True)
    row_w = jnp.zeros((R,), jnp.float32).at[dest].set(
        weight.reshape(A).astype(jnp.float32), mode="drop",
        unique_indices=True)
    starts = jnp.arange(R // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, starts, side="right"), num_held - 1
    ).astype(jnp.int32)
    live = (ends[-1] // tm).astype(jnp.int32)
    return rows, row_w[:, None], tile_expert, live, count.sum()


def _grouped(tm: int, interpret: bool):
    """The kernel over a leading node axis; ``vmap`` folds its batch
    into that axis."""

    @jax.custom_batching.custom_vmap
    def grouped(x3, wg, wi, wo, rows, row_w, tile_expert, live, base3):
        return expert_ffn_pallas(x3, wg, wi, wo, rows, row_w, tile_expert,
                                 live, base3, tm=tm, interpret=interpret)

    @grouped.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        N = args[0].shape[1]
        out = grouped(*[a.reshape((axis_size * N,) + a.shape[2:])
                        for a in args])
        return out.reshape((axis_size, N) + out.shape[1:]), True

    return grouped


def _rows3(x, T: int):
    """(T, d) -> (1, T + 1, d/128, 128) float32, the trash row zero."""
    d = x.shape[-1]
    x = jnp.pad(x.astype(jnp.float32), ((0, 1), (0, 0)))
    return x.reshape(1, T + 1, d // LANES, LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_ffn(x, wg, wi, wo, local, weight, base, *,
               interpret: bool | None = None):
    """``base + Σ_j weight[t, j] · SwiGLU_{local[t, j]}(x[t])`` over the
    held assignments, and the counts; see the module docstring."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, d = x.shape
    H = wg.shape[0]
    k = local.shape[-1]
    tm = row_tile(-(-T * min(k, H) // H))
    with jax.named_scope("moe_dispatch"):
        rows, row_w, tile_expert, live, held = dispatch_plan(
            local, weight, H, tm)
        x3, base3 = _rows3(x, T), _rows3(base, T)
    with jax.named_scope("expert_ffn"):
        out3 = _grouped(tm, interpret)(
            x3, wg[None], wi[None], wo[None], rows[None], row_w[None],
            tile_expert[None], live[None], base3)
    with jax.named_scope("moe_combine"):
        out = out3[0, :T].reshape(T, d).astype(base.dtype)
    return out, {"held_assignments": held, "expert_tiles": live}
