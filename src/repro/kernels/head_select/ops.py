"""Jit'd public wrapper for the head_select kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.head_select.kernel import BLOCK_C, head_select_pallas
from repro.kernels.head_select.ref import (head_select_ref,
                                           head_select_stats_ref,
                                           merge_head_stats)


@functools.partial(jax.jit, static_argnames=("temperature", "k",
                                             "block_rows", "block_c",
                                             "interpret", "detector",
                                             "raw_stats"))
def head_select(hidden, w, bias=None, *, temperature: float = 10.0,
                k: int = 8, block_rows: int = 8, block_c: int = BLOCK_C,
                interpret: bool | None = None, detector: str = "msp",
                raw_stats: bool = False):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return head_select_pallas(hidden, w, bias, temperature=temperature,
                              k=k, block_rows=block_rows, block_c=block_c,
                              interpret=interpret, detector=detector,
                              raw_stats=raw_stats)


__all__ = ["head_select", "head_select_ref", "head_select_stats_ref",
           "merge_head_stats"]
