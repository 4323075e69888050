"""Pallas TPU kernel: fused IDKD public-set labeling (msp_select).

IDKD's hot loop reads every public-set logit row once and produces
(i) detector confidence and (ii) the top-k sparse soft label. Unfused,
XLA performs 2 HBM passes over the (N × vocab) logits (softmax@T=1 →
max; softmax@T → top_k); this kernel does one pass with everything
fused in VMEM. The D_ID membership bit is *not* computed here: the
threshold is ROC-calibrated from the confidences downstream, so the
mask is one compare the caller owns (``conf > t_opt``) — see
``kernels/head_select`` for the generalization that starts from hidden
states instead of logits.

Tiling: ``(block_n, block_c)`` tiles over a ``(row, vocab)`` grid. A
whole row of a 152k vocabulary is ≈ 0.6 MB in f32, so resident
``(8, C)`` rows and their temporaries overflow the TPU's scoped VMEM;
the vocab axis is therefore folded block by block into the same
online-softmax + streaming top-k carry as ``head_select`` (which this
kernel shares). Top-k of the temperature softmax equals top-k of the
logits, and the renormalized payload depends only on those k logits,
so the temperature enters only in the finalizer.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.head_select.kernel import select_call


# vocab columns per grid cell: an (8, 2048) f32 tile is 64 KB of VMEM
BLOCK_C = 2048


def _logit_scores(logits_ref):
    return logits_ref[...].astype(jnp.float32)


def msp_select_pallas(logits, *, temperature: float, k: int = 8,
                      block_n: int = 8, interpret: bool = True,
                      detector: str = "msp"):
    """logits: (N, C) -> (conf (N,), vals (N, k), idx (N, k))."""
    N, C = logits.shape
    assert k <= C, "clamp k to the class count before calling"
    block_n = min(block_n, N)
    assert N % block_n == 0, "pad rows to a block multiple"
    assert detector in ("msp", "energy"), detector
    block_c = min(BLOCK_C, C)
    in_specs = [pl.BlockSpec((block_n, block_c), lambda i, c: (i, c))]
    return select_call(_logit_scores, (logits,), in_specs, rows=N,
                       num_classes=C, row_tile=block_n, block_c=block_c,
                       k=k, temperature=temperature, detector=detector,
                       raw_stats=False, interpret=interpret)
