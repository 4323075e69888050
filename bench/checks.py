"""The comparisons that decide ``correct``, and the pieces of arithmetic
the plain references share. Nothing here imports the program under test.

Label rounds (``round_numbers``): the program's top-k picks, their
soft-label values, its in-distribution masks and the exchanged payload
are judged against the float32 reference's logits:

* ``topk_gap``: the widest gap by which a picked token's reference logit
  lies below the reference's k-th best logit of that position (0 when
  the picks are the reference's top k in any order);
* ``value_gap``: the widest gap between a soft-label value the program
  sent and the temperature softmax of the reference's logits over the
  same picks;
* ``selection_gap``: the least shift of the reference's confidences, as
  a share of the node's confidence range, that explains the program's
  selection: its in-distribution bits as "confidence above its
  threshold", and its threshold as an optimum of Youden's J over the
  calibration (in-distribution) and public (out-of-distribution) scores
  (``youden_margin``). A round that swept and selected from the right
  scores by the right rule reads at most about twice its confidences'
  drift; a threshold swept from the wrong calibration scores reads more
  wherever those move it off the optimum;
* ``payload_mismatch``: samples whose exchange weight is not the union
  of the contributors' masks, or whose contributor slots do not hold the
  contributors' own picks (exact: limit 0).

"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
ROC_THRESHOLDS = 256          # the paper's ROC sweep: 256 thresholds


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed (the driver's exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def fp8(x: jax.Array) -> jax.Array:
    """Round ``x`` to float8_e4m3 with one per-tensor scale (its absolute
    maximum maps to the format's largest finite value), back in f32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def ring_contributors(n: int) -> List[List[int]]:
    """Contributors to each node's label payload on a ring: itself first,
    then its neighbours in increasing order."""
    out = []
    for i in range(n):
        nbrs = sorted({(i - 1) % n, (i + 1) % n} - {i})
        out.append([i] + nbrs)
    return out


def roc_threshold(id_scores: np.ndarray, ood_scores: np.ndarray,
                  num: int = ROC_THRESHOLDS) -> float:
    """Youden's J over an even sweep between the lowest and the highest
    score (score > t means in-distribution), the paper's Optimal()."""
    ts = roc_sweep(id_scores, ood_scores, num)
    tpr = (id_scores[None, :] > ts[:, None]).mean(1)
    fpr = (ood_scores[None, :] > ts[:, None]).mean(1)
    return float(ts[np.argmax(tpr - fpr)])


def roc_sweep(id_scores: np.ndarray, ood_scores: np.ndarray,
              num: int = ROC_THRESHOLDS) -> np.ndarray:
    lo = min(id_scores.min(), ood_scores.min())
    hi = max(id_scores.max(), ood_scores.max())
    return np.linspace(lo - 1e-6, hi + 1e-6, num, dtype=np.float32)


def youden_margin(threshold: float, id_scores: np.ndarray,
                  ood_scores: np.ndarray, iters: int = 60) -> float:
    """The least margin ``m`` (a share of the scores' range) at which
    ``threshold`` is an optimum of Youden's J for some scores within
    ``m`` of these: where J at ``threshold`` with every score moved its
    way by ``m`` is at least the best J over the sweep with every score
    moved against it. A threshold swept from scores that lie within ``d``
    of these reads at most ``2d``."""
    if not np.isfinite(threshold):
        return float("inf")
    id_scores = np.asarray(id_scores, np.float64)
    ood_scores = np.asarray(ood_scores, np.float64)
    ts = roc_sweep(id_scores, ood_scores).astype(np.float64)
    lo = min(id_scores.min(), ood_scores.min())
    hi = max(id_scores.max(), ood_scores.max())
    span = max(hi - lo, 1e-30)

    def holds(m: float) -> bool:
        d = m * span
        at = (id_scores > threshold - d).mean() - \
            (ood_scores > threshold + d).mean()
        worst = (id_scores[None, :] > ts[:, None] + d).mean(1) - \
            (ood_scores[None, :] > ts[:, None] - d).mean(1)
        return at >= worst.max()

    if holds(0.0):
        return 0.0
    a = 0.0
    b = (max(abs(threshold - lo), abs(threshold - hi)) + span) / span
    for _ in range(iters):
        mid = 0.5 * (a + b)
        a, b = (a, mid) if holds(mid) else (mid, b)
    return b


# ------------------------------------------------------------ label rounds
def unpack_payload(vals: np.ndarray, idx: np.ndarray, weights: np.ndarray,
                   contributors: List[List[int]], k: int):
    """Split each node's exchanged payload ``(n, P, S, D·k)`` back into
    every contributor's picks, values and mask.

    Returns ``picks (n, P, S, k)``, ``values (n, P, S, k)`` (NaN where
    the contributor's mask is off), ``masks (n, P)`` and the number of
    payload slots that disagree with one another or with the weights.
    """
    n = vals.shape[0]
    shape = vals.shape[:-1]
    picks = np.full(shape + (k,), -1, np.int64)
    values = np.full(shape + (k,), np.nan)
    masks = np.zeros(vals.shape[:2], bool)
    seen = np.zeros(n, bool)
    mismatch = 0
    for i in range(n):
        slots = contributors[i]
        sv = vals[i].reshape(shape[1:] + (len(slots), k))
        si = idx[i].reshape(shape[1:] + (len(slots), k))
        on = (sv > 0).any(axis=(-1, -3))                     # (P, D)
        cnt = on.sum(-1)                                     # (P,)
        mismatch += int(((cnt > 0) != (weights[i] > 0)).sum())
        for d, j in enumerate(slots):
            pj = si[..., d, :]
            if seen[j]:
                mismatch += int((picks[j] != pj).any(axis=(-1, -2)).sum())
                mismatch += int((masks[j] != on[:, d]).sum())
                continue
            seen[j] = True
            picks[j] = pj
            masks[j] = on[:, d]
            share = np.where(on[:, d], 1.0 / np.maximum(cnt, 1), np.nan)
            values[j] = sv[..., d, :] / share[:, None, None]
    if not seen.all():
        mismatch += int((~seen).sum()) * vals.shape[1]
    return picks, values, masks, mismatch


def round_numbers(*, picks, values, masks, thresholds, ref_topk,
                  ref_at_picks, ref_conf_pub, ref_conf_val,
                  temperature: float) -> Dict[str, float]:
    """The label-round numbers for one set of outputs (the program's, or
    the control's put in its place) against the float32 reference.

    ``picks/values (n, P, S, k)``; ``ref_topk (n, P, S, k)`` sorted
    descending; ``ref_at_picks (n, P, S, k)`` the reference logits at
    ``picks``; ``ref_conf_pub (n, P)``, ``ref_conf_val (n, V)``.
    """
    kth = ref_topk[..., -1:]
    topk_gap = float(np.max(np.maximum(kth - ref_at_picks, 0.0)))
    z = ref_at_picks / temperature
    z = z - z.max(-1, keepdims=True)
    soft = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    known = ~np.isnan(values)
    value_gap = float(np.max(np.abs(values - soft)[known])) \
        if known.any() else 0.0
    gaps = [0.0]
    for j in range(masks.shape[0]):
        gaps.append(youden_margin(float(thresholds[j]), ref_conf_val[j],
                                  ref_conf_pub[j]))
        conf = ref_conf_pub[j]
        span = max(conf.max(), ref_conf_val[j].max()) - \
            min(conf.min(), ref_conf_val[j].min())
        span = max(span, 1e-30)
        disagree = masks[j] != (conf > thresholds[j])
        if disagree.any():
            gaps.append(float(np.max(np.abs(conf[disagree] - thresholds[j])
                                     ) / span))
    return {"topk_gap": topk_gap, "value_gap": value_gap,
            "selection_gap": max(gaps)}
