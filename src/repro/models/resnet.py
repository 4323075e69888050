"""ResNet-CIFAR with EvoNorm-S0 — the paper's architecture (ResNet20).

Faithful to §4.1: BasicBlock ResNet (3 stages), BatchNorm replaced with a
batch-statistics-free EvoNorm so the model trains correctly under non-IID
decentralized data (Hsieh et al. 2020; Andreux et al. 2020).
Implemented in NHWC with jax.lax.conv_general_dilated.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import evonorm_b0, init_evonorm


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
    return w * jnp.sqrt(2.0 / fan_in)


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_im2col(x, w, stride=1):
    """SAME conv as patch-gather + one matmul (no ``lax.conv`` in the
    graph). XLA:CPU runs vmapped-kernel convs ~4× slower and any conv
    inside a ``while`` loop ~5× slower (DESIGN.md §5); matmuls hit
    neither pathology, so this path makes the scan/shard runners viable
    for conv models on CPU (``ModelConfig.conv_backend="im2col"``).
    Padding follows XLA's SAME convention (low = total // 2), so outputs
    match ``_conv`` to float tolerance at every stride.
    """
    kh, kw, cin, cout = w.shape
    B, H, W, _ = x.shape
    ho = -(-H // stride)
    wo = -(-W // stride)
    ph = max((ho - 1) * stride + kh - H, 0)
    pw = max((wo - 1) * stride + kw - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    patches = [
        xp[:, dy:dy + (ho - 1) * stride + 1:stride,
           dx:dx + (wo - 1) * stride + 1:stride, :]
        for dy in range(kh) for dx in range(kw)
    ]
    cols = jnp.stack(patches, axis=-2)          # (B, ho, wo, kh·kw, cin)
    cols = cols.reshape(B, ho, wo, kh * kw * cin)
    return cols @ w.reshape(kh * kw * cin, cout)


class ResNetModel:
    """Same interface surface as DecoderModel (init / forward / loss)."""

    input_key = "images"

    def __init__(self, cfg: ModelConfig):
        assert cfg.arch_type == "cnn"
        if cfg.conv_backend not in ("lax", "im2col"):
            raise ValueError(f"unknown conv_backend {cfg.conv_backend!r}; "
                             "expected 'lax' or 'im2col'")
        self.cfg = cfg
        self._conv = _conv_im2col if cfg.conv_backend == "im2col" else _conv

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 256))
        p: Dict[str, Any] = {
            "stem": _conv_init(next(keys), 3, 3, cfg.image_channels,
                               cfg.cnn_width),
            "stem_norm": init_evonorm(cfg.cnn_width),
        }
        cin = cfg.cnn_width
        for si, blocks in enumerate(cfg.cnn_stages):
            cout = cfg.cnn_width * (2 ** si)
            for bi in range(blocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                blk = {
                    "conv1": _conv_init(next(keys), 3, 3, cin, cout),
                    "norm1": init_evonorm(cout),
                    "conv2": _conv_init(next(keys), 3, 3, cout, cout),
                    "norm2": init_evonorm(cout),
                }
                if stride != 1 or cin != cout:
                    blk["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                p[f"s{si}b{bi}"] = blk
                cin = cout
        p["fc_w"] = jax.random.normal(next(keys), (cin, cfg.num_classes),
                                      jnp.float32) / jnp.sqrt(cin)
        p["fc_b"] = jnp.zeros((cfg.num_classes,), jnp.float32)
        return p

    def forward_features(self, params, batch):
        """batch['images']: (B, H, W, C) float32 -> (feats (B, F),
        counters={}) — the pooled pre-head activations (streaming
        labeling hook); the model counts nothing."""
        cfg = self.cfg
        conv = self._conv
        x = batch["images"]
        x = conv(x, params["stem"])
        x = evonorm_b0(x, params["stem_norm"])
        for si, blocks in enumerate(cfg.cnn_stages):
            for bi in range(blocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                blk = params[f"s{si}b{bi}"]
                h = conv(x, blk["conv1"], stride)
                h = evonorm_b0(h, blk["norm1"])
                h = conv(h, blk["conv2"])
                h = evonorm_b0(h, blk["norm2"])
                sc = conv(x, blk["proj"], stride) if "proj" in blk else x
                x = jax.nn.relu(h + sc)
        return jnp.mean(x, axis=(1, 2)), {}

    def stacked_features(self, params, batch):
        """:meth:`forward_features` of node-stacked ``params`` and
        ``batch``, vmapped over the nodes."""
        return jax.vmap(self.forward_features)(params, batch)

    def head_params(self, params):
        """(weight (F, C), bias (C,)) of the classifier head."""
        return params["fc_w"], params["fc_b"]

    def forward(self, params, batch):
        """batch['images']: (B, H, W, C) float32 -> (logits, aux=0)."""
        x, _ = self.forward_features(params, batch)
        w, b = self.head_params(params)
        return x @ w + b, jnp.zeros((), jnp.float32)

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        if labels.ndim == logits.ndim:          # soft labels (distillation)
            nll = -jnp.sum(labels * logp, axis=-1)
        else:
            nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        w = batch.get("weights")
        if w is None:
            loss = jnp.mean(nll)
        else:
            loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
        acc = jnp.mean((jnp.argmax(logits, -1) ==
                        (labels if labels.ndim == 1 else jnp.argmax(labels, -1))
                        ).astype(jnp.float32))
        return loss, {"nll": loss, "acc": acc, "aux": aux}
