"""Cells of qwen3-1.7b (``qwen3-1.7b.json``): the model at its published
widths, cut to the depth the JSON states, driven through the program's
own LM federation hooks (``repro.launch.train._LMFederation``, the hooks
``run_training`` builds).

Kinds of traffic (``"kind"`` in the workload file):

* ``label_rounds``: homogenization rounds back to back on the current
  parameters, each through the hooks' ``on_round`` -> ``idkd_label_round``
  -> ``labeling.streaming_label_round`` -> ``head_select``. Its check
  compares the last round's exchanged payload, masks and thresholds with
  the plain reference (``qwen3-1.7b.reference.py``).

The benchmark makes the weights (one jitted call on the device, in
bfloat16, independent per node) and the token data from the seed; the
program gets them as inputs.
"""
from __future__ import annotations

import gc
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks
from bench.harness import load_module

REF = load_module(Path(__file__).with_name("qwen3-1.7b.reference.py"),
                  "bench_qwen3_reference")
NORM_LEAVES = ("scale", "q_norm", "k_norm")
HEAD_KERNEL = "head_select"


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the JSON's sizes."""
    from repro.configs import get_config
    return get_config("qwen3-1.7b").replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm=True,
        dtype=cfg["torch_dtype"])


def ref_cfg(cfg: dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "num_hidden_layers")
    return tuple((k, cfg[k]) for k in keys)


def make_weights(model, cfg: dict, seed: int, nodes: int):
    """Node-stacked parameters in the program's layout, drawn from the
    seed in one jitted call and stored in the configuration's dtype."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def build(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            name = str(path[-1].key)
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, (nodes,) + s.shape, jnp.float32)
            if name in NORM_LEAVES:
                w = 1.0 + 0.1 * z
            elif name == "embed":
                w = 0.02 * z
            else:
                w = z / jnp.sqrt(float(s.shape[-2]))
            leaves.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(build)(checks.seed_key(seed))


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one token's forward pass: every weight matrix, the
    tied head, and causal attention over the average context; the
    embedding lookup is not counted."""
    d, H, KVH = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd, ff, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = 2 * (d * H * hd + 2 * d * KVH * hd + H * hd * d
                     + 3 * d * ff)
    attn = 2 * 2 * H * hd * (seq_len + 1) / 2
    return cfg["num_hidden_layers"] * (per_layer + attn) + 2 * d * V


class LabelRounds:
    """Homogenization rounds back to back (one round per unit)."""

    def __init__(self, cfg: dict, workload: dict, seed: int, devices):
        self.cfg, self.workload, self.seed = cfg, workload, seed
        self.devices = devices
        self.n = workload["nodes"]
        self.S = workload["seq_len"]
        self.P = workload["public_seqs"]
        self.M = workload["calibration_seqs"]
        self.k = workload["label_topk"]
        self.T = float(workload["temperature"])
        self.rounds = 0
        self.out = None

    # ----------------------------------------------------------- program
    def data(self):
        """Token ids from the seed: each node's private calibration
        sequences (with one extra position, as the corpus holds them)
        and the shared public set."""
        rng = np.random.default_rng(self.seed)
        V = self.cfg["vocab_size"]
        tokens = rng.integers(0, V, (self.n * self.M, self.S + 1),
                              dtype=np.int32)
        public = rng.integers(0, V, (self.P, self.S), dtype=np.int32)
        return tokens, public

    def setup(self):
        from repro.configs.base import IDKDConfig, TrainConfig
        from repro.core.algorithms import make_algorithm
        from repro.core.topology import Topology
        from repro.launch.train import _LMFederation
        from repro.models import build_model

        wl = self.workload
        self.mcfg = program_config(self.cfg)
        self.model = build_model(self.mcfg)
        idkd = IDKDConfig(start_step=0, label_topk=self.k,
                          label_backend="sparse", temperature=self.T,
                          detector=wl["detector"],
                          select_block_rows=wl["select_block_rows"],
                          every_k_steps=1, num_rounds=1)
        tcfg = TrainConfig(num_nodes=self.n, topology=wl["topology"],
                           steps=1, batch_size=1, idkd=idkd)
        algo = make_algorithm(tcfg.algorithm, momentum=tcfg.momentum,
                              weight_decay=tcfg.weight_decay)
        tokens, public = self.data()
        parts = [np.arange(i * self.M, (i + 1) * self.M)
                 for i in range(self.n)]
        self.tokens, self.public = tokens, public
        self.fed = _LMFederation(
            model=self.model, algo=algo, tcfg=tcfg, idkd_cfg=idkd,
            cfg=self.mcfg, tokens=tokens, parts=parts, public_tokens=public,
            seq_len=self.S, wire_dtype="native", driver_mode="scan",
            verbose=False)
        self.topo = Topology.make(wl["topology"], self.n)
        self.active = np.ones(self.n, bool)
        self.params = make_weights(self.model, self.cfg, self.seed, self.n)
        jax.block_until_ready(self.params)
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self.unit()

    def unit(self) -> Dict[str, float]:
        with jax.profiler.TraceAnnotation("bench.round"):
            self.fed.on_round(self.params, self.rounds, 0, self.topo,
                              self.active)
            jax.block_until_ready(self.fed.ctx)
        self.rounds += 1
        return {"rounds": 1}

    def release(self):
        """Copy what the last round produced to the host and free the
        program's device state before the reference runs."""
        ctx = self.fed.ctx
        stats = self.fed.last_round_stats
        self.out = {"vals": np.asarray(ctx["pub_vals"]),
                    "idx": np.asarray(ctx["pub_idx"]),
                    "weights": np.asarray(ctx["pub_w"]),
                    "thresholds": np.asarray(stats["thresholds"],
                                             np.float64)}
        self.fed = self.params = None
        gc.collect()

    # --------------------------------------------------------- reference
    def _passes(self, precision: str, picks_sets=()):
        """The reference of every node over the public and the
        calibration sets at ``precision``: per-sequence confidences
        (public (n, P), calibration (n, M)), the k best logits of every
        public position and their indices ((n, P, S, k) each), and the
        logits at each of ``picks_sets`` ((G, n, P, S, k))."""
        params = make_weights(self.model, self.cfg, self.seed, self.n)
        rc = ref_cfg(self.cfg)
        block = self.workload.get("reference_rows", 1024)
        priv = self.tokens[:, :self.S].reshape(self.n, self.M, self.S)
        G = len(picks_sets)
        out = {"conf_pub": [], "conf_val": [], "top_v": [], "top_i": [],
               "at": []}
        for i in range(self.n):
            picks = np.stack([ps[i].reshape(-1, self.k) for ps in picks_sets]
                             ) if G else None
            for name, tokens in (("pub", self.public), ("val", priv[i])):
                h = REF.hidden(params, i, jnp.asarray(tokens), cfg=rc,
                               precision=precision)
                h = h.reshape(-1, h.shape[-1])
                conf, tv, ti, at = [], [], [], []
                for r0 in range(0, h.shape[0], block):
                    hb = h[r0:r0 + block]
                    pk = (picks[:, r0:r0 + block] if name == "pub" and G
                          else np.zeros((1, hb.shape[0], self.k), np.int32))
                    c, v, ix, a = REF.head_block(hb, params["embed"], i,
                                                 jnp.asarray(pk), k=self.k,
                                                 precision=precision)
                    conf.append(np.asarray(c))
                    if name == "pub":
                        tv.append(np.asarray(v))
                        ti.append(np.asarray(ix))
                        at.append(np.asarray(a))
                out[f"conf_{name}"].append(
                    np.concatenate(conf).reshape(len(tokens), self.S)
                    .mean(-1))
                if name == "pub":
                    shape = (self.P, self.S, self.k)
                    out["top_v"].append(np.concatenate(tv).reshape(shape))
                    out["top_i"].append(np.concatenate(ti).reshape(shape))
                    out["at"].append(np.concatenate(at, axis=1).reshape(
                        (-1,) + shape))
            del h
        del params
        out = {k: np.stack(v) for k, v in out.items()}
        out["at"] = np.moveaxis(out["at"], 0, 1)[:G]
        return out

    def control_outputs(self, precision: str = "fp8"):
        """The reference put in the program's place at a lower precision:
        its picks, values, masks and thresholds, computed as the program
        would (ROC threshold, confidence above it)."""
        r = self._passes(precision)
        thr = np.asarray([checks.roc_threshold(r["conf_val"][i],
                                               r["conf_pub"][i])
                          for i in range(self.n)])
        masks = r["conf_pub"] > thr[:, None]
        z = np.exp((r["top_v"] - r["top_v"][..., :1]) / self.T)
        values = np.where(masks[:, :, None, None],
                          z / z.sum(-1, keepdims=True), np.nan)
        return {"picks": r["top_i"], "values": values, "masks": masks,
                "thresholds": thr}

    def program_outputs(self):
        picks, values, masks, mismatch = checks.unpack_payload(
            self.out["vals"], self.out["idx"], self.out["weights"],
            checks.ring_contributors(self.n), self.k)
        return {"picks": picks, "values": values, "masks": masks,
                "thresholds": self.out["thresholds"],
                "mismatch": mismatch}

    def numbers(self, with_control: bool = False) -> Dict[str, Dict]:
        """The compared numbers of the program (and of the control)."""
        runs = {"program": self.program_outputs()}
        if with_control:
            runs["control"] = self.control_outputs()
        ref = self._passes("f32", [np.clip(o["picks"], 0, None)
                                   for o in runs.values()])
        out = {}
        for (name, o), at in zip(runs.items(), ref["at"]):
            nums = checks.round_numbers(
                picks=o["picks"], values=o["values"], masks=o["masks"],
                thresholds=o["thresholds"], ref_topk=ref["top_v"],
                ref_at_picks=at, ref_conf_pub=ref["conf_pub"],
                ref_conf_val=ref["conf_val"], temperature=self.T)
            nums["payload_mismatch"] = float(o.get("mismatch", 0))
            out[name] = nums
        return out

    def check(self) -> List[dict]:
        nums = self.numbers()["program"]
        limits = self.workload["limits"]
        return [{"name": k, "value": nums[k],
                 "limit": float(limits[k]) if limits.get(k) is not None
                 else float("nan")}
                for k in ("topk_gap", "value_gap", "selection_gap",
                          "payload_mismatch")]

    # ------------------------------------------------------------- counts
    def head_select_work(self) -> Dict[str, float]:
        """FLOPs and the least bytes of one round's ``head_select``
        calls: every public and calibration position of every node
        against the node's (d, V) head, each head read once per call."""
        d, V = self.cfg["hidden_size"], self.cfg["vocab_size"]
        rows = self.n * (self.P + self.M) * self.S
        itemsize = jnp.dtype(self.cfg["torch_dtype"]).itemsize
        head_reads = 2 * self.n          # public pass and calibration pass
        return {"flops": 2.0 * rows * d * V,
                "bytes": (head_reads * d * V + rows * d) * itemsize
                + rows * (1 + 2 * self.k) * 4}

    def flops(self) -> Dict[str, float]:
        tokens = self.n * (self.P + self.M) * self.S
        return {"round": tokens * forward_flops_per_token(self.cfg, self.S),
                "head_select": self.head_select_work()}

    def kernels(self) -> List[str]:
        return [HEAD_KERNEL]


KINDS = {"label_rounds": LabelRounds}


def make_cell(cfg: dict, workload: dict, seed: int, devices):
    kind = workload["kind"]
    if kind not in KINDS:
        raise ValueError(f"qwen3-1.7b has no cell of kind {kind!r}; "
                         f"known: {sorted(KINDS)}")
    return KINDS[kind](cfg, workload, seed, devices)
