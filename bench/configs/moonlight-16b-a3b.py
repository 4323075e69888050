"""Cells of moonlight-16b-a3b (``moonlight-16b-a3b.json``): one chip's
share of an expert- and vocab-parallel deployment of the model at its
published widths and depth, driven through the program's own LM
federation hooks, as the qwen3-1.7b cells are (``qwen3-1.7b.py``, whose
label-round cell this one extends).

Kinds of traffic (``"kind"`` in the workload file):

* ``label_rounds``: homogenization rounds back to back on the current
  parameters, each through the hooks' ``on_round`` ->
  ``idkd_label_round`` -> ``labeling.streaming_label_round``: the
  decoder's latent attention and held-expert layers
  (``kernels/expert_ffn``), then ``head_select`` over the vocabulary
  slice. Its check compares the last round's exchanged payload, masks
  and thresholds with the plain reference
  (``moonlight-16b-a3b.reference.py``).

Set-up draws the weights from the seed leaf by leaf on the device (the
expert banks layer by layer, so no float32 copy of a whole bank is
live), then fits every node's per-layer router correction bias
(``e_score_correction_bias``) on a fitting batch drawn from the seed
apart from the public set, layer by layer through the reference's own
layers at bfloat16: the aux-free update b <- b + g * sign(mean load -
load) of DeepSeek-V3's training, until every expert's share of the
top-k picks is within ``FIT_TOLERANCE`` of 1/E. The program and the
reference both read the fitted bias as a weight.
"""
from __future__ import annotations

import functools
import gc
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks
from bench.harness import load_module

HERE = Path(__file__).resolve().parent
BASE = load_module(HERE / "qwen3-1.7b.py", "bench_qwen3_builder")
REF = load_module(HERE / "moonlight-16b-a3b.reference.py",
                  "bench_moonlight_reference")
NORM_LEAVES = ("scale", "kv_norm")
HEAD_KERNEL, EXPERT_KERNEL = "head_select", "expert_ffn"
FIT_TOLERANCE = 0.05         # each expert's share within 5% of 1/E
FIT_STEPS = 600


def held_range(cfg: dict):
    first, count = cfg["deployment"]["held_experts"]
    assert count == cfg["n_routed_experts"]
    return first, count


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the JSON's sizes: the published
    router width, the held experts and the vocabulary slice."""
    import dataclasses

    from repro.configs import get_config
    base = get_config("moonlight-16b-a3b")
    q_lora = cfg["q_lora_rank"] or 0
    return base.replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        mla=dataclasses.replace(
            base.mla, q_lora_rank=q_lora, kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe,
            num_experts=cfg["deployment"]["published_n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_d_ff=cfg["moe_intermediate_size"],
            num_shared_experts=cfg["n_shared_experts"],
            first_k_dense=cfg["first_k_dense_replace"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            held_experts=held_range(cfg)))


def ref_cfg(cfg: dict):
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "rope_theta", "num_hidden_layers",
            "first_k_dense_replace", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys) + (
        ("first_held_expert", held_range(cfg)[0]),)


@functools.partial(jax.jit, static_argnames=("shape", "kind", "layers",
                                             "dtype"))
def _draw(key, *, shape, kind: str, layers: int, dtype: str):
    """One leaf (node-stacked) in ``dtype``; a leaf stacked over
    ``layers`` is drawn one layer at a time into its buffer."""
    def one(k, s):
        z = jax.random.normal(k, s, jnp.float32)
        if kind == "norm":
            return (1.0 + 0.1 * z).astype(dtype)
        if kind == "embed":
            return (0.02 * z).astype(dtype)
        return (z / jnp.sqrt(float(s[-2]))).astype(dtype)

    if not layers:
        return one(key, shape)
    per = shape[:1] + shape[2:]

    def body(li, buf):
        w = one(jax.random.fold_in(key, li), per)
        return jax.lax.dynamic_update_index_in_dim(buf, w, li, 1)
    return jax.lax.fori_loop(0, layers, body, jnp.zeros(shape, dtype))


def make_weights(model, seed: int, nodes: int):
    """Node-stacked parameters in the program's layout and the
    configuration's dtype, drawn from the seed leaf by leaf; the router
    biases start at zero."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    key = checks.seed_key(seed)
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(path[-1].key)
        shape = (nodes,) + s.shape
        if name == "router_bias":
            leaves.append(jnp.zeros(shape, jnp.float32))
            continue
        kind = ("norm" if name in NORM_LEAVES else
                "embed" if name == "embed" else "matrix")
        layered = str(path[0].key).startswith("layers_") and \
            len(s.shape) >= 3
        leaves.append(_draw(jax.random.fold_in(key, i), shape=shape,
                            kind=kind, layers=s.shape[0] if layered else 0,
                            dtype=model.cfg.dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


@functools.partial(jax.jit, static_argnames=("k", "steps"))
def fit_bias(scores, *, k: int, steps: int = FIT_STEPS):
    """The correction bias (E,) that balances the top-k picks of
    ``scores + bias`` over the rows of ``scores (R, E)``: DeepSeek-V3's
    aux-free update with a step that shrinks geometrically, keeping the
    most even bias seen. Returns (bias, largest deviation of an expert's
    load from the mean, as a share of the mean)."""
    R, E = scores.shape
    mean = R * k / E

    def load(b):
        _, ids = jax.lax.top_k(scores + b, k)
        return jnp.zeros(E, jnp.float32).at[ids.reshape(-1)].add(1.0)

    def step(t, carry):
        b, best_b, best = carry
        ld = load(b)
        dev = jnp.max(jnp.abs(ld - mean)) / mean
        best_b, best = jax.lax.cond(dev < best, lambda: (b, dev),
                                    lambda: (best_b, best))
        b = b + 0.02 * 0.985 ** t * jnp.sign(mean - ld)
        return b, best_b, best

    zero = jnp.zeros((E,), jnp.float32)
    _, bias, dev = jax.lax.fori_loop(0, steps, step, (zero, zero, jnp.inf))
    return bias, dev


def held_share(scores, bias, k: int, first: int, count: int) -> float:
    _, ids = jax.lax.top_k(scores + bias, k)
    ids = np.asarray(ids)
    return float(((ids >= first) & (ids < first + count)).mean())


def fit_router_biases(params, cfg: dict, tokens: np.ndarray, nodes: int):
    """Every node's correction bias per expert layer, fitted layer by
    layer on ``tokens (B, S)`` through the reference's layers at
    bfloat16, each layer's input made with the biases fitted below it.
    Returns the biases ``(nodes, L, E)`` and, per node and layer, the
    held experts' share of the picks with a zero bias and with the
    fitted one, and the fit's largest deviation."""
    rc = ref_cfg(cfg)
    c = dict(rc)
    first, count = held_range(cfg)
    k = c["num_experts_per_tok"]
    dense, moe = params["layers_0"], params["layers_1"]
    biases, report = [], []
    for i in range(nodes):
        x = REF._embed(params["embed"], i, jnp.asarray(tokens))
        for li in range(c["first_k_dense_replace"]):
            x = REF.attention(x, dense, i, li, cfg=rc, precision="bf16")
            x = REF.dense_mlp(x, dense, i, li, cfg=rc, precision="bf16")
        node_b, node_r = [], []
        for li in range(moe["ln1"]["scale"].shape[1]):
            x = REF.attention(x, moe, i, li, cfg=rc, precision="bf16")
            s = REF.router_scores(x, moe, i, li, cfg=rc)
            s = s.reshape(-1, s.shape[-1])
            b, dev = fit_bias(s, k=k)
            node_r.append({
                "unfitted": held_share(s, jnp.zeros_like(b), k, first,
                                       count),
                "fitted": held_share(s, b, k, first, count),
                "deviation": float(dev)})
            node_b.append(b)
            x = REF.expert_layer(x, moe, i, li, b, cfg=rc,
                                 precision="bf16")
        biases.append(jnp.stack(node_b))
        report.append(node_r)
    return jnp.stack(biases), report


def layer_flops_per_token(cfg: dict, seq_len: int) -> Dict[str, float]:
    """Forward FLOPs of one token by part, from shapes: latent attention
    (projections and the causal scores over the average context), the
    dense layer's MLP, one expert layer's shared experts and router, one
    held assignment's expert, and the head slice. The embedding lookup
    is not counted."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lat, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    E = cfg["deployment"]["published_n_routed_experts"]
    mla = 2 * (d * H * (nope + rope) + d * (lat + rope)
               + lat * H * (nope + vd) + H * vd * d) \
        + 2 * H * (nope + rope + vd) * (seq_len + 1) / 2
    return {"mla": mla,
            "dense_mlp": 2 * 3 * d * cfg["intermediate_size"],
            "shared": 2 * 3 * d * f * cfg["n_shared_experts"],
            "router": 2 * d * E,
            "expert": 2 * 3 * d * f,
            "head": 2 * d * cfg["vocab_size"]}


class LabelRounds(BASE.LabelRounds):
    """Homogenization rounds back to back (one round per unit)."""

    def fit_tokens(self) -> np.ndarray:
        """The router fit's batch: ``fit_seqs`` sequences of the slice
        from the seed, a stream apart from the public set's."""
        rng = np.random.default_rng([self.seed, 1])
        return rng.integers(0, self.cfg["vocab_size"],
                            (self.workload["fit_seqs"], self.S),
                            dtype=np.int32)

    def weights(self):
        """The node-stacked weights with the fitted router biases, and
        the fit's report."""
        params = make_weights(self.model, self.seed, self.n)
        biases, report = fit_router_biases(params, self.cfg,
                                           self.fit_tokens(), self.n)
        params["layers_1"]["moe"]["router_bias"] = biases
        return params, biases, report

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
                          stream_microbatch=wl["stream_microbatch"],
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
        self.params, self.biases, self.fit_report = self.weights()
        jax.block_until_ready(self.params)
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self.unit()
        # what set-up made and keeps (the weights' tree, the fit, the
        # reference's and the program's compiled functions) leaves the
        # collector's view: a round's full collections then scan what
        # the rounds make, not the set-up's objects again
        gc.collect()
        gc.freeze()

    def round_counts(self) -> Dict[str, int]:
        """The last round's held-expert counters (``last_round_stats``)."""
        if self.fed is not None:
            stats = self.fed.last_round_stats
            self.counts = {name: stats[name] for name in
                           ("held_assignments", "expert_tiles")}
        return self.counts

    def release(self):
        """Copy what the last round produced and its counters to the
        host, and free the program's device state (the weights stay:
        the reference reads them)."""
        self.round_counts()
        gc.unfreeze()
        params = self.params
        super().release()
        self.params = params

    # --------------------------------------------------------- reference
    def _passes(self, precision: str, picks_sets=()):
        """The reference of every node over the public and the
        calibration sets at ``precision``, in blocks of
        ``reference_seqs`` sequences: per-sequence confidences (public
        (n, P), calibration (n, M)), the k best logits of every public
        position and their indices ((n, P, S, k) each), and the logits
        at each of ``picks_sets`` ((G, n, P, S, k))."""
        params = self.params
        rc = ref_cfg(self.cfg)
        rows = self.workload.get("reference_rows", 1024)
        seqs = self.workload.get("reference_seqs", 128)
        priv = self.tokens[:, :self.S].reshape(self.n, self.M, self.S)
        G = len(picks_sets)
        out = {"conf_pub": [], "conf_val": [], "top_v": [], "top_i": [],
               "at": []}
        for i in range(self.n):
            picks = np.stack([ps[i].reshape(-1, self.k) for ps in picks_sets]
                             ) if G else None
            for name, tokens in (("pub", self.public), ("val", priv[i])):
                conf, tv, ti, at = [], [], [], []
                for s0 in range(0, len(tokens), seqs):
                    h = REF.hidden(params, i, jnp.asarray(tokens[s0:s0 + seqs]),
                                   self.biases[i], cfg=rc,
                                   precision=precision)
                    h = h.reshape(-1, h.shape[-1])
                    base = s0 * self.S
                    for r0 in range(0, h.shape[0], rows):
                        hb = h[r0:r0 + rows]
                        pk = (picks[:, base + r0:base + r0 + rows]
                              if name == "pub" and G else
                              np.zeros((1, hb.shape[0], self.k), np.int32))
                        c, v, ix, a = REF.head_block(
                            hb, params["head"], i, jnp.asarray(pk), k=self.k,
                            precision=precision)
                        conf.append(np.asarray(c))
                        if name == "pub":
                            tv.append(np.asarray(v))
                            ti.append(np.asarray(ix))
                            at.append(np.asarray(a))
                    del h
                out[f"conf_{name}"].append(
                    np.concatenate(conf).reshape(len(tokens), self.S)
                    .mean(-1))
                if name == "pub":
                    shape = (self.P, self.S, self.k)
                    out["top_v"].append(np.concatenate(tv).reshape(shape))
                    out["top_i"].append(np.concatenate(ti).reshape(shape))
                    out["at"].append(np.concatenate(at, axis=1).reshape(
                        (-1,) + shape))
        out = {k: np.stack(v) for k, v in out.items()}
        out["at"] = np.moveaxis(out["at"], 0, 1)[:G]
        return out

    def check(self) -> List[dict]:
        """The numbers the workload limits: ``selection_gap`` and
        ``payload_mismatch``. ``topk_gap`` and ``value_gap`` have no
        limit in this cell: their largest reading over the public set
        falls on tokens that a bf16 forward routes to other experts than
        the float32 reference, where the program, the reference at bf16
        and the fp8 control read alike (PERF.md section 4)."""
        limits = self.workload["limits"]
        return [c for c in super().check() if c["name"] in limits]

    # ------------------------------------------------------------- counts
    def expert_ffn_work(self) -> Dict[str, float]:
        """FLOPs and the least bytes of one round's ``expert_ffn`` calls,
        from the counted held assignments: each assignment's SwiGLU, its
        token row read and its output row written once, and every held
        expert's weights read once per call (one call per expert layer,
        public chunk and calibration pass, both nodes at once)."""
        c = self.cfg
        d, f = c["hidden_size"], c["moe_intermediate_size"]
        item = jnp.dtype(c["torch_dtype"]).itemsize
        held = self.round_counts()["held_assignments"]
        layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
        mb = min(self.workload["stream_microbatch"], self.P)
        calls = layers * (-(-self.P // mb) + 1)
        weights = self.n * c["n_routed_experts"] * 3 * d * f * item
        return {"flops": 2.0 * 3 * d * f * held,
                "bytes": calls * weights + held * 2 * d * item}

    def flops(self) -> Dict[str, float]:
        """A round's forward FLOPs: every token through attention, the
        dense MLP, the shared experts, the router and the head slice,
        and the held experts' FLOPs of the assignments counted."""
        c = self.cfg
        per = layer_flops_per_token(c, self.S)
        L = c["num_hidden_layers"]
        dense = c["first_k_dense_replace"]
        tokens = self.n * (self.P + self.M) * self.S
        per_token = (L * per["mla"] + dense * per["dense_mlp"]
                     + (L - dense) * (per["shared"] + per["router"])
                     + per["head"])
        routed = per["expert"] * self.round_counts()["held_assignments"]
        return {"round": tokens * per_token + routed,
                "head_select": self.head_select_work(),
                "expert_ffn": self.expert_ffn_work()}

    def kernels(self) -> List[str]:
        return [HEAD_KERNEL, EXPERT_KERNEL]


KINDS = {"label_rounds": LabelRounds}


def make_cell(cfg: dict, workload: dict, seed: int, devices):
    kind = workload["kind"]
    if kind not in KINDS:
        raise ValueError(f"moonlight-16b-a3b has no cell of kind {kind!r}; "
                         f"known: {sorted(KINDS)}")
    return KINDS[kind](cfg, workload, seed, devices)
