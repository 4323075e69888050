"""Decentralized LLM training driver.

Runs the full IDKD pipeline on token data: node-stacked params, per-node
private corpus shards (Dirichlet over topics), QG-DSGDm-N gossip steps,
and periodic IDKD homogenization rounds with top-k sparse soft labels on a
public corpus. On CPU this drives reduced configs end-to-end. On a TPU
the same functions run unchanged: ``chip_smoke.py`` at the repo root runs
them on one v5e chip at qwen3-1.7b's published widths (depth cut), and
``tests/test_tpu_compile.py`` compiles the label-round kernels for v5e
without a chip. ``dryrun.py`` compiles for forced CPU devices, so it says
nothing about a TPU.

The step loop is the unified on-device driver (``core.driver``): one
``make_step`` per phase (plain LM / LM + sparse-KD), per-node batch
sampling under jit, and the inner loop compiled as a ``lax.scan`` between
log boundaries. The outer loop is the federation scheduler
(``repro.sched``): homogenization rounds fire every
``IDKDConfig.every_k_steps`` (``num_rounds`` of them), churn / rewire
events remake the gossip mixer mid-run, and all traffic — wire-dtype
aware params-gossip plus the sparse label payloads — lands in one
communication ledger. Params-gossip and the IDKD label exchange share
one ``tcfg.topology`` graph (the seed gossiped on a hardwired ring while
labels moved on ``tcfg.topology``).

``--driver shard`` runs the federation under ``shard_map`` over a node
mesh (DESIGN.md §7): per-device node blocks, ppermute params-gossip,
shard-local label scoring with a top-k-only exchange. Develop/test
multi-device behaviour on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Adding
``--model-parallel N`` factors the device grid into a 2-D
``("node", "model")`` mesh (DESIGN.md §10): each replica's params and
optimizer state shard over N devices (FSDP-style), gossip stays
node-axis-only, and streaming label rounds run vocab-sharded.

``--compression topk --compression-frac 0.01`` sparsifies the gossip
wire (error-feedback top-k / random-k, DESIGN.md §9), ``--gossip
delayed`` switches to one-step-stale mixing, and ``--churn-mode stale``
turns ``--churn`` windows into straggler-tolerant rounds — the slow
node's neighbours keep mixing its last payload instead of stalling. All
three run under both the node-stacked and the shard drivers and land
compression-aware bytes in the ledger.

Usage (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 40 --nodes 8 --idkd [--rounds 2] [--churn 3@20-30]
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sched
from repro.configs import get_config
from repro.configs.base import IDKDConfig, ModelConfig, TrainConfig
from repro.core import distill, driver, labeling
from repro.core.algorithms import make_algorithm
from repro.core.mixing import (Mixer, make_mixer, normalize_compression,
                               payload_elem_count)
from repro.core.topology import Topology
from repro.data.dirichlet import dirichlet_partition
from repro.data.synthetic import make_lm_data
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import consensus_params, stack_params
from repro.models import build_model
from repro.obs import log as obs_log
from repro.obs.compile_path import CompileWatch
from repro.obs.trace import span
from repro.resil import SimulatedCrash


def make_gossip_mixer(tcfg: TrainConfig, wire_dtype: str = "native",
                      topology: Optional[Topology] = None,
                      active=None, stale=None, compression=None,
                      gossip: str = "sync", stateful=None,
                      wire_fault=None,
                      wire_guard=None) -> Tuple[Topology, Mixer]:
    """The (topology, mixer) pair the launch path gossips params on —
    ``_LMFederation``'s mixer construction point.

    Built from ``tcfg.topology`` (or an explicit ``topology``, e.g. after
    a rewire event) — the same graph object the IDKD label exchange uses,
    so params-gossip and label-exchange always agree. ``wire_dtype``
    applies to every phase, KD included (the seed's KD step silently
    built an f32-wire mixer, losing the §Perf bf16-wire halving);
    ``active`` is the churn mask. ``stale`` / ``compression`` /
    ``gossip`` / ``stateful`` are the compressed-wire controls
    (DESIGN.md §9) and ``wire_fault`` / ``wire_guard`` the resilience
    layer's fault-injection controls (DESIGN.md §12), all forwarded
    verbatim to ``mixing.make_mixer``.
    """
    topo = topology or Topology.make(tcfg.topology, tcfg.num_nodes)
    return topo, make_mixer(topo, wire_dtype=wire_dtype, active=active,
                            stale=stale, compression=compression,
                            gossip=gossip, stateful=stateful,
                            wire_fault=wire_fault, wire_guard=wire_guard)


def _streams(model, idkd_cfg: IDKDConfig, backend: str) -> bool:
    """Whether :func:`idkd_label_round` takes the streaming path.
    Multi-codebook heads (MusicGen) have no single (d, V) unembedding
    for head_select to tile — they keep the one-shot path."""
    return (idkd_cfg.stream_labels and backend in ("fused", "sparse")
            and getattr(model.cfg, "num_codebooks", 0) <= 1)


def idkd_label_round(model, params_stacked, public_tokens, private_tokens,
                     idkd_cfg: IDKDConfig, topology: Topology,
                     backend: str = "sparse", active=None, mesh=None):
    """LLM IDKD round via the unified labeling engine: per-sequence
    detector confidences + top-k soft labels on the public corpus,
    ROC-calibrated threshold, sparse neighbour label exchange.

    Returns (sparse_labels, weights (n, P), id_mask, thresholds,
    counters): ``counters`` are the models' forward counters of a
    node-stacked streaming round ((n,) each, e.g. the held-expert
    layers' ``held_assignments`` and ``expert_tiles``), else empty. The
    labels stay sparse end to end — neighbour averaging concatenates
    payloads along the k axis (k_out = (max_deg+1)·k) instead of the
    seed's densify→average→resparsify detour through (n, P, S, V).
    ``active`` masks churned-out nodes from the exchange. With ``mesh``
    (the shard driver's node mesh) the round runs sharded: score/select
    shard-local, the exchange ppermutes only top-k payloads across the
    node axis.

    With ``idkd_cfg.stream_labels`` (the default) the round is
    *streaming* (DESIGN.md §8): the public corpus goes through
    ``labeling.streaming_label_round`` / ``shard_streaming_label_round``
    in ``stream_microbatch``-sized chunks of the fused head-select pass,
    so the (n, P, S, V) public logit stack — the dominant HBM cost of a
    round at LLM vocab — never materializes. ``stream_labels=False``
    keeps the one-shot oracle path.

    The uploads of the tokens are an ``idkd.inputs`` span
    (:func:`repro.obs.trace.span`).
    """
    with span("idkd.inputs"):
        pub = jnp.asarray(public_tokens)
        priv = jnp.asarray(private_tokens)                  # (n, Vp, S)
    if _streams(model, idkd_cfg, backend):
        if mesh is not None:
            if active is not None:
                raise ValueError("sharded label rounds have no churn "
                                 "path; run churn schedules node-stacked")
            out = labeling.shard_streaming_label_round(
                model, params_stacked, pub, priv, topology, idkd_cfg,
                mesh=mesh)
        else:
            out = labeling.streaming_label_round(
                model, params_stacked, pub, priv, topology, idkd_cfg,
                active=active)
        return (out.labels, out.weights, out.id_masks, out.thresholds,
                out.counters or {})

    n = params_stacked and jax.tree.leaves(params_stacked)[0].shape[0]

    @jax.jit
    def node_logits(p, toks):
        return jax.vmap(lambda pp, tt: model.forward(pp, {"tokens": tt})[0]
                        )(p, toks)

    pub_b = jnp.broadcast_to(pub[None], (n,) + pub.shape)
    logits_pub = node_logits(params_stacked, pub_b)        # (n, P, S, V)
    logits_priv = node_logits(params_stacked, priv)
    # val = the node's private corpus (ID); cal=None = the public corpus
    if mesh is not None:
        if active is not None:
            raise ValueError("sharded label rounds have no churn path; "
                             "run churn schedules node-stacked")
        out = labeling.shard_label_round(logits_pub, logits_priv,
                                         topology, idkd_cfg, mesh=mesh)
    else:
        out = labeling.label_round(logits_pub, logits_priv, None,
                                   topology, idkd_cfg, backend=backend,
                                   active=active)
    return out.labels, out.weights, out.id_masks, out.thresholds, {}


class _LMFederation(sched.CompiledFederationHooks):
    """Scheduler hooks for the LM launch path: plain and sparse-KD steps
    per (graph, availability mask), labeling rounds refreshing the KD
    sampler ctx, per-round label byte accounting (cache machinery lives
    on :class:`sched.CompiledFederationHooks`)."""

    def __init__(self, *, model, algo, tcfg: TrainConfig,
                 idkd_cfg: IDKDConfig, cfg: ModelConfig, tokens, parts,
                 public_tokens, seq_len: int, wire_dtype: str,
                 driver_mode: str, verbose: bool, model_parallel: int = 1):
        super().__init__()
        self.model_parallel = model_parallel
        self.model = model
        self.algo = algo
        self.tcfg = tcfg
        self.idkd_cfg = idkd_cfg
        self.cfg = cfg
        self.tokens = tokens
        self.parts = parts
        self.public_tokens = public_tokens
        self.seq_len = seq_len
        self.wire_dtype = wire_dtype
        self.driver_mode = driver_mode
        self.verbose = verbose
        self.lr_fn = lambda s: jnp.asarray(tcfg.lr, jnp.float32)
        self.priv_parts = driver.pad_partitions(parts)
        self.plain_sampler = driver.make_lm_sampler(
            self.priv_parts, tokens, tcfg.batch_size)
        self.kd_sampler = None
        # compressed-wire spec ((kind, frac) or None) read off the config;
        # self.gossip is overwritten from the schedule by init_comm
        self.compression = tcfg.compression_spec
        self.head_reads = None      # per streaming round, from shapes

    def _make_mixer(self, topo: Topology, active, stale=None):
        return make_gossip_mixer(self.tcfg, self.wire_dtype,
                                 topology=topo, active=active, stale=stale,
                                 **self._mixer_opts())[1]

    def _adapter(self):
        return (driver.lm_adapter if self.phase == "plain"
                else driver.lm_sparse_kd_adapter(self.idkd_cfg))

    def _sampler(self):
        return (self.plain_sampler if self.phase == "plain"
                else self.kd_sampler)

    def restore_ctx(self, ctx: Dict, phase: str) -> None:
        """Mid-phase resume from a durable snapshot: rebuild the sparse
        LM-KD sampler from the snapshot's flat ctx payload instead of
        re-running the label round."""
        ctx = {k: jnp.asarray(v) for k, v in ctx.items()}
        self.ctx = ctx
        if self.kd_sampler is None:
            self.kd_sampler = driver.make_lm_kd_sampler(
                self.priv_parts, self.tokens, self.tcfg.batch_size,
                self.public_tokens, ctx["pub_vals"], ctx["pub_idx"],
                ctx["pub_w"], pub_batch=min(4, len(self.public_tokens)))
        self.phase = phase

    def on_round(self, params, round_index: int, step: int, topo: Topology,
                 active: np.ndarray) -> np.ndarray:
        """One label round, as an ``idkd.round`` span; its compile-path
        counts (:mod:`repro.obs.compile_path`) join
        ``last_round_stats``."""
        with span("idkd.round", round=int(round_index),
                  nodes=int(topo.n)), CompileWatch() as watch:
            label_bytes = self._label_round(params, round_index, step,
                                            topo, active)
        self.last_round_stats.update(watch.stats)
        return label_bytes

    def _label_round(self, params, round_index: int, step: int,
                     topo: Topology, active: np.ndarray) -> np.ndarray:
        """The round's host phases, each an ``idkd.*`` span: ``inputs``
        (the private sequences stacked and uploaded), the streaming
        round's passes, threshold and exchange, ``readback`` (the KD
        context, the masks, thresholds and forward counters read back
        in one transfer, the label bytes) and ``topk_overlap``."""
        cfg = self.idkd_cfg
        n = self.tcfg.num_nodes
        m_priv = max(1, min(16, min(len(p) for p in self.parts)))
        with span("idkd.inputs"):
            priv = np.stack([self.tokens[self.parts[i][:m_priv],
                                         :self.seq_len] for i in range(n)])
        backend = cfg.label_backend
        if backend not in ("fused", "sparse"):
            # the LM KD step consumes sparse payloads; the dense
            # oracle backend is not an option at vocab scale
            obs_log.warning("idkd.backend_fallback", requested=backend,
                            using="sparse")
            backend = "sparse"
        sparse, w, id_mask, thr, counters = idkd_label_round(
            self.model, params, self.public_tokens, priv, cfg, topo,
            backend=backend, active=None if active.all() else active,
            mesh=(self.shard_mesh(n) if self.driver_mode == "shard"
                  else None))
        with span("idkd.readback"):
            self.ctx = driver.lm_kd_ctx(sparse.values, sparse.indices, w)
            mask, thr, counters = jax.device_get((id_mask, thr, counters))
            id_fraction = float(mask.mean())
            counts = mask.sum(axis=1)
            k_wire = min(cfg.label_topk or labeling.DEFAULT_TOPK,
                         self.cfg.vocab_size)
            label_bytes = np.array(
                [distill.label_bytes(int(c) * self.seq_len,
                                     self.cfg.vocab_size, k_wire)
                 for c in counts], np.float64)
        if self.kd_sampler is None:
            self.kd_sampler = driver.make_lm_kd_sampler(
                self.priv_parts, self.tokens, self.tcfg.batch_size,
                self.public_tokens, sparse.values, sparse.indices, w,
                pub_batch=min(4, len(self.public_tokens)))
        self.phase = "kd"
        if self.verbose:
            obs_log.info("idkd.round", step=step, round=round_index,
                         id_fraction=round(id_fraction, 4),
                         thresholds=thr.round(3).tolist())
        # telemetry: run_schedule forwards this to on_labels + the
        # "labels" run-log event right after on_round returns
        with span("idkd.topk_overlap"):
            mean_ov, per_edge = labeling.neighbor_topk_overlap(
                np.asarray(sparse.indices), topo)
        self.last_round_stats = {
            "thresholds": thr, "selected": counts,
            "id_fraction": id_fraction, "detector": cfg.detector,
            "topk_overlap": mean_ov, "topk_overlap_per_edge": per_edge}
        # the forward's counters, summed over the nodes (held-expert
        # layers: held_assignments, expert_tiles)
        self.last_round_stats.update(
            {name: int(np.sum(c)) for name, c in counters.items()})
        if _streams(self.model, cfg, backend):
            if self.head_reads is None:
                self.head_reads = labeling.head_reads(
                    self.model, params, self.public_tokens, priv, cfg,
                    model_size=(self.model_parallel
                                if self.driver_mode == "shard" else 1))
            self.last_round_stats["head_reads"] = self.head_reads
        return label_bytes


def run_training(cfg: ModelConfig, tcfg: TrainConfig, *, seq_len: int = 64,
                 n_seqs: int = 512, n_public: int = 64, log_every: int = 10,
                 use_idkd: bool = False, verbose: bool = True,
                 wire_dtype: str = "native", driver_mode: str = "scan",
                 events: Sequence = (),
                 schedule: Optional[sched.Schedule] = None,
                 model_parallel: int = 1,
                 telemetry=None, resil=None) -> Dict[str, Any]:
    """End-to-end reduced-scale decentralized LM training (CPU-friendly).

    ``events`` (churn / rewire) and a custom ``schedule`` feed the
    federation scheduler; by default the schedule is compiled from
    ``tcfg`` (log boundaries + the IDKD rounds ``tcfg.idkd`` asks for).
    ``model_parallel > 1`` (shard driver only) runs each replica sharded
    over the second (``"model"``) axis of the 2-D federation mesh
    (DESIGN.md §10): FSDP-style parameter/optimizer sharding,
    vocab-sharded streaming label rounds, node-axis-only gossip.

    ``telemetry`` (a :class:`repro.obs.Telemetry`) turns on the run-log /
    metrics-bus / trace-span layers for this run (DESIGN.md §11); the
    trajectory is bitwise identical with it on or off.

    Returns the consensus ``params``, the node-stacked ``node_params``
    as the runners left them (placed on the shard mesh under
    ``driver_mode="shard"``), the eval ``loss_history``, the model,
    topology, ledger and schedule.

    ``resil`` (a :class:`repro.resil.Resilience`) turns on the
    resilience layer (DESIGN.md §12): health guards + quarantine,
    durable snapshots with auto-resume, rollback-on-divergence. A
    ``crash`` FaultEvent in the schedule raises
    :class:`repro.resil.SimulatedCrash` out of this function — rerun
    with the same ``resil.snapshot_dir`` to resume.
    """
    n = tcfg.num_nodes
    model = build_model(cfg)
    # the one graph params-gossip and the label exchange share; the hooks
    # build (and cache) the actual mixers per availability mask
    topo = Topology.make(tcfg.topology, tcfg.num_nodes)
    algo = make_algorithm(tcfg.algorithm, momentum=tcfg.momentum,
                          weight_decay=tcfg.weight_decay)
    tokens, topics = make_lm_data(cfg.vocab_size, seq_len + 1, n_seqs,
                                  seed=tcfg.seed)
    parts = dirichlet_partition(topics, n, tcfg.alpha,
                                np.random.default_rng(tcfg.seed))
    public_tokens, _ = make_lm_data(cfg.vocab_size, seq_len, n_public,
                                    num_topics=10, seed=tcfg.seed + 99)
    params = stack_params(model.init(jax.random.PRNGKey(tcfg.seed)), n)
    idkd_cfg = tcfg.idkd or IDKDConfig(label_topk=8)

    kd_fires = use_idkd and 0 <= idkd_cfg.start_step < tcfg.steps
    if schedule is None:
        rounds = (sched.idkd_round_steps(idkd_cfg, tcfg.steps)
                  if kd_fires else ())
        schedule = sched.compile_schedule(tcfg.steps, log_every,
                                          round_steps=rounds, events=events,
                                          gossip=tcfg.gossip)
    elif events:
        raise ValueError("pass events to compile_schedule, not alongside "
                         "a prebuilt schedule")
    if schedule.gossip != tcfg.gossip:
        raise ValueError(
            f"schedule gossip mode {schedule.gossip!r} disagrees with "
            f"TrainConfig.gossip={tcfg.gossip!r}; pass gossip= to "
            "compile_schedule (or drop the prebuilt schedule)")
    if schedule.round_steps and not use_idkd:
        raise ValueError("schedule contains homogenization rounds but "
                         "use_idkd=False")

    if model_parallel != 1 and driver_mode != "shard":
        raise ValueError("model_parallel > 1 shards each replica over "
                         "the 2-D federation mesh and needs "
                         "driver_mode='shard' (DESIGN.md §10)")
    fed = _LMFederation(model=model, algo=algo, tcfg=tcfg,
                        idkd_cfg=idkd_cfg, cfg=cfg, tokens=tokens,
                        parts=parts, public_tokens=public_tokens,
                        seq_len=seq_len, wire_dtype=wire_dtype,
                        driver_mode=driver_mode, verbose=verbose,
                        model_parallel=model_parallel)
    opt_state = algo.init(params)
    key = jax.random.PRNGKey(tcfg.seed + 1)

    if driver_mode == "shard":
        # shard-mode pre-flight: fail before training, not mid-schedule
        from repro.core.mixing import shard_supported_topology
        from repro.launch.sharding import federation_shardings
        if wire_dtype != "native":
            raise ValueError("driver_mode='shard' moves shards in their "
                             f"storage dtype; wire_dtype={wire_dtype!r} "
                             "needs the node-stacked runners")
        if not shard_supported_topology(topo):
            raise ValueError(
                f"driver_mode='shard' gossips on ring/complete graphs "
                f"only; topology {topo.name!r} needs driver_mode="
                "'scan' or 'host'")
        sched.validate_shard_schedule(schedule, n, model_parallel)
        mesh = fed.shard_mesh(n)
        params = jax.device_put(
            params, federation_shardings(params, mesh, n))
        opt_state = jax.device_put(
            opt_state, federation_shardings(opt_state, mesh, n))

    nparams = sum(x.size for x in jax.tree.leaves(params)) // n
    comp = normalize_compression(tcfg.compression_spec)
    payload_elems = (payload_elem_count(params, comp, node_stacked=True)
                     if comp is not None else None)
    index_bytes = 4 if comp is not None else 0
    comp_kind, comp_frac = comp if comp is not None else ("none", 0.0)
    ledger = sched.CommLedger(n, meta={
        "topology": topo.name, "wire_dtype": wire_dtype,
        "param_count": int(nparams),
        "compression": comp_kind, "compression_frac": comp_frac,
        "gossip": schedule.gossip})

    history = []
    t0 = time.time()

    def on_eval(params, step, losses):
        history.append(float(losses[-1]))
        if verbose:
            obs_log.info("train.eval", step=step,
                         loss=round(history[-1], 4),
                         elapsed_s=round(time.time() - t0, 1))

    fed.on_eval = on_eval
    params, opt_state, key, _ = sched.run_schedule(
        schedule, fed, params, opt_state, key, topology=topo,
        ledger=ledger, param_count=int(nparams),
        elem_bytes=sched.wire_elem_bytes(wire_dtype, cfg.dtype),
        payload_elems=payload_elems, index_bytes=index_bytes,
        telemetry=telemetry, resil=resil)
    return {"params": consensus_params(params), "node_params": params,
            "loss_history": history, "model": model, "topology": topo,
            "ledger": ledger.as_dict(), "schedule": schedule}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--idkd", action="store_true")
    ap.add_argument("--rounds", type=int, default=1,
                    help="IDKD homogenization rounds (spaced every-k)")
    ap.add_argument("--every-k", type=int, default=0,
                    help="steps between rounds (default: fit them evenly "
                         "into the post-start span)")
    ap.add_argument("--churn", default="",
                    help="churn spec node@down-up[,...], e.g. 3@20-30")
    ap.add_argument("--churn-mode", default="freeze",
                    choices=list(sched.CHURN_MODES),
                    help="what --churn means: freeze (hold params), "
                         "isolate (train but no gossip), or stale "
                         "(straggler — neighbours mix its last payload)")
    ap.add_argument("--wire-dtype", default="native",
                    choices=["native", "float32"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "topk", "randk"],
                    help="gossip wire compression (DESIGN.md §9)")
    ap.add_argument("--compression-frac", type=float, default=0.01,
                    help="fraction of each leaf kept per send (top-k / "
                         "random-k)")
    ap.add_argument("--gossip", default="sync",
                    choices=list(sched.GOSSIP_MODES),
                    help="sync mixes this step's params; delayed mixes "
                         "the previous step's payload (one-step-stale)")
    ap.add_argument("--driver", default="scan",
                    choices=["scan", "host", "shard"])
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="width of the 2-D federation mesh's 'model' "
                         "axis (shard driver only): each replica's "
                         "params/optimizer shard over this many devices "
                         "while gossip stays node-axis-only "
                         "(DESIGN.md §10)")
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config — TPU scale")
    ap.add_argument("--telemetry", default="", metavar="DIR",
                    help="write run.jsonl (events + metrics-bus flushes) "
                         "under DIR (DESIGN.md §11); off when empty")
    ap.add_argument("--trace", action="store_true",
                    help="also export Chrome trace_event spans to "
                         "DIR/trace.json (Perfetto-loadable; needs "
                         "--telemetry)")
    ap.add_argument("--faults", default="", metavar="SPEC",
                    help="deterministic fault injection: comma-separated "
                         "kind@step[/nodes][/mode] events, e.g. "
                         "'corrupt@8/2/nan,crash@14,clear@16' "
                         "(DESIGN.md §12)")
    ap.add_argument("--guards", action="store_true",
                    help="turn on the on-device health guard: non-finite "
                         "loss/grad/param detection + wire validation, "
                         "tripped nodes quarantined at the segment "
                         "boundary")
    ap.add_argument("--snapshot-dir", default="", metavar="DIR",
                    help="write durable checkpointed snapshots under DIR "
                         "at segment boundaries; if DIR already holds "
                         "snapshots the run auto-resumes from the newest "
                         "valid one")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="min steps between durable snapshots (0 = every "
                         "segment boundary)")
    ap.add_argument("--rollback", action="store_true",
                    help="on a guard trip, restore the pre-segment state "
                         "and re-run with the offender quarantined "
                         "(implies --guards)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace and not args.telemetry:
        ap.error("--trace needs --telemetry DIR for the output location")
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    start = args.steps // 2
    every_k = args.every_k or sched.fit_every_k(args.steps, start,
                                                args.rounds)
    tcfg = TrainConfig(num_nodes=args.nodes, steps=args.steps, lr=0.1,
                       alpha=args.alpha, batch_size=8,
                       topology=args.topology,
                       compression=args.compression,
                       compression_frac=args.compression_frac,
                       gossip=args.gossip,
                       idkd=IDKDConfig(start_step=start, label_topk=8,
                                       every_k_steps=every_k,
                                       num_rounds=args.rounds))
    events = (sched.parse_churn(args.churn, args.nodes, args.steps,
                                mode=args.churn_mode)
              if args.churn else ())
    if args.faults:
        events = (*events, *sched.parse_faults(args.faults, args.nodes,
                                               args.steps))
    resil = None
    if args.guards or args.rollback or args.snapshot_dir:
        from repro.resil import GuardSpec, Resilience
        resil = Resilience(
            guard=(GuardSpec() if args.guards or args.rollback else None),
            snapshot_dir=args.snapshot_dir or None,
            snapshot_every=args.snapshot_every,
            rollback=args.rollback)
    telemetry = None
    if args.telemetry:
        from repro.obs import Telemetry
        telemetry = Telemetry(args.telemetry, trace=args.trace,
                              meta={"arch": args.arch, "steps": args.steps,
                                    "nodes": args.nodes,
                                    "topology": args.topology,
                                    "driver": args.driver,
                                    "idkd": args.idkd})
    try:
        out = run_training(cfg, tcfg, use_idkd=args.idkd,
                           wire_dtype=args.wire_dtype,
                           driver_mode=args.driver, events=events,
                           model_parallel=args.model_parallel,
                           telemetry=telemetry, resil=resil)
    except SimulatedCrash as e:
        # injected crash: a clean exit so harnesses (the CI chaos job)
        # can re-invoke with the same --snapshot-dir and auto-resume
        obs_log.warning("simulated_crash_exit", step=e.step,
                        snapshot_dir=args.snapshot_dir or None)
        print(f"simulated crash at step {e.step}; re-run with the same "
              "--snapshot-dir to resume from the last durable snapshot")
        return
    finally:
        if telemetry is not None:
            telemetry.close()
    print(f"final loss: {out['loss_history'][-1]:.4f}")
    led = out["ledger"]
    print(f"comm ledger: {led['gossip_bytes']/1e6:.2f} MB gossip + "
          f"{led['label_bytes']/1e6:.3f} MB labels over "
          f"{len(led['per_round'])} round bucket(s)")
    if args.telemetry:
        print(f"telemetry: {args.telemetry}/run.jsonl"
              + (f" + {args.telemetry}/trace.json" if args.trace else ""))


if __name__ == "__main__":
    main()
