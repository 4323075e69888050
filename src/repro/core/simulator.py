"""In-process decentralized training simulator (CPU accuracy experiments).

All N nodes live in one process: parameters are node-stacked pytrees
(leading axis = node), per-node gradients come from ``vmap``, gossip is the
dense mixing matrix — mathematically identical to the paper's MPI cluster
under synchronous rounds, which is what the paper runs.

The step loop is the unified driver (``core.driver``): loss adapters +
``make_step`` build the jitted steps, per-node sampling runs on device,
and the inner loop executes as ``lax.scan`` chunks between eval
boundaries (``driver_mode="auto"`` keeps lax-conv models on the
per-step host runner on CPU — DESIGN.md §5 CPU caveats;
``ModelConfig.conv_backend="im2col"`` lifts that). ``driver_mode=
"shard"`` places the node axis on a device mesh instead: the step runs
under ``shard_map`` with ppermute/psum gossip and the homogenization
round exchanges only top-k payloads across the node axis (DESIGN.md
§7) — trajectory-equivalent to the node-stacked runners on supported
(ring/complete) graphs, with churn rejected up front.

The *outer* loop is the federation scheduler (``repro.sched``, DESIGN.md
§6): ``run()`` compiles a :class:`~repro.sched.Schedule` (or accepts a
custom one) and replays it through ``sched.run_schedule`` — periodic
re-homogenization rounds every ``IDKDConfig.every_k_steps``, churn
(nodes dropping out and rejoining with masked Metropolis mixing), graph
rewires, mid-run checkpoint capture/resume, and a unified per-round
communication ledger all ride on that one loop. A 1-round schedule is
byte-identical to the pre-scheduler behaviour (degenerate-schedule
equivalence).

Supports the full method grid of Tables 2–7:
  * algorithms: dsgd / dsgdm / qg-dsgdm-n / d2 / relaysgd / centralized
  * ``kd_mode``: None (no distillation), "vanilla" (no OoD filter — the
    QG-DSGDm-N + KD baseline), "idkd" (MSP-filtered — the paper's method)
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sched
from repro.configs.base import IDKDConfig, ModelConfig, TrainConfig
from repro.core import distill, driver, idkd, labeling
from repro.core.algorithms import make_algorithm
from repro.core.mixing import (consensus_distance, make_mixer,
                               normalize_compression, payload_elem_count)
from repro.core.topology import Topology
from repro.data.dirichlet import dirichlet_partition, partition_stats
from repro.data.synthetic import ClassificationData
from repro.models import build_model
from repro.obs.compile_path import CompileWatch
from repro.obs.trace import span
from repro.optim.schedules import step_decay


@dataclass
class SimResult:
    final_acc: float
    acc_history: List[float] = field(default_factory=list)
    loss_history: List[float] = field(default_factory=list)
    consensus_history: List[float] = field(default_factory=list)
    pre_hist: Optional[np.ndarray] = None    # (n, C) class hists pre-IDKD
    post_hist: Optional[np.ndarray] = None   # (n, C) class hists post-IDKD
    thresholds: Optional[np.ndarray] = None
    id_fraction: float = 0.0                 # fraction of D_P kept as ID
    comm_bytes_per_iter: float = 0.0
    label_bytes_total: float = 0.0
    wall_seconds: float = 0.0
    rounds: List[Dict] = field(default_factory=list)  # per-round diagnostics
    ledger: Optional[Dict] = None            # sched.CommLedger.as_dict()
    captured: Optional[Dict] = None          # run(capture_at=...) snapshot


class _SimFederation(sched.CompiledFederationHooks):
    """Scheduler hooks binding the simulator's samplers, steps, and
    mixers to the federation loop (cache machinery lives on
    :class:`sched.CompiledFederationHooks`); the prebuilt default-mixer
    steps from ``sim._build_jits`` are reused for the all-up mask on the
    run's own gossip graph."""

    def __init__(self, sim: "DecentralizedSimulator", result: SimResult,
                 idkd_cfg: IDKDConfig):
        super().__init__()
        self.sim = sim
        self.model = sim.model
        self.algo = sim.algo
        self.lr_fn = sim.lr_fn
        self.driver_mode = sim.driver_mode
        self.result = result
        self.idkd_cfg = idkd_cfg
        self.sparse_round = False
        self.compression = sim.compression
        self.gossip = sim.gossip            # re-set per run by init_comm
        self._node_mesh = sim.node_mesh     # shard mode: one shared mesh
        self.model_parallel = sim.model_parallel
        self.priv_parts = driver.pad_partitions(sim.parts)
        self.plain_sampler = driver.make_classification_sampler(
            self.priv_parts, sim.data.train_x, sim.data.train_y,
            sim.mcfg.num_classes, sim.tcfg.batch_size)
        self.kd_sampler = None

    def reset(self, result: SimResult) -> None:
        """Rebind for a fresh run, keeping the compiled mixer/step/runner
        caches (repeated ``sim.run()`` calls — the bench warm-up path and
        checkpoint-resume runs — pay zero recompiles)."""
        self.result = result
        self.phase = "plain"
        self.ctx = None
        self.sparse_round = False
        # drop any previous run's (likely closed) telemetry sink; each
        # run() passes its own through run_schedule — same for the
        # resilience config and any leftover injected-fault state
        self.telemetry = None
        self.resil = None
        self.wire_fault = None

    # ----------------------------------------------------- cache plumbing
    def _make_mixer(self, topo: Topology, active, stale=None):
        sim = self.sim
        # the prebuilt mixer knows nothing of injected wire faults —
        # fault segments rebuild through make_mixer's validated wrap
        if (active is None and stale is None
                and self._fault_key() is None
                and topo.edge_key() == sim.gossip_topo.edge_key()
                and self._force_state == sim._prebuilt_stateful):
            return sim.mixer
        return make_mixer(topo, "dense", wire_dtype=sim.wire_dtype,
                          active=active, stale=stale, **self._mixer_opts())

    def _adapter(self):
        return {
            "plain": driver.classification_adapter,
            "kd_dense": driver.dense_kd_adapter(
                self.idkd_cfg.temperature, self.idkd_cfg.kd_weight),
            "kd_sparse": driver.sparse_kd_adapter(
                self.idkd_cfg.temperature, self.idkd_cfg.kd_weight),
        }[self.phase]

    def _sampler(self):
        return (self.plain_sampler if self.phase == "plain"
                else self.kd_sampler)

    def _base_step(self, topo: Topology, active: np.ndarray,
                   stale: np.ndarray):
        sim = self.sim
        # the prebuilt steps from sim._build_jits were compiled without
        # the metrics/guard carries and fault-free — telemetry, guarded,
        # and fault segments rebuild through the cache
        if (active.all() and not stale.any() and not self._metrics_on()
                and self._fault_key() is None
                and self._guard_spec() is None
                and topo.edge_key() == sim.gossip_topo.edge_key()
                and self._force_state == sim._prebuilt_stateful):
            return {"plain": sim._plain_step, "kd_dense": sim._kd_step,
                    "kd_sparse": sim._sparse_kd_step}[self.phase]
        return super()._base_step(topo, active, stale)

    # -------------------------------------------------------------- hooks
    def restore_ctx(self, ctx: Dict, phase: str) -> None:
        """Mid-phase resume from a durable snapshot: rebuild the KD
        sampler state straight from the snapshot's flat ctx payload
        (exactly what :meth:`on_round` would have produced) instead of
        re-running the label round."""
        sim = self.sim
        ctx = {k: jnp.asarray(v) for k, v in ctx.items()}
        self.sparse_round = "values" in ctx
        payload = ((ctx["values"], ctx["indices"]) if self.sparse_round
                   else ctx["labels"])
        self.ctx = ctx
        if self.kd_sampler is None:
            self.kd_sampler = driver.make_homogenized_sampler(
                self.priv_parts,
                driver.PaddedParts(ctx["pub_idx"], ctx["pub_size"]),
                sim.data.train_x, sim.data.train_y, sim.public_x,
                ctx["weights"], payload, sim.mcfg.num_classes,
                sim.tcfg.batch_size)
        self.phase = phase

    def on_round(self, params, round_index: int, step: int, topo: Topology,
                 active: np.ndarray) -> np.ndarray:
        """One label round, as an ``idkd.round`` span; its compile-path
        counts (:mod:`repro.obs.compile_path`) join
        ``last_round_stats``."""
        with span("idkd.round", round=int(round_index),
                  nodes=int(topo.n)), CompileWatch() as watch:
            per_node = self._label_round(params, round_index, step, topo,
                                         active)
        self.last_round_stats.update(watch.stats)
        return per_node

    def _label_round(self, params, round_index: int, step: int,
                     topo: Topology, active: np.ndarray) -> np.ndarray:
        sim = self.sim
        cfg = self.idkd_cfg
        hom = sim._homogenize(params, cfg, topo,
                              None if active.all() else active,
                              wire_fault=self._fault_key())
        self.sparse_round = isinstance(hom, labeling.SparseHomogenizedSet)
        payload = ((hom.labels.values, hom.labels.indices)
                   if self.sparse_round else np.asarray(hom.labels))
        weights = np.asarray(hom.weights)
        self.ctx = driver.homogenized_ctx(weights, payload,
                                          len(sim.public_x))
        if self.kd_sampler is None:
            self.kd_sampler = driver.make_homogenized_sampler(
                self.priv_parts,
                driver.PaddedParts(self.ctx["pub_idx"],
                                   self.ctx["pub_size"]),
                sim.data.train_x, sim.data.train_y, sim.public_x,
                weights, payload, sim.mcfg.num_classes,
                sim.tcfg.batch_size)
        self.phase = "kd_sparse" if self.sparse_round else "kd_dense"

        # diagnostics: last round wins the summary fields, every round is
        # appended to result.rounds
        res = self.result
        res.thresholds = np.asarray(hom.thresholds)
        res.id_fraction = float(np.mean(np.asarray(hom.id_masks)))
        res.post_hist = sim._post_histograms(hom)
        # wire cost: sparse backends ship each node's own top-k payload;
        # the dense backend always ships full (P, C) rows
        k_wire = (min(cfg.label_topk or labeling.DEFAULT_TOPK,
                      sim.mcfg.num_classes) if self.sparse_round else 0)
        id_counts = np.asarray(hom.id_masks).sum(axis=1)
        per_node = np.array([distill.label_bytes(int(c),
                                                 sim.mcfg.num_classes,
                                                 k_wire)
                             for c in id_counts], np.float64)
        res.rounds.append({"step": step, "round": round_index,
                           "id_fraction": res.id_fraction,
                           "label_bytes": float(per_node.sum())})
        # telemetry: run_schedule reads this right after on_round and
        # forwards it to hooks.on_labels + the "labels" run-log event
        stats = {"thresholds": np.asarray(hom.thresholds),
                 "selected": id_counts, "id_fraction": res.id_fraction,
                 "detector": cfg.detector}
        if self.sparse_round:
            mean_ov, per_edge = labeling.neighbor_topk_overlap(
                np.asarray(hom.labels.indices), topo)
            stats["topk_overlap"] = mean_ov
            stats["topk_overlap_per_edge"] = per_edge
        self.last_round_stats = stats
        return per_node

    def on_eval(self, params, step: int, losses) -> None:
        acc, nll = self.sim._eval(params)
        self.result.acc_history.append(acc)
        self.result.loss_history.append(nll)
        cons = float(consensus_distance(params))
        self.result.consensus_history.append(cons)
        tel = self.telemetry
        if tel is not None:
            tel.event("accuracy", step=step, acc=acc, nll=nll,
                      consensus=cons)
        if not (np.isfinite(nll) and np.isfinite(acc)):
            if tel is not None:
                tel.event("health", step=step, kind="eval_nonfinite",
                          acc=acc, nll=nll)


class DecentralizedSimulator:
    def __init__(self, model_cfg: ModelConfig, train_cfg: TrainConfig,
                 data: ClassificationData, public_x: Optional[np.ndarray] = None,
                 kd_mode: Optional[str] = None, eval_every: int = 50,
                 eval_batches: int = 4, driver_mode: str = "auto",
                 wire_dtype: str = "float32", model_parallel: int = 1):
        self.mcfg = model_cfg
        self.tcfg = train_cfg
        self.data = data
        self.public_x = public_x
        self.kd_mode = kd_mode
        self.eval_every = eval_every
        self.eval_batches = eval_batches
        self.driver_mode = driver.resolve_runner_mode(
            driver_mode, model_cfg.arch_type, model_cfg.conv_backend)
        # shard mode only: width of the federation mesh's "model" axis
        # (1 = the 1-D node mesh; DESIGN.md §10)
        self.model_parallel = model_parallel
        if model_parallel > 1 and self.driver_mode != "shard":
            raise ValueError(
                "model_parallel > 1 shards each replica over the 2-D "
                "federation mesh and needs driver_mode='shard'")
        # paper-faithful full-precision mixing is the simulator default;
        # the configured value reaches the mixer, the ledger, and the
        # result metadata alike (no more pinned "float32" anywhere)
        self.wire_dtype = wire_dtype

        n = train_cfg.num_nodes
        self.topology = Topology.make(train_cfg.topology, n)
        if train_cfg.algorithm == "centralized":
            # exact averaging reference: the complete graph's Metropolis
            # matrix is exactly uniform 1/n mixing — and its masked path
            # averages over the surviving nodes under churn
            self.gossip_topo = Topology.make("full", n)
        else:
            self.gossip_topo = self.topology
        # the prebuilt mixer/steps bake in the config's compression +
        # gossip mode; a schedule that needs a different statefulness
        # (e.g. stale churn on an uncompressed config) rebuilds through
        # the scheduler's cache instead of reusing these
        self.compression = normalize_compression(train_cfg.compression_spec)
        self.gossip = train_cfg.gossip
        self._prebuilt_stateful = bool(self.compression is not None
                                       or self.gossip == "delayed")
        self.mixer = make_mixer(self.gossip_topo, "dense",
                                wire_dtype=self.wire_dtype,
                                compression=self.compression,
                                gossip=self.gossip)
        self.algo = make_algorithm(train_cfg.algorithm,
                                   topology=self.topology,
                                   momentum=train_cfg.momentum,
                                   weight_decay=train_cfg.weight_decay)
        self.model = build_model(model_cfg)

        self.node_mesh = None
        if self.driver_mode == "shard":
            # every shard-mode limitation fails here, at construction —
            # not mid-schedule when a step/round first compiles
            from repro.core.mixing import shard_supported_topology
            if not shard_supported_topology(self.gossip_topo):
                raise ValueError(
                    f"driver_mode='shard' gossips on ring/complete graphs "
                    f"only; topology {self.gossip_topo.name!r} needs the "
                    "node-stacked runners (driver_mode='scan' or 'host')")
            if kd_mode is not None and \
                    not shard_supported_topology(self.topology):
                # centralized runs gossip on the complete graph but
                # label-exchange on the run topology — validate both
                raise ValueError(
                    f"driver_mode='shard' exchanges labels on "
                    f"ring/complete graphs only; topology "
                    f"{self.topology.name!r} needs the node-stacked "
                    "runners (driver_mode='scan' or 'host')")
            icfg = train_cfg.idkd or IDKDConfig()
            if kd_mode is not None and icfg.label_backend == "dense":
                raise ValueError(
                    "driver_mode='shard' moves only top-k label payloads "
                    "across the node axis; set IDKDConfig.label_backend="
                    "'sparse' (or 'fused'), or use driver_mode='scan'/"
                    "'host' for the dense oracle")
            from repro.launch.mesh import make_federation_mesh
            self.node_mesh = make_federation_mesh(n, self.model_parallel)

        rng = np.random.default_rng(train_cfg.seed)
        if train_cfg.algorithm == "centralized":
            # paper: centralized reference uses a random IID distribution
            idx = rng.permutation(len(data.train_y))
            self.parts = [np.asarray(p) for p in np.array_split(idx, n)]
        else:
            self.parts = dirichlet_partition(
                data.train_y, n, alpha=getattr(train_cfg, "alpha", 0.1),
                rng=rng)
        self.lr_fn = step_decay(train_cfg.lr, train_cfg.steps,
                                train_cfg.lr_decay_milestones,
                                train_cfg.lr_decay_factor)
        self._fed: Optional[_SimFederation] = None
        self._build_jits()

    # ------------------------------------------------------------------ setup
    def _build_jits(self):
        """Steps come from the unified driver (core.driver.make_step, or
        make_shard_step under driver_mode="shard"); only the diagnostics
        (forward/eval) are built here."""
        model, mixer, algo = self.model, self.mixer, self.algo
        icfg = self.tcfg.idkd or IDKDConfig()

        if self.driver_mode == "shard":
            self._plain_step = driver.make_shard_step(
                model, algo, driver.classification_adapter,
                mesh=self.node_mesh, topology=self.gossip_topo,
                compression=self.compression, gossip=self.gossip)
            self._sparse_kd_step = driver.make_shard_step(
                model, algo,
                driver.sparse_kd_adapter(icfg.temperature, icfg.kd_weight),
                mesh=self.node_mesh, topology=self.gossip_topo,
                compression=self.compression, gossip=self.gossip)
            # dense label payloads never exist in shard mode (top-k wire)
            self._kd_step = None
        else:
            self._plain_step = driver.make_step(
                model, algo, mixer, driver.classification_adapter)
            self._kd_step = driver.make_step(
                model, algo, mixer,
                driver.dense_kd_adapter(icfg.temperature, icfg.kd_weight))
            self._sparse_kd_step = driver.make_step(
                model, algo, mixer,
                driver.sparse_kd_adapter(icfg.temperature, icfg.kd_weight))

        @jax.jit
        def forward_logits(params, images):
            """vmapped per-node forward: images (n, B, ...) -> (n, B, C)."""
            return jax.vmap(
                lambda p, x: model.forward(p, {"images": x})[0])(params, images)

        @jax.jit
        def consensus_eval(params, images, labels, mask):
            mean_p = jax.tree.map(lambda t: jnp.mean(
                t.astype(jnp.float32), axis=0).astype(t.dtype), params)
            logits, _ = model.forward(mean_p, {"images": images})
            hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
            cnt = jnp.maximum(jnp.sum(mask), 1.0)
            acc = jnp.sum(hit * mask) / cnt
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            per = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
            nll = jnp.sum(per * mask) / cnt
            return acc, nll

        self._forward_logits = forward_logits
        self._consensus_eval = consensus_eval

    def _stacked_init(self):
        key = jax.random.PRNGKey(self.tcfg.seed)
        params = self.model.init(key)   # identical init on all nodes (paper)
        n = self.tcfg.num_nodes
        return jax.tree.map(lambda t: jnp.broadcast_to(t[None],
                                                       (n,) + t.shape), params)

    # -------------------------------------------------------------- inference
    def _node_logits(self, params, x: np.ndarray, batch: int = 256):
        """All-node logits on a shared array x: returns (n, len(x), C).
        Stays on device — shard mode keeps the stack sharded over the
        node mesh axis (params carry the placement, so the vmapped
        forward partitions over nodes); host callers np.asarray it."""
        n = self.tcfg.num_nodes
        outs = []
        for i in range(0, len(x), batch):
            xb = jnp.asarray(x[i:i + batch])
            xb = jnp.broadcast_to(xb[None], (n,) + xb.shape)
            outs.append(self._forward_logits(params, xb))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def _per_node_val_inputs(self, batch: int = 256):
        """Each node's own private samples (n, m, ...) — its ID set
        (paper: D_V^i; the node's training samples)."""
        m = min(min(len(p) for p in self.parts), batch)
        idx = np.stack([p[:m] for p in self.parts])
        return jnp.asarray(self.data.train_x[idx])

    def _per_node_val_logits(self, params, batch: int = 256):
        """Each node's logits on its own private samples (ID scores)."""
        return self._forward_logits(params,
                                    self._per_node_val_inputs(batch))

    # ------------------------------------------------------------------- run
    def default_schedule(self) -> sched.Schedule:
        """The schedule this simulator's config asks for: eval boundaries
        plus the IDKD rounds (``start_step`` + ``num_rounds`` ×
        ``every_k_steps``) when KD is active."""
        idkd_cfg = self.tcfg.idkd or IDKDConfig()
        rounds = (sched.idkd_round_steps(idkd_cfg, self.tcfg.steps)
                  if self._kd_active(idkd_cfg) else ())
        return sched.compile_schedule(self.tcfg.steps, self.eval_every,
                                      round_steps=rounds,
                                      gossip=self.gossip)

    def _kd_active(self, idkd_cfg: IDKDConfig) -> bool:
        return (self.kd_mode is not None and self.public_x is not None
                and idkd_cfg.start_step < self.tcfg.steps)

    def run(self, schedule: Optional[sched.Schedule] = None,
            resume: Optional[Dict] = None,
            capture_at: Optional[int] = None,
            telemetry=None, resil=None) -> SimResult:
        """Replay the federation schedule through the scheduler: chunked
        scan/host runners between boundaries, homogenization rounds
        re-labeling and refreshing the KD sampler as they fire, churn /
        rewire events remaking the mixer, and every byte of gossip and
        label traffic logged to the communication ledger.

        ``resume`` is a ``{"params", "opt_state", "key", "step"}`` state
        (as produced by ``capture_at``) restarting mid-schedule at a legal
        boundary; ``capture_at`` snapshots the state at that boundary into
        ``result.captured``.

        ``telemetry`` (a :class:`repro.obs.Telemetry`) turns on the
        observability layers for this run — JSONL run events, the
        on-device metrics bus, and trace spans (DESIGN.md §11). The
        trajectory is bitwise identical with it on or off.

        ``resil`` (a :class:`repro.resil.Resilience`) turns on the
        resilience layer (DESIGN.md §12): the on-device health guard,
        quarantine-on-trip, durable snapshots with auto-resume, and
        rollback-on-divergence. With guards on and no fault firing the
        trajectory is bitwise identical to guards off. A ``crash``
        :class:`~repro.sched.FaultEvent` in the schedule raises
        :class:`repro.resil.SimulatedCrash` out of this method; calling
        ``run()`` again with the same ``resil.snapshot_dir`` resumes
        from the last durable snapshot.
        """
        t0 = time.time()
        tcfg = self.tcfg
        n = tcfg.num_nodes
        idkd_cfg = tcfg.idkd or IDKDConfig()
        kd_active = self._kd_active(idkd_cfg)
        if schedule is None:
            schedule = self.default_schedule()
        elif schedule.round_steps and not kd_active:
            raise ValueError(
                "schedule contains homogenization rounds but the simulator "
                "has no kd_mode/public data to run them")
        if schedule.gossip != self.gossip:
            raise ValueError(
                f"schedule compiled with gossip={schedule.gossip!r} but "
                f"this simulator's TrainConfig.gossip is {self.gossip!r}; "
                "pass gossip= to sched.compile_schedule (or use "
                "default_schedule()) so the prebuilt steps and the "
                "schedule agree")

        result = SimResult(final_acc=0.0)
        result.pre_hist = partition_stats(self.data.train_y, self.parts,
                                          self.mcfg.num_classes)
        if resume is not None:
            params, opt_state = resume["params"], resume["opt_state"]
            key, resume_step = resume["key"], int(resume["step"])
        else:
            params = self._stacked_init()
            opt_state = self.algo.init(params)
            key = jax.random.PRNGKey(tcfg.seed)
            resume_step = 0
        if self.driver_mode == "shard":
            # churn / unsupported rewires fail here, before any training
            sched.validate_shard_schedule(schedule, n, self.model_parallel)
            from repro.launch.sharding import federation_shardings
            params = jax.device_put(
                params, federation_shardings(params, self.node_mesh, n))
            opt_state = jax.device_put(
                opt_state,
                federation_shardings(opt_state, self.node_mesh, n))

        proto = self.model.init(jax.random.PRNGKey(0))
        nparams = sum(x.size for x in jax.tree.leaves(proto))
        param_dtype = str(jax.tree.leaves(proto)[0].dtype)
        elem_bytes = sched.wire_elem_bytes(self.wire_dtype, param_dtype)
        # compressed wires ship (value, int32 index) pairs of the top-k /
        # random-k per-node payload instead of the dense parameter row
        payload_elems = (payload_elem_count(proto, self.compression,
                                            node_stacked=False)
                         if self.compression is not None else None)
        index_bytes = 4 if self.compression is not None else 0
        comp_kind, comp_frac = (self.compression
                                if self.compression is not None
                                else ("none", 0.0))
        ledger = sched.CommLedger(n, meta={
            "topology": self.gossip_topo.name,
            "wire_dtype": self.wire_dtype,
            "param_count": int(nparams),
            "compression": comp_kind, "compression_frac": comp_frac,
            "gossip": schedule.gossip})
        if self._fed is None:
            self._fed = _SimFederation(self, result, idkd_cfg)
        else:
            self._fed.reset(result)
        fed = self._fed
        params, opt_state, key, captured = sched.run_schedule(
            schedule, fed, params, opt_state, key,
            topology=self.gossip_topo, ledger=ledger,
            param_count=int(nparams), elem_bytes=elem_bytes,
            payload_elems=payload_elems, index_bytes=index_bytes,
            resume_step=resume_step, capture_at=capture_at,
            telemetry=telemetry, resil=resil)

        result.final_acc = (result.acc_history[-1]
                            if result.acc_history else 0.0)
        steps_run = ledger.gossip_steps()
        result.comm_bytes_per_iter = (
            ledger.gossip_bytes / steps_run / n if steps_run else 0.0)
        result.label_bytes_total = ledger.label_bytes
        result.ledger = ledger.as_dict()
        result.captured = captured
        result.wall_seconds = time.time() - t0
        return result

    # ------------------------------------------------------------ IDKD round
    def _homogenize(self, params, idkd_cfg: IDKDConfig,
                    topology: Optional[Topology] = None,
                    active: Optional[np.ndarray] = None,
                    wire_fault=None) -> labeling.HomogenizedResult:
        # kd_mode="vanilla" is the no-OoD-filter baseline (every public
        # sample kept) — the engine's filter_ood=False branch
        filter_ood = self.kd_mode != "vanilla"
        topo = topology or self.topology
        streaming = (idkd_cfg.stream_labels
                     and idkd_cfg.label_backend != "dense")
        if wire_fault is not None and not wire_fault.is_noop():
            if self.driver_mode == "shard":
                raise ValueError(
                    "label-round fault injection is unsupported under "
                    "driver_mode='shard' — run fault schedules "
                    "node-stacked (DESIGN.md §12)")
            if streaming:
                # the streaming round never materializes the logits
                # stack to corrupt-and-validate, so both fault kinds
                # degrade to dropped payloads: merge the faulted senders
                # out of the gossip-weight averaging via the active mask
                from repro.obs import log
                n = self.tcfg.num_nodes
                lost = np.zeros(n, bool)
                lost[list(wire_fault.senders)] = True
                act = (np.ones(n, bool) if active is None
                       else np.asarray(active, bool)) & ~lost
                if not act.any():
                    raise RuntimeError("label-round fault leaves no "
                                       "valid label payloads")
                log.warning("label_payload_lost",
                            nodes=np.flatnonzero(lost).tolist())
                active = act
                wire_fault = None
        if self.driver_mode == "shard":
            if active is not None:
                raise ValueError("sharded label rounds have no churn "
                                 "path; run churn schedules node-stacked")
            if streaming:
                # scan inside the shard body: no device ever holds more
                # than its local chunk of logits (DESIGN.md §8)
                return labeling.shard_streaming_label_round(
                    self.model, params, jnp.asarray(self.public_x),
                    self._per_node_val_inputs(), topo, idkd_cfg,
                    mesh=self.node_mesh, filter_ood=filter_ood)
            # score/select shard-local, top-k-only exchange (DESIGN.md §7)
            return labeling.shard_label_round(
                self._node_logits(params, self.public_x),
                self._per_node_val_logits(params), topo, idkd_cfg,
                mesh=self.node_mesh, filter_ood=filter_ood)
        if streaming:
            # microbatched fused pass — the (n, P, C) stack never exists
            return labeling.streaming_label_round(
                self.model, params, jnp.asarray(self.public_x),
                self._per_node_val_inputs(), topo, idkd_cfg,
                filter_ood=filter_ood, active=active)
        # one-shot oracle paths (dense backend, or stream_labels=False):
        # cal_logits=None = D_C is the public set (paper's default)
        logits = self._node_logits(params, self.public_x)
        if wire_fault is not None and not wire_fault.is_noop():
            # label-round wire faults: a dropped payload is lost outright
            # and a corrupted one fails payload validation — both degrade
            # to "that node contributes no labels this round" by merging
            # it out of the gossip-weight averaging via the active mask
            from repro.obs import log
            from repro.resil.faults import (DEFAULT_MAX_ABS, corrupt_rows,
                                            payload_valid)
            n = self.tcfg.num_nodes
            lost = np.zeros(n, bool)
            lost[list(wire_fault.drop)] = True
            if wire_fault.corrupt:
                logits = corrupt_rows(logits, wire_fault.corrupt,
                                      wire_fault.mode)
                valid = np.asarray(payload_valid(
                    jnp.reshape(logits, (n, -1)), DEFAULT_MAX_ABS))
                lost |= ~valid
            act = (np.ones(n, bool) if active is None
                   else np.asarray(active, bool)) & ~lost
            if not act.any():
                raise RuntimeError(
                    "label-round fault leaves no valid label payloads")
            if lost.any():
                log.warning("label_payload_invalid",
                            nodes=np.flatnonzero(lost).tolist())
            active = act
        return labeling.label_round(
            logits,
            self._per_node_val_logits(params), None, topo, idkd_cfg,
            backend=idkd_cfg.label_backend, filter_ood=filter_ood,
            active=active)

    def _post_histograms(self, hom: labeling.HomogenizedResult) -> np.ndarray:
        C = self.mcfg.num_classes
        sparse_round = isinstance(hom, labeling.SparseHomogenizedSet)
        hists = []
        for i in range(self.tcfg.num_nodes):
            soft = (distill.SparseLabels(hom.labels.values[i],
                                         hom.labels.indices[i])
                    if sparse_round else hom.labels[i])
            h = idkd.class_histogram(
                jnp.asarray(self.data.train_y[self.parts[i]]),
                soft, hom.weights[i], C)
            hists.append(np.asarray(h))
        return np.stack(hists)

    # ------------------------------------------------------------------ eval
    def _eval(self, params, batch: int = 256):
        """Deterministic test-set sweep: contiguous batches, each sample
        counted at most once (the seed's ``(b*B) % len`` wraparound could
        short-batch and double-count, adding noise to every accuracy
        number). The last batch is zero-padded with a mask so the jitted
        eval keeps one shape; means are weighted by true sample count."""
        N = len(self.data.test_y)
        num_batches = min(self.eval_batches, -(-N // batch))
        tot_acc = tot_nll = tot_cnt = 0.0
        for b in range(num_batches):
            lo = b * batch
            hi = min(lo + batch, N)
            cnt = hi - lo
            xb = self.data.test_x[lo:hi]
            yb = self.data.test_y[lo:hi]
            if cnt < batch:
                pad = batch - cnt
                xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:],
                                                  xb.dtype)])
                yb = np.concatenate([yb, np.zeros((pad,), yb.dtype)])
            mask = np.zeros((batch,), np.float32)
            mask[:cnt] = 1.0
            a, l = self._consensus_eval(params, jnp.asarray(xb),
                                        jnp.asarray(yb), jnp.asarray(mask))
            tot_acc += float(a) * cnt
            tot_nll += float(l) * cnt
            tot_cnt += cnt
        acc, nll = tot_acc / tot_cnt, tot_nll / tot_cnt
        if not (np.isfinite(nll) and np.isfinite(acc)):
            # a diverged / guard-worthy model state: surface it loudly
            # instead of letting NaN accuracies ride the result silently
            from repro.obs import log
            log.warning("eval_nonfinite", acc=acc, nll=nll)
        return acc, nll

