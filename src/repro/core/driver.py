"""One on-device decentralized training driver.

Both consumers of the gossip step loop — the CPU accuracy simulator
(``core.simulator.DecentralizedSimulator``) and the LM launch path
(``launch.train.run_training``) — run on this engine instead of private
Python loops. Three pieces compose:

**Loss adapters + step factory.** :func:`make_step` builds the one
decentralized train step — per-node ``value_and_grad`` via ``vmap`` on
node-stacked params, then ``algo.step`` with an abstract gossip mixer —
parameterized by a *loss adapter* ``adapter(model) -> node_loss(params,
batch)``. Adapters exist for hard-CE classification, dense-KD, sparse-KD,
LM next-token, and LM next-token + sparse-KD; they are the only per-task
code. (The seed tree had five near-duplicate jitted step builders; they
are gone.)

**On-device sampling.** Per-node batch sampling runs under ``jit`` via
``jax.random`` over padded partition-index arrays (:class:`PaddedParts`,
a jit-friendly port of ``data.pipeline.NodeSampler`` /
``HomogenizedSampler``), and the private/public image-label merge that
the seed did with host-side ``np.where`` happens inside the jitted
sampler. One behavioural delta vs the host samplers: draws are always
with replacement (``jax.random.randint``), where the numpy samplers
switched to without-replacement for large partitions.

**Scan / host runners.** :func:`make_scan_runner` compiles the inner loop
as one ``lax.scan`` over a chunk of steps between eval boundaries — no
per-step Python dispatch or host↔device batch round-trips.
:func:`make_host_runner` drives the *same* jitted step + sampler from a
per-step Python loop; it exists as the dispatch-overhead baseline
(``benchmarks/bench_driver.py``) and the equivalence oracle
(``tests/test_driver.py``): both runners consume identical PRNG key
sequences, so their trajectories match to float tolerance.

**Sharded execution** (``driver_mode="shard"``, DESIGN.md §7).
:func:`make_shard_step` places the node axis on a
``jax.sharding.Mesh`` (``launch.mesh.make_node_mesh``) and runs the
per-node train step inside ``shard_map``: each device holds a
contiguous block of nodes, gossip is the ``ppermute`` mixer backend
(boundary-row collective-permutes on rings, ``psum`` exact averaging on
the complete graph), and the per-step loss is a ``psum`` mean. From the
outside the step has the node-stacked contract — same shapes, same
sampler, same PRNG sequence — so the scan runner drives it unchanged
and trajectories match the node-stacked runners to float tolerance
(``tests/test_shard.py``).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import IDKDConfig
from repro.core import distill

PyTree = Any
Batch = Dict[str, jax.Array]
NodeLoss = Callable[[PyTree, Batch], jax.Array]
LossAdapter = Callable[..., NodeLoss]
SampleFn = Callable[[jax.Array, jax.Array], Batch]

RUNNER_MODES = ("scan", "host", "auto", "shard")
NODE_AXIS = "node"


def resolve_runner_mode(mode: str, arch_type: str = "",
                        conv_backend: str = "lax") -> str:
    """``auto`` → the empirically fastest runner for the backend.

    On XLA:CPU, ``lax.conv`` inside ``while`` loops falls off the
    threaded fast path (~5× slower; measured in
    ``benchmarks/bench_driver.py``), so conv models keep the per-step
    host loop there — unless the model opts into the im2col conv path
    (``ModelConfig.conv_backend="im2col"``, plain matmuls with no conv
    pathology), which makes the scan/shard runners viable on CPU.
    Everything else — and every accelerator backend — gets the scan
    driver. ``"shard"`` is never picked automatically; it is an explicit
    opt-in.
    """
    if mode != "auto":
        return mode
    if arch_type == "cnn" and conv_backend != "im2col" \
            and jax.default_backend() == "cpu":
        return "host"
    return "scan"


# --------------------------------------------------------------- adapters
def classification_adapter(model) -> NodeLoss:
    """Weighted soft-CE on (soft or one-hot) labels — the plain phase."""
    def node_loss(params, batch):
        logits, _ = model.forward(params, {"images": batch["images"]})
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.sum(batch["labels"] * logp, axis=-1)
        w = batch["weights"]
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
    return node_loss


def dense_kd_adapter(temperature: float,
                     kd_weight: float = 1.0) -> LossAdapter:
    """Private rows: hard CE. Public rows: T²-scaled KD loss (the one
    distillation convention, ``distill.kd_loss`` — Hinton's T² factor
    keeps KD gradients comparable to the hard-CE gradients), scaled by
    ``IDKDConfig.kd_weight`` (the LM adapter always honoured it; the
    classification adapters silently dropped it)."""
    def adapter(model) -> NodeLoss:
        def node_loss(params, batch):
            logits, _ = model.forward(params, {"images": batch["images"]})
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            hard_nll = -jnp.sum(batch["labels"] * logp, axis=-1)
            kd = distill.kd_loss(logits, batch["labels"], temperature)
            nll = jnp.where(batch["is_pub"], kd_weight * kd, hard_nll)
            w = batch["weights"]
            return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
        return node_loss
    return adapter


def sparse_kd_adapter(temperature: float,
                      kd_weight: float = 1.0) -> LossAdapter:
    """dense_kd on top-k sparse labels, never densified: private rows
    carry their one-hot as a k=1 sparse label, so hard CE is the T=1
    sparse soft-CE on the same payload."""
    def adapter(model) -> NodeLoss:
        def node_loss(params, batch):
            logits, _ = model.forward(params, {"images": batch["images"]})
            sp = distill.SparseLabels(batch["values"], batch["indices"])
            hard_nll = distill.sparse_kd_loss(logits, sp, 1.0)
            kd = distill.sparse_kd_loss(logits, sp, temperature)
            nll = jnp.where(batch["is_pub"], kd_weight * kd, hard_nll)
            w = batch["weights"]
            return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
        return node_loss
    return adapter


def lm_adapter(model) -> NodeLoss:
    """Next-token LM loss. The whole batch goes to ``model.loss`` —
    frontend keys (VLM images, audio conditioning) ride along."""
    def node_loss(params, batch):
        loss, _ = model.loss(params, batch)
        return loss
    return node_loss


def lm_sparse_kd_adapter(idkd_cfg: IDKDConfig) -> LossAdapter:
    """LM next-token loss + sparse-KD on homogenized public batches.

    The KD term is ``distill.sparse_kd_loss`` — T²-scaled, the same
    convention as the classification adapters (the seed's LM step divided
    the T² back out, so the two drivers disagreed by a factor of T²).
    """
    def adapter(model) -> NodeLoss:
        def node_loss(params, batch):
            base, _ = model.loss(params, batch)
            logits, _ = model.forward(params, {"tokens": batch["pub_tokens"]})
            kd = distill.sparse_kd_loss(
                logits, distill.SparseLabels(batch["pub_vals"],
                                             batch["pub_idx"]),
                idkd_cfg.temperature)
            kd = jnp.sum(kd.mean(-1) * batch["pub_w"]) / \
                jnp.maximum(jnp.sum(batch["pub_w"]), 1.0)
            return base + idkd_cfg.kd_weight * kd
        return node_loss
    return adapter


# ----------------------------------------------------------- step factory
def make_step(model, algo, mixer, loss_adapter,
              telemetry: bool = False, guard=None) -> Callable:
    """The one decentralized train step.

    ``loss_adapter`` is either ``adapter(model) -> node_loss`` directly
    (``classification_adapter``, ``lm_adapter``) or the result of a
    parameterized factory (``dense_kd_adapter(T)`` etc.). Returns
    ``step(params, opt_state, batch, lr) -> (params, opt_state, loss)``
    on node-stacked pytrees, with ``step.init_opt = algo.init``.

    A *stateful* mixer (compressed / delayed / straggler gossip —
    ``mixing.make_mixer(..., compression=..., gossip=..., stale=...)``)
    changes the contract: the step carries the mixer's comm pytree
    (error-feedback residuals + last wire payloads) like the sampler
    ctx — ``step(params, opt_state, batch, lr, comm) -> (params,
    opt_state, loss, comm)``, flagged ``step.comm = True``, with
    ``step.init_comm = mixer.init_state`` building the initial state.

    ``telemetry=True`` adds the on-device metrics bus
    (:mod:`repro.obs.metrics`) as a trailing carry, after comm when both
    are present: ``step(..., metrics) -> (..., metrics)``, flagged
    ``step.metrics = True``. The metrics pytree accumulates per-node
    loss / grad norm / consensus distance (and, with a stateful mixer,
    the ‖x − x̂‖ EF residual via ``mixer.ef_ref``) with no host syncs.

    ``guard`` (a ``repro.resil.GuardSpec``) appends the on-device health
    guard (:mod:`repro.resil.guards`) as the last trailing carry, after
    comm and metrics: ``step(..., guard) -> (..., guard)``, flagged
    ``step.guard = True``. When the mixer carries fault injection its
    ``wire_check`` feeds per-sender wire invalidity into the guard.

    Trailing carries are always ordered (comm, metrics, guard). The
    metrics and guard updates touch nothing the training math reads, so
    telemetry-on / guard-on trajectories are bitwise-equal to the plain
    step.
    """
    node_loss = loss_adapter(model)
    grad_fn = jax.vmap(jax.value_and_grad(node_loss))
    if telemetry:
        from repro.obs import metrics as obs_metrics
    if guard is not None:
        from repro.resil import guards as resil_guards
    ef_fn = getattr(mixer, "ef_ref", None) if telemetry else None
    stateful = getattr(mixer, "stateful", False)
    wire_check = getattr(mixer, "wire_check", None)

    def step(params, opt_state, batch, lr, *rest):
        rest = list(rest)
        comm = rest.pop(0) if stateful else None
        metrics = rest.pop(0) if telemetry else None
        guard_state = rest.pop(0) if guard is not None else None
        # sender attribution must read the *pre-mix* payload: after the
        # mix, propagated corruption (validate_wire=False) has already
        # poisoned the victims' params, and checking those would flag
        # victim and offender in the same step — the strictly-later
        # invariant wire_offenders relies on only holds pre-mix
        wire_invalid = (wire_check(params)
                        if guard is not None and wire_check is not None
                        else None)
        losses, grads = grad_fn(params, batch)
        if stateful:
            bound = mixer.bind(comm)
            params, opt_state = algo.step(params, grads, opt_state, lr,
                                          bound)
            comm = bound.finalize()
        else:
            params, opt_state = algo.step(params, grads, opt_state, lr,
                                          mixer)
        out = [params, opt_state, jnp.mean(losses)]
        if stateful:
            out.append(comm)
        if telemetry:
            out.append(obs_metrics.update(
                metrics, losses, grads, params,
                ef_ref=(ef_fn(comm) if stateful and ef_fn is not None
                        else None)))
        if guard is not None:
            out.append(resil_guards.update(
                guard_state, guard, losses, grads, params,
                wire_invalid=wire_invalid))
        return tuple(out)

    step.comm = stateful
    step.metrics = telemetry
    step.guard = guard is not None
    if stateful:
        step.init_comm = mixer.init_state
    step.init_opt = algo.init
    return step


def make_shard_step(model, algo, loss_adapter, *, mesh, topology,
                    axis: str = NODE_AXIS, compression=None,
                    gossip: str = "sync", telemetry: bool = False,
                    guard=None) -> Callable:
    """The decentralized train step under ``shard_map`` over the mesh
    node axis — the ``driver_mode="shard"`` twin of :func:`make_step`.

    Node-stacked params / optimizer state / batches shard their leading
    node axis over ``mesh``'s ``axis`` (``launch.sharding.
    node_stacked_specs``); leaves without a node axis (e.g. D²'s scalar
    step counter) replicate. Inside the shard_map body each device runs
    ``vmap(value_and_grad)`` over its own block of nodes and gossips
    through the ``ppermute`` mixer backend — ring neighbours exchange
    boundary rows via ``lax.ppermute`` (complete graphs reduce via
    ``psum``), so the wire carries exactly the paper's peer-to-peer
    traffic, no all-reduce. The returned step keeps :func:`make_step`'s
    node-stacked contract (global shapes in, global shapes out, scalar
    mean loss), so the scan runner and samplers drive it unchanged and
    fixed-seed trajectories match the node-stacked runners to float
    tolerance.

    Eager validation (fail at build, not mid-schedule): the topology
    must be a ring or complete graph (others need the node-stacked
    ``gather``/``dense`` backends), the node count must be divisible by
    the mesh size, and per-edge-state algorithms (RelaySGD) are
    rejected. Churn / availability masks are unsupported under shard_map
    (DESIGN.md §7) — the scheduler raises before the run starts.

    ``compression`` / ``gossip="delayed"`` select the stateful
    compressed-wire ppermute backend (``mixing.
    make_compressed_ppermute_mixer`` — top-k payloads cross device
    boundaries as value+index pairs). The step then follows
    :func:`make_step`'s stateful contract (``step.comm``,
    ``step.init_comm``); the comm pytree shards its node axis like the
    params (``init_comm`` runs *outside* shard_map on global arrays —
    device_put its result with ``launch.sharding.federation_shardings``).

    **2-D federation mesh** (DESIGN.md §10): when ``mesh`` carries a
    non-trivial ``"model"`` axis (``launch.mesh.make_federation_mesh``),
    params / optimizer state / comm store FSDP-style model-axis shards
    (``launch.sharding.federation_specs``). The body all-gathers the
    model-sharded weight leaves back to full width for the forward /
    backward, slices the grads back to the local shard, and runs the
    algorithm update + gossip on the *sharded* trees — elementwise
    updates and the linear node-axis mix commute with the slicing, so
    the 2-D trajectory equals the 1-D shard run exactly. All gossip
    collectives stay on the node axis (model peers hold shards of the
    *same* replica); ``psum`` touches the model axis only for true
    replica-wide reductions (qg-dsgdm-n grad norms — see the mixer's
    ``reduce_tree_sum`` hook). Compressed gossip wraps the mixer in
    ``mixing.make_model_sharded_mixer`` so payload top-k still sees full
    delta rows.

    ``telemetry=True`` adds the on-device metrics-bus carry (see
    :func:`make_step`): per-node quantities are computed *inside* the
    shard_map body — the node mean for consensus is psum'd over the node
    axis, and on a 2-D mesh the per-leaf contributions of model-sharded
    leaves are additionally psum'd over the model axis (the same
    reduction split as ``reduce_tree_sum``). EF residuals are reported
    for 1-D compressed/delayed gossip and for the shard-native
    uncompressed state; the 2-D compressed mixer keeps full-width
    estimates against sharded params, so its ``ef_sq`` stays zero.

    ``guard`` (a ``repro.resil.GuardSpec``) appends the on-device health
    guard carry after metrics, sharded over the node axis like the
    metrics bus and following the same 2-D model-axis reduction split
    (wire fault injection has no shard path — ``validate_shard_schedule``
    rejects drop/corrupt faults — so ``wire_invalid`` stays zero here).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import mixing
    from repro.launch.sharding import (federation_specs, gather_model_tree,
                                       node_stacked_specs, slice_model_tree,
                                       spec_model_dim)

    n = topology.n
    size = mesh.shape[axis]
    model_axis = "model"
    model_size = dict(mesh.shape).get(model_axis, 1)
    if n % size != 0:
        raise ValueError(
            f"shard driver needs the node count ({n}) divisible by the "
            f"mesh {axis!r} axis ({size}); build the mesh with "
            "launch.mesh.make_federation_mesh")
    if getattr(algo, "needs_topology", False):
        raise ValueError(
            f"algorithm {algo.name!r} carries per-edge state and cannot "
            "run under shard_map; use the node-stacked runners "
            "(driver_mode='scan'/'host')")
    # rejects non-ring/non-full topologies eagerly, naming the fallback
    mixer = mixing.make_mixer(topology, backend="ppermute",
                              axis_names=(axis,), axis_sizes=(size,),
                              local_nodes=n // size,
                              compression=compression, gossip=gossip)

    node_loss = loss_adapter(model)
    grad_fn = jax.vmap(jax.value_and_grad(node_loss))

    def specs_of(tree):
        return federation_specs(tree, n, mesh, axis)

    def _leaf_model_dims(p_specs):
        return [spec_model_dim(s) for s in jax.tree.leaves(
            p_specs, is_leaf=lambda s: isinstance(s, P))]

    def _make_reduce(model_dims):
        # replica-wide tree-sum for qg-dsgdm-n's grad norm: model-sharded
        # leaf sums are partial (complete over "model" too); replicated
        # leaves appear on every model peer (node axis only, or they
        # would be counted model_size times)
        def reduce_tree_sum(sq):
            leaves = jax.tree.leaves(sq)
            sh = [v for v, d in zip(leaves, model_dims) if d is not None]
            rep = [v for v, d in zip(leaves, model_dims) if d is None]
            total = 0.0
            if sh:
                total = total + jax.lax.psum(sum(sh), (axis, model_axis))
            if rep:
                total = total + jax.lax.psum(sum(rep), (axis,))
            return total
        return reduce_tree_sum

    if telemetry:
        from repro.obs import metrics as obs_metrics
    if guard is not None:
        from repro.resil import guards as resil_guards

    if getattr(mixer, "stateful", False):
        def comm_step(params, opt_state, batch, lr, comm, *rest):
            rest = list(rest)
            metrics = rest.pop(0) if telemetry else None
            guard_state = rest.pop(0) if guard is not None else None
            p_specs = specs_of(params)
            model_dims = _leaf_model_dims(p_specs)
            step_mixer = mixer
            if model_size > 1 and compression is not None:
                # payload selection must see full delta rows (see
                # make_model_sharded_mixer); the uncompressed delayed
                # mixer is per-coordinate linear and runs shard-natively
                step_mixer = mixing.make_model_sharded_mixer(
                    mixer, model_dims, model_size, model_axis)
            ef_fn = (getattr(step_mixer, "ef_ref", None) if telemetry
                     else None)

            def comm_body(params, opt_state, batch, lr, comm, *m):
                full = (gather_model_tree(params, p_specs, model_axis)
                        if model_size > 1 else params)
                losses, grads = grad_fn(full, batch)
                if model_size > 1:
                    grads = slice_model_tree(grads, p_specs, model_size,
                                             model_axis)
                bound = step_mixer.bind(comm)
                if model_size > 1:
                    bound.reduce_tree_sum = _make_reduce(model_dims)
                params, opt_state = algo.step(params, grads, opt_state, lr,
                                              bound)
                comm = bound.finalize()
                loss = jax.lax.psum(jnp.sum(losses), axis) / n
                out = [params, opt_state, loss, comm]
                m = list(m)
                if metrics is not None:
                    out.append(obs_metrics.update(
                        m.pop(0), losses, grads, params,
                        ef_ref=ef_fn(comm) if ef_fn is not None else None,
                        axis_name=axis, num_nodes=n,
                        model_dims=(model_dims if model_size > 1 else None),
                        model_axis=model_axis))
                if guard_state is not None:
                    out.append(resil_guards.update(
                        m.pop(0), guard, losses, grads, params,
                        axis_name=axis, num_nodes=n,
                        model_dims=(model_dims if model_size > 1 else None),
                        model_axis=model_axis))
                return tuple(out)

            base_in = (p_specs, specs_of(opt_state),
                       node_stacked_specs(batch, n, axis), P(),
                       specs_of(comm))
            base_out = (p_specs, specs_of(opt_state), P(), specs_of(comm))
            extra_specs, extra_args = (), ()
            for carry in (metrics, guard_state):
                if carry is not None:
                    extra_specs += (node_stacked_specs(carry, n, axis),)
                    extra_args += (carry,)
            sharded = jax.shard_map(comm_body, mesh=mesh,
                                    in_specs=base_in + extra_specs,
                                    out_specs=base_out + extra_specs,
                                    check_vma=False)
            return sharded(params, opt_state, batch, lr, comm, *extra_args)

        comm_step.comm = True
        comm_step.metrics = telemetry
        comm_step.guard = guard is not None
        comm_step.init_comm = mixer.init_state
        comm_step.init_opt = algo.init
        return comm_step

    def step(params, opt_state, batch, lr, *rest):
        rest = list(rest)
        metrics = rest.pop(0) if telemetry else None
        guard_state = rest.pop(0) if guard is not None else None
        p_specs = specs_of(params)
        model_dims = _leaf_model_dims(p_specs)

        def body(params, opt_state, batch, lr, *m):
            full = (gather_model_tree(params, p_specs, model_axis)
                    if model_size > 1 else params)
            losses, grads = grad_fn(full, batch)
            if model_size > 1:
                grads = slice_model_tree(grads, p_specs, model_size,
                                         model_axis)
                mixer.reduce_tree_sum = _make_reduce(model_dims)
            params, opt_state = algo.step(params, grads, opt_state, lr,
                                          mixer)
            loss = jax.lax.psum(jnp.sum(losses), axis) / n
            out = [params, opt_state, loss]
            m = list(m)
            if metrics is not None:
                out.append(obs_metrics.update(
                    m.pop(0), losses, grads, params, axis_name=axis,
                    num_nodes=n,
                    model_dims=(model_dims if model_size > 1 else None),
                    model_axis=model_axis))
            if guard_state is not None:
                out.append(resil_guards.update(
                    m.pop(0), guard, losses, grads, params,
                    axis_name=axis, num_nodes=n,
                    model_dims=(model_dims if model_size > 1 else None),
                    model_axis=model_axis))
            return tuple(out)

        base_in = (p_specs, specs_of(opt_state),
                   node_stacked_specs(batch, n, axis), P())
        base_out = (p_specs, specs_of(opt_state), P())
        extra_specs, extra_args = (), ()
        for carry in (metrics, guard_state):
            if carry is not None:
                extra_specs += (node_stacked_specs(carry, n, axis),)
                extra_args += (carry,)
        sharded = jax.shard_map(body, mesh=mesh,
                                in_specs=base_in + extra_specs,
                                out_specs=base_out + extra_specs,
                                check_vma=False)
        return sharded(params, opt_state, batch, lr, *extra_args)

    step.metrics = telemetry
    step.guard = guard is not None
    step.init_opt = algo.init
    return step


def make_frozen_step(step_fn, active) -> Callable:
    """Churn wrapper: nodes with ``active[i] == False`` hold their params
    and node-stacked optimizer state — they neither train nor gossip
    (pair with a masked mixer, ``make_mixer(..., active=...)``, so the
    surviving nodes' Metropolis weights stay doubly stochastic). Leaves
    without a leading node axis (e.g. D²'s scalar step counter) pass
    through untouched. The per-step PRNG spend is unchanged — frozen
    nodes still draw (and discard) their batches — so a node rejoining
    later leaves every other node's trajectory byte-identical.
    """
    act = jnp.asarray(np.asarray(active, bool))
    n = act.shape[0]

    def select(new, old):
        if new.ndim >= 1 and new.shape[0] == n:
            return jnp.where(act.reshape((n,) + (1,) * (new.ndim - 1)),
                             new, old)
        return new

    # trailing carries pass through untouched: the stateful mixer's own
    # freshness mask (active & ~stale) already holds down nodes' comm
    # residuals and payloads, and the metrics bus keeps accumulating the
    # inner step's pre-freeze values (a frozen node's rows describe the
    # discarded hypothetical update — telemetry, not training state)
    def step(params, opt_state, batch, lr, *rest):
        out = step_fn(params, opt_state, batch, lr, *rest)
        return (jax.tree.map(select, out[0], params),
                jax.tree.map(select, out[1], opt_state)) + tuple(out[2:])

    step.comm = getattr(step_fn, "comm", False)
    step.metrics = getattr(step_fn, "metrics", False)
    step.guard = getattr(step_fn, "guard", False)
    if hasattr(step_fn, "init_comm"):
        step.init_comm = step_fn.init_comm
    step.init_opt = step_fn.init_opt
    return step


# ------------------------------------------------------ on-device sampling
class PaddedParts(NamedTuple):
    """Padded per-node partition indices, samplable under jit."""
    idx: jax.Array    # (n, Pmax) int32 — rows padded (padding never drawn)
    size: jax.Array   # (n,) int32 — true row lengths (may be 0)


def pad_partitions(parts: List[np.ndarray]) -> PaddedParts:
    n = len(parts)
    pmax = max(max((len(p) for p in parts), default=0), 1)
    idx = np.zeros((n, pmax), np.int32)
    size = np.zeros((n,), np.int32)
    for i, p in enumerate(parts):
        p = np.asarray(p, np.int64)
        idx[i, :len(p)] = p
        size[i] = len(p)
    return PaddedParts(jnp.asarray(idx), jnp.asarray(size))


def sample_partition(parts: PaddedParts, key, batch_size: int) -> jax.Array:
    """(n, B) global indices, node i drawn uniformly from its partition.
    Empty partitions yield index 0 — mask on ``parts.size > 0``."""
    keys = jax.random.split(key, parts.idx.shape[0])

    def one(k, row, size):
        r = jax.random.randint(k, (batch_size,), 0, jnp.maximum(size, 1))
        return row[r]

    return jax.vmap(one)(keys, parts.idx, parts.size)


def _bcast(mask, ndim: int):
    """Broadcast a (n, B) mask over trailing sample axes."""
    return mask.reshape(mask.shape + (1,) * (ndim - mask.ndim))


def _require_nonempty(parts: PaddedParts, what: str) -> None:
    """Private partitions must be non-empty: sample_partition would
    silently return index 0 for an empty row (the host samplers raised
    there). Empty *public* D_ID rows stay legal — ``is_pub`` masks them."""
    sizes = np.asarray(parts.size)
    if (sizes == 0).any():
        empty = np.flatnonzero(sizes == 0).tolist()
        raise ValueError(f"empty {what} partition for node(s) {empty}; "
                         "cannot sample a training batch from them")


def make_classification_sampler(parts: PaddedParts, train_x, train_y,
                                num_classes: int,
                                batch_size: int) -> SampleFn:
    """Plain-phase batches: private images + one-hot labels."""
    _require_nonempty(parts, "private")
    train_x = jnp.asarray(train_x)
    train_y = jnp.asarray(train_y)

    def sample(key, step) -> Batch:
        idx = sample_partition(parts, key, batch_size)
        return {"images": train_x[idx],
                "labels": jax.nn.one_hot(train_y[idx], num_classes,
                                         dtype=jnp.float32),
                "weights": jnp.ones(idx.shape, jnp.float32)}

    return sample


def homogenized_ctx(hom_weights, payload, capacity: int) -> Dict:
    """Round-varying KD sampler state as one pytree.

    The scheduler refreshes the :func:`make_homogenized_sampler` between
    chunks by passing a new ctx through the runner instead of rebuilding
    (and recompiling) the sampler: padded public partitions are sized to
    the fixed ``capacity`` (the public set size) so every round shares
    one compiled executable. Keys: ``pub_idx`` (n, capacity), ``pub_size``
    (n,), ``weights`` (n, P), and ``labels`` (dense) or
    ``values``/``indices`` (sparse top-k payload).
    """
    w = np.asarray(hom_weights, np.float32)
    n = w.shape[0]
    idx = np.zeros((n, max(capacity, 1)), np.int32)
    size = np.zeros((n,), np.int32)
    for i, row in enumerate(w):
        nz = np.flatnonzero(row > 0)
        idx[i, :len(nz)] = nz
        size[i] = len(nz)
    ctx = {"pub_idx": jnp.asarray(idx), "pub_size": jnp.asarray(size),
           "weights": jnp.asarray(w)}
    if isinstance(payload, (tuple, list, distill.SparseLabels)):
        ctx["values"] = jnp.asarray(payload[0])
        ctx["indices"] = jnp.asarray(payload[1])
    else:
        ctx["labels"] = jnp.asarray(payload)
    return ctx


def make_homogenized_sampler(priv_parts: PaddedParts, pub_parts: PaddedParts,
                             train_x, train_y, public_x, hom_weights,
                             payload, num_classes: int,
                             batch_size: int) -> SampleFn:
    """KD-phase batches from D_T^i ∪ D_ID (Algorithm 1 line 15), merged
    inside jit: each slot is public with probability |D_ID| / (|D_T| +
    |D_ID|); images, labels, and weights are ``jnp.where``-selected from
    the private or public source.

    ``payload`` is the post-round label payload: a dense (n, P, C) array,
    or a ``distill.SparseLabels`` / (values, indices) pair — sparse rides
    through un-densified, with private one-hots as k=1 sparse labels.

    ``sample(key, step, ctx=None)``: with ``ctx`` (see
    :func:`homogenized_ctx`) the round-varying state — D_ID membership,
    weights, label payload — is read from the passed pytree instead of
    the factory arguments, so repeated homogenization rounds reuse one
    compiled runner. The draws are identical either way: partition
    padding width never affects which indices are sampled.
    """
    _require_nonempty(priv_parts, "private")
    train_x = jnp.asarray(train_x)
    train_y = jnp.asarray(train_y)
    public_x = jnp.asarray(public_x)
    hom_weights = jnp.asarray(hom_weights, jnp.float32)
    n = hom_weights.shape[0]
    sparse = isinstance(payload, (tuple, list, distill.SparseLabels))
    if sparse:
        default_ctx = {"pub_idx": pub_parts.idx, "pub_size": pub_parts.size,
                       "weights": hom_weights,
                       "values": jnp.asarray(payload[0]),
                       "indices": jnp.asarray(payload[1])}
    else:
        default_ctx = {"pub_idx": pub_parts.idx, "pub_size": pub_parts.size,
                       "weights": hom_weights,
                       "labels": jnp.asarray(payload)}
    nidx = jnp.arange(n)[:, None]

    def sample(key, step, ctx=None) -> Batch:
        c = default_ctx if ctx is None else ctx
        pub_c = PaddedParts(c["pub_idx"], c["pub_size"])
        p_pub = c["pub_size"] / jnp.maximum(priv_parts.size + c["pub_size"],
                                            1)
        kp, kq, ku = jax.random.split(key, 3)
        priv = sample_partition(priv_parts, kp, batch_size)    # (n, B)
        pub = sample_partition(pub_c, kq, batch_size)
        u = jax.random.uniform(ku, priv.shape)
        is_pub = (u < p_pub[:, None]) & (c["pub_size"] > 0)[:, None]
        img_priv = train_x[priv]
        images = jnp.where(_bcast(is_pub, img_priv.ndim),
                           public_x[pub], img_priv)
        weights = jnp.where(is_pub, c["weights"][nidx, pub], 1.0
                            ).astype(jnp.float32)
        batch = {"images": images, "weights": weights, "is_pub": is_pub}
        if sparse:
            vals = c["values"][nidx, pub]                      # (n, B, k)
            cls = c["indices"][nidx, pub]
            pv = jnp.zeros_like(vals).at[..., 0].set(1.0)
            pi = jnp.zeros_like(cls).at[..., 0].set(
                train_y[priv].astype(cls.dtype))
            batch["values"] = jnp.where(is_pub[..., None], vals, pv)
            batch["indices"] = jnp.where(is_pub[..., None], cls, pi)
        else:
            lab_priv = jax.nn.one_hot(train_y[priv], num_classes,
                                      dtype=jnp.float32)
            batch["labels"] = jnp.where(is_pub[..., None],
                                        c["labels"][nidx, pub], lab_priv)
        return batch

    return sample


def make_lm_sampler(parts: PaddedParts, tokens, batch_size: int) -> SampleFn:
    """LM batches: (n, B, S) token/next-token pairs from per-node shards."""
    _require_nonempty(parts, "private")
    tokens = jnp.asarray(tokens)

    def sample(key, step) -> Batch:
        idx = sample_partition(parts, key, batch_size)
        seq = tokens[idx]                                      # (n, B, S+1)
        return {"tokens": seq[..., :-1], "labels": seq[..., 1:]}

    return sample


def lm_kd_ctx(pub_vals, pub_idx, pub_w) -> Dict:
    """Round-varying LM-KD sampler state (see :func:`make_lm_kd_sampler`):
    the sparse label payload + weights refreshed by each homogenization
    round, passed through the runner so one compiled executable serves
    every round."""
    return {"pub_vals": jnp.asarray(pub_vals),
            "pub_idx": jnp.asarray(pub_idx),
            "pub_w": jnp.asarray(pub_w, jnp.float32)}


def make_lm_kd_sampler(parts: PaddedParts, tokens, batch_size: int,
                       public_tokens, pub_vals, pub_idx, pub_w,
                       pub_batch: int) -> SampleFn:
    """LM batches + a per-node public sub-batch with its sparse payload.
    ``sample(key, step, ctx=None)`` — ``ctx`` (:func:`lm_kd_ctx`)
    overrides the factory payload for post-first-round refreshes."""
    base = make_lm_sampler(parts, tokens, batch_size)
    public_tokens = jnp.asarray(public_tokens)
    default_ctx = lm_kd_ctx(pub_vals, pub_idx, pub_w)
    n = default_ctx["pub_w"].shape[0]
    nidx = jnp.arange(n)[:, None]

    def sample(key, step, ctx=None) -> Batch:
        c = default_ctx if ctx is None else ctx
        k1, k2 = jax.random.split(key)
        batch = base(k1, step)
        pb = jax.random.randint(k2, (n, pub_batch), 0, len(public_tokens))
        batch["pub_tokens"] = public_tokens[pb]
        batch["pub_vals"] = c["pub_vals"][nidx, pb]
        batch["pub_idx"] = c["pub_idx"][nidx, pb]
        batch["pub_w"] = c["pub_w"][nidx, pb]
        return batch

    return sample


# ---------------------------------------------------------------- runners
def make_scan_runner(step_fn, sample_fn: SampleFn, lr_fn) -> Callable:
    """``run(params, opt_state, key, step0, num_steps, ctx=None)`` — the
    whole chunk of steps is one ``lax.scan`` under jit (sampling
    included): zero per-step dispatch. ``step0`` is traced (chunks at
    different offsets share one executable); ``num_steps`` is static (one
    compile per distinct chunk length); ``ctx`` is the round-varying
    sampler state (traced — the scheduler swaps label payloads between
    homogenization rounds without triggering a recompile).

    A comm-carrying step (``step_fn.comm`` — stateful compressed/delayed
    gossip) extends the contract to ``run(params, opt_state, key, step0,
    num_steps, ctx=None, comm=None) -> (params, opt_state, key, losses,
    comm)``: the mixer state rides the scan carry next to params, flagged
    ``run.comm = True``. A metrics-carrying step (``step_fn.metrics`` —
    the :mod:`repro.obs` metrics bus) appends ``metrics`` the same way
    (after comm when both are present), flagged ``run.metrics = True``;
    a guard-carrying step (``step_fn.guard`` — the
    :mod:`repro.resil.guards` health guard) appends ``guard`` last,
    flagged ``run.guard = True``. All carries ride one generic scan: jax
    treats ``None`` as an empty pytree, so absent carries cost nothing
    in the compiled program.
    """
    has_comm = getattr(step_fn, "comm", False)
    has_metrics = getattr(step_fn, "metrics", False)
    has_guard = getattr(step_fn, "guard", False)

    if has_comm or has_metrics or has_guard:
        @functools.partial(jax.jit, static_argnums=(4,))
        def aug_run(params, opt_state, key, step0, num_steps, ctx=None,
                    comm=None, metrics=None, guard=None):
            def body(carry, t):
                params, opt_state, key, comm, metrics, guard = carry
                key, sub = jax.random.split(key)
                batch = (sample_fn(sub, step0 + t) if ctx is None
                         else sample_fn(sub, step0 + t, ctx))
                args = (params, opt_state, batch, lr_fn(step0 + t))
                if has_comm:
                    args += (comm,)
                if has_metrics:
                    args += (metrics,)
                if has_guard:
                    args += (guard,)
                out = step_fn(*args)
                params, opt_state, loss = out[0], out[1], out[2]
                rest = list(out[3:])
                if has_comm:
                    comm = rest.pop(0)
                if has_metrics:
                    metrics = rest.pop(0)
                if has_guard:
                    guard = rest.pop(0)
                return (params, opt_state, key, comm, metrics, guard), loss

            (params, opt_state, key, comm, metrics, guard), losses = \
                jax.lax.scan(
                    body, (params, opt_state, key, comm, metrics, guard),
                    jnp.arange(num_steps))
            out = (params, opt_state, key, losses)
            if has_comm:
                out += (comm,)
            if has_metrics:
                out += (metrics,)
            if has_guard:
                out += (guard,)
            return out

        aug_run.comm = has_comm
        aug_run.metrics = has_metrics
        aug_run.guard = has_guard
        return aug_run

    @functools.partial(jax.jit, static_argnums=(4,))
    def run(params, opt_state, key, step0, num_steps, ctx=None):
        def body(carry, t):
            params, opt_state, key = carry
            key, sub = jax.random.split(key)
            batch = (sample_fn(sub, step0 + t) if ctx is None
                     else sample_fn(sub, step0 + t, ctx))
            params, opt_state, loss = step_fn(params, opt_state, batch,
                                              lr_fn(step0 + t))
            return (params, opt_state, key), loss

        (params, opt_state, key), losses = jax.lax.scan(
            body, (params, opt_state, key), jnp.arange(num_steps))
        return params, opt_state, key, losses

    return run


def make_host_runner(step_fn, sample_fn: SampleFn, lr_fn) -> Callable:
    """Same contract as :func:`make_scan_runner`, but a per-step Python
    loop around one jitted step — the dispatch-overhead baseline. Key
    handling matches the scan body exactly, so trajectories agree."""
    has_comm = getattr(step_fn, "comm", False)
    has_metrics = getattr(step_fn, "metrics", False)
    has_guard = getattr(step_fn, "guard", False)

    if has_comm or has_metrics or has_guard:
        @jax.jit
        def aug_one(params, opt_state, key, t, ctx=None, comm=None,
                    metrics=None, guard=None):
            key, sub = jax.random.split(key)
            batch = (sample_fn(sub, t) if ctx is None
                     else sample_fn(sub, t, ctx))
            args = (params, opt_state, batch, lr_fn(t))
            if has_comm:
                args += (comm,)
            if has_metrics:
                args += (metrics,)
            if has_guard:
                args += (guard,)
            out = step_fn(*args)
            params, opt_state, loss = out[0], out[1], out[2]
            rest = list(out[3:])
            if has_comm:
                comm = rest.pop(0)
            if has_metrics:
                metrics = rest.pop(0)
            if has_guard:
                guard = rest.pop(0)
            return params, opt_state, key, loss, comm, metrics, guard

        def aug_run(params, opt_state, key, step0, num_steps, ctx=None,
                    comm=None, metrics=None, guard=None):
            losses = []
            for t in range(num_steps):
                params, opt_state, key, loss, comm, metrics, guard = \
                    aug_one(params, opt_state, key,
                            jnp.asarray(step0 + t, jnp.int32), ctx, comm,
                            metrics, guard)
                losses.append(loss)
            out = (params, opt_state, key,
                   jnp.stack(losses) if losses
                   else jnp.zeros((0,), jnp.float32))
            if has_comm:
                out += (comm,)
            if has_metrics:
                out += (metrics,)
            if has_guard:
                out += (guard,)
            return out

        aug_run.comm = has_comm
        aug_run.metrics = has_metrics
        aug_run.guard = has_guard
        return aug_run

    @jax.jit
    def one(params, opt_state, key, t, ctx=None):
        key, sub = jax.random.split(key)
        batch = sample_fn(sub, t) if ctx is None else sample_fn(sub, t, ctx)
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          lr_fn(t))
        return params, opt_state, key, loss

    def run(params, opt_state, key, step0, num_steps, ctx=None):
        losses = []
        for t in range(num_steps):
            params, opt_state, key, loss = one(
                params, opt_state, key, jnp.asarray(step0 + t, jnp.int32),
                ctx)
            losses.append(loss)
        return (params, opt_state, key,
                jnp.stack(losses) if losses else jnp.zeros((0,), jnp.float32))

    return run


def make_runner(step_fn, sample_fn: SampleFn, lr_fn,
                mode: str = "scan", arch_type: str = "",
                conv_backend: str = "lax") -> Callable:
    """``mode="shard"`` expects a :func:`make_shard_step`-built step and
    drives it with the scan runner — sampling stays outside shard_map
    (replicated, identical PRNG math), the step reshards per its specs."""
    if mode not in RUNNER_MODES:
        raise ValueError(f"unknown driver mode {mode!r}; "
                         f"expected one of {RUNNER_MODES}")
    mode = resolve_runner_mode(mode, arch_type, conv_backend)
    maker = make_host_runner if mode == "host" else make_scan_runner
    return maker(step_fn, sample_fn, lr_fn)


def eval_boundaries(steps: int, eval_every: int,
                    extra: Optional[int] = None) -> List[Tuple[int, int]]:
    """Chunk [start, stop) spans between eval/homogenization boundaries.

    Chunks end right after each eval step (``s % eval_every == 0`` or the
    last step) and break *before* ``extra`` (the homogenization step), so
    the driver can swap samplers between chunks. Chunk lengths take only
    a few distinct values → a few scan compiles per run.
    """
    cuts = {0, steps}
    cuts |= {s + 1 for s in range(steps)
             if s % eval_every == 0 or s == steps - 1}
    if extra is not None and 0 <= extra < steps:
        cuts.add(extra)
    edges = sorted(cuts)
    return [(a, b) for a, b in zip(edges[:-1], edges[1:])]
