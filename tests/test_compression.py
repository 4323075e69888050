"""Compressed / compute-overlapped gossip tests (DESIGN.md §9).

* spec plumbing: compression spec normalization and the payload byte
  math the ledger consumes (top-k 1% → ≥10× fewer gossip bytes);
* oracle equivalences: the stateful-but-uncompressed sync mixer is
  bitwise the plain backend; ``frac=1, γ=1`` top-k recovers the dense
  Metropolis mix; delayed gossip's step 0 mixes the exact init;
* error feedback: the ``x - x̂`` gap drains to zero on fixed params —
  every cut coordinate eventually crosses the wire;
* random-k: deterministic from a given comm state, keys advance;
* the bound-mixer recorder rejects double-mixing algorithms (gradient
  tracking) and never-mixing ones (RelaySGD) loudly;
* the shard_map twin reproduces node-stacked trajectories;
* end-to-end: top-k 1% LM run lands in the dense run's loss band at a
  fraction of the ledger bytes; delayed-vs-sync divergence is bounded;
  stale (straggler) churn keeps the node training while its neighbours
  mix its frozen payload and the ledger charges it nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sched
from repro.configs.base import TrainConfig
from repro.configs.resnet20_cifar import SMALL_CONFIG
from repro.core import driver, mixing
from repro.core.algorithms import make_algorithm
from repro.core.simulator import DecentralizedSimulator
from repro.core.topology import Topology
from repro.data.synthetic import make_classification_data


@pytest.fixture(scope="module")
def tiny_data():
    return make_classification_data(image_size=8, n_train=512, n_val=64,
                                    n_test=300, noise=0.8, seed=0)


@pytest.fixture(scope="module")
def mcfg():
    return SMALL_CONFIG.replace(image_size=8)


def _stacked(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n, 29)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)}


def _run_stateful(mix, tree, steps=1, comm=None):
    comm = mix.init_state(tree) if comm is None else comm
    x = tree
    for _ in range(steps):
        b = mix.bind(comm)
        x = b(x)
        comm = b.finalize()
    return x, comm


# ------------------------------------------------------------ spec + bytes
def test_normalize_compression_specs():
    assert mixing.normalize_compression(None) is None
    assert mixing.normalize_compression("none") is None
    assert mixing.normalize_compression(("none", 0.5)) is None
    assert mixing.normalize_compression("topk") == ("topk", 0.01)
    assert mixing.normalize_compression("topk:0.1") == ("topk", 0.1)
    assert mixing.normalize_compression(("randk", 0.05)) == ("randk", 0.05)
    with pytest.raises(ValueError, match="unknown compression kind"):
        mixing.normalize_compression("lz4")
    with pytest.raises(ValueError, match="fraction"):
        mixing.normalize_compression(("topk", 0.0))
    with pytest.raises(ValueError, match="fraction"):
        mixing.normalize_compression("topk:1.5")


def test_payload_byte_math():
    tree = _stacked(4)                       # per-node leaves: 29 + 5
    assert mixing.payload_elem_count(tree, None) == 34
    # top-k 1% keeps max(1, round(.01·size)) per leaf -> 1 + 1
    assert mixing.payload_elem_count(tree, ("topk", 0.01)) == 2
    # round() is banker's: k(29,.5)=14, k(5,.5)=2
    assert mixing.payload_elem_count(tree, ("topk", 0.5)) == 14 + 2
    single = {k: v[0] for k, v in tree.items()}
    assert mixing.payload_elem_count(single, ("topk", 0.01),
                                     node_stacked=False) == 2
    # ledger view: value+index pairs must still win ≥10× at 1% f32
    dense_bytes = 34 * 4
    comp_bytes = 2 * (4 + 4)
    assert dense_bytes / comp_bytes >= 8     # tiny leaves; real nets ~50×
    assert mixing.payload_k(100, 0.01) == 1
    assert mixing.payload_k(100, 1.0) == 100
    assert mixing.payload_k(3, 0.01) == 1    # never zero


# ---------------------------------------------------------------- oracles
def test_stateful_uncompressed_sync_is_plain_bitwise():
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    mix = mixing.make_mixer(topo, "roll", stateful=True)
    assert mix.stateful
    y, comm = _run_stateful(mix, tree)
    ref = mixing.make_mixer(topo, "roll")(tree)
    for a, b in zip(jax.tree.leaves(y), jax.tree.leaves(ref)):
        assert bool(jnp.array_equal(a, b))
    # prev snapshot advanced to the pre-mix params
    for p, t in zip(jax.tree.leaves(comm["prev"]), jax.tree.leaves(tree)):
        assert bool(jnp.array_equal(p, t))


def test_topk_full_fraction_recovers_dense_mix():
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    mix = mixing.make_mixer(topo, "dense", compression=("topk", 1.0))
    y, _ = _run_stateful(mix, tree)
    ref = mixing.make_mixer(topo, "dense")(tree)
    for a, b in zip(jax.tree.leaves(y), jax.tree.leaves(ref)):
        assert jnp.allclose(a, b, atol=1e-6)


def test_delayed_step0_mixes_exact_init():
    """x̂₀ = x₀, so the first delayed step equals the dense sync mix —
    staleness only sets in once estimates start lagging."""
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    mix = mixing.make_mixer(topo, "dense", compression=("topk", 0.2),
                            gossip="delayed")
    y, _ = _run_stateful(mix, tree)
    ref = mixing.make_mixer(topo, "dense")(tree)
    for a, b in zip(jax.tree.leaves(y), jax.tree.leaves(ref)):
        assert jnp.allclose(a, b, atol=1e-6)


def test_error_feedback_gap_drains():
    """Hold params fixed and keep gossiping: the shared estimates must
    converge to the params (implicit EF — cut coordinates stay in the
    gap and ride later deltas until everything crossed the wire)."""
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    mix = mixing.make_mixer(topo, "dense", compression=("topk", 0.1))
    comm = mix.init_state(tree)
    x = jax.tree.map(lambda t: t + 1.0, tree)     # move x off x̂
    gap0 = None
    for _ in range(30):
        b = mix.bind(comm)
        b(x)
        comm = b.finalize()
        gap = max(float(jnp.abs(jnp.asarray(t).reshape(4, -1) - h).max())
                  for t, h in zip(jax.tree.leaves(x),
                                  jax.tree.leaves(comm["hat"])))
        gap0 = gap if gap0 is None else gap0
    assert gap0 > 0.5            # the gap was real after one step
    assert gap < 1e-5            # and fully drained after 30


def test_randk_deterministic_and_key_advances():
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    mix = mixing.make_mixer(topo, "dense", compression=("randk", 0.3))
    comm = mix.init_state(tree)
    x = jax.tree.map(lambda t: t * 2.0, tree)    # nonzero x - x̂ deltas
    y1, c1 = _run_stateful(mix, x, comm=comm)
    y2, c2 = _run_stateful(mix, x, comm=comm)
    for a, b in zip(jax.tree.leaves(y1), jax.tree.leaves(y2)):
        assert bool(jnp.array_equal(a, b))
    assert not bool(jnp.array_equal(c1["key"], comm["key"]))
    # same estimates, advanced key -> a different random selection
    y3, _ = _run_stateful(mix, x, comm={**comm, "key": c1["key"]})
    assert not all(bool(jnp.array_equal(a, b)) for a, b in
                   zip(jax.tree.leaves(y1), jax.tree.leaves(y3)))


def test_unbound_stateful_mixer_rejects_direct_call():
    mix = mixing.make_mixer(Topology.make("ring", 4), "dense",
                            compression=("topk", 0.5))
    with pytest.raises(TypeError, match="bind"):
        mix(_stacked(4))


# ------------------------------------------------- incompatible algorithms
def test_recorder_rejects_double_and_missing_mixes():
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    mix = mixing.make_mixer(topo, "dense", compression=("topk", 0.5))
    comm = mix.init_state(tree)
    bound = mix.bind(comm)
    bound(tree)
    with pytest.raises(ValueError, match="more leaves"):
        bound.mix_leaf(jax.tree.leaves(tree)[0])
    partial = mix.bind(comm)
    partial.mix_leaf(jax.tree.leaves(tree)[0])
    with pytest.raises(ValueError, match="never mixed"):
        partial.finalize()


def test_gradient_tracking_rejected_with_compression(tiny_data, mcfg):
    """Gradient tracking mixes params AND trackers each step — two
    whole-tree mixes per bind — which the per-leaf wire state cannot
    express; the recorder must reject it at trace time."""
    from repro.models import build_model
    from repro.launch.steps import stack_params
    data = tiny_data
    model = build_model(mcfg)
    topo = Topology.make("ring", 4)
    mix = mixing.make_mixer(topo, "dense", compression=("topk", 0.1))
    algo = make_algorithm("gradient-tracking")
    step = driver.make_step(model, algo, mix, driver.classification_adapter)
    assert step.comm
    params = stack_params(model.init(jax.random.PRNGKey(0)), 4)
    comm = step.init_comm(params)
    batch = {"images": jnp.asarray(data.train_x[:32]).reshape(
                 (4, 8) + data.train_x.shape[1:]),
             "labels": jax.nn.one_hot(
                 jnp.asarray(data.train_y[:32]).reshape(4, 8),
                 mcfg.num_classes),
             "weights": jnp.ones((4, 8), jnp.float32)}
    with pytest.raises(ValueError, match="more leaves"):
        step(params, step.init_opt(params), batch,
             jnp.asarray(0.1, jnp.float32), comm)


# ----------------------------------------------------- shard_map twin
@pytest.mark.parametrize("topo_name,comp,gossip", [
    ("ring", ("topk", 0.2), "sync"),
    ("ring", ("topk", 0.2), "delayed"),
    ("ring", None, "delayed"),
    ("ring", ("randk", 0.3), "sync"),
    ("full", ("topk", 0.2), "sync"),
    ("full", ("topk", 0.2), "delayed"),
])
def test_shard_twin_matches_stacked(topo_name, comp, gossip):
    """The compressed ppermute mixer must reproduce the node-stacked
    compressed trajectory to float tolerance (same estimates, same
    payload selection) — over however many host devices divide the node
    axis (1 device → degenerate block mesh, same code path)."""
    from jax.sharding import Mesh
    from repro.launch.sharding import node_stacked_specs
    n = 4
    topo = Topology.make(topo_name, n)
    tree = _stacked(n, seed=3)
    ms = mixing.make_mixer(topo, "dense", compression=comp, gossip=gossip,
                           stateful=True)
    xs, _ = _run_stateful(ms, tree, steps=3)

    size = max(d for d in range(1, min(len(jax.devices()), n) + 1)
               if n % d == 0)
    mesh = Mesh(np.asarray(jax.devices()[:size]), ("node",))
    mp = mixing.make_mixer(topo, "ppermute", compression=comp,
                           gossip=gossip, stateful=True,
                           axis_names=("node",), axis_sizes=(size,),
                           local_nodes=n // size)
    comm = mp.init_state(tree)

    def body(x, c):
        b = mp.bind(c)
        y = b(x)
        return y, b.finalize()

    sx = node_stacked_specs(tree, n, "node")
    sc = node_stacked_specs(comm, n, "node")
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(sx, sc),
                              out_specs=(sx, sc), check_vma=False))
    xp = tree
    for _ in range(3):
        xp, comm = f(xp, comm)
    for a, b in zip(jax.tree.leaves(xs), jax.tree.leaves(xp)):
        assert jnp.allclose(a, b, atol=2e-5), float(jnp.abs(a - b).max())


# ----------------------------------------------------------- end to end
def _tiny_lm_cfg():
    from repro.configs import get_config
    return get_config("qwen1.5-0.5b").reduced().replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, dtype="float32")


def _lm_run(tcfg, **kw):
    from repro.launch.train import run_training
    return run_training(_tiny_lm_cfg(), tcfg, seq_len=16, n_seqs=64,
                        n_public=8, log_every=6, verbose=False, **kw)


def test_lm_topk_reduces_bytes_and_stays_in_band():
    """The acceptance A/B: top-k 1% on the ring LM config ships ≥10×
    fewer ledger gossip bytes than the dense f32 wire, with the
    fixed-seed final loss inside the dense run's noise band."""
    mk = lambda **kw: TrainConfig(                       # noqa: E731
        num_nodes=4, steps=12, lr=0.1, alpha=0.1, batch_size=4,
        topology="ring", seed=3, **kw)
    dense = _lm_run(mk())
    topk = _lm_run(mk(compression="topk", compression_frac=0.01))
    db = dense["ledger"]["gossip_bytes"]
    cb = topk["ledger"]["gossip_bytes"]
    assert db / cb >= 10.0, (db, cb)
    assert topk["ledger"]["meta"]["compression"] == "topk"
    assert topk["ledger"]["meta"]["compression_frac"] == 0.01
    l_dense = dense["loss_history"][-1]
    l_topk = topk["loss_history"][-1]
    assert np.isfinite(l_topk)
    assert abs(l_topk - l_dense) < 0.25, (l_dense, l_topk)


def test_lm_delayed_vs_sync_bounded_divergence():
    """One-step-stale gossip must track the sync trajectory: bounded
    loss divergence, same byte accounting, finite throughout (the sync
    path is the equivalence oracle — band, not bitwise)."""
    mk = lambda **kw: TrainConfig(                       # noqa: E731
        num_nodes=4, steps=12, lr=0.1, alpha=0.1, batch_size=4,
        topology="ring", seed=3, **kw)
    sync = _lm_run(mk())
    delayed = _lm_run(mk(gossip="delayed"))
    assert delayed["ledger"]["meta"]["gossip"] == "delayed"
    assert delayed["ledger"]["gossip_bytes"] == \
        sync["ledger"]["gossip_bytes"]
    l_sync = sync["loss_history"][-1]
    l_delayed = delayed["loss_history"][-1]
    assert np.isfinite(l_delayed)
    assert abs(l_delayed - l_sync) < 0.25, (l_sync, l_delayed)
    # params diverge but stay in a consensus ball
    d = mixing.consensus_distance(
        {"p": jnp.stack([jnp.ravel(jax.tree.leaves(sync["params"])[0]),
                         jnp.ravel(jax.tree.leaves(
                             delayed["params"])[0])])})
    assert float(d) < 1.0


def test_sim_schedule_gossip_mismatch_raises(tiny_data, mcfg):
    tcfg = TrainConfig(algorithm="dsgd", num_nodes=4, alpha=0.1, steps=6,
                       batch_size=8, lr=0.2, seed=7, gossip="delayed")
    sim = DecentralizedSimulator(mcfg, tcfg, tiny_data, None, kd_mode=None,
                                 eval_every=5)
    bad = sched.compile_schedule(tcfg.steps, 5)          # sync schedule
    with pytest.raises(ValueError, match="gossip"):
        sim.run(schedule=bad)
    r = sim.run()                                        # default agrees
    assert np.isfinite(r.loss_history).all()
    assert r.ledger["meta"]["gossip"] == "delayed"


def test_stale_straggler_end_to_end(tiny_data, mcfg):
    """mode="stale" churn: the straggler keeps *training* (unlike
    freeze), the run stays finite with neighbours consuming its frozen
    payload, and the ledger charges the stale sender zero bytes for the
    window."""
    tcfg = TrainConfig(algorithm="dsgd", num_nodes=4, alpha=0.1, steps=6,
                       batch_size=8, lr=0.3, seed=7,
                       compression="topk", compression_frac=0.1)

    def node2(mode):
        sim = DecentralizedSimulator(mcfg, tcfg, tiny_data, None,
                                     kd_mode=None, eval_every=5)
        schedule = sched.compile_schedule(
            tcfg.steps, 5, events=[sched.ChurnEvent(step=2, down=(2,),
                                                    mode=mode)])
        down = sim.run(schedule=schedule, capture_at=2)
        end = sim.run(schedule=schedule, capture_at=tcfg.steps)
        return (np.asarray(jax.tree.leaves(
                    down.captured["params"])[0][2], np.float32),
                np.asarray(jax.tree.leaves(
                    end.captured["params"])[0][2], np.float32),
                end)

    s_down, s_end, stale_run = node2("stale")
    assert not np.array_equal(s_down, s_end)     # the straggler trains
    assert np.isfinite(stale_run.acc_history).all()
    # the straggler ships nothing during its window, neighbours still do
    per_node = np.sum([row["gossip_per_node"]
                       for row in stale_run.ledger["per_round"]], axis=0)
    assert per_node[2] < per_node[1]
    f_down, f_end, _ = node2("freeze")
    assert np.array_equal(f_down, f_end)         # freeze really holds


def test_stale_payload_frozen_for_neighbours():
    """While a node is stale its x̂ row (the payload neighbours mix) must
    not move, and it must resume updating once the node is fresh again."""
    topo = Topology.make("ring", 4)
    tree = _stacked(4)
    stale = np.zeros(4, bool)
    stale[2] = True
    mix = mixing.make_mixer(topo, "dense", compression=("topk", 0.5),
                            stale=stale)
    comm = mix.init_state(tree)
    x = jax.tree.map(lambda t: t * 2.0, tree)
    _, c1 = _run_stateful(mix, x, comm=comm)
    h0 = jax.tree.leaves(comm["hat"])[0]
    h1 = jax.tree.leaves(c1["hat"])[0]
    assert bool(jnp.array_equal(h0[2], h1[2]))       # frozen payload
    assert not bool(jnp.array_equal(h0[0], h1[0]))   # fresh rows move
    # back to fresh: remake without the stale mask, row catches up
    fresh_mix = mix.remake()
    _, c2 = _run_stateful(fresh_mix, x, comm=c1)
    h2 = jax.tree.leaves(c2["hat"])[0]
    assert not bool(jnp.array_equal(h1[2], h2[2]))
