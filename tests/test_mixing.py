import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mixing import (consensus_distance, make_dense_mixer,
                               make_gather_mixer, make_mixer,
                               make_roll_mixer)
from repro.core.topology import Topology
from repro.launch.steps import consensus_params, stack_params


def _stacked(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n, 4, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)}


def _tree_allclose(a, b, atol=1e-5):
    return all(np.allclose(np.asarray(x), np.asarray(y), atol=atol)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_dense_mixer_preserves_mean():
    n = 8
    W = Topology.make("ring", n).mixing_matrix()
    mix = make_dense_mixer(W)
    x = _stacked(n)
    y = mix(x)
    for k in x:
        assert np.allclose(np.asarray(y[k]).mean(0), np.asarray(x[k]).mean(0),
                           atol=1e-5)


def test_dense_mixer_reduces_consensus_distance():
    n = 8
    mix = make_dense_mixer(Topology.make("ring", n).mixing_matrix())
    x = _stacked(n)
    d0 = float(consensus_distance(x))
    d1 = float(consensus_distance(mix(x)))
    assert d1 < d0


def test_roll_mixer_equals_dense_ring_mixer():
    """The production roll/ppermute mixer must equal the dense MH ring W."""
    n = 8
    x = _stacked(n)
    roll_mix = make_roll_mixer(n)
    W = Topology.make("ring", n).mixing_matrix()  # ring: 1/3,1/3,1/3
    dense_mix = make_dense_mixer(W)
    assert _tree_allclose(roll_mix(x), dense_mix(x))


@pytest.mark.parametrize("n", [1, 2, 3])
def test_roll_mixer_small_n(n):
    x = _stacked(n)
    y = make_roll_mixer(n)(x)
    for k in x:
        assert np.allclose(np.asarray(y[k]).mean(0), np.asarray(x[k]).mean(0),
                           atol=1e-5)
    if n == 1:
        assert np.allclose(np.asarray(y["w"]), np.asarray(x["w"]))


# ----------------------------------------------------- make_mixer backends
@pytest.mark.parametrize("kind,n", [("ring", 8), ("torus", 9), ("full", 6),
                                    ("social", 15), ("chain", 5),
                                    ("exponential", 8)])
def test_gather_mixer_equals_dense(kind, n):
    """Neighbour-gather gossip == dense-W einsum on every topology."""
    topo = Topology.make(kind, n)
    x = _stacked(n, seed=n)
    dense = make_mixer(topo, backend="dense")(x)
    gather = make_mixer(topo, backend="gather")(x)
    assert _tree_allclose(dense, gather)


def test_roll_backend_matches_and_rejects_non_ring():
    topo = Topology.make("ring", 8)
    x = _stacked(8, seed=3)
    assert _tree_allclose(make_mixer(topo, backend="roll")(x),
                          make_mixer(topo, backend="dense")(x))
    with pytest.raises(ValueError, match="ring"):
        make_mixer(Topology.make("torus", 9), backend="roll")


def test_auto_backend_picks_roll_on_ring_gather_elsewhere(monkeypatch):
    ring, torus = Topology.make("ring", 6), Topology.make("torus", 9)
    xr, xt = _stacked(6, seed=1), _stacked(9, seed=2)
    assert _tree_allclose(make_mixer(ring)(xr),
                          make_mixer(ring, backend="dense")(xr))
    assert _tree_allclose(make_mixer(torus)(xt),
                          make_mixer(torus, backend="dense")(xt))
    # pin the *selection*, not just value equality (all backends agree
    # numerically, so a broken _is_ring would otherwise pass silently);
    # sentinels are functions because make_mixer tags its result with a
    # .remake handle
    from repro.core import mixing

    def roll_sentinel(tree):
        return "ROLL"

    def gather_sentinel(tree):
        return "GATHER"

    monkeypatch.setattr(mixing, "make_roll_mixer",
                        lambda n, wd="native": roll_sentinel)
    monkeypatch.setattr(mixing, "make_gather_mixer",
                        lambda t, wd="native", active=None: gather_sentinel)
    assert mixing.make_mixer(ring) is roll_sentinel
    assert mixing.make_mixer(torus) is gather_sentinel


def test_wire_dtype_native_close_to_f32_wire():
    """bf16 params: the native wire halves bytes; values stay close to the
    full-precision wire (f32 accumulate either way)."""
    topo = Topology.make("torus", 9)
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.normal(size=(9, 8, 4)), jnp.bfloat16)}
    y_native = make_gather_mixer(topo, wire_dtype="native")(x)
    y_f32 = make_gather_mixer(topo, wire_dtype="float32")(x)
    assert y_native["w"].dtype == jnp.bfloat16
    assert np.allclose(np.asarray(y_native["w"], np.float32),
                       np.asarray(y_f32["w"], np.float32), atol=0.1)


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown mixer backend"):
        make_mixer(Topology.make("ring", 4), backend="nope")


def test_ppermute_backend_rejects_non_ring_and_f32_wire():
    with pytest.raises(ValueError, match="ring"):
        make_mixer(Topology.make("torus", 9), backend="ppermute",
                   axis_names=("data",), axis_sizes=(9,))
    with pytest.raises(ValueError, match="wire_dtype"):
        make_mixer(Topology.make("ring", 4), backend="ppermute",
                   wire_dtype="float32",
                   axis_names=("data",), axis_sizes=(4,))


def test_ppermute_errors_name_the_fallback_backend():
    """Shard-mode misconfigurations must fail eagerly at make_mixer time
    with the node-stacked fallback named, not mid-schedule."""
    with pytest.raises(ValueError, match="gather"):
        make_mixer(Topology.make("torus", 9), backend="ppermute",
                   axis_names=("node",), axis_sizes=(9,))
    with pytest.raises(ValueError, match="gather"):
        make_mixer(Topology.make("ring", 4), backend="ppermute",
                   active=np.asarray([True, False, True, True]),
                   axis_names=("node",), axis_sizes=(4,))


def _shard_mix(mixer, tree, n_local):
    """Run a shard_map mixer on node-stacked data over however many
    devices divide the node axis (1 device → degenerate block mesh)."""
    from jax.sharding import Mesh, PartitionSpec as P
    n = jax.tree.leaves(tree)[0].shape[0]
    size = n // n_local
    mesh = Mesh(np.asarray(jax.devices()[:size]), ("node",))
    return jax.jit(jax.shard_map(mixer, mesh=mesh, in_specs=(P("node"),),
                                 out_specs=P("node"), check_vma=False))(tree)


@pytest.mark.parametrize("n", [2, 3, 8])
def test_block_ppermute_mixer_equals_dense_ring(n):
    """The block ppermute mixer (local node blocks, boundary rows via
    collective-permute) must equal the dense Metropolis ring mix —
    including the n == 2 half/half degenerate weights."""
    from repro.core.mixing import make_ppermute_mixer
    x = _stacked(n, seed=n)
    size = max(d for d in range(1, min(len(jax.devices()), n) + 1)
               if n % d == 0)
    mix = make_ppermute_mixer(("node",), (size,), local_nodes=n // size)
    out = _shard_mix(mix, x, n // size)
    ref = make_mixer(Topology.make("ring", n), backend="dense")(x)
    assert _tree_allclose(out, ref)


def test_psum_mixer_equals_dense_full():
    """Complete-graph shard gossip is one psum — must equal the full
    graph's (uniform 1/n) Metropolis einsum."""
    n = 6
    x = _stacked(n, seed=1)
    size = max(d for d in range(1, min(len(jax.devices()), n) + 1)
               if n % d == 0)
    mix = make_mixer(Topology.make("full", n), backend="ppermute",
                     axis_names=("node",), axis_sizes=(size,),
                     local_nodes=n // size)
    out = _shard_mix(mix, x, n // size)
    ref = make_mixer(Topology.make("full", n), backend="dense")(x)
    assert _tree_allclose(out, ref)


def test_every_backend_exposes_mix_leaf():
    """The per-leaf mixer protocol (mix.mix_leaf + tree.map equivalence)
    is what lets QG-DSGDm-N fuse the gossip mix into its whole-tree
    update pass — every backend must provide it."""
    topo = Topology.make("ring", 6)
    x = _stacked(6, seed=2)
    for backend in ("dense", "gather", "roll"):
        mix = make_mixer(topo, backend=backend)
        assert callable(mix.mix_leaf)
        leafwise = jax.tree.map(mix.mix_leaf, x)
        assert _tree_allclose(leafwise, mix(x))
    from repro.core.mixing import make_ppermute_mixer, make_psum_mixer
    assert callable(make_ppermute_mixer(("node",), (1,),
                                        local_nodes=6).mix_leaf)
    assert callable(make_psum_mixer("node", 6).mix_leaf)


def test_stack_and_consensus_roundtrip():
    p = {"a": jnp.ones((3, 2)), "b": jnp.arange(4.0)}
    s = stack_params(p, 5)
    assert s["a"].shape == (5, 3, 2)
    c = consensus_params(s)
    assert np.allclose(np.asarray(c["a"]), np.asarray(p["a"]))
