"""Streaming label rounds (DESIGN.md §8): head_select kernel parity,
streaming == one-shot equivalence, the no-dense-stack jaxpr audit, and
end-to-end trajectory equality of the streaming vs one-shot rounds.

* ``head_select`` (vocab-tiled fused select from hidden states) must
  match its jnp oracle in interpret mode — fixed shapes plus a
  hypothesis sweep over scales/temperatures/k, same style as
  ``tests/test_kernels_msp.py``.
* ``streaming_label_round`` must reproduce the one-shot fused backend
  of ``label_round`` to float tolerance — classifier (n, P, C) and LM
  (n, P, S, V) stacks, ring + complete graphs, including a public-set
  size that is *not* a multiple of the microbatch (ragged tail).
* The jaxpr of the streaming round must contain **no** intermediate
  shaped like the public logit stack — the audit walks every sub-jaxpr
  (scan bodies included) and is validated against the one-shot path,
  where the forbidden shape *is* present.
* Fixed-seed end-to-end trajectories (simulator and LM launch,
  node-stacked and shard drivers) with streaming rounds must match the
  ``stream_labels=False`` one-shot rounds to float tolerance.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # optional dev dep: shim keeps collection
    from hypothesis_shim import given, settings, st

from repro.configs.base import IDKDConfig, ModelConfig, TrainConfig
from repro.core import labeling
from repro.core.topology import Topology
from repro.kernels.head_select import head_select, head_select_ref
from repro.models import build_model

from jaxpr_audit import dense_stack_avals

N = 4


# ------------------------------------------------------ head_select kernel
def _check_head(h, w, b, T, k, det="msp", block_rows=4, block_c=64):
    conf, vals, idx = head_select(h, w, b, temperature=T, k=k,
                                  block_rows=block_rows, block_c=block_c,
                                  interpret=True, detector=det)
    cr, vr, ir = head_select_ref(h, w, b, temperature=T, k=k, detector=det)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)
    assert (np.asarray(idx) == np.asarray(ir)).all()


@pytest.mark.parametrize("rows,D,C,k,bc", [(16, 32, 200, 4, 64),
                                           (8, 16, 50, 8, 16),
                                           (24, 64, 1024, 8, 256)])
@pytest.mark.parametrize("T", [1.0, 10.0])
def test_head_select_matches_ref(rows, D, C, k, bc, T):
    """Vocab-tiled kernel == oracle, including ragged C (200 % 64 != 0)."""
    rng = np.random.default_rng(rows + C)
    h = jnp.asarray(rng.normal(size=(rows, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, C)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    _check_head(h, w, b, T, k, block_c=bc)


@pytest.mark.parametrize("det", ["msp", "energy"])
def test_head_select_detector_matches_ref(det):
    """Both OoD detectors fall out of the one online-softmax carry."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(8, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 96)) * 0.5, jnp.float32)
    _check_head(h, w, None, 5.0, 4, det=det, block_c=32)


def test_head_select_single_vocab_block():
    """block_c >= C degenerates to the unblocked msp_select dataflow."""
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 40)), jnp.float32)
    _check_head(h, w, None, 10.0, 4, block_c=512)


# ----------------------------------------- vocab-sharded stats + merge
def _merged_shards(h, w, b, S, T, k, det):
    """Emulate the 2-D label round's vocab sharding in pure numpy/jnp:
    pad W to S equal column shards (padded bias = NEG_INF so fake
    columns self-mask), per-shard raw stats, offset local indices to
    global, merge across shards."""
    from repro.kernels.head_select import (NEG_INF, head_select_stats_ref,
                                           merge_head_stats)
    C = w.shape[1]
    w_sh = -(-C // S)
    pad = S * w_sh - C
    wp = np.pad(np.asarray(w), ((0, 0), (0, pad)))
    bv = np.zeros(C, np.float32) if b is None else np.asarray(b)
    bp = np.pad(bv, (0, pad), constant_values=NEG_INF)
    k_loc = min(k, w_sh)
    ms, zs, tvs, tis = [], [], [], []
    for s in range(S):
        m, z, tv, ti = head_select_stats_ref(
            jnp.asarray(h), jnp.asarray(wp[:, s * w_sh:(s + 1) * w_sh]),
            jnp.asarray(bp[s * w_sh:(s + 1) * w_sh]), k=k_loc)
        ms.append(m)
        zs.append(z)
        tvs.append(tv)
        tis.append(ti + s * w_sh)
    return merge_head_stats(jnp.stack(ms), jnp.stack(zs), jnp.stack(tvs),
                            jnp.stack(tis), temperature=T, k=k,
                            detector=det)


@pytest.mark.parametrize("det", ["msp", "energy"])
@pytest.mark.parametrize("C,S,k", [(50, 4, 4),    # ragged: 50 % 4 != 0
                                   (64, 4, 8),    # exact split
                                   (10, 3, 8),    # k > shard width (k_loc=4)
                                   (96, 2, 1)])
def test_merge_head_stats_matches_unsharded_ref(det, C, S, k):
    """The cross-shard online-softmax merge == the unsharded oracle:
    same confidences, renormalized top-k payloads, and *global* vocab
    indices — including ragged vocab tails (C % S != 0, where padded
    columns must self-mask out of both z and the top-k) and shards
    narrower than k."""
    rng = np.random.default_rng(C * 7 + S)
    h = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, C)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    conf, vals, idx = _merged_shards(h, w, b, S, 5.0, k, det)
    cr, vr, ir = head_select_ref(h, w, b, temperature=5.0, k=k,
                                 detector=det)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ir))


def test_merge_head_stats_no_bias_matches_ref():
    """bias=None on the sharded path (zeros + NEG_INF padding) == the
    no-bias oracle."""
    rng = np.random.default_rng(42)
    h = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 50)), jnp.float32)
    conf, vals, idx = _merged_shards(h, w, None, 4, 10.0, 4, "msp")
    cr, vr, ir = head_select_ref(h, w, temperature=10.0, k=4)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ir))


def test_head_select_raw_stats_matches_stats_ref():
    """The kernel's raw_stats mode (what the vocab-sharded round feeds
    the merge on TPU) == the jnp stats oracle: pre-softmax m/z and raw
    top-k logits, not finalized payloads."""
    from repro.kernels.head_select import head_select_stats_ref
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 80)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(80,)), jnp.float32)
    m, z, tv, ti = head_select(h, w, b, temperature=7.0, k=4,
                               block_rows=4, block_c=32, interpret=True,
                               raw_stats=True)
    mr, zr, tvr, tir = head_select_stats_ref(h, w, b, k=4)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tv), np.asarray(tvr), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(tir))


@given(scale=st.floats(0.1, 4.0), T=st.floats(0.5, 20.0),
       k=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_head_select_property(scale, T, k):
    """Hypothesis sweep over scales/temperatures/k: kernel == oracle and
    payloads are sorted, renormalized convex weights."""
    rng = np.random.default_rng(int(scale * 100) + k)
    h = jnp.asarray(rng.normal(size=(8, 16)) * scale, jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 72)), jnp.float32)
    conf, vals, idx = head_select(h, w, temperature=T, k=k, block_rows=4,
                                  block_c=32, interpret=True)
    cr, vr, ir = head_select_ref(h, w, temperature=T, k=k)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)
    v = np.asarray(vals)
    assert (np.diff(v, axis=-1) <= 1e-6).all()
    np.testing.assert_allclose(v.sum(-1), 1.0, atol=1e-4)


# ------------------------------------------- head_select row tile
def _head_select_at(monkeypatch, h, w, b, *, budget, **kw):
    """``head_select_pallas`` (interpret mode) with its row tile sized
    against ``budget`` bytes of scoped VMEM."""
    from repro.kernels.head_select import kernel
    monkeypatch.setattr(kernel, "VMEM_BUDGET", budget)
    return kernel.head_select_pallas(h, w, b, temperature=5.0,
                                     interpret=True, **kw)


@pytest.mark.parametrize("N,D,C,bc,k,det,raw,tile,dtype", [
    (20, 32, 200, 64, 8, "msp", False, None, "float32"),    # N < R
    (40, 64, 300, 128, 1, "energy", True, 16, "bfloat16"),  # N % R != 0
    (100, 32, 1000, 128, 8, "energy", False, 32, "bfloat16"),
    (64, 16, 256, 64, 1, "msp", True, None, "float32"),     # C % bc == 0
    (44, 32, 90, 64, 8, "msp", True, 16, "float32"),
])
def test_head_select_row_tile_bitwise_equals_granule_tile(
        monkeypatch, N, D, C, bc, k, det, raw, tile, dtype):
    """Rows per head read sized from the shapes (``head_row_tile``; a
    ``tile`` sizes the budget so the chooser lands on it) give bitwise
    the outputs of one ``block_rows`` granule per head read: each row's
    scores, carry and finalizer are the same arithmetic whatever the
    tile, with rows padded to whole tiles and sliced back."""
    from repro.kernels.head_select import kernel
    rng = np.random.default_rng(N + C)
    h = jnp.asarray(rng.normal(size=(N, D)), dtype)
    w = jnp.asarray(rng.normal(size=(D, C)) * 0.3, dtype)
    b = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    kw = dict(k=k, block_rows=8, block_c=bc, detector=det, raw_stats=raw)
    budget = (kernel.VMEM_BUDGET if tile is None else
              kernel.head_vmem_bytes(tile, D, bc, h.dtype, w.dtype))
    monkeypatch.setattr(kernel, "VMEM_BUDGET", budget)
    assert kernel.head_row_tile(N, D, bc, 8, h.dtype, w.dtype) == (
        tile or -(-N // 8) * 8)
    shaped = _head_select_at(monkeypatch, h, w, b, budget=budget, **kw)
    granule = _head_select_at(monkeypatch, h, w, b, budget=0, **kw)
    assert len(shaped) == (4 if raw else 3)
    for got, want in zip(shaped, granule):
        assert got.shape == want.shape and got.shape[0] == N
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,D,bc,granule,dtype,want", [
    (8192, 2048, 512, 8, "bfloat16", 256),     # qwen3-1.7b public pass
    (2048, 2048, 512, 8, "bfloat16", 256),     # its calibration pass
    (8192, 4096, 512, 8, "bfloat16", 128),
    (300, 2048, 512, 8, "bfloat16", 152),      # 2 tiles, evened out
    (256, 64, 10, 8, "float32", 256),          # resnet head: one tile
    (4, 64, 10, 8, "float32", 8),              # fewer rows than a granule
    (8192, 65536, 512, 8, "float32", 8),       # nothing fits: the granule
])
def test_head_row_tile_fits_vmem_budget(rows, D, bc, granule, dtype, want):
    """The row tile is a multiple of the granule, fits the scoped-VMEM
    budget by its own footprint formula wherever more than a granule
    does, and is the largest power-of-two multiple that does (evened out
    over the tiles it implies)."""
    from repro.kernels.head_select import kernel
    tile = kernel.head_row_tile(rows, D, bc, granule, dtype, dtype)
    assert tile == want and tile % granule == 0
    if tile > granule:
        assert kernel.head_vmem_bytes(tile, D, bc, dtype, dtype) \
            <= kernel.VMEM_BUDGET
    assert 2 * tile >= rows or kernel.head_vmem_bytes(
        2 * tile, D, bc, dtype, dtype) > kernel.VMEM_BUDGET


# ------------------------------------------------- fixtures (tiny models)
@pytest.fixture(scope="module")
def cls_setup():
    rng = np.random.default_rng(0)
    mcfg = ModelConfig(arch_type="cnn", cnn_stages=(1,), cnn_width=8,
                       image_size=8, num_classes=10)
    model = build_model(mcfg)
    params = jax.vmap(model.init)(
        jax.random.split(jax.random.PRNGKey(0), N))
    P = 52                                 # not a multiple of microbatch 8
    pub = jnp.asarray(rng.normal(size=(P, 8, 8, 3)), jnp.float32)
    val = jnp.asarray(rng.normal(size=(N, 6, 8, 8, 3)), jnp.float32)
    return model, params, pub, val


@pytest.fixture(scope="module")
def lm_setup():
    rng = np.random.default_rng(1)
    mcfg = ModelConfig(arch_type="dense", num_layers=1, d_model=32,
                       num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                       dtype="float32", remat=False)
    model = build_model(mcfg)
    params = jax.vmap(model.init)(
        jax.random.split(jax.random.PRNGKey(1), N))
    pub = jnp.asarray(rng.integers(0, 64, size=(21, 6)), jnp.int32)
    val = jnp.asarray(rng.integers(0, 64, size=(N, 4, 6)), jnp.int32)
    return model, params, pub, val


def _one_shot(model, params, pub, val, topo, cfg, key=None):
    """The one-shot fused reference: full logit stacks into label_round."""
    fwd = jax.vmap(lambda p, x: model.forward(
        p, {model.input_key: x})[0])
    n = jax.tree.leaves(params)[0].shape[0]
    pub_b = jnp.broadcast_to(pub[None], (n,) + pub.shape)
    return labeling.label_round(fwd(params, pub_b), fwd(params, val),
                                None, topo, cfg, backend="fused")


def _assert_rounds_match(out, ref, C):
    assert isinstance(out, labeling.SparseHomogenizedSet)
    np.testing.assert_allclose(np.asarray(out.thresholds),
                               np.asarray(ref.thresholds), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out.id_masks),
                                  np.asarray(ref.id_masks))
    np.testing.assert_array_equal(np.asarray(out.weights),
                                  np.asarray(ref.weights))
    np.testing.assert_allclose(np.asarray(out.densify(C)),
                               np.asarray(ref.densify(C)), atol=1e-5)


# ------------------------------------------- streaming == one-shot rounds
@pytest.mark.parametrize("topo_kind", ["ring", "full"])
@pytest.mark.parametrize("mb", [8, 52, 64])
def test_streaming_matches_one_shot_classifier(cls_setup, topo_kind, mb):
    """(n, P, C) stacks: P=52 is ragged at mb=8 (6 full chunks + tail 4),
    exact at mb=52, single-chunk at mb=64 > P."""
    model, params, pub, val = cls_setup
    topo = Topology.make(topo_kind, N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=mb)
    ref = _one_shot(model, params, pub, val, topo, cfg)
    out = labeling.streaming_label_round(model, params, pub, val, topo, cfg)
    _assert_rounds_match(out, ref, 10)


@pytest.mark.parametrize("topo_kind", ["ring", "full"])
def test_streaming_matches_one_shot_lm(lm_setup, topo_kind):
    """(n, P, S, V) stacks: per-token payloads, sequence confidence =
    mean over S; P=21 is ragged at mb=8."""
    model, params, pub, val = lm_setup
    topo = Topology.make(topo_kind, N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8)
    ref = _one_shot(model, params, pub, val, topo, cfg)
    out = labeling.streaming_label_round(model, params, pub, val, topo, cfg)
    assert out.labels.values.shape[:3] == (N, 21, 6)
    _assert_rounds_match(out, ref, 64)


def test_streaming_detectors_and_vanilla(cls_setup):
    """Energy detector and the filter_ood=False baseline stream too."""
    model, params, pub, val = cls_setup
    topo = Topology.make("ring", N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8, detector="energy")
    ref = _one_shot(model, params, pub, val, topo,
                    IDKDConfig(label_topk=4, detector="energy"))
    out = labeling.streaming_label_round(model, params, pub, val, topo, cfg)
    _assert_rounds_match(out, ref, 10)
    out = labeling.streaming_label_round(model, params, pub, val, topo, cfg,
                                         filter_ood=False)
    assert np.asarray(out.id_masks).all()
    assert (np.asarray(out.thresholds) == 0.0).all()


def test_streaming_active_mask(cls_setup):
    """Churn: a down node contributes and receives nothing."""
    model, params, pub, val = cls_setup
    topo = Topology.make("ring", N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=16)
    active = np.array([True, False, True, True])
    out = labeling.streaming_label_round(model, params, pub, val, topo, cfg,
                                         active=active)
    assert not np.asarray(out.id_masks)[1].any()
    assert (np.asarray(out.weights)[1] == 0).all()


def test_shard_streaming_matches_stacked(cls_setup):
    """The shard twin (scan inside shard_map, top-k-only exchange) equals
    the node-stacked streaming round on any device count."""
    from repro.launch.mesh import make_node_mesh
    model, params, pub, val = cls_setup
    mesh = make_node_mesh(N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8)
    for topo_kind in ("ring", "full"):
        topo = Topology.make(topo_kind, N)
        ref = labeling.streaming_label_round(model, params, pub, val, topo,
                                             cfg)
        out = labeling.shard_streaming_label_round(
            model, params, pub, val, topo, cfg, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out.id_masks),
                                      np.asarray(ref.id_masks))
        np.testing.assert_allclose(np.asarray(out.thresholds),
                                   np.asarray(ref.thresholds), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(out.weights),
                                      np.asarray(ref.weights))
        np.testing.assert_allclose(np.asarray(out.densify(10)),
                                   np.asarray(ref.densify(10)), atol=1e-5)


@pytest.mark.parametrize("setup_name,C", [("cls_setup", 10),
                                          ("lm_setup", 64)])
def test_shard_streaming_2d_mesh_matches_stacked(request, setup_name, C):
    """The vocab-sharded round on the 2-D (node, model) mesh — per-shard
    head passes merged with the online-softmax streaming math — equals
    the node-stacked streaming round, classifier and LM stacks. C=10
    over model=2 shards ragged-free; vocab=64 splits exactly; both hit
    the NEG_INF-padded tail when the device pool forces model > C
    factors."""
    if len(jax.devices()) < 2:
        pytest.skip("model axis needs >= 2 devices")
    from repro.launch.mesh import make_federation_mesh
    model, params, pub, val = request.getfixturevalue(setup_name)
    mesh = make_federation_mesh(N, 2)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8)
    for topo_kind in ("ring", "full"):
        topo = Topology.make(topo_kind, N)
        ref = labeling.streaming_label_round(model, params, pub, val, topo,
                                             cfg)
        out = labeling.shard_streaming_label_round(
            model, params, pub, val, topo, cfg, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out.id_masks),
                                      np.asarray(ref.id_masks))
        np.testing.assert_allclose(np.asarray(out.thresholds),
                                   np.asarray(ref.thresholds), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(out.weights),
                                      np.asarray(ref.weights))
        np.testing.assert_allclose(np.asarray(out.densify(C)),
                                   np.asarray(ref.densify(C)), atol=1e-5)


# ------------------------------------------------------- name scopes
SCOPES = ("public_pass", "calibration_pass", "exchange")


@pytest.mark.parametrize("scope", SCOPES)
def test_round_phases_carry_their_name_scope(lm_setup, scope):
    """Each phase's operations carry its ``jax.named_scope`` in the op
    metadata of the lowered program, and no other phase's name."""
    model, params, pub, val = lm_setup
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8)
    topo = Topology.make("ring", N)
    P = pub.shape[0]

    def run_public(pr, pb):
        chunks, _, _ = labeling._chunk_public(pb, cfg.stream_microbatch)
        return labeling._stream_public(model, pr, chunks, P, cfg, 4)

    def run_calibration(pr, vl):
        return labeling._stream_val_conf(model, pr, vl, cfg)

    def run_exchange(conf, vals, idx):
        sparse = labeling.distill.SparseLabels(vals, idx)
        return labeling.exchange_sparse(topo, conf > 0.5, sparse)

    calls = {
        "public_pass": (run_public, (params, pub)),
        "calibration_pass": (run_calibration, (params, val)),
        "exchange": (run_exchange, (jnp.zeros((N, P)),
                                jnp.zeros((N, P, 6, 4)),
                                jnp.zeros((N, P, 6, 4), jnp.int32))),
    }
    fn, args = calls[scope]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    named = {part for loc in re.findall(r'loc\("([^"]*)"', text)
             for part in loc.split("/")}
    assert named & set(SCOPES) == {scope}


# --------------------------------------------------------- jaxpr audit
def test_streaming_jaxpr_has_no_dense_stack(cls_setup, lm_setup):
    """The shape audit: no (n, P, C)- or (n, P, S, V)-shaped intermediate
    anywhere in the streaming round's jaxpr — validated against the
    one-shot round, where the stack IS present."""
    topo = Topology.make("ring", N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8)
    for setup, C in ((cls_setup, 10), (lm_setup, 64)):
        model, params, pub, val = setup
        P = pub.shape[0]
        stream_jaxpr = jax.make_jaxpr(
            lambda pr, pb, vl: labeling.streaming_label_round(
                model, pr, pb, vl, topo, cfg))(params, pub, val)
        assert not dense_stack_avals(stream_jaxpr.jaxpr, P, C), \
            dense_stack_avals(stream_jaxpr.jaxpr, P, C)
        one_shot_jaxpr = jax.make_jaxpr(
            lambda pr, pb, vl: _one_shot(model, pr, pb, vl, topo, cfg))(
                params, pub, val)
        assert dense_stack_avals(one_shot_jaxpr.jaxpr, P, C), \
            "audit is blind: one-shot stack not detected"


def test_shard_streaming_jaxpr_has_no_dense_stack(cls_setup):
    """Same audit through shard_map: the scan inside the shard body
    keeps every logit intermediate at microbatch width."""
    from repro.launch.mesh import make_node_mesh
    model, params, pub, val = cls_setup
    topo = Topology.make("ring", N)
    cfg = IDKDConfig(label_topk=4, stream_microbatch=8)
    jx = jax.make_jaxpr(
        lambda pr, pb, vl: labeling.shard_streaming_label_round(
            model, pr, pb, vl, topo, cfg, mesh=make_node_mesh(N)))(
                params, pub, val)
    assert not dense_stack_avals(jx.jaxpr, pub.shape[0], 10)


# --------------------------------------- end-to-end trajectory equality
def _sim_result(stream: bool, driver_mode: str):
    from repro.configs.resnet20_cifar import SMALL_CONFIG
    from repro.core.simulator import DecentralizedSimulator
    from repro.data.synthetic import (make_classification_data,
                                      make_public_data)
    data = make_classification_data(image_size=8, n_train=256, n_val=64,
                                    n_test=128, noise=0.8, seed=0)
    pub = make_public_data(data, n_public=96, kind="aligned", seed=1)
    tcfg = TrainConfig(algorithm="qg-dsgdm-n", num_nodes=4, alpha=0.05,
                       steps=8, batch_size=8, lr=0.3, seed=4,
                       idkd=IDKDConfig(start_step=4, temperature=10.0,
                                       label_topk=4, label_backend="sparse",
                                       stream_labels=stream,
                                       stream_microbatch=40))  # 96 ragged
    mcfg = SMALL_CONFIG.replace(image_size=8, conv_backend="im2col")
    sim = DecentralizedSimulator(mcfg, tcfg, data, pub, kd_mode="idkd",
                                 eval_every=3, driver_mode=driver_mode)
    return sim.run()


@pytest.mark.parametrize("driver_mode", ["scan", "shard"])
def test_sim_trajectory_streaming_equals_one_shot(driver_mode):
    """Simulator end-to-end on fixed seeds: the streaming round and the
    one-shot round produce the same training trajectory, node-stacked
    and sharded."""
    stream = _sim_result(True, driver_mode)
    one_shot = _sim_result(False, driver_mode)
    np.testing.assert_allclose(stream.acc_history, one_shot.acc_history,
                               atol=1e-5)
    np.testing.assert_allclose(stream.loss_history, one_shot.loss_history,
                               atol=1e-4)
    np.testing.assert_allclose(stream.thresholds, one_shot.thresholds,
                               atol=1e-5)
    assert stream.label_bytes_total == one_shot.label_bytes_total


def _lm_history(stream: bool, driver_mode: str):
    from repro.configs import get_config
    from repro.launch.train import run_training
    cfg = get_config("qwen1.5-0.5b").reduced().replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, dtype="float32")
    tcfg = TrainConfig(num_nodes=2, steps=6, lr=0.1, alpha=0.1,
                       batch_size=4,
                       idkd=IDKDConfig(start_step=3, label_topk=4,
                                       kd_weight=0.3, stream_labels=stream,
                                       stream_microbatch=3))  # 8 ragged
    out = run_training(cfg, tcfg, seq_len=16, n_seqs=32, n_public=8,
                       use_idkd=True, log_every=2, verbose=False,
                       driver_mode=driver_mode)
    return out["loss_history"]


@pytest.mark.parametrize("driver_mode", ["scan", "shard"])
def test_lm_trajectory_streaming_equals_one_shot(driver_mode):
    """LM launch end-to-end on fixed seeds, node-stacked and sharded."""
    np.testing.assert_allclose(_lm_history(True, driver_mode),
                               _lm_history(False, driver_mode),
                               rtol=1e-4, atol=1e-5)
