"""Pallas msp_select kernel vs oracle (interpret mode) + hypothesis sweep."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # optional dev dep: shim keeps collection
    from hypothesis_shim import given, settings, st


from repro.kernels.msp_select import msp_select, msp_select_ref


def _check(logits, T, k, block_n=4):
    conf, vals, idx = msp_select(logits, temperature=T, k=k,
                                 block_n=block_n, interpret=True)
    cr, vr, ir = msp_select_ref(logits, temperature=T, k=k)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)
    assert (np.asarray(idx) == np.asarray(ir)).all()


@pytest.mark.parametrize("N,C,k", [(16, 64, 4), (8, 1024, 8), (32, 257, 2),
                                   (8, 5000, 8)])   # ragged vocab tiles
@pytest.mark.parametrize("T", [1.0, 10.0])
def test_msp_select_matches_ref(N, C, k, T):
    logits = jnp.asarray(np.random.default_rng(N + C).normal(size=(N, C)) * 4,
                         jnp.float32)
    _check(logits, T, k)


@pytest.mark.parametrize("det", ["msp", "energy"])
def test_msp_select_detector_matches_ref(det):
    """Both OoD detectors come out of the kernel's one fused pass."""
    logits = jnp.asarray(np.random.default_rng(7).normal(size=(16, 96)) * 4,
                         jnp.float32)
    conf, vals, idx = msp_select(logits, temperature=10.0, k=4, block_n=4,
                                 interpret=True, detector=det)
    cr, vr, ir = msp_select_ref(logits, temperature=10.0, k=4, detector=det)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)


def test_msp_select_bf16_logits():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(8, 128)) * 4,
                         jnp.bfloat16)
    conf, vals, idx = msp_select(logits.astype(jnp.float32),
                                 temperature=10.0, k=4, block_n=4,
                                 interpret=True)
    assert conf.shape == (8,)


@given(scale=st.floats(0.1, 8.0), k=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_msp_select_property(scale, k):
    """Property sweep: values sorted desc, renormalized to 1."""
    logits = jnp.asarray(
        np.random.default_rng(int(scale * 100)).normal(size=(8, 96)) * scale,
        jnp.float32)
    conf, vals, idx = msp_select(logits, temperature=5.0, k=k, block_n=4,
                                 interpret=True)
    v = np.asarray(vals)
    assert (np.diff(v, axis=-1) <= 1e-6).all()          # descending
    np.testing.assert_allclose(v.sum(-1), 1.0, atol=1e-4)
    assert ((np.asarray(conf) > 0) & (np.asarray(conf) <= 1 + 1e-6)).all()
