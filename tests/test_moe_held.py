"""The held-expert expert layer (``MoEConfig.held_experts``) and its
``expert_ffn`` kernel against plain references, at small sizes on the
CPU (the kernel in Pallas interpret mode).

The model-level reference is the benchmark's plain Moonlight forward
(``bench/configs/moonlight-16b-a3b.reference.py``, which imports nothing
of the program), run here at a small width."""
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.expert_ffn import (dispatch_plan, expert_ffn,
                                     expert_ffn_ref, row_tile)
from repro.models import build_model
from repro.models.moe import _route, init_moe, moe_layer

ROOT = Path(__file__).resolve().parent.parent
KEY = jax.random.PRNGKey(0)


def _reference():
    import importlib.util
    import sys
    sys.path.insert(0, str(ROOT))
    path = ROOT / "bench" / "configs" / "moonlight-16b-a3b.reference.py"
    spec = importlib.util.spec_from_file_location("moonlight_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=(4, 4), experts=16, top_k=4):
    """Moonlight's architecture at small widths: d 128, 4 heads, latent
    32, 16 experts top-4 (4 held) of width 64 plus 2 shared, 1 dense + 3
    expert layers, vocab 256, float32."""
    cfg = get_config("moonlight-16b-a3b").replace(
        num_layers=4, d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
        vocab_size=256, dtype="float32")
    return cfg.replace(
        mla=dataclasses.replace(cfg.mla, kv_lora_rank=32,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16),
        moe=dataclasses.replace(cfg.moe, num_experts=experts,
                                num_experts_per_tok=top_k, moe_d_ff=64,
                                held_experts=held))


def ref_cfg(cfg):
    m, a = cfg.moe, cfg.mla
    return (("hidden_size", cfg.d_model),
            ("num_attention_heads", cfg.num_heads),
            ("kv_lora_rank", a.kv_lora_rank),
            ("qk_nope_head_dim", a.qk_nope_head_dim),
            ("qk_rope_head_dim", a.qk_rope_head_dim),
            ("v_head_dim", a.v_head_dim), ("rms_norm_eps", cfg.norm_eps),
            ("rope_theta", cfg.rope_theta),
            ("num_hidden_layers", cfg.num_layers),
            ("first_k_dense_replace", m.first_k_dense),
            ("n_routed_experts", m.held_range[1]),
            ("num_experts_per_tok", m.num_experts_per_tok),
            ("routed_scaling_factor", m.routed_scaling_factor),
            ("first_held_expert", m.held_range[0]))


def _random_bias(params, scale=0.05):
    moe = params["layers_1"]["moe"]
    moe["router_bias"] = scale * jax.random.normal(
        jax.random.PRNGKey(7), moe["router_bias"].shape)
    return params


def test_forward_matches_reference_and_counts():
    """The decoder's normal path (latent attention, one dense layer, then
    the scanned held-expert layers) against the plain reference's
    logits; its counters equal the held picks counted on the host."""
    cfg = tiny_config()
    model = build_model(cfg)
    params = _random_bias(model.init(KEY))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward(params, {"tokens": tokens})
        _, counts = model.forward_features(params, {"tokens": tokens})
    REF = _reference()
    stacked = jax.tree.map(lambda t: t[None], params)
    rc = ref_cfg(cfg)
    h = REF.hidden(stacked, 0, tokens,
                   stacked["layers_1"]["moe"]["router_bias"][0], cfg=rc)
    ref = jnp.einsum("bsd,dv->bsv", h, params["head"],
                     precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)
    # the held picks of every expert layer, from the reference's layers
    x = REF._embed(stacked["embed"], 0, tokens)
    x = REF.attention(x, stacked["layers_0"], 0, 0, cfg=rc, precision="f32")
    x = REF.dense_mlp(x, stacked["layers_0"], 0, 0, cfg=rc, precision="f32")
    held = 0
    moe = stacked["layers_1"]
    for li in range(3):
        x = REF.attention(x, moe, 0, li, cfg=rc, precision="f32")
        s = REF.router_scores(x, moe, 0, li, cfg=rc)
        _, ids = jax.lax.top_k(s + moe["moe"]["router_bias"][0, li], 4)
        held += int(((ids >= 4) & (ids < 8)).sum())
        x = REF.expert_layer(x, moe, 0, li, moe["moe"]["router_bias"][0, li],
                             cfg=rc, precision="f32")
    assert int(counts["held_assignments"]) == held


def _bank(H, d=256, f=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    wg = (jax.random.normal(ks[0], (H, d, f)) / d ** 0.5).astype(jnp.bfloat16)
    wi = (jax.random.normal(ks[1], (H, d, f)) / d ** 0.5).astype(jnp.bfloat16)
    wo = (jax.random.normal(ks[2], (H, f, d)) / f ** 0.5).astype(jnp.bfloat16)
    return wg, wi, wo


def _picks(kind, T, k, H, key):
    """Distinct experts per token, of 2H (values outside [0, H) are
    experts not held)."""
    if kind == "ragged":
        return jax.vmap(lambda kk: jax.random.permutation(
            kk, jnp.arange(-H // 2, H + H // 2))[:k])(jax.random.split(key, T))
    if kind == "empty_expert":            # held expert 1 is picked by none
        p = jax.vmap(lambda kk: jax.random.permutation(
            kk, jnp.array([0, 2, 3, 5, 6, 7])[:H + 2])[:k])(
                jax.random.split(key, T))
        return jnp.where(p >= H, p + 10, p)
    if kind == "all_on_one":              # every token on held expert 0
        rest = jax.vmap(lambda kk: jax.random.permutation(
            kk, jnp.arange(H, 3 * H))[:k - 1])(jax.random.split(key, T))
        return jnp.concatenate([jnp.zeros((T, 1), jnp.int32), rest], 1)
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["ragged", "empty_expert", "all_on_one"])
def test_expert_ffn_kernel_matches_ref(kind):
    """The kernel in interpret mode against the dense masked reference:
    ragged group sizes, a held expert no token picks, and every token on
    one held expert (dropless: the worst-case buffer holds them all)."""
    T, k, H = 96, 3, 4
    wg, wi, wo = _bank(H)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (T, 256)).astype(jnp.bfloat16)
    local = _picks(kind, T, k, H, ks[1])
    w = jax.random.uniform(ks[2], (T, k))
    base = jax.random.normal(ks[3], (T, 256)).astype(jnp.bfloat16)
    out, counts = expert_ffn(x, wg, wi, wo, local, w, base)
    ref, held = expert_ffn_ref(x, wg, wi, wo, local, w, base)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=0.03, rtol=0.02)
    assert int(counts["held_assignments"]) == int(held)
    sizes = np.bincount(np.asarray(local)[(np.asarray(local) >= 0)
                                          & (np.asarray(local) < H)],
                        minlength=H)
    if kind == "all_on_one":
        assert sizes[0] == T
    if kind == "empty_expert":
        assert sizes[1] == 0
    tm = row_tile(-(-T * min(k, H) // H))
    live = dispatch_plan(local, w, H, tm)[3]
    assert int(counts["expert_tiles"]) == int(live) == int(
        np.sum(-(-sizes // tm)))


def test_expert_ffn_under_vmap_is_per_node():
    """Two nodes under ``vmap`` (the label round's node axis becomes the
    kernel's leading grid axis) give each node its own answer."""
    T, k, H = 64, 3, 4
    wg, wi, wo = _bank(H, seed=1)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (2, T, 256)).astype(jnp.bfloat16)
    local = jnp.stack([_picks("ragged", T, k, H, kk)
                       for kk in jax.random.split(ks[1], 2)])
    w = jax.random.uniform(ks[2], (2, T, k))
    base = jnp.zeros((T, 256), jnp.bfloat16)
    out, counts = jax.vmap(
        lambda a, b, c: expert_ffn(a, wg, wi, wo, b, c, base))(x, local, w)
    for i in range(2):
        ref, held = expert_ffn_ref(x[i], wg, wi, wo, local[i], w[i], base)
        np.testing.assert_allclose(np.asarray(out[i], np.float32),
                                   np.asarray(ref), atol=0.03, rtol=0.02)
        assert int(counts["held_assignments"][i]) == int(held)


def _layer_ref(REF, x, params, cfg):
    """The reference's expert layer on ``x`` (unit norm scales): its
    routed and shared part, and the normalized input it acts on."""
    rc = ref_cfg(cfg)
    layers = {"ln2": {"scale": jnp.ones((1, 1, x.shape[-1]))},
              "moe": jax.tree.map(lambda t: t[None, None], params)}
    y = REF.expert_layer(x, layers, 0, 0, params["router_bias"], cfg=rc,
                         precision="f32") - x
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    return y, h


def test_share_sum_equals_uncut_layer():
    """The parts of a 16-expert layer that 4 chips give, each holding 4
    experts, with the shared experts counted once, add up to the uncut
    reference layer."""
    from repro.models.layers import apply_mlp
    full = tiny_config(held=(0, 16))
    params = init_moe(jax.random.PRNGKey(6), full, jnp.float32)
    params["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(8), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 128))
    want, h = _layer_ref(_reference(), x, params, full)
    with jax.default_matmul_precision("highest"):
        parts = [moe_layer(dict(params, experts=jax.tree.map(
            lambda t: t[first:first + 4], params["experts"])), h,
            tiny_config(held=(first, 4)))[0] for first in range(0, 16, 4)]
        shared = apply_mlp(params["shared"], h, full)
    total = sum(parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_bias_ranks_and_scaling_weighs():
    """The correction bias changes which experts are picked and not
    their weights, which are the unbiased sigmoid scores normalized over
    the top k and scaled by ``routed_scaling_factor``; the held part of
    the output scales with the factor."""
    cfg = tiny_config()
    params = init_moe(jax.random.PRNGKey(9), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(10), (64, 128))
    bias = jnp.zeros((16,)).at[5].set(10.0)       # expert 5 always picked
    ids0, w0, _ = _route(dict(params, router_bias=jnp.zeros((16,))), x, cfg)
    ids, w, _ = _route(dict(params, router_bias=bias), x, cfg)
    assert bool((ids == 5).any(-1).all())
    assert not bool((ids0 == 5).any(-1).all())
    scores = jax.nn.sigmoid(jnp.dot(x, params["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(scores, ids, -1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)
                                  * cfg.moe.routed_scaling_factor),
        rtol=1e-5)
    no_shared = dataclasses.replace(cfg.moe, num_shared_experts=0)
    outs = [moe_layer(params, x[None], cfg.replace(moe=dataclasses.replace(
        no_shared, routed_scaling_factor=f)))[0] for f in (1.0, 2.446)]
    np.testing.assert_allclose(np.asarray(outs[1]),
                               2.446 * np.asarray(outs[0]), rtol=2e-5,
                               atol=1e-6)


def test_dropless_under_total_imbalance():
    """A bias that sends every token's k picks to held experts: all T·k
    assignments are computed (none dropped), and the layer matches the
    reference."""
    cfg = tiny_config()
    params = init_moe(jax.random.PRNGKey(11), cfg, jnp.float32)
    params["router_bias"] = jnp.zeros((16,)).at[4:8].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 32, 128))
    want, h = _layer_ref(_reference(), x, params, cfg)
    with jax.default_matmul_precision("highest"):
        out, _, counts = moe_layer(params, h, cfg)
    assert int(counts["held_assignments"]) == 2 * 32 * 4
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
