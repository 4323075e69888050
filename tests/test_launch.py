"""Launch-layer unit tests that need no devices: input specs, shape
support rules, config registry, param-count analytics, HLO parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCHS, ASSIGNED_ARCHS, LONG_CONTEXT_VARIANTS,
                           SHAPES, get_config, shape_supported)
from repro.launch import input_specs as ispec
from repro.launch.dryrun import collective_bytes
from repro.models import build_model


def test_registry_complete():
    assert len(ASSIGNED_ARCHS) == 10
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    for a in ASSIGNED_ARCHS:
        cfg = get_config(a)
        assert cfg.source, f"{a} missing source citation"


def test_long_context_support_rules():
    """long_500k runs for SSM/hybrid/sliding-window, skips pure full-attn."""
    runs = [a for a in ASSIGNED_ARCHS
            if shape_supported(get_config(a, shape="long_500k"),
                               SHAPES["long_500k"])]
    assert set(runs) == {"mamba2-780m", "hymba-1.5b", "mistral-nemo-12b"}
    # the mistral long-context variant is the sliding-window config
    assert LONG_CONTEXT_VARIANTS["mistral-nemo-12b"].sliding_window == 4096


def test_exact_assigned_configs():
    """Spot-check the assignment table numbers."""
    c = get_config("deepseek-v3-671b")
    assert (c.num_layers, c.d_model, c.num_heads) == (61, 7168, 128)
    assert (c.moe.num_experts, c.moe.num_experts_per_tok) == (256, 8)
    c = get_config("arctic-480b")
    assert (c.num_layers, c.moe.num_experts, c.moe.num_experts_per_tok) == \
        (35, 128, 2)
    c = get_config("hymba-1.5b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads) == \
        (32, 1600, 25, 5)
    c = get_config("mamba2-780m")
    assert c.ssm.state_size == 128 and c.is_attention_free
    c = get_config("paligemma-3b")
    assert c.num_kv_heads == 1 and c.vocab_size == 257_216


def test_param_counts_at_scale():
    """Analytic totals near the models' nameplate sizes."""
    approx = {
        "deepseek-v3-671b": (671e9, 0.10),
        "arctic-480b": (480e9, 0.15),
        "mistral-nemo-12b": (12e9, 0.15),
        "phi3-mini-3.8b": (3.8e9, 0.15),
        "qwen1.5-0.5b": (0.46e9, 0.25),
        "mamba2-780m": (0.78e9, 0.25),
    }
    for arch, (target, tol) in approx.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < tol, f"{arch}: {n:.3e}"


def test_train_specs_shapes():
    cfg = get_config("qwen3-1.7b")
    specs = ispec.train_specs(cfg, SHAPES["train_4k"], num_nodes=16)
    assert specs["tokens"].shape == (16, 16, 4096)
    assert specs["tokens"].dtype == jnp.int32
    cfg = get_config("musicgen-medium")
    specs = ispec.train_specs(cfg, SHAPES["train_4k"], num_nodes=16)
    assert specs["tokens"].shape == (16, 16, 4096, 4)
    assert specs["conditioning"].shape == (16, 16, 64, 1536)
    cfg = get_config("paligemma-3b")
    specs = ispec.train_specs(cfg, SHAPES["train_4k"], num_nodes=16)
    assert specs["patch_embeddings"].shape == (16, 16, 256, 2048)


def test_decode_specs_use_eval_shape_only():
    """decode_specs must not allocate: works on a reduced model and
    returns ShapeDtypeStructs for the full cache pytree."""
    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(cfg)
    tok, state, extras = ispec.decode_specs(cfg, SHAPES["decode_32k"], model)
    assert tok.shape == (128, 1)
    leaves = jax.tree.leaves(state)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    kv = state[0]["kv"]
    assert kv.k.shape[2] == 32_768          # (L, B, cap, KVH, hd)


def test_collective_parser_counts_while_loops():
    hlo = """
HloModule m

%cond (p: (s32[])) -> pred[] {
  %p = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(28)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0)
  %ar = bf16[128,256]{1,0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[]) tuple(%i)
}

ENTRY %main (a: bf16[2,2]) -> bf16[2,2] {
  %w = (s32[]) while(%init), condition=%cond, body=%body
  %cp = f32[64]{0} collective-permute(%a), source_target_pairs={{0,1}}
  ROOT %r = bf16[2,2] copy(%a)
}
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 256 * 2 * 28     # ×28 trip count
    assert out["collective-permute"] == 64 * 4          # entry: ×1


def test_reduced_variant_bounds():
    for a in ASSIGNED_ARCHS:
        r = get_config(a).reduced()
        assert r.num_layers <= 2
        assert r.d_model <= 512
        if r.moe.enabled:
            assert r.moe.num_experts <= 4


# ---------------------------------------------------------- compile cache
def test_compile_cache_defaults_to_checkout(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the entry points keep the cache
    in a fixed directory of the checkout."""
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        assert got == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_is_used(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the helper points nowhere else."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    checkout = Path(__file__).resolve().parents[1]
    default = checkout / ".jax_cache"
    before = sorted(default.iterdir()) if default.exists() else []
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu", PYTHONPATH=str(checkout / "src"))
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "d = enable_compile_cache()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(d, jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path / "cc")] * 2
    assert any((tmp_path / "cc").iterdir())
    after = sorted(default.iterdir()) if default.exists() else []
    assert after == before
