"""Compile rehearsals of the label-round kernels for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described v5e
topology, so a kernel the chip's compiler would refuse (rank-1 blocks,
unsupported gathers, scoped-VMEM overflow) fails here. Each test
compiles one kernel at the widths the trainer runs it at and checks that
the compiled program holds the Mosaic kernel (``tpu_custom_call``), not
an interpreted or jnp fallback.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold, so nothing
here touches it at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.head_select import BLOCK_C, head_row_tile, head_select
from repro.kernels.msp_select import msp_select

QWEN3_D, QWEN3_VOCAB = 2048, 151_936        # qwen3-1.7b published widths
RESNET_D, RESNET_C = 64, 10                 # resnet20-evonorm head


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for an absent chip cannot be read back from the
    # persistent cache; keep it out of the cache while these tests run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("raw_stats,C", [(False, QWEN3_VOCAB),
                                         (True, QWEN3_VOCAB // 2)])
def test_head_select_compiles_at_qwen3_widths(one_chip, raw_stats, C):
    """The LM label round's kernel: qwen3-1.7b hidden states against the
    full tied unembedding (finalize mode), and the per-shard half-vocab
    slice of the 2-D mesh's vocab-sharded round (raw_stats mode)."""
    h = _spec((2048, QWEN3_D), jnp.bfloat16, one_chip)
    w = _spec((QWEN3_D, C), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda h, w: head_select(h, w, temperature=10.0, k=8,
                                 interpret=False, raw_stats=raw_stats),
        h, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,k", [(8192, 8), (2048, 1)])
def test_head_select_compiles_at_label_round_shapes(one_chip, rows, k):
    """The qwen3-1.7b label round's two passes as the round runs them,
    two nodes under ``vmap``: 64 × 128 public positions with top-8 and
    16 × 128 calibration positions with top-1 against the full bf16
    head, at the row tile the kernel sizes from these shapes (256 rows
    per head read; 512 overflows the scoped VMEM)."""
    assert head_row_tile(rows, QWEN3_D, BLOCK_C, 8, jnp.bfloat16,
                         jnp.bfloat16) == 256
    h = _spec((2, rows, QWEN3_D), jnp.bfloat16, one_chip)
    w = _spec((2, QWEN3_D, QWEN3_VOCAB), jnp.bfloat16, one_chip)
    text = _compiled_text(
        jax.vmap(lambda h, w: head_select(h, w, temperature=10.0, k=k,
                                          interpret=False)), h, w)
    assert "tpu_custom_call" in text


def test_head_select_compiles_at_resnet_head(one_chip):
    """The simulator's label round: the ResNet-20 classifier head."""
    h = _spec((256, RESNET_D), jnp.float32, one_chip)
    w = _spec((RESNET_D, RESNET_C), jnp.float32, one_chip)
    b = _spec((RESNET_C,), jnp.float32, one_chip)
    text = _compiled_text(
        lambda h, w, b: head_select(h, w, b, temperature=10.0, k=8,
                                    interpret=False), h, w, b)
    assert "tpu_custom_call" in text


def test_msp_select_compiles_at_qwen3_vocab(one_chip):
    """The one-shot fused backend's kernel over full-vocabulary f32
    logit rows (the vocab axis is tiled to stay inside scoped VMEM)."""
    logits = _spec((256, QWEN3_VOCAB), jnp.float32, one_chip)
    text = _compiled_text(
        lambda x: msp_select(x, temperature=10.0, k=8, interpret=False),
        logits)
    assert "tpu_custom_call" in text
