"""Config system for the IDKD framework.

Two layers of configuration:

* :class:`ModelConfig` — a single composable description that can express
  every assigned architecture family (dense / MoE / SSM / hybrid / VLM /
  audio) plus the paper's own ResNet20-EvoNorm classifier.
* :class:`ShapeConfig` — one of the four assigned input shapes
  (train_4k / prefill_32k / decode_32k / long_500k).

Configs are plain frozen dataclasses so they hash, print, and diff cleanly;
``reduced()`` derives the CPU smoke-test variant of any full config
(≤2 layers, d_model ≤ 512, ≤4 experts) required by the assignment.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config (GShard-style top-k routing)."""

    num_experts: int = 0                # routed experts
    num_experts_per_tok: int = 0        # top-k
    moe_d_ff: int = 0                   # per-expert hidden width
    num_shared_experts: int = 0         # DeepSeek-style always-on experts
    dense_residual_ff: int = 0          # Arctic-style parallel dense MLP
    first_k_dense: int = 0              # leading dense layers (DeepSeek: 3)
    capacity_factor: float = 1.25
    router_type: str = "softmax"        # "softmax" | "sigmoid" (DeepSeek-v3)
    router_aux_coef: float = 0.01       # load-balance aux loss weight
    router_f32: bool = False            # router logits from float32
                                        # operands at 'highest' precision
                                        # (the DeepSeek-V3 gate)
    routed_scaling_factor: float = 1.0  # scales the normalized top-k
                                        # weights (DeepSeek-V3: 2.5,
                                        # Moonlight: 2.446)
    held_experts: Optional[Tuple[int, int]] = None
                                        # (first, count): the layer holds
                                        # routed experts [first, first +
                                        # count) of num_experts, routes over
                                        # all of them and computes, dropless,
                                        # only its own experts' part (expert
                                        # parallelism's share of one chip).
                                        # None: it holds every expert and
                                        # drops past capacity_factor
    dispatch_groups: int = 1            # §Perf: GShard-style local dispatch
                                        # groups (= data shards). A global
                                        # argsort is unshardable — GSPMD
                                        # all-gathers every token; per-group
                                        # sorting keeps dispatch local and
                                        # turns the traffic into all-to-alls

    @property
    def enabled(self) -> bool:
        return self.num_experts > 0

    @property
    def held_range(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this layer holds."""
        return self.held_experts or (0, self.num_experts)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3)."""

    q_lora_rank: int = 0                # 0 => full-rank q projection
    kv_lora_rank: int = 0               # 0 => MLA disabled
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def enabled(self) -> bool:
        return self.kv_lora_rank > 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) mixer sub-config."""

    state_size: int = 0                 # N (d_state)
    head_dim: int = 64                  # P
    expand: int = 2                     # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256               # SSD chunk length
    ngroups: int = 1                    # B/C groups (GVA-style)
    split_proj: bool = False            # §Perf: split the fused in-proj into
                                        # per-stream (z/x/B/C/dt) projections
                                        # so every output dim is individually
                                        # TP-shardable (no re-gather at the
                                        # fused-tensor split points)

    @property
    def enabled(self) -> bool:
        return self.state_size > 0


@dataclass(frozen=True)
class ModelConfig:
    """A composable decoder-stack description covering all assigned archs."""

    name: str = "model"
    arch_type: str = "dense"            # dense|moe|ssm|hybrid|vlm|audio|cnn
    source: str = ""                    # citation for the config numbers

    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0                   # 0 => d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024

    # attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0             # 0 => full attention
    global_attn_every: int = 0          # hybrid SWA: every k-th layer global
    prefix_lm_prefix: int = 0           # bidirectional prefix length (VLM)
    cross_attention: bool = False       # audio: cross-attend to conditioning
    cross_attn_len: int = 0             # conditioning sequence length

    # MLP
    mlp_type: str = "swiglu"            # swiglu | geglu | gelu
    norm_type: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-6
    norm_in_f32: bool = True            # §Perf knob: f32 norm math makes XLA
                                        # hoist the convert above the TP
                                        # all-reduce (f32 wire); False keeps
                                        # the wire in bf16

    # embeddings / heads
    tie_embeddings: bool = False
    num_codebooks: int = 0              # audio: parallel codebook streams
    num_prefix_tokens: int = 0          # VLM patch / Hymba meta tokens
    mtp_depth: int = 0                  # DeepSeek multi-token-prediction

    moe: MoEConfig = field(default_factory=MoEConfig)
    mla: MLAConfig = field(default_factory=MLAConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    hybrid_parallel: bool = False       # Hymba: attn ∥ SSM heads in-block

    # numerics / distribution
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing"       # what jax.checkpoint saves per layer:
                                        # "nothing" (recompute all — min HBM),
                                        # "dots" (save matmul outputs — avoids
                                        # recomputing TP all-reduces),
                                        # "everything" (remat as a no-op).
                                        # "full" is a legacy alias of
                                        # "nothing".
    scan_layers: bool = True
    node_scope: str = "replica"         # gossip node = data replica | "pod"
                                        # ("pod" for models too large to hold
                                        #  per-replica parameters)
    use_pallas: bool = False            # TPU path; CPU uses the jnp oracle
    attn_chunk: int = 512               # chunked-attention KV block

    # CNN (paper-faithful ResNet repro) ------------------------------------
    cnn_stages: Tuple[int, ...] = ()    # blocks per stage, e.g. (3,3,3)
    cnn_width: int = 16
    image_size: int = 32
    image_channels: int = 3
    num_classes: int = 10
    conv_backend: str = "lax"           # "lax" | "im2col". im2col lowers
                                        # convs to patch-gather + matmul,
                                        # dodging the XLA:CPU conv
                                        # pathologies (vmapped kernels ~4x,
                                        # conv-in-while ~5x — DESIGN.md §5)
                                        # so conv models can opt into the
                                        # scan/shard runners on CPU

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """True when decode state is sub-linear in context (long_500k ok)."""
        return self.ssm.enabled or self.sliding_window > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: ≤2 layers, d_model ≤ 512, ≤4 experts."""
        d_model = min(self.d_model, 256)
        heads = min(self.num_heads, 4)
        kv = max(1, min(self.num_kv_heads, heads))
        head_dim = min(self.resolved_head_dim, 64)
        moe = self.moe
        if moe.enabled:
            moe = dataclasses.replace(
                moe,
                num_experts=min(moe.num_experts, 4),
                num_experts_per_tok=min(moe.num_experts_per_tok, 2),
                moe_d_ff=min(moe.moe_d_ff, 128),
                num_shared_experts=min(moe.num_shared_experts, 1),
                dense_residual_ff=min(moe.dense_residual_ff, 128),
                first_k_dense=min(moe.first_k_dense, 1),
                held_experts=(None if moe.held_experts is None
                              else (0, min(moe.num_experts, 4))),
            )
        mla = self.mla
        if mla.enabled:
            mla = dataclasses.replace(
                mla, q_lora_rank=min(mla.q_lora_rank, 64),
                kv_lora_rank=min(mla.kv_lora_rank, 32),
                qk_nope_head_dim=min(mla.qk_nope_head_dim, 32),
                qk_rope_head_dim=min(mla.qk_rope_head_dim, 16),
                v_head_dim=min(mla.v_head_dim, 32))
        ssm = self.ssm
        if ssm.enabled:
            ssm = dataclasses.replace(
                ssm, state_size=min(ssm.state_size, 16),
                head_dim=min(ssm.head_dim, 16), chunk_size=32)
        return self.replace(
            num_layers=min(self.num_layers, 2),
            d_model=d_model,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            num_prefix_tokens=min(self.num_prefix_tokens, 8),
            cross_attn_len=min(self.cross_attn_len, 8),
            mtp_depth=min(self.mtp_depth, 1),
            moe=moe, mla=mla, ssm=ssm,
            cnn_stages=tuple(min(b, 1) for b in self.cnn_stages),
            cnn_width=min(self.cnn_width, 8),
            image_size=min(self.image_size, 8),
            attn_chunk=64,
            dtype="float32",
            remat=False,
        )

    def param_count(self) -> int:
        """Analytic parameter count (used for comm-cost + MODEL_FLOPS)."""
        if self.arch_type == "cnn":
            # rough resnet count: conv stacks + fc
            n = 3 * 3 * self.image_channels * self.cnn_width
            w = self.cnn_width
            for si, blocks in enumerate(self.cnn_stages):
                wo = self.cnn_width * (2 ** si)
                for b in range(blocks):
                    wi = w if b == 0 else wo
                    n += 9 * wi * wo + 9 * wo * wo
                    if wi != wo:
                        n += wi * wo
                w = wo
            n += w * self.num_classes
            return n
        d = self.d_model
        n = self.vocab_size * d  # embed
        if not self.tie_embeddings:
            n += self.vocab_size * d
        if self.num_codebooks:
            n += (self.num_codebooks - 1) * self.vocab_size * d  # extra heads+embeds
        per_layer = 0
        hd = self.resolved_head_dim
        if self.mla.enabled:
            m = self.mla
            qd = m.qk_nope_head_dim + m.qk_rope_head_dim
            if m.q_lora_rank:
                per_layer += d * m.q_lora_rank + m.q_lora_rank * self.num_heads * qd
            else:
                per_layer += d * self.num_heads * qd
            per_layer += d * (m.kv_lora_rank + m.qk_rope_head_dim)
            per_layer += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
            per_layer += self.num_heads * m.v_head_dim * d
        elif not self.is_attention_free:
            per_layer += d * hd * (self.num_heads + 2 * self.num_kv_heads)
            per_layer += self.num_heads * hd * d
        if self.ssm.enabled:
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            conv_dim = d_in + 2 * s.ngroups * s.state_size
            per_layer += d * (2 * d_in + 2 * s.ngroups * s.state_size + nheads)
            per_layer += conv_dim * s.conv_width
            per_layer += d_in * d + 2 * nheads
        if self.moe.enabled:
            m = self.moe
            moe_layers = self.num_layers - m.first_k_dense
            dense_layers = m.first_k_dense
            glu = 3 if self.mlp_type in ("swiglu", "geglu") else 2
            n += moe_layers * (
                m.num_experts * glu * d * m.moe_d_ff
                + m.num_shared_experts * glu * d * m.moe_d_ff
                + m.dense_residual_ff * glu * d
                + d * m.num_experts)
            n += dense_layers * glu * d * self.d_ff
            n += self.num_layers * per_layer
            return n
        glu = 3 if self.mlp_type in ("swiglu", "geglu") else 2
        if self.d_ff:
            per_layer += glu * d * self.d_ff
        return n + self.num_layers * per_layer

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top-k only)."""
        if not self.moe.enabled:
            return self.param_count()
        m = self.moe
        d = self.d_model
        glu = 3 if self.mlp_type in ("swiglu", "geglu") else 2
        total = self.param_count()
        routed_all = (self.num_layers - m.first_k_dense) * m.num_experts * glu * d * m.moe_d_ff
        routed_active = (self.num_layers - m.first_k_dense) * m.num_experts_per_tok * glu * d * m.moe_d_ff
        return total - routed_all + routed_active


@dataclass(frozen=True)
class ShapeConfig:
    """One of the four assigned input shapes."""

    name: str
    seq_len: int
    global_batch: int
    mode: str                  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class IDKDConfig:
    """Hyper-parameters of the paper's Algorithm 1."""

    temperature: float = 10.0       # best distillation temperature (paper §4.2)
    start_step: int = 0             # "local convergence" trigger
    every_k_steps: int = 100        # label-exchange period: rounds fire at
                                    # start_step + j*every_k_steps for
                                    # j < num_rounds (sched.idkd_round_steps)
    num_rounds: int = 1             # homogenization rounds in the schedule
                                    # (1 = the paper's single round at
                                    # start_step; the federation scheduler
                                    # re-labels every round)
    kd_weight: float = 1.0          # weight of soft-CE on D_ID (applied in
                                    # every KD adapter, cls and LM alike)
    label_topk: int = 0             # 0 => dense soft labels (paper);
                                    # >0 => top-k sparse (LLM-scale codec)
    detector: str = "msp"
    label_backend: str = "dense"    # labeling engine backend (DESIGN.md §2):
                                    # "dense" (jnp oracle) | "fused"
                                    # (msp_select kernel pass) | "sparse"
                                    # (top-k wire format end-to-end)
    stream_labels: bool = True      # sparse/fused label rounds stream the
                                    # public set in microbatches through the
                                    # fused head-select pass — peak memory
                                    # O(microbatch·C) + O(n·P·k), never the
                                    # (n, P, C) logit stack (DESIGN.md §8);
                                    # False = the one-shot oracle path
    stream_microbatch: int = 256    # public samples per streaming chunk
                                    # (the simulator's pre-streaming host
                                    # batching used the same 256)
    select_block_rows: int = 8      # row granule of the msp_select /
                                    # head_select kernels: msp_select's
                                    # row block; head_select sizes its
                                    # row tile (rows per read of the
                                    # head) from the shapes, as a
                                    # multiple of this


@dataclass(frozen=True)
class TrainConfig:
    """Decentralized training run description."""

    algorithm: str = "qg-dsgdm-n"   # dsgd|dsgdm|qg-dsgdm-n|relaysgd|d2|centralized
    topology: str = "ring"
    num_nodes: int = 16
    alpha: float = 0.1              # Dirichlet non-IID skew parameter
    lr: float = 0.5
    momentum: float = 0.9
    weight_decay: float = 1e-4
    batch_size: int = 32            # per-node
    steps: int = 300
    lr_decay_milestones: Tuple[float, float] = (0.6, 0.8)
    lr_decay_factor: float = 0.1
    seed: int = 4                   # paper seeds: 4, 34, 5
    idkd: Optional[IDKDConfig] = None

    # compressed / compute-overlapped gossip (DESIGN.md §9)
    compression: str = "none"       # none | topk | randk (sparsified wire
                                    # with per-node error feedback)
    compression_frac: float = 0.01  # kept fraction of each leaf's elements
    gossip: str = "sync"            # sync | delayed (one-step-stale mixing)

    @property
    def compression_spec(self):
        """The ``mixing.make_mixer``-ready spec: None, or (kind, frac)."""
        if self.compression in (None, "", "none"):
            return None
        return (self.compression, self.compression_frac)
