"""Model configuration covering every assigned architecture family.

One dataclass describes dense / GQA / MQA / MoE / Mamba / xLSTM / hybrid /
encoder-only / VLM+audio-backbone decoders.  Layers are described by a
repeating ``block_pattern`` of (mixer, mlp) pairs so heterogeneous stacks
(Jamba's 1:7 attention:mamba interleave, xLSTM's mLSTM/sLSTM mix,
DeepSeek-MoE's dense first layer) compile to a compact scan-over-periods HLO.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

# Mixer kinds (sequence-mixing sublayer).
ATTN = "attn"
MAMBA = "mamba"
MLSTM = "mlstm"
SLSTM = "slstm"

# MLP kinds (channel-mixing sublayer).
SWIGLU = "swiglu"
GEGLU = "geglu"
RELU2 = "relu2"  # squared-ReLU (Nemotron-4)
GELU = "gelu"    # plain 2-layer GELU MLP (HuBERT)
MOE = "moe"
NO_MLP = "none"  # xLSTM blocks carry their own projections

ROPE_NONE = "none"
ROPE = "rope"
MROPE = "mrope"  # Qwen2-VL multimodal 3D RoPE


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One layer position inside the repeating pattern."""
    mixer: str = ATTN
    mlp: str = SWIGLU


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling (arXiv:2309.00071; DeepSeek-V2's ``rope_scaling``
    of type "yarn"): the inverse frequencies blend the plain and the
    ``factor``-interpolated ones between the correction dims that
    ``beta_fast`` and ``beta_slow`` rotations give over
    ``original_max_position`` positions, and the softmax scale grows by
    ``mscale(factor, mscale_all_dim)`` squared where ``mscale`` equals
    ``mscale_all_dim`` (the cos/sin factor is then 1)."""
    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    arch_type: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""          # citation (arXiv id / model card)

    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0          # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 512

    # Repeating layer pattern; len must divide num_layers (after prefix).
    block_pattern: Tuple[BlockSpec, ...] = (BlockSpec(),)
    # Layers preceding the periodic body (e.g. DeepSeek-MoE dense layer 0).
    prefix_blocks: Tuple[BlockSpec, ...] = ()

    # Norm
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    norm_eps: float = 1e-5
    # Whether attention/mlp use parallel residual (not used by assigned archs)
    qk_norm: bool = False

    # Positional encoding
    rope: str = ROPE
    rope_theta: float = 10_000.0
    partial_rotary_factor: float = 1.0   # StableLM-2: 0.25, Nemotron: 0.5
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # t/h/w rotary halves

    # YaRN scaling of the rotary frequencies (None: plain RoPE)
    yarn: Optional[YaRN] = None

    # Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), on when
    # kv_lora_rank > 0: every attention layer caches one latent of
    # kv_lora_rank + qk_rope_head_dim values a position, shared by all
    # heads (models/mla.py), instead of per-head K/V
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # Attention
    causal: bool = True
    sliding_window: Optional[int] = None  # Mixtral: 4096
    attn_logit_softcap: Optional[float] = None

    # Kernel dispatch (kernels/dispatch.py): "xla" | "pallas" | "auto".
    # "auto" resolves to pallas on TPU and xla elsewhere; "pallas" off-TPU
    # runs the kernels in interpret mode (the parity-test configuration).
    backend: str = "auto"
    # Cache block (sequence slots per VMEM block) for the Pallas verify
    # kernel; 0 = kernel default (512).  Serving aligns its DecodeState
    # buffers to this so the kernel never repads per step.
    kernel_block_s: int = 0

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 2
    num_shared_experts: int = 0   # DeepSeek-MoE: 2
    moe_d_ff: int = 0             # expert width (DeepSeek fine-grained: 1408)
    router_aux_loss_coef: float = 0.01
    # scatter | dense (dense = oracle for tests) | gmm (dropless grouped
    # matmul over the experts held, models/moe.py)
    moe_impl: str = "scatter"
    capacity_factor: float = 2.0
    # top-k weights renormalised to sum to 1 (Mixtral, Jamba); DeepSeek-V2
    # keeps the softmax weights as they are
    norm_topk_prob: bool = True
    # The experts this layer holds: ``experts_held`` of the router's
    # ``num_experts``, from ``experts_offset`` on (0: all of them).  The
    # router keeps its width; the layer computes only its own experts'
    # part (one chip's share under expert parallelism).
    experts_held: int = 0
    experts_offset: int = 0

    # Mamba (Jamba)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0        # 0 -> ceil(d_model / 16)

    # xLSTM
    xlstm_mlstm_proj_factor: float = 2.0
    xlstm_slstm_proj_factor: float = 4.0 / 3.0
    xlstm_conv_kernel: int = 4

    # Embedding / head
    tie_embeddings: bool = False
    scale_embed: bool = False     # Gemma: x * sqrt(d_model)
    encoder_only: bool = False    # HuBERT: bidirectional, no decode path
    # Modality frontend stub: inputs are precomputed embeddings, not tokens.
    embedding_inputs: bool = False

    # Gemma-style GeGLU uses approximate tanh gelu
    gelu_approx: bool = True

    param_dtype: jnp.dtype = jnp.bfloat16
    compute_dtype: jnp.dtype = jnp.bfloat16

    # ---- derived ----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def rotary_dim(self) -> int:
        rd = int(self.resolved_head_dim * self.partial_rotary_factor)
        return rd - (rd % 2)

    @property
    def body_layers(self) -> int:
        return self.num_layers - len(self.prefix_blocks)

    @property
    def pattern_period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_periods(self) -> int:
        assert self.body_layers % self.pattern_period == 0, (
            f"{self.name}: body layers {self.body_layers} not divisible by "
            f"pattern period {self.pattern_period}")
        return self.body_layers // self.pattern_period

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff if self.moe_d_ff else self.d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held if self.experts_held else self.num_experts

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Values an MLA cache holds per position: the latent and the
        shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def resolved_dt_rank(self) -> int:
        return self.mamba_dt_rank if self.mamba_dt_rank else -(-self.d_model // 16)

    def validate(self) -> "ModelConfig":
        assert self.num_heads % self.num_kv_heads == 0, self.name
        assert self.backend in ("xla", "pallas", "auto"), self.backend
        _ = self.num_periods
        for b in tuple(self.prefix_blocks) + tuple(self.block_pattern):
            assert b.mixer in (ATTN, MAMBA, MLSTM, SLSTM), b
            assert b.mlp in (SWIGLU, GEGLU, RELU2, GELU, MOE, NO_MLP), b
            if b.mlp == MOE:
                assert self.num_experts > 0, self.name
        assert self.moe_impl in ("scatter", "dense", "gmm"), self.moe_impl
        assert 0 <= self.experts_offset and (
            self.experts_offset + self.held_experts <= self.num_experts), (
            self.name, self.experts_offset, self.held_experts)
        if self.held_experts != self.num_experts:
            # the capacity and oracle paths compute every expert
            assert self.moe_impl == "gmm", (self.name, self.moe_impl)
        if self.mla:
            assert self.qk_rope_head_dim > 0 and self.qk_rope_head_dim % 2 \
                == 0 and self.qk_nope_head_dim > 0 and self.v_head_dim > 0, \
                self.name
            assert self.rope == ROPE, (self.name, self.rope)
            assert self.sliding_window is None and not self.qk_norm, \
                self.name
        if self.encoder_only:
            assert not self.causal
        return self

    # Parameter count (for 6ND roofline math). Counts active params for MoE
    # when ``active_only`` is set.
    def param_count(self, active_only: bool = False) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        n = self.vocab_size * d  # input embed
        if not self.tie_embeddings:
            n += self.vocab_size * d
        total = n
        blocks = list(self.prefix_blocks)
        blocks += list(self.block_pattern) * self.num_periods
        for b in blocks:
            if b.mixer == ATTN and self.mla:
                H, r = self.num_heads, self.kv_lora_rank
                total += d * H * (self.qk_nope_head_dim
                                  + self.qk_rope_head_dim)          # q
                total += d * self.latent_dim + r                  # kv_a
                total += r * H * (self.qk_nope_head_dim + self.v_head_dim)
                total += H * self.v_head_dim * d                  # o
            elif b.mixer == ATTN:
                total += d * (self.num_heads * hd) * 2  # q, o
                total += d * (self.num_kv_heads * hd) * 2  # k, v
            elif b.mixer == MAMBA:
                di = self.mamba_d_inner
                total += d * di * 2  # in_proj (x and z)
                total += di * self.mamba_d_conv  # conv
                total += di * (self.resolved_dt_rank + 2 * self.mamba_d_state)
                total += self.resolved_dt_rank * di + di * self.mamba_d_state
                total += di * d  # out proj
            elif b.mixer == MLSTM:
                di = int(self.d_model * self.xlstm_mlstm_proj_factor)
                total += d * di * 2 + di * di * 3 + 3 * di + di * d
            elif b.mixer == SLSTM:
                total += 4 * d * d + d * int(self.d_model *
                                             self.xlstm_slstm_proj_factor) * 2
            if b.mlp in (SWIGLU, GEGLU):
                total += 3 * d * self.d_ff
            elif b.mlp in (RELU2, GELU):
                total += 2 * d * self.d_ff
            elif b.mlp == MOE:
                e_ff = self.expert_d_ff
                eff_experts = self.held_experts + self.num_shared_experts
                if active_only:
                    eff_experts = self.num_experts_per_tok + self.num_shared_experts
                total += eff_experts * 3 * d * e_ff
                total += d * self.num_experts  # router
        return total


def layer_blocks(cfg: ModelConfig) -> Tuple[BlockSpec, ...]:
    """Full per-layer block list (prefix + periodic body expanded)."""
    return tuple(cfg.prefix_blocks) + tuple(cfg.block_pattern) * cfg.num_periods
