"""Configuration system.

Every architecture is described by a single :class:`ModelConfig`. Configs are
registered by id (``--arch <id>``) in :mod:`repro.configs`. Input shapes are
described by :class:`InputShape` (the four assigned shapes live in
``repro.configs.shapes``). FL / FibecFed hyper-parameters live in
:class:`FibecFedConfig`, mirroring Table 8 of the paper.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm", "audio", "encoder")


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0  # the router's width: every expert a token may pick
    top_k: int = 1
    d_ff_expert: int = 0
    # Shared (always-on) expert, as in Llama-4; DeepSeek's n_shared_experts
    # run as one SwiGLU of their summed width.
    shared_expert: bool = False
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    # Tokens are routed within groups of this size, so the dispatch one-hot
    # tensor of the softmax router stays (groups, G, E, capacity).
    router_group_size: int = 512
    aux_loss_weight: float = 0.01
    # "softmax": top-k of a softmax, capacity-dropping dispatch, aux loss.
    # "sigmoid_bias" (DeepSeek-V3's noaux_tc): top-k of sigmoid scores plus a
    # frozen correction bias, weighted by the unbiased scores normalised over
    # the k, times ``routed_scale``; dropless, no aux loss.
    router: str = "softmax"
    routed_scale: float = 1.0
    # sigmoid_bias only: the experts this chip holds, 0..held_experts-1 of
    # num_experts (the chip's share of an expert-parallel split); None holds
    # them all. The absent experts' part of the result is left out.
    held_experts: Optional[int] = None
    # leading layers with a dense SwiGLU of width ModelConfig.d_ff in place
    # of the experts (DeepSeek's first_k_dense_replace)
    first_dense_layers: int = 0

    @property
    def held(self) -> int:
        return self.num_experts if self.held_experts is None else self.held_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): q from the hidden state
    (no q compression), keys and values expanded from a normed latent of
    ``kv_lora_rank``; each head's key is ``qk_nope_head_dim`` dims without
    RoPE plus ``qk_rope_head_dim`` rotary dims shared by all heads."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk_size: int = 128
    conv_width: int = 4

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # one of FAMILIES
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // num_heads

    # attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    rope: str = "full"  # "full" | "2d" (chatglm: rope on half the head dim) | "none"
    rope_theta: float = 10000.0
    attention_window: Optional[int] = None  # sliding-window size (None = full)
    parallel_residual: bool = False
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    mlp: str = "swiglu"  # "swiglu" | "gelu"
    logit_soft_cap: Optional[float] = None
    tie_embeddings: bool = False

    norm_eps: float = 1e-6  # every RMSNorm's epsilon

    # family-specific
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): one shared attention+mlp block applied every
    # `hybrid_period` SSM layers.
    hybrid_period: int = 6
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq_len: int = 1500  # stubbed conv/mel frame count
    # vlm / audio stub frontend
    num_prefix_embeddings: int = 0  # patch/frame embeddings prepended to text

    # encoder-only classification (RoBERTa, the paper's own model)
    num_classes: Optional[int] = None

    max_seq_len: int = 8192
    dtype: str = "bfloat16"

    # ---- performance-iteration knobs (§Perf; default = paper-faithful) ----
    remat: bool = False  # activation-checkpoint each layer (recompute in bwd)
    seq_parallel: bool = False  # sequence-parallel activation constraints
    attn_score_dtype: str = "float32"  # bf16 halves attention score traffic
    # uneven-E MoE (granite): replicate experts + shard token groups over the
    # model axis instead of within-expert tensor parallelism (§Perf B)
    moe_token_parallel: bool = False

    # LoRA
    lora_rank: int = 8
    lora_alpha: float = 16.0

    citation: str = ""

    def __post_init__(self):
        assert self.family in FAMILIES, self.family
        if self.family in ("moe",):
            assert self.moe is not None and self.moe.num_experts > 0
        if self.family in ("ssm", "hybrid"):
            assert self.ssm is not None
        if self.moe is not None and self.moe.router == "sigmoid_bias":
            assert 0 < self.moe.held <= self.moe.num_experts, self.moe
        if self.moe is not None:
            # the held-expert layer runs in the MLA decoder, the softmax
            # capacity layer in the GQA one
            assert (self.moe.router == "sigmoid_bias") == (self.mla is not None), self.moe.router

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """long_500k decodes need sub-quadratic attention (SSM/hybrid or SWA)."""
        return self.family in ("ssm", "hybrid") or self.attention_window is not None

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        small: Dict = dict(
            num_layers=2,
            d_model=min(self.d_model, 128),
            num_heads=min(self.num_heads, 4),
            num_kv_heads=min(self.num_kv_heads, min(self.num_heads, 4)),
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            head_dim=32,
            max_seq_len=256,
            encoder_layers=2 if self.encoder_layers else 0,
            encoder_seq_len=min(self.encoder_seq_len, 16),
            num_prefix_embeddings=min(self.num_prefix_embeddings, 8),
            hybrid_period=2,
            lora_rank=4,
            dtype="float32",
        )
        if self.moe is not None and self.moe.router == "sigmoid_bias":
            # 16 experts, the published share held (8 of 64 -> 2 of 16)
            E = min(self.moe.num_experts, 16)
            small["moe"] = dataclasses.replace(
                self.moe,
                num_experts=E,
                top_k=min(self.moe.top_k, 4),
                d_ff_expert=min(self.moe.d_ff_expert, 64),
                d_ff_shared=min(self.moe.d_ff_shared, 128) if self.moe.shared_expert else 0,
                held_experts=(
                    None if self.moe.held_experts is None
                    else max(1, self.moe.held_experts * E // self.moe.num_experts)
                ),
                first_dense_layers=min(self.moe.first_dense_layers, 1),
            )
        elif self.moe is not None:
            small["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=min(self.moe.d_ff_expert, 128),
                d_ff_shared=min(self.moe.d_ff_shared, 128) if self.moe.shared_expert else 0,
                router_group_size=64,
            )
        if self.mla is not None:
            small["mla"] = MLAConfig(
                kv_lora_rank=min(self.mla.kv_lora_rank, 32),
                qk_nope_head_dim=min(self.mla.qk_nope_head_dim, 16),
                qk_rope_head_dim=min(self.mla.qk_rope_head_dim, 8),
                v_head_dim=min(self.mla.v_head_dim, 16),
            )
        if self.ssm is not None:
            small["ssm"] = dataclasses.replace(
                self.ssm, d_state=min(self.ssm.d_state, 16), head_dim=32, chunk_size=32
            )
        if self.attention_window is not None:
            small["attention_window"] = 64
        small.update(overrides)
        # ensure kv divides heads
        nh, nkv = small["num_heads"], small["num_kv_heads"]
        if nkv and nh % nkv:
            small["num_kv_heads"] = 1
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# Input shapes (the four assigned global shapes)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# ---------------------------------------------------------------------------
# FibecFed / FL configuration (paper Table 8 defaults)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FibecFedConfig:
    num_devices: int = 100  # K in the paper
    devices_per_round: int = 10
    rounds: int = 100  # T
    local_epochs: int = 1
    batch_size: int = 8
    learning_rate: float = 4e-4

    # curriculum (Formula 18): B_k^t = (beta + (1-beta) * t/(alpha*T)) * n_k/B
    curriculum: str = "linear"  # "linear" | "sqrt" | "exp" | "none"
    beta_initial_ratio: float = 0.6  # beta (Table 12 best ~0.6)
    alpha_full_data: float = 0.8  # alpha

    # GAL selection
    noise_budget: float = 0.05  # gamma in Eq. 6/8
    norm_p: float = 2.0  # l_p of the perturbation
    gal_fraction: Optional[float] = 0.75  # override; None -> lossless criterion
    mu_global_local: float = 1.0  # mu in N* = mu/N * sum n_k N_k*

    # local sparse update
    fim_momentum: float = 0.9  # gamma (momentum) in F_k^t
    fim_warmup_epochs: int = 2  # T'
    sparse_ratio: Optional[float] = 0.5  # rho override; None -> lossless
    lanczos_iters: int = 16  # Hessian spectrum estimation

    # non-IID partition
    dirichlet_alpha: float = 1.0
    seed: int = 0


@dataclass(frozen=True)
class MeshConfig:
    data: int = 16
    model: int = 16
    pods: int = 1

    @property
    def num_chips(self) -> int:
        return self.data * self.model * self.pods


# TPU v5e roofline constants (per chip).
@dataclass(frozen=True)
class HardwareSpec:
    peak_flops: float = 197e12  # bf16 FLOP/s
    hbm_bandwidth: float = 819e9  # bytes/s
    ici_bandwidth: float = 50e9  # bytes/s per link


TPU_V5E = HardwareSpec()
