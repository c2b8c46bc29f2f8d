"""ModelConfig — one dataclass covering all assigned architecture families.

A model is ``first_k_dense_replace`` leading layers (the pattern's mixer
with a dense FFN, DeepSeek-V3's ``first_k_dense_replace``) followed by a
repeating ``layer_pattern`` of (mixer, ffn) pairs:
  mixer ∈ {"attn", "mla", "ssm"};  ffn ∈ {"dense", "moe"}.
The leading layers run one by one; the remaining ``n_layers -
first_k_dense_replace`` must be a multiple of ``len(layer_pattern)`` and
execute as ``lax.scan`` over their repeats with the P pattern positions
unrolled inside the block (small HLO even for 64-layer models,
heterogeneous patterns like Jamba's attn:ssm 1:7 / MoE-every-2 supported).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"          # "attn" | "mla" | "ssm"
    ffn: str = "dense"           # "dense" | "moe" | "none" (ssm-only layers)

    def __post_init__(self):
        assert self.mixer in ("attn", "mla", "ssm"), self.mixer
        assert self.ffn in ("dense", "moe", "none"), self.ffn


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    n_kv_heads: Optional[int] = None          # None = MHA
    head_dim: Optional[int] = None            # None = d_model // n_heads

    # Attention options
    qk_norm: bool = False                     # per-head RMSNorm on q,k (Qwen3)
    qkv_bias: bool = False                    # Qwen2
    rope_theta: float = 10000.0
    prefix_lm: bool = False                   # bidirectional prefix (PaliGemma)

    # MLA (MiniCPM3 / DeepSeek-style)
    q_lora_rank: int = 0                      # 0 = one direct q projection
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0                        # experts held by this chip
    n_experts_active: int = 0                 # top-k
    moe_d_ff: int = 0                         # expert hidden dim (0 -> d_ff)
    n_shared_experts: int = 0                 # shared experts (one SwiGLU of
                                              # n_shared * moe_d_ff)
    n_routed_experts: int = 0                 # router width (0 -> n_experts):
                                              # experts over all chips
    expert_offset: int = 0                    # id of the first held expert
    moe_score: str = "softmax"                # "softmax" | "sigmoid" router
    moe_score_bias: bool = False              # select top-k on score plus a
                                              # learned correction bias
                                              # (DeepSeek-V3 noaux_tc)
    moe_routed_scale: float = 1.0             # routed weights' scale
    capacity_factor: float = 1.25             # training dispatch; 0 = dropless
    router_aux_coef: float = 0.01
    moe_group_size: int = 4096                # GShard routing-group size

    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0                        # N
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64                    # P
    ssm_chunk: int = 256                      # SSD chunk length Q
    ssm_groups: int = 1                       # B/C groups (G)

    # Layer pattern (defaults to all-(attn,dense))
    layer_pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    first_k_dense_replace: int = 0            # leading (mixer, dense) layers

    # Multimodal stubs
    n_codebooks: int = 0                      # MusicGen EnCodec streams (K)
    n_img_patches: int = 0                    # PaliGemma SigLIP patch count

    # Misc
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    act_fn: str = "silu"                      # "silu" | "gelu"
    dtype: str = "bfloat16"                   # compute dtype
    param_dtype: str = "float32"
    logits_softcap: float = 0.0

    # Attention memory knobs
    attn_chunk: int = 1024                    # flash chunk (kv block length)
    remat: bool = True
    remat_policy: str = "nothing"             # nothing | dots_nobatch | everything

    def __post_init__(self):
        # moe_apply, the capacity dispatch, routes a softmax over the held
        # experts alone; anything else would train silently on the wrong
        # router or fail far from the cause
        if self.capacity_factor > 0 and self.n_experts and (
                self.moe_score != "softmax" or self.moe_score_bias
                or self.moe_routed_scale != 1.0 or self.expert_offset
                or self.router_width != self.n_experts):
            raise ValueError(
                f"{self.name}: a sigmoid, biased, scaled or partly held "
                "router runs dropless only: set capacity_factor=0")

    # --- derived -----------------------------------------------------------
    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def pattern_len(self) -> int:
        return len(self.layer_pattern)

    @property
    def n_repeats(self) -> int:
        scanned = self.n_layers - self.first_k_dense_replace
        assert scanned % self.pattern_len == 0, (
            f"{self.name}: n_layers={self.n_layers} less "
            f"{self.first_k_dense_replace} leading layers not divisible by "
            f"pattern length {self.pattern_len}")
        return scanned // self.pattern_len

    @property
    def lead_spec(self) -> LayerSpec:
        """The leading layers' (mixer, ffn): the pattern's mixer, dense."""
        return LayerSpec(self.layer_pattern[0].mixer, "dense")

    @property
    def router_width(self) -> int:
        return self.n_routed_experts or self.n_experts

    @property
    def has_moe(self) -> bool:
        return any(s.ffn == "moe" for s in self.layer_pattern)

    @property
    def d_inner(self) -> int:                 # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def has_attention(self) -> bool:
        return any(s.mixer in ("attn", "mla") for s in self.layer_pattern)

    @property
    def is_pure_attention(self) -> bool:
        return all(s.mixer in ("attn", "mla") for s in self.layer_pattern)

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_cache_dims(self) -> int:
        """Per-token per-layer KV entries (for roofline/memory accounting)."""
        if self.is_mla:
            return self.kv_lora_rank + self.qk_rope_head_dim   # latent cache
        return 2 * self.kv_heads * self.hd

    def param_count(self) -> int:
        """Analytic parameter count (exact for our param layout)."""
        d, v = self.d_model, self.vocab_size
        total = v * d                                    # embed
        if not self.tie_embeddings:
            total += d * v                               # lm_head
        if self.n_codebooks:
            total += (self.n_codebooks - 1) * v * d      # extra codebook embeds
            total += (self.n_codebooks - 1) * d * v      # extra heads
        lead = ((self.lead_spec, self.first_k_dense_replace),) \
            if self.first_k_dense_replace else ()
        for spec, times in lead + tuple((s, self.n_repeats)
                                        for s in self.layer_pattern):
            cnt = 2 * d                                  # 2 norms (approx; ssm has 1+)
            if spec.mixer == "attn":
                h, kh, hd = self.n_heads, self.kv_heads, self.hd
                cnt += d * h * hd + 2 * d * kh * hd + h * hd * d
                if self.qkv_bias:
                    cnt += (h + 2 * kh) * hd
                if self.qk_norm:
                    cnt += 2 * hd
            elif spec.mixer == "mla":
                r_q, r_kv = self.q_lora_rank, self.kv_lora_rank
                h = self.n_heads
                qd = self.qk_nope_head_dim + self.qk_rope_head_dim
                cnt += (d * r_q + r_q * h * qd if r_q     # q_a, q_b
                        else d * h * qd)                 # direct q
                cnt += d * (r_kv + self.qk_rope_head_dim)  # kv_a
                cnt += r_kv * h * (self.qk_nope_head_dim + self.v_head_dim)  # kv_b
                cnt += h * self.v_head_dim * d           # wo
                cnt += r_q + r_kv                        # lora norms
            elif spec.mixer == "ssm":
                di, g, n, hh = self.d_inner, self.ssm_groups, self.ssm_state, self.ssm_heads
                conv_dim = di + 2 * g * n
                cnt += d * (2 * di + 2 * g * n + hh)     # in_proj [z,x,B,C,dt]
                cnt += conv_dim * self.ssm_conv + conv_dim
                cnt += 3 * hh + di                       # A_log, D, dt_bias, gn gain
                cnt += di * d                            # out_proj
            if spec.ffn == "dense":
                cnt += 3 * d * self.d_ff                 # SwiGLU
            elif spec.ffn == "moe":
                f = self.expert_d_ff
                cnt += d * self.router_width             # router
                if self.moe_score_bias:
                    cnt += self.router_width
                cnt += self.n_experts * 3 * d * f
                cnt += self.n_shared_experts * 3 * d * f
            total += cnt * times
        total += d                                       # final norm
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE top-k counts only k experts)."""
        if self.n_experts == 0:
            return self.param_count()
        full = self.param_count()
        f = self.expert_d_ff
        n_moe_layers = sum(1 for s in self.layer_pattern if s.ffn == "moe") * self.n_repeats
        inactive = max(self.n_experts - self.n_experts_active, 0) * 3 * self.d_model * f
        return int(full - n_moe_layers * inactive)


def repeat_pattern(spec_pairs, times: int) -> Tuple[LayerSpec, ...]:
    return tuple(LayerSpec(m, f) for m, f in spec_pairs) * times
