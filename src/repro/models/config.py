"""Architecture configuration (static, hashable, jit-friendly)."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

__all__ = ["ArchConfig", "SHAPES", "ShapeSpec"]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# Assigned input-shape set (LM transformer shapes)
SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0              # 0 -> d_model // n_heads
    d_ff: int = 0
    qk_norm: bool = False
    swa_window: int | None = None
    rope: bool = True
    rope_theta: float = 1e4
    causal: bool = True
    norm_eps: float = 1e-6       # every RMSNorm of the residual stream
    # --- latent attention (MLA, DeepSeek-V2/V3): d_head is the per-head
    # q/k width without rotary, which rides the extra qk_rope_dim ---
    kv_lora_rank: int = 0        # 0 -> grouped-query attention
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # --- MoE ---
    moe_experts: int = 0         # the router's width: every expert
    moe_topk: int = 0
    moe_dff: int = 0
    moe_router: str = "softmax"  # | "sigmoid" (with a correction bias)
    moe_route_scale: float = 1.0  # times the renormalised top-k weights
    moe_shared_dff: int = 0      # width of the shared SwiGLU, 0 -> none
    moe_first_dense: int = 0     # leading layers with a dense MLP (d_ff)
    moe_held: int = 0            # experts this chip holds, 0 -> all
    moe_first_held: int = 0      # the first of them
    # --- SSM (mamba2 / hybrid) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    # --- modality frontend stub ---
    frontend: str | None = None  # "vision" | "audio"
    frontend_dim: int = 0
    frontend_tokens: int = 0     # vision prefix length (vlm)
    # --- numerics ---
    param_dtype: str = "float32"
    act_dtype: str = "float32"
    remat: bool = True
    remat_policy: str = "dots"   # "full" (save nothing) | "dots"
    attn_q_chunk: int = 1024
    ssd_chunk: int = 128
    note: str = ""

    def __post_init__(self):
        if self.n_heads and not self.d_head:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)

    # ---------------- derived properties -----------------
    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def rope_dim(self) -> int:
        """Width of each head's rotary part."""
        return self.qk_rope_dim if self.mla else self.d_head

    @property
    def held_experts(self) -> int:
        return self.moe_held or self.moe_experts

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to an MXU-aligned, TP-divisible multiple
        (Megatron-style padding; padded logits are masked in the loss)."""
        return -(-self.vocab // 128) * 128 if self.vocab else 0

    @property
    def has_attention(self) -> bool:
        return self.family in ("dense", "moe", "encoder", "vlm", "hybrid")

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def has_decode(self) -> bool:
        # no latent KV cache yet (ROADMAP Reach 5)
        return self.family != "encoder" and not self.mla

    @property
    def subquadratic(self) -> bool:
        """Can this arch run long_500k decode with bounded state?"""
        return self.family in ("ssm", "hybrid") or self.swa_window is not None

    def runnable_shapes(self) -> list[str]:
        """The assignment's skip rules (DESIGN.md §4)."""
        out = ["train_4k", "prefill_32k"]
        if self.has_decode:
            out.append("decode_32k")
            if self.subquadratic:
                out.append("long_500k")
        return out

    def param_jdtype(self):
        return jnp.dtype(self.param_dtype)

    def act_jdtype(self):
        return jnp.dtype(self.act_dtype)

    def params_count(self) -> int:
        """Approximate parameter count (for MODEL_FLOPS / memory checks)."""
        d, l = self.d_model, self.n_layers
        n = self.vocab * d  # embed
        if self.vocab:
            n += self.vocab * d  # untied lm head
        per_layer = 0
        if self.mla:
            h, r, rd = self.n_heads, self.kv_lora_rank, self.qk_rope_dim
            per_layer += d * h * (self.d_head + rd) + d * (r + rd) + r + \
                r * h * (self.d_head + self.v_head_dim) + \
                h * self.v_head_dim * d
        elif self.has_attention:
            hdh = self.n_heads * self.d_head
            kvdh = self.n_kv_heads * self.d_head
            per_layer += d * hdh + 2 * d * kvdh + hdh * d
        if self.family in ("dense", "encoder", "vlm", "hybrid") and self.d_ff:
            per_layer += 3 * d * self.d_ff
        if self.family == "moe":
            experts = self.held_experts * 3 * d * self.moe_dff + \
                d * self.moe_experts + 3 * d * self.moe_shared_dff
            per_layer += experts
            # the leading dense layers hold a dense MLP instead
            n += self.moe_first_dense * (3 * d * self.d_ff - experts)
        if self.has_ssm:
            d_inner = self.ssm_heads * self.ssm_head_dim
            conv = d_inner + 2 * self.ssm_state
            per_layer += d * (d_inner + conv + self.ssm_heads) + d_inner * d
        return n + l * per_layer

    def active_params_count(self) -> int:
        """MoE: only routed experts count towards MODEL_FLOPS."""
        if self.family != "moe":
            return self.params_count()
        d, l = self.d_model, self.n_layers - self.moe_first_dense
        dense = self.params_count() - \
            l * self.held_experts * 3 * d * self.moe_dff
        return dense + l * self.moe_topk * 3 * d * self.moe_dff
