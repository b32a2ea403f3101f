"""moonlight-16b-a3b [moe]: 27L d=2048, DeepSeek-V3's block.  Layer 0 has
a dense SwiGLU (d_ff 11264); layers 1-26 route each token to 6 of 64
experts of width 1408 (sigmoid scores, a correction bias that moves the
choice only, the top 6 renormalised and scaled by 2.446) beside 2
shared experts (one SwiGLU of width 2816).  Latent attention with no
q-LoRA: 16 heads, qk 128 + 64 rotary, v 128, kv rank 512.  Vocab
163840, untied head.  [hf:moonshotai/Moonlight-16B-A3B]

The rotary columns of ``wq`` and ``wkv_a`` are stored de-interleaved
(evens, then odds, of the published layout), so that rotating halves
equals the published rotation of adjacent pairs."""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_head=128,
    d_ff=11264, vocab=163840, rope_theta=50000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128,
    moe_experts=64, moe_topk=6, moe_dff=1408, moe_router="sigmoid",
    moe_route_scale=2.446, moe_shared_dff=2816, moe_first_dense=1,
    param_dtype="bfloat16", act_dtype="bfloat16",
    note="full attention; decode needs a latent KV cache (ROADMAP Reach 5)",
)

# rank 0 of a 2-way expert split: holds experts 0-3 of the router's 8
SMOKE = ArchConfig(
    name="moonlight-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_head=16,
    d_ff=128, vocab=128, rope_theta=50000.0, norm_eps=1e-5,
    kv_lora_rank=32, qk_rope_dim=8, v_head_dim=16,
    moe_experts=8, moe_topk=2, moe_dff=32, moe_router="sigmoid",
    moe_route_scale=2.446, moe_shared_dff=64, moe_first_dense=1,
    moe_held=4, attn_q_chunk=16,
)
