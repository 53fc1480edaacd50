"""DeepSeek-V2-Lite — latent attention (MLA) and fine-grained MoE with
shared experts.  [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]
27L d_model=2048 16H; layer 0 dense (d_ff=10944), layers 1-26 MoE:
64 routed experts of width 1408 (softmax router, greedy top-6, gates
not renormalised, routed scaling factor 1) beside 2 shared experts
computed as one SwiGLU of width 2816.  vocab=102400, untied head.

Attention is MLA with no query compression (``q_lora_rank`` null):
keys and values come from a 512-wide latent plus a 64-wide rope key
shared by the 16 heads, so the decode cache holds 576 values per token
and layer.  Each head's query and key are 128 no-rope + 64 rope dims
(``head_dim`` is the no-rope part), values 128.  Rope is YaRN (factor
40 over an original 4096 context) on the 64 rope dims, rotated in
pairs.  The softmax scale is ``192**-0.5 * mscale(40, 0.707)**2``, as
DeepSeek's own modelling code has it.
"""

from repro.configs.base import ModelConfig, Segment, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    d_model=2048,
    num_layers=27,
    segments=(Segment(("mla", "mlp"), 1), Segment(("mla", "moe"), 26)),
    vocab_size=102400,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    kv_lora_rank=512,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn=Yarn(factor=40.0, original_max_pos=4096, mscale=0.707,
              mscale_all_dim=0.707),
    d_ff=10944,
    mlp_kind="swiglu",
    num_experts=64,
    num_routed_experts=64,
    top_k=6,
    moe_d_ff=1408,
    shared_d_ff=2816,
    norm_eps=1e-6,
)


def smoke() -> ModelConfig:
    """One dense layer and two MoE layers holding 4 of 8 experts."""
    return ModelConfig(
        name="deepseek-v2-lite-smoke", family="moe", d_model=64,
        num_layers=3,
        segments=(Segment(("mla", "mlp"), 1), Segment(("mla", "moe"), 2)),
        vocab_size=256, num_heads=2, num_kv_heads=2, head_dim=32,
        kv_lora_rank=32, qk_rope_dim=16, v_head_dim=32,
        yarn=Yarn(factor=40.0, original_max_pos=64, mscale=0.707,
                  mscale_all_dim=0.707),
        d_ff=128, mlp_kind="swiglu", num_experts=4, num_routed_experts=8,
        top_k=2, moe_d_ff=64, shared_d_ff=128, norm_eps=1e-6)
