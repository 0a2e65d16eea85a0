"""moonlight-16b-a3b [moe] — DeepSeek-V3 blocks: latent attention and 64
sigmoid-routed experts with 2 shared experts.

Published [hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3]: 27L
d_model=2048 16H, no q-LoRA, kv_lora_rank 512, qk_nope 128, qk_rope 64
(interleaved), v 128; layer 0 a dense SwiGLU of 11264, then MoE layers of
64 routed experts (width 1408, top-6, sigmoid scores plus a selection-only
correction bias, normalised weights times 2.446) and 2 shared experts
(2816 wide together, ungated); rope_theta 50000, rms_norm_eps 1e-5, vocab
163840, untied.

This is one chip's share of an 8-chip expert-parallel deployment: 8 of the
64 routed experts per MoE layer (ids 0-7; the router keeps its 64 outputs
and top-6 over all of them), an eighth of the vocabulary (20480), and 20 of
the 27 layers (the rest lie on further pipeline stages). No width is cut.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=20,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab=20480,
    head_dim=192,
    act="silu",
    rope_theta=50_000.0,
    norm_eps=1e-5,
    first_dense=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        expert_ff=1408,
        num_shared=2,
        shared_ff=2816,
        scoring="sigmoid",
        score_bias=True,
        routed_scale=2.446,
        shared_gate=False,
        held=8,
    ),
)
