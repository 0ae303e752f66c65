"""Kimi-VL-A3B as a VLA: OpenVLA's recipe (a vision-language model plus
action tokens) on Kimi-VL-A3B-Instruct's MoE + MLA language model and its
MoonViT vision tower.

Language model (DeepSeek-V3 layout): 27 layers, d_model 2048, MLA with
16 heads (128 nope + 64 rope query/key dims, 512-wide latent, no q-LoRA),
rope theta 800000; layer 0 a dense SwiGLU of width 11264, layers 1-26
MoE: 64 routed SwiGLU experts of width 1408 behind a sigmoid router with
bias-corrected top-6 selection, gates normalised and scaled by 2.446, and
2 shared experts.  Vision: MoonViT (SigLIP-SO400M shape, 27 blocks of
1152, 16 heads of 72, GELU MLP of 4304, 2D rope), 448x448 at patch 14
(1024 patches), 2x2 pixel shuffle and an MLP projector to 2048.  Action
head: OpenVLA's detok over the last 256 ids of the 163840-token vocab.
[huggingface.co/moonshotai/Kimi-VL-A3B-Instruct; arXiv:2504.07491;
arXiv:2406.09246]
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-vl-a3b-vla",
    family="vla",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=800_000.0,
    norm_eps=1e-5,
    use_mla=True,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    moe_dropless=True,
    moe_scoring="sigmoid",
    moe_routed_scale=2.446,
    vla_action_head="detok",
    vit_layers=27,
    vit_dim=1152,
    vit_heads=16,
    vit_ff=4304,
    vit_block="siglip",
    vit_merge=2,
    n_patches=1024,
    action_dim=7,
    action_horizon=1,
)
