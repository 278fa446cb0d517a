"""DeepSeek-V2-Lite [moe, latent attention] — arXiv:2405.04434;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json

27 layers, d_model 2048, 16 heads of MLA with no query compression
(kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128), YaRN rope (factor
40 over 4,096 positions), layer 0 a dense SwiGLU of width 10,944, layers
1-26 fine-grained MoE: 2 shared + 64 routed experts of width 1,408,
softmax scoring, greedy top-6, gates not renormalized. Vocabulary
102,400, embeddings not tied. Fine-tuned on the federated path as LoRA
(rank 16, alpha 32) on each layer's attention projections over a frozen
base (ModelSpec kind "zoo").
"""
import dataclasses

from repro.configs.base import (LoRAConfig, MLAConfig, ModelConfig,
                                MoEConfig, YarnConfig)

LORA = LoRAConfig(rank=16, alpha=32.0, targets=("wq", "wkva", "wkvb", "wo"))

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10_944,             # the leading dense layer's SwiGLU width
    vocab_size=102_400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    moe=MoEConfig(num_experts=64, num_shared_experts=2, top_k=6,
                  expert_d_ff=1408, router_aux_weight=0.0),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    mscale=0.707, mscale_all_dim=0.707, beta_fast=32.0,
                    beta_slow=1.0),
    first_k_dense_replace=1,
    lora=LORA,
    citation="arXiv:2405.04434",
)

# CPU tests: every mechanism at a small size (a dense layer and two MoE
# layers, 8 routed experts of which 4 are held, top-3 of 8)
REDUCED = dataclasses.replace(
    CONFIG, num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=96, vocab_size=128,
    moe=dataclasses.replace(CONFIG.moe, num_experts=8, top_k=3,
                            expert_d_ff=32, held=(0, 4)),
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    yarn=dataclasses.replace(CONFIG.yarn,
                             original_max_position_embeddings=16),
    lora=LoRAConfig(rank=4, alpha=8.0, targets=LORA.targets))
