"""DeepSeek-V2-Lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite
config.json] — MLA (kv_lora_rank 512, no q-LoRA), YaRN rope, one dense
layer, then 64 routed experts (top-6 of a softmax, gates not
renormalised) beside 2 shared experts in every layer.

All 64 routed experts are held: this is the whole model. A chip's share
of the experts under expert parallelism sets ``experts_held`` and
``first_expert`` (bench/configs/deepseek-v2-lite.json holds 8 of them).
"""
from repro.configs.base import ArchConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    source="arXiv:2405.04434",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,           # qk_nope 128 + rope 64
    d_ff=10944,             # first (dense) layer FFN
    vocab_size=102_400,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    mla=True,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    qk_nope_head_dim=128,
    v_head_dim=128,
    n_experts=64,
    n_experts_per_tok=6,
    n_shared_experts=2,
    moe_d_ff=1408,
    n_dense_layers=1,
    norm_topk_prob=False,
    router_aux_loss_coef=0.003,
    moe_dispatch_groups=16,  # shard-local dispatch (§Perf iter 1/4)
)
