"""mellum2-12b [moe]: 28L d_model=2304 32H (GQA kv=4) x 128, vocab=98304,
every MLP sparse: 64 experts of width 896, top 8 (``norm_topk_prob``), no
shared expert.  Source: JetBrains/Mellum2-12B-A2.5B-Instruct config.json,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json

``layer_types``: three ``sliding_attention`` layers (window 1024, plain
RoPE at theta 5e5), then one ``full_attention`` layer (YaRN: theta 5e5,
factor 16 over 8192 original positions, beta_fast 32, beta_slow 1,
attention_factor 1.2772588722239782), repeated 7 times.  RMSNorm eps 1e-6,
untied embeddings.  Not in the config, so assumed: no QK-norm; the MTP
head is left out (off the non-speculative decode path).
"""
from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="mellum2-12b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,  # intermediate_size: no layer is dense, so none uses it
    vocab_size=98_304,
    attention_window=1024,
    block_pattern=("moe_sliding",) * 3 + ("moe_full",),
    rope_theta=500_000.0,
    rope_yarn=Yarn(factor=16.0, original_max_position_embeddings=8192,
                   beta_fast=32.0, beta_slow=1.0,
                   attention_factor=1.2772588722239782),
    num_experts=64,
    num_shared_experts=0,
    experts_per_token=8,
    moe_d_ff=896,
    moe_impl="dropless",
    act="silu",
    norm_eps=1e-6,
    tie_embeddings=False,
    remat="full",
    attn_block_kv=1024,
)
