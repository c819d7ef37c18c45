"""granite-4.0-h-micro [hybrid]: 40L d_model=2048 vocab=100352 — Mamba-2
(SSD) layers and NoPE GQA layers (32H, kv=8, head 64) in one stack, every
layer with a gated SiLU MLP (d_ff 8192). [hf ibm-granite/granite-4.0-h-micro,
model_type granitemoehybrid]

``layer_types``: a period of ten layers, 5 mamba, 1 attention, 4 mamba,
repeated 4 times (36 SSD + 4 attention). SSD: 64 heads x 64 (d_inner
4096), state 128, 1 group, conv 4 with bias, chunk 256. Attention scale
``attention_multiplier`` 1/64; embedding x 12, each sublayer's output x
0.22 into the residual, logits / 8; tied embeddings.
"""
from ..models.config import ArchConfig

PATTERN = ("ssm",) * 5 + ("dense",) + ("ssm",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="hybrid", n_layers=40, d_model=2048,
    n_heads=32, n_kv_heads=8, head_dim=64, d_ff=8192, vocab_size=100352,
    gated_mlp=True, act="silu", rope_theta=None, attn_scale=0.015625,
    tie_embeddings=True, norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_ngroups=1, ssm_conv=4,
    ssm_conv_bias=True, ssm_chunk=256, block_pattern=PATTERN,
)

# one whole period at a small width
REDUCED = ArchConfig(
    name="granite-4.0-h-reduced", family="hybrid", n_layers=10, d_model=128,
    n_heads=8, n_kv_heads=2, head_dim=16, d_ff=256, vocab_size=512,
    gated_mlp=True, act="silu", rope_theta=None, attn_scale=1 / 16,
    tie_embeddings=True, norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    ssm_state=16, ssm_headdim=32, ssm_expand=2, ssm_ngroups=1, ssm_conv=4,
    ssm_conv_bias=True, ssm_chunk=32, block_pattern=PATTERN, dtype="float32",
)
