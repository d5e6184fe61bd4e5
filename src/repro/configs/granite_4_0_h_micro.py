"""granite-4.0-h-micro — hybrid stack of Mamba-2 and attention layers.

[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json]
40L d_model=2048: 36 Mamba-2 layers (64 heads of 64, d_inner 4096, state 128,
one group, conv 4 with bias, chunk 256) and 4 NoPE GQA attention layers (32
query / 8 KV heads of 64, softmax scale attention_multiplier 1/64) at layers
5, 15, 25 and 35; a SwiGLU MLP 2048 -> 8192 on every layer (no experts);
RMSNorm eps 1e-5; embedding x12, each sublayer's output x0.22 before the
residual add, logits /8; tied vocabulary 100352. About 3.19B parameters.
"""
from repro.configs.base import ATTENTION, MAMBA, ModelConfig

LAYER_TYPES = tuple(ATTENTION if i % 10 == 5 else MAMBA for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    mlp_type="swiglu",
    norm_type="rmsnorm",
    tie_embeddings=True,
    pos_embedding="none",
    attention_type="full",
    layer_types=LAYER_TYPES,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    ssm_state_size=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_conv_width=4,
    shard_attention=False,
)
