"""granite-4.0-h-small [hybrid_moe] — 32B-A9B: 36 Mamba-2 and 4 NoPE GQA
mixers by pattern, every one followed by a dropless 72-expert top-10 MoE
plus a shared SwiGLU; Granite multipliers, tied embedding.
[hf:ibm-granite/granite-4.0-h-small; config.json, model_type granitemoehybrid]
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, register

# attention at 5, 15, 25 and 35; every other layer is a Mamba-2 mixer
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))


def full_config() -> ModelConfig:
    return ModelConfig(
        arch="granite-4.0-h-small", family="hybrid_moe",
        num_layers=40, d_model=4096, num_heads=32, num_kv_heads=8,
        d_ff=768, vocab_size=100352, head_dim=128,
        tie_embeddings=True, norm_eps=1e-5, rope_theta=10000.0,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0078125, logits_scaling=16.0,
        nope=True, layer_types=LAYER_TYPES,
        ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_kernel=4,
                      n_groups=1, chunk_size=256),
        moe=MoEConfig(num_experts=72, top_k=10, d_expert=768,
                      num_shared_experts=1, d_shared=1536),
        source="[hf:ibm-granite/granite-4.0-h-small; hf]",
    )


def smoke_config() -> ModelConfig:
    """One period of the published pattern (the attention layer at index
    5) at toy widths."""
    return ModelConfig(
        arch="granite-4.0-h-small", family="hybrid_moe",
        num_layers=10, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=32, vocab_size=256, head_dim=16,
        tie_embeddings=True, norm_eps=1e-5,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=16.0,
        nope=True, layer_types=LAYER_TYPES[:10],
        ssm=SSMConfig(d_state=16, head_dim=16, expand=2, conv_kernel=4,
                      n_groups=1, chunk_size=32),
        moe=MoEConfig(num_experts=8, top_k=2, d_expert=32,
                      num_shared_experts=1, d_shared=48),
    )


register("granite-4.0-h-small", full_config, smoke_config)
