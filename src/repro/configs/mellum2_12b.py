"""mellum2-12b — JetBrains' code model, sparse experts with mixed window
and full attention.

[hf: JetBrains/Mellum2-12B-A2.5B-Instruct, config.json]. 28L d_model=2304
32H (GQA kv=4, head_dim 128) vocab=98304, untied head; every layer sparse:
64 routed experts of width 896, top-8 with renormalised gates, no shared
expert. Layers repeat (sliding, sliding, sliding, full): sliding layers
attend over the last 1024 positions with the default rope (theta 5e5),
full layers over all of them with YaRN rope (factor 16 over 8192
positions, beta 32/1, attention factor 1.27726). The MTP head the model
card describes is not served; the config has no QK norm.
"""
from repro.configs import ArchSpec
from repro.models.transformer import TransformerConfig

_PATTERN = ("sliding_attention",) * 3 + ("full_attention",)
_ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                       "factor": 16, "original_max_position_embeddings": 8192,
                       "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}

ARCH = ArchSpec(
    arch_id="mellum2_12b",
    family="moe",
    module="transformer",
    model_cfg=TransformerConfig(
        name="mellum2_12b", n_layers=28, d_model=2304, n_heads=32,
        n_kv_heads=4, head_dim=128, d_ff=896, vocab=98304, n_experts=64,
        top_k=8, rope_theta=5e5, norm_eps=1e-6, layer_types=_PATTERN * 7,
        sliding_window=1024, rope_parameters=_ROPE, q_chunk=512,
        kv_chunk=512),
    smoke_cfg=TransformerConfig(
        name="mellum2_12b_smoke", n_layers=4, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, vocab=128, n_experts=8, top_k=2,
        rope_theta=5e5, layer_types=_PATTERN, sliding_window=8,
        rope_parameters=_ROPE, q_chunk=16, kv_chunk=16),
    source="hf: JetBrains/Mellum2-12B-A2.5B-Instruct",
)
