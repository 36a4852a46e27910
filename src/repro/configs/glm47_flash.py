"""GLM-4.7-Flash [hf:zai-org/GLM-4.7-Flash, ``glm4_moe_lite``]: latent
attention (MLA, 20 heads) and a ``noaux_tc`` sigmoid-routed MoE of 64
experts of 1536, 4 per token, one shared expert, after one dense SwiGLU
layer of 10240.  The MTP layer (speculative decoding only) is left out."""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        arch_id="glm47-flash", family="moe",
        n_layers=47, d_model=2048, n_heads=20, n_kv=20, d_ff=10240,
        vocab=154880, head_dim=256, rope_theta=1e6, norm_eps=1e-5,
        dense_layers=1,
        mla=MLAConfig(q_lora_rank=768, kv_lora_rank=512, qk_nope_dim=192,
                      qk_rope_dim=64, v_head_dim=256),
        moe=MoEConfig(num_experts=64, top_k=4, d_expert=1536,
                      num_shared=1, d_shared=1536, capacity_factor=0.0,
                      scoring="sigmoid", routed_scale=1.8),
    )
