"""kimi-vl-a3b — Kimi-VL-A3B-Instruct's language model: latent attention and
64 sigmoid-routed experts.

[hf:moonshotai/Kimi-VL-A3B-Instruct]
https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json
(Kimi-VL Technical Report, arXiv:2504.07491). The language model is a
DeepSeek-V3-style decoder (arXiv:2412.19437; MLA from arXiv:2405.04434):
27 layers, d_model 2048, vocab 163840, untied head, rope_theta 800000,
RMSNorm eps 1e-5. Attention is MLA with 16 heads and no query LoRA:
kv_lora_rank 512, q/k head width 128 + 64 (rope), v head width 128. Layer 0
has a dense SwiGLU of width 11264 (``first_k_dense_replace`` 1); layers
1-26 hold 64 routed SwiGLU experts of width 1408, top-6 by sigmoid scores
plus a correction bias (``noaux_tc``, one group), weights renormalised and
scaled by 2.446, and 2 shared experts (one SwiGLU of width 2816).

The MoonViT tower is the repository's vision stub; the connector is one
linear map from MoonViT's 1152 x 4 = 4608 features after its 2x2 pixel
shuffle. The rotary dims use rotate-halves where the published model
interleaves them: a fixed permutation of q_pe/k_pe columns, which random
weights absorb.
"""
from repro.configs.base import AdapterConfig, MLAConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="kimi-vl-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=192,
        d_ff=1408,
        vocab_size=163840,
        max_seq_len=131072,
        pos_type="rope",
        rope_theta=800000.0,
        norm="rmsnorm",
        norm_eps=1e-5,
        act="swiglu",
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, shared_d_ff=2816, scoring="sigmoid",
                      score_bias=True, routed_scale=2.446),
        n_dense_layers=1,
        dense_d_ff=11264,
        frontend_dim=4608,
        adapter=AdapterConfig(rank=64, alpha=128.0, modalities=("text", "image")),
    )
