"""moonlight-16b-a3b [moe] — DeepSeek-V3 layout: latent attention (MLA,
no query compression), one leading dense layer, then 26 layers of 64
routed experts (6 a token, sigmoid gate, noaux_tc) beside 2 shared ones.
[hf:moonshotai/Moonlight-16B-A3B config.json]
"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840,
    rope_theta=50_000.0, norm_eps=1e-5, tie_embeddings=False,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    n_experts=64, top_k=6, router="sigmoid", d_ff_expert=1408,
    n_shared_experts=2, first_dense_layers=1, routed_scale=2.446,
    # DeepSeek-V3's sequence-wise balance weight; Moonlight's is not
    # published (its config sets seq_aux)
    balance_coef=1e-4,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
)
