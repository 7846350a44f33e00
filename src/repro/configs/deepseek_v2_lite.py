"""deepseek-v2-lite [moe] — latent attention (MLA), 64 routed experts top-6
plus 2 shared, the first layer dense. [arXiv:2405.04434; hf]

27L d_model=2048 16H; MLA without query LoRA: kv_lora_rank=512,
qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128; YaRN rope
(factor 40 over 4,096 positions); experts d_ff=1408, softmax gate with the
top-6 weights not renormalised; layer 0 a dense MLP of d_ff=10944;
vocab=102400.
"""
from .base import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    block=(LayerSpec(kind="mla", ffn="moe"),),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    moe_experts=64,
    moe_topk=6,
    moe_d_ff=1408,
    moe_shared_d_ff=2816,            # n_shared_experts 2 x 1408, one MLP
    moe_norm_topk=False,
    # 64 / 6: no expert can overflow, as the published model drops nothing
    moe_capacity_factor=10.67,
    norm_eps=1e-6,
    max_seq=163840,
)
