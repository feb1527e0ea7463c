"""deepseek-v2-236b — MoE with multi-head latent attention (MLA).

[arXiv:2405.04434; hf] 60L d_model=5120 128H, MLA kv_lora=512
(q_lora=1536, qk_nope=128, qk_rope=64, v=128), MoE: 2 shared + 160
routed experts, top-6 with the raw softmax scores as gates
(norm_topk_prob false), expert d_ff=1536, first layer dense
(d_ff=12288), vocab=102400.
"""
from repro.configs.base import MLACfg, ModelConfig, MoECfg

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,          # MLA: effectively MHA over latent KV
    head_dim=128,
    d_ff=1536,                 # routed-expert width
    vocab_size=102_400,
    layer_pattern=("full",),
    prologue_layers=1,         # first layer dense FFN, outside the scan
    rope_theta=10_000.0,
    mlp="swiglu",
    tie_embeddings=False,
    mla=MLACfg(q_lora_rank=1536, kv_lora_rank=512,
               qk_nope_dim=128, qk_rope_dim=64, v_dim=128),
    moe=MoECfg(num_experts=160, top_k=6, d_ff_expert=1536,
               num_shared=2, d_ff_dense=12288, first_k_dense=1,
               norm_topk=False),    # published norm_topk_prob: false
    param_dtype="bfloat16",
    remat="full",
)
