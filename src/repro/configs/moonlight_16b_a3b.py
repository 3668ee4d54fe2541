"""moonlight-16b-a3b [moe, deepseek_v3] — 27L d_model=2048, 16 MLA heads
(direct query: q_lora_rank null; kv_lora 512, rope/nope/v 64/128/128),
one dense SwiGLU layer (d_ff 11264) then 26 MoE layers of 64 routed
experts (d_ff 1408, 6 a token) plus 2 shared; sigmoid router with a
selection bias (noaux_tc), gates normalised and scaled by 2.446, one
group; sequence-wise balance loss; vocab 163840, untied; no MTP layer.
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]

`CHIP_SHARE` is the cut one chip trains: each layer divided over 8 chips
by experts (attention and shared experts replicated), layers past the
fifth MoE layer on further chips as pipeline stages, and an eighth of the
vocabulary for the embedding and the head (`ModelConfig.share`).
Assumed where the config is silent: the balance loss's alpha 0.001 (the
DeepSeek-V3 family's), the selection bias starting at zero and left out
of training (its update rule is a pre-training device; SGD gives it no
gradient), and the initialiser's scales.
"""
from repro.models.lm.config import MLAConfig, ModelConfig, MoEConfig, Segment

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,                       # the dense layer (the first)
    vocab_size=163840,
    mlp="swiglu",
    segments=(
        Segment(kind="attn", n_layers=1),
        Segment(kind="moe", n_layers=26),
    ),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                  router_aux_coef=0.001, scoring="sigmoid",
                  routed_scale=2.446),
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    rope_theta=50000.0,
    norm_eps=1e-5,
    dtype="float32",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
)

# One chip of an 8-chip expert-parallel deployment of each layer.
CHIP_SHARE = dict(moe_layers=5, held=tuple(range(8)), vocab_size=20480)
