"""granite-4.0-h-micro [hybrid] -- Mamba-2 + GQA NoPE, dense SwiGLU MLPs.

[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]
40 layers, each a mixer and a SwiGLU MLP of width 8,192 (no experts): 36
Mamba-2 mixers and 4 attention mixers, at layers 5, 15, 25, 35. Attention is
GQA, 32 query and 8 KV heads of 64, with no position encoding. Mamba-2:
d_inner 4,096 (expand 2), 64 heads of 64, state 128, one group, conv 4 with
a bias, chunk 256. Granite's scalings: embeddings times 12, attention
scores times 1/64 (in place of 1/sqrt(64)), each branch times 0.22 before
its residual add, logits divided by 8. RMSNorm eps 1e-5, tied embeddings.

Departures: the exit after layer 9 (the end of the first period of 10) and
its head, an RMSNorm and an unembed of its own, are the system's side
branch, not part of the published model; its logits are divided by 8 as
the final head's are.
"""
from repro.configs.base import ModelConfig, smoke_variant

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,  # shared_intermediate_size; num_local_experts = 0
    vocab_size=100_352,
    use_rope=False,  # position_embedding_type: nope
    attn_every=10,
    attn_offset=5,  # attention at layers 5, 15, 25, 35
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,  # d_inner 4096 -> 64 Mamba heads
    ssm_conv=4,
    ssm_chunk=256,
    ssm_n_groups=1,
    norm_type="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    exit_layers=(9,),
    source="hf:ibm-granite/granite-4.0-h-micro (40L d2048 32H kv8 ff8192, "
    "36 Mamba-2 + 4 attention NoPE, vocab 100352)",
)

#: every kind of layer and every scaling at d 256: Mamba, attention, Mamba,
#: attention, the exit after the first attention layer, GQA kept (4 query
#: heads over 2 KV heads)
SMOKE = smoke_variant(CONFIG).replace(
    num_layers=4, num_kv_heads=2, exit_layers=(1,), exit_loss_weights=(1.0,)
)
