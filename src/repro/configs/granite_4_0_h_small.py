"""granite-4.0-h-small [hybrid] — 40L d_model=4096, 36 Mamba-2 + 4 GQA
attention layers (32H, kv=8, NoPE), every layer followed by a MoE of 72
routed SwiGLU experts (width 768, top-10) plus one shared expert (1536),
vocab=100352 tied, muP multipliers. [hf:ibm-granite/granite-4.0-h-small]

Period of 10 layers: attention at slot 5 (layer indices 5, 15, 25, 35 of the
published ``layer_types``), Mamba-2 everywhere else (128 heads of 64,
d_state 128, one group, conv 4, SSD chunk 256).  Each mixer is followed by
the norm → FFN sub-block (``mamba_ffn``): the router's softmax over its
top-10 logits weights the routed experts, and the shared expert is added
once.  The published model holds all 72 experts (``expert_shards=1``); a
deployment that divides each layer's experts over chips sets
``expert_shards``.
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=768,  # intermediate_size: every FFN is the MoE, so no dense MLP runs
    vocab_size=100352,
    layout=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    moe=MoEConfig(
        n_experts=72, top_k=10, d_ff_expert=768, d_ff_shared=1536, dropless=True
    ),
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64, n_groups=1, chunk_size=256),
    mamba_ffn=True,
    position_embedding="nope",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    activation="swiglu",
    norm_eps=1e-5,
    tie_embeddings=True,
    rope_theta=10_000.0,
)
