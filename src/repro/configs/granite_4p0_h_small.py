"""granite-4.0-h-small — hybrid Mamba-2 / attention decoder with a
72-expert MoE FFN and a shared expert in every layer ("32B-A9B").

[hf:ibm-granite/granite-4.0-h-small config.json, ``granitemoehybrid``]
40 layers at d_model 4096: 36 Mamba-2 mixers (128 heads × 64, d_state
128, one B/C group, conv 4 with bias, chunk 256) and 4 attention mixers
at layers 5, 15, 25 and 35 (GQA 32 query over 8 KV heads, head_dim 128,
no positional encoding, score scale ``attention_multiplier`` 1/128).
Every layer then runs a top-10-of-72 MoE FFN (experts 768 wide, gates a
softmax over the 10 chosen router logits) plus a shared expert 1536
wide, all SwiGLU.  Embeddings × 12, each residual branch × 0.22, logits
÷ 16; tied 100,352-entry vocabulary.

The architecture is not in ``ARCH_IDS``: it is served (engine, pool,
scheduler) but the dry-run training path does not cover it.
"""
from repro.config import ModelConfig, MoEConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=0,
    vocab_size=100352,
    rms_eps=1e-5,
    tie_embeddings=True,
    moe=MoEConfig(n_experts=72, top_k=10, d_expert=768, d_shared=1536),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_kernel=4,
                  chunk_size=256, n_groups=1),
    layer_types=_PERIOD * 4,
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-small",
)

# CPU variant: both mixer kinds, an attention layer that is not first,
# and one chip's share (2 of 8 experts) of a top-2 MoE with a shared
# expert
REDUCED = ModelConfig(
    name="granite-4.0-h-reduced",
    family="hybrid",
    n_layers=4,
    d_model=256,
    n_heads=4,
    n_kv_heads=2,
    head_dim=64,
    d_ff=0,
    vocab_size=512,
    rms_eps=1e-5,
    tie_embeddings=True,
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=128, d_shared=128,
                  held_start=2, held_count=2),
    ssm=SSMConfig(d_state=16, head_dim=64, chunk_size=32),
    layer_types=("mamba", "attention", "mamba", "mamba"),
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=1.0 / 64,
    source="reduced smoke variant",
)
