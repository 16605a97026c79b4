"""zamba2-7b [hybrid]: Zyphra/Zamba2-7B-Instruct's config.json
[arXiv:2411.15242].  81 Mamba2 layers at d_model=3584 (inner width
7168, 112 heads x 64, state 64, 2 B/C groups, conv 4 with bias,
chunk 256); the 13 layers of ``hybrid_layer_ids`` first run one of two
weight-tied transformer blocks, alternating by ordinal, on
concat(x, embeddings): 32 MHA heads x 224 over the 7168-wide input,
rope theta 1e4, a gated exact-gelu MLP of 14336 with a rank-128 LoRA per
hybrid layer on its gate/up projection, and a per-hybrid-layer
3584x3584 linear whose output is added to that layer's Mamba input.
Vocabulary 32000, rms_norm_eps 1e-5.

Not stated by the config, as ``transformers``' ``Zamba2`` has them: the
attention scale (head_dim / 2) ** -0.5, rope over the whole head, and an
embedding tied to the head (the ``Zamba2Config`` default).
"""
from repro.configs.base import (AttentionConfig, ModelConfig, SSMConfig,
                                register)

CONFIG = register(ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    d_ff=14336,
    vocab_size=32_000,
    attention=AttentionConfig(   # the shared transformer blocks
        num_heads=32,
        num_kv_heads=32,
        head_dim=224,            # attention_head_dim: 2 * 3584 / 32
        rope_theta=10_000.0,
        softmax_scale=(224 / 2) ** -0.5,
    ),
    ssm=SSMConfig(
        state_dim=64,
        head_dim=64,
        expand=2,
        conv_kernel=4,
        chunk_size=256,
        n_groups=2,
        hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71,
                          77),
        n_shared_blocks=2,
        adapter_rank=128,
    ),
    activation="geglu_exact",
    norm_eps=1e-5,
    tie_embeddings=True,
    max_seq_len=4096,
))
