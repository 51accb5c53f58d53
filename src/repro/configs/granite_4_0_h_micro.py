"""granite-4.0-h-micro [hybrid] — 40L d_model=2048, Mamba-2 / attention
3,191,396,096 parameters, vocab 100352, tied.
[hf:ibm-granite/granite-4.0-h-micro config.json]

40 layers = (mamba2 ×5, attn, mamba2 ×4) × 4: attention at layers 5, 15,
25 and 35, Mamba-2 everywhere else.  Attention: GQA 32/8 heads of 64, no
bias, no position embedding ("nope"), softmax scale 1/64
(``attention_multiplier``).  Mamba-2: 64 heads of 64 (d_inner 4,096), state
128, n_groups 1, conv 4 with bias, chunk 256.  A SwiGLU MLP of 8,192 in
every layer (``shared_intermediate_size``; no experts).  Granite's scalars:
embeddings ×12, each block's output ×0.22, logits ÷8; RMSNorm eps 1e-5.
"""

from .base import Block, ModelConfig

_M, _A = Block("mamba2"), Block("attn")

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    unit=(_M, _M, _M, _M, _M, _A, _M, _M, _M, _M),
    num_units=4,
    mlp_kind="swiglu",
    tie_embeddings=True,
    rms_eps=1e-5,
    rope=False,
    attn_scale=0.015625,
    embed_mult=12.0,
    residual_mult=0.22,
    logits_div=8.0,
    mamba_heads=64,
    mamba_head_dim=64,
    mamba_state=128,
    mamba_conv=4,
    mamba_chunk=256,
    max_seq_len=131072,
    source="hf:ibm-granite/granite-4.0-h-micro (config.json)",
)
