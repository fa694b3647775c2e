"""granite-4-h-micro [hybrid] — Mamba-2 + NoPE GQA, 9 SSD : 1 attention
[hf:ibm-granite/granite-4.0-h-micro].

40L in four periods of ten (five SSD layers, one attention layer, four SSD
layers), d_model=2048; SSD mixers of 64 heads of 64 (d_inner=4096),
d_state=128, one group, conv width 4 with a bias, chunk 256; attention 32
query and 8 KV heads of 64 with no position embedding; a SwiGLU MLP of 8192
in every layer; vocab=100352, tied.  Granite's scalars: embeddings ×12,
attention scores ×1/64, residual branches ×0.22, logits ÷8, RMSNorm eps
1e-5.  Sub-quadratic only in its SSD layers, so no long_500k."""

from repro.configs.base import ArchConfig, SSDCfg
from repro.core.structures import StructureConfig

CONFIG = ArchConfig(
    name="granite-4-h-micro",
    family="hybrid",
    vocab=100_352,
    d_model=2048,
    n_layers=40,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    ffn_kind="swiglu",
    tie_embeddings=True,
    pos_embed="none",
    pattern=("ssd",) * 5 + ("attn",) + ("ssd",) * 4,
    ssd=SSDCfg(d_state=128, head_dim=64, expand=2, chunk=256, conv_width=4),
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    rms_norm_eps=1e-5,
    structure=StructureConfig(kind="blast", b=16, keep_ratio=0.5),
)
