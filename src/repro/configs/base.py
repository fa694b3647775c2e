"""Architecture / run configuration schema.

Every assigned architecture is expressed as an ``ArchConfig``; the BLAST
structure (or any baseline structure) is selected orthogonally via
``StructureConfig`` so each arch runs as dense or compressed.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.core.structures import StructureConfig
from repro.quant import QuantConfig


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    d_shared: int = 0
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class MLACfg:
    q_lora_rank: int
    kv_lora_rank: int
    rope_head_dim: int
    nope_head_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class SSDCfg:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk: int = 128
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class RGLRUCfg:
    lru_width: int = 0      # 0 → d_model
    conv_width: int = 4
    c: float = 8.0


@dataclasses.dataclass(frozen=True)
class EncoderCfg:
    """Whisper-style encoder; the conv/mel frontend is a stub — input_specs
    provides precomputed frame embeddings (B, n_frames, d_model)."""

    n_layers: int
    n_frames: int = 1500


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: int = 0                 # 0 → d_model // n_heads
    ffn_kind: str = "swiglu"          # swiglu | gelu | none
    qkv_bias: bool = False
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    rope_theta: float = 10000.0
    pos_embed: str = "rope"           # rope | learned | sinusoidal | none
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    embed_scale: bool = False         # gemma-style sqrt(d_model) embed scaling
    # Granite-style scalars; the defaults add no operation to a program
    embedding_multiplier: float = 1.0  # embeddings times this
    attention_multiplier: float = 0.0  # score scale; 0 → 1/sqrt(head_dim)
    residual_multiplier: float = 1.0   # each block's branch times this
    logits_scaling: float = 1.0        # logits divided by this
    rms_norm_eps: float = 1e-6

    # per-layer mixer pattern, cycled over n_layers:
    #   'attn' | 'local_attn' | 'rglru' | 'ssd' | 'mla'
    pattern: Sequence[str] = ("attn",)
    window: int = 0                   # sliding-window size for 'local_attn'

    moe: MoECfg | None = None
    mla: MLACfg | None = None
    ssd: SSDCfg | None = None
    rglru: RGLRUCfg | None = None
    encoder: EncoderCfg | None = None
    mtp: bool = False                 # DeepSeek-V3 multi-token prediction head

    embeds_input: bool = False        # llava/whisper-enc: inputs are embeddings
    sub_quadratic: bool = False       # supports long_500k decode

    # structure of the linear layers (the paper's technique).  ``structure``
    # covers attention/mixer projections; ``structure_ffn`` (if set) overrides
    # for FFN / MoE-expert linears — the paper uses different ranks per role
    # (Table 9: r=1024 attn, r=1488 MLP for Llama-7B at 50%).
    structure: StructureConfig = dataclasses.field(default_factory=StructureConfig)
    structure_ffn: StructureConfig | None = None
    max_seq: int = 8192               # learned-pos table size (pos_embed=learned)

    # execution
    # legacy flag, now a full alias for quant.cache="int8" (quantizes every
    # family's cache — MLA latent and SSD/RG-LRU state included, not just
    # attention KV as before PR 4)
    kv_quant: bool = False
    # serving-time storage formats (weights / caches); see repro/quant.
    # ``quant.weights`` drives Engine quantize-at-load and LM.quantize_params;
    # ``quant.cache`` switches every family's KV/latent/state cache to int8.
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    scan_layers: bool = True
    remat: bool = True
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    optimizer_dtype: str = "float32"  # AdamW m/v dtype (bf16 for huge archs)
    q_chunk: int = 512                # chunked-attention tile sizes (XLA path)
    kv_chunk: int = 1024

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def cache_quant(self) -> bool:
        """int8 caches requested (new ``quant.cache`` knob or legacy flag)."""
        return self.kv_quant or self.quant.cache != "none"

    @property
    def ffn_structure(self) -> StructureConfig:
        return self.structure_ffn or self.structure

    def layer_kinds(self) -> list[str]:
        pat = list(self.pattern)
        return [pat[i % len(pat)] for i in range(self.n_layers)]

    def with_structure(self, structure: StructureConfig) -> "ArchConfig":
        return dataclasses.replace(self, structure=structure)

    def reduced(self, **overrides) -> "ArchConfig":
        """Small same-family variant for CPU smoke tests."""
        # two periods of a short layer pattern, one of a long one
        period = len(self.pattern)
        small: dict = dict(
            vocab=min(self.vocab, 512),
            d_model=min(self.d_model, 64),
            n_layers=min(self.n_layers, period * (1 if period > 4 else 2)),
            d_ff=min(self.d_ff, 128) if self.d_ff else 0,
            param_dtype="float32",
            compute_dtype="float32",
            scan_layers=self.scan_layers,
            remat=False,
            q_chunk=32,
            kv_chunk=32,
        )
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        small.update(n_heads=n_heads, n_kv_heads=n_kv, head_dim=16)
        if self.window:
            small["window"] = 16
        if self.moe:
            small["moe"] = dataclasses.replace(
                self.moe, n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2), d_expert=32,
                d_shared=32 if self.moe.n_shared else 0,
                dense_d_ff=64 if self.moe.first_dense_layers else 0,
                first_dense_layers=min(self.moe.first_dense_layers, 1))
            small["n_layers"] = max(small["n_layers"],
                                    (self.moe.first_dense_layers and 1) + 2)
        if self.mla:
            small["mla"] = MLACfg(q_lora_rank=32, kv_lora_rank=16,
                                  rope_head_dim=8, nope_head_dim=16, v_head_dim=16)
            small["head_dim"] = 0
        if self.ssd:
            small["ssd"] = dataclasses.replace(self.ssd, d_state=16, head_dim=8, chunk=8)
        if self.rglru:
            small["rglru"] = dataclasses.replace(self.rglru, lru_width=0)
        if self.encoder:
            small["encoder"] = dataclasses.replace(self.encoder, n_layers=2, n_frames=24)
        def shrink(st):
            if st is not None and st.kind in ("blast", "monarch", "block_diag"):
                return dataclasses.replace(st, b=min(st.b, 4), rank=None)
            return st
        small["structure"] = shrink(self.structure)
        small["structure_ffn"] = shrink(self.structure_ffn)
        small["max_seq"] = 256
        small.update(overrides)
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# Assigned input-shape grid (the 4 shapes every LM arch is paired with).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeCfg:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeCfg] = {
    "train_4k": ShapeCfg("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCfg("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCfg("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCfg("long_500k", 524288, 1, "decode"),
}


def shape_applicable(arch: ArchConfig, shape: ShapeCfg) -> tuple[bool, str]:
    """Whether a (arch × shape) cell runs, and why not if it doesn't."""
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return False, "long_500k needs sub-quadratic attention (skip: full-attn arch)"
    return True, ""
