"""Architecture registry: the assigned archs + the paper's own models.

Every config is importable and selectable via ``--arch <id>``; the BLAST
structure (keep=0.5, b=16 — the paper's Llama-7B headline setting) is the
default for assigned archs; ``variant(cfg, 'dense'|'blast50'|...)`` switches
the structure without touching the architecture.
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import ArchConfig, SHAPES, ShapeCfg, shape_applicable  # noqa: F401
from repro.core.structures import StructureConfig

from repro.configs.recurrentgemma_2b import CONFIG as recurrentgemma_2b
from repro.configs.granite_moe_1b import CONFIG as granite_moe_1b
from repro.configs.deepseek_v3_671b import CONFIG as deepseek_v3_671b
from repro.configs.smollm_135m import CONFIG as smollm_135m
from repro.configs.internlm2_1_8b import CONFIG as internlm2_1_8b
from repro.configs.granite_3_2b import CONFIG as granite_3_2b
from repro.configs.granite_4_h_micro import CONFIG as granite_4_h_micro
from repro.configs.qwen1_5_32b import CONFIG as qwen1_5_32b
from repro.configs.mamba2_130m import CONFIG as mamba2_130m
from repro.configs.whisper_base import CONFIG as whisper_base
from repro.configs.llava_next_34b import CONFIG as llava_next_34b
from repro.configs.paper_models import GPT2_BLAST, VIT_BLAST, LLAMA7B_BLAST

ARCHS: dict[str, ArchConfig] = {
    "recurrentgemma-2b": recurrentgemma_2b,
    "granite-moe-1b-a400m": granite_moe_1b,
    "deepseek-v3-671b": deepseek_v3_671b,
    "smollm-135m": smollm_135m,
    "internlm2-1.8b": internlm2_1_8b,
    "granite-3-2b": granite_3_2b,
    "granite-4-h-micro": granite_4_h_micro,
    "qwen1.5-32b": qwen1_5_32b,
    "mamba2-130m": mamba2_130m,
    "whisper-base": whisper_base,
    "llava-next-34b": llava_next_34b,
    # paper's own models
    "gpt2-blast": GPT2_BLAST,
    "vit-base-blast": VIT_BLAST,
    "llama7b-blast": LLAMA7B_BLAST,
}

ASSIGNED = [k for k in ARCHS if not k.endswith("-blast")]

VARIANTS = ("blast50", "blast80", "dense", "low_rank50", "monarch50",
            "block_diag", "pixelfly50")


def variant(cfg: ArchConfig, name: str) -> ArchConfig:
    """Swap the linear-layer structure, keeping the architecture fixed."""
    b = cfg.structure.b if cfg.structure.kind in ("blast", "monarch") else 16
    table = {
        "dense": StructureConfig(kind="dense"),
        "blast50": StructureConfig(kind="blast", b=b, keep_ratio=0.5),
        "blast80": StructureConfig(kind="blast", b=b, keep_ratio=0.8),
        "low_rank50": StructureConfig(kind="low_rank", keep_ratio=0.5),
        "monarch50": StructureConfig(kind="monarch", b=b, keep_ratio=0.5),
        "block_diag": StructureConfig(kind="block_diag", b=b, keep_ratio=0.5),
        "pixelfly50": StructureConfig(kind="pixelfly", b=b, keep_ratio=0.5),
    }
    st = table[name]
    return dataclasses.replace(cfg, structure=st, structure_ffn=None)


def get(name: str, structure: str | None = None) -> ArchConfig:
    cfg = ARCHS[name]
    return variant(cfg, structure) if structure else cfg
