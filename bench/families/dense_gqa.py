"""Counts and program check of the ``dense_gqa`` family: a decoder of
identical layers, each grouped-query attention with rotary positions and
one key/value pair per token in the cache, then a SwiGLU MLP, every linear
dense or BLAST (``bench/counts.py``), RMSNorm, a tied or untied vocabulary
head.  Granite-3.0 and Llama are of this family.

FLOPs count a multiply-add as two.  Attention of one query at context c
does 2 hq hd c multiply-adds per layer (scores and weighted values).  The
vocabulary head counts only for tokens that are sampled; padding columns
and rows never count.
"""

from __future__ import annotations

import math

from bench.counts import DTYPE_BYTES, linear_factors, linear_params


def program_config(cfg: dict, reduced: bool = False):
    """The program's ArchConfig for a configuration file; checks that every
    size and option the file states is what the program will run."""
    import dataclasses
    from repro import configs
    from repro.core.structures import StructureConfig

    st = cfg["structure"]
    structure = (StructureConfig(kind="dense") if st["kind"] == "dense" else
                 StructureConfig(kind=st["kind"], b=st["b"],
                                 keep_ratio=st["keep_ratio"]))
    arch = dataclasses.replace(
        configs.get(cfg["arch"]), structure=structure, structure_ffn=None,
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])
    if reduced:
        arch = arch.reduced(param_dtype=cfg["param_dtype"],
                            compute_dtype=cfg["compute_dtype"])
        return arch, reduced_config(cfg, arch)
    have = {"hidden_size": arch.d_model, "intermediate_size": arch.d_ff,
            "num_hidden_layers": arch.n_layers,
            "num_attention_heads": arch.n_heads,
            "num_key_value_heads": arch.n_kv_heads,
            "head_dim": arch.head_dim_, "vocab_size": arch.vocab,
            "tie_word_embeddings": arch.tie_embeddings,
            "rope_theta": arch.rope_theta}
    wrong = {k: (cfg[k], v) for k, v in have.items() if cfg[k] != v}
    plain = (arch.pattern == ("attn",) and arch.ffn_kind == "swiglu"
             and arch.norm == "rmsnorm" and arch.pos_embed == "rope"
             and not arch.qkv_bias and not arch.embed_scale
             and not arch.logit_softcap and arch.moe is None)
    if wrong or not plain:
        raise SystemExit(f"{cfg['name']}: the program's {cfg['arch']} differs "
                         f"from the configuration file: {wrong or 'layers'}")
    return arch, cfg


def reduced_config(cfg: dict, arch) -> dict:
    """The configuration dictionary of a ``.reduced()`` program config (CPU
    rehearsals of the harness only)."""
    out = dict(cfg)
    out.update(hidden_size=arch.d_model, intermediate_size=arch.d_ff,
               num_hidden_layers=arch.n_layers, num_attention_heads=arch.n_heads,
               num_key_value_heads=arch.n_kv_heads, head_dim=arch.head_dim_,
               vocab_size=arch.vocab,
               attention_multiplier=1.0 / math.sqrt(arch.head_dim_))
    if cfg["structure"]["kind"] != "dense":
        out["structure"] = dict(cfg["structure"], b=arch.structure.b)
    return out


def linear_shapes(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, d_in, d_out) of every linear in one layer."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return [("qkv", d, (hq + 2 * hkv) * hd), ("out", hq * hd, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def param_count(cfg: dict) -> int:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_layer = sum(linear_params(cfg, i, o) for _, i, o in linear_shapes(cfg))
    per_layer += 2 * d                                   # two norm gains
    n = L * per_layer + cfg["vocab_size"] * d + d        # embed, final norm
    if not cfg["tie_word_embeddings"]:
        n += cfg["vocab_size"] * d
    return n


def param_bytes(cfg: dict) -> int:
    return param_count(cfg) * DTYPE_BYTES[cfg["param_dtype"]]


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one token in every layer's cache."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"] * DTYPE_BYTES[cfg["compute_dtype"]])


def linear_flops_per_token(cfg: dict) -> int:
    return 2 * cfg["num_hidden_layers"] * sum(
        linear_params(cfg, i, o) for _, i, o in linear_shapes(cfg))


def attn_flops_per_context(cfg: dict) -> int:
    """Attention FLOPs of one query per token of context, all layers."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def span_flops(cfg: dict, start: int, stop: int) -> int:
    """FLOPs of feeding the tokens at positions start..stop-1 of one
    sequence (token at position p attends to p + 1 tokens), head excluded."""
    n = stop - start
    if n <= 0:
        return 0
    ctx = (start + 1 + stop) * n // 2                    # sum of p + 1
    return n * linear_flops_per_token(cfg) + ctx * attn_flops_per_context(cfg)


def decode_step(cfg: dict, contexts: list[int], peak: dict) -> dict:
    """Least time of one decode step whose live rows hold ``contexts``
    tokens each after the step (the new token included).

    Needed bytes: every weight once at its stored dtype, the keys and
    values of each live row's context, and those written for the new
    tokens.  Returns flops, bytes, the least time and which peak bounds it.
    """
    flops = sum(span_flops(cfg, c - 1, c) + head_flops(cfg) for c in contexts)
    kv = kv_bytes_per_token(cfg)
    nbytes = param_bytes(cfg) + kv * sum(contexts) + kv * len(contexts)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes, "least_s": max(t_flops, t_bytes),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}


def describe(cfg: dict) -> dict:
    """The counts a reader of PERF.md wants, in one dictionary."""
    return {"params": param_count(cfg), "param_bytes": param_bytes(cfg),
            "kv_bytes_per_token": kv_bytes_per_token(cfg),
            "linear_flops_per_token": linear_flops_per_token(cfg),
            "linears": {n: linear_factors(cfg, i, o)
                        for n, i, o in linear_shapes(cfg)}}
