"""Counts and program check of the ``hybrid_mamba2`` family: a decoder whose
layers repeat a period of Mamba-2 (SSD) mixers and grouped-query attention
layers with no position embedding (NoPE), every layer ending in a SwiGLU
MLP; every linear dense or BLAST (``bench/counts.py``), RMSNorm, a tied
vocabulary head, and Granite's four scalar multipliers.  Granite-4.0-H is of
this family (``model_type`` ``granitemoehybrid`` with no experts).

A Mamba-2 mixer of H heads of P channels and state N keeps, per row, the
state h (H, P, N) at the file's ``state_dtype`` and the last K - 1 inputs of
its depthwise conv (K the conv width) at the compute dtype.  Its work is
counted by the recurrent form whatever form the program computes:
h <- a h + dt B (x) x and y = C h are 5 H P N FLOPs a token (the decay, the
outer product and its add, the read-out's multiply-adds); the conv, gates
and norms are elementwise and, like every norm here, not counted.  FLOPs
count a multiply-add as two; attention of one query at context c does
2 hq hd c multiply-adds per attention layer; the vocabulary head counts only
for tokens that are sampled; padding columns and rows never count.
"""

from __future__ import annotations

from bench.counts import DTYPE_BYTES, linear_factors, linear_params

KINDS = {"ssd": "mamba", "attn": "attention"}   # program kind -> layer_types


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
        # two KV heads, so that the reduced attention still groups queries
        arch = arch.reduced(param_dtype=cfg["param_dtype"],
                            compute_dtype=cfg["compute_dtype"], n_kv_heads=2)
        return arch, reduced_config(cfg, arch)
    plain = (arch.ssd is not None and arch.ffn_kind == "swiglu"
             and arch.norm == "rmsnorm" and not arch.embed_scale
             and not arch.logit_softcap and arch.moe is None
             and set(arch.pattern) <= set(KINDS) and not arch.cache_quant)
    wrong = {k: (cfg[k], v) for k, v in _program_keys(arch).items()
             if cfg[k] != v} if plain else {}
    if wrong or not plain:
        raise SystemExit(f"{cfg['name']}: the program's {cfg['arch']} differs "
                         f"from the configuration file: {wrong or 'layers'}")
    return arch, cfg


def _program_keys(arch) -> dict:
    """The configuration file's keys as the program runs them."""
    ssd = arch.ssd
    return {
        "hidden_size": arch.d_model, "intermediate_size": arch.d_ff,
        "shared_intermediate_size": arch.d_ff,
        "num_hidden_layers": arch.n_layers,
        "layer_types": [KINDS.get(k, k) for k in arch.layer_kinds()],
        "num_attention_heads": arch.n_heads,
        "num_key_value_heads": arch.n_kv_heads, "head_dim": arch.head_dim_,
        "vocab_size": arch.vocab, "tie_word_embeddings": arch.tie_embeddings,
        "position_embedding_type": ("nope" if arch.pos_embed == "none"
                                    else arch.pos_embed),
        "attention_bias": arch.qkv_bias,
        "hidden_act": "silu" if arch.ffn_kind == "swiglu" else arch.ffn_kind,
        "normalization_function": arch.norm,
        "mamba_d_state": ssd.d_state, "mamba_d_head": ssd.head_dim,
        "mamba_expand": ssd.expand,
        "mamba_n_heads": ssd.expand * arch.d_model // ssd.head_dim,
        "mamba_n_groups": 1, "mamba_d_conv": ssd.conv_width,
        "mamba_chunk_size": ssd.chunk,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "embedding_multiplier": arch.embedding_multiplier,
        "attention_multiplier": arch.attention_multiplier,
        "residual_multiplier": arch.residual_multiplier,
        "logits_scaling": arch.logits_scaling,
        "rms_norm_eps": arch.rms_norm_eps,
    }


def reduced_config(cfg: dict, arch) -> dict:
    """The configuration dictionary of a ``.reduced()`` program config (CPU
    rehearsals of the harness and the tests only)."""
    out = dict(cfg)
    out.update(_program_keys(arch))
    if cfg["structure"]["kind"] != "dense":
        out["structure"] = dict(cfg["structure"], b=arch.structure.b)
    return out


# ---------------------------------------------------------------------------
# Shapes.
# ---------------------------------------------------------------------------


def ssd_dims(cfg: dict) -> dict:
    """Heads H, head channels P, state N, groups G, conv width K, inner
    width d_inner = H P and the conv's channels d_inner + 2 G N."""
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, G = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return {"H": H, "P": P, "N": N, "G": G, "K": cfg["mamba_d_conv"],
            "d_inner": H * P, "conv_ch": H * P + 2 * G * N}


def n_layers(cfg: dict, kind: str) -> int:
    return sum(1 for t in cfg["layer_types"] if t == kind)


def linear_shapes(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    """(name, d_in, d_out) of every linear in one layer of ``kind``
    (``mamba`` or ``attention``)."""
    d, ff = cfg["hidden_size"], cfg["shared_intermediate_size"]
    mlp = [("gate", d, ff), ("up", d, ff), ("down", ff, d)]
    if kind == "mamba":
        s = ssd_dims(cfg)
        return [("in_proj", d, 2 * s["d_inner"] + 2 * s["G"] * s["N"]
                 + s["H"]), ("out_proj", s["d_inner"], d)] + mlp
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return [("qkv", d, (hq + 2 * hkv) * hd), ("out", hq * hd, d)] + mlp


def _linears(cfg: dict, kind: str) -> int:
    return sum(linear_params(cfg, i, o) for _, i, o in linear_shapes(cfg, kind))


def param_bytes(cfg: dict) -> int:
    """Stored weight bytes: every weight at the parameter dtype but the SSD
    mixers' per-head A_log, D and dt bias, which the program keeps in
    float32."""
    pb = DTYPE_BYTES[cfg["param_dtype"]]
    d, s = cfg["hidden_size"], ssd_dims(cfg)
    mamba = (_linears(cfg, "mamba") + s["K"] * s["conv_ch"] + s["conv_ch"]
             + s["d_inner"] + 2 * d) * pb + 3 * s["H"] * 4
    attn = (_linears(cfg, "attention") + 2 * d) * pb
    n = (n_layers(cfg, "mamba") * mamba + n_layers(cfg, "attention") * attn
         + (cfg["vocab_size"] * d + d) * pb)
    if not cfg["tie_word_embeddings"]:
        n += cfg["vocab_size"] * d * pb
    return n


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state in every Mamba-2 layer: h at the file's
    ``state_dtype`` and the conv window at the compute dtype."""
    s = ssd_dims(cfg)
    per_layer = (s["H"] * s["P"] * s["N"] * DTYPE_BYTES[cfg["state_dtype"]]
                 + (s["K"] - 1) * s["conv_ch"]
                 * DTYPE_BYTES[cfg["compute_dtype"]])
    return n_layers(cfg, "mamba") * per_layer


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one token in every attention layer's cache."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * n_layers(cfg, "attention") * DTYPE_BYTES[cfg["compute_dtype"]])


def linear_flops_per_token(cfg: dict) -> int:
    return 2 * (n_layers(cfg, "mamba") * _linears(cfg, "mamba")
                + n_layers(cfg, "attention") * _linears(cfg, "attention"))


def ssd_flops_per_token(cfg: dict) -> int:
    """The recurrence's FLOPs of one token, all Mamba-2 layers."""
    s = ssd_dims(cfg)
    return 5 * s["H"] * s["P"] * s["N"] * n_layers(cfg, "mamba")


def attn_flops_per_context(cfg: dict) -> int:
    """Attention FLOPs of one query per token of context, all layers."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * n_layers(cfg, "attention"))


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def span_flops(cfg: dict, start: int, stop: int) -> int:
    """FLOPs of feeding the tokens at positions start..stop-1 of one
    sequence (token at position p attends to p + 1 tokens), head excluded."""
    n = stop - start
    if n <= 0:
        return 0
    ctx = (start + 1 + stop) * n // 2                    # sum of p + 1
    return (n * (linear_flops_per_token(cfg) + ssd_flops_per_token(cfg))
            + ctx * attn_flops_per_context(cfg))


def decode_step(cfg: dict, contexts: list[int], peak: dict) -> dict:
    """Least time of one decode step whose live rows hold ``contexts``
    tokens each after the step (the new token included).

    Needed bytes: every weight once at its stored dtype; each live row's
    recurrent state, read and written; the keys and values of each live
    row's context in the attention layers, and those written for the new
    tokens.  Returns flops, bytes, the least time and which peak bounds it.
    """
    flops = sum(span_flops(cfg, c - 1, c) + head_flops(cfg) for c in contexts)
    kv = kv_bytes_per_token(cfg)
    nbytes = (param_bytes(cfg) + 2 * state_bytes(cfg) * len(contexts)
              + kv * sum(contexts) + kv * len(contexts))
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes, "least_s": max(t_flops, t_bytes),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}


def describe(cfg: dict) -> dict:
    """The counts a reader of PERF.md wants, in one dictionary."""
    return {"param_bytes": param_bytes(cfg), "state_bytes": state_bytes(cfg),
            "kv_bytes_per_token": kv_bytes_per_token(cfg),
            "linear_flops_per_token": linear_flops_per_token(cfg),
            "ssd_flops_per_token": ssd_flops_per_token(cfg),
            "linears": {kind: {n: linear_factors(cfg, i, o)
                               for n, i, o in linear_shapes(cfg, kind)}
                        for kind in ("mamba", "attention")}}
