"""Work and bytes of a decoder step, counted from a configuration's shapes.

Everything here is a function of the configuration file and of the token
positions a step processes, never of the compiled program: a change that
removes a copy or swaps a kernel is judged against the same work.

FLOPs count a multiply-add as two.  A BLAST linear with b blocks and rank r
does (m + n + b^2) r multiply-adds per token (paper Alg. 1), a dense one m n.
Attention of one query at context c does 2 hq hd c multiply-adds per layer
(scores and weighted values).  The vocabulary head counts only for tokens
that are sampled; padding columns and rows never count.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def blocks(d_in: int, d_out: int, b: int) -> int:
    """Largest block count <= b dividing both sides."""
    bb = min(b, d_in, d_out)
    while bb > 1 and (d_in % bb or d_out % bb):
        bb -= 1
    return max(bb, 1)


def blast_rank(d_in: int, d_out: int, b: int, keep: float, align: int) -> int:
    """Largest rank whose BLAST parameters stay within keep * d_in * d_out,
    rounded down to a multiple of ``align``."""
    r = int(keep * d_out * d_in // (d_out + d_in + b * b))
    if align > 1 and r >= 2 * align:
        r = (r // align) * align
    return max(r, 1)


def linear_shapes(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, d_in, d_out) of every linear in one layer."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return [("qkv", d, (hq + 2 * hkv) * hd), ("out", hq * hd, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def linear_factors(cfg: dict, d_in: int, d_out: int) -> dict:
    """Structure of one linear: kind, and for BLAST its b and r."""
    st = cfg["structure"]
    if st["kind"] == "dense":
        return {"kind": "dense"}
    if st["kind"] != "blast":
        raise ValueError(f"no counts for structure {st['kind']!r}")
    b = blocks(d_in, d_out, st["b"])
    return {"kind": "blast", "b": b,
            "r": blast_rank(d_in, d_out, b, st["keep_ratio"],
                            st.get("rank_align", 1))}


def linear_params(cfg: dict, d_in: int, d_out: int) -> int:
    """Stored parameters of one linear (also its multiply-adds per token)."""
    f = linear_factors(cfg, d_in, d_out)
    if f["kind"] == "dense":
        return d_in * d_out
    return (d_in + d_out + f["b"] ** 2) * f["r"]


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
