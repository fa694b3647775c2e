"""Work and bytes of a decoder step, counted from a configuration's shapes.

Everything here is a function of the configuration file and of the token
positions a step processes, never of the compiled program: a change that
removes a copy or swaps a kernel is judged against the same work.

What a family's layers hold lives in its own module,
``bench/families/<family>.py``, found by the configuration's ``family``.
Every family module defines:

  program_config(cfg, reduced)   the program's ArchConfig and the
      dictionary the run uses; refuses a program that differs from the file
  param_bytes(cfg)               stored weight bytes
  head_flops(cfg)                FLOPs of the vocabulary head, one token
  span_flops(cfg, start, stop)   FLOPs of feeding positions start..stop-1
      of one sequence, head excluded
  decode_step(cfg, contexts, peak)
      flops, bytes, least time and its bound of one decode step whose live
      rows hold ``contexts`` tokens each after it
  describe(cfg)                  the counts a reader of PERF.md wants

The functions below dispatch to it.  What follows them is shared by every
family whose linears are dense or BLAST: a BLAST linear with b blocks and
rank r does (m + n + b^2) r multiply-adds per token (paper Alg. 1), a dense
one m n.
"""

from __future__ import annotations

from pathlib import Path

from bench import ROOT, load

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def family(cfg: dict, root: Path = ROOT):
    """The module of the configuration's family under ``root``."""
    return load("families", cfg["family"], root)


def param_bytes(cfg: dict) -> int:
    return family(cfg).param_bytes(cfg)


def head_flops(cfg: dict) -> int:
    return family(cfg).head_flops(cfg)


def span_flops(cfg: dict, start: int, stop: int) -> int:
    return family(cfg).span_flops(cfg, start, stop)


def decode_step(cfg: dict, contexts: list[int], peak: dict) -> dict:
    return family(cfg).decode_step(cfg, contexts, peak)


def describe(cfg: dict) -> dict:
    return family(cfg).describe(cfg)


def __getattr__(name: str):
    """``dense_gqa``'s own terms, under the names they had here."""
    if name in ("kv_bytes_per_token", "linear_flops_per_token",
                "attn_flops_per_context"):
        return getattr(load("families", "dense_gqa"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


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
