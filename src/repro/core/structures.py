"""Unified structured-linear interface: dense / low_rank / monarch /
block_diag / blast behind one spec, so every model layer is structure-
agnostic and the paper's baselines (§4) are first-class.

Each structure defines:
  * ``init(key, dtype)``   → params pytree (dict of arrays)
  * ``apply(params, x)``   → ``x: (..., d_in) → (..., d_out)``
  * ``quantize(params, bits)`` → params with per-block-int QArray leaves
  * ``apply_q(qparams, x)`` → same contract as ``apply`` on quantized params,
    with dequantization fused at the innermost matmul: weights enter the
    contraction as integer codes and the per-block scales multiply the
    *product*, never a materialized float weight tensor
  * ``num_params``, ``flops_per_token`` (multiplications, matching paper's
    FLOPs accounting which counts multiplications)
  * ``logical_axes``       → dict param-name → tuple of logical axis names,
    consumed by launch/sharding.py to build PartitionSpecs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro import quant as qt
from repro.core import blast as blast_lib

Params = dict[str, jax.Array]

STRUCTURES = ("dense", "blast", "low_rank", "monarch", "block_diag",
              "pixelfly")


@dataclasses.dataclass(frozen=True)
class StructureConfig:
    """How to structure the linear layers of a model.

    kind:        one of STRUCTURES
    b:           number of blocks per axis (blast / monarch / block_diag)
    keep_ratio:  target params / dense params; used to solve ranks when an
                 explicit rank is not given.
    rank:        explicit rank override (blast r / low-rank t / monarch k)
    """

    kind: str = "dense"
    b: int = 16
    keep_ratio: float = 0.5
    rank: int | None = None
    # BLAST tensor-parallel scheme: "rank" (Megatron-2-layer analogue: shard
    # r, one output AR per linear) or "block" (shard the b block axis; stage
    # 1/3 run block-local and the cross-block coupling reshards via
    # all-to-all/reduce-scatter of the (tokens, b, r) intermediate).
    tp: str = "rank"

    def __post_init__(self):
        if self.kind not in STRUCTURES:
            raise ValueError(f"unknown structure kind {self.kind!r}")


@dataclasses.dataclass(frozen=True)
class LinearSpec:
    kind: str
    d_in: int
    d_out: int
    shapes: dict[str, tuple[int, ...]]
    logical_axes: dict[str, tuple[str | None, ...]]
    init: Callable[..., Params]
    apply: Callable[[Params, jax.Array], jax.Array]
    num_params: int
    flops_per_token: int
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)
    quantize: Callable[..., Params] = None
    apply_q: Callable[[Params, jax.Array], jax.Array] = None

    def abstract_params(self, dtype=jnp.float32) -> dict[str, jax.ShapeDtypeStruct]:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in self.shapes.items()}


def _pick_blocks(d_in: int, d_out: int, b: int) -> int:
    """Largest b' ≤ b dividing both dims (keeps configs robust to odd dims)."""
    bb = min(b, d_in, d_out)
    while bb > 1 and (d_in % bb or d_out % bb):
        bb -= 1
    return max(bb, 1)


def _block_quantizer(block_axes: dict[str, tuple[int, ...]]):
    """Build a ``quantize(params, bits)`` that maps each named param to a
    per-block QArray (params not listed — e.g. bias — pass through)."""
    def quantize(params: Params, bits: int = 8) -> Params:
        out: Params = {}
        for k, v in params.items():
            ba = block_axes.get(k)
            out[k] = v if ba is None else qt.quantize(v, bits=bits,
                                                      block_axes=ba)
        return out
    return quantize


def _iv(qa, dtype):
    """Integer codes of a QArray cast for the MXU contraction (int8 values
    are exactly representable in bf16/f32 — the cast is lossless)."""
    return qt.int_values(qa).astype(dtype)


# -- dense ------------------------------------------------------------------


def _dense_spec(d_in: int, d_out: int, cfg: StructureConfig) -> LinearSpec:
    shapes = {"w": (d_in, d_out)}

    def init(key, dtype=jnp.float32, scale=None):
        std = scale if scale is not None else 1.0 / math.sqrt(d_in)
        return {"w": (std * jax.random.normal(key, (d_in, d_out))).astype(dtype)}

    def apply(params, x):
        return x @ params["w"]

    def apply_q(params, x):
        w = params["w"]
        y = x @ _iv(w, x.dtype)                 # int codes on the MXU
        return (y * w.scale[0]).astype(x.dtype)  # dequant fused post-matmul

    return LinearSpec(
        kind="dense", d_in=d_in, d_out=d_out, shapes=shapes,
        logical_axes={"w": ("in", "out")},
        init=init, apply=apply,
        num_params=d_in * d_out, flops_per_token=d_in * d_out,
        quantize=_block_quantizer({"w": (0,)}),  # per-output-channel scales
        apply_q=apply_q,
    )


# -- blast ------------------------------------------------------------------


def _blast_spec(d_in: int, d_out: int, cfg: StructureConfig) -> LinearSpec:
    m, n = d_out, d_in
    b = _pick_blocks(n, m, cfg.b)
    r = cfg.rank or blast_lib.rank_for_compression(m, n, b, cfg.keep_ratio,
                                                   align=16)
    p, q = m // b, n // b

    def init(key, dtype=jnp.float32, scale=None):
        params = blast_lib.init(key, m, n, b, r, dtype=dtype)
        return {"U": params.U, "S": params.S, "V": params.V}

    def apply(params, x):
        return blast_lib.matmul(x, blast_lib.BlastParams(params["U"], params["S"], params["V"]))

    def apply_q(params, x):
        """Alg. 1 with per-block int8/int4 factors; each stage dequantizes by
        a scalar-per-block multiply on the stage *output* (XLA mirror of the
        fused Pallas kernel in kernels/blast_matmul.py).  With the process-
        wide activation mode set to "int8" (W8A8/W4A8), x is quantized per
        token and stage 1 contracts int8 codes in int32, dequantizing once
        with the fused ``sx · sv_j`` product — mirroring the integer
        kernels."""
        Uq, Sq, Vq = params["U"], params["S"], params["V"]
        lead = x.shape[:-1]
        if activations_mode() == "int8":
            xq, sx = qt.quantize_act(x)
            z = jnp.einsum("...jq,jqr->...jr", xq.reshape(*lead, b, q),
                           qt.int_values(Vq),
                           preferred_element_type=jnp.int32)
            z = (z.astype(jnp.float32) * sx[..., None]    # (..., 1, 1)
                 * Vq.scale[:, :, 0])                     # (b, 1) per block
        else:
            xb = x.reshape(*lead, b, q)
            z = jnp.einsum("...jq,jqr->...jr", xb, _iv(Vq, x.dtype))
            z = z.astype(jnp.float32) * Vq.scale[:, :, 0]
        s = qt.int_values(Sq).astype(jnp.float32) * Sq.scale  # in-register
        w = jnp.einsum("...jr,ijr->...ir", z, s)
        y = jnp.einsum("...ir,ipr->...ip", w, _iv(Uq, jnp.float32))
        y = y * Uq.scale[:, :, 0]
        return y.reshape(*lead, m).astype(x.dtype)

    if cfg.tp == "block":
        axes = {"U": ("blocks_tp", "out_block", None),
                "S": ("blocks_tp", "blocks_j", None),
                "V": ("blocks_tp", "in_block", None)}
    else:
        axes = {"U": ("blocks", "out_block", "rank"),
                "S": ("blocks", "blocks_j", "rank"),
                "V": ("blocks", "in_block", "rank")}
    return LinearSpec(
        kind="blast", d_in=d_in, d_out=d_out,
        shapes={"U": (b, p, r), "S": (b, b, r), "V": (b, q, r)},
        logical_axes=axes,
        init=init, apply=apply,
        num_params=blast_lib.num_params(m, n, b, r),
        flops_per_token=blast_lib.matvec_flops(m, n, b, r),
        meta={"b": b, "r": r},
        # one scale per U_i / V_j factor block, one per s_ij coupling vector
        quantize=_block_quantizer({"U": (1, 2), "S": (2,), "V": (1, 2)}),
        apply_q=apply_q,
    )


# -- low rank ---------------------------------------------------------------


def _low_rank_spec(d_in: int, d_out: int, cfg: StructureConfig) -> LinearSpec:
    t = cfg.rank or max(1, int(cfg.keep_ratio * d_in * d_out / (d_in + d_out)))
    if t >= 32:
        t = (t // 16) * 16  # TP-shardable rank

    def init(key, dtype=jnp.float32, scale=None):
        k1, k2 = jax.random.split(key)
        s1 = 1.0 / math.sqrt(d_in)
        s2 = 1.0 / math.sqrt(t)
        return {
            "w_down": (s1 * jax.random.normal(k1, (d_in, t))).astype(dtype),
            "w_up": (s2 * jax.random.normal(k2, (t, d_out))).astype(dtype),
        }

    def apply(params, x):
        return (x @ params["w_down"]) @ params["w_up"]

    def apply_q(params, x):
        d, u = params["w_down"], params["w_up"]
        h = (x @ _iv(d, x.dtype)) * d.scale[0]
        y = (h.astype(x.dtype) @ _iv(u, x.dtype)) * u.scale[0]
        return y.astype(x.dtype)

    return LinearSpec(
        kind="low_rank", d_in=d_in, d_out=d_out,
        shapes={"w_down": (d_in, t), "w_up": (t, d_out)},
        logical_axes={"w_down": ("in", "rank"), "w_up": ("rank", "out")},
        init=init, apply=apply,
        num_params=t * (d_in + d_out), flops_per_token=t * (d_in + d_out),
        meta={"rank": t},
        quantize=_block_quantizer({"w_down": (0,), "w_up": (0,)}),
        apply_q=apply_q,
    )


# -- monarch ----------------------------------------------------------------


def _monarch_spec(d_in: int, d_out: int, cfg: StructureConfig) -> LinearSpec:
    """Monarch/BLR: y = reshape(einsum(R, transpose(einsum(L, x)))).

    L: (b, q, k) block-diagonal over input blocks; permute; R: (k, b, c)
    block-diagonal over the k axis, with c == b so that out = (c, k) → m.
    k is solved from the parameter budget; requires k·b == d_out.
    """
    m, n = d_out, d_in
    b = _pick_blocks(n, m, cfg.b)
    q = n // b
    c = b
    k = m // c  # out = (c, k) flatten → exact-monarch mid width
    if cfg.rank is not None:
        k = cfg.rank
    else:
        # Budget: params = b·q·k + k·b·c ≤ keep·m·n  → k ≤ keep·m·n / (b(q+c))
        k_budget = int(cfg.keep_ratio * m * n / (b * (q + c)))
        k = max(1, min(k, k_budget))
    # If k no longer divides m we fall back to rectangular R: (k, b, m//b) and
    # flatten as (b_out, p) with p = m//b — the generalized BLR form.
    exact = (k * c == m)
    p = m // b

    def init(key, dtype=jnp.float32, scale=None):
        k1, k2 = jax.random.split(key)
        s1 = 1.0 / math.sqrt(q)
        s2 = 1.0 / math.sqrt(k)
        L = (s1 * jax.random.normal(k1, (b, q, k))).astype(dtype)
        R = (s2 * jax.random.normal(k2, (k, b, c if exact else p))).astype(dtype)
        return {"L": L, "R": R}

    def apply(params, x):
        lead = x.shape[:-1]
        xb = x.reshape(*lead, b, q)
        u = jnp.einsum("...bq,bqk->...bk", xb, params["L"])
        if exact:
            y = jnp.einsum("...bk,kbc->...ck", u, params["R"])  # (..., c, k)
            return y.reshape(*lead, m)
        y = jnp.einsum("...bk,kbp->...bp", u, params["R"])
        return y.reshape(*lead, m)

    def apply_q(params, x):
        Lq, Rq = params["L"], params["R"]
        lead = x.shape[:-1]
        xb = x.reshape(*lead, b, q)
        u = jnp.einsum("...bq,bqk->...bk", xb, _iv(Lq, x.dtype))
        u = u.astype(jnp.float32) * Lq.scale[:, :, 0]        # (b, 1)
        if exact:
            # contraction over b → R's scale must be constant over b: one
            # scale per k-indexed (b, c) block, applied on the k output axis
            y = jnp.einsum("...bk,kbc->...ck", u, _iv(Rq, jnp.float32))
            y = y * Rq.scale[:, 0, 0]                        # (k,)
        else:
            y = jnp.einsum("...bk,kbp->...bp", u, _iv(Rq, jnp.float32))
            y = y * Rq.scale[0, :, :]                        # (b, 1)
        return y.reshape(*lead, m).astype(x.dtype)

    n_params = b * q * k + k * b * (c if exact else p)
    return LinearSpec(
        kind="monarch", d_in=d_in, d_out=d_out,
        shapes={"L": (b, q, k), "R": (k, b, c if exact else p)},
        logical_axes={"L": ("blocks", "in_block", "rank"),
                      "R": ("rank", "blocks", "out_block")},
        init=init, apply=apply,
        num_params=n_params, flops_per_token=n_params,
        meta={"b": b, "k": k, "exact": exact},
        quantize=_block_quantizer(
            {"L": (1, 2), "R": (1, 2) if exact else (0, 2)}),
        apply_q=apply_q,
    )


# -- block diagonal ----------------------------------------------------------


def _block_diag_spec(d_in: int, d_out: int, cfg: StructureConfig) -> LinearSpec:
    # Budget: params = m·n/b → choose the smallest b' ≥ cfg.b meeting keep.
    b = _pick_blocks(d_in, d_out, cfg.b)
    if cfg.rank is None and cfg.keep_ratio < 1.0 / b:
        bb = math.ceil(1.0 / cfg.keep_ratio)
        b = _pick_blocks(d_in, d_out, max(bb, b))
    q, p = d_in // b, d_out // b

    def init(key, dtype=jnp.float32, scale=None):
        std = 1.0 / math.sqrt(q)
        return {"w": (std * jax.random.normal(key, (b, q, p))).astype(dtype)}

    def apply(params, x):
        lead = x.shape[:-1]
        xb = x.reshape(*lead, b, q)
        y = jnp.einsum("...bq,bqp->...bp", xb, params["w"])
        return y.reshape(*lead, d_out)

    def apply_q(params, x):
        w = params["w"]
        lead = x.shape[:-1]
        xb = x.reshape(*lead, b, q)
        y = jnp.einsum("...bq,bqp->...bp", xb, _iv(w, x.dtype))
        y = y.astype(jnp.float32) * w.scale[:, :, 0]         # (b, 1)
        return y.reshape(*lead, d_out).astype(x.dtype)

    return LinearSpec(
        kind="block_diag", d_in=d_in, d_out=d_out,
        shapes={"w": (b, q, p)},
        logical_axes={"w": ("blocks", "in_block", "out_block")},
        init=init, apply=apply,
        num_params=b * q * p, flops_per_token=b * q * p,
        meta={"b": b},
        quantize=_block_quantizer({"w": (1, 2)}),
        apply_q=apply_q,
    )


# -- pixelfly (block-sparse butterfly + low-rank, Chen et al. 2022) ----------


def _pixelfly_blocks(b: int) -> list[tuple[int, int]]:
    """Flat block-butterfly support: block (i, j) is live iff i == j or
    |i − j| is a power of two — the flattened butterfly connectivity used
    by Pixelated Butterfly's block-sparse component."""
    live = []
    for i in range(b):
        for j in range(b):
            d = abs(i - j)
            if d == 0 or (d & (d - 1)) == 0:
                live.append((i, j))
    return live


def _pixelfly_spec(d_in: int, d_out: int, cfg: StructureConfig) -> LinearSpec:
    """Pixelfly ≈ block-sparse butterfly W_s (+ optional low-rank W_lr).

    The paper evaluates Pixelfly as its block-sparse baseline (§4.1).  We
    implement the flat block-butterfly support with dense resident blocks —
    a gather → batched-GEMM → scatter-add chain (no zero padding), with the
    residual low-rank term solved from the remaining parameter budget."""
    b = _pick_blocks(d_in, d_out, cfg.b)
    q, p = d_in // b, d_out // b
    live = _pixelfly_blocks(b)
    nnz = len(live)
    sparse_params = nnz * q * p
    budget = cfg.keep_ratio * d_in * d_out
    t = max(0, int((budget - sparse_params) // (d_in + d_out)))
    if t >= 32:
        t = (t // 16) * 16
    rows = jnp.array([i for i, _ in live], jnp.int32)
    cols = jnp.array([j for _, j in live], jnp.int32)

    def init(key, dtype=jnp.float32, scale=None):
        k1, k2, k3 = jax.random.split(key, 3)
        fan_in = q * sum(1 for _, j in live)  # loose bound; per-row varies
        std = 1.0 / math.sqrt(max(q * (2 * int(math.log2(b)) + 1 if b > 1
                                       else 1), 1))
        params = {"w": (std * jax.random.normal(k1, (nnz, q, p))).astype(dtype)}
        if t:
            params["w_down"] = ((1.0 / math.sqrt(d_in))
                                * jax.random.normal(k2, (d_in, t))).astype(dtype)
            params["w_up"] = ((1.0 / math.sqrt(max(t, 1)))
                              * jax.random.normal(k3, (t, d_out))).astype(dtype)
        return params

    def apply(params, x):
        lead = x.shape[:-1]
        xb = x.reshape(*lead, b, q)
        xg = jnp.take(xb, cols, axis=-2)                 # (..., nnz, q)
        yb = jnp.einsum("...eq,eqp->...ep", xg, params["w"])
        y = jnp.zeros((*lead, b, p), yb.dtype).at[..., rows, :].add(yb)
        y = y.reshape(*lead, b * p)
        if "w_down" in params:
            y = y + (x @ params["w_down"]) @ params["w_up"]
        return y

    def apply_q(params, x):
        w = params["w"]
        lead = x.shape[:-1]
        xb = x.reshape(*lead, b, q)
        xg = jnp.take(xb, cols, axis=-2)
        yb = jnp.einsum("...eq,eqp->...ep", xg, _iv(w, x.dtype))
        yb = yb.astype(jnp.float32) * w.scale[:, :, 0]       # (nnz, 1)
        y = jnp.zeros((*lead, b, p), yb.dtype).at[..., rows, :].add(yb)
        y = y.reshape(*lead, b * p)
        if "w_down" in params:
            d, u = params["w_down"], params["w_up"]
            h = (x @ _iv(d, x.dtype)) * d.scale[0]
            y = y + (h.astype(x.dtype) @ _iv(u, x.dtype)) * u.scale[0]
        return y.astype(x.dtype)

    shapes = {"w": (nnz, q, p)}
    axes = {"w": ("blocks", "in_block", "out_block")}
    qaxes = {"w": (1, 2)}
    if t:
        shapes.update(w_down=(d_in, t), w_up=(t, d_out))
        axes.update(w_down=("in", "rank"), w_up=("rank", "out"))
        qaxes.update(w_down=(0,), w_up=(0,))
    n_params = sparse_params + t * (d_in + d_out)
    return LinearSpec(
        kind="pixelfly", d_in=d_in, d_out=d_out, shapes=shapes,
        logical_axes=axes, init=init, apply=apply,
        num_params=n_params, flops_per_token=n_params,
        meta={"b": b, "nnz": nnz, "rank": t},
        quantize=_block_quantizer(qaxes),
        apply_q=apply_q,
    )


_MAKERS = {
    "dense": _dense_spec,
    "blast": _blast_spec,
    "low_rank": _low_rank_spec,
    "monarch": _monarch_spec,
    "block_diag": _block_diag_spec,
    "pixelfly": _pixelfly_spec,
}


def make_linear(d_in: int, d_out: int, structure: StructureConfig | None = None,
                *, structured: bool = True) -> LinearSpec:
    """Build a linear spec. ``structured=False`` forces dense (e.g. router,
    norm-adjacent projections the paper keeps dense)."""
    cfg = structure or StructureConfig()
    if not structured:
        cfg = StructureConfig(kind="dense")
    return _MAKERS[cfg.kind](d_in, d_out, cfg)


# ---------------------------------------------------------------------------
# Nested-rank truncation: a rank-r BLAST factor set contains every lower-rank
# model for free — dropping trailing components of U/S/V (or low-rank
# w_down/w_up columns) yields a cheaper model sharing storage with the full
# one.  This is the draft side of self-speculative decoding (serve/engine.py):
# the draft and the verifier are the SAME weights at two ranks, so the only
# new serving state is the per-layer truncation plan.
# ---------------------------------------------------------------------------


_RANK_AXES = {"blast": {"U": 2, "S": 2, "V": 2},
              "low_rank": {"w_down": 1, "w_up": 0}}


def rank_kind(params: Params) -> str | None:
    """'blast' | 'low_rank' for a rank-bearing linear's param dict, else None
    (dense / monarch / block_diag / pixelfly pass truncation through).
    Key-based so it works on any storage format (float / int8 / packed-int4
    QArrays) and under vmap (stacked MoE experts, scanned layer cycles)."""
    if not isinstance(params, dict):
        return None
    core = set(params) - {"bias"}
    if core == {"U", "S", "V"}:
        return "blast"
    if core == {"w_down", "w_up"}:
        return "low_rank"
    return None


def linear_rank(params: Params) -> int | None:
    """Static rank of a rank-bearing linear (QArray.shape reports the
    logical extent for nibble-packed int4)."""
    kind = rank_kind(params)
    if kind is None:
        return None
    return int(params["U" if kind == "blast" else "w_down"].shape[-1])


def _as_f32(a) -> jax.Array:
    return qt.dequantize(a) if qt.is_qarray(a) else a.astype(jnp.float32)


def rank_spectrum(params: Params) -> jax.Array | None:
    """Per-component energy e_rho — the exact squared-Frobenius contribution
    of rank component rho to the dense matrix (block rows/cols are disjoint,
    so contributions add):

      blast:    e_rho = sum_ij S[i,j,rho]^2 * |U[i,:,rho]|^2 * |V[j,:,rho]|^2
      low_rank: e_t   = |w_down[:,t]|^2 * |w_up[t,:]|^2

    Quantized params are dequantized first.  Returns None for kinds with no
    rank axis."""
    kind = rank_kind(params)
    if kind is None:
        return None
    if kind == "blast":
        U, S, V = (_as_f32(params[k]) for k in ("U", "S", "V"))
        su = jnp.sum(U * U, axis=1)                      # (b, r)
        sv = jnp.sum(V * V, axis=1)                      # (b, r)
        return jnp.einsum("ijr,ir,jr->r", S * S, su, sv)
    d, u = _as_f32(params["w_down"]), _as_f32(params["w_up"])
    return jnp.sum(d * d, axis=0) * jnp.sum(u * u, axis=1)


def _gather_rank(arr: jax.Array, idx: jax.Array, axis: int,
                 full: int) -> jax.Array:
    """Gather rank components along ``axis``; axes without the full rank
    extent (broadcast / per-block scales) pass through untouched."""
    if arr.shape[axis] != full:
        return arr
    return jnp.take(arr, idx, axis=axis)


def _take_rank(a, idx: jax.Array, axis: int, full: int):
    """Rank-gather one factor, preserving its storage format.

    int8 QArrays gather codes; their per-block scales gather only if the
    rank axis has full extent (blast block scales are (b,1,1)/(b,b,1) — no
    rank extent — and stay exact: the surviving codes decode with the same
    scale as before).  Packed int4 with the rank on the packed (last) axis
    unpacks, gathers, and repacks — a bit-exact roundtrip."""
    if not qt.is_qarray(a):
        return _gather_rank(a, idx, axis, full)
    scale = _gather_rank(a.scale, idx, axis, full)
    if a.bits == 4 and axis == a.q.ndim - 1:
        v = jnp.take(qt.int_values(a), idx, axis=axis)
        return qt.QArray(qt.pack_int4(v), scale, bits=4,
                         last_dim=int(idx.shape[0]))
    return qt.QArray(_gather_rank(a.q, idx, axis, full), scale, bits=a.bits,
                     last_dim=a.last_dim)


def truncate_rank(params: Params, r_prime: int) -> Params:
    """Truncate a rank-bearing linear to its ``r_prime`` highest-energy
    components; non-rank-bearing kinds (and ``r_prime >= r``) return the
    params unchanged.

    Kept indices are sorted ascending, so full-rank truncation is the
    identity and — because the rank contraction is permutation-invariant —
    the truncated ``apply`` equals the full ``apply`` with the dropped
    components zeroed.  Works for float, int8 and packed-int4 storage; the
    result is a normal param dict the unmodified apply paths consume (they
    read ranks from array shapes, not specs)."""
    kind = rank_kind(params)
    if kind is None:
        return params
    full = linear_rank(params)
    r_prime = max(1, min(int(r_prime), full))
    if r_prime == full:
        return dict(params)
    idx = jnp.sort(jax.lax.top_k(rank_spectrum(params), r_prime)[1])
    axes = _RANK_AXES[kind]
    return {k: (_take_rank(v, idx, axes[k], full) if k in axes else v)
            for k, v in params.items()}


# ---------------------------------------------------------------------------
# Grouped dispatch: run a layer's shape-congruent same-input projections
# (gate+up, MLA a-projections, RG-LRU input/gate branches, …) as ONE matmul
# launch instead of one per projection.  At decode time every launch
# re-streams its factors and pads T=1 to a sublane tile, so collapsing a
# bundle is a direct hot-path win; the Pallas side is
# ``kernels/blast_matmul.py``'s grouped kernels (leading G grid dim, one
# shared x-tile), the XLA/GSPMD side is the batched einsum chain below.
# ---------------------------------------------------------------------------


_GROUPING = [True]     # process-wide toggle (trace-time; see grouping())
_DISPATCHES = [0]      # structured-matmul dispatch counter (trace-time)
_STACKS = [0]          # per-step factor-stacking counter (trace-time)
_ACT_MODE = ["none"]   # activation storage: "none" | "int8" (trace-time)
_TP_MESH = [None]      # (mesh, axis) routing Pallas applies under shard_map


def set_tp_mesh(mesh, axis: str = "model") -> None:
    """Route ``group_apply(use_pallas=True)`` through the shard_map TP
    wrappers (``kernels/ops.py::blast_matmul_grouped*_tp``): each device
    contracts its rank shard with its own grouped launch and the stage-3
    output is psum'd.  Trace-time process toggle like ``set_activations`` —
    the engine flips it at build when its model carries an active mesh with
    tp > 1; ``set_tp_mesh(None)`` restores the single-launch path.  The XLA
    einsum path (``use_pallas=False``) is unaffected: GSPMD realizes the
    same rank-parallel scheme from the factor shardings directly."""
    _TP_MESH[0] = None if mesh is None else (mesh, axis)


def tp_mesh():
    return _TP_MESH[0]


@contextlib.contextmanager
def tp_sharding(mesh, axis: str = "model"):
    """Temporarily route Pallas grouped applies under shard_map (trace-time
    toggle, same contract as ``grouping``)."""
    prev = _TP_MESH[0]
    set_tp_mesh(mesh, axis)
    try:
        yield
    finally:
        _TP_MESH[0] = prev


def set_activations(mode: str) -> None:
    """Select the activation storage for quantized blast applies process-
    wide ("none" float activations, "int8" per-token integer contractions —
    the W8A8/W4A8 paths).  Trace-time like ``grouping``: it bakes into
    programs compiled afterwards; the engine sets it at build from
    ``QuantConfig.activations``."""
    if mode not in ("none", "int8"):
        raise ValueError(f"activation mode must be 'none'|'int8', got {mode}")
    _ACT_MODE[0] = mode


def activations_mode() -> str:
    return _ACT_MODE[0]


@contextlib.contextmanager
def activations(mode: str):
    """Temporarily select the activation storage (trace-time toggle, same
    contract as ``grouping``)."""
    prev = _ACT_MODE[0]
    set_activations(mode)
    try:
        yield
    finally:
        _ACT_MODE[0] = prev


def row_health(logits, absmax: float | None = None):
    """Per-row numeric health of a logits block: (B,) bool, True where the
    row is finite everywhere and (optionally) |logit| ≤ ``absmax``.

    This is the guarded-apply check the serving layer runs after every
    jitted step — low-precision paths (int8 activation rounding, truncated
    draft ranks) are exactly where overflow/NaN faults originate, and one
    cheap reduction here is what lets a poisoned row degrade gracefully
    instead of wedging the batch.  Reduces over every non-batch axis, so it
    accepts (B, V), (B, C, V) and any wider logits layout.  Traced under
    the name scope ``guardrail``."""
    axes = tuple(range(1, logits.ndim))
    with jax.named_scope("guardrail"):
        finite = jnp.isfinite(logits)
        ok = finite.all(axis=axes)
        if absmax is not None:
            # mask non-finite entries out of the max so inf does not shadow
            # the finiteness bit with a second (redundant) trip reason
            mag = jnp.abs(jnp.where(finite, logits, 0.0)).max(axis=axes)
            ok = ok & (mag <= absmax)
    return ok


def record_dispatch(n: int = 1) -> None:
    """Count one projection-matmul dispatch (== one kernel launch on the
    Pallas path).  Incremented at trace/eager-apply time — measure per-step
    launch counts by applying an *unrolled* model eagerly (see
    benchmarks/serving_throughput.py)."""
    _DISPATCHES[0] += n


def dispatch_count() -> int:
    return _DISPATCHES[0]


def reset_dispatch_count() -> None:
    _DISPATCHES[0] = 0


def record_stack(n: int = 1) -> None:
    """Count one in-step bundle stack (the pad+concat of a grouped bundle's
    member factors).  Zero per step once the caller supplies pre-stacked
    ``GroupBundle``s (``prestack`` / ``Engine(prestack=True)``) — measured
    the same way as dispatches: unrolled model, eager apply."""
    _STACKS[0] += n


def stack_count() -> int:
    return _STACKS[0]


def reset_stack_count() -> None:
    _STACKS[0] = 0


def grouping_enabled() -> bool:
    return _GROUPING[0]


@contextlib.contextmanager
def grouping(enabled: bool):
    """Temporarily toggle the grouped fast path (affects only code traced
    inside the context — useful for grouped-vs-loop comparisons)."""
    prev = _GROUPING[0]
    _GROUPING[0] = bool(enabled)
    try:
        yield
    finally:
        _GROUPING[0] = prev


def _storage(params: Params) -> str:
    """'float' | 'int8' | 'int4' | 'mixed' for one linear's param dict.
    The bias (always float, added post-matmul and stripped before
    ``group_apply``) does not participate in the classification."""
    kinds = set()
    for k, v in params.items():
        if k == "bias":
            continue
        kinds.add(f"int{v.bits}" if qt.is_qarray(v) else "float")
    return kinds.pop() if len(kinds) == 1 else "mixed"


def group_plan(specs: Sequence[LinearSpec],
               params_list: Sequence[Params]) -> dict | None:
    """Congruence check: can these same-input linears run as one grouped
    launch?  Eligible: ≥2 members, all the same structure kind out of
    {blast, dense, block_diag}, same d_in (they share x), same block count
    b for the blocked kinds, and uniform storage — all-float, all-int8, or
    all-int4.  int4 blast bundles stack their nibble-packed bytes *packed*
    and run the grouped q4 kernel (one launch, half the factor reads);
    int4 dense / block_diag bundles unpack to int8 codes at stack time
    (once, at prestack) and ride the int8 grouped path.  d_out / rank
    may differ: members are zero-padded to the group max, which is exact
    (padded rows/ranks — and for int4 padded zero bytes, i.e. zero codes —
    contribute nothing and are sliced off).  Returns the stacking plan, or
    None → caller falls back to the per-projection loop.
    """
    if not _GROUPING[0] or len(specs) < 2:
        return None
    kind = specs[0].kind
    if kind not in ("blast", "dense", "block_diag"):
        return None
    if any(s.kind != kind or s.d_in != specs[0].d_in for s in specs):
        return None
    storage = _storage(params_list[0])
    if storage not in ("float", "int8", "int4"):
        return None
    if any(_storage(p) != storage for p in params_list[1:]):
        return None
    plan = {"kind": kind, "storage": storage, "d_in": specs[0].d_in,
            "d_outs": [s.d_out for s in specs]}
    if kind in ("blast", "block_diag"):
        b = specs[0].meta["b"]
        if any(s.meta["b"] != b for s in specs):
            return None
        plan["b"] = b
        plan["p"] = max(s.d_out // b for s in specs)
        if kind == "blast":
            # rank from the actual factor arrays, not the spec: truncated
            # draft params (truncate_rank) carry r' < spec.meta["r"], and
            # padding them back to spec rank would undo the truncation
            # (QArray.shape reports the logical rank for packed storage)
            plan["r"] = max(int(p["U"].shape[-1]) for p in params_list)
    return plan


def _pad_to(a: jax.Array, axis: int, size: int) -> jax.Array:
    if a.shape[axis] == size:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, pad)


def _split_group(y: jax.Array, plan: dict, lead: tuple[int, ...],
                 dtype) -> list[jax.Array]:
    """(G, ..., m̂) grouped output → per-member (..., d_out) slices."""
    outs = []
    b = plan.get("b")
    for g, d_out in enumerate(plan["d_outs"]):
        yg = y[g]
        if b is not None:
            p_hat = yg.shape[-1] // b
            p_g = d_out // b
            if p_g != p_hat:
                yg = yg.reshape(*lead, b, p_hat)[..., :p_g]
            yg = yg.reshape(*lead, d_out)
        else:
            yg = yg[..., :d_out]
        outs.append(yg.astype(dtype))
    return outs


def _stack_group(params_list: Sequence[Params], plan: dict) -> dict:
    """Pad + stack a grouped bundle's member factors into the batched arrays
    ``group_apply`` contracts against.  This is per-step work when the
    caller passes raw per-member params; ``prestack`` runs it once at engine
    load and carries the result in a ``GroupBundle`` so the step skips it
    entirely — the ``record_stack`` counter is how tests pin that down."""
    record_stack(1)
    kind, storage = plan["kind"], plan["storage"]
    if kind == "dense":
        m_hat = max(plan["d_outs"])
        if storage == "float":
            return {"W": jnp.stack([_pad_to(p["w"], 1, m_hat)
                                    for p in params_list])}
        return {"W": jnp.stack([_pad_to(qt.int_values(p["w"]), 1, m_hat)
                                for p in params_list]),
                "sc": jnp.stack([_pad_to(p["w"].scale[0], 0, m_hat)
                                 for p in params_list])}       # (G, m̂)
    if kind == "block_diag":
        p_hat = plan["p"]
        if storage == "float":
            return {"W": jnp.stack([_pad_to(p["w"], 2, p_hat)
                                    for p in params_list])}
        return {"W": jnp.stack([_pad_to(qt.int_values(p["w"]), 2, p_hat)
                                for p in params_list]),
                "sw": jnp.stack([p["w"].scale[:, 0, 0]
                                 for p in params_list])}       # (G, b)

    b, p_hat, r_hat = plan["b"], plan["p"], plan["r"]
    q = plan["d_in"] // b
    packed = storage == "int4"
    # int4 members stack *packed*: the byte axis pads with zero bytes (two
    # zero codes each), so the grouped q4 kernel's plane unpack sees exact
    # zero-rank padding and the operands never materialize as int8
    r_tgt = (r_hat + 1) // 2 if packed else r_hat

    def stack(name: str, width: int):
        """Pad each member's factor to (b, width, r̂) and stack over G."""
        outs = []
        for pp in params_list:
            a = pp[name]
            if qt.is_qarray(a):
                a = a.q if packed else qt.int_values(a)
            outs.append(_pad_to(_pad_to(a, 2, r_tgt), 1, width))
        return jnp.stack(outs)

    out = {"U": stack("U", p_hat), "S": stack("S", b), "V": stack("V", q)}
    if storage in ("int8", "int4"):
        out["su"] = jnp.stack([pp["U"].scale.reshape(b)
                               for pp in params_list])
        out["ss"] = jnp.stack([pp["S"].scale.reshape(b, b)
                               for pp in params_list])
        out["sv"] = jnp.stack([pp["V"].scale.reshape(b)
                               for pp in params_list])
    return out


def _plan_items(plan: dict) -> tuple:
    """Hashable (pytree-aux-safe) encoding of a group plan."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in plan.items()))


@jax.tree_util.register_pytree_node_class
class GroupBundle:
    """Pre-stacked grouped-projection factors, built once at engine load
    (``prestack``) instead of on every step.  A pytree: children are the
    stacked arrays, aux data is the (static, hashable) plan — so a bundle
    rides inside a param dict through jit/vmap, and a stale bundle (plan
    mismatch after re-quantization or truncation) is simply ignored by
    ``linear_group_apply``."""

    def __init__(self, arrays: dict, plan_items: tuple):
        self.arrays = dict(arrays)
        self.plan_items = plan_items

    @property
    def plan(self) -> dict:
        d = dict(self.plan_items)
        d["d_outs"] = list(d["d_outs"])
        return d

    def tree_flatten(self):
        names = tuple(sorted(self.arrays))
        return tuple(self.arrays[n] for n in names), (names, self.plan_items)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, plan_items = aux
        return cls(dict(zip(names, children)), plan_items)


def prestack(specs: Sequence[LinearSpec],
             params_list: Sequence[Params]) -> GroupBundle | None:
    """Build a ``GroupBundle`` for one projection bundle, or None when the
    bundle is not groupable (mixed storage / grouping disabled / ineligible
    kind) — then the per-step path is the fallback loop and there is
    nothing to pre-stack.  int4 blast bundles pre-stack their *packed*
    bytes.  Load-time stacking is excluded from the per-step counter."""
    plan = group_plan(specs, params_list)
    if plan is None:
        return None
    core = [{k: v for k, v in p.items() if k != "bias"} for p in params_list]
    before = _STACKS[0]
    arrays = _stack_group(core, plan)
    _STACKS[0] = before
    return GroupBundle(arrays, _plan_items(plan))


def group_apply(specs: Sequence[LinearSpec], params_list: Sequence[Params],
                x: jax.Array, *, plan: dict | None = None,
                use_pallas: bool = False,
                stacked: dict | None = None) -> list[jax.Array]:
    """Apply G congruent same-input linears as ONE grouped matmul.

    ``plan`` must come from ``group_plan`` (callers usually go through
    ``models/layers.py::linear_group_apply``, which handles the fallback).
    The default path is the stacked einsum chain (XLA/GSPMD, mirroring the
    per-structure ``apply``/``apply_q``); ``use_pallas=True`` dispatches the
    fused grouped Pallas kernel instead (shard_map-per-device execution).
    Counts as a single dispatch.

    ``stacked``: pre-stacked factor arrays (a ``GroupBundle.arrays`` built
    by ``prestack`` at load).  When omitted the member factors are padded
    and stacked inside the step — XLA fuses the concatenate into the
    consumer on the shapes we run, but the pre-stacked path skips the work
    outright (and the per-step ``stack_count`` stays zero)."""
    if plan is None:
        plan = group_plan(specs, params_list)
    assert plan is not None, "group_apply requires a valid group_plan"
    record_dispatch(1)
    st = stacked if stacked is not None else _stack_group(params_list, plan)
    lead = x.shape[:-1]
    G = len(specs)
    kind, storage = plan["kind"], plan["storage"]

    if kind == "dense":
        m_hat = max(plan["d_outs"])
        if storage == "float":
            y = jnp.einsum("...n,gnm->g...m", x, st["W"])
        else:
            y = jnp.einsum("...n,gnm->g...m", x, st["W"].astype(x.dtype))
            y = y * st["sc"].reshape(G, *([1] * len(lead)), m_hat)
        return _split_group(y, plan, lead, x.dtype)

    if kind == "block_diag":
        b = plan["b"]
        q = plan["d_in"] // b
        p_hat = plan["p"]
        xb = x.reshape(*lead, b, q)
        if storage == "float":
            y = jnp.einsum("...bq,gbqp->g...bp", xb, st["W"])
        else:
            y = jnp.einsum("...bq,gbqp->g...bp", xb, st["W"].astype(x.dtype))
            y = (y.astype(jnp.float32)
                 * st["sw"].reshape(G, *([1] * len(lead)), b, 1))
        y = y.reshape(G, *lead, b * p_hat)
        return _split_group(y, plan, lead, x.dtype)

    # -- blast ---------------------------------------------------------------
    b, p_hat = plan["b"], plan["p"]
    q = plan["d_in"] // b
    U, S, V = st["U"], st["S"], st["V"]
    if storage == "float":
        if use_pallas:
            from repro.kernels import ops as kops
            tpm = _TP_MESH[0]
            if tpm is not None:
                y = kops.blast_matmul_grouped_tp(x, U, S, V, mesh=tpm[0],
                                                 axis=tpm[1])
            else:
                y = kops.blast_matmul_grouped(x, U, S, V)
        else:
            xb = x.reshape(*lead, b, q)
            z = jnp.einsum("...jq,gjqr->g...jr", xb, V)
            w = jnp.einsum("g...jr,gijr->g...ir", z, S)
            y = jnp.einsum("g...ir,gipr->g...ip", w, U)
            y = y.reshape(G, *lead, b * p_hat)
        return _split_group(y, plan, lead, x.dtype)

    su, ss, sv = st["su"], st["ss"], st["sv"]
    act = activations_mode()
    if use_pallas:
        from repro.kernels import ops as kops
        tpm = _TP_MESH[0]
        if storage == "int4":
            if tpm is not None:
                y = kops.blast_matmul_grouped_q4_tp(
                    x, U, S, V, su, ss, sv, act=act,
                    mesh=tpm[0], axis=tpm[1])
            else:
                y = kops.blast_matmul_grouped_q4(x, U, S, V, su, ss, sv,
                                                 act=act)
        else:
            if tpm is not None:
                y = kops.blast_matmul_grouped_q_tp(
                    x, U, S, V, su, ss, sv, act=act,
                    mesh=tpm[0], axis=tpm[1])
            else:
                y = kops.blast_matmul_grouped_q(x, U, S, V, su, ss, sv,
                                                act=act)
    else:
        # XLA mirror of the fused grouped quant kernels: integer codes enter
        # the contraction, per-block scales multiply each stage's output
        # (int4 operands stay packed until here; plane order is exact).
        if storage == "int4":
            U, S, V = (qt.unpack_int4_planes(a) for a in (U, S, V))
        one = (1,) * len(lead)
        if act == "int8":
            xq, sx = qt.quantize_act(x)
            z = jnp.einsum("...jq,gjqr->g...jr", xq.reshape(*lead, b, q), V,
                           preferred_element_type=jnp.int32)
            z = (z.astype(jnp.float32) * sx[..., None]
                 * sv.reshape(G, *one, b, 1))
        else:
            xb = x.reshape(*lead, b, q)
            z = jnp.einsum("...jq,gjqr->g...jr", xb, V.astype(x.dtype))
            z = z.astype(jnp.float32) * sv.reshape(G, *one, b, 1)
        s = S.astype(jnp.float32) * ss[..., None]
        w = jnp.einsum("g...jr,gijr->g...ir", z, s)
        y = jnp.einsum("g...ir,gipr->g...ip", w, U.astype(jnp.float32))
        y = y * su.reshape(G, *one, b, 1)
        y = y.reshape(G, *lead, b * p_hat)
    return _split_group(y, plan, lead, x.dtype)
