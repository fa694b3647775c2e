"""Mamba-2 SSD (state-space duality) mixer [arXiv:2405.21060].

Chunked algorithm: within each length-L chunk the output is computed with the
"dual" quadratic attention form (MXU-friendly batched matmuls); across chunks
a linear recurrence over the (H, P, N) chunk states runs in a lax.scan —
T/L sequential steps of tiny state math.  Decode is the pure recurrent form:
O(1) state update per token, so ``long_500k`` is representable.

The in/out projections are structured (BLAST-able) linears; the SSD scan
itself is attention-free and has no weight matrix — the paper's technique is
*inapplicable to the recurrence*, as recorded in DESIGN.md §Arch-applicability.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import quant as qt
from repro.configs.base import ArchConfig
from repro.core.structures import LinearSpec, make_linear
from repro.models import layers as L
from repro.models.ops import causal_conv_chunk, rms_norm
from repro.models.rglru import _conv1d
from repro.parallel import Parallel, NO_PARALLEL

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SSDSpec:
    cfg: ArchConfig
    d_inner: int
    n_heads: int
    head_dim: int
    d_state: int
    chunk: int
    conv_width: int
    n_groups: int
    in_proj: LinearSpec   # d -> 2·d_inner + 2·G·N + H   (z, x, B, C, dt)
    out_proj: LinearSpec  # d_inner -> d


def make_ssd(cfg: ArchConfig) -> SSDSpec:
    s = cfg.ssd
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    G = 1
    d_in_proj = 2 * d_inner + 2 * G * s.d_state + n_heads
    return SSDSpec(
        cfg=cfg, d_inner=d_inner, n_heads=n_heads, head_dim=s.head_dim,
        d_state=s.d_state, chunk=s.chunk, conv_width=s.conv_width, n_groups=G,
        in_proj=make_linear(cfg.d_model, d_in_proj, cfg.structure),
        out_proj=make_linear(d_inner, cfg.d_model, cfg.structure),
    )


def ssd_init(spec: SSDSpec, key, dtype) -> Params:
    """Mamba-2's initialiser: dt from log-uniform (1e-3, 1e-1) through
    softplus⁻¹, A = -exp(A_log) from U(1, 16), D = 1, the depthwise conv's
    taps and bias from U(±1/sqrt(K)) (a conv layer's default), the gated
    norm's gain 1 (scale 0)."""
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    H, K = spec.n_heads, spec.conv_width
    conv_ch = spec.d_inner + 2 * spec.n_groups * spec.d_state
    dt = jnp.exp(jax.random.uniform(k3, (H,), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # softplus⁻¹(dt)
    bound = 1.0 / K ** 0.5
    return {
        "in_proj": L.linear_init(spec.in_proj, k1, dtype),
        "out_proj": L.linear_init(spec.out_proj, k2, dtype),
        "conv_w": jax.random.uniform(k4, (K, conv_ch), minval=-bound,
                                     maxval=bound).astype(dtype),
        "conv_b": jax.random.uniform(k5, (conv_ch,), minval=-bound,
                                     maxval=bound).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(k6, (H,), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": {"scale": jnp.zeros((spec.d_inner,), dtype=dtype)},
    }


def ssd_axes(spec: SSDSpec) -> dict:
    return {
        "in_proj": L.linear_axes(spec.in_proj, out_axis="ffn"),
        "out_proj": L.linear_axes(spec.out_proj, in_axis="ffn", out_axis="fsdp_in"),
        "conv_w": (None, "ffn"),
        "conv_b": ("ffn",),
        "A_log": (None,),
        "D": (None,),
        "dt_bias": (None,),
        "norm": {"scale": ("ffn",)},
    }


def ssd_quantize(spec: SSDSpec, params: Params, bits: int = 8) -> Params:
    """Quantize the structured in/out projections (where the params live);
    conv / gates / norm stay float — they are O(d_inner), not O(d²)."""
    qp = dict(params)
    qp["in_proj"] = L.linear_quantize(spec.in_proj, params["in_proj"], bits)
    qp["out_proj"] = L.linear_quantize(spec.out_proj, params["out_proj"], bits)
    return qp


def _split_in_proj(spec: SSDSpec, zxbcdt: jax.Array):
    d_inner, G, N, H = spec.d_inner, spec.n_groups, spec.d_state, spec.n_heads
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner: 2 * d_inner + 2 * G * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * G * N:]
    return z, xBC, dt


def _split_xbc(spec: SSDSpec, xBC: jax.Array):
    d_inner, G, N = spec.d_inner, spec.n_groups, spec.d_state
    x = xBC[..., :d_inner]
    Bm = xBC[..., d_inner: d_inner + G * N]
    Cm = xBC[..., d_inner + G * N:]
    return x, Bm, Cm


def _segsum(da: jax.Array) -> jax.Array:
    """da: (..., L) → (..., L, L) lower-tri matrix of Σ_{j<i≤k} da_k."""
    Ln = da.shape[-1]
    cs = jnp.cumsum(da, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # Σ over (j, i]
    mask = jnp.tril(jnp.ones((Ln, Ln), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, chunk: int, h0: jax.Array | None = None):
    """Chunked SSD scan (fp32), from the state ``h0`` (zero if None).

    x: (B, T, H, P); dt: (B, T, H); A: (H,); Bm/Cm: (B, T, G, N);
    h0: (B, H, P, N).  → y: (B, T, H, P), h_last: (B, H, P, N).

    The H heads fall into G groups of R = H / G that share B and C, so the
    scores C_l·B_s are computed once per group.  A step with dt = 0 leaves
    the state exactly where it was (decay 1, input 0).
    """
    Bsz, T, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G
    Ln = min(chunk, T)
    pad = (-T) % Ln
    if pad:
        # zero-pad the tail: dt=0 ⇒ decay=1 and x̄=0, so the padded steps
        # neither move the state nor contribute output (sliced off below).
        z = lambda t: jnp.pad(t, [(0, pad if i == 1 else 0)
                                  for i in range(t.ndim)])
        x, dt, Bm, Cm = z(x), z(dt), z(Bm), z(Cm)
        T += pad
    nc = T // Ln
    xdt = (x * dt[..., None]).reshape(Bsz, nc, Ln, G, R, Pd)      # x̄ = dt·x
    da = (dt * A).reshape(Bsz, nc, Ln, G, R)
    Bc = Bm.reshape(Bsz, nc, Ln, G, N)
    Cc = Cm.reshape(Bsz, nc, Ln, G, N)

    # ---- intra-chunk (dual quadratic form)
    Lmat = jnp.exp(_segsum(da.transpose(0, 1, 3, 4, 2)))           # (B,nc,G,R,L,L)
    scores = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc)              # (B,nc,G,L,L)
    y_intra = jnp.einsum("bcgls,bcgrls,bcsgrp->bclgrp", scores, Lmat, xdt)

    # ---- chunk states  S_c = Σ_l exp(Σ_{k>l} da) · B_l ⊗ x̄_l
    da_cum = jnp.cumsum(da, axis=2)                                # (B,nc,L,G,R)
    decay_states = jnp.exp(da_cum[:, :, -1:] - da_cum)
    S = jnp.einsum("bclgr,bclgn,bclgrp->bcgrpn", decay_states, Bc, xdt)

    # ---- inter-chunk recurrence:  h_c = exp(Σ da_c)·h_{c-1} + S_c
    chunk_decay = jnp.exp(da_cum[:, :, -1])                        # (B,nc,G,R)

    def step(h, inp):
        dec, s = inp
        return dec[..., None, None] * h + s, h  # emit the state entering

    h_init = (jnp.zeros((Bsz, G, R, Pd, N), jnp.float32) if h0 is None
              else h0.reshape(Bsz, G, R, Pd, N))
    h_last, h_prev = jax.lax.scan(
        step, h_init, (chunk_decay.transpose(1, 0, 2, 3),
                       S.transpose(1, 0, 2, 3, 4, 5)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4, 5)                    # (B,nc,G,R,P,N)

    # ---- inter-chunk output:  y_l += exp(da_cum_l) · C_l · h_prev
    y_inter = jnp.einsum("bclgr,bclgn,bcgrpn->bclgrp",
                         jnp.exp(da_cum), Cc, h_prev)
    y = (y_intra + y_inter).reshape(Bsz, T, H, Pd)
    if pad:
        y = y[:, : T - pad]
    return y, h_last.reshape(Bsz, H, Pd, N)


def ssd_apply(spec: SSDSpec, params: Params, x: jax.Array,
              positions: jax.Array, parallel: Parallel = NO_PARALLEL,
              *, return_cache: bool = False):
    """x: (B, T, d_model) → (B, T, d_model) [, cache]."""
    Bsz, T, _ = x.shape
    H, Pd, N, G = spec.n_heads, spec.head_dim, spec.d_state, spec.n_groups
    zxbcdt = L.linear_apply(spec.in_proj, params["in_proj"], x)
    zxbcdt = parallel.constraint(zxbcdt, parallel.batch_spec(None, None))
    z, xBC_pre, dt_raw = _split_in_proj(spec, zxbcdt)
    with jax.named_scope("ssd_conv"):
        xBC = jax.nn.silu(_conv1d(xBC_pre, params["conv_w"],
                                  params["conv_b"]))
    xin, Bm, Cm = _split_xbc(spec, xBC)
    with jax.named_scope("ssd_scan"):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"])
        y, h_last = ssd_chunked(
            xin.reshape(Bsz, T, H, Pd).astype(jnp.float32), dt, A,
            Bm.reshape(Bsz, T, G, N).astype(jnp.float32),
            Cm.reshape(Bsz, T, G, N).astype(jnp.float32), spec.chunk)
        y = y + params["D"][None, None, :, None] * xin.reshape(
            Bsz, T, H, Pd).astype(jnp.float32)
    y = y.reshape(Bsz, T, spec.d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"]["scale"],
                 spec.cfg.rms_norm_eps)
    out = L.linear_apply(spec.out_proj, params["out_proj"], y)
    out = parallel.shard_batch(out)
    if not return_cache:
        return out
    K = spec.conv_width
    tail = xBC_pre[:, -(K - 1):] if T >= K - 1 else jnp.pad(
        xBC_pre, ((0, 0), (K - 1 - T, 0), (0, 0)))
    return out, qt.pack_state_cache(spec.cfg.cache_quant,
                                      tail.astype(x.dtype), h_last)


def ssd_cache_init(spec: SSDSpec, batch: int, max_len: int, dtype) -> Params:
    conv_ch = spec.d_inner + 2 * spec.n_groups * spec.d_state
    h_shape = (batch, spec.n_heads, spec.head_dim, spec.d_state)
    c: Params = {}
    if spec.cfg.cache_quant:
        c["conv"] = jnp.zeros((batch, spec.conv_width - 1, conv_ch), jnp.int8)
        c["conv_scale"] = jnp.zeros((batch, spec.conv_width - 1), jnp.bfloat16)
        c["h"] = jnp.zeros(h_shape, jnp.int8)
        c["h_scale"] = jnp.zeros(h_shape[:-1], jnp.float32)
    else:
        c["conv"] = jnp.zeros((batch, spec.conv_width - 1, conv_ch), dtype=dtype)
        c["h"] = jnp.zeros(h_shape, jnp.float32)
    return c


def ssd_cache_axes(spec: SSDSpec) -> dict:
    a = {"conv": ("batch", None, "ffn"), "h": ("batch", None, None, None)}
    if spec.cfg.cache_quant:
        a["conv_scale"] = ("batch", None)
        a["h_scale"] = ("batch", None, None)
    return a


def ssd_prefill(spec: SSDSpec, params: Params, cache: Params, x: jax.Array,
                steps: jax.Array, n_tokens: jax.Array,
                parallel: Parallel = NO_PARALLEL, *,
                collect: bool = False) -> tuple[jax.Array, Params]:
    """Multi-token prefill from each row's cached state.

    The structured in/out projections — where the (tokens × rank) BLAST tiles
    and hence the FLOPs live — run over the whole (B, C) chunk.  The state
    math takes the chunked dual form (``ssd_chunked``, inner chunks of the
    configuration's ``chunk``) from the cached state, reading and writing
    the (B, H, P, N) state once per inner chunk; a decode step (C = 1) and
    a speculative verify (``collect``) advance it token by token.  Rows are
    ragged: column i of row b is live iff i < n_tokens[b]; dead columns get
    dt = 0, so they neither advance (conv, h) nor contribute (their outputs
    are garbage the engine discards), and a row with n_tokens = 0 keeps its
    state exactly.  ``steps`` is unused (no positional state) but kept for
    the uniform mixer-prefill signature.

    ``collect=True`` additionally returns per-token state snapshots in the
    cache (``h_snap (B, C+1, H, P, N)`` with index 0 = the incoming state,
    plus the full conv history ``conv_hist``) so a speculative verify step
    can be rolled back to any draft boundary (``ssd_cache_rollback``).
    """
    del steps
    Bsz, C, _ = x.shape
    H, Pd, N, G = spec.n_heads, spec.head_dim, spec.d_state, spec.n_groups
    conv_prev, h_prev = qt.unpack_state_cache(spec.cfg.cache_quant,
                                              cache, x.dtype)
    zxbcdt = L.linear_apply(spec.in_proj, params["in_proj"], x)
    z, xBC_pre, dt_raw = _split_in_proj(spec, zxbcdt)
    valid = jnp.arange(C)[None, :] < n_tokens[:, None]           # (B, C)

    with jax.named_scope("ssd_conv"):
        y_conv, conv_f = causal_conv_chunk(conv_prev, xBC_pre,
                                           params["conv_w"], params["conv_b"],
                                           n_tokens)
        xBC = jax.nn.silu(y_conv)
    xin, Bm, Cm = _split_xbc(spec, xBC)
    with jax.named_scope("ssd_scan"):
        xin = xin.reshape(Bsz, C, H, Pd).astype(jnp.float32)
        Bm = Bm.reshape(Bsz, C, G, N).astype(jnp.float32)
        Cm = Cm.reshape(Bsz, C, G, N).astype(jnp.float32)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0)  # dead: a=1, x̄=0 → h fixed
        A = -jnp.exp(params["A_log"])
        if C > 1 and not collect:
            y, h_f = ssd_chunked(xin, dt, A, Bm, Cm, spec.chunk,
                                 h0=h_prev.astype(jnp.float32))
        else:
            y, h_f, hs = _ssd_tokens(xin, dt, A, Bm, Cm, h_prev, collect)
        y = y + params["D"][None, None, :, None] * xin
    y = y.reshape(Bsz, C, spec.d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"]["scale"],
                 spec.cfg.rms_norm_eps)
    out = L.linear_apply(spec.out_proj, params["out_proj"], y)
    new_cache = qt.pack_state_cache(spec.cfg.cache_quant, conv_f, h_f)
    if collect:
        new_cache["h_snap"] = jnp.concatenate(
            [h_prev.astype(jnp.float32)[:, None], hs], axis=1)  # (B,C+1,H,P,N)
        new_cache["conv_hist"] = jnp.concatenate([conv_prev, xBC_pre], axis=1)
    return parallel.shard_batch(out), new_cache


def _ssd_tokens(x, dt, A, Bm, Cm, h0, collect: bool):
    """The SSD recurrence one token at a time: h ← exp(dt·A)·h + dt·B⊗x,
    y = C·h.  Shapes as ``ssd_chunked``; also returns every token's state
    (B, T, H, P, N) when ``collect``, else None."""
    H, G = x.shape[2], Bm.shape[2]
    Bm = jnp.repeat(Bm, H // G, axis=2)
    Cm = jnp.repeat(Cm, H // G, axis=2)
    a = jnp.exp(dt * A)                                          # (B, T, H)

    def tok(h, inp):
        a_t, dt_t, Bm_t, Cm_t, x_t = inp
        h_new = (a_t[:, :, None, None] * h
                 + jnp.einsum("bh,bhn,bhp->bhpn", dt_t, Bm_t, x_t))
        y_t = jnp.einsum("bhn,bhpn->bhp", Cm_t, h_new)
        return h_new, ((y_t, h_new) if collect else y_t)

    t_major = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    h_f, ys = jax.lax.scan(tok, h0, tuple(map(t_major, (a, dt, Bm, Cm, x))))
    hs = None
    if collect:
        ys, hs = ys
        hs = jnp.moveaxis(hs, 0, 1)
    return jnp.moveaxis(ys, 0, 1), h_f, hs


def ssd_cache_rollback(spec: SSDSpec, cache: Params,
                       n_comm: jax.Array) -> Params:
    """Rewind a ``collect=True`` prefill's cache to its first ``n_comm``
    tokens.  Dead/rejected columns set dt=0 (a=1, +0 update), so
    ``h_snap[:, n_comm]`` is bit-identical to never having fed the rejected
    tokens; the conv buffer is the K−1 history entries ending at n_comm.
    Re-packing through ``pack_state_cache`` reproduces quantized-cache bits
    too."""
    h_snap, hist = cache["h_snap"], cache["conv_hist"]
    B = h_snap.shape[0]
    K1 = spec.conv_width - 1
    idx = n_comm[:, None] + jnp.arange(K1, dtype=n_comm.dtype)[None, :]
    conv = jnp.take_along_axis(hist, idx[:, :, None], axis=1)
    h = h_snap[jnp.arange(B), n_comm]
    return qt.pack_state_cache(spec.cfg.cache_quant, conv, h)


def ssd_decode(spec: SSDSpec, params: Params, cache: Params, x: jax.Array,
               step: jax.Array, parallel: Parallel = NO_PARALLEL
               ) -> tuple[jax.Array, Params]:
    """Single-token recurrent decode — ``ssd_prefill`` with C=1."""
    Bsz = x.shape[0]
    return ssd_prefill(spec, params, cache, x,
                       jnp.zeros((Bsz,), jnp.int32),
                       jnp.ones((Bsz,), jnp.int32), parallel)
