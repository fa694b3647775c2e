"""Model-level numeric ops: chunked (flash-style) attention for the XLA/GSPMD
path, RoPE, norms, activations.

The chunked attention is the pure-XLA analogue of kernels/flash_attention.py:
q is processed in *statically unrolled* chunks so each chunk only contracts
against the causally-reachable (or window-reachable) slice of K/V — no
full T×S score matrix is ever materialized, and causal/window skipping is
reflected in the compiled FLOPs (what the roofline reads).  On real TPUs the
Pallas kernel replaces this inside shard_map; both share ref.py semantics.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding.  x: (B, T, H, D) even D; positions: (T,) or (B, T)."""
    dtype = x.dtype
    d_half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(d_half, dtype=jnp.float32) / d_half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, d/2)
        ang = ang[None, :, None, :]
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, T, d/2)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos * jnp.exp(-math.log(10000.0) * dim / (d // 2))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _attn_block(q, k, v, mask, scale):
    """One (q-chunk × kv-slice) attention block in fp32."""
    s = jnp.einsum("bhgtd,bhsd->bhgts", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgts,bhse->bhgte", p, v, preferred_element_type=jnp.float32)
    return o, m[..., 0], l[..., 0]


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """q: (B, Hq, T, Dk); k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv) → (B, Hq, T, Dv).

    Statically-unrolled q chunks; each contracts only its reachable KV slice
    (causal upper bound / sliding-window lower bound, both static).  Scores
    are scaled by ``scale``, by default 1/sqrt(Dk).
    """
    B, Hq, T, Dk = q.shape
    _, Hkv, S, Dv = k.shape[0], k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dk) if scale is None else scale
    qg = q.reshape(B, Hkv, G, T, Dk).astype(jnp.float32)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    # Cap the static unroll at 8 chunks: keeps the HLO (and compile time)
    # bounded for 32k+ prefill while still skipping ~44% of causal work.
    q_chunk = max(q_chunk, -(-T // 8))
    q_chunk = min(q_chunk, T)
    n_chunks = (T + q_chunk - 1) // q_chunk
    outs = []
    for ci in range(n_chunks):
        t0 = ci * q_chunk
        t1 = min(T, t0 + q_chunk)
        tc = t1 - t0
        qc = qg[:, :, :, t0:t1]
        # static reachable KV range for this q chunk
        hi = min(S, q_offset + t1) if causal else S
        lo = 0
        if window is not None:
            lo = max(0, q_offset + t0 - window + 1)
        kc = kf[:, :, lo:hi]
        vc = vf[:, :, lo:hi]
        q_pos = q_offset + jnp.arange(t0, t1)
        k_pos = jnp.arange(lo, hi)
        mask = jnp.ones((tc, hi - lo), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        o, m, l = _attn_block(qc, kc, vc, mask[None, None, None], scale)
        safe = jnp.where(l > 0, l, 1.0)
        outs.append(o / safe[..., None])
    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    return out.reshape(B, Hq, T, Dv).astype(q.dtype)


def lane_group(n_heads: int, head_dim: int) -> int:
    """Heads per lane group of a merged ``(…, n_heads·head_dim)`` cache row:
    the fewest heads (a divisor of ``n_heads``) whose lanes fill whole
    128-lane vector registers, else all of them.  A group starts on a
    register boundary, so slicing it out of the row moves no data."""
    for p in range(1, n_heads + 1):
        if n_heads % p == 0 and (p * head_dim) % 128 == 0:
            return p
    return n_heads


# Query columns per pass of ``cache_attention``: a 256-column chunk scored in
# one pass took 432 ms a step at the Granite cells, in two passes of 128
# 218 ms (TPU v5e).
CACHE_Q_BLOCK = 128


def _lanes(a: jax.Array, layer, start: int, width: int) -> jax.Array:
    """Lanes ``[start, start + width)`` of a cache row, from one layer of a
    stacked ``(L, B, S, lanes)`` cache when ``layer`` is given (one dynamic
    slice, which the consuming dot reads in place)."""
    if layer is None:
        return a[..., start:start + width]
    L, B, S, _ = a.shape
    return jax.lax.dynamic_slice(a, (layer, 0, 0, start),
                                 (1, B, S, width))[0]


def cache_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_pos: jax.Array,
    q_pos: jax.Array,
    *,
    window: int | None = None,
    layer: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Multi-token decode/prefill attention over a (possibly ring) cache.

    q: (B, T, Hq, Dk); k_cache/v_cache: (B, S_alloc, Hkv·Dk) /
    (B, S_alloc, Hkv·Dv), heads merged into the row as the cache stores
    them — or the stacked (L, B, S_alloc, ·) cache with ``layer`` naming
    the layer to read; k_pos: (B, S_alloc) absolute position of each slot
    (-1 = empty); q_pos: (B,) or (B, T) absolute position of each query
    row — per-slot offsets for continuous batching; T=1 is classic
    single-token decode, T=C a prefill chunk (intra-chunk causality falls
    out of the position comparison).  Scores are scaled by ``scale``, by
    default 1/sqrt(Dk).  Returns (B, T, Hq, Dv).

    The cache is read in the layout it is stored in: the heads are taken a
    lane group at a time (``lane_group``), and within a group each query
    row is placed block-diagonally, zero outside its own head's lanes, so
    one dot over the group's lanes gives every head's scores and one dot
    gives every head's outputs, of which each head keeps its own block.
    The zeros add nothing, so the result is the per-head attention: the
    same float32 scores, softmax and products.  A per-head contraction
    would make XLA transpose every layer's K and V first.  A chunk wider
    than ``CACHE_Q_BLOCK`` is scored ``CACHE_Q_BLOCK`` columns at a time.
    """
    B, T, Hq, Dk = q.shape
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if q_pos.ndim == 1:
        q_pos = q_pos[:, None]
    q_pos = jnp.broadcast_to(q_pos, (B, T))
    if T > CACHE_Q_BLOCK:
        return jnp.concatenate([cache_attention(
            q[:, i:i + CACHE_Q_BLOCK], k_cache, v_cache, k_pos,
            q_pos[:, i:i + CACHE_Q_BLOCK], window=window, layer=layer,
            scale=scale)
            for i in range(0, T, CACHE_Q_BLOCK)], axis=1)
    Hkv = k_cache.shape[-1] // Dk
    Dv = v_cache.shape[-1] // Hkv
    G = Hq // Hkv
    P = lane_group(Hkv, Dk)
    S = k_cache.shape[-2]
    scale = 1.0 / math.sqrt(Dk) if scale is None else scale
    valid = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        valid &= k_pos[:, None, :] > q_pos[:, :, None] - window
    eye = jnp.eye(P, dtype=jnp.float32)
    qg = q.reshape(B, T, Hkv // P, P, G, Dk).astype(jnp.float32)
    outs = []
    for i in range(Hkv // P):
        k = _lanes(k_cache, layer, i * P * Dk, P * Dk).astype(jnp.float32)
        v = _lanes(v_cache, layer, i * P * Dv, P * Dv).astype(jnp.float32)
        # rows (t, head, g); lanes (head', d): zero unless head' == head
        qbd = jnp.einsum("btpgd,pj->btpgjd", qg[:, :, i],
                         eye).reshape(B, T * P * G, P * Dk)
        s = jnp.einsum("bnk,bsk->bns", qbd, k).reshape(B, T, P * G, S) * scale
        s = jnp.where(valid[:, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-empty caches
        o = jnp.einsum("bns,bsk->bnk", p.reshape(B, T * P * G, S), v)
        o = jnp.einsum("btpgjd,pj->btpgd", o.reshape(B, T, P, G, P, Dv), eye)
        outs.append(o.reshape(B, T, P * G, Dv))
    o = jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]
    return o.astype(q.dtype)


def causal_conv_chunk(cache_conv: jax.Array, x: jax.Array, w: jax.Array,
                      b: jax.Array, n_tokens: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over [cached history ‖ chunk], ragged rows.

    cache_conv: (B, K-1, ch) — each row's last K-1 pre-conv inputs;
    x: (B, C, ch) — the chunk's pre-conv inputs, live prefix per row given
    by n_tokens (dead tail columns produce garbage outputs their caller
    discards, and never enter the returned cache); w: (K, ch); b: (ch,).
    Returns (y (B, C, ch), new_cache_conv (B, K-1, ch)) — equal to C
    sequential single-token conv steps, computed position-parallel (live
    columns only depend on earlier live/cached inputs since dead columns
    form a contiguous tail).  Shared by the SSD and RG-LRU prefills.
    """
    K, C = w.shape[0], x.shape[1]
    hist = jnp.concatenate([cache_conv, x], axis=1)    # (B, K-1+C, ch)
    y = b
    for k in range(K):
        y = y + hist[:, k:k + C] * w[k]
    # new cache: each row's last K-1 live inputs (hist index i holds the
    # input at position i-(K-1) relative to the chunk start)
    idx = n_tokens[:, None] + jnp.arange(K - 1)[None, :]
    tail = jnp.take_along_axis(hist, idx[:, :, None], axis=1)
    return y, tail.astype(cache_conv.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap else x


def cross_entropy(logits: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mean token CE + accuracy.  logits (..., V) fp32-stable."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    acc = jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
    return loss, acc
