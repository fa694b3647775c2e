"""Structure-agnostic model layers: linears (dense or BLAST or any baseline),
norms, GQA attention (train/prefill/decode), MLA attention (DeepSeek-V3,
latent-cache decode with absorbed up-projections), FFNs.

Every layer is a pair of pure functions:

    init(key, ...) -> params (dict pytree)
    apply(params, x, ...) -> y

plus an ``axes(...)`` function returning a matching pytree of *logical axis
name* tuples, consumed by launch/sharding.py.  ``tests/test_models.py``
asserts init/axes tree congruence for every architecture.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro import quant as qt
from repro.configs.base import ArchConfig, MLACfg
from repro.core import structures
from repro.core.structures import LinearSpec, StructureConfig, make_linear
from repro.parallel import NO_PARALLEL
from repro.models import ops
from repro.parallel import Parallel, NO_PARALLEL

Params = dict[str, Any]
Axes = dict[str, Any]


# ---------------------------------------------------------------------------
# Linear layers (structured or dense) with logical-axis metadata.
# ---------------------------------------------------------------------------


def linear_init(spec: LinearSpec, key, dtype, *, scale=None, bias: bool = False) -> Params:
    p = spec.init(key, dtype=dtype, scale=scale)
    if bias:
        p["bias"] = jnp.zeros((spec.d_out,), dtype=dtype)
    return p


def linear_apply(spec: LinearSpec, params: Params, x: jax.Array) -> jax.Array:
    """Storage-format-aware apply: QArray params route to the structure's
    fused-dequant ``apply_q`` path, float params to the plain ``apply``.
    Traced under the name scope ``linear``."""
    structures.record_dispatch(1)
    with jax.named_scope("linear"):
        if any(qt.is_qarray(v) for v in params.values()):
            y = spec.apply_q(params, x)
        else:
            y = spec.apply(params, x)
        if "bias" in params:
            y = y + params["bias"]
    return y


def linear_group_apply(specs: Sequence[LinearSpec],
                       params_list: Sequence[Params],
                       x: jax.Array, bundle=None) -> list[jax.Array]:
    """Apply several linears that share the input ``x``, collapsing
    shape-congruent bundles (gate+up, MLA a-projections, …) into ONE grouped
    matmul launch (``core/structures.py::group_apply`` → the grouped Pallas
    kernels / batched einsum chain).  All-int4 bundles group too — they
    stack packed and dispatch the grouped q4 kernel.  Non-congruent or
    mixed-storage bundles fall back to the per-projection loop — numerics
    are identical either way (the grouped kernel oracle-matches the loop).

    ``bundle``: an optional pre-stacked ``structures.GroupBundle`` (built
    once at engine load by ``prestack``); when its plan matches the live
    plan the per-step factor stacking is skipped.  A stale bundle (params
    re-quantized or rank-truncated after pre-stacking) mismatches and is
    ignored."""
    plan = structures.group_plan(specs, params_list)
    if plan is None:
        return [linear_apply(s, p, x) for s, p in zip(specs, params_list)]
    core = [{k: v for k, v in p.items() if k != "bias"} for p in params_list]
    stacked = None
    if isinstance(bundle, structures.GroupBundle) and bundle.plan == plan:
        stacked = bundle.arrays
    with jax.named_scope("linear"):
        ys = structures.group_apply(specs, core, x, plan=plan,
                                    stacked=stacked)
        return [y + p["bias"] if "bias" in p else y
                for y, p in zip(ys, params_list)]


def linear_group_prestack(specs: Sequence[LinearSpec],
                          params_list: Sequence[Params]):
    """Load-time counterpart of ``linear_group_apply``: pre-stack a bundle's
    factors once (None if the bundle is not groupable)."""
    return structures.prestack(specs, params_list)


def linear_quantize(spec: LinearSpec, params: Params, bits: int = 8) -> Params:
    """Quantize a linear's structure params to per-block QArrays (bias, if
    any, stays float — it is O(d_out) and added post-matmul)."""
    qp = spec.quantize({k: v for k, v in params.items() if k != "bias"}, bits)
    if "bias" in params:
        qp["bias"] = params["bias"]
    return qp


def linear_axes(spec: LinearSpec, *, bias: bool = False,
                out_axis: str = "model_out", in_axis: str = "fsdp_in") -> Axes:
    """Logical axes for a linear's params.

    Structured kinds carry their own logical names from structures.py; the
    dense kind maps (in, out) -> (in_axis, out_axis).  ``rank`` (BLAST r,
    low-rank t, monarch k) is the TP-sharded dimension.
    """
    ax: Axes = {}
    for name, axes_tuple in spec.logical_axes.items():
        mapped = []
        for a in axes_tuple:
            if a == "in":
                mapped.append(in_axis)
            elif a == "out":
                mapped.append(out_axis)
            else:
                mapped.append(a)
        ax[name] = tuple(mapped)
    if bias:
        ax["bias"] = (None,)
    return ax


def embed_lookup(table, tokens: jax.Array, dtype,
                 parallel=NO_PARALLEL) -> jax.Array:
    """Token-embedding gather over a float or per-row-quantized table.

    Quantized tables gather the *packed* rows first (int4 rows stay nibble-
    packed through the gather), then dequantize only the (B, C) gathered
    rows — the full float table is never materialized.

    Under a TP mesh the table is vocab-sharded, and GSPMD lowers a plain
    gather to an all-gather of the WHOLE table per step.  The one-hot path
    contracts an i32 one-hot against the packed byte rows instead (a
    row-parallel matmul: each shard selects its local vocab rows, one psum
    combines) — gather-then-dequant-rows with collective bytes ∝ gathered
    rows, not table size.  Byte selection through an integer matmul is
    exact, so both paths return bit-identical rows."""
    if not qt.is_qarray(table):
        return table[tokens]
    if parallel.active and parallel.tp_size > 1:
        vocab = table.q.shape[0]
        hot = jax.nn.one_hot(tokens, vocab, dtype=jnp.int32)
        rows = jnp.einsum("...v,vp->...p", hot,
                          table.q.astype(jnp.int32)).astype(table.q.dtype)
        srows = jnp.einsum("...v,v->...", hot.astype(jnp.float32),
                           table.scale[:, 0].astype(jnp.float32))[..., None]
    else:
        rows = table.q[tokens]
        srows = table.scale[tokens]
    if table.bits == 4:
        rows = qt.unpack_int4(rows, table.last_dim)
    return (rows.astype(jnp.float32) * srows).astype(dtype)


def tied_logits(table, x: jax.Array) -> jax.Array:
    """``x @ embedᵀ`` for a float or per-row-quantized embedding table.

    Per-row scales are constant along the contracted d_model axis, so
    dequantization fuses after the matmul (one multiply per logit)."""
    if not qt.is_qarray(table):
        return x @ table.T
    iv = qt.int_values(table)                        # (vocab, d)
    return ((x @ iv.T.astype(x.dtype)) * table.scale[:, 0]).astype(x.dtype)


def linear_dense_matrix(spec: LinearSpec, params: Params) -> jax.Array:
    """Materialize the (d_in, d_out) dense matrix of any structured linear.

    Used by MLA decode to absorb up-projections; cost O(d_in · flops/token).
    Works on quantized params too (routes through the apply_q path).
    """
    p0 = params[next(iter(spec.shapes))]
    dtype = p0.scale.dtype if qt.is_qarray(p0) else p0.dtype
    eye = jnp.eye(spec.d_in, dtype=dtype)
    return linear_apply(spec, {k: v for k, v in params.items() if k != "bias"},
                        eye)


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------


def norm_init(d: int, kind: str, dtype) -> Params:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype=dtype)}
    return {"scale": jnp.ones((d,), dtype=dtype), "bias": jnp.zeros((d,), dtype=dtype)}


def norm_apply(params: Params, x: jax.Array, kind: str,
               eps: float = 1e-6) -> jax.Array:
    """``eps`` is RMSNorm's; LayerNorm keeps its own."""
    if kind == "rmsnorm":
        return ops.rms_norm(x, params["scale"], eps)
    return ops.layer_norm(x, params["scale"], params["bias"])


def norm_axes(kind: str) -> Axes:
    if kind == "rmsnorm":
        return {"scale": (None,)}
    return {"scale": (None,), "bias": (None,)}


# ---------------------------------------------------------------------------
# GQA attention (full or sliding-window; train / prefill / cached decode).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    cfg: ArchConfig
    window: int | None  # None = full attention
    qkv: LinearSpec
    out: LinearSpec
    cross: bool = False  # whisper decoder cross-attention
    causal: bool = True  # False for encoder self-attention

    @property
    def dims(self) -> tuple[int, int, int]:
        c = self.cfg
        return c.n_heads, c.n_kv_heads, c.head_dim_


def make_attention(cfg: ArchConfig, *, window: int | None = None,
                   cross: bool = False, causal: bool = True) -> AttnSpec:
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    d_qkv = (hq + 2 * hkv) * hd
    # Paper §C.2: q/k/v weights stacked and modeled by ONE structured matrix.
    qkv = make_linear(cfg.d_model, d_qkv, cfg.structure)
    out = make_linear(hq * hd, cfg.d_model, cfg.structure)
    return AttnSpec(cfg=cfg, window=window, qkv=qkv, out=out, cross=cross,
                    causal=causal)


def attn_init(spec: AttnSpec, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "qkv": linear_init(spec.qkv, k1, dtype, bias=spec.cfg.qkv_bias),
        "out": linear_init(spec.out, k2, dtype,
                           scale=1.0 / math.sqrt(2 * spec.cfg.n_layers * spec.out.d_in)),
    }


def attn_axes(spec: AttnSpec) -> Axes:
    return {
        "qkv": linear_axes(spec.qkv, bias=spec.cfg.qkv_bias, out_axis="heads"),
        "out": linear_axes(spec.out, in_axis="heads", out_axis="fsdp_in"),
    }


def attn_quantize(spec: AttnSpec, params: Params, bits: int = 8) -> Params:
    return {"qkv": linear_quantize(spec.qkv, params["qkv"], bits),
            "out": linear_quantize(spec.out, params["out"], bits)}


def _split_qkv(spec: AttnSpec, qkv: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    hq, hkv, hd = spec.dims
    *lead, _ = qkv.shape
    q = qkv[..., : hq * hd].reshape(*lead, hq, hd)
    k = qkv[..., hq * hd: (hq + hkv) * hd].reshape(*lead, hkv, hd)
    v = qkv[..., (hq + hkv) * hd:].reshape(*lead, hkv, hd)
    return q, k, v


def _head_spec(parallel: Parallel, n_heads: int, *, seq_fallback: bool):
    """Attention-activation sharding that never splits head_dim.

    §Perf iteration 1: the naive fused-feature constraint lets GSPMD split
    *inside* head_dim whenever heads don't divide TP; the attention-score
    contraction then runs over a sharded dim and every score tile is
    all-reduced (the dominant collective in the baseline profile).

    §Perf iteration 6: when heads ∤ TP, replicating attention 16× blows up
    the compute/memory terms at 32k prefill — instead shard the *query
    sequence* dim (context parallelism): scores shard over q-rows with no
    partial-sum contraction, k/v stay replicated.  Measured crossover: the
    backward-pass reshard of token-sharded activations makes this a small
    loss at T=4k training but a 60–69% collective win at 32k prefill, so it
    engages at T ≥ 8192."""
    tp = parallel.tp_size
    if tp > 1 and n_heads % tp == 0:
        return parallel.batch_spec(None, parallel.model_axis, None)
    if seq_fallback and tp > 1:
        return parallel.batch_spec(parallel.model_axis, None, None)
    return parallel.batch_spec(None, None, None)


_SEQ_FALLBACK_MIN_T = 8192


def attn_apply(spec: AttnSpec, params: Params, x: jax.Array,
               positions: jax.Array, parallel: Parallel = NO_PARALLEL,
               *, memory: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention (training / prefill).  x: (B, T, d)."""
    cfg = spec.cfg
    hq, hkv, hd = spec.dims
    B, T, _ = x.shape
    qkv = linear_apply(spec.qkv, params["qkv"], x)  # (B, T, (hq+2hkv)·hd)
    q, k, v = _split_qkv(spec, qkv)
    long_seq = T >= _SEQ_FALLBACK_MIN_T
    q = parallel.constraint(q, _head_spec(parallel, hq, seq_fallback=long_seq))
    k = parallel.constraint(k, _head_spec(parallel, hkv, seq_fallback=False))
    v = parallel.constraint(v, _head_spec(parallel, hkv, seq_fallback=False))
    if spec.cross:
        assert memory is not None
        mkv = linear_apply(spec.qkv, params["qkv"], memory)
        _, k, v = _split_qkv(spec, mkv)
        causal = False
    else:
        causal = spec.causal
        if cfg.pos_embed == "rope":
            q = ops.rope(q, positions, cfg.rope_theta)
            k = ops.rope(k, positions, cfg.rope_theta)
    o = ops.chunked_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=spec.window,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        scale=cfg.attention_multiplier or None)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, hq * hd)
    y = linear_apply(spec.out, params["out"], o)
    return parallel.shard_batch(y)


def attn_cache_init(spec: AttnSpec, batch: int, max_len: int, dtype) -> Params:
    """KV cache.  Sliding-window layers allocate a ring buffer of the window
    size (this is what makes long_500k decode O(window) not O(T)).  ``pos``
    is per-slot-per-row so continuous batching can mix sequence lengths.

    K and V are stored lane-dense, ``(batch, S, Hkv·D)``: a slot's heads sit
    side by side in one row (512 lanes for 8 heads of 64), and the model's
    scan stacks the layers in front, ``(L, batch, S, Hkv·D)``.  A step
    writes its new columns into that stacked array in place, one scatter
    per leaf at (layer, row, slot) (``cache_write``), and attention reads
    each layer where it lies (``ops.cache_attention``): neither copies or
    re-lays-out a layer.

    With ``cfg.cache_quant`` (the ``quant.cache`` knob or legacy
    ``kv_quant``) the K/V tensors are int8 with per-(slot, head) bf16 scales
    — halves the dominant decode-memory term (beyond-paper; §Perf
    iteration 3).  The same row-wise codec (repro/quant) backs the MLA
    latent and SSD/RG-LRU state caches."""
    hq, hkv, hd = spec.dims
    S = min(max_len, spec.window) if spec.window else max_len
    c: Params = {
        "pos": jnp.full((batch, S), -1, dtype=jnp.int32),
    }
    if spec.cfg.cache_quant:
        c["k"] = jnp.zeros((batch, S, hkv * hd), jnp.int8)
        c["v"] = jnp.zeros((batch, S, hkv * hd), jnp.int8)
        c["k_scale"] = jnp.zeros((batch, S, hkv), jnp.bfloat16)
        c["v_scale"] = jnp.zeros((batch, S, hkv), jnp.bfloat16)
    else:
        c["k"] = jnp.zeros((batch, S, hkv * hd), dtype=dtype)
        c["v"] = jnp.zeros((batch, S, hkv * hd), dtype=dtype)
    return c


def attn_cache_axes(spec: AttnSpec) -> Axes:
    # §Perf iteration 2: shard the cache on the SEQUENCE dim over the model
    # axis — always divisible (unlike kv_heads), so a 32k-deep cache never
    # replicates 16×.  Decode attention contracts s (sharded) → the partial
    # sum is one tiny (B,H,D) all-reduce per layer instead of a 16×-bigger
    # resident cache.  The merged Hkv·D axis keeps the name "kv_heads": it
    # divides wherever Hkv does.
    a: Axes = {"k": ("batch", "kv_seq", "kv_heads"),
               "v": ("batch", "kv_seq", "kv_heads"),
               "pos": ("batch", "kv_seq")}
    # cross-attention memory caches stay float (cross_memory_cache) — only
    # self-attention caches carry int8 + scales under cache_quant
    if spec.cfg.cache_quant and not spec.cross:
        a["k_scale"] = ("batch", "kv_seq", "kv_heads")
        a["v_scale"] = ("batch", "kv_seq", "kv_heads")
    return a


def cache_layer(a: jax.Array, layer: jax.Array | None) -> jax.Array:
    """One layer of a stacked cache leaf; ``layer=None``: ``a`` is one."""
    if layer is None:
        return a
    return jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)


def cache_write(a: jax.Array, layer: jax.Array | None, rows: jax.Array,
                slot: jax.Array, vals: jax.Array) -> jax.Array:
    """Write a chunk's columns into a sequence-axis cache leaf in place.

    ``a``: (B, S, …), or the stacked (L, B, S, …) with ``layer`` naming the
    layer; rows (B, 1), slot (B, C), vals (B, C, …).  A scatter, not a
    ``dynamic_update_slice``: no start index is clamped, so a chunk written
    near the end of the cache never shifts onto live entries.  A column
    whose slot is S (out of bounds) is dropped — dead columns and ring
    tokens overwritten later in the same chunk — so every slot a row does
    not write keeps its content, and ``pos`` stays -1 where nothing was
    written."""
    idx = (rows, slot) if layer is None else (layer, rows, slot)
    return a.at[idx].set(vals.astype(a.dtype), mode="drop")


def _dequantize_heads(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """int8 rows (…, H·D) with per-head scales (…, H) → (…, H·D) floats."""
    heads = q.reshape(*q.shape[:-1], scale.shape[-1], -1)
    return qt.dequantize_rows(heads, scale, dtype).reshape(q.shape)


def _step_vec(step: jax.Array, batch: int) -> jax.Array:
    step = jnp.asarray(step, jnp.int32)
    return jnp.broadcast_to(step, (batch,)) if step.ndim == 0 else step


def attn_prefill(spec: AttnSpec, params: Params, cache: Params, x: jax.Array,
                 steps: jax.Array, n_tokens: jax.Array,
                 parallel: Parallel = NO_PARALLEL,
                 *, memory: jax.Array | None = None,
                 layer: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """Multi-token prefill at per-slot offsets (chunked-prefill step).

    x: (B, C, d); steps: (B,) absolute position of each row's first token;
    n_tokens: (B,) live tokens per row.  Rows are ragged: column i of row b
    is live iff ``i < n_tokens[b]``; dead columns are dropped from the cache
    write (``cache_write``) and produce garbage outputs the engine
    discards.  C=1 with n_tokens=1 is exactly single-token decode.
    ``layer``: the cache leaves are the model's stacked (L, …) cache and
    this is the layer to write and read; the returned leaves are stacked.
    """
    cfg = spec.cfg
    hq, hkv, hd = spec.dims
    scale = cfg.attention_multiplier or None
    B, C, _ = x.shape
    offs = jnp.arange(C, dtype=jnp.int32)
    q_pos = steps[:, None] + offs[None, :]           # (B, C)
    valid = offs[None, :] < n_tokens[:, None]        # (B, C)
    qkv = linear_apply(spec.qkv, params["qkv"], x)
    q, k, v = _split_qkv(spec, qkv)
    if spec.cross:
        # Cross-attention reads the (precomputed) encoder memory cache as-is.
        o = ops.cache_attention(
            q, cache["k"], cache["v"], cache_layer(cache["pos"], layer),
            jnp.full((B, C), jnp.iinfo(jnp.int32).max // 2, jnp.int32),
            layer=layer, scale=scale)
        y = linear_apply(spec.out, params["out"], o.reshape(B, C, hq * hd))
        return parallel.shard_batch(y), cache
    if cfg.pos_embed == "rope":
        q = ops.rope(q, q_pos, cfg.rope_theta)
        k = ops.rope(k, q_pos, cfg.rope_theta)
    S = cache["pos"].shape[-1]
    rows = jnp.arange(B)[:, None]
    if spec.window is None:
        # a full cache holds positions [0, S); a column past it is dropped
        survives = valid & (q_pos < S)
    else:
        # Ring-buffer write: when the chunk is longer than the ring, only a
        # token whose slot is not re-written later in the same chunk
        # survives — i + S >= n_tokens[b].
        survives = valid & (offs[None, :] + S >= n_tokens[:, None])
    slot = jnp.where(survives, q_pos % S, S)         # S = OOB → dropped
    new_cache = dict(cache)
    with jax.named_scope("cache_update"):
        new_cache["pos"] = cache_write(cache["pos"], layer, rows, slot, q_pos)
        if spec.cfg.cache_quant:
            kq, ks = qt.quantize_rows(k)
            vq, vs = qt.quantize_rows(v)
            for name, val in (("k", kq.reshape(B, C, -1)),
                              ("v", vq.reshape(B, C, -1)),
                              ("k_scale", ks), ("v_scale", vs)):
                new_cache[name] = cache_write(cache[name], layer, rows, slot,
                                              val)
        else:
            new_cache["k"] = cache_write(cache["k"], layer, rows, slot,
                                         k.reshape(B, C, hkv * hd))
            new_cache["v"] = cache_write(cache["v"], layer, rows, slot,
                                         v.reshape(B, C, hkv * hd))
    k_pos = cache_layer(new_cache["pos"], layer)

    def floats(c):
        """One layer's K and V as floats, (B, S, Hkv·D) each."""
        if not spec.cfg.cache_quant:
            return cache_layer(c["k"], layer), cache_layer(c["v"], layer)
        return tuple(_dequantize_heads(cache_layer(c[n], layer),
                                       cache_layer(c[n + "_scale"], layer),
                                       x.dtype) for n in ("k", "v"))

    with jax.named_scope("attention"):
        if spec.window is not None and C > 1:
            # Ring hazard: within one chunk a later token may overwrite a
            # slot still inside an earlier query's window.  Attend over the
            # pre-write ring ‖ the chunk itself — the position mask picks
            # the right keys.
            kv_pos = jnp.where(valid, q_pos, -1)
            k_old, v_old = floats(cache)
            if spec.cfg.cache_quant:
                # attend to the chunk's own keys through the same int8
                # round-trip the C=1 path reads back from the cache
                k = qt.dequantize_rows(kq, ks, x.dtype)
                v = qt.dequantize_rows(vq, vs, x.dtype)
            o = ops.cache_attention(
                q,
                jnp.concatenate([k_old, k.reshape(B, C, -1).astype(
                    k_old.dtype)], axis=1),
                jnp.concatenate([v_old, v.reshape(B, C, -1).astype(
                    v_old.dtype)], axis=1),
                jnp.concatenate([cache_layer(cache["pos"], layer), kv_pos],
                                axis=1),
                q_pos, window=spec.window, scale=scale)
        elif spec.cfg.cache_quant:
            k_cache, v_cache = floats(new_cache)
            o = ops.cache_attention(q, k_cache, v_cache, k_pos, q_pos,
                                    window=spec.window, scale=scale)
        else:
            o = ops.cache_attention(q, new_cache["k"], new_cache["v"], k_pos,
                                    q_pos, window=spec.window, layer=layer,
                                    scale=scale)
    y = linear_apply(spec.out, params["out"], o.reshape(B, C, hq * hd))
    return parallel.shard_batch(y), new_cache


def attn_decode(spec: AttnSpec, params: Params, cache: Params, x: jax.Array,
                step: jax.Array, parallel: Parallel = NO_PARALLEL,
                *, memory: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """Single-token decode.  x: (B, 1, d); step: scalar or (B,) positions."""
    B = x.shape[0]
    return attn_prefill(spec, params, cache, x, _step_vec(step, B),
                        jnp.ones((B,), jnp.int32), parallel, memory=memory)


def cross_memory_cache(spec: AttnSpec, params: Params, memory: jax.Array) -> Params:
    """Precompute the decoder cross-attention K/V from encoder output."""
    mkv = linear_apply(spec.qkv, params["qkv"], memory)
    _, k, v = _split_qkv(spec, mkv)
    B, S = memory.shape[0], memory.shape[1]
    return {"k": k.reshape(B, S, -1), "v": v.reshape(B, S, -1),
            "pos": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))}


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V3 §: multi-head latent attention).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLASpec:
    cfg: ArchConfig
    mla: MLACfg
    wq_a: LinearSpec   # d_model -> q_lora
    wq_b: LinearSpec   # q_lora -> H·(nope+rope)
    wkv_a: LinearSpec  # d_model -> kv_lora + rope  (latent + shared k_rope)
    wkv_b: LinearSpec  # kv_lora -> H·(nope+v)
    out: LinearSpec    # H·v -> d_model


def make_mla(cfg: ArchConfig) -> MLASpec:
    m = cfg.mla
    H = cfg.n_heads
    st = cfg.structure
    return MLASpec(
        cfg=cfg, mla=m,
        wq_a=make_linear(cfg.d_model, m.q_lora_rank, st),
        wq_b=make_linear(m.q_lora_rank, H * (m.nope_head_dim + m.rope_head_dim), st),
        wkv_a=make_linear(cfg.d_model, m.kv_lora_rank + m.rope_head_dim, st),
        wkv_b=make_linear(m.kv_lora_rank, H * (m.nope_head_dim + m.v_head_dim), st),
        out=make_linear(H * m.v_head_dim, cfg.d_model, st),
    )


def mla_init(spec: MLASpec, key, dtype) -> Params:
    ks = jax.random.split(key, 5)
    return {
        "wq_a": linear_init(spec.wq_a, ks[0], dtype),
        "q_norm": norm_init(spec.mla.q_lora_rank, "rmsnorm", dtype),
        "wq_b": linear_init(spec.wq_b, ks[1], dtype),
        "wkv_a": linear_init(spec.wkv_a, ks[2], dtype),
        "kv_norm": norm_init(spec.mla.kv_lora_rank, "rmsnorm", dtype),
        "wkv_b": linear_init(spec.wkv_b, ks[3], dtype),
        "out": linear_init(spec.out, ks[4], dtype,
                           scale=1.0 / math.sqrt(2 * spec.cfg.n_layers * spec.out.d_in)),
    }


def mla_axes(spec: MLASpec) -> Axes:
    return {
        "wq_a": linear_axes(spec.wq_a, out_axis=None),
        "q_norm": norm_axes("rmsnorm"),
        "wq_b": linear_axes(spec.wq_b, in_axis=None, out_axis="heads"),
        "wkv_a": linear_axes(spec.wkv_a, out_axis=None),
        "kv_norm": norm_axes("rmsnorm"),
        "wkv_b": linear_axes(spec.wkv_b, in_axis=None, out_axis="heads"),
        "out": linear_axes(spec.out, in_axis="heads", out_axis="fsdp_in"),
    }


def mla_quantize(spec: MLASpec, params: Params, bits: int = 8) -> Params:
    qp = dict(params)  # norms pass through
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "out"):
        qp[name] = linear_quantize(getattr(spec, name), params[name], bits)
    return qp


def mla_prestack(spec: MLASpec, params: Params) -> Params:
    """Pre-stack the MLA a-projection bundle (wq_a + wkv_a) once at load."""
    b = linear_group_prestack((spec.wq_a, spec.wkv_a),
                              (params["wq_a"], params["wkv_a"]))
    return {**params, "_bundle_a": b} if b is not None else params


def kv_cache_rollback(old: Params, new: Params, steps: jax.Array,
                      n_comm: jax.Array) -> Params:
    """Rewind a KV cache (attn ring buffer or MLA latent) to the first
    ``n_comm`` tokens of a chunk written at ``steps``.

    Every leaf carries a position row ``pos (B, S)`` (−1 = empty): entries
    whose position exceeds the last committed one revert to the pre-chunk
    cache.  Reverting from ``old`` (not just clearing) matters for the
    sliding-window ring: a rejected draft's write may have *overwritten* a
    still-live slot (q_pos % S collision), and only the old leaf has the
    original entry.  The result is bit-identical to having written
    ``n_comm`` tokens in the first place."""
    commit_last = (steps + n_comm - 1)[:, None]          # (B, 1)
    revert = new["pos"] > commit_last                    # (B, S)
    out = {}
    for k, v in new.items():
        m = revert.reshape(revert.shape + (1,) * (v.ndim - revert.ndim))
        out[k] = jnp.where(m, old[k], v)
    return out


def _mla_qkv(spec: MLASpec, params: Params, x: jax.Array, positions: jax.Array):
    """Shared q path + latent path.  Returns q_nope, q_rope, latent, k_rope.

    The two a-projections both consume ``x`` and are shape-congruent up to
    zero padding (same d_in, same block count), so they run as one grouped
    launch — a layer-level decode launch saved on every MLA step."""
    m = spec.mla
    H = spec.cfg.n_heads
    *lead, _ = x.shape
    q_lat, kv = linear_group_apply(
        (spec.wq_a, spec.wkv_a), (params["wq_a"], params["wkv_a"]), x,
        bundle=params.get("_bundle_a"))
    q_lat = norm_apply(params["q_norm"], q_lat, "rmsnorm")
    q = linear_apply(spec.wq_b, params["wq_b"], q_lat)
    q = q.reshape(*lead, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    latent, k_rope = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    latent = norm_apply(params["kv_norm"], latent, "rmsnorm")
    q_rope = ops.rope(q_rope, positions, spec.cfg.rope_theta)
    k_rope = ops.rope(k_rope[..., None, :], positions, spec.cfg.rope_theta)[..., 0, :]
    return q_nope, q_rope, latent, k_rope


def mla_apply(spec: MLASpec, params: Params, x: jax.Array, positions: jax.Array,
              parallel: Parallel = NO_PARALLEL) -> jax.Array:
    """Training / prefill MLA: expand latent to per-head K/V, chunked attn."""
    m = spec.mla
    H = spec.cfg.n_heads
    B, T, _ = x.shape
    q_nope, q_rope, latent, k_rope = _mla_qkv(spec, params, x, positions)
    kv = linear_apply(spec.wkv_b, params["wkv_b"], latent)
    kv = kv.reshape(B, T, H, m.nope_head_dim + m.v_head_dim)
    kv = parallel.constraint(kv, _head_spec(parallel, H, seq_fallback=False))
    q_nope = parallel.constraint(
        q_nope, _head_spec(parallel, H, seq_fallback=True))
    k_nope, v = kv[..., : m.nope_head_dim], kv[..., m.nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (B, T, H, m.rope_head_dim))], axis=-1)
    o = ops.chunked_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, q_chunk=spec.cfg.q_chunk, kv_chunk=spec.cfg.kv_chunk)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * m.v_head_dim)
    y = linear_apply(spec.out, params["out"], o)
    return parallel.shard_batch(y)


def mla_cache_init(spec: MLASpec, batch: int, max_len: int, dtype) -> Params:
    """Latent cache; with ``cfg.cache_quant`` the per-token latent and
    shared-rope vectors are int8 with per-(slot, token) bf16 scales — MLA's
    cache is already compressed (kv_lora ≪ H·hd), int8 halves it again."""
    m = spec.mla
    c: Params = {"pos": jnp.full((batch, max_len), -1, dtype=jnp.int32)}
    if spec.cfg.cache_quant:
        c["latent"] = jnp.zeros((batch, max_len, m.kv_lora_rank), jnp.int8)
        c["k_rope"] = jnp.zeros((batch, max_len, m.rope_head_dim), jnp.int8)
        c["latent_scale"] = jnp.zeros((batch, max_len), jnp.bfloat16)
        c["k_rope_scale"] = jnp.zeros((batch, max_len), jnp.bfloat16)
    else:
        c["latent"] = jnp.zeros((batch, max_len, m.kv_lora_rank), dtype=dtype)
        c["k_rope"] = jnp.zeros((batch, max_len, m.rope_head_dim), dtype=dtype)
    return c


def mla_cache_axes(spec: MLASpec) -> Axes:
    a: Axes = {"latent": ("batch", "kv_seq", None),
               "k_rope": ("batch", "kv_seq", None),
               "pos": ("batch", "kv_seq")}
    if spec.cfg.cache_quant:
        a["latent_scale"] = ("batch", "kv_seq")
        a["k_rope_scale"] = ("batch", "kv_seq")
    return a


def mla_prefill(spec: MLASpec, params: Params, cache: Params, x: jax.Array,
                steps: jax.Array, n_tokens: jax.Array,
                parallel: Parallel = NO_PARALLEL, *,
                layer: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """Latent-cache prefill/decode with absorbed up-projections.

    The cache holds only (kv_lora + rope) per token — the whole point of MLA.
    W_uk / W_uv are materialized from the (possibly structured) wkv_b and
    absorbed into the score / output einsums:
        score_h(t) = q_nope_h · W_uk_h · c_t  +  q_rope_h · k_rope_t
        out_h      = (Σ_t p_t · c_t) · W_uv_h
    x: (B, C, d); steps/n_tokens: (B,) per-slot offsets and live counts
    (ragged rows, see ``attn_prefill``; ``layer`` as there).  C=1 is
    classic decode.
    """
    m = spec.mla
    H = spec.cfg.n_heads
    B, C, _ = x.shape
    offs = jnp.arange(C, dtype=jnp.int32)
    q_pos = steps[:, None] + offs[None, :]           # (B, C)
    valid = offs[None, :] < n_tokens[:, None]
    q_nope, q_rope, latent, k_rope = _mla_qkv(spec, params, x, q_pos)
    rows = jnp.arange(B)[:, None]
    S = cache["pos"].shape[-1]
    slot = jnp.where(valid, q_pos, S)                # MLA cache is not a ring
    new_cache: Params = {}
    with jax.named_scope("cache_update"):
        if spec.cfg.cache_quant:
            lq, ls = qt.quantize_rows(latent)
            rq, rs = qt.quantize_rows(k_rope)
            for name, val in (("latent", lq), ("k_rope", rq),
                              ("latent_scale", ls), ("k_rope_scale", rs)):
                new_cache[name] = cache_write(cache[name], layer, rows, slot,
                                              val)
            lat_cache = qt.dequantize_rows(
                cache_layer(new_cache["latent"], layer),
                cache_layer(new_cache["latent_scale"], layer), x.dtype)
            rope_cache = qt.dequantize_rows(
                cache_layer(new_cache["k_rope"], layer),
                cache_layer(new_cache["k_rope_scale"], layer), x.dtype)
        else:
            new_cache["latent"] = cache_write(cache["latent"], layer, rows,
                                              slot, latent)
            new_cache["k_rope"] = cache_write(cache["k_rope"], layer, rows,
                                              slot, k_rope)
            lat_cache = cache_layer(new_cache["latent"], layer)
            rope_cache = cache_layer(new_cache["k_rope"], layer)
        new_cache["pos"] = cache_write(cache["pos"], layer, rows, slot, q_pos)
        k_pos = cache_layer(new_cache["pos"], layer)

    with jax.named_scope("attention"):
        # (kv_lora, H·(nope+v))
        w = linear_dense_matrix(spec.wkv_b, params["wkv_b"])
        w = w.reshape(m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim)
        w_uk, w_uv = w[..., : m.nope_head_dim], w[..., m.nope_head_dim:]

        q_lat = jnp.einsum("bthn,chn->bthc", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))  # (B,C,H,kv_lora)
        scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
        s = (jnp.einsum("bthc,bsc->bhts", q_lat,
                        lat_cache.astype(jnp.float32))
             + jnp.einsum("bthr,bsr->bhts", q_rope.astype(jnp.float32),
                          rope_cache.astype(jnp.float32))) * scale
        ok = ((k_pos[:, None, :] >= 0)
              & (k_pos[:, None, :] <= q_pos[:, :, None]))
        s = jnp.where(ok[:, None, :, :], s, ops.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)  # idle slots see an empty cache
        o_lat = jnp.einsum("bhts,bsc->bthc", p,
                           lat_cache.astype(jnp.float32))
        o = jnp.einsum("bthc,hcv->bthv", o_lat,
                       w_uv.transpose(1, 0, 2).astype(jnp.float32))
        o = o.reshape(B, C, H * m.v_head_dim).astype(x.dtype)
    y = linear_apply(spec.out, params["out"], o)
    return parallel.shard_batch(y), new_cache


def mla_decode(spec: MLASpec, params: Params, cache: Params, x: jax.Array,
               step: jax.Array, parallel: Parallel = NO_PARALLEL
               ) -> tuple[jax.Array, Params]:
    """Single-token MLA decode — ``mla_prefill`` with C=1."""
    B = x.shape[0]
    return mla_prefill(spec, params, cache, x, _step_vec(step, B),
                       jnp.ones((B,), jnp.int32), parallel)


# ---------------------------------------------------------------------------
# Feed-forward (SwiGLU / GELU), structured.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FFNSpec:
    """SwiGLU FFNs model gate and up as two congruent (d → ff) structured
    linears sharing the input — the canonical grouped-projection bundle:
    they dispatch as ONE grouped matmul launch (``linear_group_apply``)
    with one x-tile load, same total parameter budget as the previously
    fused d → 2·ff matrix.  GELU FFNs keep the single ``wi``."""

    kind: str  # swiglu | gelu
    wo: LinearSpec                 # ff -> d
    wi: LinearSpec | None = None   # gelu: d -> ff
    gate: LinearSpec | None = None  # swiglu: d -> ff
    up: LinearSpec | None = None    # swiglu: d -> ff

    @property
    def in_specs(self) -> tuple[LinearSpec, ...]:
        """The input-side projection bundle (all consume the block input)."""
        return (self.gate, self.up) if self.kind == "swiglu" else (self.wi,)


def make_ffn(d_model: int, d_ff: int, kind: str,
             structure: StructureConfig) -> FFNSpec:
    wo = make_linear(d_ff, d_model, structure)
    if kind == "swiglu":
        return FFNSpec(kind=kind, wo=wo,
                       gate=make_linear(d_model, d_ff, structure),
                       up=make_linear(d_model, d_ff, structure))
    return FFNSpec(kind=kind, wo=wo, wi=make_linear(d_model, d_ff, structure))


def ffn_init(spec: FFNSpec, key, dtype, n_layers: int = 1) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    wo_scale = 1.0 / math.sqrt(2 * n_layers * spec.wo.d_in)
    if spec.kind == "swiglu":
        return {"gate": linear_init(spec.gate, k1, dtype),
                "up": linear_init(spec.up, k3, dtype),
                "wo": linear_init(spec.wo, k2, dtype, scale=wo_scale)}
    return {"wi": linear_init(spec.wi, k1, dtype),
            "wo": linear_init(spec.wo, k2, dtype, scale=wo_scale)}


def ffn_axes(spec: FFNSpec) -> Axes:
    a: Axes = {"wo": linear_axes(spec.wo, in_axis="ffn", out_axis="fsdp_in")}
    if spec.kind == "swiglu":
        a["gate"] = linear_axes(spec.gate, out_axis="ffn")
        a["up"] = linear_axes(spec.up, out_axis="ffn")
    else:
        a["wi"] = linear_axes(spec.wi, out_axis="ffn")
    return a


def ffn_quantize(spec: FFNSpec, params: Params, bits: int = 8) -> Params:
    if spec.kind == "swiglu":
        return {"gate": linear_quantize(spec.gate, params["gate"], bits),
                "up": linear_quantize(spec.up, params["up"], bits),
                "wo": linear_quantize(spec.wo, params["wo"], bits)}
    return {"wi": linear_quantize(spec.wi, params["wi"], bits),
            "wo": linear_quantize(spec.wo, params["wo"], bits)}


def ffn_prestack(spec: FFNSpec, params: Params) -> Params:
    """Pre-stack the SwiGLU gate+up bundle once at load (GELU: no bundle)."""
    if spec.kind != "swiglu":
        return params
    b = linear_group_prestack((spec.gate, spec.up),
                              (params["gate"], params["up"]))
    return {**params, "_bundle_in": b} if b is not None else params


def ffn_apply(spec: FFNSpec, params: Params, x: jax.Array,
              parallel: Parallel = NO_PARALLEL) -> jax.Array:
    if spec.kind == "swiglu":
        gate, up = linear_group_apply(
            (spec.gate, spec.up), (params["gate"], params["up"]), x,
            bundle=params.get("_bundle_in"))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(linear_apply(spec.wi, params["wi"], x))
    h = parallel.constraint(h, parallel.batch_spec(None, parallel.model_axis))
    y = linear_apply(spec.wo, params["wo"], h)
    return parallel.shard_batch(y)
