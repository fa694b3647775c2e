"""Jit'd public wrappers around the Pallas kernels: shape padding, block-size
selection (VMEM budgeting + optional measured autotune cache), and the XLA
einsum path used under GSPMD (pjit shards the einsum chain; the Pallas path
is for shard_map-per-device execution on real TPUs).

Where the kernels run is decided once, by ``_interpret``: compiled on the
TPU, interpreted on the CPU (the test backend), and refused anywhere else."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import quant as qt
from repro.kernels import autotune, ref
from repro.kernels.blast_matmul import (VMEM_BUDGET,
                                        blast_matmul_grouped_pallas,
                                        blast_matmul_grouped_q4_pallas,
                                        blast_matmul_grouped_q_pallas,
                                        blast_matmul_grouped_w4a8_pallas,
                                        blast_matmul_grouped_w8a8_pallas,
                                        blast_matmul_pallas,
                                        blast_matmul_q4_pallas,
                                        blast_matmul_q_pallas,
                                        blast_matmul_w4a8_pallas,
                                        blast_matmul_w8a8_pallas, vmem_bytes)
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           flash_attention_prefill_pallas)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _interpret() -> bool:
    """Pallas kernels compile for the TPU and run interpreted on the CPU;
    any other backend is an error, never a silent interpreter fallback."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels need a TPU (or the CPU "
                           f"interpreter); the default backend is {backend!r}")
    return backend == "cpu"


def _rank_lane(packed: bool) -> int:
    """Lane granule of ``block_r``: the r tile is the last (lane) axis of the
    U/S/V blocks, so it must be a multiple of 128 — of packed *bytes* for
    nibble-packed int4, i.e. 256 logical ranks."""
    return 256 if packed else 128


def _legal_block_r(block_r: int, r: int, packed: bool) -> int:
    """A block_r the TPU compiler accepts: the whole padded rank when r fits
    one lane granule (float/int8 pad r to 8, int4 to 256), else a multiple
    of the granule."""
    lane = _rank_lane(packed)
    if r <= lane:
        return lane if packed else _round_up(r, 8)
    return max(lane, block_r // lane * lane)


def pick_blast_blocks(T: int, m: int, n: int, b: int, r: int,
                      bytes_per_el: int = 4,
                      factor_bytes: float | None = None) -> tuple[int, int]:
    """Choose (block_t, block_r) so the VMEM resident set fits the budget.

    The resident set is ``blast_matmul.vmem_bytes``: double-buffered x / U /
    S / V / out blocks plus the fp32 z and y scratch.  ``factor_bytes``
    sizes the U/S/V terms when they differ from the activations (int8
    factors with float x, 0.5 for nibble-packed int4); it defaults to
    ``bytes_per_el``.  ``block_r`` comes from the legal lattice (multiples
    of ``_rank_lane``, largest first); when nothing fits, the smallest legal
    tiling is returned and the kernel raises its VMEM limit to match.

    Candidate ``block_t`` starts at the call's actual (rounded-up) T, not a
    flat 128: a decode-sized T=1..8 call must not budget VMEM for 128-row
    tiles it never materializes — that used to force needlessly small
    ``block_r`` for skinny calls.
    """
    fb = bytes_per_el if factor_bytes is None else factor_bytes
    packed = fb < 1
    lane = _rank_lane(packed)
    r_cap = _round_up(r, lane)
    block_t = min(128, _round_up(max(T, 1), 8))
    while True:
        for br in (4 * lane, 2 * lane, lane):
            br = min(br, r_cap)
            if vmem_bytes(block_t, br, m, n, b, bytes_per_el, fb,
                          bytes_per_el) <= VMEM_BUDGET:
                return block_t, br
        if block_t <= 8:
            return 8, lane
        block_t = max(8, block_t // 2)


def _resolve_blocks(block_t: int | None, block_r: int | None, T: int, m: int,
                    n: int, b: int, r: int, x_dtype, factor_bytes,
                    G: int, kind: str, act: str = "none") -> tuple[int, int]:
    """Explicit blocks win; else the autotune cache (when enabled); else the
    VMEM heuristic — either of the last two clamped to the call's T and
    made legal for the TPU (``_legal_block_r``).  All inputs are trace-time
    statics.  ``act`` is the activation storage ("none" | "int8") — part of
    the autotune key, since int8 x-tiles shift the VMEM balance and the MXU
    path entirely."""
    if block_t is not None and block_r is not None:
        return block_t, block_r
    x_bytes = 1 if act == "int8" else jnp.dtype(x_dtype).itemsize
    hit = autotune.lookup(autotune.Key(
        T=T, m=m, n=n, b=b, r=r, G=G, dtype=jnp.dtype(x_dtype).name,
        kind=kind, backend=jax.default_backend(), act=act))
    if hit is not None:
        bt, br = hit
    else:
        bt, br = pick_blast_blocks(T, m, n, b, r, x_bytes, factor_bytes)
    block_t = block_t or min(bt, _round_up(T, 8))
    block_r = block_r or _legal_block_r(br, r, kind == "int4")
    return block_t, block_r


def _flatten_x(x: jax.Array) -> tuple[jax.Array, tuple[int, ...], int]:
    lead = x.shape[:-1]
    T = 1
    for d in lead:
        T *= d
    return x.reshape(T, x.shape[-1]), lead, T


def _pad_t(xf: jax.Array, T: int, block_t: int) -> tuple[jax.Array, int]:
    T_pad = _round_up(T, block_t)
    if T_pad != T:
        xf = jnp.pad(xf, ((0, T_pad - T), (0, 0)))
    return xf, T_pad


def _pad_last(a: jax.Array, target: int) -> jax.Array:
    """Zero-pad the trailing (rank or packed-rank) axis — exact for BLAST:
    padded ranks / zero nibble codes contribute nothing to the contraction."""
    if a.shape[-1] == target:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, target - a.shape[-1])])


@functools.partial(jax.jit, static_argnames=("block_t", "block_r", "use_pallas"))
def blast_matmul(
    x: jax.Array,
    U: jax.Array,
    S: jax.Array,
    V: jax.Array,
    *,
    block_t: int | None = None,
    block_r: int | None = None,
    use_pallas: bool = True,
) -> jax.Array:
    """x: (..., n) → (..., m).  Pads T and r to block multiples."""
    if not use_pallas:
        return ref.blast_matmul_ref(x, U, S, V)
    interpret = _interpret()
    b, p, r = U.shape
    q = V.shape[1]
    m, n = b * p, b * q
    xf, lead, T = _flatten_x(x)
    block_t, block_r = _resolve_blocks(block_t, block_r, T, m, n, b, r,
                                       x.dtype, x.dtype.itemsize, 1, "float")
    xf, _ = _pad_t(xf, T, block_t)
    r_pad = _round_up(r, block_r)
    U, S, V = (_pad_last(a, r_pad) for a in (U, S, V))
    y = blast_matmul_pallas(xf, U, S, V, block_t=block_t, block_r=block_r,
                            interpret=interpret)
    return y[:T].reshape(*lead, m)


@functools.partial(jax.jit, static_argnames=("block_t", "block_r", "use_pallas"))
def blast_matmul_grouped(
    x: jax.Array,
    U: jax.Array,
    S: jax.Array,
    V: jax.Array,
    *,
    block_t: int | None = None,
    block_r: int | None = None,
    use_pallas: bool = True,
) -> jax.Array:
    """Grouped BLAST matmul: G congruent factor sets over one shared input.

    x: (..., n); U (G,b,p,r), S (G,b,b,r), V (G,b,q,r) → (G, ..., m) in one
    kernel launch (one x-tile load amortized over the whole group).
    """
    if not use_pallas:
        return ref.blast_matmul_grouped_ref(x, U, S, V)
    interpret = _interpret()
    G, b, p, r = U.shape
    q = V.shape[2]
    m, n = b * p, b * q
    xf, lead, T = _flatten_x(x)
    block_t, block_r = _resolve_blocks(block_t, block_r, T, m, n, b, r,
                                       x.dtype, x.dtype.itemsize, G, "float")
    xf, _ = _pad_t(xf, T, block_t)
    r_pad = _round_up(r, block_r)
    U, S, V = (_pad_last(a, r_pad) for a in (U, S, V))
    y = blast_matmul_grouped_pallas(xf, U, S, V, block_t=block_t,
                                    block_r=block_r, interpret=interpret)
    return y[:, :T].reshape(G, *lead, m)


def _quantize_pad_x(xf: jax.Array, T: int,
                    block_t: int) -> tuple[jax.Array, jax.Array]:
    """Fused kernel prologue for the integer-activation path: per-token int8
    quantize of the flattened input, then zero-pad codes AND scales to the
    T block multiple (zero codes × zero scale dequantize to exactly 0)."""
    xq, sx = qt.quantize_act(xf)
    T_pad = _round_up(T, block_t)
    if T_pad != T:
        xq = jnp.pad(xq, ((0, T_pad - T), (0, 0)))
        sx = jnp.pad(sx, ((0, T_pad - T), (0, 0)))
    return xq, sx


@functools.partial(jax.jit, static_argnames=("block_t", "block_r",
                                             "use_pallas", "act"))
def blast_matmul_q(
    x: jax.Array,
    Uq: "qt.QArray",
    Sq: "qt.QArray",
    Vq: "qt.QArray",
    *,
    block_t: int | None = None,
    block_r: int | None = None,
    use_pallas: bool = True,
    act: str = "none",
) -> jax.Array:
    """Quantized-factor BLAST matmul: x (..., n) → (..., m).

    Takes the per-block ``QArray`` factors produced by the blast
    ``LinearSpec.quantize`` (U/V: one scale per block, S: one per coupling
    vector — folded to a per-(i, j) scalar grid for the kernel); scales ride
    in via scalar prefetch.  int8 factors feed the fused int8 kernel; int4
    factors stay *nibble-packed* all the way into VMEM and dispatch to
    ``blast_matmul_q4_pallas`` (half the U/S/V HBM reads again) — the packed
    uint8 arrays are the pallas_call operands, no int8 materialization.

    ``act="int8"`` selects the true integer-compute path (W8A8 / W4A8): x
    is quantized per token inside this jitted wrapper (one fused prologue
    per layer input) and stage 1 contracts codes in int32.
    """
    b = Uq.q.shape[0]
    su = Uq.scale.reshape(b)
    ss = Sq.scale.reshape(b, b)
    sv = Vq.scale.reshape(b)
    bits = {Uq.bits, Sq.bits, Vq.bits}
    if not use_pallas:
        U8, S8, V8 = (qt.int_values(a) for a in (Uq, Sq, Vq))
        if act == "int8":
            xf, lead, T = _flatten_x(x)
            xq, sx = qt.quantize_act(xf)
            y = ref.blast_matmul_a8_ref(xq, sx, U8, S8, V8, su, ss, sv)
            return y.reshape(*lead, b * U8.shape[1]).astype(x.dtype)
        return ref.blast_matmul_q_ref(x, U8, S8, V8, su, ss, sv)
    interpret = _interpret()
    if bits == {4}:
        b, p, r = Uq.shape            # logical (unpacked) factor shape
        q = Vq.shape[1]
        m, n = b * p, b * q
        xf, lead, T = _flatten_x(x)
        block_t, block_r = _resolve_blocks(block_t, block_r, T, m, n, b, r,
                                           x.dtype, 0.5, 1, "int4", act)
        r_pad = _round_up(r, block_r)
        Up, Sp, Vp = (_pad_last(a.q, r_pad // 2) for a in (Uq, Sq, Vq))
        if act == "int8":
            xq, sx = _quantize_pad_x(xf, T, block_t)
            y = blast_matmul_w4a8_pallas(xq, sx, Up, Sp, Vp, su, ss, sv,
                                         block_t=block_t, block_r=block_r,
                                         interpret=interpret,
                                         out_dtype=x.dtype)
        else:
            xf, _ = _pad_t(xf, T, block_t)
            y = blast_matmul_q4_pallas(xf, Up, Sp, Vp, su, ss, sv,
                                       block_t=block_t, block_r=block_r,
                                       interpret=interpret)
        return y[:T].reshape(*lead, m)
    U8, S8, V8 = qt.int_values(Uq), qt.int_values(Sq), qt.int_values(Vq)
    b, p, r = U8.shape
    q = V8.shape[1]
    m, n = b * p, b * q
    xf, lead, T = _flatten_x(x)
    block_t, block_r = _resolve_blocks(block_t, block_r, T, m, n, b, r,
                                       x.dtype, 1, 1, "int8", act)
    r_pad = _round_up(r, block_r)
    U8, S8, V8 = (_pad_last(a, r_pad) for a in (U8, S8, V8))
    if act == "int8":
        xq, sx = _quantize_pad_x(xf, T, block_t)
        y = blast_matmul_w8a8_pallas(xq, sx, U8, S8, V8, su, ss, sv,
                                     block_t=block_t, block_r=block_r,
                                     interpret=interpret, out_dtype=x.dtype)
    else:
        xf, _ = _pad_t(xf, T, block_t)
        y = blast_matmul_q_pallas(xf, U8, S8, V8, su, ss, sv, block_t=block_t,
                                  block_r=block_r, interpret=interpret)
    return y[:T].reshape(*lead, m)


@functools.partial(jax.jit, static_argnames=("block_t", "block_r",
                                             "use_pallas", "act"))
def blast_matmul_grouped_q(
    x: jax.Array,
    U8: jax.Array,
    S8: jax.Array,
    V8: jax.Array,
    su: jax.Array,
    ss: jax.Array,
    sv: jax.Array,
    *,
    block_t: int | None = None,
    block_r: int | None = None,
    use_pallas: bool = True,
    act: str = "none",
) -> jax.Array:
    """Grouped int8-factor BLAST matmul over one shared input.

    x (..., n); U8 (G,b,p,r), S8 (G,b,b,r), V8 (G,b,q,r) int8 codes;
    su/sv (G,b), ss (G,b,b) float scales → (G, ..., m), one launch.
    ``act="int8"`` quantizes x per token once for the whole bundle and runs
    the grouped W8A8 kernel.
    """
    G, b, p, r = U8.shape
    q = V8.shape[2]
    m, n = b * p, b * q
    if not use_pallas:
        if act == "int8":
            xf, lead, T = _flatten_x(x)
            xq, sx = qt.quantize_act(xf)
            y = ref.blast_matmul_grouped_a8_ref(xq, sx, U8, S8, V8,
                                                su, ss, sv)
            return y.reshape(G, *lead, m).astype(x.dtype)
        return ref.blast_matmul_grouped_q_ref(x, U8, S8, V8, su, ss, sv)
    interpret = _interpret()
    xf, lead, T = _flatten_x(x)
    block_t, block_r = _resolve_blocks(block_t, block_r, T, m, n, b, r,
                                       x.dtype, 1, G, "int8", act)
    r_pad = _round_up(r, block_r)
    U8, S8, V8 = (_pad_last(a, r_pad) for a in (U8, S8, V8))
    if act == "int8":
        xq, sx = _quantize_pad_x(xf, T, block_t)
        y = blast_matmul_grouped_w8a8_pallas(xq, sx, U8, S8, V8, su, ss, sv,
                                             block_t=block_t, block_r=block_r,
                                             interpret=interpret,
                                             out_dtype=x.dtype)
    else:
        xf, _ = _pad_t(xf, T, block_t)
        y = blast_matmul_grouped_q_pallas(xf, U8, S8, V8, su, ss, sv,
                                          block_t=block_t, block_r=block_r,
                                          interpret=interpret)
    return y[:, :T].reshape(G, *lead, m)


@functools.partial(jax.jit, static_argnames=("block_t", "block_r",
                                             "use_pallas", "act"))
def blast_matmul_grouped_q4(
    x: jax.Array,
    Up: jax.Array,
    Sp: jax.Array,
    Vp: jax.Array,
    su: jax.Array,
    ss: jax.Array,
    sv: jax.Array,
    *,
    block_t: int | None = None,
    block_r: int | None = None,
    use_pallas: bool = True,
    act: str = "none",
) -> jax.Array:
    """Grouped *nibble-packed* int4 BLAST matmul over one shared input —
    the launch-count hole closer: all-int4 bundles used to fall back to G
    per-member ``blast_matmul_q`` calls.

    x (..., n); Up (G,b,p,r/2), Sp (G,b,b,r/2), Vp (G,b,q,r/2) uint8 nibble
    pairs (packed along r, ``quant/qarray.py`` layout — they stay packed
    into VMEM); su/sv (G,b), ss (G,b,b) float scales → (G, ..., m), one
    launch.  ``act="int8"`` adds per-token activation codes → grouped W4A8.
    """
    G, b, p, r2 = Up.shape
    q = Vp.shape[2]
    r = 2 * r2
    m, n = b * p, b * q
    if not use_pallas:
        U8, S8, V8 = (qt.unpack_int4_planes(a) for a in (Up, Sp, Vp))
        if act == "int8":
            xf, lead, T = _flatten_x(x)
            xq, sx = qt.quantize_act(xf)
            y = ref.blast_matmul_grouped_a8_ref(xq, sx, U8, S8, V8,
                                                su, ss, sv)
            return y.reshape(G, *lead, m).astype(x.dtype)
        return ref.blast_matmul_grouped_q_ref(x, U8, S8, V8, su, ss, sv)
    interpret = _interpret()
    xf, lead, T = _flatten_x(x)
    block_t, block_r = _resolve_blocks(block_t, block_r, T, m, n, b, r,
                                       x.dtype, 0.5, G, "int4", act)
    r_pad = _round_up(r, block_r)
    Up, Sp, Vp = (_pad_last(a, r_pad // 2) for a in (Up, Sp, Vp))
    if act == "int8":
        xq, sx = _quantize_pad_x(xf, T, block_t)
        y = blast_matmul_grouped_w4a8_pallas(xq, sx, Up, Sp, Vp, su, ss, sv,
                                             block_t=block_t, block_r=block_r,
                                             interpret=interpret,
                                             out_dtype=x.dtype)
    else:
        xf, _ = _pad_t(xf, T, block_t)
        y = blast_matmul_grouped_q4_pallas(xf, Up, Sp, Vp, su, ss, sv,
                                           block_t=block_t, block_r=block_r,
                                           interpret=interpret)
    return y[:, :T].reshape(G, *lead, m)


# ---------------------------------------------------------------------------
# Tensor-parallel grouped wrappers: the BLAST rank contraction is a sum, so
# sharding the trailing rank axis of U/S/V across the mesh "model" axis makes
# stages 1-2 fully local and costs ONE stage-3 psum per bundle (the Megatron
# row-parallel pattern, DESIGN.md §3).  Each device runs its own grouped
# kernel launch on its local rank shard — one launch per bundle per shard —
# and ``_resolve_blocks`` / the autotune cache see the *local* shapes
# (r/tp), so per-shard tilings tune independently of the 1-device ones.
#
# Exactness: the per-block scales su/ss/sv are constant along r, so scaling
# each shard's partial output and summing equals scaling the full sum.  For
# the packed-int4 variant the byte axis is sharded instead (r2 = r/2 bytes);
# tp must divide r2, which keeps nibble pairs on one shard, and the plane
# unpack is a local rank permutation — invariant under the contraction.
# ---------------------------------------------------------------------------


def _grouped_tp(inner, x, factors, scales, *, mesh, axis, kwargs):
    tp = mesh.shape[axis]
    r_stored = factors[0].shape[-1]
    if tp == 1 or r_stored % tp != 0:
        # not rank-shardable on this mesh → single grouped launch, replicated
        return inner(x, *factors, *scales, **kwargs)
    fspec = P(None, None, None, axis)
    rep = P()

    def body(xl, Ul, Sl, Vl, *sc):
        y = inner(xl, Ul, Sl, Vl, *sc, **kwargs)
        return jax.lax.psum(y, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, fspec, fspec, fspec) + (rep,) * len(scales),
        out_specs=rep, check_vma=False)(x, *factors, *scales)


def blast_matmul_grouped_tp(x, U, S, V, *, mesh, axis="model",
                            block_t=None, block_r=None, use_pallas=True):
    """``blast_matmul_grouped`` under shard_map: float factors rank-sharded
    over ``mesh.shape[axis]`` devices, one grouped launch per shard plus one
    stage-3 psum.  Falls back to the replicated single launch when the rank
    is not divisible by the axis size."""
    return _grouped_tp(blast_matmul_grouped, x, (U, S, V), (),
                       mesh=mesh, axis=axis,
                       kwargs=dict(block_t=block_t, block_r=block_r,
                                   use_pallas=use_pallas))


def blast_matmul_grouped_q_tp(x, U8, S8, V8, su, ss, sv, *, mesh,
                              axis="model", block_t=None, block_r=None,
                              use_pallas=True, act="none"):
    """``blast_matmul_grouped_q`` under shard_map: int8 codes rank-sharded,
    per-block scales replicated (they are constant along r).  With
    ``act="int8"`` every shard quantizes the replicated x identically, so
    the W8A8 path stays bit-identical to the 1-device launch."""
    return _grouped_tp(blast_matmul_grouped_q, x, (U8, S8, V8), (su, ss, sv),
                       mesh=mesh, axis=axis,
                       kwargs=dict(block_t=block_t, block_r=block_r,
                                   use_pallas=use_pallas, act=act))


def blast_matmul_grouped_q4_tp(x, Up, Sp, Vp, su, ss, sv, *, mesh,
                               axis="model", block_t=None, block_r=None,
                               use_pallas=True, act="none"):
    """``blast_matmul_grouped_q4`` under shard_map: the nibble-packed byte
    axis (r/2) is sharded, so factors stay packed per shard and unpack
    in-register as usual; tp must divide the byte count (else the replicated
    fallback runs)."""
    return _grouped_tp(blast_matmul_grouped_q4, x, (Up, Sp, Vp),
                       (su, ss, sv), mesh=mesh, axis=axis,
                       kwargs=dict(block_t=block_t, block_r=block_r,
                                   use_pallas=use_pallas, act=act))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "block_q", "block_kv", "use_pallas"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    use_pallas: bool = True,
) -> jax.Array:
    """q: (B, Hq, T, D); k, v: (B, Hkv, S, D) → (B, Hq, T, D)."""
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
    interpret = _interpret()
    B, Hq, T, D = q.shape
    S_len = k.shape[2]
    block_q = min(block_q, _round_up(T, 8))
    block_kv = min(block_kv, _round_up(S_len, 8))
    T_pad = _round_up(T, block_q)
    S_pad = _round_up(S_len, block_kv)
    if T_pad != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    if S_pad != S_len:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad - S_len), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad - S_len), (0, 0)))
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        kv_len=S_len, block_q=block_q, block_kv=block_kv, interpret=interpret)
    return out[:, :, :T, :]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "use_pallas"))
def flash_attention_prefill(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offsets: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    use_pallas: bool = True,
) -> jax.Array:
    """Chunked-prefill attention at per-sequence offsets (continuous batching).

    q: (B, Hq, C, D) — one C-token chunk per row; k, v: (B, Hkv, S, D) — the
    positional KV cache (chunk keys already written at their absolute slots);
    q_offsets: (B,) int32 first-token position per row.  The causal mask is
    shifted by each row's offset — the C×max_len prefill step of the serving
    engine's mixed batches.
    """
    if not use_pallas:
        return ref.attention_prefill_ref(q, k, v, q_offsets, causal=causal,
                                         window=window)
    interpret = _interpret()
    B, Hq, T, D = q.shape
    S_len = k.shape[2]
    block_q = min(block_q, _round_up(T, 8))
    block_kv = min(block_kv, _round_up(S_len, 8))
    T_pad = _round_up(T, block_q)
    S_pad = _round_up(S_len, block_kv)
    if T_pad != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    if S_pad != S_len:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad - S_len), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad - S_len), (0, 0)))
    out = flash_attention_prefill_pallas(
        q, k, v, q_offsets, causal=causal, window=window, kv_len=S_len,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return out[:, :, :T, :]
