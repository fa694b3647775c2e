"""Fused BLAST matmul Pallas TPU kernels (paper Alg. 1, TPU-native).

GPU version (paper App. A): three separate ``torch.bmm``/broadcast kernels,
materializing ``Z = (b, T, r)`` and ``W = (b, T, r)`` in HBM between calls.

TPU adaptation: one fused kernel.  Grid = ``(T_tiles, r_tiles, b_i)``:

  * at ``i == 0`` the stage-1 products ``z_j = x_j @ V_j[:, rt]`` for *all*
    input blocks j are computed into a VMEM scratch ``(b, T_t, r_t)`` — once
    per (T, r) tile, amortized over all b output blocks;
  * each i does the VPU coupling reduce ``w_i = Σ_j s_ij ⊙ z_j`` and the MXU
    projection ``y_i += w_i @ U_iᵀ``, accumulated in a fp32 VMEM scratch
    ``(b, T_t, p)`` that is flushed to HBM once per T tile.

Z and W therefore never touch HBM; the only HBM traffic is X, U/S/V (once
per T tile) and Y (once).

Block layout: the kernels see x as ``(b, T, q)`` and write y as
``(b, T, p)`` — the wrappers below regroup ``(T, n)`` / ``(T, m)`` around
the ``pallas_call``.  Every per-block access in the kernel body is then an
index on a leading (untiled) axis: ``x_ref[j]``, ``s_ref[i]``, ``y_scr[i]``.
That is what the TPU compiler needs for any block width: a column slice at
``i·p`` lands on a lane offset that is a multiple of 128 only when p is,
and neither Llama-7B's FFN (p = 11008/16 = 688) nor SmolLM (p = 36) is.
Block shapes (``block_t`` rows, ``block_r`` ranks) are chosen in ``ops.py``
so the resident set (``vmem_bytes``) fits the scoped VMEM limit.

Variants (all share the ``_stages`` scaffold and the ``_blast_call``
wrapper — the three compute stages, accumulator init and flush, grid and
block specs are written once, parameterized by factor loaders / per-stage
dequant scalers):

  * ``blast_matmul_pallas``             float factors
  * ``blast_matmul_q_pallas``           int8-code factors, per-block scales
  * ``blast_matmul_q4_pallas``          nibble-packed int4 factors (packed in
                                        HBM *and* VMEM; unpacked in-register)
  * ``blast_matmul_grouped_pallas``     G stacked factor sets, one shared x
  * ``blast_matmul_grouped_q_pallas``   grouped + int8 factors
  * ``blast_matmul_grouped_q4_pallas``  grouped + packed int4 factors
  * ``blast_matmul_w8a8_pallas``        int8 factors × int8 activation codes
  * ``blast_matmul_w4a8_pallas``        packed int4 factors × int8 act codes
  * ``blast_matmul_grouped_w8a8_pallas`` / ``…_w4a8_pallas``  grouped ditto

Integer activations (the ``w8a8``/``w4a8`` variants): ``x`` arrives as int8
per-token codes with a fp32 per-row scale ``sx (T, 1)``.  Stage 1 — the only
stage that contracts activations — runs as a true int8×int8 MXU dot
accumulating in int32 (``preferred_element_type=jnp.int32``); the fused
dequant multiplies the int32 tile by the *product* ``sx · sv_j`` once, after
the dot.  The int32 stage-1 result is exact (|codes| ≤ 127, so q·127² per
row fits int32 for any realistic block width), so the only error the A8
path adds over the weight-only kernels is the activation rounding itself —
stages 2–3 then run on the already-dequantized fp32 ``z`` exactly as in the
weight-only kernels, keeping one shared ``_stages`` body and avoiding the
int32 overflow / requantization error a fully-integer stage 2 would incur
(``z`` entries reach q·16129 before coupling scales are applied).

Grouped kernels add a leading grid dimension over G: the x tile's block
index is independent of ``g``, so Pallas keeps it resident in VMEM across
the whole group — G shape-congruent projections (qkv bundles, gate+up,
MLA a-projections) cost one kernel launch and one x-tile load instead of G.

int4 layout: factors are nibble-packed along r (two codes per byte, the
``quant/qarray.py`` interleaved convention — byte k of a tile holds logical
ranks 2k and 2k+1).  The kernel unpacks each VMEM tile in-register into
*plane order* ``[even ranks | odd ranks]`` without re-interleaving: the
BLAST contraction reduces over r in stages 2–3 only, so any r-permutation
applied consistently to U, S, V and the derived z is exact.  Padding r to a
block multiple appends zero bytes (zero codes), which contribute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The compiler's default scoped-VMEM limit on v5e.  ``ops.pick_blast_blocks``
# keeps ``vmem_bytes`` under ``VMEM_BUDGET``; a call whose smallest legal
# tiling still exceeds it (fp32 factors at wide q) raises the limit instead.
_DEFAULT_VMEM_LIMIT = 16 * 1024 * 1024
VMEM_BUDGET = 12 * 1024 * 1024


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


def vmem_bytes(block_t: int, block_r: int, m: int, n: int, b: int,
               x_bytes: float, factor_bytes: float,
               out_bytes: float = 4) -> int:
    """Resident VMEM of one grid step: double-buffered x / U / S / V / out
    blocks plus the fp32 z and y scratch and the stage-2 product temporary.
    Lane (last) dims are counted padded to 128, as the TPU stores them."""
    p, q = m // b, n // b
    blocks = (b * block_t * _lanes(q) * x_bytes
              + (p + b * b + b * q) * block_r * factor_bytes
              + b * block_t * _lanes(p) * out_bytes)
    scratch = 2 * b * block_t * block_r * 4 + b * block_t * _lanes(p) * 4
    return int(2 * blocks + scratch)


def _unpack_nibbles(packed: jax.Array) -> jax.Array:
    """uint8 nibble pairs (..., P) → int32 codes (..., 2P) in plane order
    ``[low nibbles | high nibbles]`` (branch-free sign extension)."""
    v = packed.astype(jnp.int32)
    lo = v & 0xF
    hi = (v >> 4) & 0xF
    lo = lo - ((lo & 0x8) << 1)
    hi = hi - ((hi & 0x8) << 1)
    return jnp.concatenate([lo, hi], axis=-1)


# ---------------------------------------------------------------------------
# Shared kernel scaffold.
# ---------------------------------------------------------------------------


def _stages(x_ref, out_ref, z_scr, y_scr, *, b, n_r_tiles, rt_axis,
            load_v, load_s, load_u, scale_z, scale_y, acc1=jnp.float32):
    """The three Alg.-1 stages + accumulator init/flush, shared by every
    kernel variant.

    ``rt_axis`` is the grid axis of the r tile (the block index ``i`` rides
    on ``rt_axis + 1``); grouped kernels shift both right by one.  Factor
    access is abstracted: ``load_v(j, dtype)`` / ``load_u()`` / ``load_s(i)``
    return MXU/VPU-ready tiles (quantized variants cast codes in-register),
    ``scale_z(z_j, j)`` / ``scale_y(y_i, i)`` apply the per-block dequant
    scales on the stage *outputs*.  ``acc1`` is the stage-1 accumulator
    dtype: ``jnp.int32`` for the integer-activation kernels (int8×int8 MXU
    dot on codes; ``scale_z`` then dequantizes the int32 tile), fp32
    otherwise.
    """
    rt = pl.program_id(rt_axis)
    i = pl.program_id(rt_axis + 1)

    # ---- stage 1 (once per (T, r) tile): z_j = x_j @ V_j
    @pl.when(i == 0)
    def _compute_z():
        for j in range(b):  # b is static and small (≤16): unrolled
            xj = x_ref[j]                                 # (T_t, q)
            zj = jax.lax.dot_general(
                xj, load_v(j, xj.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=acc1,
            )
            z_scr[j] = scale_z(zj, j)

    @pl.when((rt == 0) & (i == 0))
    def _init_acc():
        y_scr[...] = jnp.zeros_like(y_scr)

    # ---- stage 2 (VPU): w_i = Σ_j s_ij ⊙ z_j
    s_i = load_s(i)                                       # (b, r_t) fp32
    w = jnp.sum(s_i[:, None, :] * z_scr[...], axis=0)     # (T_t, r_t)

    # ---- stage 3 (MXU): y_i += w @ U_iᵀ, accumulated over r tiles
    y_part = jax.lax.dot_general(
        w, load_u(), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (T_t, p)
    y_scr[i] = y_scr[i] + scale_y(y_part, i)

    # ---- flush once per T tile
    @pl.when((rt == n_r_tiles - 1) & (i == b - 1))
    def _flush():
        out_ref[...] = y_scr[...].reshape(out_ref.shape).astype(out_ref.dtype)


def _loaders(u_ref, s_ref, v_ref, su_ref, ss_ref, sv_ref, sx_ref, *, g,
             packed):
    """Factor accessors.  ``lead`` indexes away the grouped variants' unit
    factor-set block dim; per-block reads index the ref itself (``s_ref[i]``),
    never a loaded value, so the index stays a scalar address offset.

    Float kernels (no scales): tiles feed the MXU/VPU as stored.  Quantized
    kernels: U/S/V tiles arrive in VMEM as integer codes (int4: nibble-packed
    bytes), are cast (unpacked) in-register, and each stage's per-block
    scale multiplies the stage *output*.  ``su``/``sv`` are scalar-prefetched
    into SMEM; ``ss`` rides as a fp32 VMEM operand ``(b, b, 1)`` so the row
    read ``ss[i]`` is one vectorized load.  Integer-activation kernels
    (``sx_ref`` given) hand stage 1 raw int8 V codes (int4 sign-extends to
    int8) and fuse the activation and factor dequant into one multiply of
    the int32 tile: ``z · (sx ⊗ sv_j)``.
    """
    lead = (0,) * (s_ref.ndim - 3)
    if su_ref is None:
        return dict(
            load_v=lambda j, dt: v_ref[lead + (j,)],
            load_s=lambda i: s_ref[lead + (i,)].astype(jnp.float32),
            load_u=lambda: u_ref[lead + (0,)],
            scale_z=lambda z, j: z,
            scale_y=lambda y, i: y,
        )
    unpack = _unpack_nibbles if packed else (lambda t: t)
    su = (lambda i: su_ref[g, i]) if g is not None else (lambda i: su_ref[i])
    sv = (lambda j: sv_ref[g, j]) if g is not None else (lambda j: sv_ref[j])

    def load_s(i):
        codes = unpack(s_ref[lead + (i,)]).astype(jnp.float32)   # (b, r_t)
        return codes * ss_ref[lead + (i,)]                        # · (b, 1)

    if sx_ref is None:
        load_v = lambda j, dt: unpack(v_ref[lead + (j,)]).astype(dt)  # noqa: E731
        scale_z = lambda z, j: z * sv(j)                               # noqa: E731
    else:
        load_v = lambda j, dt: unpack(v_ref[lead + (j,)]).astype(jnp.int8)  # noqa: E731
        scale_z = lambda z, j: z.astype(jnp.float32) * (sx_ref[...] * sv(j))  # noqa: E731
    return dict(
        load_v=load_v,
        load_s=load_s,
        load_u=lambda: unpack(u_ref[lead + (0,)]).astype(jnp.float32),
        scale_z=scale_z,
        scale_y=lambda y, i: y * su(i),
    )


def _kernel(*refs, b: int, n_r_tiles: int, grouped: bool, quant: bool,
            act: bool, packed: bool):
    """One kernel body for every variant; the operand list is
    ``[su, sv,] x, U, S, V, [ss,] [sx,] out, z_scr, y_scr``."""
    refs = list(refs)
    su_ref = sv_ref = ss_ref = sx_ref = None
    if quant:
        su_ref, sv_ref = refs.pop(0), refs.pop(0)
    x_ref, u_ref, s_ref, v_ref = refs[:4]
    rest = refs[4:]
    if quant:
        ss_ref = rest.pop(0)
    if act:
        sx_ref = rest.pop(0)
    out_ref, z_scr, y_scr = rest
    g = pl.program_id(0) if grouped else None
    _stages(x_ref, out_ref, z_scr, y_scr, b=b, n_r_tiles=n_r_tiles,
            rt_axis=2 if grouped else 1,
            acc1=jnp.int32 if act else jnp.float32,
            **_loaders(u_ref, s_ref, v_ref, su_ref, ss_ref, sv_ref, sx_ref,
                       g=g, packed=packed))


# ---------------------------------------------------------------------------
# The one pallas_call builder.
# ---------------------------------------------------------------------------


def _blast_call(x, U, S, V, scales=None, sx=None, *, packed=False,
                block_t: int, block_r: int, interpret: bool, out_dtype=None):
    """x (T, n); U (…, b, p, r·), S (…, b, b, r·), V (…, b, q, r·) with an
    optional leading G (grouped) → y (T, m) or (G, T, m).  ``scales`` is
    ``(su, ss, sv)`` for quantized factors; ``sx (T, 1)`` marks int8
    activation codes in ``x``.  ``packed``: factors are nibble pairs along
    r (r· = r/2).  T must be a multiple of ``block_t`` and the logical r of
    ``block_r`` (``ops.py`` pads)."""
    T, n = x.shape
    grouped = U.ndim == 4
    *gdim, b, p, r_stored = U.shape
    q = V.shape[-2]
    m = b * p
    r = 2 * r_stored if packed else r_stored
    rb = block_r // 2 if packed else block_r
    assert n == b * q, (n, b, q)
    assert not packed or block_r % 2 == 0, block_r
    assert T % block_t == 0 and r % block_r == 0, (T, r, block_t, block_r)
    if sx is not None:
        assert x.dtype == jnp.int8 and sx.shape == (T, 1), (x.dtype, sx.shape)
    n_t, n_rt = T // block_t, r // block_r
    out_dtype = x.dtype if out_dtype is None else out_dtype

    gl = (1,) if grouped else ()

    def spec(shape, index):
        """BlockSpec whose index map sees (g, t, rt, i) in every variant."""
        if grouped:
            return pl.BlockSpec(shape, lambda g, t, rt, i, *_: index(g, t, rt, i))
        return pl.BlockSpec(shape, lambda t, rt, i, *_: index(None, t, rt, i))

    def gi(g):
        return (g,) if grouped else ()

    in_specs = [
        spec((b, block_t, q), lambda g, t, rt, i: (0, t, 0)),            # x
        spec(gl + (1, p, rb), lambda g, t, rt, i: gi(g) + (i, 0, rt)),   # U
        spec(gl + (b, b, rb), lambda g, t, rt, i: gi(g) + (0, 0, rt)),   # S
        spec(gl + (b, q, rb), lambda g, t, rt, i: gi(g) + (0, 0, rt)),   # V
    ]
    xb = x.reshape(T, b, q).transpose(1, 0, 2)
    operands = [xb, U, S, V]
    prefetch = []
    if scales is not None:
        su, ss, sv = (s.astype(jnp.float32) for s in scales)
        prefetch = [su, sv]
        in_specs.append(spec(gl + (b, b, 1),
                             lambda g, t, rt, i: gi(g) + (0, 0, 0)))      # ss
        operands.append(ss.reshape(*gdim, b, b, 1))
    if sx is not None:
        in_specs.append(spec((block_t, 1), lambda g, t, rt, i: (t, 0)))   # sx
        operands.append(sx.astype(jnp.float32))

    est = vmem_bytes(block_t, block_r, m, n, b, x.dtype.itemsize,
                     U.dtype.itemsize / (2 if packed else 1),
                     jnp.dtype(out_dtype).itemsize)
    kernel = functools.partial(
        _kernel, b=b, n_r_tiles=n_rt, grouped=grouped,
        quant=scales is not None, act=sx is not None, packed=packed)
    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(*gdim, n_t, n_rt, b),
            in_specs=in_specs,
            out_specs=spec(gl + (b, block_t, p),
                           lambda g, t, rt, i: gi(g) + (0, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((b, block_t, block_r), jnp.float32),   # z
                pltpu.VMEM((b, block_t, p), jnp.float32),         # y acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((*gdim, b, T, p), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_DEFAULT_VMEM_LIMIT, est + est // 4)),
        interpret=interpret,
    )(*prefetch, *operands)
    # (…, b, T, p) → (…, T, b·p)
    return jnp.swapaxes(y, -3, -2).reshape(*gdim, T, m)


# ---------------------------------------------------------------------------
# Public variants.
# ---------------------------------------------------------------------------


def blast_matmul_pallas(x, U, S, V, *, block_t: int = 128,
                        block_r: int = 128, interpret: bool = False):
    """x: (T, n) → (T, m).  Factors: U (b,p,r), S (b,b,r), V (b,q,r).

    T must be a multiple of ``block_t`` and r of ``block_r`` (ops.py pads).
    """
    return _blast_call(x, U, S, V, block_t=block_t, block_r=block_r,
                       interpret=interpret)


def blast_matmul_grouped_pallas(x, U, S, V, *, block_t: int = 128,
                                block_r: int = 128, interpret: bool = False):
    """Grouped fused BLAST matmul: one launch for G congruent factor sets.

    x: (T, n) shared input; U (G,b,p,r), S (G,b,b,r), V (G,b,q,r) →
    y (G, T, m).  The grid grows a leading G dimension; the x-tile block
    index ignores g, so the input tile is fetched once per (T, r) tile and
    revisited across the whole group.
    """
    return _blast_call(x, U, S, V, block_t=block_t, block_r=block_r,
                       interpret=interpret)


def blast_matmul_q_pallas(x, U, S, V, su, ss, sv, *, block_t: int = 128,
                          block_r: int = 128, interpret: bool = False):
    """Fused int8 BLAST matmul.  x: (T, n) float → (T, m) float.

    U (b,p,r), S (b,b,r), V (b,q,r) are int8 codes; su (b,), ss (b,b),
    sv (b,) are the per-block float32 scales — su/sv via scalar prefetch,
    ss as a (b, b, 1) fp32 VMEM operand (vectorized per-row reads).
    Same tiling contract as ``blast_matmul_pallas``.
    """
    return _blast_call(x, U, S, V, (su, ss, sv), block_t=block_t,
                       block_r=block_r, interpret=interpret)


def blast_matmul_q4_pallas(x, U, S, V, su, ss, sv, *, block_t: int = 128,
                           block_r: int = 128, interpret: bool = False):
    """Fused int4 BLAST matmul over *nibble-packed* factors.

    U (b,p,r/2), S (b,b,r/2), V (b,q,r/2) are uint8 nibble pairs packed
    along r (``quant/qarray.py`` layout) — they stay packed in HBM and VMEM
    and are unpacked in-register, so factor HBM reads are half the int8
    kernel's.  Logical r = 2·packed bytes must be a multiple of ``block_r``
    (even by construction); scales as in ``blast_matmul_q_pallas``.
    """
    return _blast_call(x, U, S, V, (su, ss, sv), packed=True,
                       block_t=block_t, block_r=block_r, interpret=interpret)


def blast_matmul_grouped_q_pallas(x, U, S, V, su, ss, sv, *,
                                  block_t: int = 128, block_r: int = 128,
                                  interpret: bool = False):
    """Grouped int8-factor BLAST matmul: one launch, one x-tile load.

    x (T, n); U (G,b,p,r), S (G,b,b,r), V (G,b,q,r) int8 codes; su (G,b),
    ss (G,b,b), sv (G,b) float scales → y (G, T, m).
    """
    return _blast_call(x, U, S, V, (su, ss, sv), block_t=block_t,
                       block_r=block_r, interpret=interpret)


def blast_matmul_grouped_q4_pallas(x, U, S, V, su, ss, sv, *,
                                   block_t: int = 128, block_r: int = 128,
                                   interpret: bool = False):
    """Grouped *nibble-packed* int4 BLAST matmul: one launch for G congruent
    factor sets AND half the factor HBM reads.

    x (T, n) float; U (G,b,p,r/2), S (G,b,b,r/2), V (G,b,q,r/2) uint8
    nibble pairs packed along r; su (G,b), ss (G,b,b), sv (G,b) float
    scales → y (G, T, m).  Factors stay packed in HBM and VMEM and unpack
    in-register to plane order (exact — the r contraction is
    permutation-invariant; pad bytes are zero codes).
    """
    return _blast_call(x, U, S, V, (su, ss, sv), packed=True,
                       block_t=block_t, block_r=block_r, interpret=interpret)


# ---------------------------------------------------------------------------
# Integer-activation (W8A8 / W4A8) variants: x arrives as int8 per-token
# codes + fp32 per-row scales; stage 1 is an int8×int8 → int32 MXU dot.
# ---------------------------------------------------------------------------


def blast_matmul_w8a8_pallas(xq, sx, U, S, V, su, ss, sv, *,
                             block_t: int = 128, block_r: int = 128,
                             interpret: bool = False, out_dtype=jnp.float32):
    """Fused W8A8 BLAST matmul: int8 activation codes × int8 factor codes.

    xq (T, n) int8 per-token codes, sx (T, 1) fp32 per-row scales
    (``quant/qarray.py::quantize_act`` layout); factors/scales as in
    ``blast_matmul_q_pallas`` → (T, m) ``out_dtype``.  Stage 1 contracts
    raw codes in int32 (exact) and dequantizes once with ``sx · sv_j``.
    """
    return _blast_call(xq, U, S, V, (su, ss, sv), sx, block_t=block_t,
                       block_r=block_r, interpret=interpret,
                       out_dtype=out_dtype)


def blast_matmul_w4a8_pallas(xq, sx, U, S, V, su, ss, sv, *,
                             block_t: int = 128, block_r: int = 128,
                             interpret: bool = False, out_dtype=jnp.float32):
    """Fused W4A8 BLAST matmul: int8 activation codes × nibble-packed int4
    factors (``blast_matmul_q4_pallas`` packing; V unpacks to int8 in
    register so stage 1 stays an integer MXU dot)."""
    return _blast_call(xq, U, S, V, (su, ss, sv), sx, packed=True,
                       block_t=block_t, block_r=block_r, interpret=interpret,
                       out_dtype=out_dtype)


def blast_matmul_grouped_w8a8_pallas(xq, sx, U, S, V, su, ss, sv, *,
                                     block_t: int = 128, block_r: int = 128,
                                     interpret: bool = False,
                                     out_dtype=jnp.float32):
    """Grouped W8A8: one launch for G int8 factor sets sharing one set of
    int8 activation codes (xq (T, n) int8, sx (T, 1) fp32) → (G, T, m)."""
    return _blast_call(xq, U, S, V, (su, ss, sv), sx, block_t=block_t,
                       block_r=block_r, interpret=interpret,
                       out_dtype=out_dtype)


def blast_matmul_grouped_w4a8_pallas(xq, sx, U, S, V, su, ss, sv, *,
                                     block_t: int = 128, block_r: int = 128,
                                     interpret: bool = False,
                                     out_dtype=jnp.float32):
    """Grouped W4A8: one launch, packed int4 factors (G,b,·,r/2), shared
    int8 activation codes → (G, T, m)."""
    return _blast_call(xq, U, S, V, (su, ss, sv), sx, packed=True,
                       block_t=block_t, block_r=block_r, interpret=interpret,
                       out_dtype=out_dtype)
