"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant as qt
from repro.core import blast
from repro.kernels import ref
from repro.kernels.ops import (blast_matmul, blast_matmul_q, flash_attention,
                               flash_attention_prefill)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


class TestBlastKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "T,m,n,b,r",
        [
            (16, 32, 24, 4, 8),      # tiny
            (64, 64, 64, 2, 16),     # square b=2 (paper Llama b=2 case)
            (40, 48, 32, 8, 12),     # unaligned T / r → padding path
            (128, 96, 96, 3, 33),    # b=3 (paper ViT), odd r
            (8, 256, 128, 16, 24),   # b=16 (paper Llama), small T (decode-ish)
        ],
    )
    def test_matches_oracle(self, T, m, n, b, r, dtype):
        key = jax.random.PRNGKey(hash((T, m, n, b, r)) % 2**31)
        params = blast.init(key, m, n, b, r, dtype=dtype)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, n), dtype=dtype)
        got = blast_matmul(x, params.U, params.S, params.V)
        want = ref.blast_matmul_ref(x, params.U, params.S, params.V)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype))

    def test_batched_leading_dims(self):
        params = blast.init(jax.random.PRNGKey(0), 32, 32, 4, 8)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
        got = blast_matmul(x, params.U, params.S, params.V)
        want = ref.blast_matmul_ref(x, params.U, params.S, params.V)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_block_sizes_explicit(self):
        params = blast.init(jax.random.PRNGKey(0), 64, 64, 4, 32)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
        for bt, br in [(16, 8), (32, 16), (64, 32)]:
            got = blast_matmul(x, params.U, params.S, params.V,
                               block_t=bt, block_r=br)
            want = ref.blast_matmul_ref(x, params.U, params.S, params.V)
            np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def _quantize_blast_factors(params, bits):
    Uq = qt.quantize(params.U, bits=bits, block_axes=(1, 2))
    Sq = qt.quantize(params.S, bits=bits, block_axes=(2,))
    Vq = qt.quantize(params.V, bits=bits, block_axes=(1, 2))
    return Uq, Sq, Vq


def _act_bound(sx, Uq, Sq, Vq):
    """Interval bound on |y_a8 − y_weight_only|: the map x → y is linear in
    x with the (dequantized) quantized factors fixed, and the activation
    codec guarantees |dq(q(x)) − x| ≤ sx/2 per token, so the deviation is
    at most the abs-factor Alg. 1 chain applied to the constant sx/2 row."""
    aU, aS, aV = (np.abs(np.asarray(qt.dequantize(t), np.float64))
                  for t in (Uq, Sq, Vq))
    b, q, _ = aV.shape
    e = np.broadcast_to(np.asarray(sx, np.float64) / 2, (sx.shape[0], b * q))
    z = np.einsum("...jq,jqr->...jr", e.reshape(-1, b, q), aV)
    w = np.einsum("...jr,ijr->...ir", z, aS)
    y = np.einsum("...ir,ipr->...ip", w, aU)
    return y.reshape(sx.shape[0], -1)


class TestBlastKernelIntActivations:
    """W8A8 / W4A8: the fused integer-contraction kernels against the
    integer XLA reference (tight — stage 1 is an exact int32 dot) and
    against the float-activation weight-only path (within the analytic
    activation-rounding bound)."""

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize(
        "T,m,n,b,r",
        [
            (16, 32, 24, 4, 8),      # tiny
            (40, 48, 32, 8, 12),     # unaligned T / r → padding path
            (8, 256, 128, 16, 24),   # b=16, decode-ish T
            (1, 128, 128, 16, 16),   # T=1 matvec
        ],
    )
    def test_matches_integer_reference(self, T, m, n, b, r, bits):
        key = jax.random.PRNGKey(hash((T, m, n, b, r, bits)) % 2**31)
        params = blast.init(key, m, n, b, r)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, n))
        Uq, Sq, Vq = _quantize_blast_factors(params, bits)
        got = blast_matmul_q(x, Uq, Sq, Vq, act="int8")
        xq, sx = qt.quantize_act(x)
        want = ref.blast_matmul_a8_ref(
            xq, sx, qt.int_values(Uq), qt.int_values(Sq), qt.int_values(Vq),
            Uq.scale.reshape(b), Sq.scale.reshape(b, b), Vq.scale.reshape(b))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("T,m,n,b,r", [(16, 32, 32, 4, 8),
                                           (8, 64, 48, 4, 16)])
    def test_within_act_bound_of_weight_only(self, T, m, n, b, r, bits):
        params = blast.init(jax.random.PRNGKey(0), m, n, b, r)
        x = jax.random.normal(jax.random.PRNGKey(2), (T, n))
        Uq, Sq, Vq = _quantize_blast_factors(params, bits)
        a8 = np.asarray(blast_matmul_q(x, Uq, Sq, Vq, act="int8"), np.float64)
        w_only = np.asarray(blast_matmul_q(x, Uq, Sq, Vq),
                            np.float64)
        _, sx = qt.quantize_act(x)
        bound = _act_bound(np.asarray(sx), Uq, Sq, Vq)
        assert (np.abs(a8 - w_only) <= bound + 1e-4).all()

    def test_int_kernel_output_dtype_follows_x(self):
        params = blast.init(jax.random.PRNGKey(3), 32, 32, 4, 8)
        Uq, Sq, Vq = _quantize_blast_factors(params, 8)
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jax.random.normal(jax.random.PRNGKey(4), (4, 32), dtype=dtype)
            y = blast_matmul_q(x, Uq, Sq, Vq, act="int8")
            assert y.dtype == dtype and y.shape == (4, 32)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,T,S,D,causal,window",
        [
            (1, 4, 4, 64, 64, 32, True, None),     # MHA causal
            (2, 8, 2, 32, 32, 16, True, None),     # GQA
            (1, 4, 1, 48, 48, 32, True, None),     # MQA, unaligned T
            (1, 2, 2, 64, 64, 16, False, None),    # bidirectional (whisper enc)
            (1, 4, 2, 96, 96, 32, True, 32),       # sliding window (griffin)
            (2, 4, 4, 8, 72, 16, True, None),      # decode-ish: short q, long kv
        ],
    )
    def test_matches_oracle(self, B, Hq, Hkv, T, S, D, causal, window, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, T, D), dtype=dtype)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype=dtype)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype=dtype)
        q_offset = S - T  # decode semantics when S > T
        got = flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, block_q=32, block_kv=32)
        want = ref.attention_ref(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype))

    def test_long_window_prefill(self):
        """Local attention over a longer sequence (recurrentgemma pattern)."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        B, H, T, D, W = 1, 2, 256, 16, 64
        q = jax.random.normal(ks[0], (B, H, T, D))
        k = jax.random.normal(ks[1], (B, H, T, D))
        v = jax.random.normal(ks[2], (B, H, T, D))
        got = flash_attention(q, k, v, causal=True, window=W,
                              block_q=64, block_kv=64)
        want = ref.attention_ref(q, k, v, causal=True, window=W)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


class TestFlashAttentionPrefill:
    """Prefill-at-offset variant: per-sequence offsets via scalar prefetch
    (the serving engine's C×max_len chunked-prefill step)."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,C,S,D,window",
        [
            (3, 4, 2, 16, 96, 16, None),   # GQA, three offsets in one batch
            (2, 2, 2, 8, 64, 32, None),    # MHA, short chunk
            (3, 4, 1, 16, 96, 16, 24),     # MQA + sliding window
            (2, 4, 4, 1, 72, 16, None),    # C=1 degenerates to decode
        ],
    )
    def test_matches_oracle(self, B, Hq, Hkv, C, S, D, window, dtype):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, Hq, C, D), dtype=dtype)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype=dtype)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype=dtype)
        # per-row offsets: fresh slot, mid-stream slot, nearly-full slot
        offs = jnp.asarray([0, (S - C) // 2, S - C][:B], jnp.int32)
        got = flash_attention_prefill(q, k, v, offs, window=window,
                                      block_q=8, block_kv=32)
        want = ref.attention_prefill_ref(q, k, v, offs, window=window)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **tol(dtype))

    def test_matches_fixed_offset_kernel(self):
        """With equal offsets the prefill variant reduces to the classic
        kernel's static q_offset path."""
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        B, H, C, S, D = 2, 4, 16, 64, 16
        q = jax.random.normal(ks[0], (B, H, C, D))
        k = jax.random.normal(ks[1], (B, H, S, D))
        v = jax.random.normal(ks[2], (B, H, S, D))
        off = S - C
        got = flash_attention_prefill(
            q, k, v, jnp.full((B,), off, jnp.int32),
            block_q=8, block_kv=32)
        want = flash_attention(q, k, v, q_offset=off, block_q=8, block_kv=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestDecodeShapes:
    """T=1 matvec (the paper's Table-4 decode regime): the fused kernel's
    single-T-tile path reads every factor exactly once — bandwidth-optimal,
    so the roofline term is the (m+n+b²)·r parameter bytes."""

    def test_blast_matvec_t1(self):
        params = blast.init(jax.random.PRNGKey(0), 128, 128, 16, 24)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 128))
        got = blast_matmul(x, params.U, params.S, params.V)
        want = ref.blast_matmul_ref(x, params.U, params.S, params.V)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_flash_decode_t1(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, 4, 1, 16))
        k = jax.random.normal(ks[1], (2, 2, 128, 16))
        v = jax.random.normal(ks[2], (2, 2, 128, 16))
        got = flash_attention(q, k, v, causal=True, q_offset=127,
                              block_q=8, block_kv=32)
        want = ref.attention_ref(q, k, v, causal=True, q_offset=127)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
