"""Quantized-storage subsystem tests: per-block codec properties
(hypothesis), fused apply_q vs dequantize-then-apply for every structure,
the int8 fused BLAST Pallas kernel vs the fp32 oracle under an *analytic*
interval bound, QArray checkpoint round-trips, and per-family quantized
serving smoke (memory halves, logits stay bounded)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro import quant as qt
from repro.checkpoint import store
from repro.core import blast
from repro.core.structures import StructureConfig, make_linear
from repro.kernels import ref
from repro.kernels.ops import blast_matmul_q
from repro.models import build_model
from repro.quant import QArray, QuantConfig
from repro.serve import (Engine, EngineConfig, MemoryConfig, Request,
                         SchedulerConfig)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # property checks fall back to a parametrized grid
    HAVE_HYPOTHESIS = False


# ---- property checks (plain functions so hypothesis and the grid fallback
# ---- exercise identical logic)


def check_roundtrip_error_at_most_half_scale(a, b, c, bits, seed):
    """Per-block symmetric quantization: |x − dq(q(x))| ≤ scale/2
    elementwise (round-to-nearest with an exactly-representable max)."""
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (a, b, c))
    qa = qt.quantize(x, bits=bits, block_axes=(1, 2))
    err = np.abs(np.asarray(qt.dequantize(qa)) - np.asarray(x))
    bound = np.broadcast_to(np.asarray(qa.scale, np.float32) / 2, err.shape)
    assert (err <= bound + 1e-6).all()


def check_requantization_idempotent(a, b, bits, seed):
    """q(dq(q(x))) == q(x) exactly: the max element quantizes to ±qmax, so
    the recovered scale matches and every code reproduces."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (a, b))
    q1 = qt.quantize(x, bits=bits, block_axes=(1,))
    q2 = qt.quantize(qt.dequantize(q1), bits=bits, block_axes=(1,))
    np.testing.assert_array_equal(np.asarray(qt.int_values(q1)),
                                  np.asarray(qt.int_values(q2)))
    np.testing.assert_allclose(np.asarray(q1.scale),
                               np.asarray(q2.scale), rtol=1e-6)


def check_zero_block_safety(a, b, bits):
    """All-zero blocks: positive scale (no 0/0), exact-zero dequant."""
    x = jnp.zeros((a, b))
    x = x.at[0].set(jax.random.normal(jax.random.PRNGKey(0), (b,)))
    qa = qt.quantize(x, bits=bits, block_axes=(1,))
    s = np.asarray(qa.scale)
    assert (s > 0).all()
    dq = np.asarray(qt.dequantize(qa))
    assert np.isfinite(dq).all()
    np.testing.assert_array_equal(dq[1:], 0.0)


def check_int4_pack_roundtrip_exact(d, seed):
    v = jax.random.randint(jax.random.PRNGKey(seed), (3, d), -7, 8,
                           dtype=jnp.int8)
    packed = qt.pack_int4(v)
    assert packed.shape[-1] == (d + 1) // 2
    np.testing.assert_array_equal(
        np.asarray(qt.unpack_int4(packed, d)), np.asarray(v))


def check_cache_row_codec(seed):
    t = jax.random.normal(jax.random.PRNGKey(seed), (2, 5, 3, 8))
    q, s = qt.quantize_rows(t, scale_dtype=jnp.float32)
    err = np.abs(np.asarray(qt.dequantize_rows(q, s, jnp.float32))
                 - np.asarray(t))
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-6).all()


def check_act_roundtrip_error_at_most_half_scale(T, n, seed):
    """Per-token activation codec: |x − dq(q(x))| ≤ sx/2 elementwise (the
    row max is exactly representable at ±127, everything else rounds)."""
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (T, n))
    q, sx = qt.quantize_act(x)
    assert q.dtype == jnp.int8 and sx.shape == (T, 1)
    assert sx.dtype == jnp.float32
    err = np.abs(np.asarray(qt.dequantize_act(q, sx)) - np.asarray(x))
    assert (err <= np.asarray(sx) / 2 + 1e-6).all()
    # row max hits a code of magnitude exactly 127
    assert (np.abs(np.asarray(q)).max(axis=-1) == 127).all()


def check_act_zero_row_safety(T, n, seed):
    """All-zero token rows: positive scale (no 0/0), exact-zero codes."""
    x = jnp.zeros((T, n))
    x = x.at[0].set(jax.random.normal(jax.random.PRNGKey(seed), (n,)))
    q, sx = qt.quantize_act(x)
    s = np.asarray(sx)
    assert (s > 0).all() and np.isfinite(s).all()
    np.testing.assert_array_equal(np.asarray(q)[1:], 0)
    np.testing.assert_array_equal(np.asarray(qt.dequantize_act(q, sx))[1:],
                                  0.0)


def check_act_batched_leading_dims(seed):
    """The codec is per *last-axis row* whatever the leading shape."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 3, 8))
    q, sx = qt.quantize_act(x)
    assert q.shape == x.shape and sx.shape == (2, 3, 1)
    qf, sf = qt.quantize_act(x.reshape(6, 8))
    np.testing.assert_array_equal(np.asarray(q).reshape(6, 8), np.asarray(qf))
    np.testing.assert_allclose(np.asarray(sx).reshape(6, 1), np.asarray(sf),
                               rtol=1e-7)


if HAVE_HYPOTHESIS:
    dims = st.sampled_from([4, 8, 12, 16])
    bits_st = st.sampled_from([8, 4])

    class TestCodecProperties:
        @given(a=dims, b=dims, c=dims, bits=bits_st,
               seed=st.integers(min_value=0, max_value=50))
        @settings(max_examples=30, deadline=None)
        def test_roundtrip_error_at_most_half_scale(self, a, b, c, bits, seed):
            check_roundtrip_error_at_most_half_scale(a, b, c, bits, seed)

        @given(a=dims, b=dims, bits=bits_st,
               seed=st.integers(min_value=0, max_value=50))
        @settings(max_examples=30, deadline=None)
        def test_requantization_idempotent(self, a, b, bits, seed):
            check_requantization_idempotent(a, b, bits, seed)

        @given(a=dims, b=dims, bits=bits_st)
        @settings(max_examples=20, deadline=None)
        def test_zero_block_safety(self, a, b, bits):
            check_zero_block_safety(a, b, bits)

        @given(d=st.sampled_from([1, 2, 5, 8, 13]),
               seed=st.integers(min_value=0, max_value=20))
        @settings(max_examples=20, deadline=None)
        def test_int4_pack_roundtrip_exact(self, d, seed):
            check_int4_pack_roundtrip_exact(d, seed)

        @given(seed=st.integers(min_value=0, max_value=20))
        @settings(max_examples=10, deadline=None)
        def test_cache_row_codec(self, seed):
            check_cache_row_codec(seed)

        @given(T=dims, n=dims, seed=st.integers(min_value=0, max_value=50))
        @settings(max_examples=30, deadline=None)
        def test_act_roundtrip_error_at_most_half_scale(self, T, n, seed):
            check_act_roundtrip_error_at_most_half_scale(T, n, seed)

        @given(T=dims, n=dims, seed=st.integers(min_value=0, max_value=20))
        @settings(max_examples=20, deadline=None)
        def test_act_zero_row_safety(self, T, n, seed):
            check_act_zero_row_safety(T, n, seed)

        @given(seed=st.integers(min_value=0, max_value=20))
        @settings(max_examples=10, deadline=None)
        def test_act_batched_leading_dims(self, seed):
            check_act_batched_leading_dims(seed)
else:
    class TestCodecProperties:
        @pytest.mark.parametrize("bits", [8, 4])
        @pytest.mark.parametrize("seed", range(5))
        def test_roundtrip_error_at_most_half_scale(self, bits, seed):
            check_roundtrip_error_at_most_half_scale(4 + seed, 8, 12, bits,
                                                     seed)

        @pytest.mark.parametrize("bits", [8, 4])
        @pytest.mark.parametrize("seed", range(5))
        def test_requantization_idempotent(self, bits, seed):
            check_requantization_idempotent(8, 4 + seed, bits, seed)

        @pytest.mark.parametrize("bits", [8, 4])
        def test_zero_block_safety(self, bits):
            check_zero_block_safety(8, 16, bits)

        @pytest.mark.parametrize("d", [1, 2, 5, 8, 13])
        def test_int4_pack_roundtrip_exact(self, d):
            check_int4_pack_roundtrip_exact(d, d)

        @pytest.mark.parametrize("seed", range(3))
        def test_cache_row_codec(self, seed):
            check_cache_row_codec(seed)

        @pytest.mark.parametrize("seed", range(5))
        def test_act_roundtrip_error_at_most_half_scale(self, seed):
            check_act_roundtrip_error_at_most_half_scale(4 + seed, 8, seed)

        @pytest.mark.parametrize("seed", range(3))
        def test_act_zero_row_safety(self, seed):
            check_act_zero_row_safety(4, 8 + seed, seed)

        @pytest.mark.parametrize("seed", range(3))
        def test_act_batched_leading_dims(self, seed):
            check_act_batched_leading_dims(seed)


class TestStructureApplyQ:
    """apply_q must equal dequantize-then-apply (the fusion is exact) for
    every structure kind and both storage widths."""

    @pytest.mark.parametrize("kind", ["dense", "blast", "low_rank", "monarch",
                                      "block_diag", "pixelfly"])
    @pytest.mark.parametrize("bits", [8, 4])
    def test_fused_equals_dequant_apply(self, kind, bits):
        spec = make_linear(32, 48, StructureConfig(kind=kind, b=4,
                                                   keep_ratio=0.6))
        params = spec.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (7, 32))
        qp = spec.quantize(params, bits)
        dq = {k: (qt.dequantize(v, jnp.float32) if qt.is_qarray(v) else v)
              for k, v in qp.items()}
        np.testing.assert_allclose(
            np.asarray(spec.apply_q(qp, x)), np.asarray(spec.apply(dq, x)),
            rtol=2e-5, atol=2e-5)

    def test_quantized_storage_halves(self):
        spec = make_linear(128, 128, StructureConfig(kind="blast", b=4,
                                                     keep_ratio=0.5))
        params = spec.init(jax.random.PRNGKey(0))
        fp = qt.tree_nbytes(jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), params))
        q8 = qt.tree_nbytes(spec.quantize(params, 8))
        q4 = qt.tree_nbytes(spec.quantize(params, 4))
        assert q8 < 0.6 * fp
        assert q4 < 0.35 * fp


def _quantize_blast(params, bits=8):
    Uq = qt.quantize(params.U, bits=bits, block_axes=(1, 2))
    Sq = qt.quantize(params.S, bits=bits, block_axes=(2,))
    Vq = qt.quantize(params.V, bits=bits, block_axes=(1, 2))
    return Uq, Sq, Vq


def _analytic_bound(x, params, Uq, Sq, Vq):
    """Exact interval bound on |y_q − y_fp|: compose |factor| + scale/2
    against |factor| through the abs-value Alg. 1 chain.  Every quantization
    error is elementwise ≤ scale/2, so the difference of the two abs
    compositions bounds all cross terms at once."""
    aU, aS, aV = (np.abs(np.asarray(t, np.float64))
                  for t in (params.U, params.S, params.V))
    dU = np.broadcast_to(np.asarray(Uq.scale, np.float64) / 2, aU.shape)
    dS = np.broadcast_to(np.asarray(Sq.scale, np.float64) / 2, aS.shape)
    dV = np.broadcast_to(np.asarray(Vq.scale, np.float64) / 2, aV.shape)
    ax = np.abs(np.asarray(x, np.float64))

    def compose(U, S, V):
        b, q, _ = V.shape
        xb = ax.reshape(*ax.shape[:-1], b, q)
        z = np.einsum("...jq,jqr->...jr", xb, V)
        w = np.einsum("...jr,ijr->...ir", z, S)
        y = np.einsum("...ir,ipr->...ip", w, U)
        return y.reshape(*ax.shape[:-1], -1)

    return compose(aU + dU, aS + dS, aV + dV) - compose(aU, aS, aV)


class TestBlastKernelInt8:
    """The fused int8 kernel (interpret mode on CPU): bit-tight against the
    dequantized oracle, and within the analytic quant tolerance of fp32."""

    @pytest.mark.parametrize(
        "T,m,n,b,r",
        [
            (16, 32, 24, 4, 8),
            (64, 64, 64, 2, 16),
            (40, 48, 32, 8, 12),      # unaligned T / r → padding path
            (8, 256, 128, 16, 24),    # b=16, decode-ish T
        ],
    )
    def test_matches_dequant_oracle(self, T, m, n, b, r):
        params = blast.init(jax.random.PRNGKey(T + m), m, n, b, r)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, n))
        Uq, Sq, Vq = _quantize_blast(params)
        got = blast_matmul_q(x, Uq, Sq, Vq)
        want = ref.blast_matmul_q_ref(
            x, qt.int_values(Uq), qt.int_values(Sq), qt.int_values(Vq),
            Uq.scale.reshape(b), Sq.scale.reshape(b, b), Vq.scale.reshape(b))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("T,m,n,b,r", [(16, 32, 32, 4, 8),
                                           (32, 64, 48, 4, 16)])
    def test_within_analytic_tolerance_of_fp32(self, T, m, n, b, r):
        params = blast.init(jax.random.PRNGKey(0), m, n, b, r)
        x = jax.random.normal(jax.random.PRNGKey(2), (T, n))
        Uq, Sq, Vq = _quantize_blast(params)
        got = np.asarray(blast_matmul_q(x, Uq, Sq, Vq),
                         np.float64)
        want = np.asarray(ref.blast_matmul_ref(x, params.U, params.S,
                                               params.V), np.float64)
        bound = _analytic_bound(x, params, Uq, Sq, Vq)
        assert (np.abs(got - want) <= bound + 1e-4).all()

    def test_int4_factors_via_unpack_path(self):
        params = blast.init(jax.random.PRNGKey(3), 32, 32, 4, 8)
        x = jax.random.normal(jax.random.PRNGKey(4), (16, 32))
        Uq, Sq, Vq = _quantize_blast(params, bits=4)
        got = blast_matmul_q(x, Uq, Sq, Vq)
        want = ref.blast_matmul_q_ref(
            x, qt.int_values(Uq), qt.int_values(Sq), qt.int_values(Vq),
            Uq.scale.reshape(4), Sq.scale.reshape(4, 4), Vq.scale.reshape(4))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestQuantConfigActivations:
    def test_requires_quantized_weights(self):
        with pytest.raises(ValueError, match="requires quantized weights"):
            QuantConfig(activations="int8")

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError):
            QuantConfig(weights="int8", activations="int4")

    @pytest.mark.parametrize("weights", ["int8", "int4"])
    def test_valid_combinations(self, weights):
        cfg = QuantConfig(weights=weights, activations="int8")
        assert cfg.enabled and cfg.act_bits == 8


class TestCheckpointRoundtrip:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_qarray_tree_roundtrip(self, tmp_path, bits):
        spec = make_linear(24, 16, StructureConfig(kind="blast", b=4,
                                                   keep_ratio=0.5))
        params = spec.init(jax.random.PRNGKey(0))
        qp = {"layer": spec.quantize(params, bits),
              "norm": {"scale": jnp.ones((16,))}}
        store.save(str(tmp_path), 3, qp)
        # restore into a zeroed skeleton: only the static (bits, last_dim)
        # metadata survives — the array values must come from disk
        skeleton = jax.tree.map(jnp.zeros_like, qp)
        restored = store.restore(str(tmp_path), 3, skeleton)
        for k in ("U", "S", "V"):
            got, want = restored["layer"][k], qp["layer"][k]
            assert isinstance(got, QArray) and got.bits == bits
            assert got.last_dim == want.last_dim
            np.testing.assert_array_equal(np.asarray(got.q),
                                          np.asarray(want.q))
            np.testing.assert_array_equal(np.asarray(got.scale),
                                          np.asarray(want.scale))


FAMILY_ARCHS = ["smollm-135m", "deepseek-v3-671b", "mamba2-130m",
                "recurrentgemma-2b"]


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
class TestQuantizedServing:
    """All four decoder families: quantized weights + caches shrink resident
    memory and keep final logits bounded-close to the float path."""

    def _models(self, arch):
        cfg = configs.ARCHS[arch].reduced()
        cfg_q = dataclasses.replace(
            cfg, quant=QuantConfig(weights="int8", cache="int8"))
        return cfg, build_model(cfg), build_model(cfg_q)

    def test_logit_deviation_bounded(self, arch):
        cfg, model, model_q = self._models(arch)
        params = model.init(jax.random.PRNGKey(0))
        params_q = model_q.quantize_params(params, model_q.cfg.quant)
        B, P = 2, 10
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                    cfg.vocab)
        steps = jnp.zeros((B,), jnp.int32)
        n_tok = jnp.full((B,), P, jnp.int32)
        base, _ = model.prefill_chunk(params, model.init_cache(B, 16),
                                      tokens, steps, n_tok)
        quant, _ = model_q.prefill_chunk(params_q, model_q.init_cache(B, 16),
                                         tokens, steps, n_tok)
        base = np.asarray(base, np.float32)
        quant = np.asarray(quant, np.float32)
        assert np.isfinite(quant).all()
        # int8 weights + caches: a loose but meaningful bound on random-init
        # smoke models (observed ≤ 0.07 relative; 4× headroom)
        rel = np.abs(quant - base).max() / (np.abs(base).max() + 1e-9)
        assert rel < 0.3, rel

    def test_memory_reduction_and_engine(self, arch):
        cfg, model, model_q = self._models(arch)
        params = model.init(jax.random.PRNGKey(0))
        base_bytes = (qt.tree_nbytes(jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 and a.ndim > 1 else a, params))
            + qt.tree_nbytes(model.init_cache(2, 32)))
        eng = Engine(model_q, params, EngineConfig(
            scheduler=SchedulerConfig(slots=2, chunk_size=4),
            memory=MemoryConfig(max_len=32)))
        assert qt.tree_is_quantized(eng.params)  # quantize-at-load fired
        q_bytes = qt.tree_nbytes(eng.params) + qt.tree_nbytes(eng.cache)
        assert q_bytes < 0.75 * base_bytes
        eng.submit(Request(uid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=3))
        eng.submit(Request(uid=1, prompt=[7, 8, 9], max_new_tokens=2))
        done = eng.run()
        assert sorted(len(r.output) for r in done) == [2, 3]
        assert all(r.done for r in done)

    def test_cache_axes_congruent_with_quant(self, arch):
        _, _, model_q = self._models(arch)
        cache = jax.eval_shape(lambda: model_q.init_cache(2, 16))
        axes = model_q.cache_axes()

        def congruent(c, a, path=""):
            if isinstance(c, dict):
                assert set(c) == set(a), (path, set(c), set(a))
                for k in c:
                    congruent(c[k], a[k], f"{path}/{k}")
            else:
                assert len(a) == c.ndim, path
        congruent(cache, axes)


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
class TestIntActivationServing:
    """W8A8/W4A8 end to end on all four decoder families: a teacher-forced
    greedy decode under the integer-activation mode stays bounded-close to
    the weight-only quantized path, which itself stays close to float."""

    def test_greedy_decode_logit_deviation(self, arch):
        from repro.core import structures
        cfg = configs.ARCHS[arch].reduced()
        qcfg = QuantConfig(weights="int4", activations="int8")
        cfg_q = dataclasses.replace(cfg, quant=qcfg)
        model = build_model(cfg)
        model_q = build_model(cfg_q)
        params = model.init(jax.random.PRNGKey(0))
        params_q = model_q.quantize_params(params, qcfg)
        B, P, STEPS = 2, 6, 3
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                    cfg.vocab)

        def decode(model_, params_, act):
            """Prefill then STEPS greedy decode steps, teacher-forced on the
            float model's tokens so logits stay comparable step by step."""
            cache = model_.init_cache(B, 16)
            steps = jnp.zeros((B,), jnp.int32)
            n_tok = jnp.full((B,), P, jnp.int32)
            with structures.activations(act):
                logits, cache = model_.prefill_chunk(params_, cache, prompt,
                                                     steps, n_tok)
            traj = [logits]
            pos = P
            for _ in range(STEPS):
                tok = jnp.argmax(traj[-1][:, -1], axis=-1)[:, None]
                tok = tok.astype(jnp.int32) % cfg.vocab
                with structures.activations(act):
                    logits, cache = model_.prefill_chunk(
                        params_, cache, tok,
                        jnp.full((B,), pos, jnp.int32),
                        jnp.ones((B,), jnp.int32))
                traj.append(logits)
                pos += 1
            return [np.asarray(l, np.float32) for l in traj]

        base = decode(model, params, "none")
        w4 = decode(model_q, params_q, "none")
        w4a8 = decode(model_q, params_q, "int8")
        for lb, l4, l48 in zip(base, w4, w4a8):
            assert np.isfinite(l48).all()
            scale = np.abs(lb).max() + 1e-9
            # activation rounding adds little on top of the int4 weight error
            rel_w = np.abs(l4 - lb).max() / scale
            rel_a = np.abs(l48 - lb).max() / scale
            assert rel_a < max(3.0 * rel_w, 0.15), (rel_a, rel_w)

    def test_engine_quantizes_and_serves_w4a8(self, arch):
        from repro.core import structures
        cfg = configs.ARCHS[arch].reduced()
        qcfg = QuantConfig(weights="int4", cache="int8", activations="int8")
        cfg_q = dataclasses.replace(cfg, quant=qcfg)
        model_q = build_model(cfg_q)
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        try:
            eng = Engine(model_q, params, EngineConfig(
                scheduler=SchedulerConfig(slots=2, chunk_size=4),
                memory=MemoryConfig(max_len=32)))
            # engine build flips the process-wide activation mode
            assert structures.activations_mode() == "int8"
            assert qt.tree_is_quantized(eng.params)
            eng.submit(Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=3))
            done = eng.run()
            assert len(done) == 1 and len(done[0].output) == 3
        finally:
            structures.set_activations("none")
