"""Model-layer equivalence oracles:

  * chunked attention == full-softmax reference (causal / window / GQA)
  * cached decode == full forward, token-for-token, for every mixer family
    (attention, MLA with absorbed latent decode, RG-LRU, SSD)
  * MoE capacity dispatch == dense every-expert oracle when nothing drops
  * SSD chunked scan == naive per-token recurrence
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, ops
from repro.models import moe as moe_lib


def fwd_vs_decode(arch, B=2, T=12, tol=2e-2):
    """Teacher-forced decode must reproduce apply() logits step-by-step."""
    cfg = configs.ARCHS[arch].reduced(param_dtype="float32",
                                      compute_dtype="float32")
    if cfg.moe is not None:
        # capacity drops differ between a 1-token decode and a T-token
        # forward (both are correct capacity-MoE behavior); equivalence
        # holds exactly when capacity is ample.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    if cfg.encoder is not None:
        frames = jax.random.normal(jax.random.PRNGKey(2),
                                   (B, cfg.encoder.n_frames, cfg.d_model))
        full = model.apply(params, tokens, frames).logits
        cache = model.init_cache(params, frames, T)
    else:
        full = model.apply(params, tokens=tokens).logits
        cache = model.init_cache(B, T)
    step_fn = jax.jit(model.decode_step)
    for t in range(T):
        logits, cache = step_fn(params, cache, tokens[:, t: t + 1],
                                jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full[:, t], np.float32), rtol=tol, atol=tol,
            err_msg=f"{arch} step {t}")


class TestDecodeEquivalence:
    @pytest.mark.parametrize("arch", [
        "smollm-135m",            # GQA attention
        "granite-3-2b",           # GQA, tied embeddings
        "deepseek-v3-671b",       # MLA absorbed-latent decode + MoE
        "recurrentgemma-2b",      # RG-LRU + local attention ring buffer
        "mamba2-130m",            # SSD recurrent decode
        "granite-4-h-micro",      # SSD + NoPE GQA, Granite's scalars
        "whisper-base",           # enc-dec with cross-attention cache
    ])
    def test_decode_matches_forward(self, arch):
        fwd_vs_decode(arch)

    def test_local_attn_ring_buffer(self):
        """Sliding-window ring cache == full forward when T > window."""
        cfg = configs.ARCHS["recurrentgemma-2b"].reduced(
            param_dtype="float32", compute_dtype="float32", window=8)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, T = 1, 20  # T > window=8: ring buffer must wrap
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
        full = model.apply(params, tokens=tokens).logits
        cache = model.init_cache(B, T)
        step_fn = jax.jit(model.decode_step)
        for t in range(T):
            logits, cache = step_fn(params, cache, tokens[:, t: t + 1],
                                    jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   np.asarray(full[:, -1], np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestChunkedAttention:
    @pytest.mark.parametrize("causal,window", [(True, None), (True, 8),
                                               (False, None)])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (6, 1)])
    def test_matches_reference(self, causal, window, hq, hkv):
        from repro.kernels import ref
        B, T, D = 2, 24, 16
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (B, hq, T, D))
        k = jax.random.normal(kk, (B, hkv, T, D))
        v = jax.random.normal(kv, (B, hkv, T, D))
        got = ops.chunked_attention(q, k, v, causal=causal, window=window,
                                    q_chunk=2)
        want = ref.attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestMoEDispatch:
    def _spec(self, E=4, k=2, d=16, cf=64.0):
        import dataclasses
        cfg = configs.ARCHS["granite-moe-1b-a400m"].reduced(
            param_dtype="float32", compute_dtype="float32")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=E, top_k=k,
                                         capacity_factor=cf))
        spec = moe_lib.make_moe(cfg)
        params = moe_lib.moe_init(spec, jax.random.PRNGKey(0), jnp.float32)
        return spec, params

    def test_matches_dense_oracle_when_no_drops(self):
        spec, params = self._spec()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
        y, aux = moe_lib.moe_apply(spec, params, x)
        # oracle: run EVERY expert on every token, combine top-k
        x2 = x.reshape(-1, 64)
        gates, eidx, _ = moe_lib._route(spec, params["router"], x2)
        ye_all = jnp.stack([
            moe_lib._expert_ffn(
                spec, jax.tree.map(lambda a: a[e: e + 1], params), x2[None]
            )[0] for e in range(spec.moe.n_experts)])
        want = jnp.einsum("tk,tkd->td", gates,
                          ye_all[eidx, jnp.arange(x2.shape[0])[:, None]])
        np.testing.assert_allclose(np.asarray(y.reshape(-1, 64)),
                                   np.asarray(want), rtol=1e-4, atol=1e-4)
        assert np.isfinite(float(aux))

    def test_capacity_drops_are_masked_not_garbage(self):
        import dataclasses
        spec, params = self._spec(cf=0.25)  # aggressive drops
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
        y, _ = moe_lib.moe_apply(spec, params, x)
        assert np.isfinite(np.asarray(y)).all()
        # dropped tokens shrink ‖y‖ vs the no-drop run, never explode it
        spec2, _ = self._spec(cf=64.0)
        y2, _ = moe_lib.moe_apply(spec2, params, x)
        assert float(jnp.linalg.norm(y)) <= float(jnp.linalg.norm(y2)) + 1e-3

    def test_positions_in_expert(self):
        e = jnp.array([1, 0, 1, 1, 0, 2], jnp.int32)
        pos = moe_lib._positions_in_expert(e, 3)
        np.testing.assert_array_equal(np.asarray(pos), [0, 0, 1, 2, 1, 0])

    def test_grad_flows_through_dispatch(self):
        spec, params = self._spec()
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 64))

        def loss(p):
            y, aux = moe_lib.moe_apply(spec, p, x)
            return jnp.sum(y ** 2) + 0.01 * aux

        g = jax.grad(loss)(params)
        gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0


class TestSSD:
    def test_chunked_matches_naive_recurrence(self):
        from repro.models.ssd import ssd_chunked
        B, T, H, P, N = 1, 16, 2, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (B, T, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, T, 1, N))
        Cm = jax.random.normal(jax.random.PRNGKey(9), (B, T, 1, N))
        y, h_last = ssd_chunked(x, dt, A, Bm, Cm, chunk=4)
        # naive: h_t = exp(dt·A) h + dt·B⊗x ; y_t = C·h
        h = jnp.zeros((B, H, P, N))
        ys = []
        for t in range(T):
            a = jnp.exp(dt[:, t] * A)                      # (B, H)
            h = (a[:, :, None, None] * h
                 + jnp.einsum("bh,bn,bhp->bhpn", dt[:, t], Bm[:, t, 0], x[:, t]))
            ys.append(jnp.einsum("bn,bhpn->bhp", Cm[:, t, 0], h))
        want = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(h),
                                   rtol=1e-4, atol=1e-4)


class TestRGLRU:
    def test_scan_matches_naive(self):
        from repro.models.rglru import _rglru_scan
        B, T, W = 2, 10, 6
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(ks[0], (B, T, W))
        r = jax.random.normal(ks[1], (B, T, W))
        i = jax.random.normal(ks[2], (B, T, W))
        lam = jnp.ones((W,))
        h_seq, h_last = _rglru_scan(x, r, i, lam, c=8.0)
        h = jnp.zeros((B, W))
        for t in range(T):
            log_a = -8.0 * jax.nn.softplus(lam) * jax.nn.sigmoid(r[:, t])
            a = jnp.exp(log_a)
            beta = jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-12))
            h = a * h + beta * (jax.nn.sigmoid(i[:, t]) * x[:, t])
        np.testing.assert_allclose(np.asarray(h_seq[:, -1]), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)


class TestGraniteScalars:
    """Granite's multipliers and RMSNorm eps (``ArchConfig``): at their
    defaults a program holds no operation for them, so Granite-3's serving
    step is the one it was; a value that is set adds its own operation."""

    @staticmethod
    def _step(cfg) -> str:
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: model.init_cache(2, 16))
        tokens = jax.ShapeDtypeStruct((2, 4), jnp.int32)
        steps = jax.ShapeDtypeStruct((2,), jnp.int32)
        return str(jax.make_jaxpr(model.prefill_chunk)(
            params, cache, tokens, steps, steps))

    def test_defaults_add_no_operation(self):
        base = configs.get("granite-3-2b").reduced()
        step = self._step(base)
        spelled = dataclasses.replace(
            base, attention_multiplier=base.head_dim_ ** -0.5,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            logits_scaling=1.0, rms_norm_eps=1e-6)
        assert self._step(spelled) == step
        count = lambda text, op: text.count(f"= {op} ")  # noqa: E731
        # one embedding, two residual adds in the layer loop's one block,
        # one logits division
        for field, value, op, added in [
                ("embedding_multiplier", 12.0, "mul", 1),
                ("residual_multiplier", 0.22, "mul", 2),
                ("logits_scaling", 8.0, "div", 1),
                ("attention_multiplier", 1 / 64, "mul", 0)]:
            text = self._step(dataclasses.replace(base, **{field: value}))
            assert text != step, field
            assert count(text, op) - count(step, op) == added, field
