"""Grouped BLAST kernels, the native int4 nibble path, and the
``group_apply`` fast path — oracle sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant as qt
from repro.core import structures
from repro.core.structures import StructureConfig, make_linear
from repro.kernels import ops, ref


def tol(dtype):
    return (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
            else dict(rtol=3e-4, atol=3e-4))


def _rand_group(key, G, b, p, q, r, dtype=jnp.float32):
    ku, ks, kv = jax.random.split(key, 3)
    U = jax.random.normal(ku, (G, b, p, r), dtype=dtype)
    S = jax.random.normal(ks, (G, b, b, r), dtype=dtype)
    V = jax.random.normal(kv, (G, b, q, r), dtype=dtype)
    return U, S, V


def _quantize_group(U, S, V, bits=8):
    Uq = qt.quantize(U, bits=bits, block_axes=(2, 3))
    Sq = qt.quantize(S, bits=bits, block_axes=(3,))
    Vq = qt.quantize(V, bits=bits, block_axes=(2, 3))
    G, b = U.shape[:2]
    return (Uq, Sq, Vq, Uq.scale.reshape(G, b), Sq.scale.reshape(G, b, b),
            Vq.scale.reshape(G, b))


class TestGroupedKernel:
    """`blast_matmul_grouped_pallas` == the per-projection loop."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "G,T,b,p,q,r",
        [
            (2, 16, 4, 8, 6, 8),     # tiny gate+up-like pair
            (3, 8, 4, 16, 16, 24),   # decode-ish T, three sets
            (2, 40, 8, 6, 4, 12),    # unaligned T / r → padding path
            (4, 1, 16, 16, 8, 16),   # T=1 matvec, wide group
        ],
    )
    def test_matches_per_projection_loop(self, G, T, b, p, q, r, dtype):
        key = jax.random.PRNGKey(hash((G, T, b, p, q, r)) % 2**31)
        U, S, V = _rand_group(key, G, b, p, q, r, dtype)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, b * q), dtype=dtype)
        got = ops.blast_matmul_grouped(x, U, S, V)
        loop = jnp.stack([ops.blast_matmul(x, U[g], S[g], V[g])
                          for g in range(G)])
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(loop, np.float32), **tol(dtype))
        want = ref.blast_matmul_grouped_ref(x, U, S, V)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    def test_batched_leading_dims(self):
        U, S, V = _rand_group(jax.random.PRNGKey(0), 2, 4, 8, 8, 8)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
        got = ops.blast_matmul_grouped(x, U, S, V)
        want = ref.blast_matmul_grouped_ref(x, U, S, V)
        assert got.shape == (2, 2, 5, 32)
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("G,T,b,p,q,r", [(2, 16, 4, 8, 6, 8),
                                             (3, 1, 4, 8, 8, 24)])
    def test_int8_matches_per_projection_loop(self, G, T, b, p, q, r):
        key = jax.random.PRNGKey(hash(("q", G, T, b, p, q, r)) % 2**31)
        U, S, V = _rand_group(key, G, b, p, q, r)
        Uq, Sq, Vq, su, ss, sv = _quantize_group(U, S, V)
        x = jax.random.normal(jax.random.PRNGKey(2), (T, b * q))
        got = ops.blast_matmul_grouped_q(x, Uq.q, Sq.q, Vq.q, su, ss, sv)
        loop = jnp.stack([
            ops.blast_matmul_q(
                x,
                qt.QArray(Uq.q[g], Uq.scale[g], 8),
                qt.QArray(Sq.q[g], Sq.scale[g], 8),
                qt.QArray(Vq.q[g], Vq.scale[g], 8))
            for g in range(G)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(loop),
                                   rtol=3e-4, atol=3e-4)
        want = ref.blast_matmul_grouped_q_ref(x, Uq.q, Sq.q, Vq.q, su, ss, sv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


class TestInt4Kernel:
    """`blast_matmul_q4_pallas`: packed operands, unpack-in-register."""

    @pytest.mark.parametrize(
        "T,b,p,q,r",
        [
            (8, 4, 8, 8, 16),    # aligned
            (5, 4, 8, 6, 13),    # odd r → pad nibble + pad bytes
            (1, 8, 16, 8, 24),   # decode matvec
        ],
    )
    def test_matches_unpacked_int8_reference(self, T, b, p, q, r):
        key = jax.random.PRNGKey(hash((T, b, p, q, r)) % 2**31)
        U, S, V = (a[0] for a in _rand_group(key, 1, b, p, q, r))
        U4 = qt.quantize(U, bits=4, block_axes=(1, 2))
        S4 = qt.quantize(S, bits=4, block_axes=(2,))
        V4 = qt.quantize(V, bits=4, block_axes=(1, 2))
        x = jax.random.normal(jax.random.PRNGKey(3), (T, b * q))
        got = ops.blast_matmul_q(x, U4, S4, V4)
        # the same int4 codes unpacked to int8 through the reference path
        want = ref.blast_matmul_q_ref(
            x, qt.int_values(U4), qt.int_values(S4), qt.int_values(V4),
            U4.scale.reshape(b), S4.scale.reshape(b, b), V4.scale.reshape(b))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # and through the int8 *kernel* on identical codes
        as8 = lambda a: qt.QArray(qt.int_values(a), a.scale, 8)
        got8 = ops.blast_matmul_q(x, as8(U4), as8(S4), as8(V4))
        np.testing.assert_allclose(np.asarray(got), np.asarray(got8),
                                   rtol=2e-5, atol=2e-5)

    def test_operands_stay_packed_at_kernel_boundary(self, monkeypatch):
        """int4 factors must reach the pallas_call still nibble-packed:
        uint8 operands with ceil(r_pad/2) bytes — no int8 materialization."""
        T, b, p, q, r = 3, 4, 8, 8, 21  # unique shape → fresh jit trace
        key = jax.random.PRNGKey(0)
        U, S, V = (a[0] for a in _rand_group(key, 1, b, p, q, r))
        U4 = qt.quantize(U, bits=4, block_axes=(1, 2))
        S4 = qt.quantize(S, bits=4, block_axes=(2,))
        V4 = qt.quantize(V, bits=4, block_axes=(1, 2))
        assert U4.q.dtype == jnp.uint8 and U4.q.shape == (b, p, (r + 1) // 2)

        seen = {}
        real = ops.blast_matmul_q4_pallas

        def spy(x, Up, Sp, Vp, su, ss, sv, **kw):
            seen["shapes"] = (Up.shape, Sp.shape, Vp.shape)
            seen["dtypes"] = (Up.dtype, Sp.dtype, Vp.dtype)
            seen["block_r"] = kw["block_r"]
            return real(x, Up, Sp, Vp, su, ss, sv, **kw)

        monkeypatch.setattr(ops, "blast_matmul_q4_pallas", spy)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, b * q))
        y = ops.blast_matmul_q(x, U4, S4, V4)
        assert y.shape == (T, b * p)
        r_pad = ((r + seen["block_r"] - 1) // seen["block_r"]) * seen["block_r"]
        assert seen["shapes"] == ((b, p, r_pad // 2), (b, b, r_pad // 2),
                                  (b, q, r_pad // 2))
        assert all(dt == jnp.uint8 for dt in seen["dtypes"])

    def test_plane_helpers_roundtrip(self):
        v = jnp.arange(-7, 8, dtype=jnp.int8)           # r = 15 (odd)
        packed = qt.pack_int4(v)
        planes = qt.unpack_int4_planes(packed)
        logical = planes[qt.plane_order(15)]
        np.testing.assert_array_equal(np.asarray(logical), np.asarray(v))


class TestGroupedQ4Kernel:
    """`blast_matmul_grouped_q4_pallas`: one launch over G nibble-packed
    member factor sets == the per-member int4 kernel loop."""

    @pytest.mark.parametrize(
        "G,T,b,p,q,r",
        [
            (2, 16, 4, 8, 6, 8),     # gate+up-like pair, aligned r
            (3, 8, 4, 16, 16, 24),   # three sets
            (2, 5, 4, 8, 6, 13),     # odd r → pad nibble + pad bytes
            (4, 1, 8, 16, 8, 16),    # T=1 matvec, wide group
        ],
    )
    def test_matches_per_member_loop(self, G, T, b, p, q, r):
        key = jax.random.PRNGKey(hash(("q4", G, T, b, p, q, r)) % 2**31)
        U, S, V = _rand_group(key, G, b, p, q, r)
        Uq, Sq, Vq, su, ss, sv = _quantize_group(U, S, V, bits=4)
        assert Uq.q.dtype == jnp.uint8          # packed bytes in, packed out
        x = jax.random.normal(jax.random.PRNGKey(2), (T, b * q))
        got = ops.blast_matmul_grouped_q4(x, Uq.q, Sq.q, Vq.q, su, ss, sv)
        loop = jnp.stack([
            ops.blast_matmul_q(
                x,
                qt.QArray(Uq.q[g], Uq.scale[g], 4, last_dim=r),
                qt.QArray(Sq.q[g], Sq.scale[g], 4, last_dim=r),
                qt.QArray(Vq.q[g], Vq.scale[g], 4, last_dim=r))
            for g in range(G)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(loop),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("G,T,b,p,q,r", [(2, 16, 4, 8, 6, 8),
                                             (3, 1, 4, 8, 8, 24)])
    def test_grouped_int_activations_match_ref(self, G, T, b, p, q, r, bits):
        """Grouped W8A8/W4A8: the integer-contraction grouped kernels against
        the integer XLA reference on identical codes (tight)."""
        key = jax.random.PRNGKey(hash(("a8", G, T, b, p, q, r, bits)) % 2**31)
        U, S, V = _rand_group(key, G, b, p, q, r)
        Uq, Sq, Vq, su, ss, sv = _quantize_group(U, S, V, bits=bits)
        x = jax.random.normal(jax.random.PRNGKey(2), (T, b * q))
        if bits == 4:
            got = ops.blast_matmul_grouped_q4(x, Uq.q, Sq.q, Vq.q, su, ss, sv,
                                              act="int8")
        else:
            got = ops.blast_matmul_grouped_q(x, Uq.q, Sq.q, Vq.q, su, ss, sv,
                                             act="int8")
        xq, sx = qt.quantize_act(x)
        want = ref.blast_matmul_grouped_a8_ref(
            xq, sx, qt.int_values(Uq), qt.int_values(Sq), qt.int_values(Vq),
            su, ss, sv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


class TestGroupApply:
    """structures.group_apply == per-member linear_apply, incl. padding."""

    def _mla_like(self):
        st = StructureConfig(kind="blast", b=4, keep_ratio=0.5)
        # same d_in/b, different d_out and rank → exercises p/r padding
        return make_linear(64, 32, st), make_linear(64, 24, st)

    def test_blast_float_matches_loop(self):
        s1, s2 = self._mla_like()
        p1 = s1.init(jax.random.PRNGKey(0))
        p2 = s2.init(jax.random.PRNGKey(1))
        x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 64))
        plan = structures.group_plan((s1, s2), (p1, p2))
        assert plan is not None and plan["kind"] == "blast"
        y1, y2 = structures.group_apply((s1, s2), (p1, p2), x, plan=plan)
        assert y1.shape == (3, 5, 32) and y2.shape == (3, 5, 24)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(s1.apply(p1, x)),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(s2.apply(p2, x)),
                                   rtol=2e-5, atol=2e-5)

    def test_blast_int8_matches_loop(self):
        s1, s2 = self._mla_like()
        q1 = s1.quantize(s1.init(jax.random.PRNGKey(0)), 8)
        q2 = s2.quantize(s2.init(jax.random.PRNGKey(1)), 8)
        x = jax.random.normal(jax.random.PRNGKey(2), (7, 64))
        ys = structures.group_apply((s1, s2), (q1, q2), x)
        for y, (s, p) in zip(ys, ((s1, q1), (s2, q2))):
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(s.apply_q(p, x)),
                                       rtol=2e-4, atol=2e-4)

    def test_blast_pallas_path_matches(self):
        s1, s2 = self._mla_like()
        p1 = s1.init(jax.random.PRNGKey(0))
        p2 = s2.init(jax.random.PRNGKey(1))
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
        xla = structures.group_apply((s1, s2), (p1, p2), x)
        pal = structures.group_apply((s1, s2), (p1, p2), x, use_pallas=True)
        for a, b_ in zip(xla, pal):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-4)

    def test_dense_and_block_diag_groups(self):
        for kind in ("dense", "block_diag"):
            st = StructureConfig(kind=kind, b=4)
            s1, s2 = make_linear(32, 16, st), make_linear(32, 16, st)
            p1 = s1.init(jax.random.PRNGKey(3))
            p2 = s2.init(jax.random.PRNGKey(4))
            x = jax.random.normal(jax.random.PRNGKey(5), (6, 32))
            plan = structures.group_plan((s1, s2), (p1, p2))
            assert plan is not None, kind
            y1, y2 = structures.group_apply((s1, s2), (p1, p2), x, plan=plan)
            np.testing.assert_allclose(np.asarray(y1),
                                       np.asarray(s1.apply(p1, x)),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(np.asarray(y2),
                                       np.asarray(s2.apply(p2, x)),
                                       rtol=2e-5, atol=2e-5)

    def test_quantized_bundle_with_bias_still_groups(self):
        """The float bias leaf (stripped before group_apply) must not make
        a quantized bundle look 'mixed'-storage — RG-LRU's gate_a/gate_x
        carry biases and must keep their grouped launch under int8."""
        from repro.models import layers as L
        st = StructureConfig(kind="block_diag", b=4)
        s1, s2 = make_linear(32, 32, st), make_linear(32, 32, st)
        p1 = L.linear_init(s1, jax.random.PRNGKey(0), jnp.float32, bias=True)
        p2 = L.linear_init(s2, jax.random.PRNGKey(1), jnp.float32, bias=True)
        p1["bias"] = p1["bias"] + 0.5
        q1 = L.linear_quantize(s1, p1, 8)
        q2 = L.linear_quantize(s2, p2, 8)
        assert structures.group_plan((s1, s2), (q1, q2)) is not None
        x = jax.random.normal(jax.random.PRNGKey(2), (3, 32))
        structures.reset_dispatch_count()
        y1, y2 = L.linear_group_apply((s1, s2), (q1, q2), x)
        assert structures.dispatch_count() == 1
        np.testing.assert_allclose(np.asarray(y1),
                                   np.asarray(L.linear_apply(s1, q1, x)),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(y2),
                                   np.asarray(L.linear_apply(s2, q2, x)),
                                   rtol=2e-4, atol=2e-4)

    def test_ineligible_and_disabled(self):
        st = StructureConfig(kind="blast", b=4)
        s1 = make_linear(64, 32, st)
        s2 = make_linear(32, 32, st)          # different d_in
        p1, p2 = s1.init(jax.random.PRNGKey(0)), s2.init(jax.random.PRNGKey(1))
        assert structures.group_plan((s1, s2), (p1, p2)) is None
        s3 = make_linear(64, 24, st)
        p3 = s3.init(jax.random.PRNGKey(2))
        # mixed storage (float + int8) is ineligible
        assert structures.group_plan((s1, s3),
                                     (p1, s3.quantize(p3, 8))) is None
        # all-int4 blast bundles group (grouped nibble-packed kernel)
        plan4 = structures.group_plan((s1, s3), (s1.quantize(p1, 4),
                                                 s3.quantize(p3, 4)))
        assert plan4 is not None and plan4["storage"] == "int4"
        # non-blast int4 bundles group too (codes unpack to int8 at stack
        # time — RG-LRU's block_diag gate pairs keep their grouped launch)
        bd = StructureConfig(kind="block_diag", b=4)
        b1, b2 = make_linear(32, 32, bd), make_linear(32, 32, bd)
        bp1 = b1.quantize(b1.init(jax.random.PRNGKey(5)), 4)
        bp2 = b2.quantize(b2.init(jax.random.PRNGKey(6)), 4)
        bd_plan = structures.group_plan((b1, b2), (bp1, bp2))
        assert bd_plan is not None and bd_plan["storage"] == "int4"
        xb = jax.random.normal(jax.random.PRNGKey(7), (3, 32))
        for got, s, p in zip(
                structures.group_apply((b1, b2), (bp1, bp2), xb,
                                       plan=bd_plan),
                (b1, b2), (bp1, bp2)):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(s.apply_q(p, xb)))
        with structures.grouping(False):
            assert structures.group_plan((s1, s3), (p1, p3)) is None
        assert structures.group_plan((s1, s3), (p1, p3)) is not None

    def test_blast_int4_matches_loop(self):
        """All-int4 bundle: ONE grouped dispatch, numerics match the
        per-member fused apply_q loop."""
        from repro.models import layers as L
        s1, s2 = self._mla_like()
        q1 = s1.quantize(s1.init(jax.random.PRNGKey(0)), 4)
        q2 = s2.quantize(s2.init(jax.random.PRNGKey(1)), 4)
        x = jax.random.normal(jax.random.PRNGKey(2), (7, 64))
        structures.reset_dispatch_count()
        ys = L.linear_group_apply((s1, s2), (q1, q2), x)
        assert structures.dispatch_count() == 1
        for y, (s, p) in zip(ys, ((s1, q1), (s2, q2))):
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(s.apply_q(p, x)),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_int_activation_mode_matches_loop(self, bits):
        """With the process-wide activation mode on, the grouped path and
        the per-member loop agree (both quantize x per token once)."""
        s1, s2 = self._mla_like()
        q1 = s1.quantize(s1.init(jax.random.PRNGKey(0)), bits)
        q2 = s2.quantize(s2.init(jax.random.PRNGKey(1)), bits)
        x = jax.random.normal(jax.random.PRNGKey(2), (5, 64))
        with structures.activations("int8"):
            ys = structures.group_apply((s1, s2), (q1, q2), x)
            for y, (s, p) in zip(ys, ((s1, q1), (s2, q2))):
                np.testing.assert_allclose(np.asarray(y),
                                           np.asarray(s.apply_q(p, x)),
                                           rtol=2e-4, atol=2e-4)

    def test_int4_prestack_keeps_packed_bytes(self):
        """Pre-stacked int4 bundles hold uint8 nibble-pairs, never an int8
        unpacked copy (the memory win must survive prestacking)."""
        s1, s2 = self._mla_like()
        q1 = s1.quantize(s1.init(jax.random.PRNGKey(0)), 4)
        q2 = s2.quantize(s2.init(jax.random.PRNGKey(1)), 4)
        bundle = structures.prestack((s1, s2), (q1, q2))
        assert bundle is not None and bundle.plan["storage"] == "int4"
        for k in ("U", "S", "V"):
            assert bundle.arrays[k].dtype == jnp.uint8
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
        ys = structures.group_apply((s1, s2), (q1, q2), x,
                                    plan=bundle.plan, stacked=bundle.arrays)
        for y, (s, p) in zip(ys, ((s1, q1), (s2, q2))):
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(s.apply_q(p, x)),
                                       rtol=2e-4, atol=2e-4)

    def test_dispatch_counter(self):
        from repro.models import layers as L
        st = StructureConfig(kind="blast", b=4)
        s1, s2 = make_linear(64, 32, st), make_linear(64, 32, st)
        p1, p2 = s1.init(jax.random.PRNGKey(0)), s2.init(jax.random.PRNGKey(1))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 64))
        structures.reset_dispatch_count()
        L.linear_group_apply((s1, s2), (p1, p2), x)
        assert structures.dispatch_count() == 1          # one grouped launch
        with structures.grouping(False):
            structures.reset_dispatch_count()
            L.linear_group_apply((s1, s2), (p1, p2), x)
            assert structures.dispatch_count() == 2      # per-projection loop


class TestPickBlocksTClamp:
    """pick_blast_blocks must budget VMEM for the T it will actually run."""

    def test_decode_t_clamps_block_t(self):
        bt, _ = ops.pick_blast_blocks(1, 4096, 4096, 16, 1024)
        assert bt == 8
        bt, _ = ops.pick_blast_blocks(17, 4096, 4096, 16, 1024)
        assert bt <= 24

    def test_decode_gets_no_smaller_block_r(self):
        # With block_t clamped, the freed VMEM must not shrink block_r:
        # decode tiles deserve at least the prefill pick's r granularity.
        _, br_decode = ops.pick_blast_blocks(1, 8192, 8192, 16, 2048)
        _, br_prefill = ops.pick_blast_blocks(512, 8192, 8192, 16, 2048)
        assert br_decode >= br_prefill


class TestTpuLegalBlocks:
    """Every heuristic tiling is one the TPU compiler accepts: block_r a
    multiple of 128 (packed int4: 128 bytes = 256 ranks) or the whole rank
    padded to 8, and the resident set within the VMEM budget."""

    @pytest.mark.parametrize("T", [1, 8, 128])
    @pytest.mark.parametrize("m,n,r", [(4096, 4096, 1024),
                                       (11008, 4096, 1488),
                                       (4096, 11008, 1488),
                                       (576, 576, 112)])
    @pytest.mark.parametrize("kind,fb", [("float", 2), ("int8", 1),
                                         ("int4", 0.5)])
    def test_heuristic_blocks_are_legal(self, T, m, n, r, kind, fb):
        from repro.kernels.blast_matmul import VMEM_BUDGET, vmem_bytes
        bt, br = ops._resolve_blocks(None, None, T, m, n, 16, r,
                                     jnp.bfloat16, fb, 1, kind)
        lane = 256 if kind == "int4" else 128
        whole = r <= lane and br == (lane if kind == "int4"
                                     else ops._round_up(r, 8))
        assert br % lane == 0 or whole, br
        assert bt % 8 == 0 and bt <= max(8, ops._round_up(T, 8))
        assert vmem_bytes(bt, br, m, n, 16, 2, fb, 2) <= VMEM_BUDGET
