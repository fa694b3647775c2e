"""Counts from shapes against the program's own parameter shapes, and the
decode step's needed bytes term by term."""

import jax
import pytest

from conftest import config


@pytest.mark.parametrize("name,expect", [
    ("granite-3-2b-blast50", 2_591_440_896),
    ("granite-3-2b-dense", 5_067_063_296)])
def test_param_bytes_match_program_shapes(name, expect):
    from bench import counts, run as R
    from repro.models import build_model

    cfg = config(name)
    arch, _ = R.program_config(cfg)
    model = build_model(arch)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert counts.param_bytes(cfg) == have == expect


def test_decode_bytes_are_the_three_terms():
    from bench import counts

    cfg = config("granite-3-2b-blast50")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = [100, 1500, 7]
    c = counts.decode_step(cfg, ctx, peak)
    kv = counts.kv_bytes_per_token(cfg)
    assert kv == 2 * 8 * 64 * 40 * 2
    assert c["bytes"] == counts.param_bytes(cfg) + kv * sum(ctx) + kv * 3
    assert c["bound"] == "bytes"
    assert c["least_s"] == pytest.approx(c["bytes"] / 819e9)


def test_span_flops_is_the_sum_over_tokens():
    from bench import counts

    cfg = config("granite-3-2b-dense")
    one = lambda p: counts.span_flops(cfg, p, p + 1)  # noqa: E731
    assert counts.span_flops(cfg, 5, 40) == sum(one(p) for p in range(5, 40))
    assert one(9) == (counts.linear_flops_per_token(cfg)
                      + 10 * counts.attn_flops_per_context(cfg))
    assert counts.linear_flops_per_token(cfg) == 2 * 40 * (
        2048 * 3072 + 2048 * 2048 + 3 * 2048 * 8192)
