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


PEAK_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the counts of both Granite files as bench/counts.py gave them before they
# moved into bench/families/dense_gqa.py: the move changes no count
GOLDEN = {
    "granite-3-2b-blast50": {
        ("param_bytes",): 2591440896,
        ("head_flops",): 201338880,
        ("span_flops", 0, 1): 2390097920,
        ("span_flops", 5, 40): 83905740800,
        ("span_flops", 255, 256): 2473656320,
        ("span_flops", 1000, 1256): 706446622720,
        ("decode_step", (100, 1500, 7)): {
            "flops": 8299909120, "bytes": 2723332096,
            "least_s": 0.0033251918144078145, "bound": "bytes"},
        ("decode_step", (1024,) * 12): {
            "flops": 35119841280, "bytes": 3599056896,
            "least_s": 0.004394452864468865, "bound": "bytes"}},
    "granite-3-2b-dense": {
        ("param_bytes",): 5067063296,
        ("head_flops",): 201338880,
        ("span_flops", 0, 1): 4865720320,
        ("span_flops", 5, 40): 170552524800,
        ("span_flops", 255, 256): 4949278720,
        ("span_flops", 1000, 1256): 1340205957120,
        ("decode_step", (100, 1500, 7)): {
            "flops": 15726776320, "bytes": 5198954496,
            "least_s": 0.006347929787545788, "bound": "bytes"},
        ("decode_step", (1024,) * 12): {
            "flops": 64827310080, "bytes": 6074679296,
            "least_s": 0.007417190837606838, "bound": "bytes"}},
}


@pytest.mark.parametrize("name,call,expect", [
    (name, call, expect) for name, calls in GOLDEN.items()
    for call, expect in calls.items()], ids=str)
def test_golden_counts(name, call, expect):
    from bench import counts

    cfg = config(name)
    fn, *args = call
    if fn == "decode_step":
        got = counts.decode_step(cfg, list(args[0]), PEAK_V5E)
    else:
        got = getattr(counts, fn)(cfg, *args)
    assert got == expect
