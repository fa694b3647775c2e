"""The ``hybrid_mamba2`` family (Granite-4.0-H-Micro): its plain reference
against the logits the engine's timed step produced, prefill then decode
through the cache; the harness end to end on its cell; its counts; and its
program check.  At ``.reduced()`` sizes on the CPU.

Both sides hold and compute in float32 in the reference comparison, so
they differ by float32 rounding alone (about 1e-6 of the largest logit):
the tolerance, 1e-4 of it, leaves that a hundredfold room, and is ten times
under what the state math in bfloat16 gives (about 1e-3); a dropped D term
or reversed conv taps give about 1.  Each of those three breakages is
planted in the program below and must fail."""

import itertools
import json

import numpy as np
import pytest

from conftest import ROOT, TINY_MIX, config

NAME = "granite-4-h-micro-blast50"
CELL = f"{NAME}.long-decode"
TOL = 1e-4          # of the largest reference logit; see the module's doc
PEAK_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _family():
    from bench import load
    return load("families", "hybrid_mamba2")


def _reference():
    from bench import load
    return load("reference", "hybrid_mamba2")


# -- the reference against the served logits ----------------------------------


def _bf16_state(monkeypatch):
    """The SSD state kept in bfloat16: rounded as it enters and leaves each
    call of either served form (every token when decoding)."""
    import jax.numpy as jnp
    from repro.models import ssd

    chunked, tokens = ssd.ssd_chunked, ssd._ssd_tokens
    rnd = lambda h: h.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def chunked_bf16(x, dt, A, Bm, Cm, chunk, h0=None):
        y, h = chunked(x, dt, A, Bm, Cm, chunk, h0=rnd(h0))
        return y, rnd(h)

    def tokens_bf16(x, dt, A, Bm, Cm, h0, collect):
        y, h, hs = tokens(x, dt, A, Bm, Cm, rnd(h0), collect)
        return y, rnd(h), hs

    monkeypatch.setattr(ssd, "ssd_chunked", chunked_bf16)
    monkeypatch.setattr(ssd, "_ssd_tokens", tokens_bf16)


def _edit_params(leaf: str, fn):
    """A change of every SSD mixer's ``leaf`` in the program's weights."""
    import jax

    def edit(params):
        def one(path, a):
            return fn(a) if getattr(path[-1], "key", None) == leaf else a
        return jax.tree_util.tree_map_with_path(one, params)
    return edit


BREAKAGES = {
    "sound": None,
    "bf16_state": _bf16_state,
    "no_D": _edit_params("D", lambda a: a * 0),
    "reversed_conv": _edit_params("conv_w", lambda a: a[..., ::-1, :]),
}


@pytest.fixture(scope="module")
def served():
    """One reduced float32 engine and its reference configuration: every
    breakage below runs on it, so that it compiles once."""
    from bench import run as R

    cfg = dict(config(NAME), param_dtype="float32", compute_dtype="float32")
    arch, rcfg = R.program_config(cfg, reduced=True)
    key = R.seed_key(2**31 + 11)
    return R.build_engine(arch, TINY_MIX, key, None), rcfg, key


def _served_logits(engine, n_requests, seed, uid0):
    """Drive the engine; keep the logits of every sampled row."""
    from bench import traffic
    from repro.serve import Request

    seen = {}
    step = engine._step

    def spy(params, cache, tokens, steps, n):
        logits, cache = step(params, cache, tokens, steps, n)
        steps_h, n_h = np.asarray(steps), np.asarray(n)
        for b, slot in enumerate(engine.slots):
            r = slot.req
            if r is not None and n_h[b] and (
                    steps_h[b] + n_h[b] == len(r.prompt) + len(r.output)):
                seen.setdefault(r.uid, []).append(
                    np.asarray(logits[b, 0], np.float32))
        return logits, cache

    engine._step = spy
    reqs = []
    for p in itertools.islice(traffic.schedule(
            TINY_MIX, seed, engine.model.cfg.vocab, 2.0, uid0=uid0),
            n_requests):
        reqs.append(Request(uid=p.uid, prompt=p.prompt,
                            max_new_tokens=p.max_new))
        engine.submit(reqs[-1])
    try:
        while engine.queue or any(s.req is not None for s in engine.slots):
            engine.tick()
    finally:
        engine._step = step
    return reqs, seen


@pytest.mark.parametrize("breakage", sorted(BREAKAGES))
def test_reference_matches_engine_prefill_then_decode(served, breakage,
                                                     monkeypatch):
    import jax

    engine, rcfg, key = served
    plant = BREAKAGES[breakage]
    if breakage == "bf16_state":
        plant(monkeypatch)       # traced anew, with the breakage in place
        monkeypatch.setattr(engine, "_step",
                            jax.jit(engine.model.prefill_chunk))
    elif plant is not None:
        monkeypatch.setattr(engine, "params", plant(engine.params))
    reqs, seen = _served_logits(engine, 4, seed=3,
                                uid0=1000 * sorted(BREAKAGES).index(breakage))
    # every sequence in one padded batch: the reference compiles once
    seqs = [r.prompt + r.output[:-1] for r in reqs]
    tokens = np.zeros((len(seqs), TINY_MIX["engine"]["max_len"]), np.int32)
    for i, seq in enumerate(seqs):
        tokens[i, :len(seq)] = seq
    want_all = _reference().logits(rcfg, key, tokens)
    worst = 0.0
    for r, want in zip(reqs, want_all):
        got = np.stack(seen[r.uid])
        assert got.shape[0] == len(r.output)   # the prefill's, then decodes'
        n0 = len(r.prompt) - 1
        want = want[n0:n0 + len(r.output)]
        worst = max(worst, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
    assert (worst < TOL) == (breakage == "sound"), worst


# -- the harness on the cell ---------------------------------------------------


@pytest.fixture
def tiny_hybrid_cell():
    from bench import run as R

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = lambda ms: [m for m in ms  # noqa: E731
                       if CELL in m.get("workloads", [CELL])]
    lim = json.loads((ROOT / "bench" / "limits" / f"{CELL}.json").read_text())
    return R.Cell(CELL, 1, config(NAME), TINY_MIX, mine(bench["end_to_end"]),
                  mine(bench["per_layer"]), lim)


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           ("stale_cache", False)])
def test_harness_checks_the_cell(tiny_hybrid_cell, fault, correct):
    from bench import run as R

    res = R.run_cell(tiny_hybrid_cell, 2**31 + 21, 2.0, False, fault=fault,
                     reduced=True)
    assert res["correct"] is correct
    assert res["attempted"] > 0 and res["failed"] == 0


def test_prefill_tick_reads_the_prefill_ticks(served, tiny_hybrid_cell):
    """The cell's prefill ticks: the median host time of the window's ticks
    that fed prompt tokens, and nothing when the window fed none."""
    import statistics

    import jax
    from bench import run as R

    engine, cfg, _ = served
    cell = tiny_hybrid_cell
    rec = R.Recorder(engine, cfg)
    win = R.drive(engine, rec, cell.mix, 2**31 + 23, 1.5, False,
                  R.CompileCount())
    rec.close()
    run = R.Run(cell, cfg, rec, win, jax.devices()[:1], peak=PEAK_V5E)
    assert "prefill_tick_ms.offline" in {m["name"] for m in cell.per_layer}
    read = R.reader("prefill_tick_ms.offline")
    ticks = [t.end - t.start for t in rec.ticks
             if t.prefill and run.in_window(t.end)]
    assert ticks
    assert read(run) == pytest.approx(1e3 * statistics.median(ticks))
    for t in rec.ticks:
        t.prefill = False
    assert read(run) is None


# -- counts and the program check ---------------------------------------------


def test_param_bytes_match_program_shapes():
    import jax
    from bench import run as R
    from repro.models import build_model

    cfg = config(NAME)
    arch, _ = R.program_config(cfg)
    shapes = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    have = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert _family().param_bytes(cfg) == have


GOLDEN = {
    ("param_bytes",): 3347588096,
    ("state_bytes",): 76437504,
    ("kv_bytes_per_token",): 8192,
    ("head_flops",): 411041792,
    ("span_flops", 0, 1): 3028729856,
    ("span_flops", 5, 40): 106030776320,
    ("span_flops", 1000, 1256): 784812998656,
    ("decode_step", (100, 1500, 7)): {
        "flops": 10371874816, "bytes": 3819402240,
        "least_s": 0.004663494798534799, "bound": "bytes"},
    ("decode_step", (1800,) * 32): {
        "flops": 111959080960, "bytes": 8711709696,
        "least_s": 0.010637008175824176, "bound": "bytes"},
}


@pytest.mark.parametrize("call,expect", GOLDEN.items(), ids=str)
def test_golden_counts(call, expect):
    fam = _family()
    cfg = config(NAME)
    fn, *args = call
    if fn == "decode_step":
        got = fam.decode_step(cfg, list(args[0]), PEAK_V5E)
    else:
        got = getattr(fam, fn)(cfg, *args)
    assert got == expect


def test_counts_by_term():
    """The SSD mixer by its recurrence, 5 H P N a token and layer; state
    and KV per row by the file's dtypes."""
    fam = _family()
    cfg = config(NAME)
    assert fam.ssd_flops_per_token(cfg) == 5 * 64 * 64 * 128 * 36
    assert fam.state_bytes(cfg) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert fam.kv_bytes_per_token(cfg) == 2 * 8 * 64 * 4 * 2
    one = lambda p: fam.span_flops(cfg, p, p + 1)  # noqa: E731
    assert fam.span_flops(cfg, 5, 40) == sum(one(p) for p in range(5, 40))
    assert one(9) == (fam.linear_flops_per_token(cfg)
                      + fam.ssd_flops_per_token(cfg)
                      + 10 * fam.attn_flops_per_context(cfg))


@pytest.mark.parametrize("key,value", [
    ("mamba_d_state", 64), ("mamba_n_heads", 32), ("num_hidden_layers", 36),
    ("position_embedding_type", "rope"), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.125), ("rms_norm_eps", 1e-6),
    ("shared_intermediate_size", 4096),
    ("layer_types", ["attention"] + ["mamba"] * 39)])
def test_program_config_refuses_a_differing_file(key, value):
    from bench import run as R

    cfg = dict(config(NAME), **{key: value})
    with pytest.raises(SystemExit, match=f"differs from the configuration "
                                         f"file: .*{key}"):
        R.program_config(cfg)


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-130m"])
def test_refuses_other_layers(arch):
    from bench import run as R

    with pytest.raises(SystemExit, match="differs from the configuration "
                                         "file: layers$"):
        R.program_config(dict(config(NAME), arch=arch))
