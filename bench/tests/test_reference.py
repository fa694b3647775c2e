"""The plain reference against the logits the engine's timed step produced,
prefill chunks first and then decode steps, at ``.reduced()`` sizes.

Both sides hold and compute in float32 here, so they differ by float32
rounding alone: any weight the reference made differently from the
program's initialiser would show far above it."""

import importlib.util

import numpy as np
import pytest

from conftest import ROOT, TINY_MIX, config


def _reference():
    path = ROOT / "bench" / "reference" / "dense_gqa.py"
    spec = importlib.util.spec_from_file_location("ref_dense_gqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _served_logits(engine, n_requests, seed):
    """Drive the engine; keep the logits of every sampled row."""
    import itertools
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
    plans = itertools.islice(traffic.schedule(
        TINY_MIX, seed, engine.model.cfg.vocab, 2.0), n_requests)
    reqs = []
    for p in plans:
        reqs.append(Request(uid=p.uid, prompt=p.prompt,
                            max_new_tokens=p.max_new))
        engine.submit(reqs[-1])
    while engine.queue or any(s.req is not None for s in engine.slots):
        engine.tick()
    return reqs, seen


@pytest.mark.parametrize("name", ["granite-3-2b-blast50",
                                  "granite-3-2b-dense"])
def test_reference_matches_engine_prefill_then_decode(name):
    from bench import run as R

    cfg = dict(config(name), param_dtype="float32", compute_dtype="float32")
    arch, rcfg = R.program_config(cfg, reduced=True)
    key = R.seed_key(2**31 + 11)
    engine = R.build_engine(arch, TINY_MIX, key, None)
    reqs, seen = _served_logits(engine, 6, seed=3)
    ref = _reference()
    worst = 0.0
    for r in reqs:
        seq = r.prompt + r.output[:-1]
        want = ref.logits(rcfg, key, np.asarray([seq]))[0]
        got = np.stack(seen[r.uid])
        assert got.shape[0] == len(r.output)   # the prefill's, then decodes'
        n0 = len(r.prompt) - 1
        want = want[n0:n0 + len(r.output)]
        worst = max(worst, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
    assert worst < 1e-4, worst


def test_served_gaps_are_zero_for_the_reference_own_choice():
    from bench import run as R

    cfg = config("granite-3-2b-blast50")
    arch, rcfg = R.program_config(cfg, reduced=True)
    key = R.seed_key(7)
    ref = _reference()
    prompt = [5, 9, 2, 77, 31]
    lg = ref.logits(rcfg, key, np.asarray([prompt]))[0]
    best = int(lg[-1].argmax())
    worst = int(lg[-1].argmin())
    g = ref.served_gaps(rcfg, key, [(prompt, [best]), (prompt, [worst])],
                        length=16, rows=2)
    assert g[0][0] == 0.0
    assert g[1][0] == pytest.approx(lg[-1].max() - lg[-1].min(), rel=1e-5)
