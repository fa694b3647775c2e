"""The served SSD prefill (``models/ssd.py::ssd_prefill``).

A chunk wider than one token takes the chunked dual form from each row's
cached state; it must leave every row where C single-token steps (the
recurrent form a decode step runs) leave it: the same outputs on live
columns, the same state and conv window after the chunk, across inner
chunk boundaries, with ragged rows, dead columns and a row that feeds
nothing.  Both forms compute in float32 and differ only in the order of
their sums: 1e-5 of the largest value leaves that rounding (about 2e-7
here) fifty times room.  A row with no token keeps its state bit for bit.

And a slot of the hybrid Granite-4.0-H configuration reused after a longer
request serves the next one as a fresh engine does: admission overwrites
every cache leaf of the row, SSD state and conv window included.

Both paths name their conv and state math (``ssd_conv``, ``ssd_scan``) in
the compiled step's op metadata, which is what ``scripts/scope_time.py``
sums the device time by.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, ssd
from repro.serve import Engine, EngineConfig, MemoryConfig, Request
from repro.serve import SchedulerConfig

RTOL = 1e-5


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("inner", [4, 16, 128])
def test_chunked_prefill_is_the_token_recurrence(inner):
    cfg = configs.get("granite-4-h-micro").reduced()
    spec = dataclasses.replace(ssd.make_ssd(cfg), chunk=inner)
    params = ssd.ssd_init(spec, jax.random.PRNGKey(0), jnp.float32)
    B, C = 4, 16
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    empty = ssd.ssd_cache_init(spec, B, 64, jnp.float32)
    cache = {"conv": jax.random.normal(k[0], empty["conv"].shape),
             "h": jax.random.normal(k[1], empty["h"].shape)}
    x = jax.random.normal(k[2], (B, C, cfg.d_model))
    n = jnp.array([16, 5, 0, 9], jnp.int32)      # whole, ragged, none, ragged
    steps = jnp.zeros((B,), jnp.int32)
    y, got = ssd.ssd_prefill(spec, params, cache, x, steps, n)

    want, ys = cache, []
    for t in range(C):
        y_t, want = ssd.ssd_prefill(spec, params, want, x[:, t:t + 1], steps,
                                    (t < n).astype(jnp.int32))
        ys.append(y_t)
    live = (jnp.arange(C)[None, :] < n[:, None])[..., None]
    y_tok = jnp.concatenate(ys, axis=1)
    assert _rel(jnp.where(live, y, 0), jnp.where(live, y_tok, 0)) < RTOL
    assert _rel(got["h"], want["h"]) < RTOL
    assert _rel(got["conv"], want["conv"]) < RTOL
    assert (got["h"][2] == cache["h"][2]).all()
    assert (got["conv"][2] == cache["conv"][2]).all()


def test_slot_reused_after_a_longer_request():
    """The same logits, bit for bit, as a fresh engine gives the request."""
    cfg = configs.get("granite-4-h-micro").reduced(vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    step = jax.jit(model.prefill_chunk)

    def serve(*requests):
        seen = []

        def spy(*args):
            logits, cache = step(*args)
            seen.append(np.asarray(logits))
            return logits, cache
        eng = Engine(model, params, EngineConfig(
            scheduler=SchedulerConfig(slots=1, chunk_size=8),
            memory=MemoryConfig(max_len=64)), step_fn=spy)
        for r in requests:
            eng.submit(r)
        return {r.uid: r.output for r in eng.run()}, seen

    short = [7, 3, 9, 1]
    want, fresh = serve(Request(uid=0, prompt=short, max_new_tokens=6))
    done, reused = serve(
        Request(uid=1, prompt=list(range(2, 30)), max_new_tokens=12),
        Request(uid=2, prompt=short, max_new_tokens=6))
    assert len(done[1]) == 12 and done[2] == want[0]
    assert len(reused) > len(fresh)
    for a, b in zip(reused[-len(fresh):], fresh):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("C", [1, 16], ids=["tokens", "chunked"])
def test_both_paths_name_their_scopes(C):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))
    import scope_time

    cfg = configs.get("granite-4-h-micro").reduced(vocab=64)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 32))
    tokens = jax.ShapeDtypeStruct((2, C), jnp.int32)
    steps = jax.ShapeDtypeStruct((2,), jnp.int32)
    hlo = jax.jit(model.prefill_chunk).lower(
        params, cache, tokens, steps, steps).compile().as_text()
    named = set(scope_time.op_scopes(hlo).values())
    assert {"ssd_conv", "ssd_scan", "attention", "linear"} <= named
