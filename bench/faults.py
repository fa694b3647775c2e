"""Faults planted in the timed path, to show that the correctness check
fails them.  Each wraps a call of one engine instance after warm-up; none
is used by a benchmark run.

  stale_cache   the step returns its cache unchanged (state not updated)
  alter_token   every fifth emitted token is replaced by its successor id
"""

from __future__ import annotations


def plant(engine, kind: str) -> None:
    if kind == "stale_cache":
        import jax
        import jax.numpy as jnp
        step = engine._step

        def stale(params, cache, *args):
            # a copy taken before the step: a step may consume (donate) the
            # cache it is given
            before = jax.tree.map(jnp.copy, cache)
            logits, _ = step(params, cache, *args)
            return logits, before
        engine._step = stale
    elif kind == "alter_token":
        emit, count = engine._emit, [0]
        vocab = engine.model.cfg.vocab

        def altered(req, tok):
            count[0] += 1
            if count[0] % 5 == 0:
                tok = (tok + 1) % vocab
            emit(req, tok)
        engine._emit = altered
    else:
        raise ValueError(f"unknown fault {kind!r}")
