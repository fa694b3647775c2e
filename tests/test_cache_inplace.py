"""The layer loop of ``LM.prefill_chunk`` writes the stacked cache in place.

The sequence-axis cache leaves (GQA K/V/pos and int8 scales, MLA latent /
rope / pos) ride the loop's carry as the stacked ``(L, B, S, …)`` arrays and
each layer scatters its new columns into them at (layer, row, slot); the
recurrent state leaves are sliced in and stacked out per layer.  These tests
hold that loop to a plain per-layer reference — a Python loop over the
layers, each block run on its own unstacked slice of the cache — for every
cache family, and check the write rule on its own terms: ragged rows, dead
columns, a chunk that runs past the end of the cache, a ring shorter than
the chunk.  ``ops.cache_attention``, which reads K and V in their stored
lane-dense layout, is held to a per-head softmax oracle.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model, ops
from repro.models import transformer as tf
from repro.quant import QuantConfig

MAX_LEN = 32


def _cfg(family):
    base = {"gqa": "smollm-135m", "int8": "smollm-135m",
            "mla": "deepseek-v3-671b", "ssd": "mamba2-130m",
            "rglru": "recurrentgemma-2b", "rglru-int8": "recurrentgemma-2b"}
    cfg = configs.ARCHS[base[family]].reduced(vocab=64)
    if family.endswith("int8"):
        cfg = dataclasses.replace(cfg, quant=QuantConfig(cache="int8"))
    if cfg.moe is not None:  # no capacity drops: routing is per token
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    return cfg


def per_layer_prefill(model, params, cache, tokens, steps, n_tokens):
    """``prefill_chunk`` as a Python loop over layers: every block reads and
    returns its own unstacked slice of the cache, and the slices are
    stacked again at the end."""
    B, C = tokens.shape
    par = model.parallel
    x = model._embed(params, tokens)
    new = {}
    for i, spec in enumerate(model.prefix_specs):
        x, new[f"pre_{i}"] = tf.block_prefill(
            spec, params[f"pre_{i}"], cache[f"pre_{i}"], x, steps, n_tokens,
            par)
    if model.n_cycles:
        outs = []
        for layer in range(model.n_cycles):
            p = jax.tree.map(lambda a: a[layer], params["cycles"])
            c = jax.tree.map(lambda a: a[layer], cache["cycles"])
            out = {}
            for j, spec in enumerate(model.cycle_specs):
                x, out[f"blk_{j}"] = tf.block_prefill(
                    spec, p[f"blk_{j}"], c[f"blk_{j}"], x, steps, n_tokens,
                    par)
            outs.append(out)
        new["cycles"] = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    for i, spec in enumerate(model.tail_specs):
        x, new[f"tail_{i}"] = tf.block_prefill(
            spec, params[f"tail_{i}"], cache[f"tail_{i}"], x, steps,
            n_tokens, par)
    last = jnp.clip(n_tokens - 1, 0, C - 1)[:, None, None]
    x = jnp.take_along_axis(x, jnp.broadcast_to(last, (B, 1, x.shape[-1])),
                            axis=1)
    return model._head(params, x), new


@functools.lru_cache(maxsize=None)
def _model(family):
    """(model, params, jitted step, jitted reference), built once a family."""
    model = build_model(_cfg(family))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    ref = jax.jit(functools.partial(per_layer_prefill, model))
    return model, params, jax.jit(model.prefill_chunk), ref


def _run(family, prefix, steps, n_tokens, C):
    """Prefill ``prefix`` (each row's live count) from position 0, then one
    chunk of width C at ``steps`` / ``n_tokens``, through ``prefill_chunk``
    and through the per-layer reference.  Returns both results and the
    cache before the chunk."""
    model, params, step, ref = _model(family)
    B = len(steps)
    key = jax.random.PRNGKey(1)
    toks0 = jax.random.randint(key, (B, max(prefix)), 0, 64)
    _, cache0 = step(params, model.init_cache(B, MAX_LEN), toks0,
                     jnp.zeros((B,), jnp.int32),
                     jnp.asarray(prefix, jnp.int32))
    toks = jax.random.randint(jax.random.fold_in(key, 1), (B, C), 0, 64)
    steps = jnp.asarray(steps, jnp.int32)
    n_tokens = jnp.asarray(n_tokens, jnp.int32)
    got = step(params, cache0, toks, steps, n_tokens)
    want = ref(params, cache0, toks, steps, n_tokens)
    return model, cache0, got, want


def _assert_same(got, want, n_tokens, family):
    (lg, cache), (lw, ref) = got, want
    live = np.asarray(n_tokens) > 0   # a row with nothing live: no logit
    np.testing.assert_allclose(np.asarray(lg)[live], np.asarray(lw)[live],
                               rtol=1e-5, atol=1e-5, err_msg=family)
    flat_g = jax.tree_util.tree_flatten_with_path(cache)[0]
    flat_w = dict(jax.tree_util.tree_flatten_with_path(ref)[0])
    assert len(flat_g) == len(flat_w)
    for path, a in flat_g:
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(flat_w[path], np.float32),
            rtol=1e-5, atol=1e-5,
            err_msg=f"{family} {jax.tree_util.keystr(path)}")


def _seq_leaves(model, cache):
    """(path, leaf, batch axis, seq axis) of every full-length (not ring)
    sequence-axis leaf."""
    axes = jax.tree_util.tree_flatten_with_path(
        model.cache_axes(), is_leaf=lambda t: isinstance(t, tuple))[0]
    leaves = dict(jax.tree_util.tree_flatten_with_path(cache)[0])
    out = []
    for path, ax in axes:
        if "kv_seq" in ax and leaves[path].shape[ax.index("kv_seq")] == MAX_LEN:
            out.append((path, leaves[path], ax.index("batch"),
                        ax.index("kv_seq")))
    return out


def _assert_writes_only(model, cache0, cache, steps, n_tokens):
    """Each row changed exactly the slots of its live columns that fit in
    the cache; ``pos`` there holds the position, and -1 past them."""
    old = dict((jax.tree_util.keystr(p), (a, b, s))
               for p, a, b, s in _seq_leaves(model, cache0))
    checked = 0
    for path, new, bax, sax in _seq_leaves(model, cache):
        name = jax.tree_util.keystr(path)
        prev = old[name][0]
        new, prev = np.moveaxis(np.asarray(new, np.float32), (bax, sax),
                                (0, 1)), \
            np.moveaxis(np.asarray(prev, np.float32), (bax, sax), (0, 1))
        for b, (s0, n) in enumerate(zip(steps, n_tokens)):
            written = [p for p in range(s0, s0 + n) if p < MAX_LEN]
            keep = [p for p in range(MAX_LEN) if p not in written]
            np.testing.assert_array_equal(new[b, keep], prev[b, keep],
                                          err_msg=f"{name} row {b}")
            if name.endswith("['pos']"):
                np.testing.assert_array_equal(
                    new[b, written],
                    np.broadcast_to(np.asarray(written, np.float32)[
                        (slice(None),) + (None,) * (new.ndim - 2)],
                        new[b, written].shape), err_msg=name)
                past = slice(max([s0 + n, *written], default=0), MAX_LEN)
                assert (new[b, past] == -1).all(), name
            checked += 1
    return checked


FAMILIES = ["gqa", "mla", "ssd", "rglru", "int8"]

# case: (prefix live counts, chunk steps, chunk live counts, chunk width)
CASES = {
    # rows at different depths, ragged live counts, one idle row
    "ragged": ([8, 5, 2, 1], [8, 5, 2, 1], [8, 3, 1, 0], 8),
    # dead columns: one live column in a wide chunk, and none at all
    "dead": ([6, 6, 3], [6, 6, 3], [1, 0, 5], 8),
    # a row one slot from the end of the cache with four live columns:
    # exactly one slot is written and nothing shifts
    "edge": ([4, 4], [MAX_LEN - 1, 4], [4, 4], 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("family", FAMILIES)
def test_matches_per_layer_reference(family, case):
    prefix, steps, n_tokens, C = CASES[case]
    model, cache0, got, want = _run(family, prefix, steps, n_tokens, C)
    _assert_same(got, want, n_tokens, family)
    checked = _assert_writes_only(model, cache0, got[1], steps, n_tokens)
    if family in ("gqa", "mla", "int8"):   # full-length attention caches
        assert checked


@pytest.mark.parametrize("family", ["rglru", "rglru-int8"])
def test_ring_shorter_than_chunk(family):
    """A sliding-window layer (ring of 16 slots) fed a 24-column chunk
    keeps each row's last 16 live positions, as the per-layer reference
    does."""
    steps, n_tokens = [3, 0], [24, 20]
    model, _, got, want = _run(family, [3, 1], steps, n_tokens, 24)
    _assert_same(got, want, n_tokens, family)
    pos = [np.asarray(leaf) for path, leaf in
           jax.tree_util.tree_flatten_with_path(got[1])[0]
           if jax.tree_util.keystr(path).endswith("['pos']")]
    assert pos, "no ring layer"
    for p in pos:   # stacked (L, B, 16)
        for layer in range(p.shape[0]):
            for b, (s0, n) in enumerate(zip(steps, n_tokens)):
                assert sorted(p[layer, b]) == list(range(s0 + n - 16, s0 + n))


def _per_head(q, k, v, k_pos, q_pos, window):
    """Softmax attention one head at a time, float64, K/V as (B, S, H, D)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    out = np.zeros((B, T, Hq, v.shape[-1]))
    for b in range(B):
        for t in range(T):
            ok = (k_pos[b] >= 0) & (k_pos[b] <= q_pos[b, t])
            if window is not None:
                ok &= k_pos[b] > q_pos[b, t] - window
            if not ok.any():
                continue
            for h in range(Hq):
                kh = h // (Hq // Hkv)
                s = k[b, ok, kh] @ q[b, t, h] / math.sqrt(D)
                p = np.exp(s - s.max())
                out[b, t, h] = (p / p.sum()) @ v[b, ok, kh]
    return out


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("shape", [(8, 64, 1), (8, 64, 5), (2, 128, 3),
                                   (3, 64, 2), (1, 16, 4),
                                   (2, 64, ops.CACHE_Q_BLOCK + 3)],
                         ids=["granite-decode", "granite-chunk", "d128",
                              "three-heads", "one-head", "two-passes"])
def test_cache_attention_matches_per_head(shape, stacked):
    """Lane groups (``ops.lane_group``) of one, two and all heads, from an
    unstacked cache and from one layer of a stacked one, and a chunk wider
    than one pass of queries."""
    Hkv, D, T = shape
    B, S, G, L = 2, max(24, T + 8), 2, 3
    rng = np.random.default_rng(Hkv * D + T)
    q = rng.standard_normal((B, T, Hkv * G, D)).astype(np.float32)
    k = rng.standard_normal((L, B, S, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((L, B, S, Hkv, D)).astype(np.float32)
    k_pos = np.where(rng.random((B, S)) < 0.8, np.arange(S), -1)
    q_pos = np.array([[10 + t for t in range(T)], [S - T + t for t in
                                                   range(T)]])
    window = 7 if T > 1 else None
    layer = 1
    ks, vs = (a.reshape(L, B, S, Hkv * D) for a in (k, v))
    if stacked:
        got = ops.cache_attention(q, ks, vs, k_pos, q_pos, window=window,
                                  layer=jnp.int32(layer))
    else:
        got = ops.cache_attention(q, ks[layer], vs[layer], k_pos, q_pos,
                                  window=window)
    want = _per_head(q.astype(np.float64), k[layer].astype(np.float64),
                     v[layer].astype(np.float64), k_pos, q_pos, window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
