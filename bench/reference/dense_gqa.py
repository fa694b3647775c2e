"""Plain float32 reference for decoder LMs with grouped-query attention,
SwiGLU FFNs and dense or BLAST linears (configuration family ``dense_gqa``).

It imports nothing of the program.  It makes the weights itself from the
run's key, with the same random draws the program's initialiser makes
(embedding, then one key per layer folded from a split of the fourth of
eight keys), and rounds them to the configuration's parameter dtype, so
both sides hold the same numbers without either handing them to the other.
A BLAST linear is materialised as the dense matrix its factors define,
A[i-block, j-block] = U_i diag(s_ij) V_j^T, and applied as x A^T; every
contraction runs in float32 at precision "highest".  The model runs one
layer at a time over a batch of whole sequences, so that only one layer's
weights live at once.

``served_gaps`` returns, for each served token, how far its logit lies
below the reference's best logit at that position.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512      # query rows per attention / head block


def _blocks(d_in: int, d_out: int, b: int) -> int:
    bb = min(b, d_in, d_out)
    while bb > 1 and (d_in % bb or d_out % bb):
        bb -= 1
    return max(bb, 1)


def _rank(d_in: int, d_out: int, b: int, keep: float, align: int) -> int:
    r = int(keep * d_out * d_in // (d_out + d_in + b * b))
    if align > 1 and r >= 2 * align:
        r = (r // align) * align
    return max(r, 1)


def _linear(cfg: dict, key, d_in: int, d_out: int, scale=None) -> jax.Array:
    """The (d_in, d_out) float32 matrix of one linear, from its key."""
    st, dt = cfg["structure"], jnp.dtype(cfg["param_dtype"])
    if st["kind"] == "dense":
        std = scale if scale is not None else 1.0 / math.sqrt(d_in)
        w = (std * jax.random.normal(key, (d_in, d_out))).astype(dt)
        return w.astype(jnp.float32)
    if st["kind"] != "blast":
        raise ValueError(f"no reference for structure {st['kind']!r}")
    b = _blocks(d_in, d_out, st["b"])
    r = _rank(d_in, d_out, b, st["keep_ratio"], st.get("rank_align", 1))
    p, q = d_out // b, d_in // b
    ku, kv, ks = jax.random.split(key, 3)
    s_max = 2.0
    s_rms = s_max / math.sqrt(3.0)
    std = (1.0 / (d_in * r)) ** 0.25 / math.sqrt(s_rms)
    U = (std * jax.random.normal(ku, (b, p, r))).astype(dt).astype(jnp.float32)
    V = (std * jax.random.normal(kv, (b, q, r))).astype(dt).astype(jnp.float32)
    S = jax.random.uniform(ks, (b, b, r), minval=0.0,
                           maxval=s_max).astype(dt).astype(jnp.float32)
    A = jnp.einsum("ipr,ijr,jqr->ipjq", U, S, V, precision=HI)
    return A.reshape(d_out, d_in).T


def _layer_weights(cfg: dict, key) -> dict:
    d, ff, L = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_hidden_layers"])
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    k_attn, k_ffn, _, _ = jax.random.split(key, 4)
    k_qkv, k_out = jax.random.split(k_attn)
    k_gate, k_down, k_up = jax.random.split(k_ffn, 3)
    return {
        "qkv": _linear(cfg, k_qkv, d, (hq + 2 * hkv) * hd),
        "out": _linear(cfg, k_out, hq * hd, d,
                       scale=1.0 / math.sqrt(2 * L * hq * hd)),
        "gate": _linear(cfg, k_gate, d, ff),
        "up": _linear(cfg, k_up, d, ff),
        "down": _linear(cfg, k_down, ff, d, scale=1.0 / math.sqrt(2 * ff)),
    }


def _layer_keys(key, n_layers: int):
    keys = jax.random.split(key, 8)
    per = jax.random.split(keys[3], n_layers)
    return [jax.random.fold_in(per[i], 0) for i in range(n_layers)]


def _embedding(cfg: dict, key) -> jax.Array:
    keys = jax.random.split(key, 8)
    e = 0.02 * jax.random.normal(keys[0],
                                 (cfg["vocab_size"], cfg["hidden_size"]))
    return e.astype(jnp.dtype(cfg["param_dtype"])).astype(jnp.float32)


def _rms(cfg: dict, x):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + cfg["rms_norm_eps"])   # gain 1 (scale 0)


def _rope(cfg: dict, x, pos):
    """x (N, T, H, D); pos (T,)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(cfg["rope_theta"])
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg: dict, q, k, v):
    """Causal GQA over whole sequences, in blocks of query rows.
    q (N, T, Hq, D); k, v (N, T, Hkv, D)."""
    N, T, hq, hd = q.shape
    g = hq // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for t0 in range(0, T, Q_BLOCK):
        t1 = min(T, t0 + Q_BLOCK)
        s = jnp.einsum("nthd,nshd->nhts", q[:, t0:t1], k[:, :t1],
                       precision=HI) * cfg["attention_multiplier"]
        mask = jnp.arange(t1)[None, :] <= jnp.arange(t0, t1)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("nhts,nshd->nthd", p, v[:, :t1], precision=HI))
    return jnp.concatenate(outs, axis=1)


@partial(jax.jit, static_argnums=(0,))
def _layer(cfg_items, key, x):
    cfg = _unhash(cfg_items)
    W = _layer_weights(cfg, key)
    N, T, _ = x.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    res = cfg["residual_multiplier"]
    h = _rms(cfg, x)
    qkv = jnp.matmul(h, W["qkv"], precision=HI)
    q = qkv[..., :hq * hd].reshape(N, T, hq, hd)
    k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(N, T, hkv, hd)
    v = qkv[..., (hq + hkv) * hd:].reshape(N, T, hkv, hd)
    pos = jnp.arange(T)
    q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
    o = _attention(cfg, q, k, v).reshape(N, T, hq * hd)
    x = x + res * jnp.matmul(o, W["out"], precision=HI)
    h = _rms(cfg, x)
    gate = jnp.matmul(h, W["gate"], precision=HI)
    up = jnp.matmul(h, W["up"], precision=HI)
    f = jnp.matmul(jax.nn.silu(gate) * up, W["down"], precision=HI)
    return x + res * f


@partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    cfg = _unhash(cfg_items)
    return _embedding(cfg, key)[tokens] * cfg["embedding_multiplier"]


def _logits(cfg: dict, table, x):
    return jnp.einsum("ntd,vd->ntv", _rms(cfg, x), table,
                      precision=HI) / cfg["logits_scaling"]


@partial(jax.jit, static_argnums=(0,))
def _gaps(cfg_items, key, x, targets):
    """Best logit minus the target's logit at every position."""
    cfg = _unhash(cfg_items)
    table = _embedding(cfg, key)
    out = []
    for t0 in range(0, x.shape[1], Q_BLOCK):
        lg = _logits(cfg, table, x[:, t0:t0 + Q_BLOCK])
        tgt = jnp.take_along_axis(lg, targets[:, t0:t0 + Q_BLOCK, None], -1)
        out.append(lg.max(axis=-1) - tgt[..., 0])
    return jnp.concatenate(out, axis=1)


def _hidden(cfg: dict, key, tokens):
    items = _hashable(cfg)
    x = _embed(items, key, jnp.asarray(tokens))
    for lk in _layer_keys(key, cfg["num_hidden_layers"]):
        x = _layer(items, lk, x)
    return x


def logits(cfg: dict, key, tokens) -> np.ndarray:
    """Logits (N, T, vocab) of whole sequences ``tokens`` (N, T); for tests
    at small sizes."""
    x = _hidden(cfg, key, tokens)
    return np.asarray(_logits(cfg, _embedding(cfg, key), x))


def _hashable(cfg: dict) -> tuple:
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "param_dtype")
    items = [(k, cfg[k]) for k in keep]
    items.append(("structure", tuple(sorted(cfg["structure"].items()))))
    return tuple(items)


def _unhash(items: tuple) -> dict:
    cfg = dict(items)
    cfg["structure"] = dict(cfg["structure"])
    return cfg


def served_gaps(cfg: dict, key, sequences, *, length: int, rows: int
                ) -> list[np.ndarray]:
    """``sequences``: (prompt, served tokens) pairs.  Each is run whole:
    the input is the prompt and every served token but the last, padded to
    ``length`` positions; ``rows`` sequences go through each layer at once
    (padded with empty rows), so every call has one shape.  Returns one
    array per sequence: for each served token, the reference's best logit
    at that position minus the served token's logit (0 where the served
    token is the reference's greedy choice)."""
    items = _hashable(cfg)
    out = []
    for s0 in range(0, len(sequences), rows):
        chunk = sequences[s0:s0 + rows]
        tokens = np.zeros((rows, length), np.int32)
        targets = np.zeros((rows, length), np.int32)
        for i, (prompt, served) in enumerate(chunk):
            seq = list(prompt) + list(served)
            if len(seq) - 1 > length:
                raise ValueError(f"sequence of {len(seq) - 1} inputs does "
                                 f"not fit {length} positions")
            tokens[i, :len(seq) - 1] = seq[:-1]
            targets[i, :len(seq) - 1] = seq[1:]
        x = _hidden(cfg, key, tokens)
        gaps = np.asarray(_gaps(items, key, x,
                                jnp.asarray(targets)))
        for i, (prompt, served) in enumerate(chunk):
            n0 = len(prompt) - 1
            out.append(gaps[i, n0:n0 + len(served)])
    return out
