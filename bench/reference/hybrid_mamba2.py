"""Plain float32 reference for the ``hybrid_mamba2`` family: Mamba-2 mixers
and NoPE grouped-query attention layers in a repeating period, each layer
ending in a SwiGLU MLP, dense or BLAST linears, Granite's multipliers.

It imports nothing of the program.  It makes the weights itself from the
run's key with the draws the program's initialiser makes (embedding at
0.02 over the embedding multiplier, then layer i of period p from
``fold_in(split(fourth of eight keys, cycles)[i // p], i % p)``), rounded to
the configuration's parameter dtype; the BLAST and dense linears, causal
attention and the logits are those of ``dense_gqa``'s reference.  Every contraction runs in float32 at precision
"highest", one layer at a time over a batch of whole sequences.

A Mamba-2 layer, as published (Dao and Gu, 2024), token by token:

    z, xBC, dt = split(W_in · rms(u))
    xBC_t = silu(b + sum_k w_k · xBC_{t-K+1+k})        depthwise causal conv
    x, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t         h: (H, P, N)
    y_t = C_t · h_t + D x_t
    out = W_out · rms(y (*) silu(z))                     gated RMSNorm

``served_gaps`` returns, for each served token, how far its logit lies
below the reference's best logit at that position.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_gqa import (HI, Q_BLOCK, _attention, _linear,
                                       _logits, _rms)


def _dims(cfg: dict):
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, G = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return H, P, N, G, cfg["mamba_d_conv"]


def _embedding(cfg: dict, key) -> jax.Array:
    """The tied table, drawn at 0.02 over the embedding multiplier."""
    keys = jax.random.split(key, 8)
    e = (0.02 / cfg["embedding_multiplier"]
         * jax.random.normal(keys[0], (cfg["vocab_size"], cfg["hidden_size"])))
    return e.astype(jnp.dtype(cfg["param_dtype"])).astype(jnp.float32)


def _mlp_weights(cfg: dict, key) -> dict:
    d, ff = cfg["hidden_size"], cfg["shared_intermediate_size"]
    k_gate, k_down, k_up = jax.random.split(key, 3)
    return {"gate": _linear(cfg, k_gate, d, ff),
            "up": _linear(cfg, k_up, d, ff),
            "down": _linear(cfg, k_down, ff, d, scale=1.0 / math.sqrt(2 * ff))}


def _mamba_weights(cfg: dict, key) -> dict:
    d, dt_param = cfg["hidden_size"], jnp.dtype(cfg["param_dtype"])
    H, P, N, G, K = _dims(cfg)
    conv_ch = H * P + 2 * G * N
    k_mixer, k_mlp, _, _ = jax.random.split(key, 4)
    k_in, k_out, k_dt, k_w, k_b, k_a = jax.random.split(k_mixer, 6)
    dt = jnp.exp(jax.random.uniform(k_dt, (H,), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    bound = 1.0 / K ** 0.5
    rounded = lambda a: a.astype(dt_param).astype(jnp.float32)  # noqa: E731
    return {
        "in": _linear(cfg, k_in, d, 2 * H * P + 2 * G * N + H),
        "out": _linear(cfg, k_out, H * P, d),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "conv_w": rounded(jax.random.uniform(k_w, (K, conv_ch),
                                             minval=-bound, maxval=bound)),
        "conv_b": rounded(jax.random.uniform(k_b, (conv_ch,),
                                             minval=-bound, maxval=bound)),
        "A_log": jnp.log(jax.random.uniform(k_a, (H,), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((H,), jnp.float32),
        **_mlp_weights(cfg, k_mlp)}


def _attn_weights(cfg: dict, key) -> dict:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    k_mixer, k_mlp, _, _ = jax.random.split(key, 4)
    k_qkv, k_out = jax.random.split(k_mixer)
    return {"qkv": _linear(cfg, k_qkv, d, (hq + 2 * hkv) * hd),
            "out": _linear(cfg, k_out, hq * hd, d,
                           scale=1.0 / math.sqrt(2 * L * hq * hd)),
            **_mlp_weights(cfg, k_mlp)}


def _mlp(cfg: dict, W: dict, x):
    h = _rms(cfg, x)
    gate = jnp.matmul(h, W["gate"], precision=HI)
    up = jnp.matmul(h, W["up"], precision=HI)
    f = jnp.matmul(jax.nn.silu(gate) * up, W["down"], precision=HI)
    return x + cfg["residual_multiplier"] * f


def _mamba(cfg: dict, W: dict, u):
    """The Mamba-2 mixer over whole sequences u (S, T, d), token by token."""
    S, T, _ = u.shape
    H, P, N, G, K = _dims(cfg)
    zxbcdt = jnp.matmul(_rms(cfg, u), W["in"], precision=HI)
    z, xbc, dt = (zxbcdt[..., :H * P], zxbcdt[..., H * P:2 * H * P + 2 * G * N],
                  zxbcdt[..., 2 * H * P + 2 * G * N:])
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(W["conv_b"] + sum(W["conv_w"][k] * pad[:, k:k + T]
                                        for k in range(K)))
    x = xbc[..., :H * P].reshape(S, T, H, P)
    rep = lambda m: jnp.repeat(m.reshape(S, T, G, N), H // G, axis=2)  # noqa
    Bm, Cm = rep(xbc[..., H * P:H * P + G * N]), rep(xbc[..., H * P + G * N:])
    dt = jax.nn.softplus(dt + W["dt_bias"])                  # (S, T, H)
    a = jnp.exp(dt * -jnp.exp(W["A_log"]))

    def token(h, inp):
        a_t, dt_t, B_t, C_t, x_t = inp
        h = (a_t[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, :, None, :])
        return h, jnp.einsum("shn,shpn->shp", C_t, h, precision=HI)

    first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(token, jnp.zeros((S, H, P, N), jnp.float32),
                        tuple(map(first, (a, dt, Bm, Cm, x))))
    y = jnp.moveaxis(y, 0, 1) + W["D"][:, None] * x
    y = _rms(cfg, y.reshape(S, T, H * P) * jax.nn.silu(z))
    return jnp.matmul(y, W["out"], precision=HI)


def _nope_attention(cfg: dict, W: dict, u):
    S, T, _ = u.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    qkv = jnp.matmul(_rms(cfg, u), W["qkv"], precision=HI)
    q = qkv[..., :hq * hd].reshape(S, T, hq, hd)
    k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(S, T, hkv, hd)
    v = qkv[..., (hq + hkv) * hd:].reshape(S, T, hkv, hd)
    o = _attention(cfg, q, k, v).reshape(S, T, hq * hd)   # no rotation
    return jnp.matmul(o, W["out"], precision=HI)


@partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg_items, kind, key, x):
    cfg = _unhash(cfg_items)
    if kind == "mamba":
        W = _mamba_weights(cfg, key)
        x = x + cfg["residual_multiplier"] * _mamba(cfg, W, x)
    else:
        W = _attn_weights(cfg, key)
        x = x + cfg["residual_multiplier"] * _nope_attention(cfg, W, x)
    return _mlp(cfg, W, x)


@partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    cfg = _unhash(cfg_items)
    return _embedding(cfg, key)[tokens] * cfg["embedding_multiplier"]


def _layer_keys(cfg: dict, key):
    """(kind, key) of every layer, in order."""
    kinds = cfg["layer_types"]
    period = _period(kinds)
    cycles = jax.random.split(jax.random.split(key, 8)[3],
                              len(kinds) // period)
    return [(kind, jax.random.fold_in(cycles[i // period], i % period))
            for i, kind in enumerate(kinds)]


def _period(kinds: list) -> int:
    """The shortest period that tiles the layer types."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return p


def _hidden(cfg: dict, key, tokens):
    items = _hashable(cfg)
    x = _embed(items, key, jnp.asarray(tokens))
    for kind, lk in _layer_keys(cfg, key):
        x = _layer(items, kind, lk, x)
    return x


def logits(cfg: dict, key, tokens) -> np.ndarray:
    """Logits (S, T, vocab) of whole sequences ``tokens`` (S, T); for tests
    at small sizes."""
    x = _hidden(cfg, key, tokens)
    return np.asarray(_logits(cfg, _embedding(cfg, key), x))


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


def _hashable(cfg: dict) -> tuple:
    keep = ("hidden_size", "shared_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rms_norm_eps", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "param_dtype", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv")
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
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not give every layer")
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
        gaps = np.asarray(_gaps(items, key, x, jnp.asarray(targets)))
        for i, (prompt, served) in enumerate(chunk):
            n0 = len(prompt) - 1
            out.append(gaps[i, n0:n0 + len(served)])
    return out
