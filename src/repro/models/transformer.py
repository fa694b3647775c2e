"""Decoder LM assembly: pattern-cycled blocks (attn / local_attn / mla /
rglru / ssd mixers × ffn / moe / none), scan-over-layers with remat,
token or embedding inputs (llava), MTP head (DeepSeek-V3), cached decode.

Layer streaming: ``n_layers`` decomposes into

    [prefix]  unrolled first-k layers (DeepSeek's dense-FFN warmup layers)
    [cycles]  jax.lax.scan over repetitions of the arch's mixer pattern —
              one compiled block per pattern position, params stacked over
              cycles (compile time & HLO size stay O(pattern), not O(L))
    [tail]    unrolled leftover layers when the pattern doesn't divide

The same decomposition drives init, logical axes, cache init and decode.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import structures
from repro.core.structures import make_linear
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ops
from repro.models.rglru import (RGLRUSpec, make_rglru, rglru_apply, rglru_axes,
                                rglru_cache_axes, rglru_cache_init,
                                rglru_cache_rollback, rglru_init,
                                rglru_prefill, rglru_prestack, rglru_quantize)
from repro.models.ssd import (SSDSpec, make_ssd, ssd_apply, ssd_axes,
                              ssd_cache_axes, ssd_cache_init,
                              ssd_cache_rollback, ssd_init, ssd_prefill,
                              ssd_quantize)
from repro.parallel import Parallel, NO_PARALLEL
from repro import quant as qt
from repro.quant import QuantConfig

Params = dict[str, Any]


class Output(NamedTuple):
    logits: jax.Array
    aux: jax.Array              # MoE load-balance loss (0 for non-MoE)
    mtp_logits: jax.Array | None = None


# ---------------------------------------------------------------------------
# Block: one residual layer = mixer (+ optional cross-attn) (+ ffn/moe).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    kind: str                   # attn | local_attn | mla | rglru | ssd
    mixer: Any
    ffn: Any | None
    ffn_kind: str               # ffn | moe | none
    norm: str
    cross: L.AttnSpec | None = None
    eps: float = 1e-6           # RMSNorm epsilon
    residual: float = 1.0       # each branch is added times this

    def add(self, x: jax.Array, branch: jax.Array) -> jax.Array:
        """The residual add; a multiplier of 1 adds no operation."""
        return x + (branch if self.residual == 1.0 else branch * self.residual)


def make_block(cfg: ArchConfig, kind: str, *, moe_layer: bool = False,
               dense_ff_width: int = 0, causal: bool = True,
               cross: bool = False) -> BlockSpec:
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else None
        mixer = L.make_attention(cfg, window=window, causal=causal)
    elif kind == "mla":
        mixer = L.make_mla(cfg)
    elif kind == "rglru":
        mixer = make_rglru(cfg)
    elif kind == "ssd":
        mixer = make_ssd(cfg)
    else:
        raise ValueError(kind)
    if moe_layer:
        ffn, ffn_kind = moe_lib.make_moe(cfg), "moe"
    else:
        width = dense_ff_width or cfg.d_ff
        if width:
            ffn = L.make_ffn(cfg.d_model, width, cfg.ffn_kind, cfg.ffn_structure)
            ffn_kind = "ffn"
        else:
            ffn, ffn_kind = None, "none"
    xspec = L.make_attention(cfg, cross=True) if cross else None
    return BlockSpec(kind=kind, mixer=mixer, ffn=ffn, ffn_kind=ffn_kind,
                     norm=cfg.norm, cross=xspec, eps=cfg.rms_norm_eps,
                     residual=cfg.residual_multiplier)


def block_init(spec: BlockSpec, key, dtype, d_model: int) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if spec.kind in ("attn", "local_attn"):
        mixer = L.attn_init(spec.mixer, k1, dtype)
    elif spec.kind == "mla":
        mixer = L.mla_init(spec.mixer, k1, dtype)
    elif spec.kind == "rglru":
        mixer = rglru_init(spec.mixer, k1, dtype)
    else:
        mixer = ssd_init(spec.mixer, k1, dtype)
    p: Params = {"norm1": L.norm_init(d_model, spec.norm, dtype), "mixer": mixer}
    if spec.cross is not None:
        p["norm_x"] = L.norm_init(d_model, spec.norm, dtype)
        p["cross"] = L.attn_init(spec.cross, k4, dtype)
    if spec.ffn_kind == "moe":
        p["norm2"] = L.norm_init(d_model, spec.norm, dtype)
        p["ffn"] = moe_lib.moe_init(spec.ffn, k2, dtype)
    elif spec.ffn_kind == "ffn":
        p["norm2"] = L.norm_init(d_model, spec.norm, dtype)
        p["ffn"] = L.ffn_init(spec.ffn, k2, dtype)
    return p


def block_axes(spec: BlockSpec) -> dict:
    if spec.kind in ("attn", "local_attn"):
        mixer = L.attn_axes(spec.mixer)
    elif spec.kind == "mla":
        mixer = L.mla_axes(spec.mixer)
    elif spec.kind == "rglru":
        mixer = rglru_axes(spec.mixer)
    else:
        mixer = ssd_axes(spec.mixer)
    a = {"norm1": L.norm_axes(spec.norm), "mixer": mixer}
    if spec.cross is not None:
        a["norm_x"] = L.norm_axes(spec.norm)
        a["cross"] = L.attn_axes(spec.cross)
    if spec.ffn_kind == "moe":
        a["norm2"] = L.norm_axes(spec.norm)
        a["ffn"] = moe_lib.moe_axes(spec.ffn)
    elif spec.ffn_kind == "ffn":
        a["norm2"] = L.norm_axes(spec.norm)
        a["ffn"] = L.ffn_axes(spec.ffn)
    return a


def block_quantize(spec: BlockSpec, params: Params, bits: int = 8) -> Params:
    """Quantize a block's structured linears to per-block QArrays (norms
    pass through).  Mirrors ``block_axes``' dispatch over mixer kinds."""
    if spec.kind in ("attn", "local_attn"):
        mixer = L.attn_quantize(spec.mixer, params["mixer"], bits)
    elif spec.kind == "mla":
        mixer = L.mla_quantize(spec.mixer, params["mixer"], bits)
    elif spec.kind == "rglru":
        mixer = rglru_quantize(spec.mixer, params["mixer"], bits)
    else:
        mixer = ssd_quantize(spec.mixer, params["mixer"], bits)
    p = dict(params)
    p["mixer"] = mixer
    if spec.cross is not None:
        p["cross"] = L.attn_quantize(spec.cross, params["cross"], bits)
    if spec.ffn_kind == "moe":
        p["ffn"] = moe_lib.moe_quantize(spec.ffn, params["ffn"], bits)
    elif spec.ffn_kind == "ffn":
        p["ffn"] = L.ffn_quantize(spec.ffn, params["ffn"], bits)
    return p


def block_linear_specs(spec: BlockSpec) -> list:
    """Every structured LinearSpec one block dispatches per step (mixer,
    cross-attention, FFN / MoE experts + shared expert) — the shape registry
    the serving engine feeds to the kernel autotuner."""
    mx = spec.mixer
    if spec.kind in ("attn", "local_attn"):
        specs = [mx.qkv, mx.out]
    elif spec.kind == "mla":
        specs = [mx.wq_a, mx.wq_b, mx.wkv_a, mx.wkv_b, mx.out]
    elif spec.kind == "rglru":
        specs = [mx.in_x, mx.in_gate, mx.out, mx.gate_a, mx.gate_x]
    else:
        specs = [mx.in_proj, mx.out_proj]
    if spec.cross is not None:
        specs += [spec.cross.qkv, spec.cross.out]
    if spec.ffn_kind == "moe":
        specs += [spec.ffn.wi, spec.ffn.wo]
        if spec.ffn.shared is not None:
            specs += [*spec.ffn.shared.in_specs, spec.ffn.shared.wo]
    elif spec.ffn_kind == "ffn":
        specs += [*spec.ffn.in_specs, spec.ffn.wo]
    return specs


def block_apply(spec: BlockSpec, params: Params, x: jax.Array,
                positions: jax.Array, parallel: Parallel,
                memory: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    h = L.norm_apply(params["norm1"], x, spec.norm, spec.eps)
    if spec.kind in ("attn", "local_attn"):
        m = L.attn_apply(spec.mixer, params["mixer"], h, positions, parallel)
    elif spec.kind == "mla":
        m = L.mla_apply(spec.mixer, params["mixer"], h, positions, parallel)
    elif spec.kind == "rglru":
        m = rglru_apply(spec.mixer, params["mixer"], h, positions, parallel)
    else:
        m = ssd_apply(spec.mixer, params["mixer"], h, positions, parallel)
    x = spec.add(x, m)
    if spec.cross is not None:
        h = L.norm_apply(params["norm_x"], x, spec.norm, spec.eps)
        x = spec.add(x, L.attn_apply(spec.cross, params["cross"], h,
                                     positions, parallel, memory=memory))
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn_kind == "moe":
        h = L.norm_apply(params["norm2"], x, spec.norm, spec.eps)
        f, aux = moe_lib.moe_apply(spec.ffn, params["ffn"], h, parallel)
        x = spec.add(x, f)
    elif spec.ffn_kind == "ffn":
        h = L.norm_apply(params["norm2"], x, spec.norm, spec.eps)
        x = spec.add(x, L.ffn_apply(spec.ffn, params["ffn"], h, parallel))
    # MaxText-style layer-boundary constraint: the residual stream re-enters
    # each block batch-sharded / d-replicated, so GSPMD never speculatively
    # leaves a TP partial-sum layout to flow across blocks
    return parallel.shard_batch(x), aux


def block_cache_init(spec: BlockSpec, batch: int, max_len: int, dtype) -> Params:
    if spec.kind in ("attn", "local_attn"):
        c = {"mixer": L.attn_cache_init(spec.mixer, batch, max_len, dtype)}
    elif spec.kind == "mla":
        c = {"mixer": L.mla_cache_init(spec.mixer, batch, max_len, dtype)}
    elif spec.kind == "rglru":
        c = {"mixer": rglru_cache_init(spec.mixer, batch, max_len, dtype)}
    else:
        c = {"mixer": ssd_cache_init(spec.mixer, batch, max_len, dtype)}
    if spec.cross is not None:
        # placeholder; filled by cross_memory_cache at prefill/encode time
        hq, hkv, hd = spec.cross.dims
        n_mem = 1  # overwritten with real memory length by encdec
        c["cross"] = {"k": jnp.zeros((batch, n_mem, hkv * hd), dtype),
                      "v": jnp.zeros((batch, n_mem, hkv * hd), dtype),
                      "pos": jnp.zeros((n_mem,), jnp.int32)}
    return c


def block_cache_axes(spec: BlockSpec) -> dict:
    if spec.kind in ("attn", "local_attn"):
        a = {"mixer": L.attn_cache_axes(spec.mixer)}
    elif spec.kind == "mla":
        a = {"mixer": L.mla_cache_axes(spec.mixer)}
    elif spec.kind == "rglru":
        a = {"mixer": rglru_cache_axes(spec.mixer)}
    else:
        a = {"mixer": ssd_cache_axes(spec.mixer)}
    if spec.cross is not None:
        a["cross"] = L.attn_cache_axes(spec.cross)
    return a


def block_prefill(spec: BlockSpec, params: Params, cache: Params, x: jax.Array,
                  steps: jax.Array, n_tokens: jax.Array, parallel: Parallel,
                  collect: bool = False, *, layer: jax.Array | None = None
                  ) -> tuple[jax.Array, Params]:
    """Multi-token cached step.  x: (B, C, d); steps/n_tokens: (B,) per-slot
    offsets and live token counts (ragged rows — see the mixer prefills).
    ``collect=True`` makes the recurrent mixers (SSD / RG-LRU) return
    per-token state snapshots in their cache for speculative rollback (the
    KV families rewind by position and need no snapshots).

    ``layer``: the leaves whose axes carry ``kv_seq`` (the attention and MLA
    caches) are the model's stacked (L, …) leaves and ``layer`` is this
    block's index in them; they are written in place and returned stacked.
    The other leaves (recurrent state) are this layer's own."""
    h = L.norm_apply(params["norm1"], x, spec.norm, spec.eps)
    new_cache = dict(cache)
    if spec.kind in ("attn", "local_attn"):
        m, new_cache["mixer"] = L.attn_prefill(
            spec.mixer, params["mixer"], cache["mixer"], h, steps, n_tokens,
            parallel, layer=layer)
    elif spec.kind == "mla":
        m, new_cache["mixer"] = L.mla_prefill(
            spec.mixer, params["mixer"], cache["mixer"], h, steps, n_tokens,
            parallel, layer=layer)
    elif spec.kind == "rglru":
        m, new_cache["mixer"] = rglru_prefill(
            spec.mixer, params["mixer"], cache["mixer"], h, steps, n_tokens,
            parallel, collect=collect)
    else:
        m, new_cache["mixer"] = ssd_prefill(
            spec.mixer, params["mixer"], cache["mixer"], h, steps, n_tokens,
            parallel, collect=collect)
    x = spec.add(x, m)
    if spec.cross is not None:
        h = L.norm_apply(params["norm_x"], x, spec.norm, spec.eps)
        m, _ = L.attn_prefill(spec.cross, params["cross"], cache["cross"], h,
                              steps, n_tokens, parallel, layer=layer)
        x = spec.add(x, m)
    if spec.ffn_kind == "moe":
        h = L.norm_apply(params["norm2"], x, spec.norm, spec.eps)
        f, _ = moe_lib.moe_apply(spec.ffn, params["ffn"], h, parallel)
        x = spec.add(x, f)
    elif spec.ffn_kind == "ffn":
        h = L.norm_apply(params["norm2"], x, spec.norm, spec.eps)
        x = spec.add(x, L.ffn_apply(spec.ffn, params["ffn"], h, parallel))
    return parallel.shard_batch(x), new_cache


def block_decode(spec: BlockSpec, params: Params, cache: Params, x: jax.Array,
                 step: jax.Array, parallel: Parallel
                 ) -> tuple[jax.Array, Params]:
    """Single-token cached step — ``block_prefill`` with C=1."""
    B = x.shape[0]
    step = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (B,))
    return block_prefill(spec, params, cache, x, step,
                         jnp.ones((B,), jnp.int32), parallel)


def block_cache_rollback(spec: BlockSpec, old: Params, new: Params,
                         steps: jax.Array, n_comm: jax.Array) -> Params:
    """Rewind one block's cache after a speculative verify chunk: keep the
    first ``n_comm`` tokens written at ``steps``, revert the rest.  ``old``
    is the pre-verify cache (needed by the KV families — a ring-buffer write
    from a rejected draft may have clobbered a still-live slot); ``new`` is
    the verify chunk's ``collect_states=True`` output (carries the recurrent
    families' snapshots).  The result drops the snapshot leaves, matching
    the ``block_cache_init`` tree."""
    out = dict(new)  # cross-attn memories are static; pass through
    if spec.kind in ("attn", "local_attn", "mla"):
        out["mixer"] = L.kv_cache_rollback(old["mixer"], new["mixer"],
                                           steps, n_comm)
    elif spec.kind == "rglru":
        out["mixer"] = rglru_cache_rollback(spec.mixer, new["mixer"], n_comm)
    else:
        out["mixer"] = ssd_cache_rollback(spec.mixer, new["mixer"], n_comm)
    return out


def block_prestack(spec: BlockSpec, params: Params) -> Params:
    """Pre-stack every grouped projection bundle a block dispatches (MLA
    a-projections, RG-LRU in/gate pairs, SwiGLU gate+up incl. the MoE shared
    expert) once at engine load — see ``structures.prestack``."""
    p = dict(params)
    if spec.kind == "mla":
        p["mixer"] = L.mla_prestack(spec.mixer, params["mixer"])
    elif spec.kind == "rglru":
        p["mixer"] = rglru_prestack(spec.mixer, params["mixer"])
    if spec.ffn_kind == "moe":
        p["ffn"] = moe_lib.moe_prestack(spec.ffn, params["ffn"])
    elif spec.ffn_kind == "ffn":
        p["ffn"] = L.ffn_prestack(spec.ffn, params["ffn"])
    return p


# -- nested-rank draft models (self-speculative decoding) --------------------


def _is_rank_linear(t) -> bool:
    return structures.rank_kind(t) is not None


def _vmap_depth(lin: Params) -> int:
    """Leading stacked axes on a rank-bearing linear's factors (0 normally,
    1 for vmap-stacked MoE expert params)."""
    probe = lin["U"] if "U" in lin else lin["w_down"]
    base = 3 if "U" in lin else 2
    return len(probe.shape) - base


def _collect_spectra(tree, path: str = "") -> dict:
    """path → rank_spectrum for every rank-bearing linear in a params tree.
    Stacked-expert linears vmap the spectrum and average over the expert
    axis (truncation must be uniform there to keep stacked shapes)."""
    if _is_rank_linear(tree):
        fn = structures.rank_spectrum
        for _ in range(_vmap_depth(tree)):
            fn = jax.vmap(fn)
        e = fn(tree)
        while e.ndim > 1:
            e = jnp.mean(e, axis=0)
        return {path: e}
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_collect_spectra(v, f"{path}.{k}" if path else k))
        return out
    return {}


def _truncate_tree(tree, plan: dict, path: str = ""):
    """Apply a {path: r'} truncation plan to a params tree (stacked-expert
    linears truncate under vmap: per-expert component choices, uniform r')."""
    if _is_rank_linear(tree):
        r = plan.get(path)
        if r is None:
            return tree
        fn = lambda p: structures.truncate_rank(p, r)
        for _ in range(_vmap_depth(tree)):
            fn = jax.vmap(fn)
        return fn(tree)
    if isinstance(tree, dict):
        return {k: _truncate_tree(v, plan, f"{path}.{k}" if path else k)
                for k, v in tree.items()}
    return tree


def _is_axes(t) -> bool:
    return isinstance(t, tuple)


def _split(tree: Params, mask) -> tuple[Params, Params]:
    """Split a nested cache dict by a bool tree of the same nesting into
    (leaves where True, the rest), both keeping the nesting.  Keys the mask
    does not name (speculative state snapshots) go to the rest."""
    yes: Params = {}
    no: Params = {}
    for k, v in tree.items():
        m = mask.get(k, False)
        if isinstance(v, dict):
            yes[k], no[k] = _split(v, m if isinstance(m, dict) else {})
        elif m is True:
            yes[k] = v
        else:
            no[k] = v
    return yes, no


def _join(a: Params, b: Params) -> Params:
    """Inverse of ``_split``."""
    out = dict(b)
    for k, v in a.items():
        out[k] = _join(v, b.get(k, {})) if isinstance(v, dict) else v
    return out


# ---------------------------------------------------------------------------
# The language model.
# ---------------------------------------------------------------------------


class LM:
    """Decoder-only LM over any ArchConfig (all assigned non-enc-dec archs)."""

    def __init__(self, cfg: ArchConfig, parallel: Parallel = NO_PARALLEL):
        self.cfg = cfg
        self.parallel = parallel
        self.dtype = jnp.dtype(cfg.param_dtype)
        kinds = cfg.layer_kinds()
        n_prefix = cfg.moe.first_dense_layers if cfg.moe else 0
        self.prefix_specs = [
            make_block(cfg, kinds[i], dense_ff_width=cfg.moe.dense_d_ff)
            for i in range(n_prefix)]
        rest = kinds[n_prefix:]
        plen = len(cfg.pattern)
        self.n_cycles = len(rest) // plen if cfg.scan_layers else 0
        cyc, tail = rest[: self.n_cycles * plen], rest[self.n_cycles * plen:]
        if self.n_cycles:
            template = cyc[:plen]
            assert all(cyc[i * plen:(i + 1) * plen] == template
                       for i in range(self.n_cycles)), "pattern must tile"
            self.cycle_specs = [make_block(cfg, k, moe_layer=bool(cfg.moe))
                                for k in template]
        else:
            self.cycle_specs = []
            tail = rest
        self.tail_specs = [make_block(cfg, k, moe_layer=bool(cfg.moe))
                           for k in tail]
        self.head = make_linear(cfg.d_model, cfg.vocab, structured=False)
        if cfg.mtp:
            self.mtp_proj = make_linear(2 * cfg.d_model, cfg.d_model,
                                        structured=False)
            self.mtp_spec = make_block(cfg, kinds[-1], moe_layer=bool(cfg.moe))

    # -- init / axes ---------------------------------------------------------

    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        # a scaled embedding enters the first layer at 0.02; unscaled, a
        # tied head makes a random-weight model repeat its input token
        params: Params = {
            "embed": (0.02 / cfg.embedding_multiplier * jax.random.normal(
                keys[0], (cfg.vocab, cfg.d_model))).astype(self.dtype),
            "final_norm": L.norm_init(cfg.d_model, cfg.norm, self.dtype),
        }
        if cfg.pos_embed == "learned":
            params["pos"] = (0.02 * jax.random.normal(
                keys[7], (cfg.max_seq, cfg.d_model))).astype(self.dtype)
        if not cfg.tie_embeddings:
            params["head"] = L.linear_init(
                self.head, keys[1], self.dtype, scale=0.02)
        for i, spec in enumerate(self.prefix_specs):
            params[f"pre_{i}"] = block_init(
                spec, jax.random.fold_in(keys[2], i), self.dtype, cfg.d_model)
        if self.n_cycles:
            def cycle_init(k):
                return {f"blk_{j}": block_init(
                    spec, jax.random.fold_in(k, j), self.dtype, cfg.d_model)
                    for j, spec in enumerate(self.cycle_specs)}
            params["cycles"] = jax.vmap(cycle_init)(
                jax.random.split(keys[3], self.n_cycles))
        for i, spec in enumerate(self.tail_specs):
            params[f"tail_{i}"] = block_init(
                spec, jax.random.fold_in(keys[4], i), self.dtype, cfg.d_model)
        if cfg.mtp:
            params["mtp"] = {
                "proj": L.linear_init(self.mtp_proj, keys[5], self.dtype),
                "norm": L.norm_init(cfg.d_model, cfg.norm, self.dtype),
                "block": block_init(self.mtp_spec, keys[6], self.dtype,
                                    cfg.d_model),
            }
        return params

    def axes(self) -> dict:
        cfg = self.cfg
        a: dict = {
            "embed": ("vocab", "embed"),
            "final_norm": L.norm_axes(cfg.norm),
        }
        if cfg.pos_embed == "learned":
            a["pos"] = (None, "embed")
        if not cfg.tie_embeddings:
            a["head"] = {"w": ("embed", "vocab")}
        for i, spec in enumerate(self.prefix_specs):
            a[f"pre_{i}"] = block_axes(spec)
        if self.n_cycles:
            cyc = {f"blk_{j}": block_axes(spec)
                   for j, spec in enumerate(self.cycle_specs)}
            a["cycles"] = jax.tree.map(
                lambda ax: ("layers",) + ax, cyc,
                is_leaf=_is_axes)
        for i, spec in enumerate(self.tail_specs):
            a[f"tail_{i}"] = block_axes(spec)
        if cfg.mtp:
            a["mtp"] = {"proj": {"w": (None, None)},
                        "norm": L.norm_axes(cfg.norm),
                        "block": block_axes(self.mtp_spec)}
        return a

    def quantize_params(self, params: Params, quant: QuantConfig) -> Params:
        """Quantize-at-load: every structured linear (and the untied vocab
        head) becomes a per-block QArray; embeddings and norms stay float.
        Scan-stacked cycle params quantize under vmap — the per-cycle
        QArray trees stack on the layers axis like any other params."""
        bits = quant.weight_bits
        if bits is None:
            return params
        cfg = self.cfg
        qp = dict(params)
        # per-row embedding quantization: the gather and the tied head both
        # fuse the per-row scale (embed_lookup / tied_logits)
        qp["embed"] = qt.quantize(params["embed"], bits=bits, block_axes=(1,))
        if not cfg.tie_embeddings:
            qp["head"] = L.linear_quantize(self.head, params["head"], bits)
        for i, spec in enumerate(self.prefix_specs):
            qp[f"pre_{i}"] = block_quantize(spec, params[f"pre_{i}"], bits)
        if self.n_cycles:
            def cycle_quantize(p):
                return {f"blk_{j}": block_quantize(spec, p[f"blk_{j}"], bits)
                        for j, spec in enumerate(self.cycle_specs)}
            qp["cycles"] = jax.vmap(cycle_quantize)(params["cycles"])
        for i, spec in enumerate(self.tail_specs):
            qp[f"tail_{i}"] = block_quantize(spec, params[f"tail_{i}"], bits)
        if cfg.mtp:
            qp["mtp"] = {
                "proj": L.linear_quantize(self.mtp_proj,
                                          params["mtp"]["proj"], bits),
                "norm": params["mtp"]["norm"],
                "block": block_quantize(self.mtp_spec,
                                        params["mtp"]["block"], bits),
            }
        return qp

    def prestack_params(self, params: Params) -> Params:
        """Pre-stack every grouped projection bundle once at load: the
        stacked factor arrays ride inside the param tree as ``GroupBundle``
        pytrees, and the per-step grouped apply skips its pad+stack work
        (``structures.stack_count`` stays 0 per step).  Run this LAST —
        after quantization and any rank truncation — since both change the
        factors a bundle caches (a stale bundle is ignored, not wrong)."""
        pp = dict(params)
        for i, spec in enumerate(self.prefix_specs):
            pp[f"pre_{i}"] = block_prestack(spec, params[f"pre_{i}"])
        if self.n_cycles:
            def one(p):
                return {f"blk_{j}": block_prestack(spec, p[f"blk_{j}"])
                        for j, spec in enumerate(self.cycle_specs)}
            pp["cycles"] = jax.vmap(one)(params["cycles"])
        for i, spec in enumerate(self.tail_specs):
            pp[f"tail_{i}"] = block_prestack(spec, params[f"tail_{i}"])
        return pp

    # -- nested-rank drafts (self-speculative decoding) ----------------------

    def rank_spectra(self, params: Params) -> dict:
        """name → per-component energy spectrum for every rank-bearing
        linear (blast / low_rank).  Scan-stacked cycles average over the
        layer axis (one pattern-position spec serves all cycles, so its
        truncated rank must be uniform); MoE experts likewise."""
        rest = {k: v for k, v in params.items() if k != "cycles"}
        out = _collect_spectra(rest)
        if "cycles" in params:
            cyc = jax.vmap(
                lambda p: _collect_spectra({"cycles": p}))(params["cycles"])
            out.update({k: jnp.mean(v, axis=0) for k, v in cyc.items()})
        return out

    def draft_plan(self, params: Params, frac: float) -> dict:
        """Calibrate per-layer draft ranks from the factor spectra: keep the
        globally highest-energy ~``frac`` of the pooled rank budget (see
        ``core/compress.py::calibrate_ranks``).  Eager (numpy) — run once at
        engine load."""
        from repro.core.compress import calibrate_ranks
        spectra = jax.jit(self.rank_spectra)(params)
        return calibrate_ranks(
            {k: np.asarray(v) for k, v in spectra.items()}, frac)

    def truncate_params(self, params: Params, plan: dict) -> Params:
        """Build the draft model: truncate every planned linear to its r'.
        Shares no new weight storage conceptually — the draft factors are
        column subsets of the full ones (the paper's nesting property); the
        unmodified apply paths read ranks from array shapes."""
        out = _truncate_tree(
            {k: v for k, v in params.items() if k != "cycles"}, plan)
        if "cycles" in params:
            out["cycles"] = jax.vmap(
                lambda p: _truncate_tree({"cycles": p}, plan)["cycles"]
            )(params["cycles"])
        return out

    def rollback_cache(self, old: Params, new: Params, steps: jax.Array,
                       n_comm: jax.Array) -> Params:
        """Rewind a ``collect_states=True`` verify chunk to its first
        ``n_comm[b]`` tokens per row — bit-identical to having fed exactly
        those tokens.  ``old`` is the pre-verify cache; the result matches
        the ``init_cache`` tree (snapshots dropped)."""
        steps = jnp.asarray(steps, jnp.int32)
        n_comm = jnp.asarray(n_comm, jnp.int32)
        out: Params = {}
        for i, spec in enumerate(self.prefix_specs):
            out[f"pre_{i}"] = block_cache_rollback(
                spec, old[f"pre_{i}"], new[f"pre_{i}"], steps, n_comm)
        if self.n_cycles:
            def roll(oc, nc):
                return {f"blk_{j}": block_cache_rollback(
                    spec, oc[f"blk_{j}"], nc[f"blk_{j}"], steps, n_comm)
                    for j, spec in enumerate(self.cycle_specs)}
            out["cycles"] = jax.vmap(roll)(old["cycles"], new["cycles"])
        for i, spec in enumerate(self.tail_specs):
            out[f"tail_{i}"] = block_cache_rollback(
                spec, old[f"tail_{i}"], new[f"tail_{i}"], steps, n_comm)
        return out

    def linear_specs(self) -> list:
        """All structured LinearSpecs the model dispatches (layer-unique:
        scan cycles contribute one copy per pattern position).  Consumed by
        ``serve/engine.py`` to warm the kernel autotune cache at build."""
        specs = []
        for s in (*self.prefix_specs, *self.cycle_specs, *self.tail_specs):
            specs += block_linear_specs(s)
        if not self.cfg.tie_embeddings:
            specs.append(self.head)
        if self.cfg.mtp:
            specs += [self.mtp_proj, *block_linear_specs(self.mtp_spec)]
        return specs

    # -- forward --------------------------------------------------------------

    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        x = L.embed_lookup(params["embed"], tokens, self.dtype,
                           self.parallel)
        if self.cfg.embed_scale:
            x = x * jnp.sqrt(float(self.cfg.d_model)).astype(x.dtype)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * self.cfg.embedding_multiplier
        return x

    def _head(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = L.norm_apply(params["final_norm"], x, cfg.norm, cfg.rms_norm_eps)
        if cfg.tie_embeddings:
            logits = L.tied_logits(params["embed"], x)
        else:
            logits = L.linear_apply(self.head, params["head"], x)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        logits = self.parallel.constraint(
            logits, self.parallel.batch_spec(None, self.parallel.model_axis))
        return ops.softcap(logits, cfg.logit_softcap)

    def apply(self, params: Params, tokens: jax.Array | None = None,
              embeds: jax.Array | None = None, *,
              last_only: bool = False) -> Output:
        """Full-sequence forward (training / prefill).

        tokens: (B, T) int32 — or embeds: (B, T, d) for stub-frontend archs.
        ``last_only`` projects logits for the final position only (serving
        prefill: no point computing a 32k×V logit tensor to sample 1 token).
        """
        cfg, parallel = self.cfg, self.parallel
        if embeds is None:
            x = self._embed(params, tokens)
        else:
            x = embeds.astype(self.dtype)
        T = x.shape[1]
        if cfg.pos_embed == "learned":
            x = x + params["pos"][:T][None]
        elif cfg.pos_embed == "sinusoidal":
            x = x + ops.sinusoidal_positions(T, cfg.d_model).astype(x.dtype)[None]
        x = parallel.shard_batch(x)
        positions = jnp.arange(T)
        aux = jnp.zeros((), jnp.float32)

        for i, spec in enumerate(self.prefix_specs):
            x, a = block_apply(spec, params[f"pre_{i}"], x, positions, parallel)
            aux += a

        if self.n_cycles:
            def cycle(x, p):
                a_tot = jnp.zeros((), jnp.float32)
                for j, spec in enumerate(self.cycle_specs):
                    x, a = block_apply(spec, p[f"blk_{j}"], x, positions, parallel)
                    a_tot += a
                return x, a_tot
            if cfg.remat:
                cycle = jax.checkpoint(cycle)
            x, auxs = jax.lax.scan(cycle, x, params["cycles"])
            aux += jnp.sum(auxs)

        for i, spec in enumerate(self.tail_specs):
            x, a = block_apply(spec, params[f"tail_{i}"], x, positions, parallel)
            aux += a

        logits = self._head(params, x[:, -1:] if last_only else x)

        mtp_logits = None
        if cfg.mtp and tokens is not None and not last_only:
            # DeepSeek-V3 MTP: one extra block predicting token t+2 from
            # (h_t, embed(t+1)); lm_head shared.
            nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
            h = jnp.concatenate(
                [L.norm_apply(params["mtp"]["norm"], x, cfg.norm,
                              cfg.rms_norm_eps),
                 self._embed(params, nxt)], axis=-1)
            h = L.linear_apply(self.mtp_proj, params["mtp"]["proj"], h)
            h, _ = block_apply(self.mtp_spec, params["mtp"]["block"], h,
                               positions, parallel)
            mtp_logits = self._head(params, h)
        return Output(logits=logits, aux=aux, mtp_logits=mtp_logits)

    # -- cached decode ---------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> Params:
        dtype = jnp.dtype(self.cfg.compute_dtype)
        cache: Params = {}
        for i, spec in enumerate(self.prefix_specs):
            cache[f"pre_{i}"] = block_cache_init(spec, batch, max_len, dtype)
        if self.n_cycles:
            def one(_):
                return {f"blk_{j}": block_cache_init(spec, batch, max_len, dtype)
                        for j, spec in enumerate(self.cycle_specs)}
            cache["cycles"] = jax.vmap(one)(jnp.arange(self.n_cycles))
        for i, spec in enumerate(self.tail_specs):
            cache[f"tail_{i}"] = block_cache_init(spec, batch, max_len, dtype)
        return cache

    def cache_axes(self) -> dict:
        a: dict = {}
        for i, spec in enumerate(self.prefix_specs):
            a[f"pre_{i}"] = block_cache_axes(spec)
        if self.n_cycles:
            cyc = {f"blk_{j}": block_cache_axes(spec)
                   for j, spec in enumerate(self.cycle_specs)}
            a["cycles"] = jax.tree.map(
                lambda ax: ("layers",) + ax, cyc,
                is_leaf=_is_axes)
        for i, spec in enumerate(self.tail_specs):
            a[f"tail_{i}"] = block_cache_axes(spec)
        return a

    def prefill_chunk(self, params: Params, cache: Params, tokens: jax.Array,
                      steps: jax.Array, n_tokens: jax.Array | None = None,
                      *, all_logits: bool = False, collect_states: bool = False
                      ) -> tuple[jax.Array, Params]:
        """Multi-token cached step — the unified serving entry point.

        tokens: (B, C) int32; steps: (B,) absolute position of each slot's
        first token; n_tokens: (B,) live tokens per row (defaults to C).
        Rows are ragged: row b consumes tokens[b, :n_tokens[b]], writing its
        KV/state caches at offsets steps[b]..steps[b]+n_tokens[b]; trailing
        columns are padding (no cache/state effect).  Returns
        (logits (B, 1, V), new cache) — the vocab head runs only on each
        row's final live column (serving samples exactly one token per row
        per step; projecting all C columns would waste ~C× head FLOPs).
        C=1 with n_tokens=1 is exactly a decode step, so one jitted instance
        per chunk width C serves mixed prefill+decode batches
        (chunked-prefill continuous batching).

        Speculative-verify knobs (both static): ``all_logits=True`` heads
        every column — logits (B, C, V), column i predicting the token at
        position steps+i+1 — so one call scores k drafts at once;
        ``collect_states=True`` adds per-token recurrent-state snapshots to
        the SSD / RG-LRU caches for ``rollback_cache``.
        """
        cfg, parallel = self.cfg, self.parallel
        B, C = tokens.shape
        steps = jnp.asarray(steps, jnp.int32)
        if n_tokens is None:
            n_tokens = jnp.full((B,), C, jnp.int32)
        n_tokens = jnp.asarray(n_tokens, jnp.int32)
        with jax.named_scope("embed"):
            x = self._embed(params, tokens)
            if cfg.pos_embed == "learned":
                q_pos = (steps[:, None]
                         + jnp.arange(C, dtype=jnp.int32)[None, :])
                x = x + params["pos"][jnp.clip(q_pos, 0, cfg.max_seq - 1)]
        x = parallel.shard_batch(x)
        new_cache: Params = {}
        for i, spec in enumerate(self.prefix_specs):
            x, new_cache[f"pre_{i}"] = block_prefill(
                spec, params[f"pre_{i}"], cache[f"pre_{i}"], x, steps,
                n_tokens, parallel, collect_states)
        if self.n_cycles:
            # The sequence-axis leaves ride the loop's carry, stacked, and
            # each layer writes its columns into them in place; the small
            # per-row state leaves are sliced in and stacked out per layer.
            seq = jax.tree.map(lambda ax: "kv_seq" in ax,
                               self.cache_axes()["cycles"], is_leaf=_is_axes)
            stacked, per_layer = _split(cache["cycles"], seq)

            def cycle(carry, pc):
                x, stacked, i = carry
                p, c = pc
                stacked, out = dict(stacked), {}
                for j, spec in enumerate(self.cycle_specs):
                    b = f"blk_{j}"
                    x, new = block_prefill(
                        spec, p[b], _join(stacked[b], c[b]), x, steps,
                        n_tokens, parallel, collect_states, layer=i)
                    stacked[b], out[b] = _split(new, seq[b])
                return (x, stacked, i + 1), out
            (x, stacked, _), per_layer = jax.lax.scan(
                cycle, (x, stacked, jnp.int32(0)),
                (params["cycles"], per_layer))
            new_cache["cycles"] = _join(stacked, per_layer)
        for i, spec in enumerate(self.tail_specs):
            x, new_cache[f"tail_{i}"] = block_prefill(
                spec, params[f"tail_{i}"], cache[f"tail_{i}"], x, steps,
                n_tokens, parallel, collect_states)
        with jax.named_scope("head"):
            if not all_logits:
                last = jnp.clip(n_tokens - 1, 0, C - 1)[:, None, None]
                x = jnp.take_along_axis(x, jnp.broadcast_to(
                    last, (B, 1, x.shape[-1])), axis=1)   # (B, 1, d)
            logits = self._head(params, x)
        return logits, new_cache

    def decode_step(self, params: Params, cache: Params, tokens: jax.Array,
                    step: jax.Array) -> tuple[jax.Array, Params]:
        """One decode step.  tokens: (B, 1) int32; step: scalar or (B,)
        positions.  Returns (logits (B, 1, V), new cache).  Thin wrapper:
        ``prefill_chunk`` with C=1."""
        B = tokens.shape[0]
        step = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (B,))
        return self.prefill_chunk(params, cache, tokens, step,
                                  jnp.ones((B,), jnp.int32))
