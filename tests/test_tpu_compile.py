"""Compile the main-path kernels and the serving step for a described TPU
v5e, without the chip.

Interpret mode (every other kernel test) accepts tilings the TPU compiler
refuses: unaligned lane slices, block shapes off the (8, 128) tiling, more
VMEM than a kernel may use.  These tests run the chip's own compiler
(installed with libtpu) against a ``v5e:2x2`` topology description and
assert the compiled program holds a Mosaic kernel (``tpu_custom_call``),
so none of them ran interpreted.  The topology is described inside a
fixture, never at import: only the worker that runs this file loads the
TPU library.
"""

import dataclasses
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro import configs, quant as qt
from repro.core.structures import StructureConfig
from repro.kernels import ops
from repro.models import build_model

B16 = 16
# (name, d_out m, d_in n, rank r): llama7b-blast attention / FFN up / FFN
# down (paper Table 9) and a smollm-135m projection (p = q = 36 lanes).
SHAPES = [("attn", 4096, 4096, 1024), ("up", 11008, 4096, 1488),
          ("down", 4096, 11008, 1488), ("smollm", 576, 576, 112)]
KINDS = ["float", "grouped", "int8", "int4"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``ops`` interprets the kernels on the CPU backend; steer it to the
    compiled kernel, which is what a TPU backend selects."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _blast_call(kind, m, n, r, T, sh):
    """(fresh jit of the ops wrapper, abstract operands) for one kernel."""
    b = B16
    p, q = m // b, n // b
    x = _sds((T, n), jnp.bfloat16, sh)
    if kind in ("float", "grouped"):
        lead = (2,) if kind == "grouped" else ()
        fac = [_sds(lead + s, jnp.bfloat16, sh)
               for s in ((b, p, r), (b, b, r), (b, q, r))]
        fn = ops.blast_matmul_grouped if lead else ops.blast_matmul
        return jax.jit(fn.__wrapped__), (x, *fac)
    bits = 8 if kind == "int8" else 4
    rs = r if bits == 8 else (r + 1) // 2
    code = jnp.int8 if bits == 8 else jnp.uint8

    def qa(shape, scale_shape):
        return qt.QArray(_sds(shape[:-1] + (rs,), code, sh),
                         _sds(scale_shape, jnp.float32, sh), bits=bits,
                         last_dim=shape[-1])
    fac = [qa((b, p, r), (b, 1, 1)), qa((b, b, r), (b, b, 1)),
           qa((b, q, r), (b, 1, 1))]
    return jax.jit(ops.blast_matmul_q.__wrapped__), (x, *fac)


@pytest.mark.parametrize("T", [8, 128])
@pytest.mark.parametrize("shape", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("kind", KINDS)
def test_blast_kernel_compiles(one_chip, compiled_kernels, kind, shape, T):
    _, m, n, r = shape
    fn, args = _blast_call(kind, m, n, r, T, one_chip)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip, compiled_kernels):
    q = _sds((1, 32, 1024, 128), jnp.bfloat16, one_chip)
    fn = jax.jit(ops.flash_attention.__wrapped__)
    compiled = fn.lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_llama7b_prefill_chunk_fits_one_chip(one_chip):
    """One serving step of llama7b-blast at full width (4 slots × chunk 16,
    max_len 512, bf16) on the engine's pre-stacked params: arguments +
    outputs + temporaries under 16 GB."""
    model = build_model(configs.get("llama7b-blast"))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = place(jax.eval_shape(
        lambda k: model.prestack_params(model.init(k)),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(4, 512)))
    tokens = _sds((4, 16), jnp.int32, one_chip)
    steps = _sds((4,), jnp.int32, one_chip)
    compiled = jax.jit(model.prefill_chunk, donate_argnums=(1,)).lower(
        params, cache, tokens, steps, steps).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, mem


# -- the decode step moves no cache it does not write --------------------------

_INST = re.compile(
    r"\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w\-]+)\(")


def _data_moves(hlo: str):
    """(in the entry computation?, name, shape) of every executed
    instruction that copies, transposes or dynamic-update-slices — a
    fusion counts as the op its fused computation ends in."""
    comps, name, entry = {}, None, None
    for line in hlo.splitlines():
        if name is None:
            m = re.match(r"(ENTRY )?%([\w.\-]+) ", line)
            if m and line.rstrip().endswith("{"):
                name = m.group(2)
                comps[name] = []
                entry = name if m.group(1) else entry
        elif line.strip() == "}":
            name = None
        else:
            comps[name].append(line)
    root, fused = {}, set()
    for cname, lines in comps.items():
        for line in lines:
            m = _INST.match(line)
            if m and m.group(1):
                root[cname] = m.group(4)
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    out = []
    for cname, lines in comps.items():
        if cname in fused:
            continue
        for line in lines:
            m = _INST.match(line)
            if not m:
                continue
            op = m.group(4)
            if op == "fusion":
                op = root.get(re.search(r"calls=%([\w.\-]+)", line).group(1))
            if op in ("copy", "transpose", "dynamic-update-slice"):
                out.append((cname == entry, m.group(2),
                            tuple(int(d) for d in m.group(3).split(",") if d)))
    return out


GRANITE = {"blast50": StructureConfig(kind="blast", b=16, keep_ratio=0.5),
           "dense": StructureConfig(kind="dense")}


@pytest.mark.parametrize("C", [1, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("structure", sorted(GRANITE))
def test_granite_step_moves_no_whole_layer_of_cache(one_chip, structure, C):
    """The serving step of the benchmark's Granite-3.0-2B configurations
    (12 slots, max_len 2048, pre-stacked params, bf16) compiled for a v5e:
    inside the layer loop no copy, transpose or dynamic-update-slice makes
    a whole layer of K or V, or the stacked cache; outside it at most one
    copy of each stacked K and V (the undonated entry); and the output is
    the cache at its own size, nothing padded or doubled."""
    cfg = dataclasses.replace(
        configs.get("granite-3-2b"), structure=GRANITE[structure],
        structure_ffn=None, param_dtype="bfloat16", compute_dtype="bfloat16")
    model = build_model(cfg)
    B, S, L = 12, 2048, cfg.n_layers
    HD = cfg.n_kv_heads * cfg.head_dim_
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = place(jax.eval_shape(
        lambda k: model.prestack_params(model.init(k)),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(B, S)))
    tokens = _sds((B, C), jnp.int32, one_chip)
    steps = _sds((B,), jnp.int32, one_chip)
    compiled = jax.jit(model.prefill_chunk).lower(
        params, cache, tokens, steps, steps).compile()

    def kv(shape):  # a layer of K or V or more, in any of its layouts
        return (math.prod(shape) >= B * S * HD
                and (shape[-1:] == (HD,) or shape[-2:] == (cfg.n_kv_heads,
                                                         cfg.head_dim_))
                and bool({S, B * S, L * B * S} & set(shape)))

    moves = [m for m in _data_moves(compiled.as_text()) if kv(m[2])]
    in_loop = [m for m in moves if not m[0]]
    assert not in_loop, in_loop
    assert len(moves) <= 2, moves
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    logits_bytes = B * cfg.vocab * jnp.dtype(cfg.compute_dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= cache_bytes + 2 * logits_bytes, mem


@pytest.mark.parametrize("C", [1, 256], ids=["decode", "prefill"])
def test_granite4_hybrid_step_fits_one_chip(one_chip, C):
    """The serving step of the benchmark's Granite-4.0-H-Micro BLAST
    configuration (32 slots, max_len 4096, pre-stacked params, bf16)
    compiled for a v5e: no copy, transpose or dynamic-update-slice inside
    the layer loop makes a whole layer of K or V, and the step with its
    weights, both caches and its temporaries fits the chip's 16 GB."""
    cfg = dataclasses.replace(configs.get("granite-4-h-micro"),
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    model = build_model(cfg)
    B, S = 32, 4096
    HD = cfg.n_kv_heads * cfg.head_dim_
    L = cfg.layer_kinds().count("attn")
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = place(jax.eval_shape(
        lambda k: model.prestack_params(model.init(k)),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(B, S)))
    tokens = _sds((B, C), jnp.int32, one_chip)
    steps = _sds((B,), jnp.int32, one_chip)
    compiled = jax.jit(model.prefill_chunk).lower(
        params, cache, tokens, steps, steps).compile()

    def kv(shape):
        return (math.prod(shape) >= B * S * HD and shape[-1:] == (HD,)
                and bool({S, B * S, L * B * S} & set(shape)))

    in_loop = [m for m in _data_moves(compiled.as_text())
               if kv(m[2]) and not m[0]]
    assert not in_loop, in_loop
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    mem = compiled.memory_analysis()
    assert (weights + mem.output_size_in_bytes + mem.temp_size_in_bytes
            + sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
            < 16e9), mem
