#!/usr/bin/env python3
"""On-chip smoke test: the BLAST kernels and the serving engine on one TPU.

    python chip_smoke.py              # one chip: kernel phase + engine phase
    python chip_smoke.py --chips 4    # only: tensor-parallel serving, 1x4

Phases (one chip):

1. Device check: exits 2, printing no result line, unless JAX's first
   device is a TPU.
2. Kernels: every Pallas BLAST kernel family (float, int8, int4; single
   and grouped) at the llama7b-blast shapes (attention 4096->4096 r=1024,
   grouped gate+up 4096->11008 r=1488, down 11008->4096 r=1488), T=8 and
   T=128, plus flash attention.  Each compiled program must contain a Mosaic
   kernel (``tpu_custom_call``) and match its ``kernels/ref.py`` oracle,
   computed on the chip in float32 at precision "highest", within
   ``KERNEL_TOL`` of the oracle's largest magnitude.
3. Engine: ``repro.launch.serve`` at full llama7b-blast width, seeded
   random weights: 8 requests, prompts 4..100 tokens, 16 new tokens each,
   4 slots, chunk 16, max_len 512.  Every request must stop on "length"
   with no step error, numeric trip, degradation or requeue, and the
   engine's logits at the last prompt position of two requests must match
   ``LM.apply`` within ``LOGIT_TOL``.

``--chips 4`` serves the same requests on a 1x4 (data x model) mesh and on
one device, compares the first step's logits within ``LOGIT_TOL``, prints
token agreement, and checks that no device holds most of the parameter
bytes.

Compile seconds (JAX's backend-compile events) and peak device bytes are
printed as set-up facts, not as speed.  The last stdout line is the JSON
result ``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Kernel outputs are bf16 (the activation dtype); bf16 keeps 8 mantissa
# bits, so one rounding of the output is up to 2^-9 relative.  The bound
# allows a few of those plus f32 reduction-order differences.
KERNEL_TOL = 2e-2
# Two bf16 forward passes of a 32-layer model that differ in reduction
# order (cached chunked prefill vs one full-sequence pass; one device vs a
# 4-way tensor-parallel psum) drift by a few bf16 ulps per layer.
LOGIT_TOL = 5e-2

SERVE_ARGV = ["--arch", "llama7b-blast", "--requests", "8",
              "--prompt-len", "4,100", "--max-new", "16", "--slots", "4",
              "--chunk", "16", "--max-len", "512"]


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


class CompileClock:
    """Sums JAX's backend-compile durations; persistent-cache hits count
    their (short) retrieval instead of a compile."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


# ---------------------------------------------------------------------------
# Kernel phase.
# ---------------------------------------------------------------------------

LLAMA_SHAPES = [("attn 4096->4096 r1024", 4096, 4096, 1024, 1),
                ("gate+up 4096->11008 r1488", 11008, 4096, 1488, 2),
                ("down 11008->4096 r1488", 4096, 11008, 1488, 1)]


def _kernel_cases(m, n, r, G, T, b, seed):
    """(kind, jitted ops wrapper, args, oracle fn, oracle args) per kind."""
    import jax
    import jax.numpy as jnp
    from repro import quant as qt
    from repro.core import blast
    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(seed), G + 1)
    x = jax.random.normal(keys[0], (T, n), jnp.bfloat16)
    sets = [blast.init(k, m, n, b, r, dtype=jnp.bfloat16) for k in keys[1:]]
    U, S, V = (jnp.stack([getattr(s, f) for s in sets]) for f in "USV")
    if G == 1:
        U, S, V = U[0], S[0], V[0]
    lead = U.ndim - 3
    xf = x.astype(jnp.float32)
    f32 = lambda *a: tuple(t.astype(jnp.float32) for t in a)  # noqa: E731
    cases = []
    if G == 1:
        cases.append(("float", ops.blast_matmul, (x, U, S, V),
                      ref.blast_matmul_ref, (xf, *f32(U, S, V))))
    else:
        cases.append(("float", ops.blast_matmul_grouped, (x, U, S, V),
                      ref.blast_matmul_grouped_ref, (xf, *f32(U, S, V))))
    for bits in (8, 4):
        uv = (lead + 1, lead + 2)
        Uq = qt.quantize(U, bits=bits, block_axes=uv)
        Sq = qt.quantize(S, bits=bits, block_axes=(lead + 2,))
        Vq = qt.quantize(V, bits=bits, block_axes=uv)
        codes = tuple(qt.int_values(a) for a in (Uq, Sq, Vq))
        gb = (G, b) if lead else (b,)
        su, sv = Uq.scale.reshape(gb), Vq.scale.reshape(gb)
        ss = Sq.scale.reshape(gb + (b,))
        kind = f"int{bits}"
        if G == 1:
            cases.append((kind, ops.blast_matmul_q, (x, Uq, Sq, Vq),
                          ref.blast_matmul_q_ref, (xf, *codes, su, ss, sv)))
        else:
            fn = (ops.blast_matmul_grouped_q if bits == 8
                  else ops.blast_matmul_grouped_q4)
            stored = codes if bits == 8 else (Uq.q, Sq.q, Vq.q)
            cases.append((kind, fn, (x, *stored, su, ss, sv),
                          ref.blast_matmul_grouped_q_ref,
                          (xf, *codes, su, ss, sv)))
    return cases


def _run_case(name, fn, args, oracle, oracle_args, errors):
    import jax
    compiled = fn.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no Mosaic kernel in the compiled program")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(oracle)(*oracle_args)
    err = rel_err(got, want)
    errors[name] = err
    log(f"kernel {name}: max|kernel - oracle| / max|oracle| = {err:.3e}")
    check(err <= KERNEL_TOL, f"{name}: error {err:.3e} > {KERNEL_TOL}")


def kernel_phase(shapes=LLAMA_SHAPES, Ts=(8, 128), b=16,
                 attn=(1, 32, 1024, 128), seed=0) -> dict:
    """Every BLAST kernel family at ``shapes`` × ``Ts``, plus flash
    attention at ``attn`` = (B, H, S, D); returns name → relative error."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    errors: dict = {}
    for label, m, n, r, G in shapes:
        for T in Ts:
            for kind, fn, args, oracle, oargs in _kernel_cases(
                    m, n, r, G, T, b, seed):
                _run_case(f"{kind} {label} T={T}", fn, args, oracle, oargs,
                          errors)
    B, H, S, D = attn
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q, k, v = (jax.random.normal(kx, (B, H, S, D), jnp.bfloat16)
               for kx in (kq, kk, kv))
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    _run_case(f"flash_attention {H}x{D} S={S}", ops.flash_attention,
              (q, k, v), ref.attention_ref, f32, errors)
    return errors


# ---------------------------------------------------------------------------
# Engine phase.
# ---------------------------------------------------------------------------


def _spy_prompt_logits(engine, record: dict, first: list):
    """Wrap the engine's jitted step: keep each request's logits at its
    last prompt position (host float32), and the first step's logits."""
    import numpy as np
    step = engine._step

    def spy(params, cache, tokens, steps, n_tok):
        logits, cache = step(params, cache, tokens, steps, n_tok)
        steps_h, n_h = np.asarray(steps), np.asarray(n_tok)
        if not first:
            first.append(np.asarray(logits, np.float32))
        for b, slot in enumerate(engine.slots):
            req = slot.req
            if (req is not None and n_h[b] and not req.output
                    and steps_h[b] + n_h[b] == len(req.prompt)):
                record[req.uid] = np.asarray(logits[b, 0], np.float32)
        return logits, cache

    engine._step = spy


def _check_served(engine, reqs, max_new: int) -> None:
    from repro.launch import serve
    reasons = {r.uid: r.stop_reason for r in reqs}
    check(all(r.stop_reason == "length" and len(r.output) == max_new
              for r in reqs), f"requests did not all stop on length: "
          f"{reasons}")
    rep = engine.resilience_report()
    counters = {k: rep[k] for k in ("step_errors", "numeric_trips",
                                    "degrade_spec_off", "degrade_act_float",
                                    "numeric_error_failures", "requeues")}
    log(f"resilience counters: {counters}")
    check(not any(counters.values()), f"resilience counters {counters}")
    bad = serve.failures(engine, reqs)
    check(not bad, "; ".join(bad))


def engine_phase(clock: CompileClock, argv=SERVE_ARGV,
                 compare_uids=(1, 8)) -> dict:
    """The serving launcher's batch run in-process, with the engine's
    prompt-end logits checked against ``LM.apply``."""
    import functools
    import jax
    import numpy as np
    from repro.launch import serve

    args = serve.parse_args(list(argv))
    c0, t0 = clock.seconds, time.perf_counter()
    cfg, model, params, engine = serve.build(args)
    record: dict = {}
    _spy_prompt_logits(engine, record, [])
    reqs, wall = serve.run_batch(engine, cfg, args)
    compile_s = clock.seconds - c0
    log(f"engine: {len(reqs)} requests served in {wall:.1f}s wall "
        f"(set-up included; not a speed measurement), "
        f"{engine.stats['steps']} steps, prompt lengths "
        f"{[len(r.prompt) for r in reqs]}")
    _check_served(engine, reqs, args.max_new)

    apply_last = jax.jit(functools.partial(model.apply, last_only=True))
    errs = {}
    for uid in compare_uids:
        req = reqs[uid - 1]
        want = np.asarray(apply_last(params, np.asarray([req.prompt]))
                          .logits[0, -1], np.float32)
        got = record[uid]
        errs[uid] = rel_err(got, want)
        log(f"engine vs LM.apply, request {uid} ({len(req.prompt)} prompt "
            f"tokens): max|diff| / max|ref| = {errs[uid]:.3e}, argmax "
            f"{int(got.argmax())} vs {int(want.argmax())}")
        check(errs[uid] <= LOGIT_TOL,
              f"request {uid}: logit error {errs[uid]:.3e} > {LOGIT_TOL}")
    return {"compile_s": compile_s, "set_up_s": time.perf_counter() - t0,
            "logit_err": errs}


# ---------------------------------------------------------------------------
# Four-chip phase.
# ---------------------------------------------------------------------------


def _param_bytes_per_device(params) -> dict:
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def mesh_phase(argv=SERVE_ARGV, mesh="1,4") -> dict:
    """Serve the same requests on one device, then on a ``mesh`` (dp,tp)
    mesh through ``--mesh``; compare first-step logits and tokens."""
    import jax
    import numpy as np
    from repro.launch import serve

    # one device first, so the process-wide TP mode the mesh engine sets
    # never reaches it
    args1 = serve.parse_args(list(argv))
    cfg, model, params, engine = serve.build(args1)
    first1: list = []
    _spy_prompt_logits(engine, {}, first1)
    reqs1, _ = serve.run_batch(engine, cfg, args1)
    _check_served(engine, reqs1, args1.max_new)
    del engine, params, model
    gc.collect()

    args4 = serve.parse_args(list(argv) + ["--mesh", mesh])
    cfg, model, params, engine = serve.build(args4)
    del params   # the engine holds the sharded copy
    gc.collect()
    per_dev = _param_bytes_per_device(engine.params)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(engine.params))
    share = max(per_dev.values()) / total
    log(f"mesh {mesh}: parameter bytes per device "
        f"{ {d: int(v) for d, v in sorted(per_dev.items())} } "
        f"(largest share {share:.3f} of {total})")
    for d in jax.devices():
        st = d.memory_stats() or {}
        log(f"{d}: bytes_in_use {st.get('bytes_in_use')}")
    check(share < 0.5, f"one device holds {share:.3f} of the parameters")
    first4: list = []
    _spy_prompt_logits(engine, {}, first4)
    reqs4, _ = serve.run_batch(engine, cfg, args4)
    _check_served(engine, reqs4, args4.max_new)

    err = rel_err(first4[0], first1[0])
    toks1 = np.concatenate([r.output for r in reqs1])
    toks4 = np.concatenate([r.output for r in reqs4])
    agree = float(np.mean(toks1 == toks4))
    log(f"mesh {mesh} vs one device: first-step logits max|diff| / max|ref| "
        f"= {err:.3e}; greedy tokens agree {agree:.3f} "
        f"({int(np.sum(toks1 == toks4))}/{toks1.size})")
    check(err <= LOGIT_TOL, f"mesh logits error {err:.3e} > {LOGIT_TOL}")
    return {"logit_err": err, "token_agreement": agree,
            "param_bytes_per_device": per_dev}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tensor-parallel (1x4) serving "
                         "phase and its one-device comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase()
        else:
            errors = kernel_phase()
            log(f"kernel phase: {len(errors)} kernels within {KERNEL_TOL}; "
                f"worst {max(errors.values()):.3e}")
            eng = engine_phase(clock)
            peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            log(f"set-up facts: engine compile {eng['compile_s']:.1f}s, "
                f"peak_bytes_in_use {peak} (process, after the engine "
                f"phase)")
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"set-up facts: total backend compile {clock.seconds:.1f}s, "
        f"{clock.cache_hits} persistent-cache hits, "
        f"{time.perf_counter() - t0:.1f}s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
