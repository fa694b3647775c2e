"""Serving launcher: chunked-prefill continuous-batching engine over any arch.

Batch smoke (default):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --reduced --requests 8 --max-new 16 --chunk 16

Paged pool + multi-tenant trace with SLA report:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --paged --pages 16 --page-size 16 --priority-classes 2 --trace \
        --report sla.json

HTTP/SSE frontend (stdlib asyncio, serves until interrupted):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --paged --http-port 8080
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_parallel, make_serving_mesh, parse_mesh
from repro.models import build_model
from repro.parallel import NO_PARALLEL
from repro.serve import (AutotuneConfig, Engine, EngineConfig, MemoryConfig,
                         Request, ResilienceConfig, SamplingParams,
                         SchedulerConfig, SpeculativeConfig)


def build_parallel(args):
    """``--mesh dp,tp`` → (Parallel, mesh string or None).

    A (1,1) mesh (or no flag) keeps the NO_PARALLEL fast path — identical
    traces to every earlier PR.  Anything larger builds the ("data",
    "model") serving mesh over the visible devices (simulate with
    XLA_FLAGS=--xla_force_host_platform_device_count=N) with serve=True
    parallelism: params TP-sharded + data-replicated, batch over "data".
    """
    spec = getattr(args, "mesh", None)
    if spec is None:
        return NO_PARALLEL, None
    dp, tp = parse_mesh(spec)
    if (dp, tp) == (1, 1):
        return NO_PARALLEL, None
    par = make_parallel(make_serving_mesh(dp, tp), serve=True)
    return par, f"{dp},{tp}"


def build_engine_config(args) -> EngineConfig:
    """Map the CLI surface onto an EngineConfig (API v2) — the launcher no
    longer touches the deprecated flat Engine kwargs."""
    return EngineConfig(
        mesh=getattr(args, "mesh", None),
        scheduler=SchedulerConfig(
            slots=args.slots, chunk_size=args.chunk,
            token_budget=args.token_budget,
            policy="priority" if args.priority_classes > 1 else "fifo",
            deadline_s=getattr(args, "deadline", None)),
        resilience=ResilienceConfig(
            watchdog_deadline_s=getattr(args, "watchdog", None),
            queue_high_water=getattr(args, "queue_high_water", None),
            heartbeat_s=getattr(args, "heartbeat", 10.0),
            fault_spec=getattr(args, "fault_plan", None)),
        memory=MemoryConfig(
            max_len=args.max_len, paged=args.paged, page_size=args.page_size,
            pages=args.pages),
        speculative=SpeculativeConfig(k=args.speculative,
                                      draft_rank_frac=args.draft_rank_frac),
        autotune=AutotuneConfig(enabled=args.autotune,
                                cache_path=args.autotune_cache),
        seed=args.seed)


def make_cli_trace(vocab, *, n_classes: int, max_new: int, seed: int):
    """Small multi-tenant trace: bulk class-(n-1) requests saturating the
    slots plus interactive class-0 arrivals sharing one prompt prefix.
    Returns [(arrival_tick, Request)] sorted by arrival."""
    key = jax.random.PRNGKey(seed + 17)
    shared = [int(t) for t in jax.random.randint(key, (48,), 0, vocab)]
    trace = []
    lo = max(0, n_classes - 1)
    for i in range(6):   # bulk: long generations, lowest priority
        p = [int(t) for t in jax.random.randint(
            jax.random.fold_in(key, i), (8,), 0, vocab)]
        trace.append((0, Request(uid=i, prompt=p, max_new_tokens=max_new * 2,
                                 priority=lo)))
    for i in range(8):   # interactive: shared 48-token prefix, short answers
        tail = [int(t) for t in jax.random.randint(
            jax.random.fold_in(key, 100 + i), (4,), 0, vocab)]
        trace.append((3 + 2 * i,
                      Request(uid=100 + i, prompt=shared + tail,
                              max_new_tokens=max_new, priority=0,
                              prefix_len=len(shared))))
    return sorted(trace, key=lambda a: a[0])


def run_trace(engine: Engine, trace) -> dict:
    """Drive the engine tick-by-tick, submitting each request at its
    arrival tick; returns the SLA report."""
    pending = list(trace)
    tick = 0
    while pending or engine.queue or any(
            s.req is not None for s in engine.slots):
        while pending and pending[0][0] <= tick:
            engine.submit(pending.pop(0)[1])
        engine.tick()
        tick += 1
    return engine.sla_report()


def _print_resilience(engine: Engine):
    """One line of chaos/degradation accounting after a run — silent when
    nothing tripped and no fault plan was armed."""
    rep = engine.resilience_report()
    tripped = any(rep[k] for k in ("numeric_trips", "step_errors", "shed",
                                   "deadline_expired"))
    if not tripped and "faults" not in rep:
        return
    h = rep["health"]
    print(f"[serve] resilience: health={h['state']}"
          f"{' (' + h['reason'] + ')' if h['reason'] else ''} — "
          f"{rep['numeric_trips']} guardrail trips "
          f"(spec_off {rep['degrade_spec_off']}, "
          f"act_float {rep['degrade_act_float']}, "
          f"failed {rep['numeric_error_failures']}), "
          f"{rep['step_errors']} step errors, {rep['requeues']} requeues, "
          f"{rep['shed']} shed, {rep['deadline_expired']} past deadline, "
          f"{h['watchdog_trips']} watchdog trips")
    if "faults" in rep:
        fr = rep["faults"]
        print(f"[serve] faults fired: {fr['fired']} of "
              f"{len(fr['planned'])} planned — {fr['fired_by_kind']}")


def failures(engine: Engine, reqs) -> list[str]:
    """Why a run without an armed fault plan must not count as finished:
    requests that ended in ``error`` / ``numeric_error`` and recorded step
    errors.  Empty when the run is clean, or when a fault plan was armed
    (chaos runs fail requests on purpose)."""
    if engine.fault_plan is not None:
        return []
    out = [f"request {r.uid} ended in {r.stop_reason}" for r in reqs
           if r.stop_reason in ("error", "numeric_error")]
    n_err = engine.stats["step_errors"]
    if n_err:
        out.append(f"{n_err} step errors: {engine.health.snapshot()}")
    return out


def make_prompts(vocab: int, n: int, lengths: str, seed: int) -> list:
    """``n`` seeded prompts whose lengths spread evenly over
    ``lengths`` = "LO,HI" tokens (inclusive)."""
    lo, hi = (int(v) for v in lengths.split(","))
    key = jax.random.PRNGKey(seed + 1)
    prompts = []
    for i in range(n):
        plen = lo + (i * (hi - lo)) // max(1, n - 1)
        prompt = jax.random.randint(jax.random.fold_in(key, i), (plen,),
                                    0, vocab)
        prompts.append([int(t) for t in prompt])
    return prompts


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--structure", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", default="4,8", metavar="LO,HI",
                    help="batch-mode prompt lengths, spread evenly over "
                         "LO..HI tokens")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prompt tokens one slot may prefill per step")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="max total tokens packed into one mixed batch")
    ap.add_argument("--paged", action="store_true",
                    help="page the KV/state cache: pool sized in tokens, "
                         "prefix sharing + preemption (serve/paged.py)")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size (default: slots*ceil(max_len/"
                         "page_size)+1, i.e. slot-static parity)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per cache page (--paged)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help=">1 enables priority scheduling: class 0 is most "
                         "urgent and may preempt higher classes under "
                         "page pressure (1 = FIFO)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve an HTTP/SSE frontend on this port instead "
                         "of running a local batch (0 = ephemeral port)")
    ap.add_argument("--trace", action="store_true",
                    help="run the built-in multi-tenant trace (bulk + "
                         "shared-prefix interactive arrivals) and print "
                         "the SLA report")
    ap.add_argument("--quant-weights", default="none",
                    choices=["none", "int8", "int4"],
                    help="quantize-at-load weight storage")
    ap.add_argument("--quant-activations", default="none",
                    choices=["none", "int8"],
                    help="per-token int8 activation quantization: with "
                         "int8/int4 weights the BLAST layers run integer "
                         "W8A8/W4A8 kernels (requires --quant-weights)")
    ap.add_argument("--quant-cache", default="none", choices=["none", "int8"],
                    help="int8 KV/latent/state caches")
    ap.add_argument("--autotune", action="store_true",
                    help="time candidate BLAST kernel tilings at engine "
                         "build and cache the winners (kernels/autotune.py)")
    ap.add_argument("--autotune-cache", default=None,
                    help="autotune cache path (default .autotune/"
                         "blast_tiling.json)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="self-speculative decoding: draft K tokens per "
                         "round with a rank-truncated copy of the model, "
                         "verify in one full-model chunk (0 = off)")
    ap.add_argument("--draft-rank-frac", type=float, default=0.5,
                    help="fraction of pooled spectral energy kept by the "
                         "draft model's rank-calibration (--speculative)")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="device mesh as 'dp,tp' (bare N means tp=N): the "
                         "same engine code runs 1-device and multi-chip; "
                         "simulate chips on CPU with XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection (serve/faults.py): "
                         "e.g. 'nan@6:u3;raise@12:u1;slow@20:0.5' — the "
                         "engine must finish every non-faulted request "
                         "token-identically")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request end-to-end deadline in seconds "
                         "(stop_reason='deadline' past it)")
    ap.add_argument("--watchdog", type=float, default=None, metavar="S",
                    help="watchdog step deadline: a jitted step exceeding "
                         "this marks the engine degraded (GET /healthz)")
    ap.add_argument("--queue-high-water", type=int, default=None,
                    help="shed queued work above this many requests in "
                         "flight (HTTP answers 429 + Retry-After)")
    ap.add_argument("--heartbeat", type=float, default=10.0, metavar="S",
                    help="SSE heartbeat interval between tokens")
    ap.add_argument("--report", default=None,
                    help="write a JSON throughput/SLA report here")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args):
    """Config, model, seeded params and engine for parsed ``args``."""
    cfg = configs.get(args.arch, args.structure)
    if args.reduced:
        cfg = cfg.reduced()
    if (args.quant_weights != "none" or args.quant_cache != "none"
            or args.quant_activations != "none"):
        import dataclasses
        from repro.quant import QuantConfig
        cfg = dataclasses.replace(cfg, quant=QuantConfig(
            weights=args.quant_weights, cache=args.quant_cache,
            activations=args.quant_activations))
    if cfg.encoder is not None:
        raise SystemExit("use examples/serve_batched.py for enc-dec archs")
    parallel, _ = build_parallel(args)
    model = build_model(cfg, parallel)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = Engine(model, params, build_engine_config(args))
    return cfg, model, params, engine


def run_batch(engine: Engine, cfg, args) -> tuple[list, float]:
    """Submit ``args.requests`` seeded prompts and drive to drain.
    Returns the requests in submit order and the wall seconds."""
    prompts = make_prompts(cfg.vocab, args.requests, args.prompt_len,
                           args.seed)
    # explicit small uids (1..N) so --fault-plan targets are addressable
    reqs = [Request(uid=i + 1, prompt=p, max_new_tokens=args.max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.run()
    return reqs, time.perf_counter() - t0


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    cfg, model, params, engine = build(args)
    parallel = getattr(model, "parallel", NO_PARALLEL)
    if parallel.active:
        rep = engine.sharding_report or {}
        print(f"[serve] mesh {parallel.dp_size}x{parallel.tp_size} "
              f"(data x model) over {parallel.dp_size * parallel.tp_size} "
              f"devices — replicated params "
              f"{rep.get('replicated_bytes', 0) / 1e6:.2f} MB of "
              f"{rep.get('total_bytes', 0) / 1e6:.2f} MB "
              f"({rep.get('replicated_leaves', 0)} leaves)")
    if args.paged:
        pc = engine._pc
        print(f"[serve] paged: {pc.pages.n_pages} pages x {pc.ps} tokens "
              f"({pc.pool_tokens()} pool tokens vs "
              f"{args.slots * args.max_len} slot-static)")
    if args.speculative:
        plan = engine.draft_plan
        print(f"[serve] speculative k={args.speculative}: draft keeps "
              f"{sum(plan.values())} of the full model's ranks "
              f"({len(plan)} calibrated linears, "
              f"frac={args.draft_rank_frac})")
    if args.autotune:
        from repro.kernels import autotune
        cache = autotune.cache()
        print(f"[serve] autotune: {len(cache.entries)} tiling entries "
              f"cached at {cache.path}")

    if args.fault_plan:
        print(f"[serve] fault plan armed: "
              f"{'; '.join(f.describe() for f in engine.fault_plan.faults)}")

    if args.http_port is not None:
        import asyncio
        from repro.serve.http import run_server
        print(f"[serve] http/sse frontend on port {args.http_port} "
              f"(POST /v1/generate, GET /v1/metrics, GET /healthz)")
        asyncio.run(run_server(engine, port=args.http_port))
        return

    if args.trace:
        trace = make_cli_trace(cfg.vocab, n_classes=args.priority_classes,
                               max_new=args.max_new, seed=args.seed)
        t0 = time.perf_counter()
        sla = run_trace(engine, trace)
        dt = time.perf_counter() - t0
        done = engine.finished
        c0 = sla["classes"].get("0", {})
        print(f"[serve] trace: {len(done)} requests in {dt:.1f}s — "
              f"interactive TTFT p50 "
              f"{(c0.get('ttft_p50_s') or 0) * 1e3:.1f} ms "
              f"p99 {(c0.get('ttft_p99_s') or 0) * 1e3:.1f} ms, "
              f"preemptions {sla['preemptions']}, "
              f"prefix-hit {sla['prefix_hit_rate']:.2f}")
        _print_resilience(engine)
        if args.report:
            report = {"arch": args.arch, "requests": len(done), "wall_s": dt,
                      "paged": args.paged,
                      "priority_classes": args.priority_classes, "sla": sla}
            with open(args.report, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
            print(f"[serve] report written to {args.report}")
        _exit_on_failures(engine, done)
        return

    done, dt = run_batch(engine, cfg, args)
    total_tokens = sum(len(r.output) for r in done)
    tp = engine.throughput()
    print(f"[serve] {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/dt:.1f} tok/s, "
          f"{args.slots} slots, chunk={args.chunk}, "
          f"{tp['steps']} jitted steps)")
    print(f"[serve] prefill {engine.stats['prefill_tokens']} toks "
          f"@ {tp['prefill_tok_s']:.1f} tok/s · "
          f"decode {engine.stats['decode_tokens']} toks "
          f"@ {tp['decode_tok_s']:.1f} tok/s")
    if args.speculative:
        print(f"[serve] speculative: {tp['spec_rounds']} rounds, "
              f"acceptance {tp['acceptance_rate']:.2f}, "
              f"{tp['tokens_per_round']:.2f} tok/round")
    _print_resilience(engine)
    if args.report:
        report = {"arch": args.arch, "requests": len(done),
                  "total_tokens": total_tokens, "wall_s": dt,
                  "tok_s": total_tokens / dt, "speculative": args.speculative,
                  "draft_rank_frac": args.draft_rank_frac, **tp}
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"[serve] report written to {args.report}")
    for r in done[:4]:
        print(f"  req {r.uid}: prompt {len(r.prompt)} toks → {r.output[:8]}…")
    _exit_on_failures(engine, done)


def _exit_on_failures(engine: Engine, reqs):
    bad = failures(engine, reqs)
    if bad:
        raise SystemExit("[serve] FAILED: " + "; ".join(bad))


if __name__ == "__main__":
    main()
