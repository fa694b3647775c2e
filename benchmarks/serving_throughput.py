"""Beyond-paper serving benchmark: chunked-prefill throughput on a
prompt-heavy workload.

The paper's Table-4 scenario is batch=1 generation; production serving is
dominated by *prompt ingestion* — BLaST's block matmuls are starved at T=1
and saturated at T=chunk, so the engine's chunk size C directly sets how
many (tokens × rank) rows each structured matmul sees per step.  This sweep
serves the same prompt-heavy request mix at several chunk sizes and reports
prefill-tokens/s and decode-tokens/s separately: prefill throughput should
climb with C (ceil(L/C) steps instead of L per prompt) while decode
throughput stays flat (decode steps are C-independent).

``quant_report`` covers the memory half: for each of the four decoder
families (GQA / MLA / SSD / RG-LRU) it compares the resident weight+cache
HBM bytes of bf16 serving against quantized storage (int8 and int4-packed
weights, int8 caches) and the final-logit deviation the quantization
introduces on a smoke prompt.

``kernel_report`` covers the launch half: per decoder family it counts the
structured-matmul dispatches one decode step issues (each == one
pallas_call on the fused-kernel path) with the grouped projection bundles
enabled vs the per-projection loop, and reduces the engine's recorded
per-step wall times to latency percentiles.  Grouping must show strictly
fewer launches per decode step wherever a family has a same-input bundle
(GQA gate+up, MLA a-projections + gate+up, RG-LRU input/gate pairs).
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro import quant as qt
from repro.core import structures
from repro.models import build_model
from repro.quant import QuantConfig
from repro.serve import (Engine, EngineConfig, MemoryConfig, Request,
                        SamplingParams, SchedulerConfig, SpeculativeConfig,
                        Tracer)


def _percentiles(samples) -> dict:
    """Per-step latency percentiles (p50/p90/p99) in seconds."""
    if len(samples) == 0:
        return {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    arr = np.asarray(samples, np.float64)
    return {f"p{p}": float(np.percentile(arr, p)) for p in (50, 90, 99)}


def _mk_requests(n, vocab, key, prompt_len=48, max_new=8):
    """Prompt-heavy mix: long prompts, short completions."""
    reqs = []
    for i in range(n):
        plen = prompt_len - 8 + (i * 5) % 17
        toks = jax.random.randint(jax.random.fold_in(key, i), (plen,), 0, vocab)
        reqs.append(Request(uid=i, prompt=[int(t) for t in toks],
                            max_new_tokens=4 + (i * 3) % max_new))
    return reqs


def run(quiet=False, n_requests=8, slots=4, chunks=(1, 8, 32)):
    cfg = configs.ARCHS["smollm-135m"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    step_fn = jax.jit(model.prefill_chunk)  # shared: compiles keyed by (B, C)

    rows = []
    for chunk in chunks:
        # warm every chunk bucket the timed run can hit, outside the timed
        # region: the power-of-two ladder below chunk (prompt remainders)
        # plus a full-chunk prompt (covers _bucket(chunk) when chunk is not
        # itself a power of two)
        warm_lens = []
        c = 1
        while c < chunk:
            warm_lens.append(c)
            c *= 2
        warm_lens.append(chunk)
        for c in warm_lens:
            warm = Engine(model, params, EngineConfig(
                scheduler=SchedulerConfig(slots=slots, chunk_size=chunk),
                memory=MemoryConfig(max_len=128)), step_fn=step_fn)
            warm.submit(Request(uid=-1, prompt=list(range(1, 1 + c)),
                                max_new_tokens=2))
            warm.run()

        eng = Engine(model, params, EngineConfig(
            scheduler=SchedulerConfig(slots=slots, chunk_size=chunk),
            memory=MemoryConfig(max_len=128)), step_fn=step_fn)
        for r in _mk_requests(n_requests, cfg.vocab, key):
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        assert len(done) == n_requests
        tp = eng.throughput()
        hbm_mb = (qt.tree_nbytes(eng.params) + qt.tree_nbytes(eng.cache)) / 2**20
        # a step's wall time: its dispatch and the wait for its logits
        ticks = eng.tracer.records()
        step_s = ticks["dispatch"] + ticks["wait"]
        decode_only = (ticks["prompt"] == 0) & (ticks["decode"] > 0)
        rows.append({
            "chunk": chunk,
            "steps": tp["steps"],
            "prefill_tok_s": tp["prefill_tok_s"],
            "decode_tok_s": tp["decode_tok_s"],
            "wall_s": wall,
            "weight_cache_mb": hbm_mb,
            "step_latency_s": _percentiles(step_s),
            "decode_step_latency_s": _percentiles(step_s[decode_only]),
        })
        if not quiet:
            pct = rows[-1]["decode_step_latency_s"]
            print(f"[serving] C={chunk:3d}: {tp['steps']:4d} steps, "
                  f"prefill {tp['prefill_tok_s']:8.1f} tok/s, "
                  f"decode {tp['decode_tok_s']:7.1f} tok/s, "
                  f"wall {wall:5.1f}s, weight+cache {hbm_mb:6.2f} MB, "
                  f"decode p50/p99 {pct['p50'] * 1e3:.1f}/"
                  f"{pct['p99'] * 1e3:.1f} ms")
    if not quiet and len(rows) > 1:
        gain = rows[-1]["prefill_tok_s"] / max(rows[0]["prefill_tok_s"], 1e-9)
        print(f"[serving] chunked prefill C={rows[-1]['chunk']} vs "
              f"token-at-a-time: {gain:.2f}× prefill throughput "
              f"({n_requests} prompt-heavy reqs, {slots} slots)")
    return rows


# -- quantized-serving memory report ----------------------------------------

FAMILIES = {
    "gqa": "smollm-135m",
    "mla": "deepseek-v3-671b",
    "ssd": "mamba2-130m",
    "rglru": "recurrentgemma-2b",
}


def quant_report(quiet=False, batch=4, max_len=64, prompt_len=12,
                 modes=(("int8", "int8", "none"), ("int4", "int8", "none"),
                        ("int4", "int8", "int8"))):
    """Weight+cache HBM bytes and final-logit deviation, bf16 vs quantized.

    For each decoder family: build the reduced smoke model in bf16, then the
    same arch with ``quant=(weights, cache, activations)``; quantize the
    *same* float params, run one prefill chunk through both, and report the
    resident memory ratio plus max |Δlogit|.  int8 weights halve storage
    (minus the per-block scale overhead); int4-packed weights quarter it, so
    the combined weight+cache reduction clears 2× with margin.  The
    activations="int8" row (W4A8) adds the per-token activation-rounding
    error on top of the weight error — storage is identical to the W4 row,
    only compute changes.
    """
    rows = []
    for family, arch in FAMILIES.items():
        cfg = configs.ARCHS[arch].reduced(param_dtype="bfloat16",
                                          compute_dtype="bfloat16")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(batch, max_len)
        w_mb = qt.tree_nbytes(params) / 2**20
        c_mb = qt.tree_nbytes(cache) / 2**20
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, prompt_len), 0, cfg.vocab)
        steps = jnp.zeros((batch,), jnp.int32)
        n_tok = jnp.full((batch,), prompt_len, jnp.int32)
        base_logits, _ = model.prefill_chunk(params, cache, tokens, steps, n_tok)
        base = np.asarray(base_logits, np.float32)
        for weights, cache_mode, act in modes:
            qcfg = QuantConfig(weights=weights, cache=cache_mode,
                               activations=act)
            cfg_q = dataclasses.replace(cfg, quant=qcfg)
            model_q = build_model(cfg_q)
            params_q = model_q.quantize_params(params, qcfg)
            cache_q = model_q.init_cache(batch, max_len)
            wq_mb = qt.tree_nbytes(params_q) / 2**20
            cq_mb = qt.tree_nbytes(cache_q) / 2**20
            with structures.activations(act):
                q_logits, _ = model_q.prefill_chunk(params_q, cache_q, tokens,
                                                    steps, n_tok)
            dev = float(np.abs(np.asarray(q_logits, np.float32) - base).max())
            rel = dev / (np.abs(base).max() + 1e-9)
            reduction = (w_mb + c_mb) / (wq_mb + cq_mb)
            rows.append({
                "family": family, "arch": arch,
                "weights": weights, "cache": cache_mode, "activations": act,
                "bf16_mb": w_mb + c_mb, "quant_mb": wq_mb + cq_mb,
                "reduction": reduction, "max_dlogit": dev, "rel_dlogit": rel,
            })
            if not quiet:
                a = f"/a{act}" if act != "none" else ""
                print(f"[quant] {family:6s} ({arch}): w+c "
                      f"{w_mb + c_mb:7.2f} MB bf16 → {wq_mb + cq_mb:7.2f} MB "
                      f"{weights}/{cache_mode}{a} ({reduction:4.2f}×), "
                      f"max|Δlogit| {dev:.4f} (rel {rel:.3f})")
    best = {}
    for r in rows:
        best.setdefault(r["family"], 0.0)
        best[r["family"]] = max(best[r["family"]], r["reduction"])
    if not quiet:
        ok = all(v >= 2.0 for v in best.values())
        print(f"[quant] ≥2× weight+cache reduction on all four families: "
              f"{'YES' if ok else 'NO'} "
              f"({', '.join(f'{k} {v:.2f}×' for k, v in best.items())})")
    return rows


# -- self-speculative decoding report ----------------------------------------


def _decay_ranks(tree, g):
    """Geometric per-rank energy decay on every rank-bearing linear.

    Random-init factors have *flat* rank spectra (iid entries), so a
    truncated draft predicts almost nothing — trained BLAST/low-rank
    factors instead concentrate energy in the leading ranks (that is why
    rank truncation works at all).  The benchmark emulates a trained
    spectrum by scaling rank ρ by ``g**ρ``; both the plain and the
    speculative engine serve the *same* decayed model, so the comparison
    stays apples-to-apples.
    """
    if isinstance(tree, dict):
        kind = structures.rank_kind(tree)
        if kind is not None:
            key = "S" if kind == "blast" else "w_down"
            arr = tree[key]
            scale = g ** jnp.arange(arr.shape[-1], dtype=jnp.float32)
            return {**tree, key: arr * scale.astype(arr.dtype)}
        return {k: _decay_ranks(v, g) for k, v in tree.items()}
    return tree


# Per-family draft-rank fraction: MoE routing (mla) flips its top-k expert
# choice under heavier truncation, so its draft has to stay closer to the
# full model to keep the greedy agreement (and thus acceptance) up.
_SPEC_FRAC = {"gqa": 0.5, "mla": 0.7, "ssd": 0.4, "rglru": 0.25}


def speculative_report(quiet=False, k=7, frac=None, decay=0.5,
                       n_requests=4, slots=2, max_new=32):
    """End-to-end decode tok/s and acceptance rate, speculative vs plain.

    Decode-heavy workload (short prompts, long completions) per family.
    Reports the draft acceptance rate, tokens emitted per round, and the
    decode-throughput ratio against the same engine with speculation off.
    ``k=7`` keeps the verify chunk on the power-of-two bucket (k+1 = 8).

    The deepseek (mla) config gets its MoE ``capacity_factor`` raised so
    expert capacity never binds: capacity-based token dropping depends on
    the *batch shape* (the verify chunk packs k+1 columns per row where
    plain decode packs 1), so exact greedy equivalence — and a meaningful
    acceptance rate — requires the dropless regime.
    """
    rows = []
    for family, arch in FAMILIES.items():
        cfg = configs.ARCHS[arch].reduced()
        if cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        model = build_model(cfg)
        params = _decay_ranks(model.init(jax.random.PRNGKey(0)), decay)
        fam_frac = _SPEC_FRAC[family] if frac is None else frac
        key = jax.random.PRNGKey(2)

        def mk_reqs():
            reqs = []
            for i in range(n_requests):
                plen = 4 + (i * 3) % 5
                toks = jax.random.randint(jax.random.fold_in(key, i),
                                          (plen,), 0, cfg.vocab)
                reqs.append(Request(uid=i, prompt=[int(t) for t in toks],
                                    max_new_tokens=max_new))
            return reqs

        def serve(spec_k):
            eng = Engine(model, params, EngineConfig(
                scheduler=SchedulerConfig(slots=slots),
                memory=MemoryConfig(max_len=128),
                speculative=SpeculativeConfig(k=spec_k,
                                              draft_rank_frac=fam_frac)))
            for r in mk_reqs():
                eng.submit(r)
            eng.run()           # warm (compile) …
            for key_ in eng.stats:  # … drop compile time from the record
                eng.stats[key_] = 0
            for r in mk_reqs():
                eng.submit(r)
            done = eng.run()    # … then the timed workload on a hot engine
            assert len(done) == n_requests
            assert all(len(r.output) == max_new for r in done)
            return eng.throughput(), {r.uid: r.output for r in done}

        tp_plain, out_plain = serve(0)
        tp_spec, out_spec = serve(k)
        assert out_spec == out_plain, f"{family}: speculative != greedy"
        speedup = tp_spec["decode_tok_s"] / max(tp_plain["decode_tok_s"], 1e-9)
        rows.append({
            "family": family, "arch": arch, "k": k,
            "draft_rank_frac": fam_frac,
            "plain_decode_tok_s": tp_plain["decode_tok_s"],
            "spec_decode_tok_s": tp_spec["decode_tok_s"],
            "speedup": speedup,
            "acceptance_rate": tp_spec["acceptance_rate"],
            "tokens_per_round": tp_spec["tokens_per_round"],
        })
        if not quiet:
            print(f"[spec] {family:6s} ({arch}): k={k} f={fam_frac}: "
                  f"acceptance {tp_spec['acceptance_rate']:.2f}, "
                  f"{tp_spec['tokens_per_round']:.2f} tok/round, "
                  f"decode {tp_plain['decode_tok_s']:7.1f} → "
                  f"{tp_spec['decode_tok_s']:7.1f} tok/s "
                  f"({speedup:.2f}×)")
    if not quiet:
        best = max(rows, key=lambda r: r["speedup"])
        print(f"[spec] best end-to-end speedup: {best['family']} "
              f"{best['speedup']:.2f}× at acceptance "
              f"{best['acceptance_rate']:.2f}")
    return rows


# -- paged multi-tenant serving report ---------------------------------------


def make_trace(vocab, *, n_interactive=12, n_batch=4, shared_len=64,
               tail_len=4, interactive_new=6, batch_new=48, seed=3):
    """Mixed-tenant trace: a handful of long low-priority batch generations
    plus a stream of short interactive requests that all share one
    ``shared_len``-token system prompt.  Returns [(arrival_tick, factory)]
    — factories so FIFO and priority runs serve identical fresh requests.
    """
    key = jax.random.PRNGKey(seed)
    shared = [int(t) for t in
              jax.random.randint(key, (shared_len,), 0, vocab)]
    trace = []

    def req(uid, prompt, max_new, priority, prefix_len=None):
        return lambda: Request(uid=uid, prompt=list(prompt),
                               max_new_tokens=max_new, priority=priority,
                               prefix_len=prefix_len)

    for i in range(n_batch):
        toks = jax.random.randint(jax.random.fold_in(key, 100 + i),
                                  (8,), 0, vocab)
        trace.append((0, req(i, [int(t) for t in toks], batch_new, 1)))
    for i in range(n_interactive):
        tail = jax.random.randint(jax.random.fold_in(key, 200 + i),
                                  (tail_len,), 0, vocab)
        # staggered arrivals: the first interactive request computes and
        # registers the shared prefix, later ones hit it
        trace.append((4 + 3 * i,
                      req(100 + i, shared + [int(t) for t in tail],
                          interactive_new, 0, prefix_len=shared_len)))
    return trace


def _run_trace(model, params, trace, *, policy, pages, slots=4, max_len=128,
               page_size=16, chunk=16):
    eng = Engine(model, params, EngineConfig(
        scheduler=SchedulerConfig(slots=slots, chunk_size=chunk,
                                  policy=policy),
        memory=MemoryConfig(max_len=max_len, paged=True, page_size=page_size,
                            pages=pages)))
    peak = 0
    for timed in (False, True):   # warm pass compiles every step variant …
        if timed:                 # … so the timed pass measures scheduling
            for k, v in eng.stats.items():
                eng.stats[k] = type(v)(0)
            eng.tracer = Tracer()
            eng.finished.clear()
        pending = sorted(trace, key=lambda e: e[0])
        i, tick = 0, 0
        while True:
            while i < len(pending) and pending[i][0] <= tick:
                eng.submit(pending[i][1]())
                i += 1
            more = eng.tick()
            if timed and eng._pc.has_paged:
                peak = max(peak, eng._pc.n_pages - 1 - eng._pc.pages.n_free)
            tick += 1
            if not more and i >= len(pending):
                break
    eng._pc.audit()
    return eng, peak


def paged_report(quiet=False, slots=4, max_len=128, page_size=16, pages=16):
    """Multi-tenant SLA report on the paged engine: FIFO vs priority
    scheduling over the same trace (shared-prefix interactive + long batch
    traffic), with the pool sized to force preemption.

    Reports TTFT/TPOT percentiles per priority class, preemption and
    prefix-hit rates, and the peak page residency against what slot-static
    allocation would have pinned (slots × max_len tokens).  Priority
    scheduling must improve interactive (class-0) TTFT over FIFO, and the
    shared system prompt must be stored once (prefix_hit_rate > 0).
    """
    cfg = configs.ARCHS["smollm-135m"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = make_trace(cfg.vocab, n_batch=8)
    out = {"slots": slots, "max_len": max_len, "page_size": page_size,
           "pages": pages, "slot_static_tokens": slots * max_len}
    for policy in ("fifo", "priority"):
        eng, peak = _run_trace(model, params, trace, policy=policy,
                               pages=pages, slots=slots, max_len=max_len,
                               page_size=page_size)
        sla = eng.sla_report()
        out[policy] = {
            "sla": sla,
            "peak_pages": peak,
            "peak_page_tokens": peak * page_size,
            "requests": len(eng.finished),
        }
        if not quiet:
            c0 = sla["classes"].get("0", {})
            print(f"[paged] {policy:8s}: interactive TTFT p50 "
                  f"{(c0.get('ttft_p50_s') or 0) * 1e3:7.1f} ms / p99 "
                  f"{(c0.get('ttft_p99_s') or 0) * 1e3:7.1f} ms, "
                  f"preemptions {sla['preemptions']}, prefix-hit "
                  f"{sla['prefix_hit_rate']:.2f}, peak pages {peak}/{pages}")
    fifo_ttft = out["fifo"]["sla"]["classes"]["0"]["ttft_p50_s"]
    prio_ttft = out["priority"]["sla"]["classes"]["0"]["ttft_p50_s"]
    out["interactive_ttft_speedup"] = fifo_ttft / max(prio_ttft, 1e-9)
    if not quiet:
        print(f"[paged] priority vs FIFO interactive TTFT p50: "
              f"{out['interactive_ttft_speedup']:.2f}× better; pool "
              f"{(pages - 1) * page_size} tokens vs slot-static "
              f"{slots * max_len}")
    return out


# -- chaos / resilience report ------------------------------------------------


def chaos_report(quiet=False, slots=2, max_len=96, n_requests=6, max_new=16,
                 fault_spec="nan@5:u1;raise@10:u2;slow@3:0.4;drop@2:u3",
                 watchdog_s=0.15):
    """Serving under deterministic fault injection (serve/faults.py).

    Runs the same request mix twice on the hardest engine configuration
    (paged pool + self-speculative decoding): once fault-free for the
    greedy reference, once with the fault plan armed and the watchdog on.
    The headline guarantee this report pins: every request the plan does
    NOT target completes with byte-identical greedy output — a NaN'd row,
    a raising step, a stalled dispatch and a mid-stream client disconnect
    each stay contained to their own request.

    Reported per fault: detection-to-completion recovery latency (fault
    fire time → the targeted request leaving the system, by completion or
    isolation).  Plus degradation-ladder counts, watchdog trips, and
    goodput under faults (full-completion tokens/s, chaos vs clean)."""
    from repro.serve import ResilienceConfig

    cfg = configs.ARCHS["smollm-135m"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(9)

    def mk_reqs():
        reqs = []
        for i in range(n_requests):
            plen = 4 + (i * 3) % 7
            toks = jax.random.randint(jax.random.fold_in(key, i), (plen,),
                                      0, cfg.vocab)
            reqs.append(Request(uid=i + 1, prompt=[int(t) for t in toks],
                                max_new_tokens=max_new))
        return reqs

    def mk_engine(spec=None, watchdog=None):
        return Engine(model, params, EngineConfig(
            scheduler=SchedulerConfig(slots=slots, chunk_size=8),
            memory=MemoryConfig(max_len=max_len, paged=True, page_size=8),
            speculative=SpeculativeConfig(k=3),
            resilience=ResilienceConfig(fault_spec=spec,
                                        watchdog_deadline_s=watchdog)))

    # fault-free reference pass
    eng0 = mk_engine()
    reqs0 = mk_reqs()
    for r in reqs0:
        eng0.submit(r)
    t0 = time.perf_counter()
    eng0.run()
    base_wall = time.perf_counter() - t0
    base = {r.uid: list(r.output) for r in reqs0}
    base_tokens = sum(len(o) for o in base.values())

    # chaos pass: engine-side faults fire from the plan's poll points; the
    # client-side drop_conn fault is simulated by cancelling the target
    # once it has streamed `events` tokens (exactly what the HTTP frontend
    # does when a disconnected client's next write fails)
    eng = mk_engine(fault_spec, watchdog_s)
    plan = eng.fault_plan
    drops = [f for f in plan.faults if f.kind == "drop_conn"]
    reqs = mk_reqs()
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    while eng.tick():
        for f in drops:
            if f.fired:
                continue
            req = next((r for r in reqs if r.uid == f.uid), None)
            if (req is not None and not req.done
                    and len(req.output) >= f.events):
                f.fired += 1
                plan.log.append({"kind": f.kind, "step": eng.stats["steps"],
                                 "uid": f.uid, "t": time.perf_counter(),
                                 "fault": f.describe()})
                eng.cancel(f.uid)
    wall = time.perf_counter() - t0
    eng.close()

    faulted = plan.faulted_uids()
    done_at = {r.uid: r.t_done for r in reqs}
    recoveries = []
    for e in plan.log:
        if e["uid"] is not None and done_at.get(e["uid"]) is not None:
            recoveries.append({"fault": e["fault"],
                               "uid": e["uid"],
                               "recovery_s": done_at[e["uid"]] - e["t"]})
    clean = [r for r in reqs if r.uid not in faulted]
    identical = all(list(r.output) == base[r.uid] for r in clean)
    assert identical, (
        "chaos broke a non-faulted request: "
        f"{ {r.uid: (r.output, base[r.uid]) for r in clean} }")
    good_tokens = sum(len(r.output) for r in reqs
                      if r.stop_reason == "length")
    res = eng.resilience_report()
    out = {
        "fault_spec": fault_spec,
        "watchdog_deadline_s": watchdog_s,
        "requests": n_requests,
        "faulted_uids": sorted(faulted),
        "non_faulted_token_identical": identical,
        "outcomes": {str(r.uid): {"stop_reason": r.stop_reason,
                                  "degrade_path": list(r.degrade_path),
                                  "tokens": len(r.output)}
                     for r in reqs},
        "recovery": recoveries,
        "recovery_p50_s": (float(np.percentile(
            [r["recovery_s"] for r in recoveries], 50))
            if recoveries else None),
        "faults_fired": res["faults"]["fired_by_kind"],
        "numeric_trips": res["numeric_trips"],
        "degrade_spec_off": res["degrade_spec_off"],
        "degrade_act_float": res["degrade_act_float"],
        "step_errors": res["step_errors"],
        "requeues": res["requeues"],
        "watchdog_trips": res["health"]["watchdog_trips"],
        "goodput_tok_s": good_tokens / wall,
        "clean_tok_s": base_tokens / base_wall,
        "goodput_ratio": (good_tokens / wall) / (base_tokens / base_wall),
    }
    if not quiet:
        print(f"[chaos] plan {fault_spec!r}: "
              f"{res['faults']['fired']} faults fired "
              f"({out['faults_fired']}), non-faulted token-identical: "
              f"{'YES' if identical else 'NO'}")
        print(f"[chaos] ladder: {out['numeric_trips']} trips "
              f"(spec_off {out['degrade_spec_off']}, act_float "
              f"{out['degrade_act_float']}), {out['step_errors']} step "
              f"errors, {out['requeues']} requeues, "
              f"{out['watchdog_trips']} watchdog trips")
        for r in recoveries:
            print(f"[chaos] recovery {r['fault']}: {r['recovery_s']*1e3:.0f} "
                  f"ms to contain uid {r['uid']}")
        print(f"[chaos] goodput under faults {out['goodput_tok_s']:.1f} "
              f"tok/s vs clean {out['clean_tok_s']:.1f} tok/s "
              f"({out['goodput_ratio']:.2f}×)")
    return out


# -- decode-step kernel-launch accounting ------------------------------------


def kernel_report(quiet=False, batch=2, max_len=32,
                  storages=("float", "int8", "int4")):
    """Structured-matmul launches per decode step, grouped vs per-projection,
    per weight-storage mode.

    Builds each family's reduced arch *unrolled* (scan_layers=False, so the
    eager dispatch count equals the runtime launch count — a scanned model
    traces its cycle body once) and executes one C=1 decode step through
    ``prefill_chunk`` with the grouped fast path on and off.  Every
    ``linear_apply`` / ``group_apply`` dispatch is one kernel launch on the
    Pallas path; grouping must never increase the count, and strictly
    decreases it for every family with a same-input bundle (GQA gate+up,
    MLA a-projections, RG-LRU input/gate pairs).

    The report is per storage mode because launch counts *are* per storage
    mode: before the grouped-q4 kernel, all-int4 bundles fell back to one
    launch per member, so int4 serving paid the full per-projection count.
    Now every storage mode must land on the same grouped count.
    """
    rows = []
    for family, arch in FAMILIES.items():
        cfg = configs.ARCHS[arch].reduced(scan_layers=False)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.ones((batch, 1), jnp.int32)
        steps = jnp.zeros((batch,), jnp.int32)
        n_tok = jnp.ones((batch,), jnp.int32)
        for storage in storages:
            if storage == "float":
                model_s, params_s = model, params
            else:
                qcfg = QuantConfig(weights=storage)
                cfg_q = dataclasses.replace(cfg, quant=qcfg)
                model_s = build_model(cfg_q)
                params_s = model_s.quantize_params(params, qcfg)
            cache = model_s.init_cache(batch, max_len)

            def count(enabled):
                with structures.grouping(enabled):
                    structures.reset_dispatch_count()
                    model_s.prefill_chunk(params_s, cache, tokens, steps,
                                          n_tok)
                    return structures.dispatch_count()

            grouped, loop = count(True), count(False)
            rows.append({"family": family, "arch": arch,
                         "layers": cfg.n_layers, "storage": storage,
                         "launches_grouped": grouped, "launches_loop": loop})
            if not quiet:
                mark = "<" if grouped < loop else "="
                print(f"[kernels] {family:6s} ({arch}) {storage:5s}: "
                      f"{grouped:3d} launches per decode step grouped "
                      f"{mark} {loop:3d} per-projection "
                      f"({cfg.n_layers} layers)")
    by_family: dict = {}
    for r in rows:
        by_family.setdefault(r["family"], {})[r["storage"]] = r
    for family, per in by_family.items():
        counts = {s: r["launches_grouped"] for s, r in per.items()}
        assert len(set(counts.values())) == 1, (
            f"{family}: grouped launch count differs across storage modes "
            f"{counts} — a quantized bundle fell off the grouped path")
    if not quiet:
        bundled = [r for r in rows if r["family"] in ("gqa", "mla", "rglru")]
        ok = all(r["launches_grouped"] < r["launches_loop"] for r in bundled)
        assert all(r["launches_grouped"] <= r["launches_loop"] for r in rows)
        print(f"[kernels] grouped launches strictly fewer on all bundled "
              f"families (every storage mode): {'YES' if ok else 'NO'}")
    return rows


# -- multi-chip mesh sweep (tensor-parallel serving) --------------------------


def _mesh_child(meshes=((1, 1), (1, 8)), max_new=12, n_requests=4):
    """Run inside the 8-fake-device subprocess: serve the same request mix
    on each mesh shape with the SAME engine code, assert token-identical
    greedy outputs, and print the sweep record as the last stdout line."""
    import json
    import sys

    from repro.launch.mesh import make_parallel, make_serving_mesh
    from repro.parallel import NO_PARALLEL
    from repro.roofline.analysis import collective_bytes

    cfg = configs.ARCHS["smollm-135m"].reduced(scan_layers=False)
    rec = {"family": "gqa", "arch": "smollm-135m",
           "devices_visible": len(jax.devices()), "meshes": []}
    outputs = {}
    for dp, tp in meshes:
        if dp * tp > len(jax.devices()):
            continue
        par = (NO_PARALLEL if (dp, tp) == (1, 1)
               else make_parallel(make_serving_mesh(dp, tp), serve=True))
        model = build_model(cfg, par)
        params = model.init(jax.random.PRNGKey(0))
        eng = Engine(model, params, EngineConfig(
            scheduler=SchedulerConfig(slots=4, chunk_size=8),
            memory=MemoryConfig(max_len=64), mesh=f"{dp},{tp}"))
        # per-shard grouped launches per decode step: under GSPMD/shard_map
        # every device executes the same partitioned program, so the
        # trace-time dispatch count IS the per-shard launch count
        tokens = jnp.ones((4, 1), jnp.int32)
        steps = jnp.zeros((4,), jnp.int32)
        n_tok = jnp.ones((4,), jnp.int32)
        with structures.grouping(True):
            structures.reset_dispatch_count()
            model.prefill_chunk(eng.params, eng.cache, tokens, steps, n_tok)
            launches = structures.dispatch_count()
        compiled = jax.jit(model.prefill_chunk).lower(
            eng.params, eng.cache, tokens, steps, n_tok).compile()
        coll, breakdown = collective_bytes(compiled.as_text())
        prompts = [r.prompt for r in
                   _mk_requests(n_requests, cfg.vocab, jax.random.PRNGKey(5),
                                prompt_len=16)]
        t0 = time.perf_counter()
        done = eng.generate_batch(prompts,
                                  SamplingParams(max_new_tokens=max_new))
        wall = time.perf_counter() - t0
        outputs[(dp, tp)] = {r.uid: list(r.output) for r in done}
        tp_stats = eng.throughput()
        total = sum(len(r.output) for r in done)
        srep = eng.sharding_report or {}
        rec["meshes"].append({
            "mesh": f"{dp}x{tp}", "dp": dp, "tp": tp, "devices": dp * tp,
            "tok_s": total / wall,
            "prefill_tok_s": tp_stats["prefill_tok_s"],
            "decode_tok_s": tp_stats["decode_tok_s"],
            "launches_per_decode_step_per_shard": launches,
            "collective_bytes_per_decode_step": coll,
            "collective_breakdown": breakdown,
            "replicated_param_bytes": srep.get("replicated_bytes", 0),
            "replicated_param_leaves": srep.get("replicated_leaves", 0),
            "param_bytes": srep.get("total_bytes", 0),
        })
    vals = list(outputs.values())
    rec["tokens_identical"] = all(v == vals[0] for v in vals[1:])
    assert rec["tokens_identical"], (
        "greedy outputs diverged across mesh shapes: "
        f"{ {k: v for k, v in outputs.items()} }")
    counts = {m["mesh"]: m["launches_per_decode_step_per_shard"]
              for m in rec["meshes"]}
    assert len(set(counts.values())) == 1, (
        f"per-shard launch count varies with mesh shape: {counts} — a "
        "bundle fell off the grouped path under sharding")
    print("MESH_SWEEP_JSON=" + json.dumps(rec))
    sys.stdout.flush()


def mesh_report(quiet=False, timeout=1800):
    """1-device vs 8-device (simulated) mesh sweep of the serving engine.

    Spawns a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` (fake-device count must be set before jax initializes, so the
    parent process cannot run this in-line) and collects, per mesh shape:
    tok/s, per-shard grouped launches per decode step, per-device collective
    bytes per decode step (from the partitioned HLO), and the
    replicated-parameter bytes left by indivisible dims.  The child asserts
    greedy outputs are token-identical across mesh shapes — one engine from
    1 to 8 devices.
    """
    import json
    import os
    import subprocess
    import sys

    here = os.path.abspath(__file__)
    root = os.path.dirname(os.path.dirname(here))
    env = dict(os.environ)
    # a simulated 8-device CPU mesh: the child never touches a chip the
    # parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, here, "--mesh-child"],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh sweep child failed (rc={proc.returncode}):\n"
            + proc.stdout[-2000:] + "\n" + proc.stderr[-4000:])
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("MESH_SWEEP_JSON=")][-1]
    rec = json.loads(line[len("MESH_SWEEP_JSON="):])
    if not quiet:
        for m in rec["meshes"]:
            print(f"[mesh] {m['mesh']:4s} ({m['devices']} devices): "
                  f"{m['tok_s']:7.1f} tok/s, "
                  f"{m['launches_per_decode_step_per_shard']:3d} launches"
                  f"/decode-step/shard, collective "
                  f"{m['collective_bytes_per_decode_step'] / 1e3:8.1f} KB"
                  f"/step, replicated params "
                  f"{m['replicated_param_bytes'] / 1e6:6.2f} MB")
        print(f"[mesh] greedy outputs token-identical across mesh shapes: "
              f"{'YES' if rec['tokens_identical'] else 'NO'}")
    return rec


# -- integer-vs-float per-call kernel timings ---------------------------------


def kernel_timing_report(quiet=False,
                         shapes=((1, 256, 256, 16, 32),
                                 (8, 256, 256, 16, 32),
                                 (128, 256, 256, 16, 32)),
                         reps=5):
    """Per-call wall time of one BLAST matmul across compute modes.

    Times the same (T, m, n, b, r) call in five modes — float, W8 (int8
    weights, float activations), W8A8, W4 and W4A8 — at decode shapes
    (T=1, T=8) and a chunked-prefill shape (T=128).  The integer-activation
    rows include the per-token quantize-act prologue inside the timed
    region, so `vs_float` is the honest end-to-end ratio a serving layer
    sees, not the bare contraction.  Uses the best-of-``reps`` protocol
    from kernels/autotune.py (compile + warm outside the timed region).
    """
    from repro.kernels import autotune as at
    from repro.kernels import ops

    backend = jax.default_backend()
    rows = []
    for (T, m, n, b, r) in shapes:
        key = jax.random.PRNGKey(7)
        kx, ku, ks, kv = jax.random.split(key, 4)
        p, q = m // b, n // b
        x = jax.random.normal(kx, (T, n), jnp.float32)
        U = jax.random.normal(ku, (b, p, r), jnp.float32)
        S = jax.random.normal(ks, (b, b, r), jnp.float32)
        V = jax.random.normal(kv, (b, q, r), jnp.float32)
        quantized = {}
        for bits, kind in ((8, "int8"), (4, "int4")):
            quantized[kind] = (qt.quantize(U, bits=bits, block_axes=(1, 2)),
                               qt.quantize(S, bits=bits, block_axes=(2,)),
                               qt.quantize(V, bits=bits, block_axes=(1, 2)))
        modes = [("float", lambda: ops.blast_matmul(x, U, S, V))]
        for kind, label_w, label_a in (("int8", "w8", "w8a8"),
                                       ("int4", "w4", "w4a8")):
            Uq, Sq, Vq = quantized[kind]
            modes.append((label_w,
                          lambda Uq=Uq, Sq=Sq, Vq=Vq:
                          ops.blast_matmul_q(x, Uq, Sq, Vq)))
            modes.append((label_a,
                          lambda Uq=Uq, Sq=Sq, Vq=Vq:
                          ops.blast_matmul_q(x, Uq, Sq, Vq, act="int8")))
        base_t = None
        for mode, fn in modes:
            dt = at._time_call(fn, reps=reps)
            if mode == "float":
                base_t = dt
            rows.append({"T": T, "m": m, "n": n, "b": b, "r": r,
                         "mode": mode, "backend": backend,
                         "time_s": dt, "vs_float": base_t / dt})
            if not quiet:
                print(f"[ktime] T={T:3d} m={m} n={n} b={b} r={r} "
                      f"{mode:5s}: {dt * 1e6:9.1f} µs "
                      f"({base_t / dt:5.2f}× vs float)")
    return rows


if __name__ == "__main__":
    import sys
    if "--mesh-child" in sys.argv:
        _mesh_child()
    else:
        run()
        quant_report()
        kernel_report()
        kernel_timing_report()
        speculative_report()
        mesh_report()
        paged_report()
        chaos_report()
