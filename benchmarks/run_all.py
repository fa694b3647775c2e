"""One-shot benchmark entry point with machine-readable output.

    PYTHONPATH=src python -m benchmarks.run_all [--fast] [--full] \
        [--out BENCH_kernels.json]

Runs the kernel/serving performance suite and emits ``BENCH_kernels.json``
— the per-PR perf-trajectory record:

  * ``serving``   chunk-size sweep: prefill/decode tok/s, weight+cache MB,
                  per-step latency percentiles (p50/p90/p99)
  * ``launches``  structured-matmul launches per decode step per family and
                  weight-storage mode (float/int8/int4), grouped bundles vs
                  the per-projection loop
  * ``quant``     weight+cache HBM reduction + logit deviation per family,
                  including the W4A8 integer-activation row
  * ``timings``   per-call BLAST matmul wall time across compute modes
                  (float / W8 / W8A8 / W4 / W4A8) at decode + chunk shapes
  * ``autotune``  measured-vs-heuristic tiling choices for decode-shaped
                  BLAST calls (written through a throwaway cache)

It also emits ``BENCH_serving.json`` — the serving-side record: chunk-sweep
tok/s, self-speculative decoding acceptance rate + decode speedup vs plain
per family, structured-matmul launches per decode step, the paged-pool
multi-tenant trace (TTFT/TPOT percentiles per priority class, preemption +
prefix-hit rates, priority-vs-FIFO interactive TTFT), and the chaos report
(deterministic fault injection with recovery latency and goodput under
faults).

``--full`` additionally runs the paper-table suite (``benchmarks.run``).
The JSON schema is versioned; downstream tooling should ignore unknown
keys so fields can be added per PR without breaking the trajectory.
"""

from __future__ import annotations

import argparse
import json
import time


def _jsonable(obj):
    """Recursively coerce numpy/jax scalars so the record always dumps."""
    import numpy as np

    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if hasattr(obj, "item") and getattr(obj, "ndim", None) == 0:
        return obj.item()
    return obj


def autotune_report(quiet: bool = False, cache_path: str | None = None):
    """Tune a few decode/prefill-shaped BLAST calls and report the measured
    winners next to the VMEM-heuristic picks."""
    import tempfile

    import jax

    from repro.kernels import autotune, ops

    path = cache_path or tempfile.mktemp(suffix="_blast_tiling.json")
    autotune.enable(path)
    shapes = [
        # (T, m, n, b, r, kind, act): decode matvec, small decode batch,
        # prefill chunk — plus the W8A8/W4A8 integer-activation twins of the
        # decode-batch shape, which key separately in the version-2 cache
        (1, 256, 256, 16, 32, "float", "none"),
        (8, 256, 256, 16, 32, "float", "none"),
        (128, 256, 256, 16, 32, "float", "none"),
        (8, 512, 128, 8, 48, "float", "none"),
        (8, 256, 256, 16, 32, "int8", "int8"),
        (8, 256, 256, 16, 32, "int4", "int8"),
    ]
    rows = []
    for T, m, n, b, r, kind, act in shapes:
        fb = {"float": 4, "int8": 1, "int4": 0.5}[kind]
        heur = ops.pick_blast_blocks(T, m, n, b, r, 4, fb)
        tuned = autotune.tune_blast(T, m, n, b, r, kind=kind, act=act, reps=2)
        rows.append({"T": T, "m": m, "n": n, "b": b, "r": r,
                     "kind": kind, "act": act,
                     "heuristic": list(heur), "tuned": list(tuned),
                     "backend": jax.default_backend()})
        if not quiet:
            print(f"[autotune] T={T:4d} m={m} n={n} b={b:2d} r={r} "
                  f"{kind}/a{act}: heuristic {heur} → tuned {tuned}")
    autotune.save()
    autotune.disable()
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="CI-sized run")
    ap.add_argument("--full", action="store_true",
                    help="also run the paper-table suite (benchmarks.run)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--out-serving", default="BENCH_serving.json",
                    help="serving-focused record: tok/s, speculative "
                         "acceptance rate, launches per decode step")
    ap.add_argument("--autotune-cache", default=None,
                    help="persist the autotune section's cache here")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import serving_throughput

    t0 = time.time()
    print("===== serving (chunk sweep + latency percentiles) =====")
    serving = serving_throughput.run(
        n_requests=4 if args.fast else 8,
        chunks=(1, 8) if args.fast else (1, 8, 32))
    print("===== kernel launches per decode step =====")
    launches = serving_throughput.kernel_report(
        storages=("float", "int4") if args.fast
        else ("float", "int8", "int4"))
    print("===== quantized serving memory =====")
    quant = serving_throughput.quant_report(
        modes=(("int8", "int8", "none"), ("int4", "int8", "int8"))
        if args.fast
        else (("int8", "int8", "none"), ("int4", "int8", "none"),
              ("int4", "int8", "int8")))
    print("===== integer vs float kernel timings =====")
    timings = serving_throughput.kernel_timing_report(
        reps=2 if args.fast else 5)
    print("===== self-speculative decoding (draft-verify) =====")
    speculative = serving_throughput.speculative_report(
        n_requests=2 if args.fast else 4,
        max_new=16 if args.fast else 32)
    print("===== autotune (measured vs heuristic tiling) =====")
    autotune = autotune_report(cache_path=args.autotune_cache)
    print("===== mesh sweep (1 vs 8 simulated devices) =====")
    mesh = serving_throughput.mesh_report()
    print("===== paged serving (prefix sharing + preemption SLA) =====")
    paged = serving_throughput.paged_report()
    print("===== chaos (fault injection + graceful degradation) =====")
    chaos = serving_throughput.chaos_report(
        n_requests=4 if args.fast else 6,
        max_new=12 if args.fast else 16)

    import jax
    record = {
        "version": 1,
        "generated_unix": time.time(),
        "wall_s": time.time() - t0,
        "backend": jax.default_backend(),
        "serving": serving,
        "launches": launches,
        "quant": quant,
        "timings": timings,
        "autotune": autotune,
        # per-shard launch counts + collective bytes per mesh shape
        "mesh": mesh,
    }
    with open(args.out, "w") as f:
        json.dump(_jsonable(record), f, indent=2)
    print(f"[run_all] wrote {args.out} ({time.time() - t0:.0f}s)")

    serving_record = {
        "version": 1,
        "generated_unix": time.time(),
        "backend": jax.default_backend(),
        # chunk-sweep tok/s, draft-verify acceptance + speedup, and
        # structured-matmul launches per decode step
        "serving": serving,
        "speculative": speculative,
        "launches": launches,
        # paged pool under a multi-tenant trace: TTFT/TPOT percentiles per
        # priority class, preemption + prefix-hit rates, FIFO contrast
        "paged": paged,
        # 1-device vs 8-device (simulated) mesh: tok/s per mesh shape,
        # per-shard launches per decode step, collective + replicated bytes
        "mesh": mesh,
        # fault injection on the paged+speculative engine: faults fired per
        # kind, degradation-ladder counts, per-fault recovery latency, and
        # goodput under faults vs the fault-free run (non-faulted requests
        # asserted token-identical)
        "chaos": chaos,
    }
    with open(args.out_serving, "w") as f:
        json.dump(_jsonable(serving_record), f, indent=2)
    print(f"[run_all] wrote {args.out_serving}")

    if args.full:
        import sys

        from benchmarks import run as paper_run
        sys.argv = ["benchmarks.run"] + (["--fast"] if args.fast else [])
        paper_run.main()


if __name__ == "__main__":
    main()
