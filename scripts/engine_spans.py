"""Where the host spends a tick: one traced benchmark run with the serving
engine's own spans (``engine.*``, ``src/repro/serve/tracing.py``) kept in
the profiler trace beside the benchmark's.

    python scripts/engine_spans.py --workload <config>.<mix> --seed N \
        --seconds S [--out chiprun_out/engine_spans.json]

Runs the cell as ``bench/run.py --trace 1`` does (on the chip: without one
it exits 3, unless ``--reduced`` rehearses it on the CPU at the model's
``.reduced()`` sizes), then reports:

  * ``idle_by_span``: the device's idle seconds in the traced window, named
    as the benchmark names them (innermost of its own spans) and, within
    each, by the innermost ``engine.*`` span covering the gap's middle;
  * ``decode_tick_ms``: medians over the window's decode-only ticks of the
    engine's ``engine.tick``, ``engine.wait`` and each phase, from the
    engine's tick ring, beside the benchmark's own per-layer metrics;
  * ``span_cost_us``: microseconds a tick of the tracer alone costs (a tick
    span, seven phase spans, one ring record), profiler off and on.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402
from bench import trace as trace_reduce  # noqa: E402
from repro.serve import tracing  # noqa: E402

ENGINE_SPANS = {"engine." + p for p in ("tick",) + tracing.PHASES}


def idle_by_span(events: dict) -> dict:
    """Idle seconds of the window by (benchmark span, engine span)."""
    host = events["host"]
    _, w0, wd, _ = next(h for h in host if h[0] == "window")
    w1 = w0 + wd
    inside = [h for h in host if h[0] != "window" and h[1] < w1
              and h[1] + h[2] > w0]
    bench = trace_reduce._Spans([h for h in inside
                                 if h[0] not in ENGINE_SPANS])
    engine = trace_reduce._Spans([h for h in inside if h[0] in ENGINE_SPANS])
    out = defaultdict(lambda: defaultdict(float))
    n = max(1, len(events["devices"]))
    for dev in events["devices"].values():
        ops = dev["ops"] or dev["modules"]
        merged = trace_reduce.union(((s, s + d) for _, s, d in ops), w0, w1)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                out[bench.innermost(mid)][engine.innermost(mid)] += \
                    (e - s) * 1e-9 / n
    return {b: dict(sorted(d.items(), key=lambda kv: -kv[1]))
            for b, d in out.items()}


def decode_ticks(run) -> dict:
    r = run.rec.engine.tracer.records(run.win.open, run.win.close)
    r = r[(r["prompt"] == 0) & (r["decode"] > 0)]
    med = lambda a: 1e3 * statistics.median(a.tolist())  # noqa: E731
    out = {"n": int(len(r)), "tick": med(r["end"] - r["start"])}
    out.update({p: med(r[p]) for p in tracing.PHASES})
    return out


def span_cost_us(ticks: int = 10_000) -> dict:
    """The tracer alone, ``ticks`` times, with no work inside the spans."""
    import tempfile

    import jax

    class Slot:
        req = object()

    slots = [Slot() for _ in range(16)]

    def loop() -> float:
        tr = tracing.Tracer()
        t = time.perf_counter()
        for i in range(ticks):
            with tr.tick(i, slots):
                with tr.span("admit"):
                    pass
                tr.queued(0)
                tr.step(16, 1, 0, 16)
                for p in tracing.PHASES[1:]:
                    with tr.span(p):
                        pass
        return (time.perf_counter() - t) / ticks * 1e6

    off = loop()
    jax.profiler.start_trace(tempfile.mkdtemp(prefix="span-cost-"))
    on = loop()
    jax.profiler.stop_trace()
    return {"profiler_off": off, "profiler_on": on}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at the model's .reduced() sizes")
    args = ap.parse_args(argv)

    trace_reduce.HOST_SPANS |= ENGINE_SPANS
    seen = {}
    reduce, per_layer = trace_reduce.reduce, R.per_layer

    def keep_events(events, decode):
        seen["idle_by_span"] = idle_by_span(events)
        return reduce(events, decode)

    def also_ticks(run):
        seen["decode_tick_ms"] = decode_ticks(run)
        return per_layer(run)

    trace_reduce.reduce, R.per_layer = keep_events, also_ticks
    cell = R.load_cell(args.workload)
    try:
        result = R.run_cell(cell, args.seed, args.seconds, True,
                            reduced=args.reduced)
    except R.NoChip as e:
        return int(e.code)
    seen["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    seen["correct"] = result["correct"]
    seen["span_cost_us"] = span_cost_us()
    line = json.dumps(seen)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
